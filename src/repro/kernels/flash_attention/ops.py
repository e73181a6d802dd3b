"""Public flash-attention op: GQA folding + head flattening."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k",
                              "interpret"))
def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """GQA flash attention.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D), Hq % Hkv == 0.
    Returns (B, Hq, Sq, D) in q.dtype.
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    flat = lambda x: x.reshape(b * hq, x.shape[2], d)
    o = flash_attention_bhsd(flat(q), flat(k), flat(v), causal=causal,
                             scale=scale, block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return o.reshape(b, hq, sq, d)
