"""Block-wise online-softmax attention (FlashAttention) for TPU.

TPU adaptation notes (vs the CUDA original):

* Tiles are MXU-aligned: ``block_q x d`` and ``block_k x d`` with
  d padded to a lane multiple (128).  The QK^T and PV matmuls both hit
  the 128x128 systolic array; the running max / denominator live in a
  float32 VMEM scratch accumulator (8x128-aligned), not registers.
* The KV loop is the innermost *grid* dimension — TPU grids execute
  sequentially per core, so VMEM scratch carries the online-softmax
  state between KV steps (the Pallas idiom replacing CUDA's intra-block
  loop + shared memory).
* Causal masking uses absolute positions with the decode convention
  (query i at position Sk - Sq + i).  Fully-masked KV blocks are
  computed-and-masked; the ops layer shrinks the grid instead when the
  shape allows it (hillclimb: see EXPERIMENTS.md §Perf).

Grid: ``(batch*heads, num_q_blocks, num_kv_blocks)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, sq: int, sk: int,
                  block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # mask out-of-range keys (sequence padding) and the causal triangle
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < sk
    if causal:
        qpos = (qi * block_q + (sk - sq)
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        mask &= kpos <= qpos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: exp(-inf - -inf) -> exp(0) must not fire
    safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(jnp.where(mask, s - safe_m, NEG_INF))
    alpha = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - safe_m))

    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)            # (bk, d)
    acc_ref[...] = (alpha * acc_ref[...]
                    + jax.lax.dot_general(
                        p, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, :, :] = (acc_ref[...]
                          / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k",
                              "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = False,
                         scale: float | None = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """Flash attention over flattened heads.

    q: (BH, Sq, D); k, v: (BH, Sk, D), all pre-padded so that
    Sq % block_q == Sk % block_k == 0 is NOT required — padding is
    handled here.  Returns (BH, Sq, D).
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    sq_p = -(-sq // block_q) * block_q
    sk_p = -(-sk // block_k) * block_k
    d_p = max(-(-d // 128) * 128, 128)
    pad3 = lambda x, s, dd: jnp.pad(
        x, ((0, 0), (0, s - x.shape[1]), (0, dd - x.shape[2])))
    qp, kp, vp = pad3(q, sq_p, d_p), pad3(k, sk_p, d_p), pad3(v, sk_p, d_p)

    grid = (bh, sq_p // block_q, sk_p // block_k)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          sq=sq, sk=sk, block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_p), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_p), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_p), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom
            pltpu.VMEM((block_q, d_p), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :sq, :d]
