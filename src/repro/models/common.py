"""Shared model machinery: config, norms, RoPE, GQA attention, FFN.

Conventions
-----------
* Params are nested dicts of jnp arrays; per-layer groups are *stacked*
  along a leading ``L`` axis and consumed by ``jax.lax.scan`` (compact
  HLO — essential for 80-layer archs lowered on 512 host devices).
* Every model provides a parallel *spec tree*: same structure as the
  params, leaves = tuples of logical axis names (see `parallel.axes`).
* Compute dtype is ``cfg.dtype`` (bf16 by default); params and softmax
  accumulate in fp32.
* Attention has two interchangeable implementations: the pure-jnp
  query-chunked online-softmax path (used for lowering/training — XLA
  TPU fuses it well and it lowers on any backend) and the Pallas
  flash-attention kernel (``repro.kernels.flash_attention``; TPU
  execution path, validated in interpret mode).  ``cfg.use_flash_kernel``
  selects.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel.axes import _mesh, resolve, serving_mode, shard


def serving_matmul(x, w, eq: str, w_logical: tuple):
    """Weight-stationary projection for serving (§Perf iteration 3).

    ``x @ w`` where w's contraction dim(s) may be sharded (serve rules
    put 'embed'/'mlp' on the data axis).  XLA's SPMD heuristic resolves
    that by ALL-GATHERING the weights every step — at decode that is
    the whole model per step.  This helper pins the weight-stationary
    schedule with shard_map: x is replicated in (decode activations
    are tiny), each device contracts against its resident weight
    shard, and partial products are psum'd over the contraction axes.
    Falls back to a plain einsum outside serving mode.
    """
    if not serving_mode() or _mesh() is None:
        return jnp.einsum(eq, x, w)
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = _mesh()
    w_spec = resolve(w_logical, w.shape)
    ins, out = eq.split("->")
    x_dims, w_dims = ins.split(",")
    flat = lambda a: (() if a is None
                      else (a,) if isinstance(a, str) else tuple(a))
    w_axes = {dim: (w_spec[i] if i < len(w_spec) else None)
              for i, dim in enumerate(w_dims)}
    # contraction = w dims absent from the output -> psum over their axes
    psum_axes = [ax for dim in w_dims if dim not in out
                 for ax in flat(w_axes[dim])]
    # x/out dims mirror w's sharding where labels are shared
    x_spec = P(*(w_axes.get(dim) for dim in x_dims))
    o_spec = P(*(w_axes.get(dim) for dim in out))

    def local(xl, wl):
        y = jnp.einsum(eq, xl, wl)
        return jax.lax.psum(y, tuple(psum_axes)) if psum_axes else y

    return shard_map(local, mesh=mesh, in_specs=(x_spec, P(*w_spec)),
                     out_specs=o_spec, check_rep=False)(x, w)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config type for every assigned architecture family."""

    name: str = "model"
    family: str = "dense"          # dense|moe|ssm|hybrid|vlm|audio
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab: int = 1024
    d_head: int = 0                # 0 -> d_model // n_heads
    qkv_bias: bool = False         # qwen2 uses QKV bias
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    use_flash_kernel: bool = False
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 2
    dense_residual: bool = False   # arctic: dense FFN in parallel w/ MoE
    capacity_factor: float = 1.25
    # --- SSM / hybrid ---
    ssm_state: int = 64            # Mamba2 state size N
    ssm_expand: int = 2            # d_inner = expand * d_model
    ssm_head_dim: int = 64         # Mamba2 head dim P
    ssm_chunk: int = 128           # SSD chunk length
    conv_kernel: int = 4
    attn_every: int = 6            # zamba: shared attn block period
    slstm_every: int = 8           # xlstm: sLSTM block period
    # --- cross-attention (vlm) / encoder-decoder (audio) ---
    cross_attn_every: int = 0      # vlm: cross-attn layer period
    n_encoder_layers: int = 0      # whisper encoder depth
    n_ctx_tokens: int = 1500       # stub frontend tokens (frames/patches)
    # --- attention flavor ---
    attn_logit_softcap: float = 0.0   # grok-1 uses 30.0
    max_seq: int = 8192            # rope table length for training

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim


# ---------------------------------------------------------------------------
# primitives


def cast_params(cfg: ModelConfig, tree):
    """Cast fp32 weights to the compute dtype BEFORE the layer scan.

    §Perf iteration 7: with the cast inside the layer body, the FSDP
    all-gather moves fp32 master weights and each device casts after —
    2x the collective bytes and 2x the HBM weight reads.  Hoisting the
    cast outside the scan ships bf16 (numerics identical: same cast,
    earlier).  fp32 master copies remain in the optimizer path.
    """
    return jax.tree_util.tree_map(
        lambda a: a.astype(cfg.dtype)
        if hasattr(a, "dtype") and a.dtype == jnp.float32 else a, tree)


def rmsnorm(x, w, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def rope_table(positions, head_dim: int, theta: float):
    """positions (...,) -> cos/sin tables (..., head_dim//2)."""
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., S, H, D); cos/sin (..., S, D//2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def heads_tp_available(n: int) -> bool:
    """True if `n` heads can shard the 'model' axis (divisibility).

    REPRO_NO_SP=1 disables the sequence-parallel fallback (§Perf A/B
    measurement knob).
    """
    import os
    if os.environ.get("REPRO_NO_SP"):
        return True
    spec = resolve(("heads",), (n,))
    return len(spec) > 0 and spec[0] is not None


def _probs_dtype():
    """bf16 unless REPRO_FP32_PROBS=1 (§Perf iteration-1 A/B knob)."""
    import os
    return jnp.float32 if os.environ.get("REPRO_FP32_PROBS") \
        else jnp.bfloat16


def _chunked_attention(q, k, v, *, causal: bool, chunk: int,
                       softcap: float = 0.0):
    """Query-chunked online attention, fp32 softmax, grouped GQA.

    q (B,S,Hq,D); k,v (B,T,Hkv,D), Hq % Hkv == 0.  The GQA group dim is
    contracted by einsum — the repeated-KV tensor is NEVER materialized
    (a `jnp.repeat` here costs Hq/Hkv x KV memory AND forces SPMD to
    reshard the expanded heads; see EXPERIMENTS.md §Perf).  Scans over
    query chunks so peak score memory is (B,Hkv,G,chunk,T).
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / (d ** 0.5)
    chunk = min(chunk, max(-(-s // 128) * 128, 128))   # no padding waste
    nq = -(-s // chunk)
    s_pad = nq * chunk
    qp = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    qc = (qp.reshape(b, nq, chunk, hkv, g, d)
          .transpose(1, 0, 2, 3, 4, 5))          # (nq,B,c,Hkv,G,D)
    # Sequence-parallel fallback (§Perf iteration 5): when the head
    # count cannot shard the 'model' axis (whisper: 20 heads on 16),
    # the score computation would be replicated 16x across it.  Shard
    # the query-chunk dim instead — each model shard owns a slice of
    # the rows, k/v are shared, and the heavy score tensors shrink by
    # the TP degree.
    seq_par = not heads_tp_available(hq)

    def body(_, args):
        i, qi = args
        if seq_par:
            qi = shard(qi, "batch", "seq", None, None, None)
        sc = jnp.einsum("bchgd,bthd->bchgt", qi.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
        if seq_par:
            sc = shard(sc, "batch", "seq", None, None, None)
        if softcap > 0.0:
            sc = softcap * jnp.tanh(sc / softcap)
        if causal:
            qpos = (i * chunk + jnp.arange(chunk)[:, None]
                    + (t - s))                    # (c,1)
            kpos = jnp.arange(t)[None, :]
            msk = (kpos <= qpos)[None, :, None, None, :]
            sc = jnp.where(msk, sc, -jnp.inf)
        m = jnp.max(sc, axis=-1, keepdims=True)
        p = jnp.exp(sc - jax.lax.stop_gradient(jnp.where(
            jnp.isfinite(m), m, 0.0)))
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        # §Perf iter 1: probabilities cross HBM in bf16 (the softmax
        # stats m/l stay fp32).  Score-sized tensors dominate the
        # memory roofline term; this halves their traffic.  The PV
        # matmul accumulates in fp32 (preferred_element_type).
        o = jnp.einsum("bchgt,bthd->bchgd", p.astype(_probs_dtype()),
                       v.astype(_probs_dtype()),
                       preferred_element_type=jnp.float32)
        o = o / l
        return None, o.astype(q.dtype)

    _, oc = jax.lax.scan(body, None, (jnp.arange(nq), qc))
    o = oc.transpose(1, 0, 2, 3, 4, 5).reshape(b, s_pad, hq, d)
    return o[:, :s]


def attention(cfg: ModelConfig, q, k, v, *, causal: bool, chunk: int = 1024):
    """GQA attention dispatch (jnp chunked path or Pallas kernel).

    q (B,S,Hq,D); k,v (B,T,Hkv,D).  Returns (B,S,Hq,D).
    """
    if cfg.use_flash_kernel:
        from repro.kernels.flash_attention import flash_attention
        o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=causal,
                            interpret=jax.default_backend() == "cpu")
        return o.transpose(0, 2, 1, 3)
    return _chunked_attention(q, k, v, causal=causal, chunk=chunk,
                              softcap=cfg.attn_logit_softcap)


# ---------------------------------------------------------------------------
# attention + FFN layers (param dicts + spec trees)


def init_attn(cfg: ModelConfig, rng, scale: float):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = dict(
        wq=jax.random.normal(ks[0], (d, hq, dh), jnp.float32) * scale,
        wk=jax.random.normal(ks[1], (d, hkv, dh), jnp.float32) * scale,
        wv=jax.random.normal(ks[2], (d, hkv, dh), jnp.float32) * scale,
        wo=jax.random.normal(ks[3], (hq, dh, d), jnp.float32) * scale,
    )
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, dh), jnp.float32)
        p["bk"] = jnp.zeros((hkv, dh), jnp.float32)
        p["bv"] = jnp.zeros((hkv, dh), jnp.float32)
    return p


def attn_specs(cfg: ModelConfig):
    # 'embed' == 'fsdp' under training rules; under serving rules it
    # keeps the d_model dim data-sharded (resident weights) instead of
    # replicating when the head count does not divide the model axis.
    p = dict(wq=("embed", "heads", None), wk=("embed", "kv_heads", None),
             wv=("embed", "kv_heads", None), wo=("heads", None, "embed"))
    if cfg.qkv_bias:
        p.update(bq=("heads", None), bk=("kv_heads", None),
                 bv=("kv_heads", None))
    return p


def attn_qkv(cfg: ModelConfig, p, x, positions):
    """Project + rope.  x (B,S,d) -> q (B,S,Hq,D), k/v (B,S,Hkv,D)."""
    dt = cfg.dtype
    specs = attn_specs(cfg)
    q = serving_matmul(x, p["wq"].astype(dt), "bsd,dhk->bshk",
                       specs["wq"])
    k = serving_matmul(x, p["wk"].astype(dt), "bsd,dhk->bshk",
                       specs["wk"])
    v = serving_matmul(x, p["wv"].astype(dt), "bsd,dhk->bshk",
                       specs["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def attn_out(cfg: ModelConfig, p, o):
    return serving_matmul(o, p["wo"].astype(cfg.dtype), "bshk,hkd->bsd",
                          attn_specs(cfg)["wo"])


def self_attention(cfg: ModelConfig, p, x, positions, *, causal=True):
    q, k, v = attn_qkv(cfg, p, x, positions)
    o = attention(cfg, q, k, v, causal=causal)
    return attn_out(cfg, p, o)


def init_mlp(cfg: ModelConfig, rng, scale: float, kind: str = "swiglu",
             d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    if kind == "swiglu":
        return dict(
            w_gate=jax.random.normal(ks[0], (d, f), jnp.float32) * scale,
            w_up=jax.random.normal(ks[1], (d, f), jnp.float32) * scale,
            w_down=jax.random.normal(ks[2], (f, d), jnp.float32) * scale,
        )
    return dict(   # gelu (whisper)
        w_up=jax.random.normal(ks[0], (d, f), jnp.float32) * scale,
        b_up=jnp.zeros((f,), jnp.float32),
        w_down=jax.random.normal(ks[1], (f, d), jnp.float32) * scale,
        b_down=jnp.zeros((d,), jnp.float32),
    )


def mlp_specs(kind: str = "swiglu"):
    if kind == "swiglu":
        return dict(w_gate=("embed", "mlp"), w_up=("embed", "mlp"),
                    w_down=("mlp", "embed"))
    return dict(w_up=("embed", "mlp"), b_up=("mlp",),
                w_down=("mlp", "embed"), b_down=(None,))


def mlp(cfg: ModelConfig, p, x, kind: str = "swiglu"):
    dt = cfg.dtype
    specs = mlp_specs(kind)
    mm = lambda a, name: serving_matmul(a, p[name].astype(dt),
                                        "bsd,df->bsf", specs[name])
    if kind == "swiglu":
        h = jax.nn.silu(mm(x, "w_gate")) * mm(x, "w_up")
        h = shard(h, "batch", None, "mlp")
        return serving_matmul(h, p["w_down"].astype(dt), "bsf,fd->bsd",
                              specs["w_down"])
    h = jax.nn.gelu(mm(x, "w_up") + p["b_up"].astype(dt))
    h = shard(h, "batch", None, "mlp")
    return serving_matmul(h, p["w_down"].astype(dt), "bsf,fd->bsd",
                          specs["w_down"]) + p["b_down"].astype(dt)


def init_embedding(cfg: ModelConfig, rng):
    ks = jax.random.split(rng, 2)
    p = dict(
        tok=jax.random.normal(ks[0], (cfg.vocab, cfg.d_model),
                              jnp.float32) * 0.02,
        norm_f=jnp.ones((cfg.d_model,), jnp.float32),
    )
    if not cfg.tie_embeddings:
        p["head"] = jax.random.normal(
            ks[1], (cfg.d_model, cfg.vocab), jnp.float32) * 0.02
    return p


def embedding_specs(cfg: ModelConfig):
    p = dict(tok=("vocab", "embed"), norm_f=(None,))
    if not cfg.tie_embeddings:
        p["head"] = ("embed", "vocab")
    return p


def embed(cfg: ModelConfig, p, tokens):
    x = jnp.take(p["tok"].astype(cfg.dtype), tokens, axis=0)
    return shard(x, "batch", None, None)


def logits(cfg: ModelConfig, p, x):
    x = rmsnorm(x, p["norm_f"], cfg.norm_eps)
    w = (p["tok"].T if cfg.tie_embeddings else p["head"]).astype(cfg.dtype)
    out = x @ w
    return shard(out, "batch", None, "vocab")
