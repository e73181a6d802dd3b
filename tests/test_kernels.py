"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps.

The kernels compile for the TPU by default; on the CPU they run in the
Pallas interpreter, which these tests ask for explicitly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import forall, int32_grid, integers

RNG = np.random.default_rng(42)


# -- flash attention ---------------------------------------------------------

SHAPES = [
    # b, hq, hkv, sq, sk, d, causal
    (2, 4, 4, 128, 128, 64, False),
    (2, 4, 2, 128, 128, 64, True),
    (1, 8, 1, 200, 200, 64, True),
    (2, 4, 1, 64, 384, 128, True),
    (1, 2, 2, 1, 300, 80, True),       # decode
    (1, 4, 2, 257, 512, 32, True),     # non-aligned q
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", SHAPES)
def test_flash_attention_vs_reference(shape, dtype):
    from repro.kernels.flash_attention import flash_attention, mha_reference
    b, hq, hkv, sq, sk, d, causal = shape
    q = jnp.asarray(RNG.standard_normal((b, hq, sq, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, hkv, sk, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, hkv, sk, d)), dtype)
    o = flash_attention(q, k, v, causal=causal, interpret=True)
    r = mha_reference(q, k, v, causal=causal)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_matches_model_attention_path():
    """The kernel and the model's jnp chunked path agree."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models import common as cm
    q = jnp.asarray(RNG.standard_normal((2, 150, 8, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 150, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 150, 2, 64)), jnp.float32)
    jnp_o = cm._chunked_attention(q, k, v, causal=True, chunk=64)
    pl_o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), causal=True,
                           interpret=True).transpose(0, 2, 1, 3)
    # jnp path ships bf16 probabilities (§Perf iter 1); the Pallas
    # kernel keeps fp32 probs in VMEM -> bf16-level agreement
    np.testing.assert_allclose(np.asarray(jnp_o), np.asarray(pl_o),
                               atol=2e-2, rtol=2e-2)


# -- bank timing -------------------------------------------------------------

@forall(n_cases=40,
        arrived=int32_grid((6, 256), 0, 2), is_write=int32_grid((6, 256), 0, 2),
        row=int32_grid((6, 256), 0, 8), open_e=int32_grid((6, 256), -1, 8),
        nrd=int32_grid((6, 256), 0, 100), nwr=int32_grid((6, 256), 0, 100),
        nact=int32_grid((6, 256), 0, 100), npre=int32_grid((6, 256), 0, 100),
        faw=int32_grid((6, 256), 0, 2), hitp=int32_grid((6, 256), 0, 2),
        arrival=int32_grid((6, 256), 0, 1000),
        scal=int32_grid((6, 6), 0, 100), cap=integers(0, 4))
def test_frfcfs_select_kernel_vs_reference(arrived, is_write, row, open_e,
                                           nrd, nwr, nact, npre, faw, hitp,
                                           arrival, scal, cap):
    from repro.kernels.bank_timing import (frfcfs_select, pack_scalars,
                                           scalars_tuple, select_reference)
    args = [jnp.asarray(a) for a in
            (arrived, is_write, row, open_e, nrd, nwr, nact, npre, faw,
             hitp, arrival)]
    ch = pack_scalars(jnp.int32(50), *(jnp.asarray(scal[:, i])
                                       for i in range(1, 6)))
    sel_k, cmd_k = frfcfs_select(*args, ch, row_hit_cap=cap, interpret=True)
    sel_r, cmd_r = select_reference(*args, scalars_tuple(ch),
                                    row_hit_cap=cap)
    assert (np.asarray(cmd_k) == np.asarray(cmd_r)).all()
    # when a command is selected, the slot must match too
    live = np.asarray(cmd_r) != 0
    assert (np.asarray(sel_k)[live] == np.asarray(sel_r)[live]).all()


@forall(n_cases=24, seed=1,
        arrived=int32_grid((6, 256), 0, 2), is_write=int32_grid((6, 256), 0, 2),
        row=int32_grid((6, 256), 0, 2), open_e=int32_grid((6, 256), -1, 2),
        nact=int32_grid((6, 256), 0, 2), arrival=int32_grid((6, 256), 0, 2),
        drain=int32_grid((6,), 0, 2), cap=integers(0, 2))
def test_frfcfs_select_first_index_tie_break(arrived, is_write, row, open_e,
                                             nact, arrival, drain, cap):
    """Tie-heavy planes: two arrival stamps, two rows and timing that is
    almost always met, so dozens of lanes share the top score.  The
    kernel's max-then-least-lane select must pick the same slot as
    `jnp.argmax` in every channel, including those with no command."""
    from repro.kernels.bank_timing import (frfcfs_select, pack_scalars,
                                           scalars_tuple, select_reference)
    zeros = np.zeros((6, 256), np.int32)
    args = [jnp.asarray(a) for a in
            (arrived, is_write, row, open_e, zeros, zeros, nact, zeros,
             np.ones_like(zeros), zeros, arrival)]
    z = jnp.zeros((6,), jnp.int32)
    ch = pack_scalars(jnp.int32(1), z, z, z, jnp.asarray(drain),
                      jnp.full((6,), 1, jnp.int32))
    sel_k, cmd_k = frfcfs_select(*args, ch, row_hit_cap=cap, interpret=True)
    sel_r, cmd_r = select_reference(*args, scalars_tuple(ch),
                                    row_hit_cap=cap)
    assert (np.asarray(sel_k) == np.asarray(sel_r)).all()
    assert (np.asarray(cmd_k) == np.asarray(cmd_r)).all()


# -- addr decode -------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 100, 1024, 4097, 8193])
def test_addr_decode_kernel_shapes(n):
    from repro.kernels.addr_decode import decode_skylake, decode_reference
    lines = jnp.asarray(RNG.integers(0, 2 ** 32, n, dtype=np.uint32))
    d = decode_skylake(lines, interpret=True)
    r = decode_reference(lines)
    for f in d._fields:
        assert getattr(d, f).shape == (n,)
        assert (np.asarray(getattr(d, f))
                == np.asarray(getattr(r, f))).all(), f
