"""Kernel micro-benchmarks (correctness vs the oracles + host timing).

On a TPU the kernels run compiled; on any other backend they run in
the Pallas interpreter, and the wall times are interpreter numbers —
NOT TPU performance.  The derived column reports the correctness
deltas vs the oracles and the arithmetic-intensity characteristics
that matter on the target.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.util import emit

#: the Pallas interpreter runs wherever the TPU compiler cannot
INTERPRET = jax.default_backend() != "tpu"


def bench_flash_attention():
    from repro.kernels.flash_attention import flash_attention, mha_reference
    rng = np.random.default_rng(0)
    b, hq, hkv, s, d = 1, 8, 2, 512, 64
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.bfloat16)
    o = flash_attention(q, k, v, causal=True, interpret=INTERPRET)
    t0 = time.perf_counter()
    o = flash_attention(q, k, v, causal=True,
                        interpret=INTERPRET).block_until_ready()
    us = (time.perf_counter() - t0) * 1e6
    r = mha_reference(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                - r.astype(jnp.float32))))
    flops = 4 * b * hq * s * s * d
    emit("kernel.flash_attention", us,
         f"maxerr={err:.1e} vs oracle; {flops / 1e9:.2f} GFLOP tile-case")


def bench_bank_timing():
    from repro.kernels.bank_timing import (frfcfs_select, pack_scalars,
                                           scalars_tuple, select_reference)
    rng = np.random.default_rng(1)
    C, Q = 6, 256
    r = lambda hi, shape=(C, Q): jnp.asarray(
        rng.integers(0, hi, size=shape, dtype=np.int32))
    args = [r(2), r(2), r(8), r(8) - 1, r(100), r(100), r(100), r(100),
            r(2), r(2), r(1000)]
    ch = pack_scalars(jnp.int32(50), r(100, (C,)), r(100, (C,)),
                      r(100, (C,)), r(2, (C,)), r(8, (C,)))
    sel, cmd = frfcfs_select(*args, ch, interpret=INTERPRET)
    t0 = time.perf_counter()
    sel, cmd = frfcfs_select(*args, ch, interpret=INTERPRET)
    jax.block_until_ready((sel, cmd))
    us = (time.perf_counter() - t0) * 1e6
    sr, cr = select_reference(*args, scalars_tuple(ch))
    ok = bool((np.asarray(cmd) == np.asarray(cr)).all())
    emit("kernel.bank_timing_select", us,
         f"match={ok}; {C}x{Q} eligibility plane per DRAM tick")


def bench_addr_decode():
    from repro.kernels.addr_decode import decode_skylake, decode_reference
    rng = np.random.default_rng(2)
    lines = jnp.asarray(rng.integers(0, 2 ** 32, 1 << 16, dtype=np.uint32))
    d = decode_skylake(lines, interpret=INTERPRET)
    t0 = time.perf_counter()
    d = decode_skylake(lines, interpret=INTERPRET)
    jax.block_until_ready(d.channel)
    us = (time.perf_counter() - t0) * 1e6
    r = decode_reference(lines)
    ok = all(bool((np.asarray(getattr(d, f))
                   == np.asarray(getattr(r, f))).all()) for f in d._fields)
    emit("kernel.addr_decode", us,
         f"match={ok}; 64k lines/call, 4B/line packed output")


def main(full: bool = False):
    bench_flash_attention()
    bench_bank_timing()
    bench_addr_decode()


if __name__ == "__main__":
    main()
