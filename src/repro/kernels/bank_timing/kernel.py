"""Pallas TPU kernel: per-tick DRAM eligibility + FR-FCFS select.

The cycle-accurate simulator spends its time in one block: checking the
DDR4 timing legality of every queued request and picking the winner
(row-hit CAS > ACT > PRE, oldest first).  On TPU this is a pure VPU
workload — elementwise compares over a (channels, queue) tile and a
masked argmax along lanes.  One program step takes every channel: the
blocks cover the whole (C, Q) planes, the queue axis (256 slots = 2x128
lanes) is the lane dimension and channels sit on sublanes, so the whole
eligibility computation is one VMEM-resident dataflow with no HBM
traffic beyond the initial tile loads.  The argmax is an int32 max over
lanes followed by the least lane index that attains it (the chip's
compiler has no int32 argmax), which keeps `jnp.argmax`'s first-index
tie-break exactly.

Hardware adaptation: the C++ simulators walk linked-list queues a
request at a time; the TPU formulation evaluates *all* slots per cycle
in parallel and reduces.  That is the same algorithm (priority order is
encoded in the score), vectorized.

Inputs: eleven (C, Q) int32 planes (gathered per-entry state) plus one
(C, 8) scalar plane; outputs (C, 2) int32 = (selected slot, command).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BIG = 1 << 28      # python int: becomes an immediate, not a captured const
_MIN = -(1 << 31)   # int32 minimum: identity of a lane max
NONE, RD, WR, ACT, PRE = 0, 1, 2, 3, 4

# scalar plane columns (the plane is padded to 8)
T, BUS_FREE, WTR, RTW, DRAIN, STREAK = range(6)


def _select_kernel(arrived_ref, is_write_ref, row_ref, open_ref, nrd_ref,
                   nwr_ref, nact_ref, npre_ref, faw_ref, hitp_ref,
                   arrival_ref, ch_ref, out_ref, *, row_hit_cap: int):
    C, Q = arrived_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, Q), 1)
    ch = ch_ref[...]
    ch_lane = jax.lax.broadcasted_iota(jnp.int32, ch.shape, 1)
    # column k of the (C, 8) scalar plane as a (C, 1) column
    col = lambda k: jnp.max(jnp.where(ch_lane == k, ch, _MIN), axis=1,
                            keepdims=True)
    # reduce a (C, Q) plane along lanes to (C, 1)
    lane_max = lambda x: jnp.max(x, axis=1, keepdims=True)

    arrived = arrived_ref[...] == 1
    is_wr = is_write_ref[...] == 1
    row = row_ref[...]
    open_e = open_ref[...]
    t = col(T)
    bus_ok = t >= col(BUS_FREE)
    wtr_ok = t >= col(WTR)
    rtw_ok = t >= col(RTW)
    drain = col(DRAIN) == 1
    streak = col(STREAK)

    row_hit = (open_e == row) & arrived
    closed = (open_e < 0) & arrived
    side_ok = is_wr == drain          # writes drain, reads do not
    elig_rd = (row_hit & ~is_wr & (t >= nrd_ref[...]) & bus_ok & wtr_ok
               & ~drain)
    elig_wr = (row_hit & is_wr & (t >= nwr_ref[...]) & bus_ok & rtw_ok
               & drain)
    elig_act = closed & (t >= nact_ref[...]) & (faw_ref[...] == 1) & side_ok
    elig_pre = (arrived & (open_e >= 0) & (open_e != row)
                & (t >= npre_ref[...]) & (hitp_ref[...] == 0) & side_ok)

    age = _BIG - arrival_ref[...]
    score = jnp.where(elig_rd | elig_wr, 3 * _BIG + age,
             jnp.where(elig_act, 2 * _BIG + age,
              jnp.where(elig_pre, 1 * _BIG + age, 0)))
    if row_hit_cap > 0:
        capped = streak >= row_hit_cap
        score = jnp.where(capped & (elig_rd | elig_wr), 1 * _BIG + age, score)
        score = jnp.where(capped & elig_act, 3 * _BIG + age, score)

    # argmax with jnp.argmax's tie-break: the first lane at the maximum
    best = lane_max(score)
    sel = -lane_max(jnp.where(score == best, -lane, -Q))
    onehot = lane == sel
    pick = lambda m: lane_max(jnp.where(onehot, m.astype(jnp.int32), 0))
    any_cmd = best > 0
    s_rd_ok = pick(elig_rd) == 1
    s_wr_ok = pick(elig_wr) == 1
    s_act_ok = pick(elig_act) == 1
    s_pre_ok = pick(elig_pre) == 1
    if row_hit_cap > 0:
        capped1 = streak >= row_hit_cap
        s_cas = any_cmd & (s_rd_ok | s_wr_ok) & ~(capped1 & s_act_ok)
        s_act = any_cmd & s_act_ok & ~s_cas
    else:
        s_cas = any_cmd & (s_rd_ok | s_wr_ok)
        s_act = any_cmd & s_act_ok & ~s_cas
    s_pre = any_cmd & s_pre_ok & ~s_cas & ~s_act
    s_iswr = pick(is_wr) == 1

    cmd = jnp.where(s_cas & ~s_iswr, RD,
           jnp.where(s_cas & s_iswr, WR,
            jnp.where(s_act, ACT,
             jnp.where(s_pre, PRE, NONE)))).astype(jnp.int32)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(out_lane == 0, sel, cmd)


@functools.partial(jax.jit,
                   static_argnames=("row_hit_cap", "interpret"))
def frfcfs_select(arrived, is_write, row, open_e, nrd_e, nwr_e, nact_e,
                  npre_e, faw_ok, hit_pend, arrival, ch_scalars, *,
                  row_hit_cap: int = 0, interpret: bool = False):
    """Pallas twin of the select block in `repro.core.dram.tick`.

    Per-entry planes: (C, Q) int32.  ch_scalars: (C, 8) int32 with
    columns (t, bus_free, wtr_until, rtw_until, drain, hit_streak).
    Returns (sel, cmd), each (C,) int32.  ``interpret=True`` runs the
    Pallas interpreter (CPU callers).
    """
    C = arrived.shape[0]
    out = pl.pallas_call(
        functools.partial(_select_kernel, row_hit_cap=row_hit_cap),
        out_shape=jax.ShapeDtypeStruct((C, 2), jnp.int32),
        interpret=interpret,
    )(arrived, is_write, row, open_e, nrd_e, nwr_e, nact_e, npre_e,
      faw_ok, hit_pend, arrival, ch_scalars)
    return out[:, 0], out[:, 1]
