"""Cycle-accurate DRAM device + memory-controller model (the weave backend).

A JAX-native reimplementation of the Ramulator-class cycle-accurate
memory simulation used in the paper: per-bank state machines with the
full DDRx timing set (tRCD/tRP/tCL/tRAS/tCCD_S/L/tWTR/tRTP/tRRD/tFAW/
tREFI/tRFC), FR-FCFS scheduling with open-page policy, watermark-based
write draining, rank-aware bus turnaround, and per-rank (all-bank) or
rotating per-bank (DDR5 REFsb) refresh.  The device geometry and
timings come from a `DramParams` instance — DDR4-2666 by default, or
any preset from `repro.core.presets` (DDR5-4800, HBM2e); nothing in
this module assumes a fixed channel/rank/bank-group count.

Everything is vectorized over (channel, queue-slot) and
(channel, rank*bank) so one simulated memory tick is a fixed dataflow
graph usable inside ``jax.lax.scan`` (and batchable with ``jax.vmap``
across sweep points).  Dynamic structures of the C++ simulators map to
static shapes:

* request queues  -> fixed-capacity slot arrays with a `valid` mask,
* FR-FCFS         -> masked argmax over a priority score
                     (row-hit >> activate >> precharge, oldest first),
* FAW sliding window -> a 4-deep shift register of ACT timestamps.

Per-entry reads of per-bank state, and the writes of the selected
command, go through one-hot match planes against static bank and slot
indices (`_match`): a dense select-reduce in place of an indexed
gather or scatter, which the TPU runs one index at a time.

`repro.kernels.bank_timing` is a Pallas kernel of the FR-FCFS select
alone, off the main path; `tick` does not call it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.timing import DramParams

# command codes (REF never competes in the FR-FCFS select — refresh is
# deadline-driven inside `tick` — but the command-stream recorder and
# the `repro.oracle` legality checker use it as a first-class code)
NONE, RD, WR, ACT, PRE, REF = 0, 1, 2, 3, 4, 5

_BIG = jnp.int32(1 << 28)

#: log2 latency-histogram buckets: bucket ``b`` counts values in
#: ``[2^b, 2^(b+1))``; 24 buckets cover 1 DRAM tick .. 16.7M ps
#: (values past the top edge clip into the last bucket).
N_HIST = 24


class BankPlanes(NamedTuple):
    """Loop-invariant index planes of one device geometry.

    These are pure functions of `DramParams` (never of simulation
    state), so they are built **once** per device — host-side numpy, so
    they embed as XLA constants — instead of being re-derived on every
    `tick` trace.  Both weave engines (the dense per-tick scan and the
    event-horizon scan) share one instance via `bank_planes`.
    """

    cidx: np.ndarray          # (C,)  channel index
    rank_of: np.ndarray       # (RB,) rank of each flat bank
    grp_of: np.ndarray        # (RB,) bank group of each flat bank
    bank_in_rank: np.ndarray  # (RB,) bank index within its rank


@functools.lru_cache(maxsize=None)
def bank_planes(dram: DramParams) -> BankPlanes:
    """The precomputed `BankPlanes` of one device (cached per preset)."""
    C = dram.n_channels
    RB = dram.banks_per_channel
    nbanks = dram.banks_per_rank
    bank = np.arange(RB, dtype=np.int32)
    return BankPlanes(
        cidx=np.arange(C, dtype=np.int32),
        rank_of=bank // nbanks,
        grp_of=(bank % nbanks) // dram.banks_per_group,
        bank_in_rank=bank % nbanks,
    )


@dataclasses.dataclass(frozen=True)
class SchedulerPolicy:
    """Backend-flavor knobs (Ramulator / Ramulator2 / DRAMsim3)."""

    name: str = "ramulator"
    # Per-channel request-slot array.  Slots double as the *staging
    # buffer* for requests issued later in the window (entries are
    # invisible to the scheduler until their `arrival` tick), so the
    # depth must cover a full window of offered traffic — 23 cores x
    # 64 req / 6 channels ~ 245 — or injection artificially caps the
    # achieved bandwidth far below the DRAM service rate.
    queue_depth: int = 256
    drain_hi: int = 20             # write-drain high watermark
    drain_lo: int = 6              # write-drain low watermark
    row_hit_cap: int = 0           # 0 = pure FR-FCFS; >0 caps hit streaks
    mc_extra_ticks: int = 0        # stage-10 delay buffer (MC pipe + PHY)


class QueueState(NamedTuple):
    """Per-channel request queue; all fields (C, Q) int32."""

    valid: jnp.ndarray
    is_write: jnp.ndarray
    arrival: jnp.ndarray       # DRAM tick at which the request is visible
    issue_cycle: jnp.ndarray   # CPU cycle at which the core issued it
    fbank: jnp.ndarray         # rank*16 + bank
    row: jnp.ndarray
    is_chase: jnp.ndarray      # pointer-chase (latency-probe) request


class BankState(NamedTuple):
    """Per-bank / per-channel controller state; all times in DRAM ticks.

    ``C`` = channels, ``R`` = ranks/channel, ``RB`` = ranks x banks.
    """

    open_row: jnp.ndarray      # (C, RB) int32, -1 = precharged
    next_act: jnp.ndarray      # (C, RB) earliest tick for ACT
    next_rd: jnp.ndarray       # (C, RB)
    next_wr: jnp.ndarray       # (C, RB)
    next_pre: jnp.ndarray      # (C, RB)
    faw: jnp.ndarray           # (C, R, 4) last four ACT ticks, oldest first
    next_ref: jnp.ndarray      # (C, R) next refresh deadline
    ref_slot: jnp.ndarray      # (C, R) rotating REFsb bank index (DDR5)
    bus_free: jnp.ndarray      # (C,) data-bus free tick
    wtr_until: jnp.ndarray     # (C,) reads blocked until (write->read turn)
    rtw_until: jnp.ndarray     # (C,) writes blocked until (read->write turn)
    last_rank: jnp.ndarray     # (C,) rank of last data burst (tRTRS)
    drain: jnp.ndarray         # (C,) bool: write-drain mode
    hit_streak: jnp.ndarray    # (C,) consecutive row-hit grants (for cap)


class TickStats(NamedTuple):
    """One tick's completion statistics, **per channel** ``(C,)``.

    Latency units differ by view on purpose: ``sum_rd_lat_ticks`` is
    DRAM ticks (view ① — multiply by ``dram_ps_per_clk`` for time),
    ``sum_if_lat_ps`` is CPU-perceived picoseconds (view ② — already
    crossed the clock domain).

    The fields are per-channel vectors (a channel issues at most one
    command per tick) and are *accumulated in time order per channel*
    by the weave loops.  That makes the float32 ``sum_if_lat_ps``
    window total bit-identical between the dense and event engines:
    idle ticks contribute exact ``+0.0`` (the float32 identity), so
    both engines fold the same non-zero values in the same order.
    """

    served_rd: jnp.ndarray         # (C,) int32
    served_wr: jnp.ndarray
    sum_rd_lat_ticks: jnp.ndarray  # simulator view: completion - arrival
    sum_if_lat_ps: jnp.ndarray     # interface view (CPU-domain), float32
    chase_rd: jnp.ndarray
    sum_chase_lat_ticks: jnp.ndarray


def zero_stats(dram: DramParams) -> TickStats:
    """A zeroed per-channel `TickStats` accumulator."""
    zi = jnp.zeros((dram.n_channels,), jnp.int32)
    return TickStats(served_rd=zi, served_wr=zi, sum_rd_lat_ticks=zi,
                     sum_if_lat_ps=jnp.zeros((dram.n_channels,),
                                             jnp.float32),
                     chase_rd=zi, sum_chase_lat_ticks=zi)


class TickTele(NamedTuple):
    """One tick's telemetry increments (the simulator-view counter
    planes of ``repro.obs``), **per channel** ``(C,)`` unless noted.

    Everything here is an *event count* or an *event-accounted time
    integral* — never a per-tick state sample — so the planes
    accumulate to identical window totals under the dense and the
    event-horizon weave engines (the event engine evaluates exactly
    the ticks where these events can occur).

    Row-locality counters are derivable from the command mix by the
    classical identity (each request retires with exactly one CAS):
    ``hits = cas - act``, ``misses = act - pre``, ``conflicts = pre``
    — see `repro.obs.telemetry.summarize` (refresh-forced re-ACTs can
    make per-window ``hits`` dip negative; the reduction clamps and
    documents this).
    """

    n_act: jnp.ndarray             # ACT commands issued
    n_pre: jnp.ndarray             # PRE commands issued
    n_cas_rd: jnp.ndarray          # read CAS (== TickStats.served_rd)
    n_cas_wr: jnp.ndarray          # write CAS
    n_ref: jnp.ndarray             # refresh events (per rank deadline)
    drain_enter: jnp.ndarray       # write-drain service bursts entered
    drain_ticks: jnp.ndarray       # drain service dwell (burst spans)
    busy_ticks: jnp.ndarray        # (C, RB) row-open time, at row close
    hist_rd_ticks: jnp.ndarray     # (C, N_HIST) read latency, DRAM ticks
    hist_if_ps: jnp.ndarray        # (C, N_HIST) CPU-perceived read ps


class TeleState(NamedTuple):
    """Telemetry-only carry state (exists only with telemetry on).

    Time integrals are accounted at *grant* events so both weave
    engines agree exactly: ``opened_at`` remembers each bank's last
    ACT tick (busy time is added when the row closes via PRE or
    refresh); ``last_wr_t`` / ``wr_burst`` track the channel's current
    write-CAS burst (drain dwell accrues at each write grant).
    """

    opened_at: jnp.ndarray         # (C, RB) int32 tick of last ACT
    last_wr_t: jnp.ndarray         # (C,) int32 tick of last write CAS
    wr_burst: jnp.ndarray          # (C,) bool: last CAS was a write


def zero_tele(dram: DramParams) -> TickTele:
    """A zeroed per-channel `TickTele` accumulator."""
    C, RB = dram.n_channels, dram.banks_per_channel
    zc = jnp.zeros((C,), jnp.int32)
    zh = jnp.zeros((C, N_HIST), jnp.int32)
    return TickTele(n_act=zc, n_pre=zc, n_cas_rd=zc, n_cas_wr=zc,
                    n_ref=zc, drain_enter=zc, drain_ticks=zc,
                    busy_ticks=jnp.zeros((C, RB), jnp.int32),
                    hist_rd_ticks=zh, hist_if_ps=zh)


def init_tele(dram: DramParams) -> TeleState:
    """Fresh telemetry carry (all banks closed, no drain in progress)."""
    C, RB = dram.n_channels, dram.banks_per_channel
    return TeleState(opened_at=jnp.zeros((C, RB), jnp.int32),
                     last_wr_t=jnp.zeros((C,), jnp.int32),
                     wr_burst=jnp.zeros((C,), bool))


class TickCmd(NamedTuple):
    """One tick's granted-command record (`StageConfig.cmd_trace`).

    The raw material of the `repro.oracle` command stream: what each
    channel's controller *did* at the evaluated tick.  Everything is
    derived from the tick's own command-select intermediates, so with
    the flag off the traced graph is untouched — and because command
    grants and refresh firings happen at identical ticks under both
    weave engines (the bit-identity the golden grid proves), filtering
    the records down to ``cmd != NONE`` / ``ref`` rows yields the
    **same per-channel stream** from either engine.

    Fields (``C`` channels, ``R`` ranks/channel):

    * ``cmd`` ``(C,)`` — `NONE`/`RD`/`WR`/`ACT`/`PRE` granted this tick
      (refresh is recorded separately; it can coincide with a grant).
    * ``t`` ``(C,)`` — the evaluated DRAM tick (absolute).
    * ``fbank`` ``(C,)`` — flat bank (``rank * banks_per_rank + bank``)
      of the granted command; meaningful only when ``cmd != NONE``.
    * ``row`` ``(C,)`` — target row for ACT/RD/WR; ``-1`` for PRE
      (the open row is being closed) and idle ticks.
    * ``ref`` ``(C, R)`` bool — rank ``r`` hit its refresh deadline.
    * ``ref_bank`` ``(C, R)`` — the REFsb bank-in-rank refreshed
      (pre-rotation `BankState.ref_slot`); ``-1`` for all-bank refresh.
    """

    cmd: jnp.ndarray
    t: jnp.ndarray
    fbank: jnp.ndarray
    row: jnp.ndarray
    ref: jnp.ndarray
    ref_bank: jnp.ndarray


def log2_bucket(v) -> jnp.ndarray:
    """``floor(log2(max(v, 1)))`` clipped to ``[0, N_HIST - 1]``.

    Integer-exact (count-leading-zeros, no float log), so histogram
    bucket edges land exactly on powers of two.
    """
    v = jnp.maximum(jnp.asarray(v, jnp.int32), 1)
    return jnp.minimum(31 - jax.lax.clz(v), N_HIST - 1)


def init_queue(dram: DramParams, policy: SchedulerPolicy,
               n_sockets: int = 1) -> QueueState:
    """Empty per-channel request queue: (C, queue_depth) int32 slots.

    ``queue_depth`` is derived from one socket's per-window offered
    traffic (see `SchedulerPolicy`); ``n_sockets`` scales the staging
    capacity so a multi-socket frontend keeps the same invariant —
    without it a two-socket ddr4 run (47 cores x 64 req / 6 channels
    ~ 501/window) would overflow the staging slots and silently drop
    replayed demand.
    """
    C, Q = dram.n_channels, policy.queue_depth * n_sockets
    z = jnp.zeros((C, Q), jnp.int32)
    return QueueState(valid=z, is_write=z, arrival=z, issue_cycle=z,
                      fbank=z, row=z - 1, is_chase=z)


def init_banks(dram: DramParams) -> BankState:
    """All banks precharged, refresh deadlines staggered across ranks.

    Also builds (and caches) the device's `BankPlanes` — the
    loop-invariant index planes `tick` compares against.
    """
    bank_planes(dram)            # warm the per-device plane cache
    C = dram.n_channels
    RB = dram.banks_per_channel
    R = dram.ranks_per_channel
    zi = jnp.zeros((C, RB), jnp.int32)
    return BankState(
        open_row=zi - 1,
        next_act=zi, next_rd=zi, next_wr=zi, next_pre=zi,
        faw=jnp.full((C, R, 4), -(1 << 20), jnp.int32),
        # stagger refresh deadlines across ranks like real controllers
        next_ref=(dram.tREFI
                  + jnp.arange(R, dtype=jnp.int32)[None, :] * (dram.tREFI // R)
                  + jnp.zeros((C, R), jnp.int32)),
        ref_slot=jnp.zeros((C, R), jnp.int32),
        bus_free=jnp.zeros((C,), jnp.int32),
        wtr_until=jnp.zeros((C,), jnp.int32),
        rtw_until=jnp.zeros((C,), jnp.int32),
        last_rank=jnp.zeros((C,), jnp.int32),
        drain=jnp.zeros((C,), bool),
        hit_streak=jnp.zeros((C,), jnp.int32),
    )


def _match(idx, n: int):
    """One-hot match plane of an index ``(C, ...)`` against ``arange(n)``:
    ``(C,) -> (C, n)`` and ``(C, Q) -> (C, n, Q)`` (the matched axis
    second, so a queue's slots stay on the minor axis)."""
    iota = jnp.arange(n, dtype=jnp.int32)
    if idx.ndim == 1:
        return idx[:, None] == iota
    return idx[:, None, :] == iota[:, None]


def _select(match, values, axis: int):
    """Reduce ``values`` over ``axis`` to where the one-hot ``match`` is
    set: a select-sum (select-any for bools).  Where exactly one index
    matches, that is the indexed read bit for bit."""
    if values.dtype == jnp.bool_:
        return jnp.any(match & values, axis=axis)
    return jnp.sum(jnp.where(match, values, 0), axis=axis,
                   dtype=values.dtype)


def _gather(bank_field, match):
    """(C, RB) per-bank field read per queue entry -> (C, Q).

    ``match`` is the queue's ``(C, RB, Q)`` bank-match plane
    (`_match(queue.fbank, RB)`).  Every slot holds a flat bank in
    ``[0, RB)`` (empty slots hold 0, `init_queue`), so exactly one bank
    matches each slot.
    """
    return _select(match, bank_field[:, :, None], axis=1)


def tick(queue: QueueState, banks: BankState, t, *,
         dram: DramParams, policy: SchedulerPolicy,
         tick2cpu_num: int, tick2cpu_den: int, cpu_ps_per_clk: int,
         active=True, planes: BankPlanes | None = None,
         telemetry: bool = False, tele: TeleState | None = None,
         cmd_trace: bool = False):
    """Advance the memory system by one DRAM tick.

    Args:
        queue, banks: current `QueueState` / `BankState`.
        t: current DRAM tick (int32, traced) — a scalar, or a
            per-channel ``(C,)`` vector (channels are fully decoupled
            inside a window, which is what lets the event-horizon
            engine advance each channel along its own event times).
        dram, policy: static device timings + controller flavor.
        tick2cpu_num, tick2cpu_den: DRAM tick -> CPU-perceived
            picoseconds under the active clock model
            (``cpu_ps = tick * num // den``).
        cpu_ps_per_clk: CPU picoseconds per CPU cycle (476 for 2.1 GHz).
        active: gates windows whose static tick budget exceeds the
            clock model's exact tick count (inactive ticks are no-ops);
            scalar or per-channel ``(C,)``, like ``t``.
        planes: the device's precomputed `BankPlanes`; defaults to the
            cached `bank_planes(dram)`.
        telemetry: **static** flag; when False (default) the traced
            graph is the flags-off tick graph, which the flag's code
            does not alter.  When True, the tick additionally returns
            its `TickTele` increments and the threaded `TeleState`.
        tele: the telemetry carry (`TeleState`); only read with
            ``telemetry=True``.
        cmd_trace: **static** flag; when True the tick additionally
            returns its `TickCmd` command record (the `repro.oracle`
            recorder).  Like ``telemetry``, the False path traces the
            flags-off graph unaltered.

    Returns:
        ``(queue', banks', TickStats)``; ``telemetry=True`` appends
        ``(TickTele, TeleState)`` and ``cmd_trace=True`` appends a
        trailing `TickCmd` (the flags compose, in that order).
        Latencies in `TickStats` are DRAM ticks (simulator view) and
        picoseconds (interface view).
    """
    C = dram.n_channels
    RB = dram.banks_per_channel
    nbanks = dram.banks_per_rank
    if planes is None:
        planes = bank_planes(dram)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (C,))
    active = jnp.broadcast_to(jnp.asarray(active), (C,))
    t_r = t[:, None]                    # against (C, R) / (C, RB) / (C, Q)
    open_row_pre = banks.open_row       # pre-refresh (telemetry: busy)
    ref_slot_pre = banks.ref_slot       # pre-rotation (cmd_trace: REFsb)

    # ---- refresh ----------------------------------------------------
    # All-bank (DDR4/HBM2e): close the whole rank, block it for tRFC.
    # Same-bank (DDR5 REFsb): block only the rotating target bank for
    # tRFCsb; the rest of the rank keeps serving.
    ref_due = active[:, None] & (t_r >= banks.next_ref)         # (C, R)
    refmask = jnp.repeat(ref_due, nbanks, axis=1)               # (C, RB)
    if dram.same_bank_refresh:
        target = jnp.repeat(banks.ref_slot, nbanks, axis=1)     # (C, RB)
        refmask = refmask & (planes.bank_in_rank[None, :] == target)
        ref_slot = jnp.where(ref_due, (banks.ref_slot + 1) % nbanks,
                             banks.ref_slot)
    else:
        ref_slot = banks.ref_slot
    open_row = jnp.where(refmask, -1, banks.open_row)
    next_act = jnp.where(refmask,
                         jnp.maximum(banks.next_act, t_r + dram.tRFC),
                         banks.next_act)
    next_ref = jnp.where(ref_due, banks.next_ref + dram.tREFI, banks.next_ref)
    banks = banks._replace(open_row=open_row, next_act=next_act,
                           next_ref=next_ref, ref_slot=ref_slot)

    # ---- write-drain hysteresis --------------------------------------
    arrived = (queue.valid == 1) & (queue.arrival <= t_r)       # (C, Q)
    nw = jnp.sum(arrived & (queue.is_write == 1), axis=1)       # (C,)
    nr = jnp.sum(arrived & (queue.is_write == 0), axis=1)
    drain = jnp.where(banks.drain, nw > policy.drain_lo, nw >= policy.drain_hi)
    drain = drain | ((nr == 0) & (nw > 0))
    banks = banks._replace(drain=drain)

    # ---- per-entry eligibility ---------------------------------------
    # Bank timers are compared at the bank, then read per entry through
    # the queue's bank-match plane.
    match = _match(queue.fbank, RB)                             # (C, RB, Q)
    open_e = _gather(banks.open_row, match)
    faw_ok = jnp.repeat(t_r >= banks.faw[:, :, 0] + dram.tFAW,
                        nbanks, axis=1)                         # (C, RB)

    row_hit = open_e == queue.row
    closed = open_e < 0
    is_wr = queue.is_write == 1
    bus_ok = (t >= banks.bus_free)[:, None]
    drain_c = drain[:, None]

    # During a drain the channel is dedicated to writes; outside it,
    # to reads (standard watermark write-buffering).
    side_ok = jnp.where(is_wr, drain_c, ~drain_c)
    elig_rd = (arrived & ~is_wr & row_hit
               & _gather(t_r >= banks.next_rd, match) & bus_ok
               & (t >= banks.wtr_until)[:, None] & ~drain_c)
    elig_wr = (arrived & is_wr & row_hit
               & _gather(t_r >= banks.next_wr, match) & bus_ok
               & (t >= banks.rtw_until)[:, None] & drain_c)
    elig_act = (arrived & closed & side_ok
                & _gather((t_r >= banks.next_act) & faw_ok, match))

    # FR-FCFS guard: don't precharge a row that still has pending hits
    # *on the active side* — during a write drain only write hits count
    # (a pending read hit must not block the drain's precharges, or the
    # drain can never finish and the channel deadlocks).
    pend = arrived & row_hit & (is_wr == drain_c)
    hit_pend = _select(match, pend[:, None, :], axis=2)         # (C, RB)
    elig_pre = (arrived & ~closed & ~row_hit & side_ok
                & _gather((t_r >= banks.next_pre) & ~hit_pend, match))

    # ---- FR-FCFS priority: CAS > ACT > PRE, oldest-first --------------
    age = _BIG - queue.arrival
    score = jnp.where(elig_rd | elig_wr, 3 * _BIG + age,
             jnp.where(elig_act, 2 * _BIG + age,
              jnp.where(elig_pre, 1 * _BIG + age, 0)))
    if policy.row_hit_cap > 0:
        # Ramulator2-style starvation cap: after `cap` consecutive CAS
        # grants, age wins over row-hit priority.
        capped = (banks.hit_streak >= policy.row_hit_cap)[:, None]
        score = jnp.where(capped & (elig_rd | elig_wr), 1 * _BIG + age, score)
        score = jnp.where(capped & elig_act, 3 * _BIG + age, score)
    score = jnp.where(active[:, None], score, 0)

    sel = jnp.argmax(score, axis=1)                             # (C,)
    any_cmd = jnp.max(score, axis=1) > 0                        # score[sel]
    sel_match = _match(sel, queue.valid.shape[1])               # (C, Q)

    def pick(field):
        return _select(sel_match, field, axis=1)

    s_fb = pick(queue.fbank)
    s_row = pick(queue.row)
    s_arr = pick(queue.arrival)
    s_issue = pick(queue.issue_cycle)
    s_rank = s_fb // nbanks
    s_bg = (s_fb % nbanks) // dram.banks_per_group
    s_iswr = pick(is_wr)
    s_chase = pick(queue.is_chase) == 1
    s_rd_ok = pick(elig_rd)
    s_wr_ok = pick(elig_wr)
    s_act_ok = pick(elig_act)
    s_pre_ok = pick(elig_pre)
    if policy.row_hit_cap > 0:
        capped1 = banks.hit_streak >= policy.row_hit_cap
        # under the cap inversion an ACT can outrank CAS; recompute cmd
        s_cas = any_cmd & (s_rd_ok | s_wr_ok) & ~(capped1 & s_act_ok)
        s_act = any_cmd & s_act_ok & ~s_cas
    else:
        s_cas = any_cmd & (s_rd_ok | s_wr_ok)
        s_act = any_cmd & s_act_ok & ~s_cas
    s_pre = any_cmd & s_pre_ok & ~s_cas & ~s_act
    s_rd = s_cas & ~s_iswr
    s_wr = s_cas & s_iswr

    # ---- apply the selected command per channel ----------------------
    # writes to the selected bank: a select against its one-hot row
    bsel = _match(s_fb, RB)                                     # (C, RB)
    on_act = bsel & s_act[:, None]

    # ACT
    same_rank = planes.rank_of[None, :] == s_rank[:, None]
    same_grp = (planes.grp_of[None, :] == s_bg[:, None]) & same_rank
    open_row = jnp.where(on_act, s_row[:, None], banks.open_row)
    nact = jnp.where(s_act[:, None] & same_rank,
                     jnp.maximum(banks.next_act, t_r + dram.tRRD_S),
                     banks.next_act)
    nact = jnp.where(s_act[:, None] & same_grp,
                     jnp.maximum(nact, t_r + dram.tRRD_L), nact)
    nact = jnp.where(on_act, jnp.maximum(nact, t_r + dram.tRC), nact)
    nrd = jnp.where(on_act, t_r + dram.tRCD, banks.next_rd)
    nwr = jnp.where(on_act, t_r + dram.tRCD, banks.next_wr)
    npre = jnp.where(on_act, t_r + dram.tRAS, banks.next_pre)
    # FAW shift-register push
    faw_new = jnp.concatenate(
        [banks.faw[:, :, 1:],
         jnp.broadcast_to(t[:, None, None], banks.faw[:, :, :1].shape)],
        axis=2)
    act_rank = jax.nn.one_hot(s_rank, dram.ranks_per_channel,
                              dtype=bool) & s_act[:, None]
    faw = jnp.where(act_rank[:, :, None], faw_new, banks.faw)

    # CAS (RD/WR): bus + tCCD (bank-group aware, channel-wide) + turnaround
    rank_switch = s_rank != banks.last_rank
    burst = dram.tBL + jnp.where(rank_switch, dram.tRTRS, 0)
    bus_free = jnp.where(s_cas, t + burst, banks.bus_free)
    last_rank = jnp.where(s_cas, s_rank, banks.last_rank)
    ccd = jnp.where(same_grp, dram.tCCD_L, dram.tCCD_S)
    nrd = jnp.where(s_cas[:, None], jnp.maximum(nrd, t_r + ccd), nrd)
    nwr = jnp.where(s_cas[:, None], jnp.maximum(nwr, t_r + ccd), nwr)
    npre = jnp.where(bsel & s_rd[:, None],
                     jnp.maximum(npre, t_r + dram.tRTP),
                     jnp.where(bsel & s_wr[:, None],
                               jnp.maximum(npre, t_r + dram.tCWL + dram.tBL
                                           + dram.tWR),
                               npre))
    wtr_until = jnp.where(s_wr, t + dram.tCWL + dram.tBL + dram.tWTR_L,
                          banks.wtr_until)
    rtw_until = jnp.where(s_rd, t + dram.tCL + dram.tBL + dram.tRTRS
                          - dram.tCWL, banks.rtw_until)

    # PRE
    on_pre = bsel & s_pre[:, None]
    open_row = jnp.where(on_pre, -1, open_row)
    nact = jnp.where(on_pre, jnp.maximum(nact, t_r + dram.tRP), nact)

    hit_streak = jnp.where(s_cas, banks.hit_streak + 1,
                           jnp.where(any_cmd, 0, banks.hit_streak))

    banks = BankState(open_row=open_row, next_act=nact, next_rd=nrd,
                      next_wr=nwr, next_pre=npre, faw=faw, next_ref=next_ref,
                      ref_slot=ref_slot, bus_free=bus_free,
                      wtr_until=wtr_until, rtw_until=rtw_until,
                      last_rank=last_rank, drain=drain,
                      hit_streak=hit_streak)

    # retire CAS'd entries
    served = (sel_match & s_cas[:, None]).astype(jnp.int32)
    queue = queue._replace(valid=queue.valid & (1 - served))

    # ---- stats --------------------------------------------------------
    done_t = t + dram.tCL + dram.tBL + policy.mc_extra_ticks
    rd_lat = done_t - s_arr                                     # ticks
    if_lat_i = (done_t * tick2cpu_num // tick2cpu_den
                - s_issue * cpu_ps_per_clk)                     # ps, int32
    if_lat_ps = if_lat_i.astype(jnp.float32)
    stats = TickStats(
        served_rd=s_rd.astype(jnp.int32),
        served_wr=s_wr.astype(jnp.int32),
        sum_rd_lat_ticks=jnp.where(s_rd, rd_lat, 0),
        sum_if_lat_ps=jnp.where(s_rd, if_lat_ps, 0.0),
        chase_rd=(s_rd & s_chase).astype(jnp.int32),
        sum_chase_lat_ticks=jnp.where(s_rd & s_chase, rd_lat, 0),
    )
    if not telemetry and not cmd_trace:
        return queue, banks, stats

    extras = ()
    if telemetry:
        # ---- telemetry counter planes (static flag: with telemetry
        # off, the path above is the whole graph) ----------------------
        # Everything is accounted at *events* (command grants, refresh
        # deadlines, row closes), never sampled per tick, so the planes
        # are engine-invariant: the event-horizon scan evaluates
        # exactly the ticks where these events occur.
        if tele is None:
            tele = init_tele(dram)
        # row-open busy time, accounted when the row closes.  A refresh
        # close covers every refreshed bank that held an open row; a
        # PRE close covers the selected bank (ACT and PRE are mutually
        # exclusive per channel per tick, so `opened_at` ordering is
        # safe).
        busy = jnp.where(refmask & (open_row_pre >= 0),
                         t_r - tele.opened_at, 0)
        opened_at = jnp.where(on_act, t_r, tele.opened_at)
        busy = busy + jnp.where(on_pre, t_r - opened_at, 0)
        # write-drain planes at CAS resolution: a maximal run of write
        # CAS grants (uninterrupted by a read CAS) is one drain service
        # burst, and its dwell — span from first to last write grant,
        # plus one burst of bus time — accrues incrementally at each
        # write grant.  The controller's drain *flag* can flip at ticks
        # the event engine provably need not evaluate (when the last
        # drained write retires, nothing new becomes eligible until the
        # next arrival), so flag transitions are NOT engine-invariant;
        # CAS grants are, by bit-identity of the engines.
        enter = s_wr & ~tele.wr_burst
        dwell = jnp.where(s_wr, jnp.where(tele.wr_burst,
                                          t - tele.last_wr_t, dram.tBL), 0)
        last_wr_t = jnp.where(s_wr, t, tele.last_wr_t)
        wr_burst = jnp.where(s_cas, s_wr, tele.wr_burst)
        # log2 latency histograms: simulator view in DRAM ticks,
        # interface view in CPU-perceived picoseconds (the int behind
        # sum_if_lat_ps)
        one_rd = s_rd.astype(jnp.int32)
        hist_rd = (_match(log2_bucket(rd_lat), N_HIST)
                   & s_rd[:, None]).astype(jnp.int32)
        hist_if = (_match(log2_bucket(if_lat_i), N_HIST)
                   & s_rd[:, None]).astype(jnp.int32)
        tele_inc = TickTele(
            n_act=s_act.astype(jnp.int32), n_pre=s_pre.astype(jnp.int32),
            n_cas_rd=one_rd, n_cas_wr=s_wr.astype(jnp.int32),
            n_ref=jnp.sum(ref_due.astype(jnp.int32), axis=1),
            drain_enter=enter.astype(jnp.int32), drain_ticks=dwell,
            busy_ticks=busy, hist_rd_ticks=hist_rd, hist_if_ps=hist_if)
        extras = (tele_inc, TeleState(opened_at, last_wr_t, wr_burst))
    if cmd_trace:
        # ---- command-stream record (the `repro.oracle` recorder) -----
        # Pure functions of the command-select intermediates above: the
        # grant code, its bank/row target, and the refresh firings —
        # everything the protocol-legality checker replays.
        cmd = jnp.where(s_rd, RD, jnp.where(s_wr, WR,
                        jnp.where(s_act, ACT,
                                  jnp.where(s_pre, PRE, NONE))))
        cmdrec = TickCmd(
            cmd=cmd.astype(jnp.int32), t=t, fbank=s_fb,
            row=jnp.where(s_act | s_cas, s_row, -1),
            ref=ref_due,
            ref_bank=(jnp.where(ref_due, ref_slot_pre, -1)
                      if dram.same_bank_refresh
                      else jnp.full_like(ref_slot_pre, -1)))
        extras += (cmdrec,)
    return (queue, banks, stats) + extras


def next_event(queue: QueueState, banks: BankState, t, end, *,
               dram: DramParams, policy: SchedulerPolicy):
    """The exact event horizon: earliest tick > ``t`` where `tick` can act.

    Evaluated on the *post-tick* state at ``t``, this returns — **per
    channel** — the smallest tick at which that channel's behaviour can
    differ from a no-op; the event-driven weave engine jumps each
    channel straight there (`tick` couples channels only through the
    window-level stats reduction, never through state, so per-channel
    time vectors are exact).  Every dense tick strictly between
    ``t[c]`` and the returned tick is provably a no-op for channel
    ``c``: no request arrives, no refresh deadline passes, no command
    becomes issuable, and the write-drain hysteresis sits at its fixed
    point.  The candidates, all exact (never early, never late):

    * **arrival** — the min ``arrival`` over valid not-yet-visible
      entries (visibility changes the drain counts and FR-FCFS pool);
    * **drain settle** — ``t + 1`` whenever one application of the
      write-drain hysteresis would flip the channel's ``drain`` flag
      (the dense scan re-evaluates it every tick; between arrivals and
      retirements one application reaches the fixed point, so a single
      forced step is exact);
    * **CAS** — per arrived row-hit entry on the active drain side:
      ``max(next_rd|next_wr, bus_free, wtr_until|rtw_until)``;
    * **ACT** — per arrived closed-bank entry on the active side:
      ``max(next_act, FAW expiry of its rank)``;
    * **PRE** — per arrived row-conflict entry on the active side with
      no pending same-side row hits: ``next_pre``;
    * **refresh** — the channel's min ``next_ref`` deadline.

    Scheduling *priority* (FR-FCFS score, row-hit caps) never needs a
    candidate: it picks among issuable commands but cannot create one.

    Args:
        queue, banks: post-`tick` state at ``t``.
        t: the tick just evaluated — scalar or per-channel ``(C,)``
            (int32, traced).
        end: static scan horizon (``window start + ticks_per_window``);
            results are clamped into ``[t + 1, end]`` — ``end`` means
            "no event on this channel before the horizon".
        dram, policy: static device timings + controller flavor.

    Returns:
        ``(C,)`` int32 per-channel next-event ticks in ``[t + 1, end]``.
    """
    nbanks = dram.banks_per_rank
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (dram.n_channels,))
    t_r = t[:, None]

    valid = queue.valid == 1
    arrived = valid & (queue.arrival <= t_r)                    # (C, Q)
    is_wr = queue.is_write == 1

    # ---- candidate: next request arrival ------------------------------
    pending = valid & (queue.arrival > t_r)
    ev = jnp.min(jnp.where(pending, queue.arrival, _BIG), axis=1)

    # ---- the drain side the scheduler holds until the next event ------
    # One application of the hysteresis reaches its fixed point under a
    # frozen arrived set (see `tick`); eligibility below must use that
    # settled side, and if settling changes the stored flag the dense
    # scan acts on it at t+1 — force a step there.
    nw = jnp.sum(arrived & is_wr, axis=1)                       # (C,)
    nr = jnp.sum(arrived & ~is_wr, axis=1)
    drain = jnp.where(banks.drain, nw > policy.drain_lo,
                      nw >= policy.drain_hi)
    drain = drain | ((nr == 0) & (nw > 0))
    ev = jnp.minimum(ev, jnp.where(drain != banks.drain, t + 1, _BIG))
    drain_c = drain[:, None]

    # ---- per-entry command readiness ----------------------------------
    # per-bank fields read per entry through the bank-match plane (`tick`)
    match = _match(queue.fbank, dram.banks_per_channel)         # (C, RB, Q)
    open_e = _gather(banks.open_row, match)
    row_hit = open_e == queue.row
    closed = open_e < 0
    side_ok = jnp.where(is_wr, drain_c, ~drain_c)

    # CAS: bank CAS timer + shared bus + write/read turnaround
    cas_ready = jnp.where(
        is_wr,
        jnp.maximum(_gather(banks.next_wr, match), banks.rtw_until[:, None]),
        jnp.maximum(_gather(banks.next_rd, match), banks.wtr_until[:, None]))
    cas_ready = jnp.maximum(cas_ready, banks.bus_free[:, None])
    ev = jnp.minimum(ev, jnp.min(jnp.where(
        arrived & row_hit & side_ok, cas_ready, _BIG), axis=1))

    # ACT: bank ACT timer + the rank's FAW sliding-window expiry
    faw_ready = jnp.repeat(banks.faw[:, :, 0] + dram.tFAW, nbanks,
                           axis=1)                              # (C, RB)
    act_ready = _gather(jnp.maximum(banks.next_act, faw_ready), match)
    ev = jnp.minimum(ev, jnp.min(jnp.where(
        arrived & closed & side_ok, act_ready, _BIG), axis=1))

    # PRE: row conflict with no pending same-side hits on the bank (a
    # bank with pending hits reads "never", like an ineligible entry)
    pend = arrived & row_hit & (is_wr == drain_c)
    hit_pend = _select(match, pend[:, None, :], axis=2)         # (C, RB)
    pre_ready = _gather(jnp.where(hit_pend, _BIG, banks.next_pre), match)
    ev = jnp.minimum(ev, jnp.min(jnp.where(
        arrived & ~closed & ~row_hit & side_ok, pre_ready, _BIG), axis=1))

    # ---- candidate: refresh deadlines ---------------------------------
    ev = jnp.minimum(ev, jnp.min(banks.next_ref, axis=1))

    return jnp.clip(ev, t + 1, end)
