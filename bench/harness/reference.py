"""The plain reference: the platform simulated one DRAM tick per step.

A stand-alone statement of the semantics the benchmark holds the
program to.  It imports nothing of the program and takes every fact
about the memory device and the frontend from the configuration file
(``platform`` group: sizes, timings, address map, refresh mode,
sockets), so a later change to the program cannot move it and a new
device is added by a configuration file alone.  It is deliberately the
simplest form of the simulation:

* one scan step per DRAM tick of every window (no event horizon, no
  knee router, no re-run of saturated points, no sharding);
* one device, ``jax.vmap`` over the points or applications of a call;
* only what the configurations use: the picosecond clock crossing,
  FR-FCFS with an open page and watermark write drain, the stride
  prefetcher, the PI-controlled immediate response, the MSHR closed
  loop, the Mess pace generator and the solo-trace replay frontend;
* ``address_map``: ``skylake_xor``, the DRAMDig fold of the 6-channel,
  2-rank, 16-bank DDR4 geometry (and no other), or ``xor_fold``, the
  generic fold for any geometry: with ``C`` channels, ``R`` ranks,
  ``B`` banks a rank, ``L`` lines a row and a ``2**k``-row bank, a line
  ``l`` goes to channel ``(l ^ l>>6 ^ l>>12 ^ l>>18) % C``, bank
  ``((l // C // L) ^ l>>13) % B``, rank ``(l>>8 ^ l>>17) % R`` and row
  ``(l>>9) & (2**k - 1)``;
* ``refresh``: ``all_bank`` closes the whole rank at its deadline and
  blocks it for ``tRFC``; ``same_bank`` (REFsb) closes and blocks only
  the rank's target bank, which starts at bank 0 and moves on by one,
  modulo ``banks_per_rank``, at each of the rank's deadlines
  (``tREFI`` is then the per-rank REFsb cadence);
* ``n_sockets``: ``n_cores`` counts the cores of every socket, all of
  them traffic cores but the last, the one chase probe they share.
  The controller queue holds ``queue_depth`` slots a socket.  With
  ``socket_channels`` ``partitioned`` a request's channel is folded
  into its socket's share of ``n_channels / n_sockets`` channels;
  ``interleaved`` leaves it as the address map gives it.

Departures from the program's own description: none in what is
simulated.  Requests enter the queues chase first, then in issue
order, then by core id, sorted on one integer key that holds the core
id in 6 bits, so ``n_cores`` is at most 64 (two sockets of 24 fit).

``fdt`` is the float type of every floating-point quantity the
simulation carries (latency sums, the PI estimate, the closed-loop
latency estimate and the views).  The configurations state float32;
the control runs the same code with bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_BIG = 1 << 28
VIEWS = ("sim_bw_gbs", "sim_lat_ns", "if_bw_gbs", "if_lat_ns",
         "app_bw_gbs", "app_lat_ns", "chase_lat_ns")
ADDRESS_MAPS = ("skylake_xor", "xor_fold")
REFRESH_MODES = ("all_bank", "same_bank")
SOCKET_CHANNELS = ("interleaved", "partitioned")
#: channels, ranks, banks a rank, lines a row and rows of the geometry
#: the Skylake fold was reverse-engineered on (DDR4-2666, 6 channels)
_DDR4 = (6, 2, 16, 128, 1 << 17)


@dataclasses.dataclass(frozen=True)
class Platform:
    """Every number the simulation needs, as the configuration states it."""

    windows: int
    warmup: int
    # CPU frontend (one Skylake socket)
    n_cores: int
    cpu_ps_per_clk: int
    window_cycles: int
    cache_path_cycles: int
    noc_req_cycles: int
    noc_resp_cycles: int
    l_ir_init_cycles: float
    pi_latency: bool
    prefetch: bool
    pf_shift: int
    cap_demand: int
    cap_pf: int
    backlog_max: int
    mshr_cap: int
    chase_region_bits: int
    pi_keep: float
    pi_blend: float
    n_sockets: int
    socket_channels: str
    # DRAM device and controller
    n_channels: int
    ranks: int
    banks_per_rank: int
    bank_groups: int
    rows_per_bank: int
    lines_per_row: int
    address_map: str
    refresh: str
    line_bytes: int
    dram_ps_per_clk: int
    queue_depth: int
    drain_hi: int
    drain_lo: int
    mc_extra_ticks: int
    tCL: int
    tRCD: int
    tRP: int
    tRAS: int
    tBL: int
    tCCD_S: int
    tCCD_L: int
    tWR: int
    tWTR_L: int
    tRTP: int
    tRRD_S: int
    tRRD_L: int
    tFAW: int
    tCWL: int
    tRTRS: int
    tREFI: int
    tRFC: int

    @classmethod
    def from_config(cls, config: dict) -> "Platform":
        p = dict(config["platform"])
        p["windows"], p["warmup"] = config["windows"], config["warmup"]
        names = {f.name for f in dataclasses.fields(cls)}
        missing = names - set(p)
        if missing:
            raise ValueError(f"configuration {config['name']!r} lacks "
                             f"platform keys {sorted(missing)}")
        return cls(**{k: p[k] for k in names})

    def __post_init__(self):
        def refuse(key, why):
            raise ValueError(f"platform key {key!r} = {getattr(self, key)!r}:"
                             f" {why}")

        for key, allowed in (("address_map", ADDRESS_MAPS),
                             ("refresh", REFRESH_MODES),
                             ("socket_channels", SOCKET_CHANNELS)):
            if getattr(self, key) not in allowed:
                refuse(key, f"not one of {list(allowed)}")
        if self.address_map == "skylake_xor" and self.geometry != _DDR4:
            refuse("address_map", "the Skylake fold is defined on the "
                   f"DDR4 geometry {_DDR4} (channels, ranks, banks, lines "
                   f"a row, rows) only, not on {self.geometry}")
        if self.rows_per_bank & (self.rows_per_bank - 1):
            refuse("rows_per_bank", "not a power of two")
        if not 1 <= self.n_sockets or self.n_cores % self.n_sockets:
            refuse("n_sockets", f"does not divide n_cores {self.n_cores}")
        if self.n_cores > 64:
            refuse("n_cores", "over 64, the admission key's core field")
        if (self.socket_channels == "partitioned"
                and self.n_channels % self.n_sockets):
            refuse("n_channels", f"not divisible by n_sockets "
                   f"{self.n_sockets} for partitioned channels")

    @property
    def n_traffic(self) -> int:
        return self.n_cores - 1

    @property
    def cand(self) -> int:
        return self.cap_demand + self.cap_pf

    @property
    def banks(self) -> int:
        return self.ranks * self.banks_per_rank

    @property
    def geometry(self) -> tuple:
        return (self.n_channels, self.ranks, self.banks_per_rank,
                self.lines_per_row, self.rows_per_bank)

    @property
    def ticks_per_window(self) -> int:
        """Picosecond clock crossing: DRAM ticks while dramPs < cpuPs."""
        return math.ceil(self.window_cycles * self.cpu_ps_per_clk
                         / self.dram_ps_per_clk)

    def cycle_to_tick(self, cycle):
        return ((cycle * self.cpu_ps_per_clk + self.dram_ps_per_clk - 1)
                // self.dram_ps_per_clk)


# ---- address maps -----------------------------------------------------

def _bit(x, i):
    return (x >> i) & 1


def decode(p: Platform, line):
    """(channel, rank, bank, row) of uint32 cache-line indices under the
    configuration's address map."""
    line = line.astype(jnp.uint32)
    if p.address_map == "xor_fold":
        return _xor_fold(p, line)
    mc = _bit(line, 0) ^ _bit(line, 6) ^ _bit(line, 11) ^ _bit(line, 17)
    ch3 = ((line >> 1) ^ (line >> 7) ^ (line >> 13) ^ (line >> 19)) % 3
    bank = (_bit(line, 2) ^ _bit(line, 12)) \
        | ((_bit(line, 3) ^ _bit(line, 14)) << 1) \
        | ((_bit(line, 4) ^ _bit(line, 15)) << 2) \
        | ((_bit(line, 5) ^ _bit(line, 16)) << 3)
    rank = _bit(line, 8) ^ _bit(line, 18)
    row = (line >> 9) & 0x1FFFF
    return ((mc * 3 + ch3).astype(jnp.int32), rank.astype(jnp.int32),
            bank.astype(jnp.int32), row.astype(jnp.int32))


def _xor_fold(p: Platform, line):
    """The generic fold: low and row bits hashed into channel, bank and
    rank, so a sequential stream scatters over all of them."""
    channel = (line ^ (line >> 6) ^ (line >> 12) ^ (line >> 18)) \
        % p.n_channels
    in_channel = line // p.n_channels
    bank = ((in_channel // p.lines_per_row) ^ (line >> 13)) \
        % p.banks_per_rank
    rank = ((line >> 8) ^ (line >> 17)) % p.ranks
    row = (line >> 9) & (p.rows_per_bank - 1)
    return (channel.astype(jnp.int32), rank.astype(jnp.int32),
            bank.astype(jnp.int32), row.astype(jnp.int32))


# ---- memory controller and device ---------------------------------------

class Queue(NamedTuple):
    valid: jnp.ndarray
    is_write: jnp.ndarray
    arrival: jnp.ndarray
    issue_cycle: jnp.ndarray
    fbank: jnp.ndarray
    row: jnp.ndarray
    is_chase: jnp.ndarray


class Banks(NamedTuple):
    open_row: jnp.ndarray
    next_act: jnp.ndarray
    next_rd: jnp.ndarray
    next_wr: jnp.ndarray
    next_pre: jnp.ndarray
    faw: jnp.ndarray
    next_ref: jnp.ndarray
    ref_bank: jnp.ndarray
    bus_free: jnp.ndarray
    wtr_until: jnp.ndarray
    rtw_until: jnp.ndarray
    last_rank: jnp.ndarray
    drain: jnp.ndarray


class Stats(NamedTuple):
    served_rd: jnp.ndarray
    served_wr: jnp.ndarray
    sum_rd_lat_ticks: jnp.ndarray
    sum_if_lat_ps: jnp.ndarray
    chase_rd: jnp.ndarray
    sum_chase_lat_ticks: jnp.ndarray


def _init_queue(p: Platform) -> Queue:
    z = jnp.zeros((p.n_channels, p.queue_depth * p.n_sockets), jnp.int32)
    return Queue(z, z, z, z, z, z - 1, z)


def _init_banks(p: Platform) -> Banks:
    C, R = p.n_channels, p.ranks
    zi = jnp.zeros((C, p.banks), jnp.int32)
    zc = jnp.zeros((C,), jnp.int32)
    return Banks(
        open_row=zi - 1, next_act=zi, next_rd=zi, next_wr=zi, next_pre=zi,
        faw=jnp.full((C, R, 4), -(1 << 20), jnp.int32),
        # refresh deadlines staggered across the ranks of a channel
        next_ref=(p.tREFI + jnp.arange(R, dtype=jnp.int32)[None, :]
                  * (p.tREFI // R) + jnp.zeros((C, R), jnp.int32)),
        ref_bank=jnp.zeros((C, R), jnp.int32),
        bus_free=zc, wtr_until=zc, rtw_until=zc, last_rank=zc,
        drain=jnp.zeros((C,), bool))


def _zero_stats(p: Platform, fdt) -> Stats:
    zi = jnp.zeros((p.n_channels,), jnp.int32)
    return Stats(zi, zi, zi, jnp.zeros((p.n_channels,), fdt), zi, zi)


def _tick(p: Platform, fdt, q: Queue, b: Banks, t, active):
    """One DRAM tick of every channel: refresh, drain, FR-FCFS, stats."""
    C, nb = p.n_channels, p.banks_per_rank
    cidx = np.arange(C, dtype=np.int32)
    bank_ids = np.arange(p.banks, dtype=np.int32)
    rank_of = bank_ids // nb
    grp_of = (bank_ids % nb) // (nb // p.bank_groups)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (C,))
    active = jnp.broadcast_to(jnp.asarray(active), (C,))
    t_r = t[:, None]

    def gather(field):
        return jnp.take_along_axis(field, q.fbank, axis=1)

    # refresh: close the rank (all-bank) or its target bank (same-bank)
    # and block it for tRFC
    ref_due = active[:, None] & (t_r >= b.next_ref)
    refmask = jnp.repeat(ref_due, nb, axis=1)
    ref_bank = b.ref_bank
    if p.refresh == "same_bank":
        refmask = refmask & (bank_ids % nb == jnp.repeat(ref_bank, nb,
                                                           axis=1))
        ref_bank = jnp.where(ref_due, (ref_bank + 1) % nb, ref_bank)
    open_row = jnp.where(refmask, -1, b.open_row)
    next_act = jnp.where(refmask, jnp.maximum(b.next_act, t_r + p.tRFC),
                         b.next_act)
    next_ref = jnp.where(ref_due, b.next_ref + p.tREFI, b.next_ref)

    # write-drain hysteresis on the requests that have arrived
    arrived = (q.valid == 1) & (q.arrival <= t_r)
    nw = jnp.sum(arrived & (q.is_write == 1), axis=1)
    nr = jnp.sum(arrived & (q.is_write == 0), axis=1)
    drain = jnp.where(b.drain, nw > p.drain_lo, nw >= p.drain_hi)
    drain = drain | ((nr == 0) & (nw > 0))

    open_e, nact_e = gather(open_row), gather(next_act)
    nrd_e, nwr_e, npre_e = (gather(b.next_rd), gather(b.next_wr),
                            gather(b.next_pre))
    rank_e = q.fbank // nb
    row_hit = open_e == q.row
    closed = open_e < 0
    is_wr = q.is_write == 1
    bus_ok = (t >= b.bus_free)[:, None]
    faw_ok = jnp.take_along_axis(t_r >= b.faw[:, :, 0] + p.tFAW, rank_e,
                                 axis=1)
    drain_c = drain[:, None]
    side_ok = jnp.where(is_wr, drain_c, ~drain_c)
    elig_rd = (arrived & ~is_wr & row_hit & (t_r >= nrd_e) & bus_ok
               & (t >= b.wtr_until)[:, None] & ~drain_c)
    elig_wr = (arrived & is_wr & row_hit & (t_r >= nwr_e) & bus_ok
               & (t >= b.rtw_until)[:, None] & drain_c)
    elig_act = arrived & closed & (t_r >= nact_e) & faw_ok & side_ok
    # no precharge of a row that still has hits pending on the active side
    hit_pend = jnp.zeros((C, p.banks), bool).at[cidx[:, None], q.fbank].max(
        arrived & row_hit & (is_wr == drain_c))
    elig_pre = (arrived & ~closed & ~row_hit & (t_r >= npre_e)
                & ~gather(hit_pend) & side_ok)

    # FR-FCFS: CAS over ACT over PRE, oldest first
    age = _BIG - q.arrival
    score = jnp.where(elig_rd | elig_wr, 3 * _BIG + age,
                      jnp.where(elig_act, 2 * _BIG + age,
                                jnp.where(elig_pre, _BIG + age, 0)))
    score = jnp.where(active[:, None], score, 0)
    sel = jnp.argmax(score, axis=1)
    any_cmd = jnp.take_along_axis(score, sel[:, None], 1)[:, 0] > 0

    def pick(field):
        return jnp.take_along_axis(field, sel[:, None], 1)[:, 0]

    s_fb, s_row, s_arr, s_issue = pick(q.fbank), pick(q.row), \
        pick(q.arrival), pick(q.issue_cycle)
    s_rank = s_fb // nb
    s_bg = (s_fb % nb) // (nb // p.bank_groups)
    s_iswr = pick(is_wr.astype(jnp.int32)) == 1
    s_chase = pick(q.is_chase) == 1
    s_cas = any_cmd & ((pick(elig_rd.astype(jnp.int32)) == 1)
                       | (pick(elig_wr.astype(jnp.int32)) == 1))
    s_act = any_cmd & (pick(elig_act.astype(jnp.int32)) == 1) & ~s_cas
    s_pre = any_cmd & (pick(elig_pre.astype(jnp.int32)) == 1) & ~s_cas \
        & ~s_act
    s_rd = s_cas & ~s_iswr
    s_wr = s_cas & s_iswr

    bsel = (cidx, s_fb)
    same_rank = rank_of[None, :] == s_rank[:, None]
    same_grp = (grp_of[None, :] == s_bg[:, None]) & same_rank
    # ACT
    open_row = open_row.at[bsel].set(jnp.where(s_act, s_row, open_row[bsel]))
    nact = jnp.where(s_act[:, None] & same_rank,
                     jnp.maximum(next_act, t_r + p.tRRD_S), next_act)
    nact = jnp.where(s_act[:, None] & same_grp,
                     jnp.maximum(nact, t_r + p.tRRD_L), nact)
    nact = nact.at[bsel].set(jnp.where(
        s_act, jnp.maximum(nact[bsel], t + p.tRAS + p.tRP), nact[bsel]))
    nrd = b.next_rd.at[bsel].set(jnp.where(s_act, t + p.tRCD,
                                           b.next_rd[bsel]))
    nwr = b.next_wr.at[bsel].set(jnp.where(s_act, t + p.tRCD,
                                           b.next_wr[bsel]))
    npre = b.next_pre.at[bsel].set(jnp.where(s_act, t + p.tRAS,
                                             b.next_pre[bsel]))
    faw_new = jnp.concatenate(
        [b.faw[:, :, 1:],
         jnp.broadcast_to(t[:, None, None], b.faw[:, :, :1].shape)], axis=2)
    act_rank = jax.nn.one_hot(s_rank, p.ranks, dtype=bool) & s_act[:, None]
    faw = jnp.where(act_rank[:, :, None], faw_new, b.faw)
    # CAS: data bus, tCCD by bank group, read/write turnaround
    burst = p.tBL + jnp.where(s_rank != b.last_rank, p.tRTRS, 0)
    bus_free = jnp.where(s_cas, t + burst, b.bus_free)
    last_rank = jnp.where(s_cas, s_rank, b.last_rank)
    ccd = jnp.where(same_grp, p.tCCD_L, p.tCCD_S)
    nrd = jnp.where(s_cas[:, None], jnp.maximum(nrd, t_r + ccd), nrd)
    nwr = jnp.where(s_cas[:, None], jnp.maximum(nwr, t_r + ccd), nwr)
    npre = npre.at[bsel].set(jnp.where(
        s_rd, jnp.maximum(npre[bsel], t + p.tRTP),
        jnp.where(s_wr, jnp.maximum(npre[bsel], t + p.tCWL + p.tBL + p.tWR),
                  npre[bsel])))
    wtr_until = jnp.where(s_wr, t + p.tCWL + p.tBL + p.tWTR_L, b.wtr_until)
    rtw_until = jnp.where(s_rd, t + p.tCL + p.tBL + p.tRTRS - p.tCWL,
                          b.rtw_until)
    # PRE
    open_row = open_row.at[bsel].set(jnp.where(s_pre, -1, open_row[bsel]))
    nact = nact.at[bsel].set(jnp.where(
        s_pre, jnp.maximum(nact[bsel], t + p.tRP), nact[bsel]))

    b = Banks(open_row, nact, nrd, nwr, npre, faw, next_ref, ref_bank,
              bus_free, wtr_until, rtw_until, last_rank, drain)
    served = jnp.zeros_like(q.valid).at[cidx, sel].set(
        s_cas.astype(jnp.int32))
    q = q._replace(valid=q.valid & (1 - served))

    done_t = t + p.tCL + p.tBL + p.mc_extra_ticks
    rd_lat = done_t - s_arr
    if_lat = (done_t * p.dram_ps_per_clk
              - s_issue * p.cpu_ps_per_clk).astype(fdt)
    stats = Stats(
        served_rd=s_rd.astype(jnp.int32), served_wr=s_wr.astype(jnp.int32),
        sum_rd_lat_ticks=jnp.where(s_rd, rd_lat, 0),
        sum_if_lat_ps=jnp.where(s_rd, if_lat, jnp.zeros((), fdt)),
        chase_rd=(s_rd & s_chase).astype(jnp.int32),
        sum_chase_lat_ticks=jnp.where(s_rd & s_chase, rd_lat, 0))
    return q, b, stats


# ---- bound phase: candidates, injection, frontends ----------------------

class Cand(NamedTuple):
    valid: jnp.ndarray
    line: jnp.ndarray
    is_write: jnp.ndarray
    issue_cycle: jnp.ndarray
    is_chase: jnp.ndarray
    is_pf: jnp.ndarray


def _lcg(x):
    return x.astype(jnp.uint32) * jnp.uint32(2654435761) \
        + jnp.uint32(0x9E3779B9)


def _segment_line(core, k):
    """Mess traffic: 64-line sequential segments at hashed bases."""
    seg = (k >> 6).astype(jnp.uint32)
    h = _lcg(seg * jnp.uint32(31) + core.astype(jnp.uint32) * jnp.uint32(97))
    return ((core.astype(jnp.uint32) << 22)
            | ((h & jnp.uint32(0xFFFF)) << 6)
            | (k.astype(jnp.uint32) & 63))


def _chase(p: Platform, seq, carry, l_ir_cycles):
    """The pointer-chase probe: one window of serialized loads."""
    j = jnp.arange(p.cand, dtype=jnp.int32)
    iter_cycles = jnp.maximum(p.cache_path_cycles + p.noc_req_cycles
                              + p.noc_resp_cycles + l_ir_cycles, 1)
    budget = p.window_cycles + carry
    iters = jnp.minimum(p.cand, budget // iter_cycles)
    line = (jnp.uint32(1) << 31) | (_lcg(_lcg((seq + j).astype(jnp.uint32)))
                                    >> (32 - p.chase_region_bits))
    return (j < iters, line, j * iter_cycles, iters,
            budget - iters * iter_cycles, iter_cycles)


class MessCores(NamedTuple):
    seq: jnp.ndarray
    backlog: jnp.ndarray
    chase_carry: jnp.ndarray


class Mess:
    """The Mess pace generator: ``pace`` demands a core, ``wr``/64 writes."""

    def __init__(self, p: Platform, pace, wr):
        self.p, self.pace, self.wr = p, pace, wr

    def init(self):
        z = jnp.zeros((self.p.n_cores,), jnp.int32)
        return MessCores(z, z, jnp.zeros((), jnp.int32))

    def bound(self, s: MessCores, l_ir_cycles, budget):
        p = self.p
        wc = p.window_cycles
        cid = jnp.arange(p.n_cores, dtype=jnp.int32)[:, None]
        j = jnp.arange(p.cand, dtype=jnp.int32)[None, :]
        is_traffic = cid < p.n_traffic
        want = self.pace + s.backlog
        quota = jnp.minimum(jnp.minimum(p.cap_demand, want),
                            budget)[..., None]
        k = s.seq[:, None] + j
        t_valid = is_traffic & (j < quota)
        t_line = _segment_line(cid, k)
        t_write = ((k + 1) * self.wr) // 64 - (k * self.wr) // 64 > 0
        t_issue = j * wc // jnp.maximum(quota, 1)
        pf_valid = jnp.zeros_like(t_valid)
        if p.prefetch:
            # stride prefetcher: overfetch past the demand quota
            pf_quota = jnp.minimum(p.cap_pf,
                                   quota[..., 0] >> p.pf_shift)[:, None]
            jp = j - p.cap_demand
            pf_valid = is_traffic & (jp >= 0) & (jp < pf_quota)
            t_valid = t_valid | pf_valid
            t_line = jnp.where(pf_valid, _segment_line(
                cid, s.seq[:, None] + quota + jp), t_line)
            t_write = t_write & ~pf_valid
            t_issue = jnp.where(pf_valid,
                                jp * wc // jnp.maximum(pf_quota, 1), t_issue)
        cv, c_line, c_issue, iters, c_carry, _ = _chase(
            p, s.seq[p.n_cores - 1], s.chase_carry, l_ir_cycles)
        c_valid = (cid == p.n_cores - 1) & cv[None, :]
        cand = Cand(
            valid=(t_valid & is_traffic) | c_valid,
            line=jnp.where(is_traffic, t_line, c_line),
            is_write=jnp.where(is_traffic, t_write, False),
            issue_cycle=jnp.where(is_traffic, t_issue,
                                  c_issue).astype(jnp.int32),
            is_chase=c_valid, is_pf=pf_valid & is_traffic)
        return cand, (quota[..., 0], want, iters, c_carry)

    def update(self, s: MessCores, aux, acc_demand):
        quota, want, iters, c_carry = aux
        p = self.p
        traffic = jnp.arange(p.n_cores) < p.n_traffic
        demanded = jnp.where(traffic, want, 0)
        backlog = jnp.clip(demanded - jnp.minimum(acc_demand, demanded),
                           0, p.backlog_max)
        seq = s.seq + jnp.where(traffic, quota, iters).astype(jnp.int32)
        return MessCores(seq, backlog, c_carry)

    def progress(self, s):
        return jnp.zeros((), jnp.int32)


class TraceCores(NamedTuple):
    pos: jnp.ndarray
    line_cum: jnp.ndarray
    carry: jnp.ndarray
    chase_seq: jnp.ndarray
    chase_carry: jnp.ndarray


class Replay:
    """One application's trace, sharded over every traffic core."""

    def __init__(self, p: Platform, delta, is_write, dep, length, foot):
        self.p = p
        self.delta, self.is_write, self.dep = delta, is_write, dep
        self.length, self.foot = length, foot

    def init(self):
        z = jnp.zeros((self.p.n_cores,), jnp.int32)
        zs = jnp.zeros((), jnp.int32)
        return TraceCores(z, z, z, zs, zs)

    def bound(self, s: TraceCores, l_ir_cycles, budget):
        p = self.p
        wc, cap = p.window_cycles, p.cap_demand
        cid = jnp.arange(p.n_cores, dtype=jnp.int32)[:, None]
        jj = jnp.arange(cap, dtype=jnp.int32)[None, :]
        is_traffic = cid < p.n_traffic
        target = jnp.where(cid[:, 0] < p.n_traffic, self.length, 0)
        pos = jnp.minimum(s.pos, self.delta.shape[-1] - cap)
        take_at = jax.vmap(
            lambda a, i: jax.lax.dynamic_slice(a, (i,), (cap,)),
            in_axes=(None, 0))
        delta, is_wr, dep = (take_at(self.delta, pos),
                             take_at(self.is_write, pos),
                             take_at(self.dep, pos))
        in_range = pos[:, None] + jj < target[:, None]
        cv, c_line, c_issue, iters, c_carry, iter_cycles = _chase(
            p, s.chase_seq, s.chase_carry, l_ir_cycles)
        c_valid = (cid == p.n_cores - 1) & cv[None, :]
        # dependent accesses wait a load-to-use, independents the
        # closed-loop issue interval
        ind_cycles = jnp.maximum(wc // jnp.maximum(budget, 1), 1)
        cost = jnp.where(dep == 1, iter_cycles, ind_cycles)
        fin = jnp.cumsum(cost, axis=1)
        avail = (wc + s.carry)[:, None]
        take = in_range & (fin <= avail)
        n_take = jnp.sum(take.astype(jnp.int32), axis=1)
        used = jnp.sum(jnp.where(take, cost, 0), axis=1)
        new_carry = jnp.clip(jnp.where(jnp.any(in_range, axis=1),
                                       avail[:, 0] - used, 0), 0, wc)
        foot = jnp.broadcast_to(self.foot, (p.n_cores,))
        cum = s.line_cum[:, None] + jnp.cumsum(delta, axis=1)
        phase = (cid[:, 0].astype(jnp.uint32) * jnp.uint32(2654435761)
                 % jnp.maximum(foot, 1).astype(jnp.uint32)).astype(jnp.int32)
        idx = jnp.remainder(cum + phase[:, None],
                            jnp.maximum(foot, 1)[:, None])
        t_line = (cid[:, 0] * self.foot).astype(jnp.uint32)[:, None] \
            + idx.astype(jnp.uint32)
        pad = p.cand - cap

        def pad2(a, v):
            return jnp.pad(a, ((0, 0), (0, pad)), constant_values=v)

        cand = Cand(
            valid=pad2(is_traffic & take, False) | c_valid,
            line=jnp.where(is_traffic, pad2(t_line, 0), c_line),
            is_write=jnp.where(is_traffic, pad2(is_wr, 0) == 1, False),
            issue_cycle=jnp.where(is_traffic,
                                  pad2(jnp.minimum(fin - cost, wc - 1), 0),
                                  c_issue).astype(jnp.int32),
            is_chase=c_valid, is_pf=jnp.zeros((p.n_cores, p.cand), bool))
        aux = (n_take, new_carry,
               s.line_cum + jnp.sum(jnp.where(take, delta, 0), axis=1),
               iters, c_carry)
        return cand, aux

    def update(self, s: TraceCores, aux, acc_demand):
        n_take, new_carry, line_cum, iters, c_carry = aux
        return TraceCores(s.pos + n_take, line_cum, new_carry,
                          s.chase_seq + iters, c_carry)

    def progress(self, s):
        return s.pos


def _inject(p: Platform, q: Queue, cand: Cand, w):
    """Admit candidates into the channel queues: chase first, then issue
    order, then core id; a full queue drops the rest."""
    C, Q = q.valid.shape
    n = p.n_cores * p.cand
    flat = jax.tree_util.tree_map(lambda a: a.reshape(n), cand)
    core_of = jnp.repeat(jnp.arange(p.n_cores, dtype=jnp.int32), p.cand)
    channel, rank, bank, row = decode(p, flat.line)
    if p.socket_channels == "partitioned":
        share = C // p.n_sockets
        socket = core_of // (p.n_cores // p.n_sockets)
        channel = socket * share + channel % share
    ch = jnp.where(flat.valid, channel, C)
    key = ((1 - flat.is_chase.astype(jnp.int32)) * (1 << 24)
           + flat.issue_cycle * 64 + core_of)
    order = jnp.argsort(ch * (1 << 26) + key)
    ch_s = ch[order]
    counts = jnp.bincount(ch_s, length=C + 1)
    start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    r = jnp.arange(n, dtype=jnp.int32) - start[ch_s]
    free_order = jnp.argsort(q.valid, axis=1, stable=True)
    n_free = Q - jnp.sum(q.valid, axis=1)
    ch_c = jnp.minimum(ch_s, C - 1)
    accepted = (ch_s < C) & (r < n_free[ch_c])
    slot = jnp.where(accepted, free_order[ch_c, jnp.minimum(r, Q - 1)], Q)
    issue_abs = w * p.window_cycles + flat.issue_cycle[order]
    arrival = p.cycle_to_tick(issue_abs + p.cache_path_cycles
                              + p.noc_req_cycles)

    def put(field, val):
        return field.at[ch_c, slot].set(
            jnp.where(accepted, val, field[ch_c, jnp.minimum(slot, Q - 1)]),
            mode="drop")

    q = Queue(
        valid=put(q.valid, jnp.ones_like(ch_c)),
        is_write=put(q.is_write, flat.is_write[order].astype(jnp.int32)),
        arrival=put(q.arrival, arrival.astype(jnp.int32)),
        issue_cycle=put(q.issue_cycle, issue_abs.astype(jnp.int32)),
        fbank=put(q.fbank, (rank * p.banks_per_rank + bank)[order]),
        row=put(q.row, row[order]),
        is_chase=put(q.is_chase, flat.is_chase[order].astype(jnp.int32)))
    acc = jnp.zeros(p.n_cores, jnp.int32).at[core_of[order]].add(
        (accepted & ~flat.is_pf[order]).astype(jnp.int32))
    return q, acc, jnp.sum(accepted.astype(jnp.int32))


# ---- the platform: windows of bound phase + dense weave ----------------

def simulate(p: Platform, frontend, fdt=jnp.float32):
    """One point or application: ``(views dict, per-window progress)``."""
    T = p.ticks_per_window
    cpu_ps, dram_ps = p.cpu_ps_per_clk, p.dram_ps_per_clk
    window_ps = p.window_cycles * cpu_ps
    noc_rt = p.noc_req_cycles + p.noc_resp_cycles

    def window(carry, w):
        q, b, fs, l_ir, lat_est = carry
        l_ir_cycles = jnp.maximum(jnp.round(l_ir).astype(jnp.int32), 1)
        budget = jnp.maximum(p.mshr_cap * window_ps
                             / jnp.maximum(lat_est, 1.0), 1.0
                             ).astype(jnp.int32)
        cand, aux = frontend.bound(fs, l_ir_cycles, budget)
        q, acc, injected = _inject(p, q, cand, w)
        fs = frontend.update(fs, aux, acc)
        start = p.cycle_to_tick(w * p.window_cycles)
        end = p.cycle_to_tick((w + 1) * p.window_cycles)

        def tick(c, i):
            q, b, acc = c
            t = start + i
            q, b, s = _tick(p, fdt, q, b, t, t < end)
            return (q, b, jax.tree_util.tree_map(jnp.add, acc, s)), None

        (q, b, st), _ = jax.lax.scan(tick, (q, b, _zero_stats(p, fdt)),
                                     jnp.arange(T, dtype=jnp.int32))
        n_rd = jnp.sum(st.served_rd)
        sum_if = jnp.sum(st.sum_if_lat_ps)
        lat_w = (jnp.sum(st.sum_rd_lat_ticks) / jnp.maximum(n_rd, 1)
                 * dram_ps + p.cache_path_cycles * cpu_ps).astype(fdt)
        lat_est = jnp.where(n_rd > 0, 0.5 * lat_est + 0.5 * lat_w, lat_est)
        avg_if = sum_if / (cpu_ps * jnp.maximum(n_rd, 1))
        l_ir_next = jnp.where(jnp.logical_and(p.pi_latency, n_rd > 0),
                              p.pi_keep * l_ir + p.pi_blend * avg_if, l_ir)
        out = dict(
            served_rd=n_rd, served_wr=jnp.sum(st.served_wr),
            sum_rd_lat_ticks=jnp.sum(st.sum_rd_lat_ticks),
            sum_if_lat_ps=sum_if, chase_rd=jnp.sum(st.chase_rd),
            sum_chase_lat_ticks=jnp.sum(st.sum_chase_lat_ticks),
            app_lat_cycles=(p.cache_path_cycles + noc_rt
                            + l_ir_cycles).astype(fdt),
            ticks=end - start, progress=frontend.progress(fs))
        return (q, b, fs, l_ir_next, lat_est), out

    lat0 = (p.cache_path_cycles * cpu_ps + (p.tCL + p.tBL) * dram_ps)
    carry0 = (_init_queue(p), _init_banks(p), frontend.init(),
              jnp.asarray(p.l_ir_init_cycles, fdt), jnp.asarray(lat0, fdt))
    _, o = jax.lax.scan(window, carry0,
                        jnp.arange(p.windows, dtype=jnp.int32))

    keep = jnp.arange(p.windows) >= p.warmup

    def ksum(x):
        return jnp.sum(jnp.where(keep, x, 0))

    n_rd, n_wr = ksum(o["served_rd"]), ksum(o["served_wr"])
    nz = jnp.maximum(n_rd, 1).astype(fdt)
    bytes_served = (n_rd + n_wr).astype(fdt) * p.line_bytes
    cpu_span = (jnp.sum(keep) * p.window_cycles * cpu_ps).astype(fdt)
    sim_span = ksum(o["ticks"]).astype(fdt) * dram_ps
    views = dict(
        sim_bw_gbs=bytes_served / sim_span * 1e3,
        sim_lat_ns=ksum(o["sum_rd_lat_ticks"]).astype(fdt)
        * (dram_ps * 1e-3) / nz,
        if_bw_gbs=bytes_served / cpu_span * 1e3,
        if_lat_ns=ksum(o["sum_if_lat_ps"]) * 1e-3 / nz,
        app_bw_gbs=bytes_served / cpu_span * 1e3,
        app_lat_ns=jnp.sum(jnp.where(keep, o["app_lat_cycles"], 0.0))
        / jnp.maximum(jnp.sum(keep), 1) * (cpu_ps * 1e-3),
        chase_lat_ns=ksum(o["sum_chase_lat_ticks"]).astype(fdt)
        * (dram_ps * 1e-3)
        / jnp.maximum(ksum(o["chase_rd"]), 1).astype(fdt),
        n_rd=n_rd, n_wr=n_wr)
    return views, o["progress"]


@functools.lru_cache(maxsize=None)
def _mess_fn(p: Platform, fdt):
    def one(pace, wr):
        return simulate(p, Mess(p, pace, wr), fdt)[0]
    return jax.jit(jax.vmap(one))


@functools.lru_cache(maxsize=None)
def _replay_fn(p: Platform, fdt):
    def one(delta, is_write, dep, length, foot):
        return simulate(p, Replay(p, delta, is_write, dep, length, foot),
                        fdt)
    return jax.jit(jax.vmap(one))


def mess(p: Platform, paces, write_mix: int, fdt=jnp.float32) -> dict:
    """Views of each pace at one write mix; host numpy, one row a pace."""
    pace = jnp.asarray(paces, jnp.int32)
    out = _mess_fn(p, fdt)(pace, jnp.full_like(pace, write_mix))
    return {k: np.asarray(jax.device_get(v)) for k, v in out.items()}


def runtime_windows(progress, length):
    """Window at which every traffic core finished its stream, 1-based;
    extrapolated from the final replay rate when the run ends first."""
    W = progress.shape[-2]
    done = progress >= length[:, None, None]
    any_done = done.any(axis=-2)
    first = np.where(any_done, done.argmax(axis=-2) + 1, W)
    est = W * length[:, None] / np.maximum(progress[:, -1, :], 1)
    rt = np.where(any_done, first, est).astype(np.float64)
    return rt.max(axis=1), any_done.all(axis=1)


def replay(p: Platform, apps, fdt=jnp.float32) -> dict:
    """Views, runtimes and completion of each application trace.

    ``apps`` is a list of ``(delta, is_write, dep, footprint_lines)``
    numpy arrays of one length; each is padded by one bound-phase slice.
    """
    pad = p.cap_demand
    delta = np.stack([np.pad(np.asarray(a[0], np.int32), (0, pad))
                      for a in apps])
    is_write = np.stack([np.pad(np.asarray(a[1], np.int32), (0, pad))
                         for a in apps])
    dep = np.stack([np.pad(np.asarray(a[2], np.int32), (0, pad))
                    for a in apps])
    length = np.array([len(a[0]) for a in apps], np.int32)
    foot = np.array([a[3] for a in apps], np.int32)
    views, progress = _replay_fn(p, fdt)(delta, is_write, dep, length, foot)
    out = {k: np.asarray(jax.device_get(v)) for k, v in views.items()}
    progress = np.asarray(jax.device_get(progress))[:, :, :p.n_traffic]
    out["runtime_windows"], out["done"] = runtime_windows(progress, length)
    return out
