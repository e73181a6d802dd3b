"""The integrated simulation platform: bound/weave windows + interface.

This is the JAX equivalent of ZSim (event-based CPU frontend) connected
to a cycle-accurate memory simulator through the CPU-memory interface —
the structure of Fig. 1.  One `run_point` simulates the platform for a
fixed number of 1000-cycle ZSim windows at one Mess operating point
(pace, read/write mix) and returns the three memory-performance views.

Per window:

1. **Bound phase** (`workload.generate`): every core's memory requests
   are generated against the *immediate-response* latency.  In the
   DAMOV baseline this latency is one CPU cycle; with the paper's
   correction it is the PI-controlled estimate (Sec. 3.4).
2. **Interface** (`workload.inject_queue` + `clocking`): requests cross the
   CPU->memory clock domain under the selected clocking model
   (broken / integer-ratio / picosecond).
3. **Weave phase** (`dram.tick` scan): the cycle-accurate backend
   processes the window's DRAM ticks; completion statistics feed the
   memory-simulator and interface views.
4. **PI update**: the immediate-response latency for the next window is
   0.95*previous + 0.05*(average weave latency) — paper Sec. 3.4.

The decoupling bug is inherent to the structure (as in ZSim): the app
view's load-to-use latency is `cache_path + immediate_response`, fixed
at bound-phase time, regardless of what the weave phase later computes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dram, workload
from repro.core.clocking import ClockModel, make_clock
from repro.core.dram import SchedulerPolicy
from repro.core.noc import NocModel, make_noc
from repro.core.timing import PlatformParams, DEFAULT_PLATFORM
from repro.core.workload import WorkloadConfig
from repro.obs import spans

PI_KEEP = 0.95       # paper: 95% previous estimate
PI_BLEND = 0.05      # paper: 5% new cycle-accurate average


@dataclasses.dataclass(frozen=True)
class StageConfig:
    """Full static configuration of one simulation stage.

    Every field is static (hashable): one `StageConfig` = one XLA
    program shape.  ``platform`` carries the CPU params and the memory
    device (`DramParams` — the DDR4-2666 default or any preset from
    `repro.core.presets`); ``l_ir_init_cycles`` is in CPU cycles,
    ``windows``/``warmup`` count 1000-cycle ZSim windows.
    """

    name: str = "01-baseline"
    clock_mode: str = "broken_noscale"
    mapping: str = "simple"
    pi_latency: bool = False          # stage 04 model correction
    noc: str = "fixed"                # stage 06
    prefetch: bool = False            # stage 07
    policy: SchedulerPolicy = dataclasses.field(default_factory=SchedulerPolicy)
    l_ir_init_cycles: float = 1.0     # DAMOV immediate-response latency
    windows: int = 96
    warmup: int = 32
    #: weave engine: ``"event"`` (default) scans a static *event budget*
    #: per window, jumping straight to the next tick where eligibility
    #: can change (`dram.next_event`) — bit-identical to ``"dense"``,
    #: the reference one-tick-per-step scan, as long as the budget
    #: covers the window's events (saturation is reported in the
    #: ``weave_sat`` view, never silent).
    weave: str = "event"
    #: event-scan steps per window; 0 derives the budget from bus
    #: occupancy (`clocking.event_budget`).
    weave_events: int = 0
    #: traffic sockets: each adds 24 frontend cores (one shared chase
    #: probe overall).  2 sockets double the frontend issue capacity —
    #: required to drive HBM2e past the single-socket ~200 GB/s ceiling.
    n_sockets: int = 1
    #: multi-socket channel ownership: "interleaved" (all sockets hit
    #: all channels) or "partitioned" (n_channels/n_sockets per socket).
    socket_channels: str = "interleaved"
    #: three-perspective telemetry (`repro.obs`): when True, the weave
    #: loop accumulates per-channel command-mix counter planes and
    #: log2 latency histograms (`dram.TickTele`) and the window step
    #: samples interface-view series (queue depth, MSHR budget, PI
    #: estimate), all emitted as ``tele_*`` keys in the views.  Static
    #: flag, off by default: the False path traces the flags-off graph,
    #: which the flag's code does not alter, so all outputs stay
    #: bit-identical and free when off.
    telemetry: bool = False
    #: command-stream recorder (`repro.oracle`): when True, every weave
    #: step also emits the granted DRAM command (`dram.TickCmd` — code,
    #: grant tick, bank, row, refresh firings) as ``cmd_*`` keys in the
    #: views, ready for `repro.oracle.extract_stream` and the protocol-
    #: legality checker.  Static flag like ``telemetry``: the False
    #: path traces the flags-off graph unaltered, and because both weave
    #: engines evaluate exactly the grant ticks, the recorded streams
    #: are engine-invariant.
    cmd_trace: bool = False
    platform: PlatformParams = dataclasses.field(
        default_factory=lambda: DEFAULT_PLATFORM)

    def __post_init__(self):
        if self.weave not in ("dense", "event"):
            raise ValueError(
                f"weave must be 'dense' or 'event', got {self.weave!r}")

    def clock(self) -> ClockModel:
        return make_clock(self.clock_mode, self.platform)

    def event_budget(self) -> int:
        """Event-scan steps per window (override or clock-derived)."""
        return self.weave_events or self.clock().events_per_window_static

    def scan_steps(self) -> int:
        """Weave scan steps per window of this stage's engine."""
        if self.weave == "event":
            return self.event_budget()
        return self.clock().ticks_per_window_static

    def noc_model(self) -> NocModel:
        return make_noc(self.noc)

    def workload_config(self) -> WorkloadConfig:
        n = self.noc_model()
        return WorkloadConfig(
            mapping=self.mapping, prefetch=self.prefetch,
            cache_path_cycles=self.platform.cpu.cache_path_cycles,
            noc_req_cycles=n.req_cycles, noc_resp_cycles=n.resp_cycles,
            dram=self.platform.dram, n_sockets=self.n_sockets,
            socket_channels=self.socket_channels)


class WindowOut(NamedTuple):
    served_rd: jnp.ndarray
    served_wr: jnp.ndarray
    sum_rd_lat_ticks: jnp.ndarray
    sum_if_lat_ps: jnp.ndarray
    chase_rd: jnp.ndarray
    sum_chase_lat_ticks: jnp.ndarray
    app_lat_cycles: jnp.ndarray     # bound-phase load-to-use (app view)
    l_ir: jnp.ndarray
    injected: jnp.ndarray
    ticks: jnp.ndarray
    progress: jnp.ndarray           # frontend progress marker (traces)


def _window_step(cfg: StageConfig, clock: ClockModel, wcfg: WorkloadConfig,
                 frontend, carry, w):
    queue, banks, fstate, l_ir, lat_est, tstate = carry
    cpu = cfg.platform.cpu
    l_ir_cycles = jnp.maximum(jnp.round(l_ir).astype(jnp.int32), 1)
    window_ps = cpu.window_cycles * cpu.cpu_ps_per_clk

    # bound phase + interface hand-off (MSHR closed-loop budget).  The
    # named scopes here and below only label the ops in a profile.
    with jax.named_scope("bound"):
        budget = workload.littles_law_budget(lat_est, window_ps)
        cand, aux = frontend.bound(fstate, l_ir_cycles, budget,
                                   cpu.window_cycles)
    with jax.named_scope("inject"):
        queue, acc_demand, injected = workload.inject_queue(
            queue, cand, clock, w, wcfg)
        fstate = frontend.update(fstate, aux, acc_demand)
    if cfg.telemetry:
        # interface-view series: per-channel queue depth right after
        # this window's injection (window boundaries are engine-
        # invariant, so the sample is identical under dense and event)
        inject_depth = jnp.sum(queue.valid, axis=1)

    # weave phase: cycle-accurate DRAM simulation of this window's ticks
    start = clock.window_start_tick(w)
    end = clock.window_end_tick(w)
    planes = dram.bank_planes(cfg.platform.dram)
    tick_fn = functools.partial(
        dram.tick, dram=cfg.platform.dram, policy=cfg.policy,
        tick2cpu_num=clock.tick_to_cpu_ps_num,
        tick2cpu_den=clock.tick_to_cpu_ps_den,
        cpu_ps_per_clk=cpu.cpu_ps_per_clk, planes=planes,
        telemetry=cfg.telemetry, cmd_trace=cfg.cmd_trace)

    # Stats accumulate (C,)-per-channel in the scan *carry*, in time
    # order per channel — idle ticks add exact zeros (the float32
    # identity), so window totals are bit-identical across engines.
    # With telemetry on, the integer `TickTele` planes accumulate in
    # the same carry (ints commute, so the planes are engine-exact).
    acc0 = dram.zero_stats(cfg.platform.dram)
    tacc0 = dram.zero_tele(cfg.platform.dram) if cfg.telemetry else None
    tree_add = functools.partial(jax.tree_util.tree_map, jnp.add)

    # Both scan bodies below are written once for all four flag
    # combinations: `None` is an *empty* pytree node, so a disabled
    # flag's carry slot / ys slot contributes no leaves and the traced
    # graph is exactly the flags-off one.
    def split_extras(rest):
        """Unpack `dram.tick`'s flag-dependent return tail."""
        ti = ts = cmd = None
        if cfg.telemetry:
            ti, ts, rest = rest[0], rest[1], rest[2:]
        if cfg.cmd_trace:
            cmd = rest[0]
        return ti, ts, cmd

    if cfg.weave == "dense":
        # reference engine: one scan step per DRAM tick
        def body(qba, i):
            q, b, acc, tacc, ts = qba
            t = start + i
            with jax.named_scope("tick"):
                q, b, s, *rest = tick_fn(q, b, t, active=t < end, tele=ts)
            ti, ts, cmd = split_extras(rest)
            return (q, b, tree_add(acc, s), tree_add(tacc, ti), ts), cmd

        with jax.named_scope("weave"):
            (queue, banks, st, tacc, tstate), cmds = jax.lax.scan(
                body, (queue, banks, acc0, tacc0, tstate),
                jnp.arange(clock.ticks_per_window_static, dtype=jnp.int32))
        weave_events = end - start
        weave_sat = jnp.zeros((), bool)
    else:
        # event-horizon engine: each step jumps every channel to its
        # own next tick where eligibility can change (`dram.next_event`
        # is per-channel-exact; `dram.tick` couples channels only
        # through the stats reduction) and applies `tick` there.  A
        # channel whose events are exhausted (tn == horizon) parks at
        # horizon-1 with `active=False`, which freezes its state just
        # like the dense scan's inactive tail ticks.
        horizon = start + clock.ticks_per_window_static
        nev_fn = functools.partial(
            dram.next_event, dram=cfg.platform.dram, policy=cfg.policy)
        t0 = jnp.full((cfg.platform.dram.n_channels,), 1, jnp.int32)

        def ebody(qbta, i):
            q, b, t, acc, tacc, ts = qbta
            with jax.named_scope("next_event"):
                tn = nev_fn(q, b, t, horizon)       # (C,)
            live = tn < horizon
            tau = jnp.minimum(tn, horizon - 1)
            with jax.named_scope("tick"):
                q, b, s, *rest = tick_fn(q, b, tau,
                                         active=live & (tau < end), tele=ts)
            ti, ts, cmd = split_extras(rest)
            return (q, b, tau, tree_add(acc, s),
                    tree_add(tacc, ti), ts), (tn < end, cmd)

        with jax.named_scope("weave"):
            (queue, banks, t_last, st, tacc, tstate), (live, cmds) = \
                jax.lax.scan(ebody, (queue, banks, t0 * (start - 1), acc0,
                                     tacc0, tstate),
                             jnp.arange(cfg.event_budget(), dtype=jnp.int32))
            # the binding constraint is the busiest channel's event count
            weave_events = jnp.max(jnp.sum(live.astype(jnp.int32), axis=0))
            # budget exhausted with events still pending anywhere before
            # the static horizon: spilled events replay next window
            # (graceful) and the window is flagged — never silent.  The
            # check runs against `horizon`, not `end`: a pending *tail*
            # event (an arrival in [end, horizon)) carries a drain-
            # hysteresis update the dense scan's inactive ticks would
            # have applied, so skipping it must flag too, or the sat=0
            # => bit-identical contract (relied on by `mess._run_mix`
            # and `traces.replay._replay_exact`) would leak a silent
            # divergence into the next window.
            weave_sat = jnp.any(
                nev_fn(queue, banks, t_last, horizon) < horizon)

    n_rd = jnp.sum(st.served_rd)
    sum_if = jnp.sum(st.sum_if_lat_ps)

    # Closed-loop latency estimate for the next window's MSHR budget:
    # load-to-use ~ cache path + weave round trip (sim domain).
    lat_w = (jnp.sum(st.sum_rd_lat_ticks) / jnp.maximum(n_rd, 1)
             * cfg.platform.dram.dram_ps_per_clk
             + wcfg.cache_path_cycles * cpu.cpu_ps_per_clk)
    lat_est = jnp.where(n_rd > 0, 0.5 * lat_est + 0.5 * lat_w, lat_est)

    # PI controller (Sec. 3.4): blend in the weave-phase average latency
    avg_if_cycles = sum_if / (cpu.cpu_ps_per_clk * jnp.maximum(n_rd, 1))
    l_ir_next = jnp.where(
        jnp.logical_and(cfg.pi_latency, n_rd > 0),
        PI_KEEP * l_ir + PI_BLEND * avg_if_cycles, l_ir)

    noc_rt = wcfg.noc_req_cycles + wcfg.noc_resp_cycles
    app_lat_cycles = (wcfg.cache_path_cycles + noc_rt
                      + l_ir_cycles).astype(jnp.float32)

    out = WindowOut(
        served_rd=n_rd, served_wr=jnp.sum(st.served_wr),
        sum_rd_lat_ticks=jnp.sum(st.sum_rd_lat_ticks),
        sum_if_lat_ps=sum_if,
        chase_rd=jnp.sum(st.chase_rd),
        sum_chase_lat_ticks=jnp.sum(st.sum_chase_lat_ticks),
        app_lat_cycles=app_lat_cycles, l_ir=l_ir_next,
        injected=injected, ticks=end - start,
        progress=frontend.progress(fstate))
    # weave-engine diagnostics ride next to WindowOut (not inside it, so
    # the per-window trajectory stays bit-identical across engines):
    # evaluated event ticks this window + the budget-saturation flag.
    diag = dict(weave_events=weave_events, weave_sat=weave_sat)
    if cfg.telemetry:
        # the three-perspective telemetry planes (`repro.obs`): the
        # per-window DRAM counter/histogram planes plus the interface-
        # view series sampled at window boundaries.  All integer
        # counters are *event-accounted* (at command grants, refresh
        # deadlines, drain flips), so both weave engines accumulate
        # identical window totals.
        diag.update({f"tele_{k}": v for k, v in tacc._asdict().items()},
                    tele_queue_depth=inject_depth,
                    tele_mshr_budget=budget,
                    tele_lat_est_ps=lat_est)
    if cfg.cmd_trace:
        # the per-step command record (`repro.oracle`): the ys axis is
        # the weave scan's step axis (dense: one slot per tick; event:
        # one per budget step), so a window's record is dense in steps
        # but sparse in commands — `repro.oracle.extract_stream`
        # filters the NONE slots and flattens to a time-ordered stream.
        diag.update({f"cmd_{k}": v for k, v in cmds._asdict().items()})
    return (queue, banks, fstate, l_ir_next, lat_est, tstate), (out, diag)


def count_launch(cfg: StageConfig, rows: int, reruns: int = 0,
                 weave_events=None) -> None:
    """Count one compiled launch of ``rows`` points on ``cfg``'s engine.

    Records the `repro.obs.spans` counters the grid drivers report per
    launch: ``repro.rows.<engine>`` (rows routed to it, less the
    ``reruns`` that are dense re-runs of saturated event rows, counted
    as ``repro.rows.rerun``), ``repro.steps.launched`` (rows x windows x
    the engine's static scan steps) and, for the event engine,
    ``repro.steps.event_used`` / ``repro.steps.event_budget``: the
    post-warm-up ``weave_events`` of the launched rows against the
    budget steps they scanned.
    """
    spans.count(f"repro.rows.{cfg.weave}", rows - reruns)
    if reruns:
        spans.count("repro.rows.rerun", reruns)
    spans.count("repro.steps.launched", rows * cfg.windows * cfg.scan_steps())
    if cfg.weave == "event":
        spans.count("repro.steps.event_used",
                    np.sum(weave_events, dtype=np.int64))
        spans.count("repro.steps.event_budget", rows * cfg.event_budget()
                    * (cfg.windows - cfg.warmup))


def run_frontend(cfg: StageConfig, frontend):
    """Simulate the platform driven by any bound-phase frontend.

    Args:
        cfg: static stage configuration (one XLA program per value).
        frontend: object following the protocol documented on
            `workload.MessFrontend`; it may close over traced arrays,
            so this function is `vmap`-able — and thus shardable via
            `repro.core.shard.sharded_vmap` — across operating points
            (Mess) or applications (trace replay).
    Returns:
        ``(views, outs)``: the aggregated three-view dict of scalars
        (bandwidths in GB/s, latencies in ns — see `_aggregate` for
        which clock domain each view reads) plus the raw per-window
        `WindowOut` trajectory (used by the replay engine to locate
        the trace-completion window).
    """
    clock = cfg.clock()
    wcfg = cfg.workload_config()
    queue = dram.init_queue(cfg.platform.dram, cfg.policy,
                            n_sockets=cfg.n_sockets)
    banks = dram.init_banks(cfg.platform.dram)
    fstate = frontend.init_state()
    l_ir0 = jnp.asarray(cfg.l_ir_init_cycles, jnp.float32)
    # optimistic unloaded estimate; the EMA converges within warmup
    lat_est0 = jnp.asarray(
        (cfg.platform.cpu.cache_path_cycles
         * cfg.platform.cpu.cpu_ps_per_clk)
        + (cfg.platform.dram.tCL + cfg.platform.dram.tBL)
        * cfg.platform.dram.dram_ps_per_clk, jnp.float32)

    step = functools.partial(_window_step, cfg, clock, wcfg, frontend)
    # the trailing telemetry-state slot is None (an empty pytree node)
    # when telemetry is off, leaving the flags-off graph unaltered
    carry0 = (queue, banks, fstate, l_ir0, lat_est0,
              dram.init_tele(cfg.platform.dram) if cfg.telemetry else None)
    _, (outs, diag) = jax.lax.scan(
        step, carry0, jnp.arange(cfg.windows, dtype=jnp.int32))
    with jax.named_scope("aggregate"):
        return _aggregate(cfg, outs, diag), outs


def run_point(cfg: StageConfig, pace, wr_num):
    """Simulate one Mess operating point; returns the three views.

    Args:
        cfg: static stage configuration.
        pace: demand requests / traffic core / window
            (int32, traced — vmap-able).
        wr_num: write-fraction numerator out of 64 (int32, traced).
    Returns:
        The three-view dict: ``sim_bw_gbs`` / ``if_bw_gbs`` /
        ``app_bw_gbs`` in GB/s, ``sim_lat_ns`` / ``if_lat_ns`` /
        ``app_lat_ns`` / ``chase_lat_ns`` in ns, plus diagnostics
        (``n_rd``/``n_wr`` served counts, ``l_ir_final`` in CPU
        cycles, ``injected`` accepted requests).
    """
    frontend = workload.MessFrontend(pace, wr_num, cfg.workload_config())
    views, _ = run_frontend(cfg, frontend)
    return views


def _aggregate(cfg: StageConfig, outs: WindowOut, diag=None):
    """Post-warmup aggregation of the three views.

    Units: bandwidths GB/s; latencies ns.  View ① (simulator) counts
    time in DRAM ticks x ``dram_ps_per_clk``; view ② (interface) in
    CPU-perceived picoseconds across the clock-domain crossing; view ③
    (application) in CPU cycles x ``cpu_ps_per_clk`` of bound-phase
    load-to-use latency.
    """
    # aggregate post-warmup
    keep = jnp.arange(cfg.windows) >= cfg.warmup
    def ksum(x):
        return jnp.sum(jnp.where(keep, x, 0))
    line = cfg.platform.dram.line_bytes
    cpu = cfg.platform.cpu

    n_rd = ksum(outs.served_rd)
    n_wr = ksum(outs.served_wr)
    bytes_served = (n_rd + n_wr).astype(jnp.float32) * line
    ticks = ksum(outs.ticks).astype(jnp.float32)
    cpu_ps = (jnp.sum(keep) * cpu.window_cycles
              * cpu.cpu_ps_per_clk).astype(jnp.float32)
    sim_ps = ticks * cfg.platform.dram.dram_ps_per_clk

    nz = jnp.maximum(n_rd, 1).astype(jnp.float32)
    # weave-engine diagnostics: evaluated event ticks post-warmup and
    # the count of budget-saturated windows (anywhere in the run —
    # warmup saturation perturbs the converged state too).  The dense
    # engine reports its active tick count and never saturates.
    if diag is None:
        weave_events = ticks.astype(jnp.int32)
        weave_sat = jnp.zeros((), jnp.int32)
    else:
        weave_events = ksum(diag["weave_events"])
        weave_sat = jnp.sum(diag["weave_sat"].astype(jnp.int32))
    # bytes/ps -> GB/s is a factor of 1e3 (1e12 ps/s over 1e9 B/GB)
    return dict(
        # ① memory-simulator view (DRAM's own clock domain, from the MC)
        sim_bw_gbs=bytes_served / sim_ps * 1e3,
        sim_lat_ns=ksum(outs.sum_rd_lat_ticks).astype(jnp.float32)
            * (cfg.platform.dram.dram_ps_per_clk * 1e-3) / nz,
        # ② memory-interface view (CPU-perceived clock domain)
        if_bw_gbs=bytes_served / cpu_ps * 1e3,
        if_lat_ns=ksum(outs.sum_if_lat_ps) * 1e-3 / nz,
        # ③ application view (bound-phase load-to-use; the outcome)
        app_bw_gbs=bytes_served / cpu_ps * 1e3,
        app_lat_ns=jnp.sum(jnp.where(keep, outs.app_lat_cycles, 0.0))
            / jnp.maximum(jnp.sum(keep), 1)
            * (cpu.cpu_ps_per_clk * 1e-3),
        # diagnostics
        n_rd=n_rd, n_wr=n_wr,
        l_ir_final=outs.l_ir[-1],
        chase_lat_ns=ksum(outs.sum_chase_lat_ticks).astype(jnp.float32)
            * (cfg.platform.dram.dram_ps_per_clk * 1e-3)
            / jnp.maximum(ksum(outs.chase_rd), 1).astype(jnp.float32),
        injected=ksum(outs.injected),
        weave_events=weave_events, weave_sat=weave_sat,
        # telemetry planes and command records pass through raw, full
        # (W, ...) per-window series (consumers slice warmup / filter
        # NONE slots themselves — `repro.obs`, `repro.oracle`).
        **{k: v for k, v in (diag or {}).items()
           if k.startswith(("tele_", "cmd_"))},
    )
