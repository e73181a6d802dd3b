"""Batched multi-application replay engine (device-sharded).

One compiled program replays a whole application suite: the stacked
`Trace` batch maps over `platform.run_frontend`, with the application
axis sharded across every available device by
`repro.core.shard.sharded_vmap` (bit-identical plain-vmap fallback on
one device), so N applications share a single XLA compile per stage —
the same pattern `mess.sweep` uses for pace points.  Stages and device
presets iterate in Python because they differ in *static*
configuration (clock model, scheduler policy, channel/bank geometry),
which changes program shapes; `replay_grid` wraps that iteration so a
full (preset x stage x app) scenario grid is one invocation.

Multiprogrammed workloads ride the same machinery: a `TraceMix`
(per-core trace batch, `repro.traces.mix`) replays through
`replay_mix`, and a *stack* of mixes through `replay_mixes` — the mix
axis is the sharded batch axis, exactly like the app axis of a solo
suite.  The frontend keeps one cursor per core either way, so per-app
runtimes in a mix come back per core and are reduced by `app_id`.

Outputs per application:

* the three views (simulator / interface / application bandwidth and
  latency) — the paper's methodology applied to real access patterns;
* a predicted application *runtime*: the window at which the trace was
  fully consumed (or an extrapolation from the final replay rate when
  the configured window count ends first).
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from repro.core.platform import StageConfig, count_launch, run_frontend
from repro.core.shard import sharded_vmap
from repro.obs import spans
from repro.traces.frontend import TraceFrontend
from repro.traces.mix import TraceMix
from repro.traces.trace import Trace

#: per-app result keys that are plain per-window scalars in the views
VIEW_KEYS = ("sim_bw_gbs", "sim_lat_ns", "if_bw_gbs", "if_lat_ns",
             "app_bw_gbs", "app_lat_ns", "chase_lat_ns", "n_rd", "n_wr")


@functools.lru_cache(maxsize=None)
def _replay_fn(cfg: StageConfig, donate: bool = False):
    """One compiled program: the app/mix axis is the sharded batch axis."""

    def one(trace):
        views, outs = run_frontend(cfg, TraceFrontend(
            trace, cfg.workload_config()))
        out = dict({k: views[k] for k in VIEW_KEYS},
                   weave_sat=views["weave_sat"],
                   weave_events=views["weave_events"],
                   progress=outs.progress)
        if cfg.telemetry:
            # three-perspective telemetry planes (`repro.obs`): full
            # (W, ...) per-window series, flat keys so the batch axis
            # vmaps and the dense fallback's row merge work unchanged
            out.update({k: v for k, v in views.items()
                        if k.startswith("tele_")})
        return out

    return sharded_vmap(one, donate=donate)


def _replay_exact(cfg: StageConfig, batch, donate: bool) -> dict:
    """Replay a batch, re-running event-budget-saturated rows dense.

    Under the default event weave engine, a row whose windows exhaust
    the static event budget (``weave_sat`` — the exact divergence
    detector) is replayed through the dense reference engine, so the
    returned results are bit-identical to an all-dense replay no
    matter how hot the workload runs.  With ``donate=True`` the input
    buffers are consumed by the first pass, so the fallback is
    unavailable — saturated rows stay flagged in ``weave_sat`` for the
    caller to handle (pre-verify the regime, or keep the default
    ``donate=False``).  Each launch is recorded under a
    `repro.obs.spans` span and counted by `platform.count_launch`;
    the first pass's ``weave_events`` only feed those counters.
    """
    with spans.span(f"repro.replay.{cfg.weave}"):
        res = _replay_fn(cfg, donate)(batch)
        with spans.span(f"repro.replay.{cfg.weave}.fetch"):
            out = jax.device_get(res)
    out = {k: np.array(v) for k, v in out.items()}
    count_launch(cfg, len(out["weave_sat"]),
                 weave_events=out.pop("weave_events"))
    sat = np.flatnonzero(out["weave_sat"] > 0)
    if sat.size and cfg.weave == "event" and not donate:
        import dataclasses

        cfg_dense = dataclasses.replace(cfg, weave="dense")
        with spans.span("repro.replay.dense"):
            sub = jax.tree_util.tree_map(lambda a: a[sat], batch)
            res = _replay_fn(cfg_dense, False)(sub)
            with spans.span("repro.replay.dense.fetch"):
                fixed = jax.device_get(res)
        count_launch(cfg_dense, sat.size, reruns=sat.size)
        fixed.pop("weave_events")
        for k, v in fixed.items():
            if k != "weave_sat":           # keep the diagnostic flag
                out[k][sat] = np.asarray(v)
    return out


def _runtime_windows(progress, target, pos0=None):
    """Per-stream completion from a (..., W, n_cores) progress history.

    Args:
        progress: per-window per-core cursor positions.
        target: (..., n_cores) per-core access counts (0 = idle).
        pos0: (..., n_cores) per-core phase offsets (cursor start);
            extrapolation measures replay rate from here, not from 0,
            so an offset core's head start is not counted as progress.
    Returns:
        ``(runtime_windows, done)`` per core: the 1-based window at
        which the core's stream completed, extrapolated from the final
        replay rate when it did not; idle cores report 0 windows.
    """
    if pos0 is None:
        pos0 = np.zeros_like(target)
    W = progress.shape[-2]
    done = progress >= target[..., None, :]          # (..., W, N)
    any_done = done.any(axis=-2)
    first_done = np.where(any_done, done.argmax(axis=-2) + 1, W)
    advanced = np.maximum(progress[..., -1, :] - pos0, 1)
    est = W * (target - pos0) / advanced
    rt = np.where(any_done, first_done, est).astype(np.float64)
    return np.where(target > 0, rt, 0.0), any_done | (target == 0)


@spans.span("repro.replay.suite")
def replay_suite(cfg: StageConfig, traces: Trace,
                 donate: bool = False) -> dict:
    """Replay a stacked trace batch through one stage; host-side dict.

    Args:
        cfg: the stage configuration (clock model, policy, platform).
        traces: a `Trace` with a leading application axis
            (see `stack_traces`); the axis is sharded across devices.
        donate: donate the trace buffers to the compiled replay
            (`repro.core.shard.sharded_vmap`), cutting per-point device
            copies / peak memory for fleet-scale batches.  The batch is
            **consumed** — pass ``True`` only when it is not replayed
            again (e.g. single-stage runs; `replay_stages` reuses the
            batch across stages and must keep the default).
    Returns:
        Numpy arrays keyed by `VIEW_KEYS` (bandwidth GB/s, latency ns)
        plus ``runtime_ms`` / ``runtime_windows`` / ``done`` /
        ``progress_final`` per application.
    """
    wcfg = cfg.workload_config()
    # host-side fields first: after a donating call the buffers are gone
    with spans.span("repro.replay.inputs"):
        length = np.asarray(jax.device_get(traces.length))  # (A,)
        # per-core regions must stay below the chase-probe region (bit
        # 31): with two sockets (48 cores) large footprints can reach it
        fmax = int(np.max(np.asarray(
            jax.device_get(traces.footprint_lines))))
    if wcfg.n_cores * fmax > 1 << 31:
        raise ValueError(
            f"{wcfg.n_cores} cores x footprint {fmax} lines overflows "
            f"the 2^31-line traffic address space (the chase-probe "
            f"region starts at bit 31); shrink the footprint")

    out = _replay_exact(cfg, traces, donate)
    with spans.span("repro.replay.runtime"):
        progress = np.asarray(out.pop("progress"))   # (A, W, n_cores)
        out = {k: np.asarray(v) for k, v in out.items()}
        cid = np.arange(wcfg.n_cores)
        target = np.where(cid[None, :] < wcfg.n_traffic,
                          length[:, None], 0)         # (A, n_cores)
        rt, done = _runtime_windows(progress, target)
        traffic = cid < wcfg.n_traffic
        # the app finishes when its slowest core does (lockstep in solo
        # mode)
        runtime_windows = rt[:, traffic].max(axis=1)

        cpu = cfg.platform.cpu
        window_ms = cpu.window_cycles * cpu.cpu_ps_per_clk * 1e-9
        out["done"] = done[:, traffic].all(axis=1)
        out["runtime_windows"] = runtime_windows
        out["runtime_ms"] = runtime_windows * window_ms
        out["progress_final"] = progress[:, -1, :][:, traffic].min(axis=1)
    return out


def replay_mix(cfg: StageConfig, mix: TraceMix) -> dict:
    """Replay one multiprogrammed mix; per-app and per-core results.

    Args:
        cfg: the stage configuration; ``cfg.n_sockets`` must match the
            mix's core count (24 cores per socket).
        mix: an unbatched `TraceMix` (`assign_traces`).
    Returns:
        The whole-platform views (scalars keyed by `VIEW_KEYS`) plus
        ``app_runtime_ms`` / ``app_runtime_windows`` / ``app_done``
        arrays indexed by app id, and the per-core
        ``core_runtime_windows`` / ``core_done`` they reduce from.
    """
    batched = jax.tree_util.tree_map(lambda a: a[None], mix)
    out = replay_mixes(cfg, batched)
    return jax.tree_util.tree_map(lambda a: a[0], out)


@spans.span("repro.replay.mixes")
def replay_mixes(cfg: StageConfig, mixes: TraceMix,
                 donate: bool = False) -> dict:
    """Replay a stack of mixes (leading mix axis, device-sharded).

    Args:
        cfg: the stage configuration (one compiled program).
        mixes: a `TraceMix` batch from `stack_mixes`; all mixes share
            the platform's core count.
        donate: donate the mix buffers to the compiled replay (the
            batch is consumed — see `replay_suite`).
    Returns:
        Host-side dict: views (M,), per-core arrays (M, n_cores), and
        per-app arrays (M, A) where A is the largest app count across
        the batch (`nan` / False padding for mixes with fewer apps).
    """
    # host-side fields first: after a donating call the buffers are gone
    with spans.span("repro.replay.inputs"):
        target = np.asarray(jax.device_get(mixes.length))  # (M, n_cores)
        app_id = np.asarray(jax.device_get(mixes.app_id))  # (M, n_cores)
        pos0 = np.asarray(jax.device_get(mixes.pos0))      # (M, n_cores)
    out = _replay_exact(cfg, mixes, donate)
    with spans.span("repro.replay.runtime"):
        progress = np.asarray(out.pop("progress"))   # (M, W, n_cores)

        rt, done = _runtime_windows(progress, target, pos0)
        cpu = cfg.platform.cpu
        window_ms = cpu.window_cycles * cpu.cpu_ps_per_clk * 1e-9

        M = app_id.shape[0]
        n_apps = int(app_id.max()) + 1 if app_id.size else 0
        app_rt = np.full((M, n_apps), np.nan)
        app_done = np.zeros((M, n_apps), bool)
        for m in range(M):
            for a in range(n_apps):
                cores = app_id[m] == a
                if cores.any():
                    # an app finishes when its slowest core does
                    app_rt[m, a] = rt[m, cores].max()
                    app_done[m, a] = done[m, cores].all()

        out["core_runtime_windows"] = rt
        out["core_done"] = done
        out["app_runtime_windows"] = app_rt
        out["app_runtime_ms"] = app_rt * window_ms
        out["app_done"] = app_done
    return out


def replay_stages(stages, traces: Trace, preset: str | None = None,
                  **overrides) -> dict:
    """Replay one trace batch across several stages.

    Args:
        stages: iterable of stage names or `StageConfig`s.
        traces: stacked `Trace` batch (leading application axis).
        preset: optional device preset applied to every named stage.
        **overrides: `StageConfig` field overrides applied to every
            named stage (window-count knobs for CI-speed vs full runs,
            ``n_sockets=2`` for a two-socket frontend, ...).
    Returns:
        ``{stage_name: replay_suite(...)}``.
    """
    from repro.core import get_stage

    results = {}
    for st in stages:
        cfg = st if isinstance(st, StageConfig) else get_stage(
            st, preset=preset, **overrides)
        results[cfg.name] = replay_suite(cfg, traces)
    return results


def replay_grid(presets, stages, traces: Trace, **overrides) -> dict:
    """One fleet-scale scenario grid: preset x stage x application.

    Every (preset, stage) cell is one compiled program whose
    application axis is sharded across all devices; presets and stages
    iterate in Python because they change static shapes (channel/bank
    geometry, clock ratios, scheduler policy).  One call covers the
    whole grid.

    Args:
        presets: iterable of device preset names (`repro.core.presets`).
        stages: iterable of stage names.
        traces: stacked `Trace` batch shared by every cell.
        **overrides: `StageConfig` overrides applied to every cell.
    Returns:
        ``{preset: {stage: replay_suite(...)}}``.
    """
    return {p: replay_stages(stages, traces, preset=p, **overrides)
            for p in presets}
