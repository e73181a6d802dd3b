"""Mess-style bandwidth-latency characterization (paper Sec. 2, Fig. 2-7).

The Mess benchmark [5] profiles a memory system as a *family of
bandwidth-latency curves*: for each read/write traffic mix, sweep the
injected bandwidth from unloaded to saturation and record the latency a
pointer-chase probe observes.  Every figure in the paper is such a
sweep evaluated at one simulation stage, plotted from each of the three
views.

This module drives `platform.run_point` over the (pace x write-mix)
grid.  Pace points are `vmap`-ed — one XLA program simulates the whole
curve — and the pace axis is sharded across every available device via
`repro.core.shard.sharded_vmap` (plain vmap on one device, bit-
identical either way).  Write mixes iterate in Python (they change
traffic shape, not shapes of arrays, but keeping the grid 1-D per
compile keeps XLA compile time low and matches how Mess runs on real
hardware: one process per mix).

Outputs are plain numpy arrays, written as CSV by the benchmark harness
in the artifact's `bandwidth_latency.csv` format.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.platform import StageConfig, count_launch, run_point
from repro.core.shard import sharded_vmap
from repro.obs import spans

#: write-fraction numerators out of 64 -> read fractions 100..50%
#: (Mess plots 100%-read lightest to 50%-read darkest).
WRITE_MIXES = (0, 8, 16, 24, 32)
#: demand requests per traffic core per window; 23 traffic cores,
#: 64 B lines, 1000 cycles at 2.1 GHz => pace 64 ~ 198 GB/s offered.
#: Offered bandwidth scales with `StageConfig.n_sockets`: a second
#: socket (47 traffic cores) makes pace 64 ~ 404 GB/s — the knob that
#: drives HBM2e past the single-socket frontend ceiling.
DEFAULT_PACES = (1, 2, 4, 6, 8, 12, 16, 20, 24, 28, 32, 40, 48, 64)


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """One stage's Mess characterization, all three views."""

    stage: str
    write_mixes: tuple
    paces: tuple
    # each (n_mixes, n_paces) float arrays
    sim_bw: np.ndarray
    sim_lat: np.ndarray
    if_bw: np.ndarray
    if_lat: np.ndarray
    app_bw: np.ndarray
    app_lat: np.ndarray
    chase_lat: np.ndarray

    def view(self, which: str):
        """(bw GB/s, lat ns) arrays for 'sim' | 'if' | 'app'."""
        return (getattr(self, f"{which}_bw"), getattr(self, f"{which}_lat"))

    def read_fraction(self, i: int) -> float:
        return 1.0 - self.write_mixes[i] / 64.0

    def to_rows(self):
        """Rows in the artifact's bandwidth_latency.csv format."""
        rows = []
        for i, wr in enumerate(self.write_mixes):
            for j, pace in enumerate(self.paces):
                rows.append(dict(
                    stage=self.stage, read_pct=round(100 * (1 - wr / 64)),
                    pace=pace,
                    sim_bw_gbs=self.sim_bw[i, j], sim_lat_ns=self.sim_lat[i, j],
                    if_bw_gbs=self.if_bw[i, j], if_lat_ns=self.if_lat[i, j],
                    app_bw_gbs=self.app_bw[i, j], app_lat_ns=self.app_lat[i, j],
                ))
        return rows


@functools.lru_cache(maxsize=None)
def _sweep_fn(cfg: StageConfig):
    """One compiled program: device-sharded vmap over pace points.

    The batched argument is a ``(pace, wr_num)`` pair with both leaves
    batched, so one compile serves every write mix and the pace axis
    shards across devices (vmap fallback on one device).  The pair is
    **donated**: `sweep` rebuilds it per mix, so XLA may alias the
    per-point buffers into the outputs instead of copying them.
    """
    return sharded_vmap(lambda pw: run_point(cfg, pw[0], pw[1]),
                        donate=True)


#: measured events/window calibration, keyed on the *device* (the
#: hashable `DramParams`) and stage name: ``(per_pace, fixed)`` linear
#: coefficients from `load_event_calibration`.  Routing only — the
#: exact ``weave_sat`` backstop means a stale entry costs speed, never
#: correctness.
_EVENT_CAL: dict = {}

#: safety margin over the measured fit: refresh beats and drain-phase
#: wander shift per-window event counts between workloads, so route a
#: point to the event engine only with measured headroom to spare.
CAL_MARGIN = 1.35


def load_event_calibration(path: str | None = None) -> int:
    """Load measured events/window fits from a ``BENCH_weave.json``.

    `benchmarks.weave_bench` fits ``events/window ~ per_pace * pace +
    fixed`` per device preset from the compiled event engine's own
    ``weave_events`` diagnostics (the ROADMAP "event-engine tuning"
    item); this registers those fits so `event_covers` routes pace
    points on *measured* rates instead of the conservative closed-form
    bound.  Entries key on ``(DramParams, stage_name)``, so a
    calibration for one device never routes another.

    Args:
        path: report path; defaults to the repo's checked-in
            ``reports/benchmarks/BENCH_weave.json``.
    Returns:
        The number of calibration entries registered (0 when the
        report is missing or carries no fits — routing falls back to
        the closed-form estimate, unchanged behavior).
    """
    import json
    import pathlib

    from repro.core.presets import PRESETS, platform_for

    if path is None:
        path = (pathlib.Path(__file__).resolve().parents[3]
                / "reports" / "benchmarks" / "BENCH_weave.json")
    path = pathlib.Path(path)
    if not path.exists():
        return 0
    report = json.loads(path.read_text())
    stage = report.get("stage", "")
    n = 0
    for preset, row in report.get("presets", {}).items():
        fit = row.get("event_rate_fit")
        if not fit or preset not in PRESETS:
            continue
        _EVENT_CAL[(platform_for(preset).dram, stage)] = (
            float(fit["per_pace"]), float(fit["fixed"]))
        n += 1
    return n


_CAL_LOADED = False


def _ensure_calibration():
    """Lazily register the checked-in calibration once per process (a
    malformed or missing report must never break a sweep — routing
    falls back to the closed-form bound)."""
    global _CAL_LOADED
    if not _CAL_LOADED:
        _CAL_LOADED = True
        try:
            load_event_calibration()
        except (OSError, ValueError, KeyError, TypeError):
            pass


def event_covers(cfg: StageConfig, pace: int) -> bool:
    """Static estimate: does the event budget cover this pace's events?

    Per window, a pace-``p`` point offers ``p * n_traffic`` requests
    over ``C`` channels; each needs at most ~3 commands (PRE+ACT+CAS
    on a row miss), plus ~``p`` arrival bursts and fixed chase-probe /
    refresh / drain-settle headroom.  When a measured calibration is
    registered for this device and stage (`load_event_calibration`),
    the fitted events/window rate (x `CAL_MARGIN` safety) replaces the
    closed-form bound.  Used by `sweep` to route points between the
    engines; deliberately conservative (command ticks coalesce across
    channels in practice), and backstopped at runtime by the exact
    ``weave_sat`` flag — a mis-routed point is re-run dense, so
    routing affects speed, never results.
    """
    wcfg = cfg.workload_config()
    dram = cfg.platform.dram
    cal = _EVENT_CAL.get((dram, cfg.name))
    if cal is not None:
        a, b = cal
        est = int((a * pace + max(b, 0.0)) * CAL_MARGIN) + 1
    else:
        est = (3 * pace * wcfg.n_traffic) // dram.n_channels + pace + 64
    return est <= cfg.event_budget()


def _launch(cfg: StageConfig, paces, wr, reruns: int = 0) -> dict:
    """One compiled launch over ``paces`` at write mix ``wr``, on the
    host, under the engine's span and counters (`platform.count_launch`;
    the last ``reruns`` paces re-run saturated event points)."""
    with spans.span(f"repro.mess.{cfg.weave}"):
        pv = jnp.asarray(paces, jnp.int32)
        res = _sweep_fn(cfg)((pv, jnp.full_like(pv, wr)))
        with spans.span(f"repro.mess.{cfg.weave}.fetch"):
            out = jax.device_get(res)
    count_launch(cfg, len(paces), reruns, out["weave_events"])
    return out


@spans.span("repro.mess.mix")
def _run_mix(cfg: StageConfig, paces, wr):
    """One write-mix row, knee-routed between the weave engines.

    With ``cfg.weave == "event"``, pace points whose event budget
    provably suffices (`event_covers`) run the event engine; the
    saturated tail runs the dense reference.  Any event-routed point
    that still reports budget saturation (``weave_sat``) is re-run
    dense — the row is **bit-identical to an all-dense sweep by
    construction**, the event engine only buys wall-clock where its
    semantics are exact.
    """
    n = len(paces)
    if cfg.cmd_trace:
        # the per-step `cmd_*` records have engine-dependent step-axis
        # shapes (dense: ticks/window, event: budget), so the knee-
        # routed engine merge below cannot column-stack them; record
        # command streams through `platform.run_frontend` +
        # `repro.oracle.extract_stream` on a single engine instead
        raise ValueError("cmd_trace is unsupported in mess.sweep's "
                         "knee-routed engine mix; run run_frontend "
                         "with an explicit weave engine instead")
    if cfg.weave != "event":
        return _launch(cfg, paces, wr)

    with spans.span("repro.mess.route"):
        _ensure_calibration()
        cfg_dense = dataclasses.replace(cfg, weave="dense")
        ev = [i for i, p in enumerate(paces) if event_covers(cfg, p)]
        dn = [i for i in range(n) if i not in ev]
    reruns = 0
    parts = {}
    if ev:
        out = _launch(cfg, [paces[i] for i in ev], wr)
        sat = np.asarray(out["weave_sat"]) > 0
        if sat.any():                      # estimator missed: go exact
            reruns = int(sat.sum())
            dn += [ev[j] for j in np.flatnonzero(sat)]
            ev = [ev[j] for j in np.flatnonzero(~sat)]
            out = {k: np.asarray(v)[~sat] for k, v in out.items()}
        parts["ev"] = (ev, out)
    if dn:
        parts["dn"] = (dn, _launch(cfg_dense, [paces[i] for i in dn], wr,
                                   reruns))
    with spans.span("repro.mess.merge"):
        first = next(iter(parts.values()))[1]
        merged = {}
        for k in first:
            proto = np.asarray(first[k])
            col = np.empty((n,) + proto.shape[1:], proto.dtype)
            for (idx, v) in parts.values():
                col[np.asarray(idx, int)] = np.asarray(v[k])
            merged[k] = col
    return merged


@spans.span("repro.mess.sweep")
def sweep(cfg: StageConfig, paces=DEFAULT_PACES,
          write_mixes=WRITE_MIXES) -> SweepResult:
    """Run the Mess characterization of one simulation stage.

    Under the default event weave engine the pace axis is knee-routed
    (`_run_mix`): below-knee points take the fast event scan, the
    saturated tail takes the dense reference, and saturation-flagged
    points fall back — results are bit-identical to an all-dense sweep
    regardless of ``cfg.weave``.
    """
    acc = {k: [] for k in ("sim_bw", "sim_lat", "if_bw", "if_lat",
                           "app_bw", "app_lat", "chase_lat")}
    for wr in write_mixes:
        out = _run_mix(cfg, tuple(paces), wr)
        acc["sim_bw"].append(out["sim_bw_gbs"])
        acc["sim_lat"].append(out["sim_lat_ns"])
        acc["if_bw"].append(out["if_bw_gbs"])
        acc["if_lat"].append(out["if_lat_ns"])
        acc["app_bw"].append(out["app_bw_gbs"])
        acc["app_lat"].append(out["app_lat_ns"])
        acc["chase_lat"].append(out["chase_lat_ns"])
    return SweepResult(
        stage=cfg.name, write_mixes=tuple(write_mixes), paces=tuple(paces),
        **{k: np.stack(v) for k, v in acc.items()})


def unloaded_latency_ns(res: SweepResult, view: str = "app") -> float:
    """Latency of the lowest-bandwidth 100%-read point."""
    _, lat = res.view(view)
    return float(lat[0, 0])


def max_bandwidth_gbs(res: SweepResult, view: str = "app",
                      mix_index: int = 0) -> float:
    bw, _ = res.view(view)
    return float(np.max(bw[mix_index]))


def saturated_latency_ns(res: SweepResult, view: str = "app",
                         mix_index: int = 0) -> float:
    bw, lat = res.view(view)
    return float(lat[mix_index, int(np.argmax(bw[mix_index]))])
