"""Three-perspective observability (`repro.obs`).

The paper's thesis is that the *simulator*, *CPU-memory interface*,
and *application* perspectives of the same run can diverge — and that
the correction ladder (stages 01→10) re-couples them.  This package
turns the platform's in-kernel telemetry planes (enabled with
``StageConfig(telemetry=True)``) into inspectable artifacts:

* `repro.obs.telemetry` — collect the raw ``tele_*`` view series into
  a `TelemetryRecord`; reduce to command mixes, row-locality splits,
  bank utilization, and latency percentiles.
* `repro.obs.export` — structured JSON reports and a Chrome-trace /
  Perfetto JSON timeline (per-channel command tracks, write-drain
  phase slices, per-core progress tracks), plus the Ramulator2-
  compatible ``.cmd.trace`` exporter for recorded `repro.oracle`
  command streams.
* `repro.obs.perspectives` — per-window rank correlation between the
  three views' latency/progress series: the machine-readable
  "perspectives diverge, corrections re-couple them" report.
* `repro.obs.spans` — not simulated time but the simulator's own: host
  spans and launch counters that the grid drivers record (always on,
  a bounded ring), also visible in a `jax.profiler` trace.

Telemetry is a **static** `StageConfig` flag: when off (default) the
traced computation is exactly the historical graph — bit-identical
outputs, zero cost.  When on, every counter is *event-accounted*
inside `repro.core.dram.tick`, so both weave engines (dense and
event-horizon) produce identical planes.
"""
from repro.obs.telemetry import (TELE_KEYS, TelemetryRecord, collect,
                                 hist_edges, hist_percentiles, summarize)
from repro.obs.export import (to_cmd_trace, to_json, to_perfetto,
                              validate_cmd_trace, validate_perfetto)
from repro.obs.perspectives import divergence_report, spearman, window_series

__all__ = [
    "TELE_KEYS", "TelemetryRecord", "collect", "hist_edges",
    "hist_percentiles", "summarize", "to_json", "to_perfetto",
    "validate_perfetto", "to_cmd_trace", "validate_cmd_trace",
    "divergence_report", "spearman", "window_series",
]
