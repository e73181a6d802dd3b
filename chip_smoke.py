"""Bring-up check: the simulator's main path, end to end, on a TPU.

    python chip_smoke.py              # one chip: four phases
    python chip_smoke.py --chips 4    # the sharded batch axis, four chips

One chip, through the public API (`repro.core`, `repro.traces`):

1. ``mess``   — the Mess characterisation of stage 10-delay-buffer on
   ddr4_2666 at paper resolution: 14 paces x 5 write mixes, 96 windows
   (32 warm-up), 6 ch x 2 ranks x 16 banks, 24 cores.  The knee router
   sends pace points to both weave engines.
2. ``oracle`` — the points of that grid the router sent to the event
   engine (paces 1-8, 25 points), on the dense engine alone in one
   batch.  Every view must equal phase 1 bit for bit
   (`repro.core.mess.sweep`'s contract); the other points ran the
   dense engine inside phase 1 already.  Phase 1 must be finite.
3. ``bands``  — the points tests/test_system.py holds to the measured
   reference (stage 07-prefetch, paces 1/32/64, 100% read), here at 96
   windows: the unloaded app latency and the saturation bandwidth must
   fall inside that test's bands.
4. ``replay`` — the DAMOV-style application suite (8192 accesses per
   app) replayed at stage 07-prefetch; per-app runtimes must be finite.

``--chips 4`` runs only the sharded path: the paper-resolution pace
batch of one write mix and the app-suite replay, each through
`repro.core.shard.sharded_vmap` on four devices and on one.  The two
must agree bit for bit, and the four-device output must live on four
devices.

Each phase prints its compile seconds (tracing, lowering and XLA
compilation, from JAX's monitoring events), its wall seconds, the
difference of the two (the run itself) and a checksum of its outputs.
``--save DIR`` also writes each phase's outputs to ``DIR/<phase>.npz``.
JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<repo>/.jax_cache`` (`repro.compile_cache`).

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed on a TPU.  Without a TPU, or when
any phase fails, the script exits non-zero; it never falls back to the
CPU.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PRESET = "ddr4_2666"
MESS_STAGE = "10-delay-buffer"
REPLAY_STAGE = "07-prefetch"
BAND_STAGE = "07-prefetch"
TRACE_LEN = 8192
SWEEP_VIEWS = ("sim_bw", "sim_lat", "if_bw", "if_lat", "app_bw", "app_lat",
               "chase_lat")
_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
))


def checksum(out: dict) -> str:
    """sha256 over every output's name, dtype, shape and bytes."""
    h = hashlib.sha256()
    for k in sorted(out):
        a = np.ascontiguousarray(np.asarray(out[k]))
        h.update(f"{k}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


class Phases:
    """Runs the phases, splits compile from run time, keeps the outputs."""

    def __init__(self):
        self.outputs: dict = {}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += secs

    def run(self, name: str, fn) -> dict:
        """Run ``fn`` once; print its times and checksum; keep its output.

        ``compile_s`` sums JAX's tracing, lowering and XLA-compile events
        during the call; ``run_s`` is the wall time less that, i.e. the
        compiled programs' execution plus the host work around them.
        """
        c0, t0 = self.compile_s, time.perf_counter()
        out = {k: np.asarray(v) for k, v in fn().items()}
        wall, compile_s = time.perf_counter() - t0, self.compile_s - c0
        print(f"phase {name}: compile_s={compile_s:.3f} wall_s={wall:.3f} "
              f"run_s={wall - compile_s:.3f} checksum={checksum(out)}",
              flush=True)
        self.outputs[name] = out
        return out


def assert_identical(name: str, got: dict, want: dict) -> None:
    """Bit-identity of two output dicts, naming the first view that differs."""
    for k in want:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        if a.shape != b.shape or not np.array_equal(a, b):
            a, b = a.astype(np.float64), b.astype(np.float64)
            rel = (np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30))
                   if a.shape == b.shape else float("nan"))
            raise AssertionError(f"{name}: view {k} differs, largest "
                                 f"relative difference {rel!r}")


def assert_finite(name: str, out: dict) -> None:
    for k, v in out.items():
        v = np.asarray(v)
        if v.dtype.kind == "f" and not np.isfinite(v).all():
            raise AssertionError(f"{name}: {k} is not finite")


def event_paces() -> list:
    """Indices of the paces `sweep`'s knee router sends to the event engine."""
    from repro.core import get_stage, mess

    mess.load_event_calibration()
    cfg = get_stage(MESS_STAGE, preset=PRESET)
    return [i for i, p in enumerate(mess.DEFAULT_PACES)
            if mess.event_covers(cfg, p)]


def dense_oracle(cols: list) -> dict:
    """The ``mess`` grid's event-routed points on the dense engine alone.

    Every (write mix, pace) point of ``cols`` goes through `run_point`
    in one vmapped program, independent of `sweep`'s routing and merge.
    The other points of the grid already ran this dense program inside
    `sweep`.  (An all-dense `sweep` of the whole grid took 599 s on a
    v5e, which with the other phases leaves too little of the run's
    time limit.)
    """
    import jax.numpy as jnp

    from repro.core import get_stage, run_point
    from repro.core.mess import DEFAULT_PACES, WRITE_MIXES
    from repro.core.shard import sharded_vmap

    cfg = get_stage(MESS_STAGE, preset=PRESET, weave="dense")
    paces = [DEFAULT_PACES[i] for i in cols]
    wr, pace = np.meshgrid(WRITE_MIXES, paces, indexing="ij")
    out = sharded_vmap(lambda pw: run_point(cfg, pw[0], pw[1]))(
        (jnp.asarray(pace.ravel(), jnp.int32),
         jnp.asarray(wr.ravel(), jnp.int32)))
    unit = lambda view: "gbs" if view.endswith("_bw") else "ns"
    return {k: np.asarray(out[f"{k}_{unit(k)}"]).reshape(wr.shape)
            for k in SWEEP_VIEWS}


def one_chip(phases: Phases) -> None:
    from repro.core import get_stage, reference, sweep
    from repro.traces import make_suite, replay_suite, stack_traces

    def mess(stage: str = MESS_STAGE, **points) -> dict:
        res = sweep(get_stage(stage, preset=PRESET), **points)
        return {k: getattr(res, k) for k in SWEEP_VIEWS}

    routed = phases.run("mess", mess)
    assert_finite("mess", routed)
    cols = event_paces()
    dense = phases.run("oracle", lambda: dense_oracle(cols))
    assert_identical("routed sweep vs dense engine",
                     {k: v[:, cols] for k, v in routed.items()}, dense)
    print(f"oracle: {dense['sim_bw'].size} event-routed points of "
          f"{routed['sim_bw'].size} bit-identical to the dense engine",
          flush=True)
    print(f"oracle: stage {MESS_STAGE} unloaded app latency "
          f"{float(routed['app_lat'][0, 0])!r} ns, saturation app bandwidth "
          f"{float(routed['app_bw'][0].max())!r} GB/s", flush=True)

    # the reference bands of tests/test_system.py, at the stage and
    # points that test uses, here at paper resolution (96 windows)
    band = phases.run("bands", lambda: mess(
        BAND_STAGE, paces=(1, 32, 64), write_mixes=(0,)))
    assert_finite("bands", band)
    unloaded = float(band["app_lat"][0, 0])
    sat_bw = float(band["app_bw"][0].max())
    ref_ns = reference.UNLOADED_NS
    ref_bw = reference.max_bandwidth_gbs(1.0, PRESET)
    print(f"bands: stage {BAND_STAGE} unloaded app latency {unloaded!r} ns "
          f"(reference {ref_ns!r}); saturation app bandwidth {sat_bw!r} "
          f"GB/s (reference {ref_bw!r})", flush=True)
    if not 0.7 * ref_ns < unloaded < 1.6 * ref_ns:
        raise AssertionError(f"unloaded latency {unloaded} out of band")
    if not 0.6 * ref_bw < sat_bw < 1.1 * ref_bw:
        raise AssertionError(f"saturation bandwidth {sat_bw} out of band")

    names, traces = make_suite(n=TRACE_LEN)
    batch = stack_traces(traces)
    cfg = get_stage(REPLAY_STAGE, preset=PRESET)
    out = phases.run("replay", lambda: replay_suite(cfg, batch))
    assert_finite("replay", out)
    for app, ms in zip(names, out["runtime_ms"]):
        print(f"replay: {app} runtime_ms={float(ms)!r}", flush=True)


def four_chips(phases: Phases, n: int) -> None:
    import jax.numpy as jnp

    from repro.core import get_stage, run_frontend, run_point
    from repro.core.mess import DEFAULT_PACES, WRITE_MIXES
    from repro.core.shard import sharded_vmap
    from repro.traces import make_suite, stack_traces
    from repro.traces.frontend import TraceFrontend

    mess_cfg = get_stage(MESS_STAGE, preset=PRESET)
    paces = jnp.asarray(DEFAULT_PACES, jnp.int32)
    pace_batch = (paces, jnp.full_like(paces, WRITE_MIXES[2]))
    replay_cfg = get_stage(REPLAY_STAGE, preset=PRESET)
    _, traces = make_suite(n=TRACE_LEN)
    cases = (
        ("mess-paces", lambda pw: run_point(mess_cfg, pw[0], pw[1]),
         pace_batch),
        ("replay", lambda tr: run_frontend(replay_cfg, TraceFrontend(
            tr, replay_cfg.workload_config()))[0], stack_traces(traces)),
    )
    for name, fn, batch in cases:
        outs = {}
        for nd in (n, 1):
            mapped = sharded_vmap(fn, n_devices=nd)

            def call():
                out = jax.block_until_ready(mapped(batch))
                spread = {len(v.sharding.device_set) for v in out.values()}
                if spread != {nd}:
                    raise AssertionError(f"{name}: {nd}-device output "
                                         f"lives on {spread} devices")
                return out

            outs[nd] = phases.run(f"{name}@{nd}", call)
        assert_identical(f"{name}: {n} devices vs 1", outs[n], outs[1])
        assert_finite(name, outs[1])
        print(f"{name}: {n}-device output bit-identical to 1-device, "
              f"spread over {n} devices", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path on four chips")
    ap.add_argument("--save", default=None,
                    help="directory to write each phase's outputs to")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; refusing to run elsewhere",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    n_entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({n_entries} entries at start)",
          flush=True)

    phases = Phases()
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(phases)
    else:
        four_chips(phases, args.chips)
    print(f"total_s={time.perf_counter() - t0:.3f} "
          f"compile_s={phases.compile_s:.3f}", flush=True)
    if args.save:
        os.makedirs(args.save, exist_ok=True)
        for name, out in phases.outputs.items():
            np.savez(os.path.join(args.save, f"{name}.npz"), **out)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
