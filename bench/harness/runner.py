"""One run of one cell: set-up, the measured window, the comparison.

Set-up builds the cell's inputs from the seed, loads (or on a cold
checkout compiles) its programs and makes one warm call at the cell's
own shapes; ``setup_s`` runs from process start to the end of that
call.  The window then repeats the call, whole calls only, until
``seconds`` have passed; every call ends in host arrays.  With
``trace`` on, the profiler records the window's first calls, up to the
first call that ends past `TRACE_SECONDS`, and the per-layer metrics
are read from that trace and from the calls after it.  After the
window the device's peak memory is read, the program's inputs are
dropped, and the plain reference runs once over the same inputs;
every call's outputs are compared with it.  Standard error gets the
calls' durations and the host time between them, the reference's time,
and last each compared number beside its limit.
"""
from __future__ import annotations

import json
import statistics
import sys
import tempfile
import time

from harness import compare, reduce
from harness.cells import Cell
from harness.compiles import CompileCounter, checksum
from harness.spec import metric_reader

#: seconds of calls the profiler records in a traced run.  It stops
#: after the first call that ends past them, so it holds at least one
#: whole call.  The profiler keeps a bounded number of op events (about
#: 6.3 million on a TPU v5e, under 3 s of these cells' calls): a longer
#: traced window would lose the ops of its later calls.
TRACE_SECONDS = 1.0


def device_info(jax) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def peak_bytes(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def window(cell: Cell, seconds: float, trace_dir: str | None):
    """Repeat the call for ``seconds``; returns the calls and how many
    of the first ones the profiler recorded."""
    import jax

    calls, traced = [], 0
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_w = time.perf_counter()
    while not calls or time.perf_counter() - t_w < seconds:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(reduce.CALL):
            out = cell.call()
        t1 = time.perf_counter()
        calls.append((t0, t1, out))
        if trace_dir and not traced and t1 - t_w >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            traced = len(calls)
    if trace_dir and not traced:
        jax.profiler.stop_trace()
        traced = len(calls)
    return calls, traced


def call_times(calls) -> str:
    """The calls' durations (quartiles and the longest) and the host
    time between calls, in ms: where a slow run lost its time."""
    durs = [t1 - t0 for t0, t1, _ in calls]
    between = [b[0] - a[1] for a, b in zip(calls, calls[1:])] or [0.0]
    quartiles = statistics.quantiles(durs, n=4) if len(durs) > 1 else durs * 3
    q1, q2, q3 = (1e3 * q for q in quartiles)
    longest = max(range(len(durs)), key=durs.__getitem__)
    return (f"call ms quartiles {q1:.3f} / {q2:.3f} / {q3:.3f}, longest "
            f"{1e3 * durs[longest]:.3f} (call {longest + 1} of "
            f"{len(durs)}); between calls median "
            f"{1e3 * statistics.median(between):.3f} ms, longest "
            f"{1e3 * max(between):.3f} ms")


def run(spec: dict, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True, out=None, err=None) -> int:
    """Run one cell; print the result line; return the exit code."""
    import jax

    out = out or sys.stdout
    err = err or sys.stderr
    work = spec["workload"]
    info = device_info(jax)
    if require_chip and (info["platform"] == "cpu"
                         or info["count"] < work["chips"]):
        print(f"bench: {work['name']} needs {work['chips']} accelerator "
              f"chip(s); JAX found {info['count']} {info['platform']} "
              "device(s)", file=err)
        return 1

    with CompileCounter() as compiles:
        cell = Cell(spec, seed)
        cell.prepare()
        warm = cell.call()
        setup_s = time.perf_counter() - t_start
        print(f"bench: {work['name']} seed {seed}: {cell.describe()}; "
              f"set-up {setup_s:.3f} s (compile {compiles.seconds:.3f} s), "
              f"warm call checksum {checksum(warm)}", file=err, flush=True)
        before = compiles.backend_compiles
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
            calls, traced = window(cell, seconds, tmp if trace else None)
            in_window = compiles.backend_compiles - before
            reduction = {}
            if trace:
                reduction = reduce.reduce(*reduce.read_xplane(tmp))
    info["memory_peak_bytes"] = peak_bytes(jax)
    cell.release()

    span = calls[-1][1] - calls[0][0]
    e2e = {"sim_windows_per_s": len(calls) * cell.point_windows / span,
           "setup_s": setup_s}
    t_ref = time.perf_counter()
    ref = cell.reference()
    t_cmp = time.perf_counter()
    verdict = compare.judge(cell.kind, [c[2] for c in calls], ref,
                            spec["config"]["limits"])
    t_end = time.perf_counter()
    print(f"bench: {len(calls)} calls in {span:.3f} s, checksum of the "
          f"last {checksum(calls[-1][2])}", file=err)
    print(f"bench: {call_times(calls)}", file=err)
    print(f"bench: reference {t_cmp - t_ref:.3f} s, comparison "
          f"{t_end - t_cmp:.3f} s", file=err)
    if reduction:
        print(f"bench: the trace holds ops of {len(reduction['call_gap_s'])}"
              f" of {traced} traced calls; the last op ends "
              f"{1e3 * reduction['op_tail_s']:.3f} ms before the last "
              "traced call", file=err)

    if trace:
        ctx = dict(cell=cell, calls=calls, traced=traced, trace=reduction,
                   compiles_in_window=in_window)
        metrics = {}
        for m in spec["per_layer"]:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduction:
            info["busy_s"] = reduction["busy_s"]
            info["window_s"] = reduction["window_s"]
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}

    limits = spec["config"]["limits"]
    checks = {n: {"value": v, "limit": limits[n]}
              for n, v in verdict["numbers"].items()}
    result = {"correct": verdict["correct"],
              "attempted": verdict["attempted"],
              "failed": verdict["failed"], "metrics": metrics,
              "device": info}
    if trace and reduction:
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["checks"] = checks
    for n, c in checks.items():
        print(f"check {n}: {c['value']!r} (limit {c['limit']!r})", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0

