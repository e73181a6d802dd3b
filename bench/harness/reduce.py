"""From a profiler trace to device busy time, idle gaps and their causes.

Everything below the reader works on plain lists of ``(start, end)``
or ``(start, end, name)`` tuples in seconds, so the arithmetic is tested
on hand-made lists.  The reader takes the device operations from the
``XLA Ops`` line of every ``/device:`` plane (asynchronous copies, on
their own line, overlap the ops that wait on them and are left out) and
the benchmark's own host spans from the ``/host:`` planes.
"""
from __future__ import annotations

import glob
import os

DEVICE_LINE = "XLA Ops"
#: the host span the harness writes around each call
CALL = "bench.call"


def union(intervals) -> list:
    """Sorted, merged ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted((s, e) for s, e, *_ in intervals if e > s):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def covered(merged, lo, hi) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo, hi) -> list:
    """The ``(start, end)`` stretches of ``[lo, hi]`` the intervals leave."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label(t, spans, default="between") -> str:
    """Short name of the host span that holds time ``t``."""
    for s, e, name in spans:
        if s <= t <= e:
            return name.rsplit(".", 1)[-1]
    return default


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def reduce(device_ops: dict, spans: list, top: int = 10) -> dict:
    """Busy time, idle gaps and host-gap per call over the traced calls.

    Args:
        device_ops: ``{device: [(start, end, op name), ...]}`` in seconds.
        spans: the harness's host spans ``[(start, end, name), ...]``;
            the window runs from the first call's start to the last
            call's end, or to the last op on any device where that is
            earlier: a profiler that has run out of room for op events
            goes on recording host spans, and the calls that start after
            its last op are left out.
    Returns:
        ``busy_s`` (union of op intervals, averaged over the devices),
        ``window_s``, ``call_gap_s`` (per call read: time in the call
        with no op on any device), ``op_tail_s`` (the last call's end
        less the last op's end), and ``device_ops`` / ``idle_gaps``
        lists of ``[name, seconds]`` (the ops that took most time
        summed over the devices; the longest idle stretches of the
        first device, named by the host span they fall in).
    """
    calls = sorted((s, e) for s, e, n in spans if n == CALL)
    if not calls or not any(device_ops.values()):
        return {}
    merged = {d: union(evs) for d, evs in device_ops.items()}
    anyop = union([iv for m in merged.values() for iv in m])
    last_op = anyop[-1][1]
    lo, hi = calls[0][0], min(calls[-1][1], last_op)
    tail = calls[-1][1] - last_op
    calls = [(s, e) for s, e in calls if s < hi]
    if not calls:
        return {}
    busy = [covered(m, lo, hi) for m in merged.values()]
    per_op: dict = {}
    for evs in device_ops.values():
        for s, e, name in evs:
            if e > lo and s < hi:
                key = op_name(name)
                per_op[key] = per_op.get(key, 0.0) + min(e, hi) - max(s, lo)
    first = merged[sorted(merged)[0]]
    idle = sorted(((label((s + e) / 2, spans), e - s)
                   for s, e in gaps(first, lo, hi)), key=lambda x: -x[1])
    return dict(
        busy_s=sum(busy) / len(busy), window_s=hi - lo,
        call_gap_s=[(e - s) - covered(anyop, s, e) for s, e in calls],
        op_tail_s=tail,
        device_ops=[[k, v] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[n, v] for n, v in idle[:top]])


def read_xplane(log_dir: str):
    """``(device_ops, spans)`` of the newest trace under ``log_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}, []
    data = jax.profiler.ProfileData.from_file(files[-1])
    device_ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    device_ops[plane.name] = [
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9, e.name)
                             for e in line.events
                             if e.name == CALL)
    return {d: v for d, v in device_ops.items() if v}, spans
