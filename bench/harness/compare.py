"""The comparison that decides ``correct``.

Each number is the widest gap, over every point or application of every
call in the window, between what the program returned and what the
plain reference returns for the same inputs:

* ``view_rel_gap`` — the seven views (simulator, interface and
  application bandwidth and latency, chase latency), relative to the
  reference.  The bandwidth views are the served read and write counts
  times a constant of the configuration, so a served count that differs
  shows here;
* ``count_rel_gap`` (replay) — the served read and write counts
  relative to the reference's (at least 1), the runtime in windows
  relative to the reference's, and 1 for an application whose ``done``
  differs.  The runtime comes from the replay cursors, which the float
  precision of the latency estimates barely moves (the bfloat16 control
  left every runtime equal at 12 windows), so it shares a number with
  the counts that the control does move.

A value that is not finite counts as infinitely far.  The limits are the
configuration's ``limits``.
"""
from __future__ import annotations

import numpy as np

from harness.reference import VIEWS

NUMBERS = {"mess": ("view_rel_gap",),
           "replay": ("view_rel_gap", "count_rel_gap")}
_TINY = 1e-30


def _rel(got, want, floor=_TINY):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    gap = np.abs(got - want) / np.maximum(np.abs(want), floor)
    return np.where(np.isfinite(gap), gap, np.inf)


def point_gaps(kind: str, got: dict, want: dict) -> dict:
    """Per point: each number's gap, one array per number."""
    gaps = {"view_rel_gap": np.max(
        [_rel(got[k], want[k]) for k in VIEWS], axis=0)}
    if kind == "replay":
        served = [_rel(got[k], want[k], 1.0) for k in ("n_rd", "n_wr")]
        runtime = _rel(got["runtime_windows"], want["runtime_windows"])
        done = (np.asarray(got["done"]) != np.asarray(want["done"])) * 1.0
        gaps["count_rel_gap"] = np.max(served + [runtime, done], axis=0)
    return gaps


def judge(kind: str, outs: list, want: dict, limits: dict) -> dict:
    """Every call's outputs against the reference.

    Returns ``numbers`` (name -> widest gap), ``attempted`` (points of
    every call), ``failed`` (points with any gap over its limit) and
    ``correct``.
    """
    missing = [n for n in NUMBERS[kind] if n not in limits]
    if missing:
        raise ValueError(f"the configuration gives no limit for {missing}")
    numbers = {n: 0.0 for n in NUMBERS[kind]}
    attempted = failed = 0
    for got in outs:
        gaps = point_gaps(kind, got, want)
        over = np.zeros(len(gaps["view_rel_gap"]), bool)
        for n, g in gaps.items():
            numbers[n] = max(numbers[n], float(np.max(g)))
            over |= g > limits[n]
        attempted += over.size
        failed += int(over.sum())
    return dict(numbers=numbers, attempted=attempted, failed=failed,
                correct=bool(outs) and failed == 0)
