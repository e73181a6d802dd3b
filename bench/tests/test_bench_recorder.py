"""The readers of the program's own spans and counters (`repro.obs.spans`):
on hand-made recorder contents, and in whole traced runs at a small size."""
import collections

import pytest

from conftest import small_spec
from harness.spec import load_benchmark, load_cell, metric_reader
from test_bench_metrics import _ctx
from test_bench_run import _result

RECORDER = ("rerun_row_share", "launched_steps_per_window", "event_step_fill",
            "dense_ms_per_call", "driver_self_ms_per_call")


def _one_replay_call(t):
    """What `replay_suite` records in a call of the damov6 cell that
    starts at ``t``: 5 of 6 rows re-run dense, the event pass using half
    of its budget."""
    from repro.obs.spans import Count, Span

    config = load_cell("replay-ddr4-s07.damov6")["config"]
    windows, kept = config["windows"], config["windows"] - config["warmup"]
    return [
        Span("repro.replay.inputs", "repro.replay.suite", t + 0.1, t + 0.2),
        Span("repro.replay.event.fetch", "repro.replay.event", t + 1, t + 3),
        Span("repro.replay.event", "repro.replay.suite", t + 0.5, t + 3),
        Count("repro.rows.event", t + 3, 6),
        Count("repro.steps.launched", t + 3, 6 * windows * 199),
        Count("repro.steps.event_used", t + 3, 3 * kept * 199),
        Count("repro.steps.event_budget", t + 3, 6 * kept * 199),
        Span("repro.replay.dense.fetch", "repro.replay.dense", t + 4, t + 7),
        Span("repro.replay.dense", "repro.replay.suite", t + 3.5, t + 7),
        Count("repro.rows.dense", t + 7, 0),
        Count("repro.rows.rerun", t + 7, 5),
        Count("repro.steps.launched", t + 7, 5 * windows * 635),
        Span("repro.replay.runtime", "repro.replay.suite", t + 7, t + 7.5),
        Span("repro.replay.suite", None, t, t + 8),
    ]


@pytest.fixture
def recorded(monkeypatch):
    """The recorder holding a warm call and three window calls."""
    from repro.obs import spans

    ring = collections.deque(maxlen=spans.RING)
    for t in (0.0, 10.0, 20.0, 30.0):
        ring.extend(_one_replay_call(t))
    # a stray entry between calls belongs to no call
    ring.append(spans.Count("repro.rows.rerun", 9.5, 100))
    monkeypatch.setattr(spans, "_ring", ring)
    return ring


@pytest.mark.parametrize("traced", [0, 1, 3])
def test_recorder_readers_on_a_hand_made_replay(recorded, traced):
    ctx = _ctx("replay-ddr4-s07.damov6")
    ctx["calls"] = [(t, t + 9.0, {}) for t in (10.0, 20.0, 30.0)]
    ctx["traced"] = traced
    got = {n: metric_reader(n)(ctx) for n in RECORDER}
    assert got["rerun_row_share"] == pytest.approx(5 / 6)
    assert got["launched_steps_per_window"] == pytest.approx(
        (6 * 199 + 5 * 635) / 6)
    assert got["event_step_fill"] == pytest.approx(0.5)
    assert got["dense_ms_per_call"] == pytest.approx(3500.0)
    # 8 s in the suite span less 2 + 3 s of fetches
    assert got["driver_self_ms_per_call"] == pytest.approx(3000.0)


def test_recorder_readers_skip_the_traced_calls(recorded):
    from repro.obs import spans

    # the traced call ran its dense pass twice as long
    recorded.append(spans.Span("repro.replay.dense", "repro.replay.suite",
                               13.5, 17.0))
    ctx = _ctx("replay-ddr4-s07.damov6")
    ctx["calls"] = [(t, t + 9.0, {}) for t in (10.0, 20.0, 30.0)]
    ctx["traced"] = 1
    assert metric_reader("dense_ms_per_call")(ctx) == pytest.approx(3500.0)
    ctx["traced"] = 0
    assert metric_reader("dense_ms_per_call")(ctx) == pytest.approx(
        3500.0 + 3500.0 / 3)


def test_recorder_readers_read_nothing_without_the_recorder(monkeypatch,
                                                            recorded):
    import sys

    import repro.obs

    ctx = _ctx("replay-ddr4-s07.damov6")
    # calls in which the program recorded nothing
    ctx["calls"] = [(40.0, 49.0, {})]
    assert all(metric_reader(n)(ctx) is None for n in RECORDER)
    # a program that has no recorder at all
    ctx["calls"] = [(10.0, 19.0, {})]
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert all(metric_reader(n)(ctx) is None for n in RECORDER)


def test_event_step_fill_needs_an_event_launch(monkeypatch):
    from repro.obs import spans

    windows = load_cell("mess-ddr4-s10.saturated")["config"]["windows"]
    ring = collections.deque([
        spans.Span("repro.mess.dense", "repro.mess.mix", 1.0, 2.0),
        spans.Span("repro.mess.mix", "repro.mess.sweep", 0.5, 2.5),
        spans.Span("repro.mess.sweep", None, 0.5, 2.5),
        spans.Count("repro.rows.dense", 2.0, 9),
        spans.Count("repro.steps.launched", 2.0, 9 * windows * 635)])
    monkeypatch.setattr(spans, "_ring", ring)
    ctx = _ctx("mess-ddr4-s10.saturated")
    ctx["calls"] = [(0.0, 3.0, {})]
    assert metric_reader("event_step_fill")(ctx) is None
    assert metric_reader("rerun_row_share")(ctx) == 0.0
    assert metric_reader("launched_steps_per_window")(ctx) == 635.0
    assert metric_reader("dense_ms_per_call")(ctx) == pytest.approx(1000.0)
    assert metric_reader("driver_self_ms_per_call")(ctx) == pytest.approx(
        2000.0)


def test_traced_mess_run_reports_the_recorder_metrics():
    result = _result(small_spec("mess-ddr4-s10.below-knee"), trace=True)
    assert result["correct"] is True
    got = {n: m["value"] for n, m in result["metrics"].items()}
    assert set(RECORDER) <= set(got)
    # both paces route to the event engine and none saturates
    assert got["rerun_row_share"] == 0.0
    assert got["launched_steps_per_window"] == got["scan_steps_per_window"]
    assert got["dense_ms_per_call"] == 0.0
    assert 0 < got["event_step_fill"] <= 1


def test_traced_replay_run_reads_the_recorder():
    # BENCHMARK.json does not yet list the replay cell for these metrics,
    # so the run is handed their entries here
    spec = small_spec("replay-ddr4-s07.damov6")
    spec["per_layer"] += [m for m in load_benchmark()["per_layer"]
                          if m["name"] in RECORDER]
    result = _result(spec, trace=True)
    assert result["correct"] is True
    got = {n: m["value"] for n, m in result["metrics"].items()}
    assert set(RECORDER) <= set(got)
    assert got["rerun_row_share"] == got["rerun_share"]
    assert got["launched_steps_per_window"] == pytest.approx(
        got["scan_steps_per_window"])
    assert 0 < got["event_step_fill"] <= 1
    assert got["driver_self_ms_per_call"] >= 0
