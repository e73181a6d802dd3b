"""The measured window's length of work, the traced part of it, and
what a run prints about its calls."""
import io
import json
import time

import pytest

from conftest import ROOT, small_spec
from harness import reduce, runner
from harness.spec import load_benchmark, load_cell

BENCH = load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
CALL = reduce.CALL


class _Clock:
    """``time`` for the runner: only ``perf_counter``, moved by hand."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


class _StubCell:
    """A cell whose every call takes ``seconds`` on the clock."""

    def __init__(self, clock, seconds):
        self.clock, self.seconds = clock, seconds

    def call(self):
        self.clock.now += self.seconds
        return {}


@pytest.mark.parametrize("call_s, seconds, traced, calls", [
    (0.4, 3.0, 3, 8),     # calls end at 0.4, 0.8, 1.2: the third is past
    (2.5, 6.0, 1, 3),     # one call longer than the traced seconds
    (0.3, 0.5, 2, 2),     # a window shorter than the traced seconds
])
def test_traced_window_stops_after_the_first_call_past_trace_seconds(
        monkeypatch, call_s, seconds, traced, calls):
    import jax

    assert runner.TRACE_SECONDS == 1.0
    clock = _Clock()
    monkeypatch.setattr(runner, "time", clock)
    cell = _StubCell(clock, call_s)
    events = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: events.append(("start", clock.now)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: events.append(("stop", clock.now)))
    got, n_traced = runner.window(cell, seconds, "unused")
    assert (n_traced, len(got)) == (traced, calls)
    # the trace stops once, at the end of the last traced call
    assert events == [("start", 0.0), ("stop", got[traced - 1][1])]
    assert got[traced - 1][1] >= min(runner.TRACE_SECONDS, seconds)
    assert all(t1 - t0 == pytest.approx(call_s) for t0, t1, _ in got)


def test_untraced_window_never_starts_the_profiler(monkeypatch):
    import jax

    clock = _Clock()
    monkeypatch.setattr(runner, "time", clock)

    def refuse(*a, **k):
        raise AssertionError("the profiler ran in an untraced window")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(jax.profiler, "stop_trace", refuse)
    got, n_traced = runner.window(_StubCell(clock, 0.4), 3.0, None)
    assert (n_traced, len(got)) == (0, 8)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_runs_what_reduced_records(conf):
    """Windows and warm-up are the ones ``reduced`` records, and both
    the run and the paper keep three windows for one of warm-up."""
    config = json.loads((ROOT / conf["file"]).read_text())
    cut = config["reduced"]
    for key in ("windows", "warmup"):
        assert config[key] == cut[key]["run"]
        assert cut[key]["run"] < cut[key]["paper"]
    assert config["windows"] == 3 * config["warmup"]
    assert cut["windows"]["paper"] == 3 * cut["warmup"]["paper"]


@pytest.mark.parametrize("name", CELLS)
def test_small_spec_shrinks_the_windows(name):
    full, small = load_cell(name)["config"], small_spec(name)["config"]
    assert (small["windows"], small["warmup"]) == (3, 1)
    assert small["windows"] < full["windows"]


def test_reduce_reads_up_to_the_last_op():
    """A profiler out of room for op events keeps recording calls: the
    window ends at the last op and the calls after it are left out."""
    spans = [(0.0, 1.0, CALL), (1.1, 2.0, CALL), (2.1, 3.0, CALL)]
    ops = {"d": [(0.1, 0.9, "%a = f32[] fusion()"),
                 (1.2, 1.5, "%b = f32[] fusion()")]}
    red = reduce.reduce(ops, spans)
    assert red["window_s"] == pytest.approx(1.5)
    assert red["busy_s"] == pytest.approx(1.1)
    # the call in which the ops stop counts whole; the third is left out
    assert red["call_gap_s"] == pytest.approx([0.2, 0.6])
    assert red["op_tail_s"] == pytest.approx(1.5)
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(0.4)
    whole = reduce.reduce({"d": ops["d"] + [(2.2, 2.95, "%c = f32[] x()")]},
                          spans)
    # all three calls are read; the window still ends at the last op
    assert whole["window_s"] == pytest.approx(2.95)
    assert len(whole["call_gap_s"]) == 3
    assert whole["op_tail_s"] == pytest.approx(0.05)


def test_call_times_gives_quartiles_longest_and_gaps():
    calls = [(0.0, 0.1, {}), (0.2, 0.4, {}), (0.5, 0.6, {}), (0.6, 1.0, {})]
    line = runner.call_times(calls)
    assert "quartiles 100.000 / 150.000 / 350.000" in line
    assert "longest 400.000 (call 4 of 4)" in line
    assert "between calls median 100.000 ms, longest 100.000 ms" in line
    one = runner.call_times(calls[:1])
    assert "quartiles 100.000 / 100.000 / 100.000" in one
    assert "between calls median 0.000 ms" in one


def test_run_prints_call_times_apart_from_the_metrics():
    out, err = io.StringIO(), io.StringIO()
    rc = runner.run(small_spec("mess-ddr4-s10.below-knee"), 2 ** 31 + 17,
                    0.5, False, time.perf_counter(), require_chip=False,
                    out=out, err=err)
    assert rc == 0
    lines = err.getvalue().splitlines()
    calls = next(i for i, ln in enumerate(lines) if " calls in " in ln)
    assert "call ms quartiles" in lines[calls + 1]
    assert lines[calls + 2].startswith("bench: reference ")
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert set(result["metrics"]) == {"sim_windows_per_s", "setup_s"}
    assert "quartiles" not in json.dumps(result)
