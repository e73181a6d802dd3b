"""The per-layer metric readers, on the cells' own static shapes."""
import numpy as np
import pytest

from conftest import small_spec
from harness.cells import Cell
from harness.spec import load_cell, metric_reader


def _ctx(name, out=None, trace=None, traced=0):
    from repro.core import mess

    mess.load_event_calibration()
    cell = Cell(load_cell(name), seed=0)
    cell.prepare()
    return dict(cell=cell, calls=[(0.0, 1.0, out or {})], traced=traced,
                trace=trace or {}, compiles_in_window=0)


@pytest.mark.parametrize("name, steps", [
    ("mess-ddr4-s10.saturated", 635.0),
    ("mess-ddr4-s10.below-knee", 199.0),
])
def test_scan_steps_per_window_on_ddr4_stage10(name, steps):
    assert metric_reader("scan_steps_per_window")(_ctx(name)) == steps


def test_replay_rerun_counts_flagged_rows():
    sat = np.array([10, 9, 0, 12, 0, 7])
    ctx = _ctx("replay-ddr4-s07.damov6", out={"weave_sat": sat})
    assert metric_reader("rerun_share")(ctx) == pytest.approx(4 / 6)
    assert metric_reader("scan_steps_per_window")(ctx) == pytest.approx(
        (6 * 199 + 4 * 635) / 6)
    assert metric_reader("rerun_share")(_ctx("mess-ddr4-s10.saturated")) \
        is None


def test_trace_readers():
    red = dict(busy_s=2.0, window_s=2.5, call_gap_s=[0.01, 0.03])
    ctx = _ctx("mess-ddr4-s10.saturated", trace=red, traced=2)
    windows = 2 * 9 * ctx["cell"].windows
    assert metric_reader("device_ms_per_window")(ctx) == pytest.approx(
        2000.0 / windows)
    assert metric_reader("host_gap_ms_per_call")(ctx) == pytest.approx(20.0)
    assert metric_reader("compiles_in_window")(ctx) == 0
    empty = _ctx("mess-ddr4-s10.saturated")
    assert metric_reader("device_ms_per_window")(empty) is None
    assert metric_reader("host_gap_ms_per_call")(empty) is None


def test_seed_draws_inputs():
    spec = small_spec("mess-ddr4-s10.saturated")
    mixes = {Cell(spec, s).write_mix for s in range(40)}
    assert mixes == set(spec["traffic"]["write_mixes"])
    big = 2 ** 31 + 12345
    assert Cell(spec, big).write_mix == Cell(spec, big).write_mix
    rspec = small_spec("replay-ddr4-s07.damov6")
    a, b = Cell(rspec, big).apps, Cell(rspec, big).apps
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
