"""Trace reduction: interval arithmetic and a trace recorded on the CPU."""
import pytest

from harness import reduce

CALL = reduce.CALL


def test_union_merges_overlaps_and_drops_empty():
    evs = [(5, 7, "b"), (0, 2, "a"), (1, 3, "a"), (3, 3, "z"), (3, 4, "c")]
    assert reduce.union(evs) == [(0, 4), (5, 7)]


def test_covered_and_gaps():
    merged = [(0, 4), (5, 7), (9, 12)]
    assert reduce.covered(merged, 2, 10) == pytest.approx(2 + 2 + 1)
    assert reduce.gaps(merged, 2, 10) == [(4, 5), (7, 9)]
    assert reduce.gaps(merged, -1, 13) == [(-1, 0), (4, 5), (7, 9),
                                           (12, 13)]
    assert reduce.gaps([], 1, 2) == [(1, 2)]


def test_reduce_attributes_idle_time_to_calls_and_between():
    spans = [(0.0, 1.0, CALL), (1.2, 2.0, CALL)]
    ops = {"/device:TPU:0": [(0.1, 0.9, "%fusion.1 = f32[] fusion()"),
                             (1.3, 1.9, "%while.2 = (s32[]) while()")],
           "/device:TPU:1": [(0.0, 1.0, "%fusion.1 = f32[] fusion()"),
                             (1.2, 2.0, "%copy.3 = s32[] copy()")]}
    red = reduce.reduce(ops, spans)
    assert red["window_s"] == pytest.approx(2.0)
    assert red["busy_s"] == pytest.approx((1.4 + 1.8) / 2)
    # no op on any device: only the time between the calls
    assert red["call_gap_s"] == pytest.approx([0.0, 0.0])
    names = [n for n, _ in red["device_ops"]]
    assert names[0] == "fusion.1"
    assert red["device_ops"][0][1] == pytest.approx(1.8)
    # the first device idles 0.1 s at each end (inside calls) and
    # 0.4 s around the host's time between the calls
    assert [n for n, _ in red["idle_gaps"]] == ["between", "call", "call"]
    assert [s for _, s in red["idle_gaps"]] == pytest.approx([0.4, 0.1, 0.1])
    one = reduce.reduce({"d": ops["/device:TPU:0"]}, spans)
    assert one["call_gap_s"] == pytest.approx([0.2, 0.2])


def test_reduce_without_calls_or_ops_reads_nothing():
    assert reduce.reduce({}, [(0, 1, CALL)]) == {}
    assert reduce.reduce({"d": [(0, 1, "x")]}, []) == {}


def test_read_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation(CALL):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    device_ops, spans = reduce.read_xplane(str(tmp_path))
    calls = [s for s in spans if s[2] == CALL]
    assert len(calls) == 3
    assert all(e > s for s, e, _ in calls)
    # the CPU backend has no /device: plane: nothing to reduce
    assert device_ops == {}
    assert reduce.reduce(device_ops, spans) == {}
    assert reduce.read_xplane(str(tmp_path / "none")) == ({}, [])
