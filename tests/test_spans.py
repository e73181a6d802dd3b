"""The host span and counter recorder (`repro.obs.spans`), and the
counters the grid drivers record at each launch, at small sizes."""
import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_stage, mess
from repro.obs import spans
from repro.obs.spans import Count, Span

FAST = dict(windows=3, warmup=1)


@pytest.fixture
def ring(monkeypatch):
    """A fresh, small ring in place of the process-wide one."""
    fresh = collections.deque(maxlen=8)
    monkeypatch.setattr(spans, "_ring", fresh)
    return fresh


def test_spans_nest_and_name_their_parents(ring):
    with spans.span("a"):
        with spans.span("a.b"):
            with spans.span("a.b.c"):
                pass
        with spans.span("a.d"):
            pass

    @spans.span("e")
    def f(x):
        return x + 1

    assert f(1) == 2
    got = {s.name: s for s in ring}
    assert [s.name for s in ring] == ["a.b.c", "a.b", "a.d", "a", "e"]
    assert {n: s.parent for n, s in got.items()} == {
        "a": None, "a.b": "a", "a.b.c": "a.b", "a.d": "a", "e": None}
    for outer, inner in (("a", "a.b"), ("a.b", "a.b.c"), ("a", "a.d")):
        assert got[outer].start <= got[inner].start
        assert got[inner].end <= got[outer].end


def test_span_closes_on_error(ring):
    with pytest.raises(ValueError):
        with spans.span("bad"):
            raise ValueError
    with spans.span("next"):
        pass
    assert [(s.name, s.parent) for s in ring] == [("bad", None),
                                                  ("next", None)]


def test_ring_drops_the_oldest_entries(ring):
    for i in range(12):
        spans.count(f"c{i}", i)
    assert len(ring) == ring.maxlen == 8
    assert [e.name for e in ring] == [f"c{i}" for i in range(4, 12)]
    assert spans.RING >= 10_000


def test_interval_reads(ring):
    ring.extend([Span("x", None, 1.0, 2.0), Span("y", "x", 1.5, 3.5),
                 Count("n", 1.2, 3), Count("n", 2.5, 4), Count("m", 4.0, 1),
                 Span("z", None, 3.0, 4.0)])
    assert [s.name for s in spans.spans_between(1.0, 3.5)] == ["x", "y"]
    assert [s.name for s in spans.spans_between(0.0, 9.0)] == ["x", "y", "z"]
    assert [s.name for s in spans.spans_between(1.1, 3.9)] == ["y"]
    assert spans.spans_between(1.6, 3.9) == []
    assert spans.counts_between(1.0, 3.0) == {"n": 7}
    assert spans.counts_between(2.0, 4.0) == {"n": 4, "m": 1}
    assert spans.counts_between(5.0, 6.0) == {}
    t0 = time.perf_counter()
    spans.count("live", 2)
    assert spans.counts_between(t0, time.perf_counter()) == {"live": 2}


def test_self_seconds_leaves_out_waits_inside_the_roots():
    hand = [
        Span("r.route", "r", 0.0, 0.1),
        Span("r.event.fetch", "r.event", 0.2, 1.0),
        Span("r.event", "r", 0.1, 1.0),
        Span("r.dense.fetch", "r.dense", 1.1, 3.0),
        Span("r.dense", "r", 1.0, 3.0),
        Span("r", None, 0.0, 3.5),
        Span("s", None, 4.0, 4.5),
        Span("s.fetch", "s", 4.1, 4.4),
        # a wait whose root lies outside the list is not counted
        Span("q.fetch", "q", 5.0, 6.0),
    ]
    # roots 3.5 + 0.5, less the waits inside them: 0.8 + 1.9 + 0.3
    assert spans.self_seconds(hand) == pytest.approx(4.0 - 3.0)
    assert spans.self_seconds(hand[:6]) == pytest.approx(3.5 - 2.7)
    assert spans.self_seconds([]) == 0.0


def test_sweep_spans_land_on_the_profiler_host_plane(tmp_path):
    cfg = get_stage("05-addrmap", **FAST)
    mess.sweep(cfg, paces=(2, 48), write_mixes=(0,))      # compile first
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("outer.call"):
        mess.sweep(cfg, paces=(2, 48), write_mixes=(0,))
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    (outer,) = [e for e in events if e[0] == "outer.call"]
    ours = {n: (s, e) for n, s, e in events if n.startswith("repro.mess.")}
    assert {"repro.mess.sweep", "repro.mess.mix", "repro.mess.route",
            "repro.mess.event", "repro.mess.event.fetch",
            "repro.mess.dense", "repro.mess.dense.fetch",
            "repro.mess.merge"} <= set(ours)
    assert all(outer[1] <= s and e <= outer[2] for s, e in ours.values())


def _counted(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, spans.counts_between(t0, time.perf_counter())


def test_sweep_counts_its_launches_at_the_static_formula():
    cfg = get_stage("05-addrmap", **FAST)
    paces = (2, 48)
    ev = [p for p in paces if mess.event_covers(cfg, p)]
    _, counts = _counted(mess.sweep, cfg, paces=paces, write_mixes=(0, 32))
    dense = cfg.clock().ticks_per_window_static
    assert ev == [2]
    assert counts["repro.rows.event"] == 2
    assert counts["repro.rows.dense"] == 2
    assert counts.get("repro.rows.rerun", 0) == 0
    assert counts["repro.steps.launched"] == 2 * cfg.windows * (
        cfg.event_budget() + dense)
    assert counts["repro.steps.event_budget"] == \
        2 * (cfg.windows - cfg.warmup) * cfg.event_budget()
    assert 0 < counts["repro.steps.event_used"] <= \
        counts["repro.steps.event_budget"]

    _, counts = _counted(mess.sweep, dataclasses.replace(cfg, weave="dense"),
                         paces=paces, write_mixes=(0,))
    assert counts == {"repro.rows.dense": 2,
                      "repro.steps.launched": 2 * cfg.windows * dense}


def test_sweep_counts_a_forced_rerun(monkeypatch):
    """A tiny event budget at a hot pace, routed to the event engine
    anyway: the saturated points are re-run dense and counted so."""
    cfg = get_stage("04-model-correct", weave_events=16, **FAST)
    paces = (1, 64)
    monkeypatch.setattr(mess, "event_covers", lambda cfg, p: True)
    pv = jnp.asarray(paces, jnp.int32)
    first = jax.device_get(mess._sweep_fn(cfg)((pv, jnp.zeros_like(pv))))
    n_sat = int(np.sum(first["weave_sat"] > 0))
    assert n_sat >= 1
    _, counts = _counted(mess.sweep, cfg, paces=paces, write_mixes=(0,))
    assert counts["repro.rows.event"] == 2
    assert counts["repro.rows.rerun"] == n_sat
    assert counts.get("repro.rows.dense", 0) == 0
    assert counts["repro.steps.launched"] == cfg.windows * (
        2 * 16 + n_sat * cfg.clock().ticks_per_window_static)
    assert counts["repro.steps.event_used"] == int(np.sum(
        first["weave_events"]))
    assert counts["repro.steps.event_budget"] == 2 * 16 * (
        cfg.windows - cfg.warmup)


def test_replay_counts_the_rows_it_reruns():
    from repro.traces import replay_suite, stack_traces
    from repro.traces.kernels import gups, pointer_chase, stream
    from repro.traces.replay import _replay_fn

    batch = stack_traces([stream(n=192), gups(n=160),
                          pointer_chase(n=64)])
    cfg = get_stage("04-model-correct", windows=6, warmup=2)
    out, counts = _counted(replay_suite, cfg, batch)
    assert "weave_events" not in out
    first = jax.device_get(_replay_fn(cfg)(batch))
    n_sat = int(np.sum(out["weave_sat"] > 0))
    assert n_sat == int(np.sum(first["weave_sat"] > 0)) >= 1
    assert counts["repro.rows.event"] == 3
    assert counts["repro.rows.rerun"] == n_sat
    dense = cfg.clock().ticks_per_window_static
    assert counts["repro.steps.launched"] == cfg.windows * (
        3 * cfg.event_budget() + n_sat * dense)
    assert counts["repro.steps.event_used"] == int(np.sum(
        first["weave_events"]))
    assert counts["repro.steps.event_budget"] == \
        3 * (cfg.windows - cfg.warmup) * cfg.event_budget()


@pytest.mark.parametrize("weave", ["event", "dense"])
def test_window_loop_names_its_phases(weave):
    """The named scopes reach the compiled ops' metadata, which a device
    profile shows beside each op."""
    import re

    from repro.core.platform import run_point

    cfg = get_stage("05-addrmap", weave=weave, **FAST)
    hlo = jax.jit(lambda p, w: run_point(cfg, p, w)).lower(
        jnp.int32(2), jnp.int32(0)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    inner = ["tick"] + (["next_event"] if weave == "event" else [])
    for path in ["/bound/", "/inject/", "/aggregate/"] + [
            f"/weave/.*/{s}/" for s in inner]:
        assert any(re.search(path, n) for n in names), path
