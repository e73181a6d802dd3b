"""The main path compiles for a TPU v5e that is described, not attached.

The TPU compiler ships with jaxlib, so these tests hand it the real
programs at their real sizes and a v5e:2x2 topology: the paper-resolution
Mess sweep (both weave engines), the application-suite replay, the two
simulator Pallas kernels compiled (not interpreted), and the sharded
batch axis on a four-chip mesh.  Nothing runs, so they say nothing about
results or times; they catch what the chip's compiler would refuse.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

PRESET = "ddr4_2666"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _pace_batch(sharding):
    from repro.core.mess import DEFAULT_PACES
    s = jax.ShapeDtypeStruct((len(DEFAULT_PACES),), jnp.int32,
                             sharding=sharding)
    return (s, s)


def _computations(hlo_text):
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith(" "):
            comps[name].append(line)
    return comps


def _reachable(comps, roots):
    """The named computations and every computation they call."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", line)
            for group in re.findall(r"(?:branch|called)_computations="
                                    r"\{([^}]*)\}", line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


@pytest.mark.parametrize("weave,points", [("dense", 9), ("event", 5)])
def test_weave_step_has_no_gather_or_scatter(one_chip, weave, points):
    """The weave scan's loop body (`dram.tick`, and `dram.next_event` in
    the event engine) holds no gather or scatter at the Mess benchmark
    cells' shapes (9 and 5 paces, 12 windows): the TPU runs those one
    index at a time, and they held most of a scan step when `tick`
    read its bank fields with `take_along_axis`.  The whole body is
    searched, since the compiler drops the `op_name` of the gathers it
    rewrites."""
    from repro.core import get_stage
    from repro.core.mess import _sweep_fn
    cfg = get_stage("10-delay-buffer", preset=PRESET, weave=weave,
                    windows=12, warmup=4)
    s = jax.ShapeDtypeStruct((points,), jnp.int32, sharding=one_chip)
    text = _sweep_fn(cfg).lower((s, s)).compile().as_text()
    comps = _computations(text)
    bodies = re.findall(r' while\(.*body=%([\w.\-]+).*'
                        r'op_name="[^"]*/weave/while"', text)
    assert len(bodies) == 1, bodies
    body = _reachable(comps, bodies)
    assert len(body) > 1
    found = [line.strip()[:160] for c in sorted(body) for line in comps[c]
             if re.search(r"\s(gather|scatter)\(", line)]
    assert not found, found


@pytest.mark.parametrize("weave", ["event", "dense"])
def test_paper_resolution_sweep_compiles(one_chip, weave):
    from repro.core import get_stage
    from repro.core.mess import _sweep_fn
    cfg = get_stage("10-delay-buffer", preset=PRESET, weave=weave)
    assert cfg.windows == 96
    compiled = _sweep_fn(cfg).lower(_pace_batch(one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_app_suite_replay_compiles(one_chip):
    from repro.core import get_stage
    from repro.traces import make_suite, stack_traces
    from repro.traces.replay import _replay_fn
    _, traces = make_suite(n=8192)
    batch = _shapes(stack_traces(traces), one_chip)
    _replay_fn(get_stage("07-prefetch", preset=PRESET)).lower(
        batch).compile()


def test_addr_decode_kernel_compiles(one_chip):
    from repro.kernels.addr_decode import decode_packed
    lines = jax.ShapeDtypeStruct((100_000,), jnp.uint32, sharding=one_chip)
    compiled = decode_packed.lower(lines, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("row_hit_cap", [0, 4])
def test_frfcfs_select_kernel_compiles(one_chip, row_hit_cap):
    from repro.kernels.bank_timing import frfcfs_select
    plane = jax.ShapeDtypeStruct((6, 256), jnp.int32, sharding=one_chip)
    scalars = jax.ShapeDtypeStruct((6, 8), jnp.int32, sharding=one_chip)
    compiled = frfcfs_select.lower(*[plane] * 11, scalars,
                                   row_hit_cap=row_hit_cap,
                                   interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_batch_axis_compiles_on_four_chips(topo):
    from repro.core import get_stage, run_point
    from repro.core.shard import BATCH_AXIS, shard_mapped
    cfg = get_stage("10-delay-buffer", preset=PRESET)
    mesh = Mesh(topo.devices[:4], (BATCH_AXIS,))
    on_mesh = NamedSharding(mesh, PartitionSpec(BATCH_AXIS))
    s = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=on_mesh)
    program = shard_mapped(lambda pw: run_point(cfg, pw[0], pw[1]), mesh)
    compiled = jax.jit(program).lower((s, s)).compile()
    # elementwise along the batch: the program needs no collective
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute"):
        assert op not in text, op
