"""Memory devices no cell runs yet: on DDR5-4800 (12 sub-channels,
same-bank refresh, the generic address fold) and on HBM2e behind two
sockets, the plain reference equals the program bit for bit, through
the knee router and through the dense engine alone."""
import dataclasses
import json

import numpy as np
import pytest

from conftest import DEVICE_CONFIGS, bench_with, device_spec
from harness import compare
from harness.cells import Cell
from harness.spec import load_cell

SEED = 2 ** 31 + 29


def _partitioned(tmp_path):
    """The two-socket HBM2e configuration with each socket owning half
    of the channels."""
    config = json.loads(
        (DEVICE_CONFIGS / "mess-hbm2e-s10-2s.json").read_text())
    config["program"]["socket_channels"] = "partitioned"
    config["platform"]["socket_channels"] = "partitioned"
    path = tmp_path / "partitioned.json"
    path.write_text(json.dumps(config))
    bench, name = bench_with(path, "saturated")
    spec = load_cell(name, bench)
    spec["config"]["windows"], spec["config"]["warmup"] = 4, 1
    spec["traffic"]["paces"] = [2, 48]
    return spec


def _assert_equal(kind, got, want):
    gaps = compare.point_gaps(kind, got, want)
    assert {n: float(g.max()) for n, g in gaps.items()} == \
        {n: 0.0 for n in compare.NUMBERS[kind]}
    for key, value in want.items():
        if key in got:
            np.testing.assert_array_equal(np.asarray(got[key]), value,
                                          err_msg=key)


@pytest.mark.parametrize("engine", ["routed", "dense"])
@pytest.mark.parametrize("device", ["mess-ddr5-s10", "mess-hbm2e-s10-2s",
                                    "mess-hbm2e-s10-2s-partitioned"])
def test_reference_equals_the_mess_sweep(device, engine, tmp_path):
    if device.endswith("-partitioned"):
        spec = _partitioned(tmp_path)
    else:
        spec = device_spec(device)
    cell = Cell(spec, SEED)
    cell.prepare()
    if engine == "dense":
        cell.cfg = dataclasses.replace(cell.cfg, weave="dense")
    got = cell.call()
    # one pace below the knee and one above: both engines run
    engines = {"routed": {"event", "dense"}, "dense": {"dense"}}[engine]
    assert {e for e, _ in cell.routes(got)} == engines
    _assert_equal("mess", got, cell.reference())


def test_reference_equals_a_ddr5_replay():
    spec = device_spec("mess-ddr5-s10", "damov6")
    cell = Cell(spec, SEED)
    cell.prepare()
    got = cell.call()
    cell.release()
    assert len(got["n_rd"]) == 1 and got["n_rd"][0] > 0
    _assert_equal("replay", got, cell.reference())
