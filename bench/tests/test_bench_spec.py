"""BENCHMARK.json and the files it names."""
import json
import pathlib
import re

import pytest

from conftest import ROOT
from harness import compare
from harness.reference import Platform
from harness.spec import SpecError, load_benchmark, load_cell, metric_reader

BENCH = load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_by_name(name):
    spec = load_cell(name)
    config = spec["config"]
    assert config["name"] == spec["workload"]["config"]
    assert spec["traffic"]["kind"] in compare.NUMBERS
    assert set(compare.NUMBERS[spec["traffic"]["kind"]]) <= set(
        config["limits"])
    Platform.from_config(config)
    for m in spec["per_layer"]:
        assert callable(metric_reader(m["name"]))
    names = {m["name"] for m in spec["end_to_end"]}
    assert {"setup_s", "sim_windows_per_s"} <= names


def test_unknown_names_fail():
    with pytest.raises(SpecError, match="unknown workload"):
        load_cell("mess-ddr4-s10.nonesuch")
    with pytest.raises(SpecError, match="no reader"):
        metric_reader("nonesuch")


def test_benchmark_json_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = 24
    assert 2 + 14 * cells * (BENCH["run_seconds"] + 60) \
        + cells * 180 + 1200 <= 43200
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in BENCH[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        config = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(config["reduced"])
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


# ---- the platform group and the program's stage describe one machine ---

NEW_KEYS = ("address_map", "refresh", "rows_per_bank", "lines_per_row",
            "n_sockets", "socket_channels")


def _load_changed(tmp_path, config_file, change, traffic="saturated"):
    """``load_cell`` of a configuration rewritten by ``change``."""
    from conftest import bench_with

    config = json.loads(pathlib.Path(config_file).read_text())
    config["name"] += "-changed"
    change(config)
    path = tmp_path / "changed.json"
    path.write_text(json.dumps(config))
    bench, name = bench_with(path, traffic)
    return load_cell(name, bench)


def _prepare(spec):
    from harness.cells import Cell

    Cell(spec, 7).prepare()


@pytest.mark.parametrize("key", NEW_KEYS)
def test_config_lacking_a_platform_key_is_refused(tmp_path, key):
    with pytest.raises(SpecError, match=key):
        _load_changed(tmp_path, ROOT / "bench/configs/mess-ddr4-s10.json",
                      lambda c: c["platform"].pop(key))


@pytest.mark.parametrize("key", ["address_map", "refresh", "socket_channels"])
def test_unknown_platform_value_is_refused(tmp_path, key):
    def change(config):
        config["platform"][key] = "nonesuch"

    with pytest.raises(SpecError, match=f"'{key}' = 'nonesuch'"):
        _load_changed(tmp_path, ROOT / "bench/configs/mess-ddr4-s10.json",
                      change)


def test_skylake_xor_needs_the_ddr4_geometry(tmp_path):
    from conftest import DEVICE_CONFIGS

    def change(config):
        config["platform"]["address_map"] = "skylake_xor"

    with pytest.raises(SpecError, match="'address_map'.*DDR4 geometry"):
        _load_changed(tmp_path, DEVICE_CONFIGS / "mess-ddr5-s10.json",
                      change)


def test_program_key_that_stage_config_lacks_is_refused(tmp_path):
    def change(config):
        config["program"]["nonesuch"] = 1

    spec = _load_changed(tmp_path, ROOT / "bench/configs/mess-ddr4-s10.json",
                         change)
    with pytest.raises(SpecError, match="'nonesuch' is not a StageConfig"):
        _prepare(spec)


def _set(group, key, value):
    def change(config):
        config[group][key] = value
    return change


@pytest.mark.parametrize("config, change, key", [
    # a timing the preset does not have
    ("mess-ddr4-s10", _set("platform", "tCL", 20), "tCL"),
    # the queue of another backend
    ("replay-ddr4-s07", _set("platform", "queue_depth", 512), "queue_depth"),
    # the program asks for two sockets, the platform group states one
    ("mess-ddr4-s10", _set("program", "n_sockets", 2), "n_sockets"),
    # the program's stage runs the Skylake fold on DDR4
    ("mess-ddr4-s10", _set("platform", "address_map", "xor_fold"),
     "address_map"),
    # DDR5-4800 refreshes one bank at a time
    ("mess-ddr5-s10", _set("platform", "refresh", "all_bank"), "refresh"),
    # the two-socket HBM2e configuration on one socket
    ("mess-hbm2e-s10-2s", _set("program", "n_sockets", 1), "n_sockets"),
], ids=["timing", "queue", "sockets", "address_map", "refresh",
        "hbm2e_one_socket"])
def test_config_that_the_program_contradicts_is_refused(tmp_path, config,
                                                        change, key):
    from conftest import DEVICE_CONFIGS

    path = ROOT / "bench/configs" / f"{config}.json"
    if not path.is_file():
        path = DEVICE_CONFIGS / f"{config}.json"
    spec = _load_changed(tmp_path, path, change)
    with pytest.raises(SpecError, match=f"{key} differs"):
        _prepare(spec)
