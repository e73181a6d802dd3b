"""BENCHMARK.json and the files it names."""
import json
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
