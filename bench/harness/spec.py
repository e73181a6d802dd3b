"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` reads ``bench/configs/<config>.json`` (the
deployment: the program's stage and preset, the windows it simulates,
every size the reference needs, and the limits of the comparison) and
``bench/traffic/<traffic>.json`` (the mix of points or applications
one call simulates).  A per-layer metric ``<name>`` is read by
``bench/metrics/<name>.py``.  Nothing here knows any particular cell,
so a cell is added by adding files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(ValueError):
    """A name that ``BENCHMARK.json`` or the files under bench/ lack."""


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"unknown {what} {name!r}; one of "
                    f"{sorted(e['name'] for e in entries)}")


def _json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_cell(name: str, bench: dict | None = None) -> dict:
    """Everything one cell needs, found by its name.

    Returns ``workload`` (the entry of ``BENCHMARK.json``), ``config``
    and ``traffic`` (the parsed files), and ``end_to_end`` /
    ``per_layer`` (the metric entries the cell reports).
    """
    bench = load_benchmark() if bench is None else bench
    work = _entry(bench["workloads"], name, "workload")
    conf_entry = _entry(bench["configs"], work["config"], "configuration")
    config = json.loads((ROOT / conf_entry["file"]).read_text())
    if config.get("name") != work["config"]:
        raise SpecError(f"{conf_entry['file']} names {config.get('name')!r},"
                        f" not {work['config']!r}")
    traffic = _json("traffic", work["traffic"])

    def applies(metric):
        return name in metric.get("workloads", [name])

    return dict(workload=work, config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path.relative_to(ROOT)} for metric "
                        f"{name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
