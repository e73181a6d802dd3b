"""The harness's tests run on the CPU, at small sizes."""
import copy
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_spec(name: str, windows: int = 3, warmup: int = 1,
               paces: int = 2, accesses: int = 512) -> dict:
    """A cell's files as loaded, cut to a size a test can run."""
    from harness.spec import load_cell

    spec = copy.deepcopy(load_cell(name))
    spec["config"]["windows"], spec["config"]["warmup"] = windows, warmup
    traffic = spec["traffic"]
    if traffic["kind"] == "mess":
        traffic["paces"] = traffic["paces"][:paces]
    else:
        traffic["accesses"] = accesses
    return spec


#: configurations of other memory devices that no cell of
#: ``BENCHMARK.json`` runs yet, kept for the tests
DEVICE_CONFIGS = BENCH / "tests" / "configs"


def bench_with(config_file, traffic: str) -> tuple:
    """``BENCHMARK.json`` with one more cell: the configuration in
    ``config_file`` (any path) under the traffic file ``traffic``.
    Returns the benchmark and the cell's name."""
    import json

    from harness.spec import load_benchmark

    name = json.loads(pathlib.Path(config_file).read_text())["name"]
    bench = load_benchmark()
    bench["configs"].append(dict(name=name, file=str(config_file)))
    cell = f"{name}.{traffic}"
    bench["workloads"].append(dict(name=cell, config=name, traffic=traffic,
                                   chips=1))
    return bench, cell


def device_spec(config: str, traffic: str = "saturated", windows: int = 4,
                warmup: int = 1, paces=(2, 48), apps: int = 1,
                accesses: int = 512) -> dict:
    """A test-only device configuration under a traffic file, loaded as
    a cell and cut to a size a test can run: for Mess, paces that the
    router sends to the event engine (2) and to the dense one (48)."""
    from harness.spec import load_cell

    bench, name = bench_with(DEVICE_CONFIGS / f"{config}.json", traffic)
    spec = copy.deepcopy(load_cell(name, bench))
    spec["config"]["windows"], spec["config"]["warmup"] = windows, warmup
    traffic = spec["traffic"]
    if traffic["kind"] == "mess":
        traffic["paces"] = list(paces)
    else:
        traffic["apps"] = traffic["apps"][:apps]
        traffic["accesses"] = accesses
    return spec
