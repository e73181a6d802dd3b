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
