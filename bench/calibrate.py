"""The readings the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,...,12 \
        --control-seeds 1,2,3

For each seed: one call of the program at the cell's own size and the
plain reference over the same inputs (the lower reading: what sound
runs give).  For each control seed: the reference computed in bfloat16
in the program's place (the control: the nearest precision below the
float32 the configuration states), against the float32 reference.
Seeds that draw the same inputs (a Mess cell has five write mixes) are
run once.  One JSON line per reading; the last line sums them up:
for each compared number, the largest sound reading and the smallest
control reading.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def _key(cell) -> tuple:
    if cell.kind == "mess":
        return ("mess", cell.write_mix)
    return ("replay",) + tuple(a[0].tobytes() for a in cell.apps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",")]

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    from harness import compare
    from harness.cells import Cell
    from harness.spec import load_cell

    spec = load_cell(args.workload)
    sound, low = {}, {}
    seen = {}
    for seed in dict.fromkeys(seeds + control):
        cell = Cell(spec, seed)
        key = _key(cell)
        if key not in seen:
            cell.prepare()
            got = cell.call()
            cell.release()
            want = cell.reference()
            seen[key] = (got, want, {})
        got, want, ctl = seen[key]
        rows = []
        if seed in seeds:
            rows.append(("sound", compare.point_gaps(cell.kind, got, want),
                         sound))
        if seed in control:
            if "bf16" not in ctl:
                ctl["bf16"] = cell.reference(jnp.bfloat16)
            rows.append(("control",
                         compare.point_gaps(cell.kind, ctl["bf16"], want),
                         low))
        for what, gaps, acc in rows:
            reading = {n: float(g.max()) for n, g in gaps.items()}
            for n, v in reading.items():
                acc.setdefault(n, []).append(v)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "inputs": cell.describe(), "what": what,
                              "reading": reading}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "lower": {n: max(v) for n, v in sound.items()},
                      "upper": {n: min(v) for n, v in low.items()}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
