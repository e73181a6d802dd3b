"""Benchmark aggregator: one section per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` runs the
paper-resolution sweeps (14 paces x 5 mixes, 96 windows); the default
is CI-speed (6 paces x 3 mixes, 48 windows).  The benchmark set comes
from the single registry in `benchmarks.registry` (``--list`` shows
it); ``--preset`` forwards a memory-device preset to the benchmarks
that accept one (fig2, app_validation).
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time

from benchmarks.registry import BENCHMARKS, get_benchmark
from repro.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (fig2,...)")
    ap.add_argument("--preset", default=None,
                    help="device preset for preset-aware benchmarks")
    ap.add_argument("--list", action="store_true",
                    help="list registered benchmarks and exit")
    args = ap.parse_args()

    if args.list:
        for spec in BENCHMARKS.values():
            print(f"{spec.name:16s} {spec.description}")
        return

    names = args.only.split(",") if args.only else list(BENCHMARKS)
    specs = [get_benchmark(n) for n in names]
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)
    print("name,us_per_call,derived")
    t0 = time.time()
    for spec in specs:
        print(f"# --- {spec.name} ---", file=sys.stderr)
        kw = {}
        if args.preset and "preset" in inspect.signature(
                spec.main).parameters:
            kw["preset"] = args.preset
        spec.main(full=args.full, **kw)
    print(f"# total {time.time() - t0:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
