"""DAMOV-style application trace generators, as plain numpy arrays.

Each generator returns ``(delta, is_write, dep, footprint_lines)``: the
per-access cache-line delta from the previous access, the write flag,
the depends-on-previous flag (all int32, one entry per access) and the
per-core footprint the lines wrap in.  The harness turns them into the
program's trace type only at the call, and the reference reads them as
they are.  Generation is deterministic from ``(kernel name, seed)``.
"""
from __future__ import annotations

import zlib

import numpy as np


def _rng(name: str, seed: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed, zlib.crc32(name.encode())])))


def _arrays(lines, is_write, dep, footprint: int):
    lines = np.asarray(lines, np.int64) % footprint
    delta = np.diff(lines, prepend=0).astype(np.int32)
    return (delta, np.asarray(is_write, np.int32),
            np.asarray(dep, np.int32), int(footprint))


def stream(n, footprint, seed):
    """STREAM triad: two streaming reads and one write per element."""
    i = np.arange(n)
    which = i % 3
    lines = (which * (footprint // 3) + i // 3) % footprint
    return _arrays(lines, which == 2, np.zeros(n), footprint)


def gups(n, footprint, seed):
    """Random-access updates: read a random line, write it back."""
    target = _rng("gups", seed).integers(0, footprint, size=(n + 1) // 2)
    lines = np.repeat(target, 2)[:n]
    return _arrays(lines, np.arange(lines.shape[0]) % 2,
                   np.zeros(lines.shape[0]), footprint)


def stencil3d(n, footprint, seed):
    """7-point 3-D stencil: seven neighbour reads, one write per point."""
    nx = max(int(round(footprint ** (1 / 3))), 4)
    nz = max(footprint // (nx * nx), 1)
    i = np.arange(n // 8)
    center = (i * 7919) % (nx * nx * max(nz - 2, 1)) + nx * nx
    offs = np.array([0, -1, +1, -nx, +nx, -nx * nx, +nx * nx])
    reads = (center[:, None] + offs[None, :]) >> 3
    writes = (center >> 3) + footprint // 2
    lines = np.concatenate([reads, writes[:, None]], axis=1).reshape(-1)[:n]
    is_write = np.zeros(lines.shape[0], np.int32)
    is_write[7::8] = 1
    return _arrays(lines, is_write, np.zeros(lines.shape[0]), footprint)


def spmv(n, footprint, seed, nnz_per_row=6):
    """CSR SpMV: per row, stream the index line, gather x, write y."""
    r = _rng("spmv", seed)
    lines, is_write = [], []
    for row in range(n // (nnz_per_row + 2) + 1):
        lines.append(row)
        lines.extend(footprint // 2
                     + r.integers(0, footprint // 4, size=nnz_per_row))
        lines.append(3 * footprint // 4 + row)
        is_write.extend([0] * (nnz_per_row + 1) + [1])
    lines = np.asarray(lines[:n])
    return _arrays(lines, is_write[:n], np.zeros(lines.shape[0]), footprint)


def pointer_chase(n, footprint, seed):
    """Linked-list traversal: every load depends on the previous one."""
    lines = _rng("pointer_chase", seed).integers(0, footprint, size=n)
    dep = np.ones(n, np.int32)
    dep[0] = 0
    return _arrays(lines, np.zeros(n), dep, footprint)


def bfs_frontier(n, footprint, seed, degree=4):
    """BFS frontier expansion: a vertex, then a dependent gather burst."""
    r = _rng("bfs", seed)
    lines, dep = [], []
    for v in range(n // (degree + 1) + 1):
        lines.append(v)
        dep.append(0)
        lines.extend(footprint // 2
                     + r.integers(0, footprint // 2, size=degree))
        dep.extend([1] + [0] * (degree - 1))
    lines = np.asarray(lines[:n])
    return _arrays(lines, np.zeros(lines.shape[0]), dep[:n], footprint)


GENERATORS = {
    "stream": stream,
    "gups": gups,
    "stencil3d": stencil3d,
    "spmv": spmv,
    "pointer_chase": pointer_chase,
    "bfs_frontier": bfs_frontier,
}


def make_apps(names, n: int, footprint: int, seed: int) -> list:
    """The named applications' arrays, in order."""
    unknown = [a for a in names if a not in GENERATORS]
    if unknown:
        raise ValueError(f"unknown application(s) {unknown}; "
                         f"one of {sorted(GENERATORS)}")
    return [GENERATORS[a](n, footprint, seed) for a in names]
