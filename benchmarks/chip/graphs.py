"""The engine cells' input graphs, made from a configuration's sizes:
undirected edge lists with each edge once and duplicates merged into
integer weights.

Copies of ``repro.graph.generators.grid3d`` and ``rmat`` (the same
7-point stencil and the same R-MAT bit recursion, here with Graph 500's
random relabelling of the vertices), kept here so that no later change to
the program changes the benchmark's inputs.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

Edges = Tuple[int, np.ndarray, np.ndarray, np.ndarray]   # n, u, v, w


def grid3d(nx: int, ny: int, nz: int) -> Edges:
    """3-D 7-point-stencil mesh: ``nx * ny * nz`` vertices."""
    idx = np.arange(nx * ny * nz).reshape(nx, ny, nz)
    u = np.concatenate([idx[:-1, :, :].ravel(), idx[:, :-1, :].ravel(),
                        idx[:, :, :-1].ravel()])
    v = np.concatenate([idx[1:, :, :].ravel(), idx[:, 1:, :].ravel(),
                        idx[:, :, 1:].ravel()])
    return _merge(nx * ny * nz, u, v)


def rmat(scale: int, edgefactor: int, a: float, b: float, c: float,
         seed: int) -> Edges:
    """R-MAT graph with the Graph 500 recursion: ``2**scale`` vertices,
    ``edgefactor * 2**scale`` generated edges, the vertex labels then
    permuted at random as Graph 500's generator does (so a vertex's id
    says nothing of its degree), self-loops dropped and duplicates
    merged."""
    n, m = 1 << scale, edgefactor << scale
    rng = np.random.default_rng(seed)
    u = np.zeros(m, dtype=np.int64)
    v = np.zeros(m, dtype=np.int64)
    for _ in range(scale):
        r = rng.random(m)
        u = 2 * u + ((r >= a + b) & (r < a + b + c)) + (r >= a + b + c)
        v = 2 * v + ((r >= a) & (r < a + b)) + (r >= a + b + c)
    label = rng.permutation(n)
    return _merge(n, label[u], label[v])


def _merge(n: int, u: np.ndarray, v: np.ndarray) -> Edges:
    u, v = np.asarray(u, np.int64), np.asarray(v, np.int64)
    keep = u != v
    lo, hi = np.minimum(u[keep], v[keep]), np.maximum(u[keep], v[keep])
    key, w = np.unique(lo * n + hi, return_counts=True)
    return n, key // n, key % n, w.astype(np.float64)


def make(spec: Dict) -> Edges:
    """The graph a configuration describes (an R-MAT graph from the
    configuration's own ``graph_seed``, so that every run places the same
    graph and a run's seed changes only how it is placed)."""
    family = spec["family"]
    if family == "grid3d":
        return grid3d(spec["nx"], spec["ny"], spec["nz"])
    if family == "rmat":
        return rmat(spec["scale"], spec["edgefactor"], spec["a"], spec["b"],
                    spec["c"], spec["graph_seed"])
    raise ValueError(f"unknown graph family {family!r}")
