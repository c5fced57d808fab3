"""Plain reference of the placement objective on a balanced machine tree.

The tree is built from a configuration's ``levels`` (root side first:
fan-out and link bandwidth of each level); a link's per-byte cost is the
leaf level's bandwidth over its own. Nodes are numbered breadth first
(root 0, then each level left to right), compute bins are the leaves in
order, and a link is named by its child node.

Loads are exact sums in float64: every vertex's weight onto its bin, and
every cut edge's weight onto each link of the tree path between its two
bins. The makespan is the largest of the bin loads over bin speed (all 1
here) and the link loads times their cost.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


class Tree:
    def __init__(self, levels: Sequence[Dict]):
        self.fanouts = [int(lv["fanout"]) for lv in levels]
        leaf_gbps = float(levels[-1]["gbps"])
        counts = np.cumprod(self.fanouts)                 # nodes per depth
        self.depth = len(self.fanouts)
        self.offset = np.concatenate([[0, 1], 1 + np.cumsum(counts)[:-1]])
        self.n_nodes = int(1 + counts.sum())
        self.k = int(counts[-1])
        parent = np.full(self.n_nodes, -1, np.int64)
        cost = np.zeros(self.n_nodes)
        for d in range(1, self.depth + 1):
            idx = np.arange(counts[d - 1])
            nodes = self.offset[d] + idx
            parent[nodes] = self.offset[d - 1] + idx // self.fanouts[d - 1]
            cost[nodes] = leaf_gbps / float(levels[d - 1]["gbps"])
        self.parent = parent
        self.cost = cost                                  # per child node
        self.leaves = self.offset[self.depth] + np.arange(self.k)

    def ancestor(self, bins: np.ndarray, d: int) -> np.ndarray:
        """Node at depth ``d`` above each bin (leaf index)."""
        below = int(np.prod(self.fanouts[d:]))
        return self.offset[d] + np.asarray(bins, np.int64) // below


def loads(tree: Tree, part: np.ndarray, u: np.ndarray, v: np.ndarray,
          w: np.ndarray, node_weight: np.ndarray
          ) -> Tuple[np.ndarray, np.ndarray, float]:
    """(bin loads [k], link loads [n_nodes] by child node, makespan) of the
    assignment ``part`` (vertex -> bin) of the undirected edges (u, v, w)."""
    part = np.asarray(part, np.int64)
    comp = np.zeros(tree.k)
    np.add.at(comp, part, np.asarray(node_weight, np.float64))
    bu, bv = part[u], part[v]
    cut = bu != bv
    pairs, inv = np.unique(np.stack([bu[cut], bv[cut]], 1), axis=0,
                           return_inverse=True)
    pair_w = np.zeros(len(pairs))
    np.add.at(pair_w, inv.ravel(), np.asarray(w, np.float64)[cut])
    comm = np.zeros(tree.n_nodes)
    a, b = pairs[:, 0], pairs[:, 1]
    for d in range(tree.depth, 0, -1):
        na, nb = tree.ancestor(a, d), tree.ancestor(b, d)
        differ = na != nb
        np.add.at(comm, na[differ], pair_w[differ])
        np.add.at(comm, nb[differ], pair_w[differ])
    makespan = max(comp.max(), (tree.cost * comm).max())
    return comp, comm, float(makespan)


def loads_low(tree: Tree, part: np.ndarray, u: np.ndarray, v: np.ndarray,
              w: np.ndarray, node_weight: np.ndarray, dtype
              ) -> Tuple[np.ndarray, np.ndarray, float]:
    """The same sums carried in ``dtype`` (the control: every partial sum
    rounded to that precision), returned as float64."""
    part = np.asarray(part, np.int64)
    comp = np.zeros(tree.k, dtype)
    np.add.at(comp, part, np.asarray(node_weight).astype(dtype))
    bu, bv = part[u], part[v]
    cut = bu != bv
    comm = np.zeros(tree.n_nodes, dtype)
    wc = np.asarray(w)[cut].astype(dtype)
    a, b = bu[cut], bv[cut]
    for d in range(tree.depth, 0, -1):
        na, nb = tree.ancestor(a, d), tree.ancestor(b, d)
        differ = na != nb
        np.add.at(comm, na[differ], wc[differ])
        np.add.at(comm, nb[differ], wc[differ])
    comp, comm = comp.astype(np.float64), comm.astype(np.float64)
    return comp, comm, float(max(comp.max(), (tree.cost * comm).max()))
