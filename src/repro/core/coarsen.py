"""Multilevel coarsening: vectorized heavy-edge matching + contraction.

Two interchangeable front ends (DESIGN.md §Device-V-cycle):

  * the host-numpy path (``coarsen``) — lexsort / ``np.add.at`` /
    ``np.unique``; the reference implementation every device result is
    pinned against;
  * the device path (``coarsen_device``) — the same heavy-edge matching
    and contraction as jitted segment-op passes (``segment_max`` proposal
    argmax, scan-based rank/relabel, sorted-run edge dedup), with the
    per-round jittered arc keys running through the
    ``kernels/match_keys.py`` Pallas kernel on TPU. Arrays are padded to
    power-of-2 buckets so the whole V-cycle compiles O(log n) executables,
    and only two scalars (coarse node/edge counts) sync back per level.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import numpy as np

from repro import obs
from repro.graph.graph import Graph, from_edges


@dataclasses.dataclass(frozen=True)
class Level:
    graph: Graph
    fine_to_coarse: np.ndarray  # [n_fine] mapping into this level's graph


def heaviest_neighbor(g: Graph, rng: np.random.Generator,
                      eligible: np.ndarray) -> np.ndarray:
    """prop[v] = eligible neighbor with max (jittered) edge weight, else v."""
    w = g.edge_weight * (1.0 + 0.01 * rng.random(g.n_arcs).astype(np.float32))
    w = np.where(eligible[g.receivers] & eligible[g.senders], w, -1.0)
    # last-per-sender after sorting by (sender, w): CSR is sender-sorted, so
    # argsort w within rows via lexsort on (w, sender)
    order = np.lexsort((w, g.senders))
    s_sorted = g.senders[order]
    last = np.nonzero(np.diff(np.append(s_sorted, -1)) != 0)[0]
    prop = np.arange(g.n_nodes, dtype=np.int64)
    best_arc = order[last]
    ok = w[best_arc] > 0
    prop[s_sorted[last][ok]] = g.receivers[best_arc][ok]
    return prop


def match_round(g: Graph, rng: np.random.Generator,
                matched: np.ndarray) -> np.ndarray:
    """One round of mutual-proposal matching. Returns partner[v] (= v if
    unmatched). Mutual handshakes only -> valid matching."""
    prop = heaviest_neighbor(g, rng, ~matched)
    partner = np.arange(g.n_nodes, dtype=np.int64)
    mutual = (prop[prop] == np.arange(g.n_nodes)) & (prop != np.arange(g.n_nodes))
    partner[mutual] = prop[mutual]
    return partner


def contract(g: Graph, partner: np.ndarray) -> Tuple[Graph, np.ndarray]:
    """Contract matched pairs. Returns (coarse graph, fine->coarse map)."""
    rep = np.minimum(np.arange(g.n_nodes, dtype=np.int64), partner)
    uniq, coarse_id = np.unique(rep, return_inverse=True)
    nc = uniq.shape[0]
    nw = np.zeros(nc, dtype=np.float32)
    np.add.at(nw, coarse_id, g.node_weight)
    cu = coarse_id[g.senders]
    cv = coarse_id[g.receivers]
    keep = cu < cv  # one arc per undirected fine edge; drops intra-cluster
    cg = from_edges(nc, cu[keep], cv[keep], g.edge_weight[keep], nw, dedup=True)
    return cg, coarse_id


def coarsen(g: Graph, k: int, seed: int = 0, max_levels: int = 40,
            coarse_factor: int = 24, min_reduction: float = 0.05) -> List[Level]:
    """Coarsening chain, finest first. ``levels[0].graph is g``; each level's
    ``fine_to_coarse`` maps into the NEXT level's graph (standard multilevel
    bookkeeping). Stops near ``coarse_factor * k`` vertices or when matching
    stalls (reduction < min_reduction)."""
    rng = np.random.default_rng(seed)
    levels = [Level(graph=g, fine_to_coarse=None)]  # type: ignore[arg-type]
    cur = g
    for _ in range(max_levels):
        if cur.n_nodes <= coarse_factor * k or cur.n_arcs == 0:
            break
        matched = np.zeros(cur.n_nodes, dtype=bool)
        partner = np.arange(cur.n_nodes, dtype=np.int64)
        for _round in range(3):
            p = match_round(cur, rng, matched)
            new = (p != np.arange(cur.n_nodes)) & ~matched
            partner[new] = p[new]
            matched |= new | matched[p]
            matched[p[new]] = True
        nxt, mapping = contract(cur, partner)
        if nxt.n_nodes >= cur.n_nodes * (1.0 - min_reduction):
            break
        levels[-1] = Level(graph=levels[-1].graph, fine_to_coarse=mapping)
        levels.append(Level(graph=nxt, fine_to_coarse=None))  # type: ignore[arg-type]
        cur = nxt
    return levels


# ---------------------------------------------------------------------------
# Device path: jitted segment-op matching + contraction
# ---------------------------------------------------------------------------

def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


@functools.lru_cache(maxsize=1)
def _coarsen_step():
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    @functools.partial(jax.jit, static_argnames=("n_pad", "rounds"))
    def step(s, r, w, nw, n_valid, m_valid, key, *, n_pad, rounds=3):
        """One level of device coarsening over padded arrays.

        ``s``/``r``/``w``: [m_pad] arc list (padding: s=r=0, w=0);
        ``nw``: [n_pad] node weights (0 on padding); ``n_valid``/``m_valid``
        traced live counts. Returns (coarse_id [n_pad], nc, nw_c [n_pad],
        cu_e [m_pad], cv_e [m_pad], w_e [m_pad], m_new): the contraction
        relabel, coarse node weights, and the deduped undirected coarse
        edge list (first ``m_new`` slots).
        """
        m_pad = w.shape[0]
        iota_n = jnp.arange(n_pad, dtype=jnp.int32)
        iota_m = jnp.arange(m_pad, dtype=jnp.int32)
        arc_ok = iota_m < m_valid
        node_ok = iota_n < n_valid
        matched = ~node_ok                       # padding nodes never match
        partner = iota_n

        for rnd in range(rounds):
            elig = (~matched).astype(jnp.float32)
            mask = (elig[s] * elig[r] * arc_ok.astype(jnp.float32)
                    * (w > 0).astype(jnp.float32))
            u = jax.random.uniform(jax.random.fold_in(key, rnd), (m_pad,))
            keys = ops.match_keys(w, u, mask)
            # two-pass exact segment argmax: per-sender max key, then the
            # max arc id among arcs attaining it (deterministic tie-break)
            seg_max = jax.ops.segment_max(keys, s, num_segments=n_pad)
            at_max = (keys > 0) & (keys >= seg_max[s])
            best_arc = jax.ops.segment_max(
                jnp.where(at_max, iota_m, -1), s, num_segments=n_pad)
            prop = jnp.where(best_arc >= 0,
                             r[jnp.clip(best_arc, 0)], iota_n)
            mutual = (prop[prop] == iota_n) & (prop != iota_n)
            new = mutual & ~matched
            partner = jnp.where(new, prop, partner)
            matched = matched | new

        # contraction: rep = min(v, partner), leaders ranked by prefix sum
        rep = jnp.minimum(iota_n, partner)
        is_leader = (rep == iota_n) & node_ok
        rank = jnp.cumsum(is_leader.astype(jnp.int32)) - 1
        coarse_id = rank[rep]
        nc = is_leader.sum()
        nw_c = jax.ops.segment_sum(jnp.where(node_ok, nw, 0.0),
                                   jnp.where(node_ok, coarse_id, 0),
                                   num_segments=n_pad)

        # dedup: keep one direction per undirected coarse edge, sort by
        # (cu, cv) via two stable passes (no 64-bit keys), sum run weights
        cu = coarse_id[s]
        cv = coarse_id[r]
        keep = arc_ok & (cu < cv)
        cu_k = jnp.where(keep, cu, n_pad)        # junk runs sort last
        cv_k = jnp.where(keep, cv, n_pad)
        w_k = jnp.where(keep, w, 0.0)
        ord1 = jnp.argsort(cv_k, stable=True)
        ord2 = jnp.argsort(cu_k[ord1], stable=True)
        order = ord1[ord2]
        cu_s, cv_s, w_s = cu_k[order], cv_k[order], w_k[order]
        kept_s = cu_s < n_pad
        head = kept_s & jnp.concatenate([
            jnp.ones((1,), bool),
            (cu_s[1:] != cu_s[:-1]) | (cv_s[1:] != cv_s[:-1])])
        eid = jnp.clip(jnp.cumsum(head.astype(jnp.int32)) - 1, 0)
        w_e = jax.ops.segment_sum(w_s, eid, num_segments=m_pad)
        cu_e = jax.ops.segment_max(jnp.where(kept_s, cu_s, -1), eid,
                                   num_segments=m_pad)
        cv_e = jax.ops.segment_max(jnp.where(kept_s, cv_s, -1), eid,
                                   num_segments=m_pad)
        m_new = head.sum()
        return coarse_id, nc, nw_c, cu_e, cv_e, w_e, m_new

    return step


def coarsen_device(g: Graph, k: int, seed: int = 0, max_levels: int = 40,
                   coarse_factor: int = 24,
                   min_reduction: float = 0.05) -> List[Level]:
    """Device-resident coarsening chain — same contract and stop criteria
    as :func:`coarsen`, with matching + contraction as jitted segment-op
    passes. Levels are materialized as host ``Graph`` objects (the
    refinement stage consumes numpy levels), but all per-arc work happens
    on the accelerator; the host only reads the two level-size scalars and
    the final sliced arrays."""
    import jax
    import jax.numpy as jnp

    step = _coarsen_step()
    key = jax.random.PRNGKey(seed)
    levels = [Level(graph=g, fine_to_coarse=None)]  # type: ignore[arg-type]
    cur = g
    for lvl in range(max_levels):
        if cur.n_nodes <= coarse_factor * k or cur.n_arcs == 0:
            break
        with obs.span("coarsen.level"):
            n_pad, m_pad = _pow2(cur.n_nodes), _pow2(cur.n_arcs)
            s = jnp.asarray(np.pad(cur.senders.astype(np.int32),
                                   (0, m_pad - cur.n_arcs)))
            r = jnp.asarray(np.pad(cur.receivers.astype(np.int32),
                                   (0, m_pad - cur.n_arcs)))
            w = jnp.asarray(np.pad(cur.edge_weight.astype(np.float32),
                                   (0, m_pad - cur.n_arcs)))
            nw = jnp.asarray(np.pad(cur.node_weight.astype(np.float32),
                                    (0, n_pad - cur.n_nodes)))
            cid, nc, nw_c, cu_e, cv_e, w_e, m_new = step(
                s, r, w, nw, jnp.int32(cur.n_nodes), jnp.int32(cur.n_arcs),
                jax.random.fold_in(key, lvl), n_pad=n_pad)
            nc, m_new = int(nc), int(m_new)
            if nc >= cur.n_nodes * (1.0 - min_reduction):
                break
            nxt = from_edges(
                nc, np.asarray(cu_e[:m_new], dtype=np.int64),
                np.asarray(cv_e[:m_new], dtype=np.int64),
                np.asarray(w_e[:m_new], dtype=np.float32),
                np.asarray(nw_c[:nc], dtype=np.float32), dedup=False)
            mapping = np.asarray(cid[:cur.n_nodes], dtype=np.int64)
            levels[-1] = Level(graph=levels[-1].graph, fine_to_coarse=mapping)
            levels.append(Level(graph=nxt, fine_to_coarse=None))  # type: ignore[arg-type]
            cur = nxt
    return levels
