"""The paper's objective, as TPU-friendly JAX.

Everything is expressed over the quotient matrix ``W`` (inter-bin edge
weights) and the subtree indicator ``S`` so the bottleneck terms are GEMMs:

    comm(l) = sum_ij W_ij * (S_li XOR S_lj)
            = (S @ r)_l + (S @ c)_l - 2 * diag(S @ W @ S^T)_l      (r/c = row/col sums)

For symmetric W this halves to the undirected edge load. ``makespan`` is the
paper's M(P) = max(max_b comp(b), max_l F_l * comm(l)); ``soft_cost`` is the
temperature-annealed potential used by the refinement (the true max has zero
gradient almost everywhere).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# Link loads are sums of edge weights. On a TPU an f32 GEMM at default
# precision rounds its operands to bf16 (8 significant bits), so loads above
# a few hundred would be off by up to 2^-8 relative; the objective and the
# acceptance tests on it must be exact, so every load GEMM runs at HIGHEST.
_EXACT = jax.lax.Precision.HIGHEST


class MakespanBreakdown(NamedTuple):
    makespan: jnp.ndarray      # scalar
    comp: jnp.ndarray          # [k] per-bin compute loads (speed-normalized
    #                            when the machine is heterogeneous)
    comm: jnp.ndarray          # [L] per-link communication volumes
    comp_max: jnp.ndarray
    comm_max: jnp.ndarray      # max_l F_l * comm(l)


def comp_loads(part: jnp.ndarray, node_weight: jnp.ndarray, k: int,
               speed: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """comp(b): sum of vertex weights mapped to each bin. [k]

    ``speed`` (relative per-bin compute speeds, fastest = 1.0) switches to
    the capacity-normalized load ``comp(b) / speed(b)`` — the paper's
    load-balanced bottleneck objective for heterogeneous PEs: a slow bin
    carrying the same weight is a worse bottleneck. ``speed=None`` is the
    exact uniform-machine path (no division)."""
    comp = jax.ops.segment_sum(node_weight, part, num_segments=k)
    if speed is not None:
        comp = comp / speed
    return comp


def quotient_matrix(part: jnp.ndarray, senders: jnp.ndarray, receivers: jnp.ndarray,
                    edge_weight: jnp.ndarray, k: int) -> jnp.ndarray:
    """W[i, j] = total arc weight from bin i to bin j. Symmetric for symmetric
    arc lists; each undirected edge contributes w to W_ij AND W_ji, and 2w to
    the diagonal if internal. [k, k]"""
    bi = part[senders].astype(jnp.int32)
    bj = part[receivers].astype(jnp.int32)
    flat = jax.ops.segment_sum(edge_weight, bi * k + bj, num_segments=k * k)
    return flat.reshape(k, k)


def link_loads_tree(W: jnp.ndarray, subtree: jnp.ndarray) -> jnp.ndarray:
    """comm(l) for a tree topology from the (symmetric, arc-based) quotient
    matrix. Result counts each undirected edge once. [L]"""
    S = subtree
    r = W.sum(axis=1)
    c = W.sum(axis=0)
    cross = jnp.einsum("li,ij,lj->l", S, W, S, precision=_EXACT)
    # arc-based W double-counts undirected edges -> halve
    return 0.5 * (jnp.matmul(S, r, precision=_EXACT)
                  + jnp.matmul(S, c, precision=_EXACT) - 2.0 * cross)


def link_loads_routing(W: jnp.ndarray, path_incidence: jnp.ndarray) -> jnp.ndarray:
    """comm(l) under a routing oracle: R[i, j, l] fractional incidence. [L]"""
    return 0.5 * jnp.einsum("ij,ijl->l", W, path_incidence,
                            precision=_EXACT)


def makespan_from_parts(comp: jnp.ndarray, comm: jnp.ndarray, F_l: jnp.ndarray,
                        router_mask: Optional[jnp.ndarray] = None) -> MakespanBreakdown:
    comp_eff = comp
    if router_mask is not None:
        # routers must carry no load; bins listed in compute space so normally
        # unused — kept for the interconnect variant where callers score raw
        # assignments.
        comp_eff = jnp.where(router_mask, 0.0, comp)
    comp_max = comp_eff.max()
    comm_cost = F_l * comm
    comm_max = comm_cost.max() if comm.shape[0] else jnp.zeros(())
    return MakespanBreakdown(jnp.maximum(comp_max, comm_max), comp, comm,
                             comp_max, comm_max)


@functools.partial(jax.jit, static_argnames=("k",))
def makespan_tree(part: jnp.ndarray, senders: jnp.ndarray, receivers: jnp.ndarray,
                  edge_weight: jnp.ndarray, node_weight: jnp.ndarray,
                  subtree: jnp.ndarray, F_l: jnp.ndarray, k: int,
                  speed: Optional[jnp.ndarray] = None) -> MakespanBreakdown:
    """M(P) for a tree topology. ``part[v]`` is a compute-bin index in [0, k).
    ``speed`` normalizes bin loads to ``comp(b)/speed(b)`` (heterogeneous
    PEs; the breakdown's ``comp`` is then the normalized load)."""
    comp = comp_loads(part, node_weight, k, speed)
    W = quotient_matrix(part, senders, receivers, edge_weight, k)
    comm = link_loads_tree(W, subtree)
    return makespan_from_parts(comp, comm, F_l)


@functools.partial(jax.jit, static_argnames=("k",))
def makespan_routing(part: jnp.ndarray, senders: jnp.ndarray, receivers: jnp.ndarray,
                     edge_weight: jnp.ndarray, node_weight: jnp.ndarray,
                     path_incidence: jnp.ndarray, F_l: jnp.ndarray,
                     k: int, speed: Optional[jnp.ndarray] = None
                     ) -> MakespanBreakdown:
    comp = comp_loads(part, node_weight, k, speed)
    W = quotient_matrix(part, senders, receivers, edge_weight, k)
    comm = link_loads_routing(W, path_incidence)
    return makespan_from_parts(comp, comm, F_l)


# ---------------------------------------------------------------------------
# Batched candidate scoring (the mapping search's hot path)
# ---------------------------------------------------------------------------

def permutation_link_loads(T: jnp.ndarray, subtree: jnp.ndarray,
                           device_to_bin: jnp.ndarray) -> jnp.ndarray:
    """comm(l) of ONE device->bin *permutation* from the traffic matrix. [L]

    The mapping case is a relabeling of ``T``: with ``P`` the 0/1 assignment
    matrix of the permutation, the quotient is ``W = P T P^T``, so
    ``S W S^T`` collapses onto the gathered indicator
    ``Sg[l, d] = S[l, bin(d)]`` and every link load is two ``[L, D]`` GEMMs
    against ``T`` — no ``segment_sum``, no edge-list rebuild. ``T`` is the
    symmetric per-direction matrix (each undirected pair appears in both
    entries), matching the arc-based ``quotient_matrix`` convention; the 0.5
    counts each undirected edge once, as ``link_loads_tree`` does.
    """
    S_g = jnp.take(subtree, device_to_bin, axis=1)     # [L, D]
    rc = jnp.matmul(S_g, T.sum(axis=1) + T.sum(axis=0),   # (S@r + S@c),
                    precision=_EXACT)                      # permuted
    cross = (jnp.matmul(S_g, T, precision=_EXACT) * S_g).sum(axis=1)
    return 0.5 * (rc - 2.0 * cross)


@functools.partial(jax.jit, static_argnames=("k", "n_nodes"))
def permutation_link_loads_batch(device_to_bin: jnp.ndarray,
                                 pair_u: jnp.ndarray, pair_v: jnp.ndarray,
                                 pair_w: jnp.ndarray, lca_table: jnp.ndarray,
                                 subtree: jnp.ndarray,
                                 node_subtree: jnp.ndarray,
                                 k: int, n_nodes: int) -> jnp.ndarray:
    """Link loads ``[C, L]`` for a ``[C, D]`` batch of device->bin
    permutations, without materializing any quotient matrix.

    Inputs are the *unique* nonzero traffic pairs ``(pair_u, pair_v)`` with
    weights ``pair_w`` ([E] each), the ``[k, k]`` bin-pair LCA table of the
    machine tree, and the node-level subtree indicator ``[L, n_nodes]``
    (``topology.TreeTopology.lca_table`` / ``node_subtree_indicator``).

    Per candidate ``c`` and pair ``e`` with endpoint bins
    ``(U, V) = (d2b[u_e], d2b[v_e])``, the XOR identity gives

        comm[c, l] = sum_e w_e * (S[l,U] + S[l,V] - 2 * S[l,U] S[l,V])

    and for a tree ``S[l,U] * S[l,V] = S_node[l, lca(U, V)]`` (both leaves
    sit below link ``l`` iff their LCA does). So all link loads collapse to
    two bucketings — pair weights by endpoint bin and by LCA node, each one
    flat ``segment_sum`` over ALL candidates at once — followed by one
    ``[C, L]`` einsum (two GEMMs) against the subtree indicators. Work is
    ``O(C * E + C * (k + n_nodes) * L)`` instead of the looped scorer's
    ``O(C)`` edge rebuilds, segment_sums over ``k^2`` bins and ``L*k*k``
    einsums — and there is exactly one device dispatch per chunk.
    """
    c = device_to_bin.shape[0]
    e = pair_u.shape[0]
    U = jnp.take(device_to_bin, pair_u, axis=1)        # [C, E] endpoint bins
    V = jnp.take(device_to_bin, pair_v, axis=1)
    row = jnp.arange(c, dtype=jnp.int32)[:, None]
    # bucket pair weights by endpoint bin: ws[c, i] = sum_e w_e [U=i or V=i]
    ids = jnp.concatenate([row * k + U, row * k + V], axis=1).reshape(-1)
    w2 = jnp.broadcast_to(jnp.concatenate([pair_w, pair_w])[None, :],
                          (c, 2 * e)).reshape(-1)
    ws = jax.ops.segment_sum(w2, ids, num_segments=c * k).reshape(c, k)
    # bucket pair weights by LCA node: q[c, n] = sum_e w_e [lca(U,V)=n]
    lca = lca_table[U, V]                              # [C, E]
    wq = jnp.broadcast_to(pair_w[None, :], (c, e)).reshape(-1)
    q = jax.ops.segment_sum(wq, (row * n_nodes + lca).reshape(-1),
                            num_segments=c * n_nodes).reshape(c, n_nodes)
    return (jnp.matmul(ws, subtree.T, precision=_EXACT)
            - 2.0 * jnp.matmul(q, node_subtree.T, precision=_EXACT))


@functools.partial(jax.jit, static_argnames=("k",))
def makespan_tree_batch(parts: jnp.ndarray, senders: jnp.ndarray,
                        receivers: jnp.ndarray, edge_weight: jnp.ndarray,
                        node_weight: jnp.ndarray, subtree: jnp.ndarray,
                        F_l: jnp.ndarray, k: int,
                        speed: Optional[jnp.ndarray] = None
                        ) -> MakespanBreakdown:
    """``vmap(makespan_tree)`` over a ``[C, n]`` batch of assignments — the
    general-graph fallback for candidate sets that are not permutations of
    the traffic matrix (arbitrary graphs, non-bijective maps). ``speed``
    (shared across candidates) normalizes bin loads."""
    def one(p):
        return makespan_tree(p, senders, receivers, edge_weight, node_weight,
                             subtree, F_l, k=k, speed=speed)
    return jax.vmap(one)(parts)


def total_cut(W: jnp.ndarray) -> jnp.ndarray:
    """Classic objective: sum of inter-bin edge weights (undirected)."""
    return 0.5 * (W.sum() - jnp.trace(W))


def comm_volumes(part: jnp.ndarray, senders: jnp.ndarray, receivers: jnp.ndarray,
                 node_weight: jnp.ndarray, k: int) -> jnp.ndarray:
    """cvol(V_i) = sum_{v in V_i} c(v) * D(v) with D(v) = #foreign blocks
    adjacent to v (Hendrickson-Kolda metric, for the baseline comparison)."""
    n = node_weight.shape[0]
    bj = part[receivers].astype(jnp.int32)
    onehot_hits = jax.ops.segment_max(
        jnp.ones_like(bj, dtype=jnp.float32),
        senders.astype(jnp.int32) * k + bj, num_segments=n * k)
    # empty segments give -inf -> clamp to 0 (not adjacent)
    adj = jnp.maximum(onehot_hits, 0.0).reshape(n, k)  # [n, k] 1 if v adj to bin j
    own = jax.nn.one_hot(part, k, dtype=adj.dtype)
    D = (adj * (1.0 - own)).sum(axis=1)      # exclude own block
    return jax.ops.segment_sum(node_weight * D, part, num_segments=k)


def soft_cost(comp: jnp.ndarray, comm: jnp.ndarray, F_l: jnp.ndarray,
              temp: jnp.ndarray,
              speed: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Smoothed bottleneck potential: temperature-scaled logsumexp over all
    load terms. -> true max as temp -> 0. Differentiable everywhere; its
    gradient concentrates weight on near-bottleneck bins/links, which is what
    the refinement prices moves with. ``comp`` is the RAW per-bin load;
    ``speed`` folds in the capacity normalization ``comp/speed``."""
    comp_n = comp if speed is None else comp / speed
    loads = jnp.concatenate([comp_n, F_l * comm])
    scale = jnp.maximum(jax.lax.stop_gradient(loads).max(), 1e-9)
    z = loads / (scale * jnp.maximum(temp, 1e-6))
    return jax.nn.logsumexp(z) * scale * jnp.maximum(temp, 1e-6)


def load_gradients(comp: jnp.ndarray, comm: jnp.ndarray, F_l: jnp.ndarray,
                   temp: jnp.ndarray, speed: Optional[jnp.ndarray] = None):
    """(g_comp [k], g_link [L]): d soft_cost / d RAW load. Softmax weights —
    computed in closed form (cheaper than jax.grad and used inside scans).
    With ``speed``, d soft/d comp(b) picks up the chain-rule 1/speed(b):
    adding weight to a slow bin is priced proportionally higher, which is
    all the refinement needs to balance a heterogeneous machine — the gain
    formulas downstream stay written in raw vertex weight."""
    comp_n = comp if speed is None else comp / speed
    loads = jnp.concatenate([comp_n, F_l * comm])
    scale = jnp.maximum(loads.max(), 1e-9)
    w = jax.nn.softmax(loads / (scale * jnp.maximum(temp, 1e-6)))
    k = comp.shape[0]
    g_comp = w[:k] if speed is None else w[:k] / speed
    return g_comp, w[k:] * F_l
