"""TPU-native bottleneck (makespan) refinement via damped label propagation.

This is the hardware adaptation of the paper's implied refinement loop
(DESIGN.md §2): classical partitioners refine with priority-queue FM — a
sequential, pointer-chasing pattern with no TPU analogue. Here every round is
a few gathers and scatters over the edge list (one arc per undirected edge)
plus small GEMMs over the bins:

  1. Price bins and links with the gradient of the annealed soft-max
     potential (softmax weights concentrate on the bottleneck terms), from
     the per-bin loads ``comp`` and per-link loads ``comm`` of the current
     assignment, carried in the state from the round that accepted it.
  2. Build the ``k x k`` *price-distance* matrix
     ``pi[a, b] = sum_l price_l * [l on path(a,b)]`` — two GEMMs against the
     subtree indicator.
  3. Every vertex evaluates candidate destination bins against ``pi`` and
     the bin prices, either densely (all k bins, via the ``partition_gain``
     connectivity kernel) or sparsely (one sampled candidate per vertex,
     O(m) via edge gathers: ``pi`` is symmetric, so one price serves both
     ends of an edge) — the dense mode is used on coarse levels, the
     sparse mode on multi-million-vertex fine levels.
  4. A damped, inflow-capped subset of positive-gain moves is applied;
     acceptance of the *round* is judged by the true (hard-max) makespan, so
     the smoothing never corrupts the objective — it only prices moves.
  5. The moved assignment is scored once (one quotient-matrix scatter of
     the edges into ``k^2`` cells, symmetrised): that scoring both judges
     the round and prices the next.

What depends on the graph alone — the edge list, each vertex's heaviest
neighbour — is found once per call, outside the rounds.

The whole loop is one ``lax.scan`` under ``jit``; the temperature anneals
from ``temp0`` toward ``temp_min`` so early rounds spread pressure across
many loaded bins/links and late rounds focus on the exact bottleneck.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import objective
from repro.core.topology import TreeTopology
from repro.graph.graph import Graph
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class RefineConfig:
    rounds: int = 64
    damping: float = 0.5          # fraction of positive-gain moves attempted
    temp0: float = 0.25           # initial softmax temperature (relative)
    temp_min: float = 0.02
    anneal: float = 0.93          # per-round multiplicative decay
    dense_threshold: int = 200_000  # n*k above this -> sparse candidate mode
    inflow_slack: float = 0.10    # allowed inflow above current bottleneck
    seed: int = 0


class RefineState(NamedTuple):
    part: jnp.ndarray        # [n] int32 current assignment
    comp: jnp.ndarray        # [k] RAW per-bin loads of ``part``
    comm: jnp.ndarray        # [L] per-link loads of ``part``
    best_part: jnp.ndarray   # [n] int32 best-so-far under true makespan
    best_m: jnp.ndarray      # scalar best true makespan
    temp: jnp.ndarray        # scalar
    key: jnp.ndarray         # PRNG


class RefineStats(NamedTuple):
    makespan: jnp.ndarray
    comp_max: jnp.ndarray
    comm_max: jnp.ndarray
    moved: jnp.ndarray


def price_matrix(g_link: jnp.ndarray, subtree: jnp.ndarray) -> jnp.ndarray:
    """pi[a, b] = sum_l g_link[l] * (S_la XOR S_lb).  [k, k], zero diagonal.

    XOR identity: S_la + S_lb - 2 S_la S_lb for 0/1 indicators.
    """
    S = subtree
    u = g_link @ S                       # [k] sum_l g_l S_la
    cross = S.T @ (g_link[:, None] * S)  # [k, k]
    return u[:, None] + u[None, :] - 2.0 * cross


def _quotient(part, eu, ev, ew, k):
    """``objective.quotient_matrix`` from one arc per edge, ``W_e + W_eᵀ``:
    half the scatter. Exact where the weights are whole numbers; fractional
    weights change ``W`` only in rounding."""
    W_e = objective.quotient_matrix(part, eu, ev, ew, k)
    return W_e + W_e.T


def _score(part, eu, ev, ew, node_weight, subtree, F_l, k, speed=None):
    """(RAW per-bin loads [k], breakdown) of ``part``. The operations of
    ``objective.makespan_tree``; the raw load is kept because pricing and
    the inflow cap take it, and the breakdown's ``comp`` is ``raw / speed``
    exactly as ``comp_loads`` divides."""
    comp = objective.comp_loads(part, node_weight, k)
    W = _quotient(part, eu, ev, ew, k)
    comm = objective.link_loads_tree(W, subtree)
    comp_n = comp if speed is None else comp / speed
    return comp, objective.makespan_from_parts(comp_n, comm, F_l)


def _prices(comp, comm, F_l, temp, speed=None):
    g_comp, g_link = objective.load_gradients(comp, comm, F_l, temp, speed)
    return g_comp, g_link


def _apply_moves(part, cand, gain, node_weight, comp, key, k, damping,
                 inflow_slack, speed=None):
    """Damped, inflow-capped application of positive-gain moves.

    A move is attempted with probability ``damping``; per destination bin,
    attempted inflow is capped so the bin does not blow past the current
    bottleneck (+slack) — stochastic thinning by the cap ratio. With per-bin
    ``speed`` the cap runs in capacity-normalized units (``comp/speed``,
    inflow weighted by 1/speed of the destination): a slow bin fills up
    proportionally sooner.
    """
    k_gate, k_thin = jax.random.split(key)
    want = (gain > 0) & (cand != part)
    want &= jax.random.uniform(k_gate, part.shape) < damping
    w_eff = node_weight if speed is None else node_weight / speed[cand]
    comp_n = comp if speed is None else comp / speed
    inflow = jax.ops.segment_sum(
        jnp.where(want, w_eff, 0.0), cand, num_segments=k)
    cap = jnp.maximum(comp_n.max() * (1.0 + inflow_slack) - comp_n, 0.0)
    ratio = jnp.where(inflow > 0, jnp.minimum(cap / jnp.maximum(inflow, 1e-9), 1.0), 0.0)
    keep = want & (jax.random.uniform(k_thin, part.shape) < ratio[cand])
    moved = keep.sum()
    return jnp.where(keep, cand, part), moved


# ---------------------------------------------------------------------------
# Dense mode: every vertex scores all k destination bins.
# ---------------------------------------------------------------------------

def _dense_round(part, comp, comm, senders, receivers, edge_weight,
                 node_weight, subtree, F_l, k, temp, key, damping,
                 inflow_slack, speed=None):
    # g_comp prices RAW load (1/speed folded in by load_gradients), so the
    # gain formula below is unchanged on heterogeneous machines
    g_comp, g_link = _prices(comp, comm, F_l, temp, speed)
    pi = price_matrix(g_link, subtree)

    conn = kops.partition_gain(part, senders, receivers, edge_weight, k)
    # gain[v, b] = sum_j conn[v,j] (pi[a_v, j] - pi[b, j]) + w_v (g_a - g_b)
    cur_price = jnp.sum(conn * pi[part], axis=1)            # [n]
    new_price = conn @ pi.T                                  # [n, k]
    gain = (cur_price[:, None] - new_price
            + node_weight[:, None] * (g_comp[part][:, None] - g_comp[None, :]))
    gain = gain.at[jnp.arange(part.shape[0]), part].set(-jnp.inf)
    cand = jnp.argmax(gain, axis=1).astype(part.dtype)
    best_gain = jnp.take_along_axis(gain, cand[:, None].astype(jnp.int32), axis=1)[:, 0]
    return _apply_moves(part, cand, best_gain, node_weight, comp, key, k,
                        damping, inflow_slack, speed)


# ---------------------------------------------------------------------------
# Sparse mode: one sampled candidate bin per vertex per round. O(m).
# ---------------------------------------------------------------------------

def _heavy_neighbours(senders, receivers, edge_weight, n):
    """[n] receiver of each vertex's heaviest outgoing arc (the largest arc
    index among ties; arc 0 for a vertex with no arcs). A function of the
    graph alone, so refinement finds it once and not every round.

    Exact two-pass segment argmax. (A float32 composite key
    ``w * (m+1) + arc`` loses the packed arc index once the arc count nears
    2^24 — multi-million-edge graphs would sample a wrong, possibly
    out-of-segment arc. Two segment_max passes are precision-safe at any
    size: first the per-segment max weight, then the largest arc index
    among the arcs attaining it.)"""
    m = senders.shape[0]
    w32 = edge_weight.astype(jnp.float32)
    seg_max = jax.ops.segment_max(w32, senders, num_segments=n)
    at_max = w32 >= seg_max[senders]          # exact: compares its own max
    arc_ids = jnp.where(at_max, jnp.arange(m, dtype=jnp.int32), -1)
    best_arc = jnp.clip(jax.ops.segment_max(arc_ids, senders, num_segments=n),
                        0, m - 1)
    return receivers[best_arc]


def _sample_candidates(part, receivers, heavy_nbr, offsets_pad, degrees,
                       g_comp, mode, key, n):
    """Candidate destination bin per vertex. Every lookup is n-sized.

    mode 0: bin of the heaviest neighbour ``heavy_nbr`` (strongest pull)
    mode 1: bin of a uniformly random incident arc (exploration)
    mode 2: cheapest-priced bin (load escape hatch for bottleneck bins)
    """
    m = receivers.shape[0]
    heavy = part[heavy_nbr].astype(jnp.int32)

    rand_off = (jax.random.uniform(key, (n,)) * jnp.maximum(degrees, 1)).astype(jnp.int32)
    rand_arc = jnp.clip(offsets_pad + rand_off, 0, m - 1)
    rnd = part[receivers[rand_arc]].astype(jnp.int32)

    cheap = jnp.argmin(g_comp).astype(jnp.int32)
    cand = jnp.where(mode == 0, heavy, jnp.where(mode == 1, rnd, cheap))
    return jnp.where(degrees > 0, cand, part.astype(jnp.int32)).astype(part.dtype)


def _gain_comm(part, cand, pi, eu, ev, ew):
    """[n] link price each vertex x saves by moving to ``cand[x]``: the sum
    over its arcs x→y of ``w (pi[part[x], part[y]] - pi[cand[x], part[y]])``,
    walked over each edge once. ``pi`` is symmetric, so ``pi[a_u, a_v]``
    prices both ends. u's terms go in first, then v's: on a graph from
    ``from_edges`` every vertex sums its terms in CSR arc order, as the walk
    over both arcs did, bit for bit."""
    a_u = part[eu].astype(jnp.int32)
    a_v = part[ev].astype(jnp.int32)
    c_u = cand[eu].astype(jnp.int32)
    c_v = cand[ev].astype(jnp.int32)
    cur = pi[a_u, a_v]
    return (jnp.zeros(part.shape[0], ew.dtype)
            .at[eu].add(ew * (cur - pi[c_u, a_v]))
            .at[ev].add(ew * (cur - pi[c_v, a_u])))


def _sparse_round(part, comp, comm, eu, ev, ew, receivers, node_weight,
                  offsets_pad, degrees, heavy_nbr, subtree, F_l, k, temp, key,
                  mode, damping, inflow_slack, speed=None):
    n = part.shape[0]
    g_comp, g_link = _prices(comp, comm, F_l, temp, speed)
    pi = price_matrix(g_link, subtree)

    k_cand, k_move = jax.random.split(key)
    cand = _sample_candidates(part, receivers, heavy_nbr, offsets_pad,
                              degrees, g_comp, mode, k_cand, n)

    gain = (_gain_comm(part, cand, pi, eu, ev, ew)
            + node_weight * (g_comp[part] - g_comp[cand]))
    return _apply_moves(part, cand, gain, node_weight, comp, k_move, k,
                        damping, inflow_slack, speed)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def _refine_core(part0, senders, receivers, edge_weight, eu, ev, ew,
                 node_weight, offsets_pad, degrees, subtree, F_l, key,
                 speed=None, *, k, rounds, dense, damping, temp0, temp_min,
                 anneal, inflow_slack):
    score = functools.partial(_score, eu=eu, ev=ev, ew=ew,
                              node_weight=node_weight, subtree=subtree,
                              F_l=F_l, k=k, speed=speed)
    heavy_nbr = (None if dense else _heavy_neighbours(
        senders, receivers, edge_weight, part0.shape[0]))

    def body(state: RefineState, ridx):
        key, sub = jax.random.split(state.key)
        if dense:
            part, moved = _dense_round(
                state.part, state.comp, state.comm, senders, receivers,
                edge_weight, node_weight, subtree, F_l, k, state.temp, sub,
                damping, inflow_slack, speed)
        else:
            mode = ridx % 3
            part, moved = _sparse_round(
                state.part, state.comp, state.comm, eu, ev, ew, receivers,
                node_weight, offsets_pad, degrees, heavy_nbr, subtree, F_l, k,
                state.temp, sub, mode, damping, inflow_slack, speed)
        # one scoring per round: acceptance, stats and the next round's
        # prices share it
        comp, br = score(part)
        m = br.makespan
        better = m < state.best_m
        best_part = jnp.where(better, part, state.best_part)
        best_m = jnp.minimum(m, state.best_m)
        temp = jnp.maximum(state.temp * anneal, temp_min)
        stats = RefineStats(m, br.comp_max, br.comm_max, moved)
        return RefineState(part, comp, br.comm, best_part, best_m, temp,
                           key), stats

    comp0, br0 = score(part0)
    init = RefineState(part0, comp0, br0.comm, part0, br0.makespan,
                       jnp.float32(temp0), key)
    final, stats = jax.lax.scan(body, init, jnp.arange(rounds))
    return final.best_part, final.best_m, stats


_STATIC = ("k", "rounds", "dense", "damping", "temp0", "temp_min", "anneal",
           "inflow_slack")
_refine_jit = functools.partial(jax.jit, static_argnames=_STATIC)(_refine_core)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _refine_batch_jit(parts0, senders, receivers, edge_weight, eu, ev, ew,
                      node_weight, offsets_pad, degrees, subtree, F_l, keys,
                      speed=None, *, k, rounds, dense, damping, temp0,
                      temp_min, anneal, inflow_slack):
    def one(p0, key):
        return _refine_core(p0, senders, receivers, edge_weight, eu, ev, ew,
                            node_weight, offsets_pad, degrees, subtree, F_l,
                            key, speed, k=k, rounds=rounds, dense=dense,
                            damping=damping, temp0=temp0, temp_min=temp_min,
                            anneal=anneal, inflow_slack=inflow_slack)
    return jax.vmap(one)(parts0, keys)


def _graph_arrays(g: Graph):
    """The graph as ``_refine_core`` takes it: arcs, edges (``Graph.edges``,
    which raises on an asymmetric arc list), node weights, CSR offsets and
    degrees."""
    eu, ev, ew = g.edges()
    return (jnp.asarray(g.senders), jnp.asarray(g.receivers),
            jnp.asarray(g.edge_weight), jnp.asarray(eu), jnp.asarray(ev),
            jnp.asarray(ew), jnp.asarray(g.node_weight),
            jnp.asarray(g.offsets[:-1], dtype=jnp.int32),
            jnp.asarray(g.degrees(), dtype=jnp.int32))


def refine(g: Graph, topo: TreeTopology, part: np.ndarray,
           cfg: Optional[RefineConfig] = None) -> Tuple[np.ndarray, float, RefineStats]:
    """Refine ``part`` on graph ``g`` over machine tree ``topo``.

    Returns (best partition, best makespan, per-round stats). Pure function
    of its inputs — does not mutate ``part``. ``topo.bin_speed`` (set by
    ``core.machine.MachineSpec`` on heterogeneous machines) switches the
    whole loop — prices, inflow caps, acceptance — to the
    capacity-normalized objective ``max(comp/speed, F_l·comm)``.
    """
    cfg = cfg or RefineConfig()
    k = topo.k
    dense = g.n_nodes * k <= cfg.dense_threshold
    key = jax.random.PRNGKey(cfg.seed)
    speed = (None if topo.bin_speed is None
             else jnp.asarray(topo.bin_speed, dtype=jnp.float32))
    best_part, best_m, stats = _refine_jit(
        jnp.asarray(part, dtype=jnp.int32),
        *_graph_arrays(g), jnp.asarray(topo.subtree), jnp.asarray(topo.F_l),
        key, speed,
        k=k, rounds=cfg.rounds, dense=bool(dense), damping=cfg.damping,
        temp0=cfg.temp0, temp_min=cfg.temp_min, anneal=cfg.anneal,
        inflow_slack=cfg.inflow_slack)
    return np.asarray(best_part), float(best_m), jax.tree.map(np.asarray, stats)


def refine_batch(g: Graph, topo: TreeTopology, parts: np.ndarray,
                 cfg: Optional[RefineConfig] = None
                 ) -> Tuple[np.ndarray, np.ndarray, RefineStats]:
    """Refine ``S`` initial partitions at once: the whole ``lax.scan``
    refinement is vmapped over the seed axis, so the per-round GEMMs batch
    across seeds and S restarts cost far less than S sequential runs.

    Slot ``i`` draws ``PRNGKey(cfg.seed + i)`` — slot 0 follows the same
    move trajectory as ``refine(g, topo, parts[0], cfg)``. Returns
    (best parts ``[S, n]``, best makespans ``[S]``, stats with a leading
    seed axis).
    """
    cfg = cfg or RefineConfig()
    parts = np.asarray(parts)
    if parts.ndim != 2:
        raise ValueError(f"parts must be [S, n], got {parts.shape}")
    k = topo.k
    dense = g.n_nodes * k <= cfg.dense_threshold
    keys = jnp.stack([jax.random.PRNGKey(cfg.seed + i)
                      for i in range(parts.shape[0])])
    speed = (None if topo.bin_speed is None
             else jnp.asarray(topo.bin_speed, dtype=jnp.float32))
    best_parts, best_ms, stats = _refine_batch_jit(
        jnp.asarray(parts, dtype=jnp.int32),
        *_graph_arrays(g), jnp.asarray(topo.subtree), jnp.asarray(topo.F_l),
        keys, speed,
        k=k, rounds=cfg.rounds, dense=bool(dense), damping=cfg.damping,
        temp0=cfg.temp0, temp_min=cfg.temp_min, anneal=cfg.anneal,
        inflow_slack=cfg.inflow_slack)
    with obs.span("refine.pull"):
        best_parts, best_ms = np.asarray(best_parts), np.asarray(best_ms)
        stats = jax.tree.map(np.asarray, stats)
    if obs.on():
        obs.add("refine.rounds", stats.makespan.size)
        obs.add("refine.edge_rounds", 0 if dense else stats.makespan.size)
        obs.add("refine.rounds_to_best",
                int(rounds_to_best(stats.makespan, best_ms).sum()))
    return best_parts, best_ms, stats


def rounds_to_best(makespan: np.ndarray, best_ms: np.ndarray) -> np.ndarray:
    """[S] rounds each slot of :func:`refine_batch` ran until it reached
    the partition it returns, from its per-round makespans ``[S, rounds]``
    and best makespans ``[S]``: the first round at the best, or 0 where no
    round reached it (the start stood). Later rounds cannot change the
    result. A round that only ties the start counts as reaching it, so the
    count errs high, never low."""
    return np.where(makespan.min(axis=1) <= best_ms,
                    makespan.argmin(axis=1) + 1, 0)
