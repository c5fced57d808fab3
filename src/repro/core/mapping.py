"""From partition to placement: the glue between the paper's objective and
the JAX distribution layer.

Two consumers (DESIGN.md §2):

1. **Block placement** (GNN node arrays, embedding-table rows): JAX shards
   arrays in contiguous equal blocks, so an arbitrary assignment ``part`` is
   realized by *permuting* rows such that block ``i`` of the sharded array
   holds exactly the vertices mapped to bin ``i`` (bins padded to the common
   block size). After the permutation, a plain ``NamedSharding`` places the
   partitioner's decision — no custom collectives.

2. **Logical-mesh -> physical-topology mapping** (dense transformers): the
   compiled HLO gives per-collective traffic over logical mesh axes; we build
   the device-pair traffic matrix, then score candidate logical->physical
   assignments with the paper's makespan objective over the machine tree.
   Candidates: axis permutations x per-axis orders (identity / blocked /
   Gray). This is classic process mapping with the paper's bottleneck metric.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core import objective
from repro.core.topology import RoutingTopology, Topology, TreeTopology
from repro.graph.graph import Graph


# ---------------------------------------------------------------------------
# 1. Block placement
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockPlacement:
    perm: np.ndarray        # [n_pad] new position of each (padded) vertex
    inverse: np.ndarray     # [n_pad] vertex at each new position
    n_pad: int              # padded length = block * k
    block: int              # rows per bin
    bin_of_row: np.ndarray  # [n_pad] bin owning each new position
    fill: np.ndarray        # [k] real vertices per bin (rest is padding)


def block_placement(part: np.ndarray, k: int) -> BlockPlacement:
    """Permutation aligning bins with contiguous equal-size blocks.

    Bin loads are generally unequal; the block size is the max bin load
    (rounded up to a multiple of 8 for TPU-friendly sublanes) and smaller
    bins are padded with sentinel rows. The memory overhead is bounded by
    the partitioner's balance — another reason the comp term matters.
    """
    part = np.asarray(part)
    n = part.shape[0]
    counts = np.bincount(part, minlength=k)
    block = int(max(counts.max(), 1))
    block = (block + 7) // 8 * 8
    n_pad = block * k
    order = np.argsort(part, kind="stable")      # vertices grouped by bin
    inverse = np.full(n_pad, n, dtype=np.int64)  # n = sentinel (padding)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for b in range(k):
        seg = order[starts[b]:starts[b + 1]]
        inverse[b * block: b * block + seg.shape[0]] = seg
    real = inverse < n
    perm_positions = np.nonzero(real)[0]
    perm_vertices = inverse[real]
    perm_full = np.full(n + 1, n_pad - 1, dtype=np.int64)
    perm_full[perm_vertices] = perm_positions
    return BlockPlacement(
        perm=perm_full[:n], inverse=inverse, n_pad=n_pad, block=block,
        bin_of_row=np.repeat(np.arange(k), block),
        fill=counts.astype(np.int64))


def apply_placement(g: Graph, pl: BlockPlacement) -> Graph:
    """Relabel graph arrays into placement order (padding rows isolated)."""
    from repro.graph.graph import Graph as _G
    s = pl.perm[g.senders]
    r = pl.perm[g.receivers]
    nw = np.zeros(pl.n_pad, dtype=np.float32)
    nw[pl.perm] = g.node_weight
    order = np.argsort(s, kind="stable")
    offsets = np.zeros(pl.n_pad + 1, dtype=np.int64)
    np.add.at(offsets, s + 1, 1)
    return _G(pl.n_pad, s[order].astype(np.int32), r[order].astype(np.int32),
              g.edge_weight[order], nw, np.cumsum(offsets))


# ---------------------------------------------------------------------------
# 2. Logical-mesh -> physical mapping
# ---------------------------------------------------------------------------

def collective_traffic_matrix(mesh_shape: Sequence[int],
                              axis_bytes: Dict[int, float]) -> np.ndarray:
    """Device-pair traffic matrix [D, D] from per-axis collective bytes.

    ``axis_bytes[a]`` = bytes each device exchanges along logical axis ``a``
    per step (from the HLO collective scan in benchmarks/roofline.py). The
    ring model charges ``bytes / (size - 1)`` to each of a device's ring
    neighbors along that axis.
    """
    shape = tuple(mesh_shape)
    d = int(np.prod(shape))
    ids = np.arange(d).reshape(shape)
    T = np.zeros((d, d), dtype=np.float64)
    for ax, nbytes in axis_bytes.items():
        size = shape[ax]
        if size <= 1 or nbytes <= 0:
            continue
        per_pair = nbytes / (size - 1)
        fwd = np.roll(ids, -1, axis=ax)
        a = ids.ravel()
        b = fwd.ravel()
        T[a, b] += per_pair
        T[b, a] += per_pair
    return T


def _gray(n: int) -> np.ndarray:
    g = np.arange(n) ^ (np.arange(n) >> 1)
    return np.argsort(g, kind="stable")


def _axis_orders(size: int) -> List[np.ndarray]:
    """Per-axis leaf orders, identity always first.

    The original set (identity / Gray / blocked) is kept as a prefix so the
    widened search space is a strict superset of the PR 2 space; the
    additions are reversed and shifted ring orders — a logical ring is
    rotation/reflection symmetric, but the machine tree's blocks are not,
    so shifting or reversing moves which ring links straddle block
    boundaries.
    """
    orders = [np.arange(size)]
    if size >= 4:
        orders.append(_gray(size))
        half = size // 2
        blocked = np.concatenate([np.arange(half) * 2,
                                  np.arange(half) * 2 + 1])[:size]
        orders.append(np.argsort(blocked, kind="stable"))
    if size >= 2:
        orders.append(np.arange(size)[::-1])         # reversed ring
    if size >= 3:
        orders.append(np.roll(np.arange(size), 1))   # shifted rings
    if size >= 4:
        orders.append(np.roll(np.arange(size), size // 2))
        orders.append(_gray(size)[::-1])
    seen, out = set(), []
    for o in orders:
        key = tuple(int(x) for x in o)
        if key not in seen:
            seen.add(key)
            out.append(o)
    return out


def _traffic_edges(T: np.ndarray):
    """Symmetric arc arrays of the device-pair traffic matrix, ready for
    ``objective.makespan_tree`` — built once per search, not per candidate
    (only ``device_to_bin`` changes between candidates)."""
    import jax.numpy as jnp
    iu = np.triu_indices(T.shape[0], 1)
    w = T[iu]
    nz = w > 0
    senders = iu[0][nz].astype(np.int32)
    receivers = iu[1][nz].astype(np.int32)
    return (jnp.asarray(np.concatenate([senders, receivers])),
            jnp.asarray(np.concatenate([receivers, senders])),
            jnp.asarray(np.concatenate([w[nz], w[nz]]).astype(np.float32)))


def _routing_loads_batch(T: np.ndarray, topo: RoutingTopology,
                         device_to_bin: np.ndarray) -> np.ndarray:
    """[C, L] link loads of a batch of device->bin permutations under a
    routing oracle: ``loads[c, l] = 0.5 Σ_ij T[i,j] R[d2b[i], d2b[j], l]``
    (the permuted quotient pushed through the fractional path incidence).

    Sparse path: traffic is reduced to its unique nonzero upper-triangle
    pairs once per call, each candidate gathers only the ``[E, P]`` padded
    link/fraction tables of its permuted pairs, and the per-link reduction
    is ONE flat ``segment_sum`` over ``row * (L+1) + link`` ids — nothing
    of size ``k^2 * L`` is ever materialized, which is what lets torus-2d
    machines scale past a few hundred devices. Candidates are chunked to
    bound the ``[C, E, P]`` gather slab. ``_routing_loads_dense`` keeps the
    historical dense-[k, k, L] einsum as the reference oracle for the
    equivalence tests."""
    import jax.numpy as jnp
    d2b = np.asarray(device_to_bin)
    if d2b.ndim == 1:
        d2b = d2b[None]
    Th = np.asarray(T, dtype=np.float64)
    iu = np.triu_indices(Th.shape[0], 1)
    pw = 0.5 * (Th[iu] + Th.T[iu])   # diag excluded: path(i, i) is empty
    nz = pw > 0
    n_cand, L = d2b.shape[0], topo.n_links
    if not nz.any() or L == 0:
        return np.zeros((n_cand, L), dtype=np.float32)
    pair_u = jnp.asarray(iu[0][nz].astype(np.int32))
    pair_v = jnp.asarray(iu[1][nz].astype(np.int32))
    pair_w = jnp.asarray(pw[nz].astype(np.float32))
    links = jnp.asarray(topo.path_links)
    fracs = jnp.asarray(topo.path_frac)
    batched = _routing_scorer()
    n_pairs = int(pair_u.shape[0])
    chunk = max(1, (1 << 24) // max(n_pairs * topo.max_path, 1))
    out = [np.asarray(batched(pair_w, pair_u, pair_v, links, fracs,
                              jnp.asarray(d2b[lo:lo + chunk], jnp.int32),
                              n_links=L))
           for lo in range(0, n_cand, chunk)]
    return np.concatenate(out, axis=0)


@functools.lru_cache(maxsize=1)
def _routing_scorer():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("n_links",))
    def batched(pair_w, pair_u, pair_v, links, fracs, rows, *, n_links):
        U = rows[:, pair_u]                      # [C, E] permuted pair bins
        V = rows[:, pair_v]
        lk = links[U, V]                         # [C, E, P] link ids (pad=L)
        fr = fracs[U, V]                         # [C, E, P] fractions (pad=0)
        contrib = pair_w[None, :, None] * fr
        c = rows.shape[0]
        seg = (jnp.arange(c, dtype=jnp.int32)[:, None, None]
               * (n_links + 1) + lk).reshape(-1)
        flat = jax.ops.segment_sum(contrib.reshape(-1), seg,
                                   num_segments=c * (n_links + 1))
        return flat.reshape(c, n_links + 1)[:, :n_links]
    return batched


def _routing_loads_dense(T: np.ndarray, topo: RoutingTopology,
                         device_to_bin: np.ndarray) -> np.ndarray:
    """Reference oracle: the historical dense-[k, k, L] einsum path. Kept
    for sparse-vs-dense equivalence tests; materializes
    ``topo.path_incidence``, so small machines only."""
    import jax.numpy as jnp
    d2b = np.asarray(device_to_bin)
    if d2b.ndim == 1:
        d2b = d2b[None]
    d = T.shape[0]
    R = jnp.asarray(topo.path_incidence)
    Tj = jnp.asarray(T, dtype=jnp.float32)
    batched = _dense_routing_scorer()
    chunk = max(1, (1 << 24) // max(d * d * topo.n_links, 1))
    out = [np.asarray(batched(Tj, R,
                              jnp.asarray(d2b[lo:lo + chunk], jnp.int32)))
           for lo in range(0, d2b.shape[0], chunk)]
    return np.concatenate(out, axis=0)


@functools.lru_cache(maxsize=1)
def _dense_routing_scorer():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def batched(Tj, R, rows):
        def one(row):
            return 0.5 * jnp.einsum("ij,ijl->l", Tj, R[row][:, row])
        return jax.vmap(one)(rows)
    return batched


def _device_map_breakdown(T: np.ndarray, topo: Topology,
                          device_to_bin: np.ndarray, edges=None):
    import jax.numpy as jnp
    if isinstance(topo, RoutingTopology):
        loads = _routing_loads_batch(T, topo, device_to_bin)[0]
        return objective.makespan_from_parts(
            jnp.zeros(T.shape[0], dtype=jnp.float32),
            jnp.asarray(loads, dtype=jnp.float32), jnp.asarray(topo.F_l))
    s2, r2, w2 = edges if edges is not None else _traffic_edges(T)
    return objective.makespan_tree(
        jnp.asarray(device_to_bin, dtype=jnp.int32), s2, r2, w2,
        jnp.zeros(T.shape[0], dtype=jnp.float32),  # comp excluded (uniform)
        jnp.asarray(topo.subtree), jnp.asarray(topo.F_l), k=topo.k)


def makespan_of_device_map(T: np.ndarray, topo: Topology,
                           device_to_bin: np.ndarray) -> float:
    """Score a device->bin assignment: bottleneck link under traffic T.
    comp is uniform (SPMD: one shard per device), so the comm term decides."""
    return float(_device_map_breakdown(T, topo, device_to_bin).comm_max)


def capacity_makespan(T: np.ndarray, topo: Topology,
                      device_to_bin: np.ndarray,
                      shard_work: float = 0.0) -> float:
    """Capacity-normalized makespan of a device->bin permutation:
    ``max(max_b shard_work / speed(b), comm makespan)``. Under SPMD every
    device carries one equal shard, so the comp term is
    permutation-invariant — ``shard_work / min(speed)`` on a heterogeneous
    machine (``topo.bin_speed``), ``shard_work`` on a uniform one — and
    "searched <= identity" carries over from the comm term verbatim."""
    comm = makespan_of_device_map(T, topo, device_to_bin)
    speed = getattr(topo, "bin_speed", None)
    if shard_work <= 0.0:
        return comm
    comp = (float(shard_work) if speed is None
            else float(shard_work / np.asarray(speed).min()))
    return max(comp, comm)


def link_loads_of_device_map(T: np.ndarray, topo: Topology,
                             device_to_bin: np.ndarray) -> np.ndarray:
    """Raw (un-weighted by F_l) per-link byte loads of a device->bin
    assignment, in ``topo.link_nodes`` order (routing topologies: link-id
    order). The dry-run's mapping report sums the entries whose link depth
    is 1 to get cross-pod (DCN) bytes. Clamped at 0: the GEMM-based load
    algebra cancels to small negatives (f32 rounding) on links that carry
    nothing."""
    comm = np.asarray(_device_map_breakdown(T, topo, device_to_bin).comm)
    return np.maximum(comm, 0.0)


@dataclasses.dataclass
class MeshMapping:
    axis_perm: Tuple[int, ...]
    axis_orders: Tuple[int, ...]   # index into _axis_orders per (new) axis;
                                   # (-1, ...) marks a winner that is NOT
                                   # reconstructible from (perm, orders) — a
                                   # random restart or a recursive-subtree
                                   # improvement
    device_to_bin: np.ndarray
    bottleneck: float              # canonical makespan_tree-path score
    n_candidates: int = 0          # size of the enumerated candidate set


def enumerate_candidates(mesh_shape: Sequence[int],
                         max_axis_perms: Optional[int] = None,
                         n_random: int = 0, seed: int = 0
                         ) -> Tuple[np.ndarray, List[Tuple[Tuple[int, ...],
                                                           Tuple[int, ...]]]]:
    """The full candidate set as ONE ``[C, D]`` device->bin array.

    Candidates are logical-axis permutations x per-axis orders, built with
    vectorized mixed-radix arithmetic: logical device ``d`` with original
    coordinates ``c`` lands on leaf ``sum_a inv_order_a[c[perm[a]]] *
    stride_a`` — no per-candidate ``reshape``/``transpose``/``take``. The
    identity assignment is candidate 0 and the enumeration order matches the
    historical nested loop, so tie-breaking (first minimum wins) is
    preserved. ``n_random`` appends seeded random device permutations
    (random restarts) after the structured block.

    Returns ``(device_to_bin [C, D] int64, meta)`` where ``meta[c]`` is the
    ``(axis_perm, axis_orders)`` pair; random restarts carry
    ``axis_orders = (-1,) * rank``.
    """
    shape = tuple(mesh_shape)
    r = len(shape)
    d = int(np.prod(shape))
    coords = np.empty((d, r), dtype=np.int64)       # original mixed radix
    rem = np.arange(d)
    for ax in range(r - 1, -1, -1):
        coords[:, ax] = rem % shape[ax]
        rem //= shape[ax]
    perms = list(itertools.permutations(range(r)))
    if max_axis_perms:
        perms = perms[:max_axis_perms]
    blocks: List[np.ndarray] = []
    meta: List[Tuple[Tuple[int, ...], Tuple[int, ...]]] = []
    for perm in perms:
        new_shape = tuple(shape[p] for p in perm)
        strides = np.ones(r, dtype=np.int64)
        for a in range(r - 2, -1, -1):
            strides[a] = strides[a + 1] * new_shape[a + 1]
        # inverse order maps: position of coordinate c along the new axis
        inv = [np.stack([np.argsort(o, kind="stable")
                         for o in _axis_orders(s)]) for s in new_shape]
        grid = np.stack(np.meshgrid(*[np.arange(p.shape[0]) for p in inv],
                                    indexing="ij"), axis=-1).reshape(-1, r)
        block = np.zeros((grid.shape[0], d), dtype=np.int64)
        for a in range(r):
            block += inv[a][grid[:, a]][:, coords[:, perm[a]]] * strides[a]
        blocks.append(block)
        meta.extend((perm, tuple(int(x) for x in row)) for row in grid)
    if n_random > 0:
        rng = np.random.default_rng(seed)
        blocks.append(np.stack([rng.permutation(d)
                                for _ in range(n_random)]).astype(np.int64))
        meta.extend((tuple(range(r)), (-1,) * r) for _ in range(n_random))
    return np.concatenate(blocks, axis=0), meta


@dataclasses.dataclass
class _ScorerCtx:
    """Per-(traffic, topology) artifacts of the batched permutation scorer:
    unique nonzero traffic pairs, bin-pair LCA table, bin- and node-level
    subtree indicators — built once per search, device-resident."""
    pair_u: object
    pair_v: object
    pair_w: object
    lca: object
    subtree: object
    node_subtree: object
    F_l: object
    k: int
    n_nodes: int
    n_pairs: int


def _make_scorer_ctx(T: np.ndarray, topo: TreeTopology) -> _ScorerCtx:
    import jax.numpy as jnp
    iu = np.triu_indices(T.shape[0], 1)
    w = np.asarray(T, dtype=np.float64)[iu]
    nz = w > 0
    return _ScorerCtx(
        pair_u=jnp.asarray(iu[0][nz].astype(np.int32)),
        pair_v=jnp.asarray(iu[1][nz].astype(np.int32)),
        pair_w=jnp.asarray(w[nz].astype(np.float32)),
        lca=jnp.asarray(topo.lca_table()),
        subtree=jnp.asarray(topo.subtree),
        node_subtree=jnp.asarray(topo.node_subtree_indicator()),
        F_l=jnp.asarray(topo.F_l), k=topo.k, n_nodes=topo.n_nodes,
        n_pairs=int(nz.sum()))


def score_device_maps(T: np.ndarray, topo: Topology,
                      device_to_bin: np.ndarray, chunk: int = 128,
                      _ctx: Optional[_ScorerCtx] = None) -> np.ndarray:
    """Bottleneck cost of every candidate device->bin permutation. [C]

    One jitted evaluation per fixed-size chunk (tail padded so every chunk
    reuses the same executable): the whole chunk's link loads come from
    ``objective.permutation_link_loads_batch`` — flat segment bucketing +
    two GEMMs against the subtree indicators — with a single host
    roundtrip, instead of one edge rebuild + ``makespan_tree`` call + sync
    per candidate. Routing topologies (``core.machine`` torus presets)
    take the sparse path-table oracle instead of the tree-LCA identity.
    """
    import jax.numpy as jnp
    if isinstance(topo, RoutingTopology):
        loads = _routing_loads_batch(T, topo, np.asarray(device_to_bin))
        return (loads * np.asarray(topo.F_l)[None, :]).max(
            axis=1).astype(np.float64)
    c = int(np.asarray(device_to_bin).shape[0])
    ctx = _ctx or _make_scorer_ctx(np.asarray(T, dtype=np.float64), topo)
    if ctx.n_pairs == 0 or topo.n_links == 0:
        return np.zeros(c, dtype=np.float64)
    d2b = jnp.asarray(np.asarray(device_to_bin), dtype=jnp.int32)
    # bound the [chunk, E] gathers for dense traffic matrices
    chunk = int(max(1, min(chunk, c, max(1, (1 << 22) // ctx.n_pairs))))
    out = []
    for lo in range(0, c, chunk):
        blk = d2b[lo:lo + chunk]
        if blk.shape[0] < chunk:
            blk = jnp.concatenate(
                [blk, jnp.tile(d2b[:1], (chunk - blk.shape[0], 1))])
        loads = objective.permutation_link_loads_batch(
            blk, ctx.pair_u, ctx.pair_v, ctx.pair_w, ctx.lca, ctx.subtree,
            ctx.node_subtree, k=ctx.k, n_nodes=ctx.n_nodes)
        out.append(np.asarray((loads * ctx.F_l[None, :]).max(axis=1)))
    return np.concatenate(out)[:c].astype(np.float64)


def _refine_subtrees(T: np.ndarray, topo: TreeTopology, d2b: np.ndarray,
                     cost: float, chunk: int,
                     ctx: _ScorerCtx) -> Tuple[np.ndarray, float]:
    """Recursive per-subtree improvement for deep trees.

    The chosen candidate fixes which device set sits under each internal
    tree node; reordering devices *within* a node's leaf block only moves
    that node's internal link loads, so each subtree can greedily adopt the
    best reordering of its own block (generic ring orders: reversal,
    shifts, Gray), recursing top-down. The identity reorder is always
    scored, so the result is never worse than the input.
    """
    best = np.asarray(d2b, dtype=np.int64).copy()
    root = int(np.nonzero(topo.parent < 0)[0][0])
    stack = [int(n) for n in topo.children(root)]
    while stack:
        node = stack.pop()
        stack.extend(int(n) for n in topo.children(node))
        leaves = topo.leaves_under(node)             # bin indices
        if leaves.size < 2:
            continue
        bin_to_device = np.argsort(best)
        devs = bin_to_device[leaves]                 # devices in this block
        orders = _axis_orders(int(leaves.size))
        trials = np.tile(best, (len(orders), 1))
        for ti, o in enumerate(orders):
            trials[ti, devs[o]] = leaves
        costs = score_device_maps(T, topo, trials, chunk=chunk, _ctx=ctx)
        ti = int(np.argmin(costs))
        if costs[ti] < cost:
            best, cost = trials[ti], float(costs[ti])
    return best, cost


def search_mesh_mapping(mesh_shape: Sequence[int],
                        axis_bytes: Dict[int, float],
                        topo: Optional[Topology] = None,
                        max_axis_perms: Optional[int] = None,
                        traffic: Optional[np.ndarray] = None,
                        n_random: int = 0, seed: int = 0,
                        recursive: bool = False,
                        chunk: int = 128,
                        warm_starts: Optional[Sequence[np.ndarray]] = None,
                        machine=None) -> MeshMapping:
    """Enumerate logical-axis permutations x per-axis orders; return the
    assignment with the smallest bottleneck-link traffic cost.

    The machine tree's leaves are taken in natural order; a candidate maps
    logical device (i_0, .., i_r) to leaf number ``mixed-radix index`` after
    permuting/reordering axes. The identity assignment (no permutation,
    natural per-axis order) is always the first candidate, so the returned
    bottleneck is never worse than identity's.

    The whole candidate set is scored in one batched, jitted evaluation
    (``score_device_maps``); ``n_random`` appends seeded random-restart
    device permutations, and ``recursive=True`` runs the per-subtree
    reordering pass on the winner (deep trees) — both can only lower the
    returned bottleneck.

    ``traffic`` supplies a measured [D, D] device-pair matrix (e.g. from
    ``launch.collectives.parse_collectives(..., traffic=True)``) instead of
    the per-axis ring model built from ``axis_bytes``.

    ``warm_starts`` appends prior winning assignments (each a device->bin
    permutation) to the candidate set — the recompile fixed-point loop
    (``launch.placement``) feeds each round's best order back in, so a
    later round can never regress below an earlier winner.

    ``machine`` (a ``core.machine.MachineSpec``) supplies the topology
    declaratively — ``machine.topology()`` — instead of an explicit
    ``topo``; routing machines (torus presets) are scored through the
    sparse path-table oracle and skip the tree-only recursive pass.
    """
    shape = tuple(mesh_shape)
    d = int(np.prod(shape))
    if topo is None:
        if machine is None:
            raise ValueError("search needs a topology: pass topo= or "
                             "machine=")
        topo = machine.topology()
    is_tree = isinstance(topo, TreeTopology)
    if topo.k != d:
        raise ValueError(f"topology has {topo.k} bins, mesh has {d} devices")
    if traffic is not None:
        T = np.asarray(traffic, dtype=np.float64)
        if T.shape != (d, d):
            raise ValueError(f"traffic is {T.shape}, mesh has {d} devices")
    else:
        T = collective_traffic_matrix(shape, axis_bytes)
    cands, meta = enumerate_candidates(shape, max_axis_perms,
                                       n_random=n_random, seed=seed)
    ws_lo = None
    if warm_starts is not None and len(warm_starts) > 0:
        ws = np.stack([np.asarray(w, dtype=np.int64) for w in warm_starts])
        if ws.shape[1] != d or not (np.sort(ws, axis=1)
                                    == np.arange(d)).all():
            raise ValueError("warm starts must be device->bin permutations "
                             f"of range({d})")
        ws_lo = cands.shape[0]
        cands = np.concatenate([cands, ws], axis=0)
        meta.extend((tuple(range(len(shape))), (-1,) * len(shape))
                    for _ in range(ws.shape[0]))
    ctx = _make_scorer_ctx(T, topo) if is_tree else None
    costs = score_device_maps(T, topo, cands, chunk=chunk, _ctx=ctx)
    # Shortlist + canonical re-score: selection ran on the batched f32
    # pipeline, but every consumer (the placement session, train's identity
    # comparison, tests) observes costs through the makespan_tree path, and
    # the two scorers can disagree by f32 rounding on near-ties. Re-scoring
    # the batched top candidates AND identity through the canonical path
    # makes the returned bottleneck comparable everywhere and keeps
    # "searched <= identity" exact, not just up to scorer noise. (Routing
    # topologies have ONE scorer, so selection and canon already agree.)
    short = list(np.argsort(costs, kind="stable")[:8])
    if 0 not in short:
        short.append(0)                      # identity is always re-scored
    if ws_lo is not None:                    # ... and so is every warm start
        short.extend(j for j in range(ws_lo, cands.shape[0])
                     if j not in short)
    edges = _traffic_edges(T) if is_tree else None
    if is_tree:
        canon = {int(j): float(_device_map_breakdown(T, topo, cands[j],
                                                     edges).comm_max)
                 for j in short}
    else:
        canon = {int(j): float(costs[j]) for j in short}
    i = min(canon, key=lambda j: (canon[j], j))   # ties -> first candidate
    perm, orders_idx = meta[i]
    best_d2b, best_cost = cands[i], canon[i]
    if recursive and is_tree:   # per-subtree pass is tree-only
        ref_d2b, _ = _refine_subtrees(T, topo, best_d2b, float(costs[i]),
                                      chunk, ctx)
        if not np.array_equal(ref_d2b, best_d2b):
            ref_cost = float(_device_map_breakdown(T, topo, ref_d2b,
                                                   edges).comm_max)
            if ref_cost < best_cost:
                best_d2b, best_cost = ref_d2b, ref_cost
                # the assignment no longer follows from (perm, orders)
                orders_idx = (-1,) * len(shape)
    return MeshMapping(perm, orders_idx, np.asarray(best_d2b, np.int64),
                       best_cost, n_candidates=int(cands.shape[0]))


def search(mesh_shape: Sequence[int], topo: Optional[Topology],
           traffic: np.ndarray, *,
           warm_starts: Optional[Sequence[np.ndarray]] = None,
           n_random: int = 0, seed: int = 0, recursive: bool = False,
           chunk: int = 128,
           max_axis_perms: Optional[int] = None,
           machine=None) -> MeshMapping:
    """Placement-facing entry of the mesh-mapping search: measured traffic
    is mandatory (the session always has a compiled module in hand) and
    ``warm_starts`` carries the prior winner(s) of the recompile fixed-point
    loop, so each round's result is monotone vs every earlier round. Thin
    keyword-only front to :func:`search_mesh_mapping`; ``topo=None`` with
    ``machine=`` (a ``core.machine.MachineSpec``) derives the topology
    from the declarative machine model.
    """
    with obs.span("map.search"):
        return search_mesh_mapping(mesh_shape, {}, topo, traffic=traffic,
                                   warm_starts=warm_starts,
                                   n_random=n_random, seed=seed,
                                   recursive=recursive, chunk=chunk,
                                   max_axis_perms=max_axis_perms,
                                   machine=machine)


def expert_placement(traffic: np.ndarray, expert_flops: np.ndarray,
                     topo: TreeTopology, seed: int = 0, seeds: int = 1):
    """MoE expert placement: experts = vertices (weight = FLOPs share),
    expert-pair token traffic = edges; returns expert->bin assignment via the
    full multilevel partitioner. [paper technique, vertex-weighted variant]
    ``seeds > 1`` runs the best-of-S vmapped refinement."""
    from repro.core.partitioner import PartitionConfig, partition
    from repro.graph.graph import from_edges
    e = traffic.shape[0]
    iu = np.triu_indices(e, 1)
    w = traffic[iu] + traffic.T[iu]
    nz = w > 0
    g = from_edges(e, iu[0][nz], iu[1][nz], w[nz].astype(np.float32),
                   expert_flops.astype(np.float32))
    res = partition(g, topo, PartitionConfig(seed=seed, seeds=seeds))
    return res.part, res
