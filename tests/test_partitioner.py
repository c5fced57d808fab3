"""Multilevel makespan partitioner: optimality gap vs brute force (C5),
improvement over random, oracle cross-check, baseline comparisons."""
import functools

import numpy as np
import pytest

from repro.core import baselines, reference
from repro.core.partitioner import PartitionConfig, partition, verify
from repro.core.refine import RefineConfig, refine
from repro.core.topology import (balanced_tree, flat_topology,
                                 production_tree, with_bin_speed)
from repro.graph.generators import grid2d, rmat, weighted_nodes


def test_brute_force_gap_small():
    """Heuristic within 1.5x of the exact optimum on tiny instances."""
    for seed in range(3):
        g = rmat(8, 20, seed=seed)
        topo = flat_topology(2, F=1.0)
        best, best_p = reference.brute_force_optimum(g, topo)
        res = partition(g, topo, PartitionConfig(
            seed=seed, coarse_factor=100,
            refine=RefineConfig(rounds=80, seed=seed)))
        assert res.makespan <= 1.5 * best + 1e-6, (res.makespan, best)


def test_partition_beats_random_and_matches_oracle():
    g = grid2d(40, 40)
    topo = balanced_tree((2, 4, 4), F=0.5, level_cost=(4.0, 0.5, 0.5))
    res = partition(g, topo)
    verify(g, topo, res)                       # JAX == path-walking oracle
    rand = baselines.random_partition(g.n_nodes, topo.k, seed=1)
    m_rand = baselines.score_all(g, topo, rand)["makespan"]
    assert res.makespan < 0.5 * m_rand


def test_refine_never_worse_than_init():
    g = rmat(300, 1200, seed=2)
    topo = flat_topology(8)
    part0 = baselines.random_partition(g.n_nodes, 8, seed=2)
    m0 = baselines.score_all(g, topo, part0)["makespan"]
    _, m1, _ = refine(g, topo, part0, RefineConfig(rounds=40))
    assert m1 <= m0 + 1e-6


def test_makespan_objective_beats_cut_objective_on_makespan():
    """C1 core claim: optimizing the bottleneck beats optimizing total cut
    when judged by the bottleneck (hierarchical topology, slow top link)."""
    g = grid2d(32, 32)
    topo = balanced_tree((2, 8), F=1.0, level_cost=(8.0, 1.0))
    ours = partition(g, topo).part
    cut = baselines.total_cut_partition(g, topo.k)
    s_ours = baselines.score_all(g, topo, ours)
    s_cut = baselines.score_all(g, topo, cut)
    assert s_ours["makespan"] < s_cut["makespan"]
    # and the classic objective still wins on its own metric
    assert s_cut["total_cut"] <= s_ours["total_cut"] * 1.5


def test_flat_twice_emulation_runs():
    g = grid2d(24, 24)
    topo = production_tree(2, 2, 4)
    part = baselines.flat_twice_partition(g, topo)
    s = baselines.score_all(g, topo, part)
    assert s["makespan"] < baselines.score_all(
        g, topo, baselines.random_partition(g.n_nodes, topo.k))["makespan"]


def test_partition_seeds_never_worse_than_single():
    """Best-of-S: slot 0 reproduces the seeds=1 trajectory (same initial
    partition, same PRNG key), so the S-way minimum can't be worse."""
    g = rmat(300, 1200, seed=3)
    topo = balanced_tree((2, 4), level_cost=(4.0, 1.0))
    m1 = partition(g, topo, PartitionConfig(seed=0)).makespan
    res = partition(g, topo, PartitionConfig(seed=0, seeds=4))
    assert res.makespan <= m1 * (1 + 1e-5) + 1e-5
    verify(g, topo, res)                      # still a valid scored partition
    with pytest.raises(ValueError):
        partition(g, topo, PartitionConfig(seeds=0))


def test_refine_batch_slot0_matches_refine():
    from repro.core.refine import refine_batch
    from repro.core.initial import random_partition as rand_init
    g = rmat(200, 700, seed=5)
    topo = flat_topology(4)
    p0 = rand_init(g.n_nodes, 4, g.node_weight, seed=0)
    p1 = rand_init(g.n_nodes, 4, g.node_weight, seed=1)
    cfg = RefineConfig(rounds=15, seed=0)
    bp, bm, _ = refine(g, topo, p0, cfg)
    bps, bms, stats = refine_batch(g, topo, np.stack([p0, p1]), cfg)
    assert bps.shape == (2, g.n_nodes) and bms.shape == (2,)
    np.testing.assert_array_equal(bp, bps[0])
    np.testing.assert_allclose(float(bms[0]), bm, rtol=1e-6)
    assert stats.makespan.shape == (2, 15)


def test_sampled_heavy_arc_is_exact():
    """The sparse-mode candidate sampler must pick the bin of the true
    heaviest incident arc (two-pass segment argmax in ``_heavy_neighbours``,
    found once per refinement; the old float32 composite key broke down on
    large arc counts)."""
    import jax
    import jax.numpy as jnp
    from repro.core import refine as refine_mod
    rng = np.random.default_rng(7)
    g = rmat(50, 200, seed=7)
    k = 4
    part = rng.integers(0, k, g.n_nodes).astype(np.int32)
    heavy_nbr = refine_mod._heavy_neighbours(
        jnp.asarray(g.senders), jnp.asarray(g.receivers),
        jnp.asarray(g.edge_weight), g.n_nodes)
    cand = refine_mod._sample_candidates(
        jnp.asarray(part), jnp.asarray(g.receivers), heavy_nbr,
        jnp.asarray(g.offsets[:-1], jnp.int32),
        jnp.asarray(g.degrees(), jnp.int32), jnp.zeros(k), 0,
        jax.random.PRNGKey(0), g.n_nodes)
    cand = np.asarray(cand)
    for v in range(g.n_nodes):
        lo, hi = g.offsets[v], g.offsets[v + 1]
        if lo == hi:
            assert cand[v] == part[v]
            continue
        w = g.edge_weight[lo:hi]
        # the sampler may pick any arc attaining the max weight
        best_bins = {int(part[g.receivers[lo + i]])
                     for i in np.nonzero(w >= w.max())[0]}
        assert int(cand[v]) in best_bins


def _trajectory_case(mode, hetero):
    """A refinement problem in sparse (n*k = 256,000 > dense_threshold) or
    dense mode, with link costs low enough that compute and links compete,
    fractional vertex weights, and optionally bins at half speed."""
    if mode == "sparse":
        g = weighted_nodes(rmat(1000, 6000, seed=11), seed=11, lo=0.2, hi=5.0)
        topo = balanced_tree((16, 16), level_cost=(0.04, 0.01))
    else:
        g = weighted_nodes(rmat(200, 800, seed=12), seed=12, lo=0.2, hi=5.0)
        topo = balanced_tree((2, 4), level_cost=(0.4, 0.1))
    if hetero:
        topo = with_bin_speed(
            topo, np.where(np.arange(topo.k) % 3 == 0, 0.5, 1.0))
    part0 = baselines.random_partition(g.n_nodes, topo.k, seed=3)
    cfg = RefineConfig(rounds=8, seed=5)
    assert (g.n_nodes * topo.k > cfg.dense_threshold) == (mode == "sparse")
    return g, topo, part0, cfg


# Recorded on XLA:CPU from the refinement that scored every partition twice
# a round and searched heavy arcs every round: (sha256[:16] of best_part's
# int32 bytes, best_m, RefineStats). Passes removed since must leave every
# bit of the trajectory as it was.
_TRAJECTORIES = {
    ("sparse", False): ("cb5f52f80de1d427", 39.23999786376953, dict(
        makespan=[56.599998474121094, 50.84000015258789, 50.84000015258789, 49.31999969482422, 39.23999786376953, 39.23999786376953, 45.63999938964844, 45.36000061035156],
        comp_max=[21.968767166137695, 23.943756103515625, 23.943756103515625, 35.17722702026367, 24.613998413085938, 28.325851440429688, 30.91587257385254, 30.301044464111328],
        comm_max=[56.599998474121094, 50.84000015258789, 50.84000015258789, 49.31999969482422, 39.23999786376953, 39.23999786376953, 45.63999938964844, 45.36000061035156],
        moved=[182, 152, 6, 101, 104, 8, 96, 99])),
    ("sparse", True): ("71f515adeb126f07", 38.47999954223633, dict(
        makespan=[56.84000015258789, 52.119998931884766, 52.119998931884766, 50.52000045776367, 38.47999954223633, 41.79999923706055, 53.23999786376953, 47.68000030517578],
        comp_max=[38.84343719482422, 33.75094985961914, 33.75094985961914, 33.40046310424805, 30.877817153930664, 38.105445861816406, 37.34116744995117, 30.972604751586914],
        comm_max=[56.84000015258789, 52.119998931884766, 52.119998931884766, 50.52000045776367, 38.47999954223633, 41.79999923706055, 53.23999786376953, 47.68000030517578],
        moved=[186, 144, 10, 97, 98, 13, 85, 99])),
    ("dense", False): ("5d28070530082842", 124.3143310546875, dict(
        makespan=[152.0, 149.1999969482422, 137.60000610351562, 130.8000030517578, 127.55146026611328, 133.0576171875, 124.3143310546875, 144.36215209960938],
        comp_max=[79.22832489013672, 84.15959167480469, 95.36636352539062, 103.55293273925781, 127.55146026611328, 133.0576171875, 124.3143310546875, 144.36215209960938],
        comm_max=[152.0, 149.1999969482422, 137.60000610351562, 130.8000030517578, 96.80000305175781, 90.4000015258789, 84.0, 72.4000015258789],
        moved=[31, 29, 32, 46, 70, 72, 46, 84])),
    ("dense", True): ("4f8d90800d9120fe", 128.98712158203125, dict(
        makespan=[140.0, 128.98712158203125, 138.17564392089844, 133.77496337890625, 157.99810791015625, 135.10494995117188, 142.32589721679688, 186.88421630859375],
        comp_max=[137.41880798339844, 128.98712158203125, 138.17564392089844, 133.77496337890625, 157.99810791015625, 135.10494995117188, 142.32589721679688, 186.88421630859375],
        comm_max=[140.0, 127.20000457763672, 115.5999984741211, 104.4000015258789, 73.5999984741211, 71.5999984741211, 51.60000228881836, 43.20000076293945],
        moved=[59, 77, 61, 75, 72, 63, 60, 61])),
}


@pytest.mark.parametrize("mode,hetero", list(_TRAJECTORIES),
                         ids=[f"{m}-{'speed' if h else 'uniform'}"
                              for m, h in _TRAJECTORIES])
def test_refine_trajectory_is_pinned(mode, hetero):
    """Each round prices moves from the scoring that accepted its input, and
    the heavy arcs are found once: the same work as scoring every partition
    afresh, so the trajectory repeats bit for bit."""
    import hashlib
    digest, best_m, fields = _TRAJECTORIES[(mode, hetero)]
    bp, bm, stats = refine(*_trajectory_case(mode, hetero))
    assert hashlib.sha256(bp.astype(np.int32).tobytes()).hexdigest()[:16] == digest
    assert np.float32(bm) == np.float32(best_m)
    for name, want in fields.items():
        got = getattr(stats, name)
        np.testing.assert_array_equal(got, np.asarray(want, dtype=got.dtype),
                                      err_msg=name)


def _eqns(jaxpr):
    import jax
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _sparse_scan_body():
    """(graph, k, every equation of the sparse refinement scan's body) of
    the pinned sparse case."""
    import jax
    import jax.numpy as jnp
    from repro.core import refine as refine_mod
    g, topo, part0, cfg = _trajectory_case("sparse", False)
    closed = jax.make_jaxpr(functools.partial(
        refine_mod._refine_core, k=topo.k, rounds=cfg.rounds, dense=False,
        damping=cfg.damping, temp0=cfg.temp0, temp_min=cfg.temp_min,
        anneal=cfg.anneal, inflow_slack=cfg.inflow_slack))(
        jnp.asarray(part0, jnp.int32), *refine_mod._graph_arrays(g),
        jnp.asarray(topo.subtree), jnp.asarray(topo.F_l),
        jax.random.PRNGKey(0))
    scans = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    return g, topo.k, list(_eqns(scans[0].params["jaxpr"].jaxpr))


def test_sparse_round_scatters_once_into_quotient():
    """The sparse scan body holds one scatter into the k^2 quotient cells
    (the scoring of the moved partition) and no scatter-max (the heavy-arc
    search runs once, outside the scan)."""
    _, k, eqns = _sparse_scan_body()
    body = [e for e in eqns if e.primitive.name.startswith("scatter")]
    names = sorted(e.primitive.name for e in body)
    quotient = [e for e in body
                if e.primitive.name == "scatter-add"
                and e.invars[0].aval.shape == (k * k,)]
    assert len(quotient) == 1, names
    assert "scatter-max" not in names, names


def test_sparse_round_walks_each_edge_once():
    """No gather or scatter of the sparse scan body walks m indices (one
    per arc). It walks m/2, one per edge: the bins of both ends of the edge
    under the partition and the candidates (4), the three prices, the bins
    of the moved partition for the quotient (2), and three scatter-adds:
    the gain at each end and the one k^2 quotient."""
    g, k, eqns = _sparse_scan_body()
    m = g.n_arcs

    def n_indices(e):
        return e.invars[1].aval.shape[0]

    walks = [e for e in eqns if e.primitive.name == "gather"
             or e.primitive.name.startswith("scatter")]
    assert all(n_indices(e) != m for e in walks)
    edge = [e.primitive.name for e in walks if n_indices(e) == m // 2]
    assert sorted(edge) == ["gather"] * 9 + ["scatter-add"] * 3, edge
    quotient = [e for e in walks if e.primitive.name == "scatter-add"
                and e.invars[0].aval.shape == (k * k,)]
    assert len(quotient) == 1 and n_indices(quotient[0]) == m // 2


def _edge_view_graphs():
    return {"rmat": rmat(1000, 6000, seed=11),
            "grid": grid2d(20, 30),
            "vertex-weighted": weighted_nodes(rmat(300, 1500, seed=4),
                                              seed=4, lo=0.2, hi=5.0)}


@pytest.mark.parametrize("name", list(_edge_view_graphs()))
def test_edge_quotient_equals_arc_quotient(name):
    """``W_e + W_eᵀ`` over one arc per edge is the quotient over both arcs,
    exactly, for whole-number edge weights."""
    import jax.numpy as jnp
    from repro.core import objective
    from repro.core import refine as refine_mod
    g = _edge_view_graphs()[name]
    k = 64
    part = jnp.asarray(np.random.default_rng(1).integers(0, k, g.n_nodes),
                       jnp.int32)
    eu, ev, ew = (jnp.asarray(x) for x in g.edges())
    want = objective.quotient_matrix(part, jnp.asarray(g.senders),
                                     jnp.asarray(g.receivers),
                                     jnp.asarray(g.edge_weight), k)
    np.testing.assert_array_equal(
        np.asarray(refine_mod._quotient(part, eu, ev, ew, k)),
        np.asarray(want))


@pytest.mark.parametrize("name", ["rmat", "grid", "grid-weighted"])
def test_edge_gain_equals_arc_gain(name):
    """The edge-once link gain equals the sum over both arcs of every edge,
    bit for bit, on graphs from ``from_edges`` (fractional weights too)."""
    import jax
    import jax.numpy as jnp
    from repro.core import refine as refine_mod
    g = {"rmat": rmat(1000, 6000, seed=11), "grid": grid2d(20, 30),
         "grid-weighted": grid2d(20, 30, seed=2, weighted=True)}[name]
    topo = balanced_tree((16, 16), level_cost=(0.04, 0.01))
    rng = np.random.default_rng(2)
    part = jnp.asarray(rng.integers(0, topo.k, g.n_nodes), jnp.int32)
    cand = jnp.asarray(rng.integers(0, topo.k, g.n_nodes), jnp.int32)
    g_link = jax.random.uniform(jax.random.PRNGKey(3), (topo.subtree.shape[0],))
    pi = refine_mod.price_matrix(g_link, jnp.asarray(topo.subtree))
    s, r = jnp.asarray(g.senders), jnp.asarray(g.receivers)
    w = jnp.asarray(g.edge_weight)
    want = jax.ops.segment_sum(w * (pi[part[s], part[r]] - pi[cand[s], part[r]]),
                               s, num_segments=g.n_nodes)
    eu, ev, ew = (jnp.asarray(x) for x in g.edges())
    np.testing.assert_array_equal(
        np.asarray(refine_mod._gain_comm(part, cand, pi, eu, ev, ew)),
        np.asarray(want))


@pytest.mark.parametrize("shape", [(16, 16), (2, 4), (4, 4, 4)])
def test_price_matrix_is_symmetric(shape):
    """``pi[a, b]`` and ``pi[b, a]`` are the same float: the edge-once round
    prices both ends of an edge with one gather."""
    import jax
    import jax.numpy as jnp
    from repro.core import refine as refine_mod
    topo = balanced_tree(shape)
    g_link = jax.random.uniform(jax.random.PRNGKey(4), (topo.subtree.shape[0],))
    pi = np.asarray(refine_mod.price_matrix(g_link, jnp.asarray(topo.subtree)))
    np.testing.assert_array_equal(pi, pi.T)


def test_asymmetric_arc_list_raises():
    """A graph that lists one arc of an edge breaks the symmetric-arc
    invariant that the edge-once round rests on: refinement refuses it."""
    from repro.graph.graph import Graph
    g = grid2d(4, 4)
    up = g.senders < g.receivers
    half = Graph(g.n_nodes, g.senders[up], g.receivers[up],
                 g.edge_weight[up], g.node_weight,
                 np.concatenate([[0], np.cumsum(np.bincount(
                     g.senders[up], minlength=g.n_nodes))]))
    with pytest.raises(ValueError, match="symmetric"):
        half.edges()
    part = baselines.random_partition(g.n_nodes, 4, seed=0)
    with pytest.raises(ValueError, match="symmetric"):
        refine(half, flat_topology(4), part, RefineConfig(rounds=2))


def test_vertex_weighted_partitioning():
    g = weighted_nodes(rmat(200, 800, seed=4), seed=4, lo=0.2, hi=5.0)
    topo = flat_topology(4, F=0.05)   # compute-dominated regime
    res = partition(g, topo)
    total_w = g.node_weight.sum()
    # bottleneck bin within 40% of perfect balance in the compute regime
    assert res.comp_max <= total_w / 4 * 1.4
