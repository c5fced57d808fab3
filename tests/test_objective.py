"""The paper's objective: JAX quotient-matrix implementation vs the
path-walking oracle, across every topology generalization of §3.1."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objective, reference
from repro.core.topology import (balanced_tree, fat_tree_topology,
                                 flat_topology, make_tree, production_tree,
                                 torus2d_topology)
from repro.graph.generators import grid2d, rmat, weighted_nodes


def _rand_graph(n=60, m=180, seed=0, weighted=True):
    g = rmat(n, m, seed=seed)
    if weighted:
        g = weighted_nodes(g, seed=seed)
    return g


def _jx_makespan(g, topo, part):
    return objective.makespan_tree(
        jnp.asarray(part, jnp.int32), jnp.asarray(g.senders),
        jnp.asarray(g.receivers), jnp.asarray(g.edge_weight),
        jnp.asarray(g.node_weight), jnp.asarray(topo.subtree),
        jnp.asarray(topo.F_l), k=topo.k)


TOPOLOGIES = [
    ("flat8", lambda: flat_topology(8)),
    ("flat8_F3", lambda: flat_topology(8, F=3.0)),
    ("tree_2_2_2", lambda: balanced_tree((2, 2, 2))),
    ("tree_costs", lambda: balanced_tree((2, 4), F=1.0,
                                         level_cost=(8.0, 1.0))),
    ("production", lambda: production_tree(2, 2, 4)),
    ("fat_tree", lambda: fat_tree_topology(16)),
]


@pytest.mark.parametrize("name,mk", TOPOLOGIES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_makespan_matches_oracle(name, mk, seed):
    topo = mk()
    g = _rand_graph(seed=seed)
    rng = np.random.default_rng(seed)
    part = rng.integers(0, topo.k, g.n_nodes)
    br = _jx_makespan(g, topo, part)
    m_ref, comp_ref, comm_ref = reference.makespan_ref(part, g, topo)
    np.testing.assert_allclose(np.asarray(br.comp), comp_ref, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(br.comm), comm_ref, rtol=1e-4,
                               atol=1e-4)
    assert abs(float(br.makespan) - m_ref) <= 1e-3 * max(1.0, m_ref)


def test_vertex_weighted_variant():
    """§3.1: bin load = sum of vertex weights."""
    topo = flat_topology(4)
    g = weighted_nodes(_rand_graph(), seed=3)
    part = np.random.default_rng(0).integers(0, 4, g.n_nodes)
    br = _jx_makespan(g, topo, part)
    for b in range(4):
        assert np.isclose(float(br.comp[b]), g.node_weight[part == b].sum(),
                          rtol=1e-5)


def test_router_generalization():
    """§3.1: routers take no load; they only appear as path interior."""
    # path: root(router) - mid(router) - 2 leaves each
    parent = [-1, 0, 0, 1, 1, 2, 2]
    topo = make_tree(parent)
    assert topo.k == 4                      # four leaves compute
    assert topo.n_links == 6
    g = grid2d(6, 6)
    part = np.arange(g.n_nodes) % 4
    m_ref, comp_ref, comm_ref = reference.makespan_ref(part, g, topo)
    br = _jx_makespan(g, topo, part)
    np.testing.assert_allclose(np.asarray(br.comm), comm_ref, atol=1e-3)
    # traffic between leaves under different mid-routers crosses 4 links
    assert comm_ref[np.argmax(comm_ref)] > 0


def test_routing_oracle_torus_single_and_multipath():
    g = _rand_graph(40, 120, seed=5)
    rng = np.random.default_rng(5)
    for multipath in (False, True):
        topo = torus2d_topology(3, 3, multipath=multipath)
        part = rng.integers(0, topo.k, g.n_nodes)
        br = objective.makespan_routing(
            jnp.asarray(part, jnp.int32), jnp.asarray(g.senders),
            jnp.asarray(g.receivers), jnp.asarray(g.edge_weight),
            jnp.asarray(g.node_weight), jnp.asarray(topo.path_incidence),
            jnp.asarray(topo.F_l), k=topo.k)
        m_ref, comp_ref, comm_ref = reference.makespan_routing_ref(
            part, g, topo)
        np.testing.assert_allclose(np.asarray(br.comm), comm_ref, atol=1e-3)
    # XY and YX dimension-ordered routes have equal hop counts, so the
    # TOTAL link traffic is conserved under multipath (the bottleneck may
    # go either way — splitting can land on an already-hot link).
    topo1 = torus2d_topology(3, 3, multipath=False)
    topo2 = torus2d_topology(3, 3, multipath=True)
    part = rng.integers(0, 9, g.n_nodes)
    _, _, c1 = reference.makespan_routing_ref(part, g, topo1)
    _, _, c2 = reference.makespan_routing_ref(part, g, topo2)
    assert abs(c1.sum() - c2.sum()) < 1e-4 * max(c1.sum(), 1.0)


def test_total_cut_and_cvol():
    g = _rand_graph(seed=7)
    part = np.random.default_rng(7).integers(0, 6, g.n_nodes)
    W = objective.quotient_matrix(
        jnp.asarray(part, jnp.int32), jnp.asarray(g.senders),
        jnp.asarray(g.receivers), jnp.asarray(g.edge_weight), 6)
    assert np.isclose(float(objective.total_cut(W)),
                      reference.total_cut_ref(part, g), rtol=1e-5)
    cvol = objective.comm_volumes(
        jnp.asarray(part, jnp.int32), jnp.asarray(g.senders),
        jnp.asarray(g.receivers), jnp.asarray(g.node_weight), 6)
    # oracle for cvol
    ref = np.zeros(6)
    for v in range(g.n_nodes):
        nbrs = g.receivers[g.offsets[v]:g.offsets[v + 1]]
        foreign = {int(part[u]) for u in nbrs} - {int(part[v])}
        ref[part[v]] += g.node_weight[v] * len(foreign)
    np.testing.assert_allclose(np.asarray(cvol), ref, rtol=1e-5)


def test_permutation_link_loads_matches_quotient_path():
    """The mapping case is a permutation of T: the gathered-indicator GEMM
    identity must reproduce quotient_matrix + link_loads_tree exactly."""
    rng = np.random.default_rng(11)
    topo = production_tree(2, 2, 2)
    d = topo.k
    T = rng.uniform(0, 5, (d, d))
    T = np.triu(T, 1)
    T = T + T.T
    for _ in range(3):
        d2b = rng.permutation(d)
        loads = np.asarray(objective.permutation_link_loads(
            jnp.asarray(T, jnp.float32), jnp.asarray(topo.subtree),
            jnp.asarray(d2b, jnp.int32)))
        # reference: relabel T into bin space, run the quotient path
        W = np.zeros_like(T)
        W[np.ix_(d2b, d2b)] = T
        ref = np.asarray(objective.link_loads_tree(
            jnp.asarray(W, jnp.float32), jnp.asarray(topo.subtree)))
        np.testing.assert_allclose(loads, ref, rtol=1e-5, atol=1e-4)


def test_permutation_batch_scorer_matches_single():
    """LCA-bucketed batch scorer == dense single-candidate identity."""
    rng = np.random.default_rng(12)
    topo = balanced_tree((2, 2, 2), level_cost=(4.0, 2.0, 1.0))
    d = topo.k
    T = rng.uniform(0, 3, (d, d)) * (rng.uniform(0, 1, (d, d)) > 0.4)
    T = np.triu(T, 1)
    T = T + T.T
    cands = np.stack([rng.permutation(d) for _ in range(5)])
    iu = np.triu_indices(d, 1)
    w = T[iu]
    nz = w > 0
    loads = np.asarray(objective.permutation_link_loads_batch(
        jnp.asarray(cands, jnp.int32),
        jnp.asarray(iu[0][nz], jnp.int32), jnp.asarray(iu[1][nz], jnp.int32),
        jnp.asarray(w[nz], jnp.float32), jnp.asarray(topo.lca_table()),
        jnp.asarray(topo.subtree),
        jnp.asarray(topo.node_subtree_indicator()),
        k=topo.k, n_nodes=topo.n_nodes))
    for c, want in zip(cands, loads):
        one = np.asarray(objective.permutation_link_loads(
            jnp.asarray(T, jnp.float32), jnp.asarray(topo.subtree),
            jnp.asarray(c, jnp.int32)))
        np.testing.assert_allclose(want, one, rtol=1e-5, atol=1e-4)


def _dot_precisions(fn, *args, **kw):
    """The precision attribute of every GEMM in ``fn``'s lowered module."""
    text = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).as_text()
    return [line.split("precision = ")[-1].split("]")[0] + "]"
            if "precision = " in line else "DEFAULT"
            for line in text.splitlines() if "stablehlo.dot_general" in line]


@pytest.mark.parametrize("scorer", ["tree", "routing", "permutation",
                                    "permutation_batch"])
def test_load_gemms_run_at_highest_precision(scorer):
    """A TPU rounds default-precision f32 GEMM operands to bf16, which
    puts integer link loads above 256 off the path-walking oracle: every
    load GEMM must ask for HIGHEST (visible in the jaxpr on any backend)."""
    topo = balanced_tree((2, 2, 2))
    k, f32, i32 = topo.k, jnp.float32, jnp.int32
    W = jnp.ones((k, k), f32)
    if scorer == "tree":
        got = _dot_precisions(objective.link_loads_tree, W,
                              jnp.asarray(topo.subtree))
    elif scorer == "routing":
        got = _dot_precisions(objective.link_loads_routing, W,
                              jnp.ones((k, k, 3), f32))
    elif scorer == "permutation":
        got = _dot_precisions(objective.permutation_link_loads, W,
                              jnp.asarray(topo.subtree), jnp.arange(k))
    else:
        pair = jnp.arange(k - 1, dtype=i32)
        got = _dot_precisions(
            objective.permutation_link_loads_batch,
            jnp.arange(k, dtype=i32)[None], pair, pair + 1,
            jnp.ones((k - 1,), f32), jnp.asarray(topo.lca_table()),
            jnp.asarray(topo.subtree),
            jnp.asarray(topo.node_subtree_indicator()),
            k=k, n_nodes=topo.n_nodes)
    assert got and all(p == "[HIGHEST, HIGHEST]" for p in got), got


def test_makespan_tree_batch_matches_per_candidate():
    """vmap fallback: batched breakdown == one makespan_tree per row."""
    g = _rand_graph(30, 90, seed=13)
    topo = balanced_tree((2, 3))
    rng = np.random.default_rng(13)
    parts = rng.integers(0, topo.k, (4, g.n_nodes))
    br = objective.makespan_tree_batch(
        jnp.asarray(parts, jnp.int32), jnp.asarray(g.senders),
        jnp.asarray(g.receivers), jnp.asarray(g.edge_weight),
        jnp.asarray(g.node_weight), jnp.asarray(topo.subtree),
        jnp.asarray(topo.F_l), k=topo.k)
    assert br.comm.shape == (4, topo.n_links)
    for i in range(4):
        one = _jx_makespan(g, topo, parts[i])
        np.testing.assert_allclose(np.asarray(br.makespan)[i],
                                   float(one.makespan), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(br.comm)[i],
                                   np.asarray(one.comm), rtol=1e-5,
                                   atol=1e-4)


def test_soft_cost_approaches_max():
    comp = jnp.asarray([3.0, 7.0, 1.0])
    comm = jnp.asarray([2.0, 9.0])
    F_l = jnp.ones(2)
    exact = 9.0
    prev = None
    for temp in (1.0, 0.3, 0.05, 0.01):
        s = float(objective.soft_cost(comp, comm, F_l, jnp.float32(temp)))
        assert s >= exact - 1e-4
        if prev is not None:
            assert s <= prev + 1e-6
        prev = s
    assert abs(prev - exact) < 0.2


def test_load_gradients_are_softmax_weights():
    comp = jnp.asarray([3.0, 7.0, 1.0])
    comm = jnp.asarray([2.0, 9.0])
    F_l = jnp.asarray([1.0, 0.5])
    g_comp, g_link = objective.load_gradients(comp, comm, F_l,
                                              jnp.float32(0.1))
    total = float(g_comp.sum() + (g_link / F_l).sum())
    assert abs(total - 1.0) < 1e-5
    assert float(g_comp[1]) > float(g_comp[0]) > float(g_comp[2])
