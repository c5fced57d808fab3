"""The benchmark's plain oracle agrees with the program's path-walking
reference (a second witness) and with a hand count."""
import numpy as np
import oracle

LEVELS = [{"name": "dcn", "fanout": 2, "gbps": 6.25},
          {"name": "ici-row", "fanout": 2, "gbps": 50.0},
          {"name": "ici", "fanout": 2, "gbps": 50.0}]


def test_tree_numbering_and_costs():
    t = oracle.Tree(LEVELS)
    assert t.k == 8 and t.n_nodes == 15
    assert list(t.parent[:7]) == [-1, 0, 0, 1, 1, 2, 2]
    assert t.cost[1] == 8.0 and t.cost[3] == 1.0 and t.cost[14] == 1.0
    assert list(t.leaves) == list(range(7, 15))


def test_hand_count_one_edge():
    t = oracle.Tree(LEVELS)
    # leaves 0 and 7 sit in different pods: the path crosses all 6 links
    comp, comm, ms = oracle.loads(t, np.array([0, 7]), np.array([0]),
                                  np.array([1]), np.array([3.0]),
                                  np.ones(2))
    assert comp[0] == 1 and comp[7] == 1
    assert sorted(np.nonzero(comm)[0]) == [1, 2, 3, 6, 7, 14]
    assert ms == 8.0 * 3.0


def test_agrees_with_program_reference():
    from repro.core.machine import MachineSpec, Level
    from repro.core.reference import makespan_ref
    from repro.graph.graph import from_edges
    spec = MachineSpec(name="t", mesh_shape=(2, 2, 2), axes=("a", "b", "c"),
                       levels=tuple(Level(lv["name"], lv["fanout"],
                                          lv["gbps"]) for lv in LEVELS))
    topo = spec.topology()
    rng = np.random.default_rng(0)
    u, v = rng.integers(0, 40, 120), rng.integers(0, 40, 120)
    keep = u < v
    key, w = np.unique(u[keep] * 40 + v[keep], return_counts=True)
    u, v, w = key // 40, key % 40, w.astype(float)
    g = from_edges(40, u, v, w.astype(np.float32))
    part = rng.integers(0, 8, 40)
    t = oracle.Tree(LEVELS)
    comp, comm, ms = oracle.loads(t, part, u, v, w, np.ones(40))
    m_ref, comp_ref, comm_ref = makespan_ref(part, g, topo)
    assert np.allclose(comp, comp_ref)
    assert np.allclose(comm[np.asarray(topo.link_nodes)], comm_ref)
    assert np.isclose(ms, m_ref)


def test_low_precision_sums_drift():
    import ml_dtypes
    t = oracle.Tree(LEVELS)
    n = 3000
    u, v = np.arange(n - 1), np.arange(1, n)
    part = np.arange(n) % 8
    w = np.ones(n - 1)
    exact = oracle.loads(t, part, u, v, w, np.ones(n))
    low = oracle.loads_low(t, part, u, v, w, np.ones(n), ml_dtypes.bfloat16)
    assert np.abs(low[1] - exact[1]).max() > 0
