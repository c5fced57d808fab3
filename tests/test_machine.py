"""The declarative machine-model API (core/machine.py): preset registry,
bit-for-bit equivalence with the historical production machine, the
heterogeneous capacity-normalized objective, routing presets through the
mapping search, the launch deprecation shims, and machine-aware cache
keys (DESIGN.md §Machine-models)."""
import dataclasses

import numpy as np
import pytest

from repro.core import machine, mapping, objective, reference
from repro.core.machine import Level, MachineSpec
from repro.core.topology import (RoutingTopology, balanced_tree,
                                 production_tree, with_bin_speed)


# ---------------------------------------------------------------------------
# Registry + presets
# ---------------------------------------------------------------------------

def test_registry_has_the_documented_presets():
    names = MachineSpec.presets()
    for name in ("tpu_v5e-256", "tpu_v5e-512", "gpu-superpod", "torus-2d",
                 "tpu-mixed-32"):
        assert name in names
    with pytest.raises(KeyError):
        MachineSpec.preset("nope")
    assert machine.resolve(None) is None
    assert machine.resolve("gpu-superpod") is MachineSpec.preset(
        "gpu-superpod")
    spec = MachineSpec.preset("tpu_v5e-512")
    assert machine.resolve(spec) is spec


def test_register_rejects_duplicates_and_validates():
    spec = MachineSpec(name="t-4", mesh_shape=(4,), axes=("data",),
                       levels=(Level("l", 4, 10.0),))
    machine.register(spec)
    with pytest.raises(ValueError):
        machine.register(spec)
    machine.register(dataclasses.replace(spec), overwrite=True)
    with pytest.raises(ValueError):        # leaves != devices
        MachineSpec(name="bad", mesh_shape=(4,), axes=("data",),
                    levels=(Level("l", 3, 10.0),))
    with pytest.raises(ValueError):        # axes arity
        MachineSpec(name="bad", mesh_shape=(2, 2), axes=("data",),
                    levels=(Level("l", 4, 10.0),))
    with pytest.raises(ValueError):        # per-leaf array length
        MachineSpec(name="bad", mesh_shape=(4,), axes=("data",),
                    levels=(Level("l", 4, 10.0),),
                    leaf_tflops=(1.0, 2.0))
    with pytest.raises(ValueError):        # unknown kind
        MachineSpec(name="bad", mesh_shape=(4,), axes=("data",),
                    kind="hypercube")
    with pytest.raises(ValueError):        # routing topologies carry no
        MachineSpec(name="bad", mesh_shape=(2, 2),  # bin_speed: refuse
                    axes=("x", "y"), kind="torus2d", torus=(2, 2),
                    leaf_tflops=(100.0, 100.0, 50.0, 50.0))


def test_v5e_presets_reproduce_production_tree_bit_for_bit():
    for name, ref in (("tpu_v5e-512", production_tree(2, 16, 16)),
                      ("tpu_v5e-256", production_tree(1, 16, 16))):
        spec = MachineSpec.preset(name)
        topo = spec.tree()
        np.testing.assert_array_equal(topo.parent, ref.parent)
        np.testing.assert_array_equal(topo.is_router, ref.is_router)
        np.testing.assert_array_equal(topo.F_l, ref.F_l)
        np.testing.assert_array_equal(topo.subtree, ref.subtree)
        assert topo.bin_speed is None       # uniform: historical code path
        # the historical hardware constants fall out of the spec
        assert float(spec.peak_flops.max()) == 197e12
        assert float(spec.hbm_bw.max()) == 819e9
        assert spec.link_bw == 50e9


def test_v5e_mesh_specs_match_historical():
    assert MachineSpec.preset("tpu_v5e-512").mesh_spec() == \
        ((2, 16, 16), ("pod", "data", "model"))
    assert MachineSpec.preset("tpu_v5e-256").mesh_spec() == \
        ((16, 16), ("data", "model"))


def test_gpu_superpod_wires_the_fat_tree():
    spec = MachineSpec.preset("gpu-superpod")
    topo = spec.tree()
    assert topo.k == 64
    # two link classes: NVLink leaves at F=1, IB uplinks at 450/100 = 4.5x
    costs = sorted(set(np.round(topo.F_l, 4)))
    assert costs == [1.0, 4.5]
    assert spec.heterogeneous is False


def test_torus_preset_is_a_routing_topology():
    spec = MachineSpec.preset("torus-2d")
    topo = spec.topology()
    assert isinstance(topo, RoutingTopology)
    assert topo.k == spec.n_devices == 64
    with pytest.raises(TypeError):
        spec.tree()


def test_heterogeneous_preset_has_nonuniform_speeds():
    spec = MachineSpec.preset("tpu-mixed-32")
    assert spec.heterogeneous
    topo = spec.tree()
    speed = topo.bin_speed
    assert speed is not None and speed.shape == (32,)
    assert speed.max() == 1.0
    assert len(set(np.round(speed, 6))) == 2     # two generations
    # per-leaf rooflines really differ across the pods
    assert spec.peak_flops[0] > spec.peak_flops[-1]
    assert spec.hbm_bw[0] > spec.hbm_bw[-1]


def test_list_leaf_capacities_coerce_to_tuples():
    """A list (the natural Python literal) must behave exactly like the
    tuple form — not silently score as a scalar."""
    spec = MachineSpec(name="list-8", mesh_shape=(2, 4), axes=("a", "b"),
                       levels=(Level("top", 2, 10.0), Level("l", 4, 50.0)),
                       leaf_tflops=[2.0] * 4 + [1.0] * 4,
                       leaf_hbm_gbps=np.full(8, 100.0))
    assert isinstance(spec.leaf_tflops, tuple)
    assert spec.heterogeneous
    assert spec.peak_flops.shape == (8,)
    np.testing.assert_allclose(spec.bin_speed, [1.0] * 4 + [0.5] * 4)
    with pytest.raises(ValueError):          # wrong-length list rejected
        MachineSpec(name="bad", mesh_shape=(4,), axes=("data",),
                    levels=(Level("l", 4, 10.0),), leaf_tflops=[1.0, 2.0])


def test_hbm_only_asymmetry_is_heterogeneous_but_speed_free():
    """Mixed HBM with uniform compute: per-bin rooflines apply
    (heterogeneous=True) but comp(b)/speed(b) stays uniform."""
    spec = MachineSpec(name="hbm-8", mesh_shape=(8,), axes=("data",),
                       levels=(Level("l", 8, 50.0),),
                       leaf_tflops=100.0,
                       leaf_hbm_gbps=tuple([800.0] * 4 + [400.0] * 4))
    assert spec.heterogeneous
    assert spec.bin_speed is None
    assert spec.hbm_bw[0] == 2 * spec.hbm_bw[-1]


def test_cache_token_is_stable_and_content_addressed():
    a = MachineSpec.preset("tpu_v5e-512")
    assert a.cache_token() == a.cache_token()
    b = dataclasses.replace(a, leaf_tflops=123.0)
    assert a.cache_token() != b.cache_token()    # edits invalidate


# ---------------------------------------------------------------------------
# Capacity-normalized objective vs the loop-based oracle
# ---------------------------------------------------------------------------

def _rand_graph(seed=0, n=40, m=120):
    from repro.graph.generators import rmat, weighted_nodes
    return weighted_nodes(rmat(n, m, seed=seed), seed=seed)


def test_comp_loads_with_speeds_pins_against_reference():
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    topo = with_bin_speed(balanced_tree((2, 4), level_cost=(8.0, 1.0)),
                          rng.uniform(0.5, 2.0, 8))
    g = _rand_graph(seed=3)
    for seed in range(3):
        part = np.random.default_rng(seed).integers(0, topo.k, g.n_nodes)
        br = objective.makespan_tree(
            jnp.asarray(part, jnp.int32), jnp.asarray(g.senders),
            jnp.asarray(g.receivers), jnp.asarray(g.edge_weight),
            jnp.asarray(g.node_weight), jnp.asarray(topo.subtree),
            jnp.asarray(topo.F_l), k=topo.k,
            speed=jnp.asarray(topo.bin_speed))
        m_ref, comp_ref, comm_ref = reference.makespan_ref(part, g, topo)
        np.testing.assert_allclose(np.asarray(br.comp), comp_ref,
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(br.comm), comm_ref,
                                   rtol=1e-4, atol=1e-4)
        assert abs(float(br.makespan) - m_ref) <= 1e-3 * max(1.0, m_ref)
        # slow bins really weigh more: normalized load >= raw load
        raw = np.zeros(topo.k)
        np.add.at(raw, part, g.node_weight)
        assert (comp_ref >= raw - 1e-6).all()


def test_with_bin_speed_validates():
    topo = balanced_tree((2, 2))
    with pytest.raises(ValueError):
        with_bin_speed(topo, [1.0, 2.0])          # wrong length
    with pytest.raises(ValueError):
        with_bin_speed(topo, [1.0, 0.0, 1.0, 1.0])  # non-positive
    sp = with_bin_speed(topo, [2.0, 4.0, 4.0, 4.0])
    np.testing.assert_allclose(sp.bin_speed, [0.5, 1.0, 1.0, 1.0])


def test_partition_balances_by_capacity_on_heterogeneous_machine():
    """On a 2-pod machine whose second pod is 2x slower, the partitioner
    must put more weight on the fast pod, and verify() must accept the
    result under the capacity-normalized oracle."""
    from repro.core.partitioner import PartitionConfig, partition, verify
    from repro.graph.generators import grid2d
    g = grid2d(24, 24)
    topo = with_bin_speed(balanced_tree((2, 4), level_cost=(8.0, 1.0)),
                          [1.0] * 4 + [0.5] * 4)
    res = partition(g, topo, PartitionConfig(seed=0))
    verify(g, topo, res)
    raw = np.zeros(topo.k)
    np.add.at(raw, res.part, g.node_weight)
    fast, slow = raw[:4].sum(), raw[4:].sum()
    assert fast > slow                       # capacity-aware balance
    # the reported makespan really is the capacity-normalized objective
    m_ref, _, _ = reference.makespan_ref(res.part, g, topo)
    assert res.makespan == pytest.approx(m_ref, rel=1e-5)


def test_expert_placement_favours_fast_pod_on_heterogeneous_machine():
    """Expert placement on the two-pod mixed-generation preset puts at
    least the slow pod's FLOPs on the fast pod, and its capacity-normalized
    makespan is no worse than a speed-blind random scatter's."""
    from repro.core import baselines
    from repro.graph.graph import from_edges
    spec = MachineSpec.preset("tpu-mixed-32")
    topo = spec.tree()
    rng = np.random.default_rng(1)
    e = 32
    traffic = rng.uniform(0, 1, (e, e))
    traffic = traffic + traffic.T
    np.fill_diagonal(traffic, 0)
    flops = rng.uniform(0.5, 2.0, e)
    part, _ = mapping.expert_placement(traffic, flops, topo)
    iu = np.triu_indices(e, 1)
    g = from_edges(e, iu[0], iu[1],
                   (traffic[iu] + traffic.T[iu]).astype(np.float32),
                   flops.astype(np.float32))
    fast_bins = np.flatnonzero(np.asarray(topo.bin_speed)
                               == max(topo.bin_speed))
    fast = float(flops[np.isin(part, fast_bins)].sum())
    assert fast >= float(flops.sum()) - fast
    scatter = rng.permutation(e) % topo.k
    assert (baselines.score_all(g, topo, part)["makespan"]
            <= baselines.score_all(g, topo, scatter)["makespan"])


# ---------------------------------------------------------------------------
# Mapping search over machine specs
# ---------------------------------------------------------------------------

def _sym_traffic(d, seed=0):
    rng = np.random.default_rng(seed)
    T = rng.uniform(0, 1, (d, d))
    T = np.triu(T, 1)
    return T + T.T


@pytest.mark.parametrize("name", ["gpu-superpod", "torus-2d",
                                  "tpu-mixed-32", "tpu_v5e-512"])
def test_search_on_preset_never_loses_to_identity(name):
    spec = MachineSpec.preset(name)
    d = spec.n_devices
    T = _sym_traffic(d, seed=1)
    topo = spec.topology()
    best = mapping.search(spec.mesh_shape, None, T, machine=spec,
                          n_random=4)
    ident = mapping.makespan_of_device_map(T, topo, np.arange(d))
    assert best.bottleneck <= ident + 1e-9
    got = mapping.makespan_of_device_map(T, topo, best.device_to_bin)
    np.testing.assert_allclose(got, best.bottleneck, rtol=1e-4)
    # capacity-normalized makespan (comp floor included) inherits <=
    cap_s = mapping.capacity_makespan(T, topo, best.device_to_bin,
                                      shard_work=1.0)
    cap_i = mapping.capacity_makespan(T, topo, np.arange(d),
                                      shard_work=1.0)
    assert cap_s <= cap_i + 1e-9


def test_search_requires_some_topology():
    with pytest.raises(ValueError):
        mapping.search((4,), None, np.zeros((4, 4)))


def test_routing_scorer_matches_single_map_breakdown():
    """Batched routing scorer == per-candidate oracle scoring."""
    from repro.core.topology import torus2d_topology
    topo = torus2d_topology(3, 3)
    d = topo.k
    T = _sym_traffic(d, seed=2)
    rng = np.random.default_rng(2)
    cands = np.stack([np.arange(d)] + [rng.permutation(d)
                                       for _ in range(4)])
    batched = mapping.score_device_maps(T, topo, cands)
    for c, got in zip(cands, batched):
        # oracle: relabel the traffic into bin space, push through R
        W = np.zeros_like(T)
        W[np.ix_(c, c)] = T
        loads = 0.5 * np.einsum("ij,ijl->l", W, topo.path_incidence)
        want = float((topo.F_l * loads).max())
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_capacity_makespan_floor():
    spec = MachineSpec.preset("tpu-mixed-32")
    topo = spec.tree()
    d = spec.n_devices
    T = np.zeros((d, d))
    # no traffic: the makespan IS the slowest bin's shard time
    got = mapping.capacity_makespan(T, topo, np.arange(d), shard_work=2.0)
    assert got == pytest.approx(2.0 / float(topo.bin_speed.min()))
    uni = MachineSpec.preset("tpu_v5e-256")
    assert mapping.capacity_makespan(
        np.zeros((256, 256)), uni.tree(), np.arange(256),
        shard_work=2.0) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# Launch layer: deprecation shims + machine-aware session
# ---------------------------------------------------------------------------

def test_production_mesh_spec_shim_warns_and_matches_preset():
    from repro.launch import mesh as mesh_lib
    for multi_pod, name in ((True, "tpu_v5e-512"), (False, "tpu_v5e-256")):
        with pytest.warns(DeprecationWarning):
            got = mesh_lib.production_mesh_spec(multi_pod)
        assert got == MachineSpec.preset(name).mesh_spec()


def test_make_production_mesh_shim_warns_and_delegates(monkeypatch):
    """The shim must build exactly the tpu_v5e preset's mesh: capture the
    delegated make_mapped_mesh call (512 devices don't exist under test)."""
    from repro.launch import mesh as mesh_lib
    calls = []

    def fake(shape, axes, order=None, devices=None):
        calls.append((tuple(shape), tuple(axes), order))
        return "mesh"

    monkeypatch.setattr(mesh_lib, "make_mapped_mesh", fake)
    with pytest.warns(DeprecationWarning):
        assert mesh_lib.make_production_mesh(multi_pod=True) == "mesh"
    assert calls == [(*MachineSpec.preset("tpu_v5e-512").mesh_spec(),
                      None)]


def test_historical_constants_rederive_from_the_preset():
    from repro.launch import mesh as mesh_lib
    assert mesh_lib.PEAK_FLOPS == 197e12
    assert mesh_lib.HBM_BW == 819e9
    assert mesh_lib.ICI_BW == 50e9
    assert mesh_lib.CHIPS_SINGLE_POD == 256
    assert mesh_lib.CHIPS_MULTI_POD == 512
    assert mesh_lib.serving_mesh_spec(512) == \
        MachineSpec.preset("tpu_v5e-512").mesh_spec()
    # non-production counts (even preset-sized ones) stay a local mesh
    assert mesh_lib.serving_mesh_spec(64) == ((64,), ("data",))


def test_session_cache_key_includes_machine():
    from repro.launch.placement import PlacementSession
    s = PlacementSession(cache_dir="", map_restarts=2)
    base = dict(arch="a", shape="s", mesh_shape=(8, 8),
                axes=("data", "model"), profile="2d", grad_compress=False,
                overrides=None, device_order=None)
    k_none = s._key(*base.values())
    k_gpu = s._key(*base.values(),
                   machine=MachineSpec.preset("gpu-superpod"))
    k_torus = s._key(*base.values(),
                     machine=MachineSpec.preset("torus-2d"))
    assert len({k_none, k_gpu, k_torus}) == 3
    assert k_gpu == s._key(*base.values(),
                           machine=MachineSpec.preset("gpu-superpod"))


def test_place_with_machine_preset_and_routing_side_metrics():
    """The stubbed fixed-point loop runs under a named machine: tree
    preset searches its F_l tree; the torus preset goes through the
    routing scorer and reports dcn_bytes = 0 (no tree depth)."""
    from test_placement import _StubSession
    d = 64
    T = mapping.collective_traffic_matrix((8, 8), {0: 1e3, 1: 1.0})
    for name, dcn_free in (("gpu-superpod", False), ("torus-2d", True)):
        s = _StubSession(lambda order: T)
        res = s.place("synthetic", "cell", machine=name, recompile=True)
        rep = res.report
        assert rep.mesh == "8x8"
        assert sorted(rep.device_order) == list(range(d))
        assert rep.searched["makespan"] <= rep.identity["makespan"] + 1e-9
        if dcn_free:
            assert rep.identity["dcn_bytes"] == 0.0
        else:
            assert rep.identity["dcn_bytes"] > 0.0


def test_place_rejects_mismatched_machine_and_mesh():
    from test_placement import _StubSession
    s = _StubSession(lambda order: np.zeros((4, 4)))
    with pytest.raises(ValueError):
        s.place("synthetic", "cell", mesh_shape=(2, 2),
                axes=("data", "model"), machine="gpu-superpod")
