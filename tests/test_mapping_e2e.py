"""End-to-end partitioner -> launch mapping: traffic attribution from HLO
replica groups, the mesh-mapping search against machine trees, mapped mesh
construction, the expert sharding profile, and the compress-residual train
loop (DESIGN.md §2/§6)."""
import numpy as np
import pytest

from repro.core import mapping
from repro.core.topology import (balanced_tree, flat_topology, guess_tree,
                                 mesh_tree, production_tree)
from repro.launch import collectives
from repro.launch import mesh as mesh_lib


# ---------------------------------------------------------------------------
# Traffic matrices from replica groups
# ---------------------------------------------------------------------------

def test_group_traffic_matches_axis_model():
    """Iota groups along one mesh axis must reproduce the per-axis ring
    model of collective_traffic_matrix bit-for-bit."""
    shape = (2, 4, 4)
    axis_bytes = {0: 7e3, 1: 5e2, 2: 11.0}
    T_axis = mapping.collective_traffic_matrix(shape, axis_bytes)
    d = int(np.prod(shape))
    T_groups = np.zeros((d, d))
    ids = np.arange(d).reshape(shape)
    for ax, nbytes in axis_bytes.items():
        groups = np.moveaxis(ids, ax, -1).reshape(-1, shape[ax])
        collectives.add_group_traffic(T_groups, groups, nbytes)
    np.testing.assert_allclose(T_axis, T_groups)


def test_materialize_groups_formats():
    iota = collectives.materialize_groups(
        "replica_groups=[4,4]<=[4,4]T(1,0)", 16)
    assert iota.shape == (4, 4)
    # T(1,0) on a [4,4] iota: groups stride over the leading dim
    np.testing.assert_array_equal(iota[0], [0, 4, 8, 12])
    listed = collectives.materialize_groups(
        "replica_groups={{0,1,2},{3,4,5}}", 6)
    np.testing.assert_array_equal(listed, [[0, 1, 2], [3, 4, 5]])
    pairs = collectives.materialize_groups(
        "source_target_pairs={{0,1},{2,3}}", 4)
    np.testing.assert_array_equal(pairs, [[0, 1], [2, 3]])
    assert collectives.materialize_groups("no groups here", 4) is None


def test_parse_collectives_async_start_done_counted_once():
    """Async pairs (all-gather-start / -done) are one collective: the
    -start line carries groups and the destination buffer (trailing tuple
    half), the -done line must not double count."""
    hlo = "\n".join([
        "ENTRY %main (p.0: f32[8]) -> f32[16] {",
        "  %p.0 = f32[8] parameter(0)",
        "  %ag = (f32[8], f32[16]) all-gather-start(f32[8] %p.0), "
        "replica_groups={{0,1}}, dimensions={0}",
        "  ROOT %out = f32[16] all-gather-done((f32[8], f32[16]) %ag)",
        "}",
    ])
    out = collectives.parse_collectives(hlo, 2, [], traffic=True)
    assert out["count"] == 1
    # destination buffer only: 16 f32 = 64 bytes; all-gather link model
    np.testing.assert_allclose(out["link"]["all-gather"], 64 * (2 - 1) / 2)
    assert out["traffic"].sum() > 0
    np.testing.assert_allclose(out["traffic"], out["traffic"].T)


def test_parse_collectives_traffic_from_real_hlo():
    """Traffic extraction on a real compiled module: a psum over 4 devices
    must produce a symmetric matrix whose total matches the per-op
    link_bf16 sum times the device count."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a real collective")
    jax.make_mesh((len(jax.devices()),), ("data",))  # pragma: no cover
    # (multi-device CI only; single-device runs take the skip above)


# ---------------------------------------------------------------------------
# Mapping search vs identity
# ---------------------------------------------------------------------------

def _asymmetric_two_level_tree():
    # 2 super-nodes x 8 leaves, expensive upper links: crossing the top
    # level is 8x a leaf link — the paper's DCN/ICI asymmetry in miniature
    return balanced_tree((2, 8), level_cost=(8.0, 1.0))


def test_searched_makespan_never_worse_than_identity():
    topo = _asymmetric_two_level_tree()
    rng = np.random.default_rng(0)
    for trial in range(3):
        # random symmetric traffic over a (4, 4) logical mesh
        T = rng.uniform(0, 1, (16, 16))
        T = np.triu(T, 1)
        T = T + T.T
        best = mapping.search_mesh_mapping((4, 4), {}, topo, traffic=T)
        identity = mapping.makespan_of_device_map(T, topo, np.arange(16))
        assert best.bottleneck <= identity + 1e-9


def test_search_moves_heavy_axis_off_the_expensive_links():
    """Heavy traffic on logical axis 1 (size 8): the searched mapping must
    keep those rings inside one super-node, beating identity for the
    transposed-identity layout where axis-1 neighbors straddle the top."""
    topo = _asymmetric_two_level_tree()
    # mesh (8, 2): axis 0 light, axis 1 heavy -> identity places axis-0
    # (stride-2) neighbors adjacently... build both orientations and check
    # the search always lands at the orientation-independent optimum.
    T_heavy_inner = mapping.collective_traffic_matrix((2, 8),
                                                      {0: 1.0, 1: 1e3})
    T_heavy_outer = mapping.collective_traffic_matrix((8, 2),
                                                      {0: 1e3, 1: 1.0})
    best_inner = mapping.search_mesh_mapping((2, 8), {}, topo,
                                             traffic=T_heavy_inner)
    best_outer = mapping.search_mesh_mapping((8, 2), {}, topo,
                                             traffic=T_heavy_outer)
    id_outer = mapping.makespan_of_device_map(T_heavy_outer, topo,
                                              np.arange(16))
    # identity for (8, 2) strides the heavy axis across super-nodes;
    # the search must do strictly better there
    assert best_outer.bottleneck < id_outer - 1e-9
    # and both orientations reach the same optimum
    np.testing.assert_allclose(best_inner.bottleneck,
                               best_outer.bottleneck, rtol=1e-6)


def test_link_loads_and_dcn_accounting():
    topo = production_tree(2, 2, 2)          # 8 leaves
    T = mapping.collective_traffic_matrix((2, 4), {0: 100.0})
    loads = mapping.link_loads_of_device_map(T, topo, np.arange(8))
    assert loads.shape[0] == topo.n_links
    depths = np.asarray([topo.depth(int(c)) for c in topo.link_nodes])
    # axis 0 of (2, 4) pairs device i with i+4 -> all of it crosses pods
    assert loads[depths == 1].sum() > 0
    br_max = float((np.asarray(topo.F_l) * loads).max())
    np.testing.assert_allclose(
        br_max, mapping.makespan_of_device_map(T, topo, np.arange(8)),
        rtol=1e-6)


def test_mesh_tree_shapes():
    assert mesh_tree((2, 16, 16)).k == 512
    assert mesh_tree((16, 16)).k == 256
    assert mesh_tree((8,)).k == 8
    with pytest.raises(ValueError):
        mesh_tree((2, 2, 2, 2))


def test_guess_tree():
    assert guess_tree(12).k == 12              # 3 x 4 split
    assert isinstance(guess_tree(7), type(flat_topology(7)))
    assert guess_tree(7).k == 7
    assert guess_tree(1).k == 1


# ---------------------------------------------------------------------------
# Batched candidate enumeration + widened search
# ---------------------------------------------------------------------------

def _naive_candidates(shape):
    """The historical per-candidate reshape/transpose/take construction the
    vectorized mixed-radix enumeration must reproduce row-for-row."""
    import itertools
    d = int(np.prod(shape))
    out = []
    for perm in itertools.permutations(range(len(shape))):
        new_shape = tuple(shape[p] for p in perm)
        choices = [range(len(mapping._axis_orders(s))) for s in new_shape]
        for oi in itertools.product(*choices):
            maps = [mapping._axis_orders(s)[o]
                    for s, o in zip(new_shape, oi)]
            ids_p = np.transpose(np.arange(d).reshape(shape), perm)
            for ax, mp in enumerate(maps):
                ids_p = np.take(ids_p, mp, axis=ax)
            d2b = np.empty(d, dtype=np.int64)
            d2b[ids_p.ravel()] = np.arange(d)
            out.append(d2b)
    return np.stack(out)


def test_enumerate_candidates_matches_naive_construction():
    for shape in [(4,), (2, 8), (2, 3, 4)]:
        cands, meta = mapping.enumerate_candidates(shape)
        np.testing.assert_array_equal(cands, _naive_candidates(shape))
        assert len(meta) == cands.shape[0]
        d = int(np.prod(shape))
        np.testing.assert_array_equal(cands[0], np.arange(d))  # identity 1st
        assert meta[0] == (tuple(range(len(shape))), (0,) * len(shape))
        # every candidate is a permutation of the devices
        assert (np.sort(cands, axis=1) == np.arange(d)).all()


def test_enumerate_candidates_random_restarts():
    cands, meta = mapping.enumerate_candidates((2, 8), n_random=5, seed=3)
    base, _ = mapping.enumerate_candidates((2, 8))
    assert cands.shape[0] == base.shape[0] + 5
    np.testing.assert_array_equal(cands[:base.shape[0]], base)
    assert all(m == ((0, 1), (-1, -1)) for m in meta[base.shape[0]:])
    assert (np.sort(cands[base.shape[0]:], axis=1) == np.arange(16)).all()
    again, _ = mapping.enumerate_candidates((2, 8), n_random=5, seed=3)
    np.testing.assert_array_equal(cands, again)   # seeded -> reproducible


def test_axis_orders_keep_legacy_prefix():
    """Strict-superset guarantee: the PR 2 order set (identity/Gray/blocked)
    must stay as a prefix so old candidates keep their indices."""
    for size in (4, 8, 16):
        orders = mapping._axis_orders(size)
        np.testing.assert_array_equal(orders[0], np.arange(size))
        np.testing.assert_array_equal(orders[1], mapping._gray(size))
        assert len(orders) > 3                     # widened
        keys = {tuple(int(x) for x in o) for o in orders}
        assert len(keys) == len(orders)            # no duplicates
    assert len(mapping._axis_orders(2)) == 2       # identity + reversed


def test_widened_search_monotone_and_recursive_refinement():
    """Wider candidate spaces (random restarts, per-subtree recursion) can
    only lower the searched bottleneck, and identity stays candidate 0."""
    topo = _asymmetric_two_level_tree()
    rng = np.random.default_rng(7)
    T = rng.uniform(0, 1, (16, 16))
    T = np.triu(T, 1)
    T = T + T.T
    base = mapping.search_mesh_mapping((4, 4), {}, topo, traffic=T)
    wide = mapping.search_mesh_mapping((4, 4), {}, topo, traffic=T,
                                       n_random=24, recursive=True)
    ident = mapping.makespan_of_device_map(T, topo, np.arange(16))
    assert base.bottleneck <= ident + 1e-9
    assert wide.bottleneck <= base.bottleneck + 1e-9
    assert wide.n_candidates == base.n_candidates + 24
    # the returned assignment really scores at the reported bottleneck
    got = mapping.makespan_of_device_map(T, topo, wide.device_to_bin)
    np.testing.assert_allclose(got, wide.bottleneck, rtol=1e-4)


@pytest.mark.parametrize("shape, tree", [
    ((4, 4), lambda shape: _asymmetric_two_level_tree()),
    ((2, 16), mesh_tree),
    ((4, 4, 4), mesh_tree)], ids=["4x4", "2x16", "4x4x4"])
def test_score_device_maps_matches_looped_scorer(shape, tree):
    topo = tree(shape)
    T = mapping.collective_traffic_matrix(
        shape, dict(enumerate((100.0, 7.0, 0.5)[:len(shape)])))
    cands, _ = mapping.enumerate_candidates(shape, n_random=8, seed=0)
    batched = mapping.score_device_maps(T, topo, cands, chunk=16)
    looped = np.asarray([mapping.makespan_of_device_map(T, topo, c)
                         for c in cands])
    np.testing.assert_allclose(batched, looped, rtol=1e-4,
                               atol=1e-5 * float(looped.max()))


# ---------------------------------------------------------------------------
# Mapped mesh construction
# ---------------------------------------------------------------------------

def test_make_mapped_mesh_roundtrips_device_order():
    import jax
    n = len(jax.devices())
    order = np.arange(n)[::-1].copy()
    mesh = mesh_lib.make_mapped_mesh((n,), ("data",), order)
    np.testing.assert_array_equal(mesh_lib.device_order_of(mesh), order)
    # identity default
    mesh_id = mesh_lib.make_mapped_mesh((n,), ("data",))
    np.testing.assert_array_equal(mesh_lib.device_order_of(mesh_id),
                                  np.arange(n))


def test_make_mapped_mesh_validates():
    import jax
    n = len(jax.devices())
    with pytest.raises(ValueError):
        mesh_lib.make_mapped_mesh((n + 1,), ("data",))
    with pytest.raises(ValueError):
        mesh_lib.make_mapped_mesh((n,), ("data",),
                                  device_order=np.zeros(n, dtype=int) if n > 1
                                  else np.array([1]))


def test_production_mesh_spec_matches_mesh():
    # the shim warns (tests/test_machine.py pins that) but must keep
    # returning the historical specs
    with pytest.warns(DeprecationWarning):
        shape, axes = mesh_lib.production_mesh_spec(multi_pod=True)
    assert shape == (2, 16, 16) and axes == ("pod", "data", "model")
    with pytest.warns(DeprecationWarning):
        shape, axes = mesh_lib.production_mesh_spec(multi_pod=False)
    assert shape == (16, 16) and axes == ("data", "model")


# ---------------------------------------------------------------------------
# Expert sharding profile
# ---------------------------------------------------------------------------

def test_expert_profile_maps_expert_to_pod():
    from repro.dist.sharding import LM_PROFILES, lm_rules
    assert "expert" in LM_PROFILES
    r = lm_rules(("pod", "data", "model"), profile="expert")
    assert r.table["expert"] == ("pod",)
    assert r.table["model"] == ("model",)
    # single-pod fallback: expert rides the tensor axis like "2d"
    r1 = lm_rules(("data", "model"), profile="expert")
    assert r1.table["expert"] == ("model",)
    with pytest.raises(ValueError):
        lm_rules(("data",), profile="nope")


def test_archdef_profiles():
    from repro import configs
    lm = configs.get("deepseek-v2-lite-16b")
    assert set(lm.profiles) == {"2d", "fsdp", "sp", "expert"}
    assert configs.get("qwen2-1.5b").profiles == lm.profiles
    assert configs.get("pna").profiles == ("2d",)


# ---------------------------------------------------------------------------
# Compress residual threading through the loop
# ---------------------------------------------------------------------------

def _toy_problem():
    import jax
    import jax.numpy as jnp

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(0, 1, (4, 2)).astype(np.float32))}
    def batches():
        while True:
            x = rng.normal(0, 1, (8, 4)).astype(np.float32)
            yield {"x": jnp.asarray(x),
                   "y": jnp.asarray(x @ np.ones((4, 2), np.float32))}
    return loss_fn, params, batches()


def test_compress_step_signature_and_error_feedback():
    import jax
    from repro.optim import adamw
    from repro.dist import compress
    from repro.train.steps import make_train_step

    loss_fn, params, batches = _toy_problem()
    ocfg = adamw.AdamWConfig(lr=1e-2, total_steps=10, warmup_steps=0)
    opt = adamw.init(params, ocfg)
    step = jax.jit(make_train_step(loss_fn, ocfg, grad_compress=True))
    cstate = compress.init_state(params)
    batch = next(batches)
    p1, o1, c1, m1 = step(params, opt, cstate, batch)
    # the residual engages: quantization error of a real gradient is nonzero
    assert float(jax.numpy.abs(c1["w"]).max()) > 0
    # feeding the residual back changes the next emitted gradient path
    p2a, _, c2a, _ = step(p1, o1, c1, batch)
    p2b, _, _, _ = step(p1, o1, compress.init_state(params), batch)
    assert not np.allclose(np.asarray(p2a["w"]), np.asarray(p2b["w"]))


def test_loop_checkpoints_and_restores_compress_state(tmp_path):
    from repro.optim import adamw
    from repro.train import loop
    from repro.train.steps import make_train_step
    import jax

    loss_fn, params, batches = _toy_problem()
    ocfg = adamw.AdamWConfig(lr=1e-2, total_steps=8, warmup_steps=0)
    opt = adamw.init(params, ocfg)
    step = jax.jit(make_train_step(loss_fn, ocfg, grad_compress=True))
    cfg = loop.LoopConfig(total_steps=8, ckpt_every=4,
                          ckpt_dir=str(tmp_path), grad_compress=True,
                          fail_at_step=6)
    with pytest.raises(loop.InjectedFailure):
        loop.run(step, params, opt, batches, cfg)
    # the step-4 checkpoint carries params + opt + residual leaves
    from repro.ckpt import checkpoint as ckpt
    assert ckpt.latest_step(str(tmp_path)) == 4
    n_param_leaves = len(jax.tree.leaves(params))
    n_opt_leaves = len(jax.tree.leaves(opt))
    import json, os
    with open(os.path.join(str(tmp_path), "step_000000004",
                           "MANIFEST.json")) as f:
        manifest = json.load(f)
    assert manifest["n_leaves"] == 2 * n_param_leaves + n_opt_leaves
    # resume finishes the run from the checkpoint
    cfg2 = loop.LoopConfig(total_steps=8, ckpt_every=4,
                           ckpt_dir=str(tmp_path), grad_compress=True)
    _, _, result = loop.run(step, params, opt, batches, cfg2)
    assert result.resumed_from == 4
    assert result.steps_run == 4


def test_loop_resume_from_pre_compress_checkpoint(tmp_path):
    """Turning grad_compress on mid-experiment: resume from a checkpoint
    written without the residual restores (params, opt) and restarts
    error feedback from zeros instead of crashing on leaf count."""
    import jax
    from repro.optim import adamw
    from repro.train import loop
    from repro.train.steps import make_train_step

    loss_fn, params, batches = _toy_problem()
    ocfg = adamw.AdamWConfig(lr=1e-2, total_steps=6, warmup_steps=0)
    opt = adamw.init(params, ocfg)
    plain = jax.jit(make_train_step(loss_fn, ocfg))
    cfg = loop.LoopConfig(total_steps=4, ckpt_every=4,
                          ckpt_dir=str(tmp_path))
    loop.run(plain, params, opt, batches, cfg)
    comp = jax.jit(make_train_step(loss_fn, ocfg, grad_compress=True))
    cfg2 = loop.LoopConfig(total_steps=6, ckpt_every=4,
                           ckpt_dir=str(tmp_path), grad_compress=True)
    _, _, result = loop.run(comp, params, opt, batches, cfg2)
    assert result.resumed_from == 4
    assert result.steps_run == 2


def test_build_cell_grad_compress_inserts_state():
    from repro import configs
    from repro.dist.sharding import lm_rules
    from repro.launch.steps import build_cell

    arch = configs.get("qwen2-1.5b")
    rules = lm_rules((), profile="2d")
    shape = arch.shapes["train_4k"]
    import dataclasses
    tiny = dataclasses.replace(
        shape, meta={"batch": 2, "seq": 8})
    base = build_cell(arch, tiny, rules, grad_compress=False,
                      overrides={"n_layers": 1})
    comp = build_cell(arch, tiny, rules, grad_compress=True,
                      overrides={"n_layers": 1})
    assert len(comp["args_sds"]) == len(base["args_sds"]) + 1
    assert comp["donate"] == (0, 1, 2)
    import jax
    assert (jax.tree.structure(comp["args_sds"][2])
            == jax.tree.structure(comp["args_sds"][0]))
