"""Unit tests for the repro.dist subsystem: rule-table resolution
semantics, spec sanitation, concrete shardings, int8 compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.dist import compress
from repro.dist.sharding import (Rules, gnn_rules, lm_rules, recsys_rules,
                                 sanitize_spec, sanitize_tree,
                                 tree_shardings)

MULTI = ("pod", "data", "model")


# ---------------------------------------------------------------------------
# Rules lookup precedence
# ---------------------------------------------------------------------------

def test_rules_none_never_consults_table():
    r = lm_rules(MULTI)
    assert tuple(r.spec(None, None)) == (None, None)
    assert tuple(r.spec()) == ()


def test_rules_filter_to_mesh_axes():
    # multi-pod rule degrades on a single-pod mesh, vanishes on no mesh
    assert tuple(lm_rules(MULTI).spec("batch")) == (("pod", "data"),)
    assert tuple(lm_rules(("data", "model")).spec("batch")) == ("data",)
    assert all(a is None for a in lm_rules(()).spec("batch", "model"))


def test_rules_first_claim_wins():
    """Within one spec a mesh axis is claimed once; later logical axes
    that map to it resolve to None (GSPMD forbids duplicates)."""
    r = lm_rules(("data", "model"))
    assert tuple(r.spec("model", "vocab")) == ("model", None)
    assert tuple(r.spec("vocab", "model")) == ("model", None)
    # ...but separate spec() calls don't share claims
    assert tuple(r.spec("vocab")) == ("model",)


def test_rules_unknown_name_raises():
    with pytest.raises(KeyError):
        lm_rules(MULTI).spec("not_an_axis")


def test_family_tables():
    assert gnn_rules(MULTI).table["rows"] == MULTI
    assert recsys_rules(MULTI).table["cand"] == MULTI
    assert lm_rules(MULTI, profile="fsdp").table["fsdp"] == ("data", "model")
    assert lm_rules(MULTI, profile="fsdp").table["model"] == ()
    assert lm_rules(MULTI, profile="sp").table["seq"] == ("model",)
    with pytest.raises(ValueError):
        lm_rules(MULTI, profile="3d")


def test_shard_is_noop_without_mesh():
    r = lm_rules(("data", "model"))
    x = jnp.ones((4, 4))
    assert r.shard(x, "batch", "model") is x


# ---------------------------------------------------------------------------
# sanitize_spec / sanitize_tree
# ---------------------------------------------------------------------------

def _mesh1():
    # the rules constrain under an Auto-typed mesh, as launch/mesh.py builds
    return jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def _amesh(**sizes):
    """AbstractMesh carries axis sizes without needing physical devices."""
    return jax.sharding.AbstractMesh(tuple(sizes.values()), tuple(sizes))


def test_sanitize_drops_non_dividing_axis():
    amesh = _amesh(data=4, model=2)
    s = sanitize_spec((7, 8), P("data", "model"), amesh)
    assert tuple(s) == (None, "model")               # 7 % 4 != 0, 8 % 2 == 0
    # tuple entry degrades to its dividing prefix, not all-or-nothing
    s = sanitize_spec((4, 8), P(("data", "model"), None), amesh)
    assert tuple(s) == ("data", None)                # 4 % 8 != 0, 4 % 4 == 0
    # axes the mesh lacks are removed outright — with a warning, since a
    # nonexistent axis is almost always a sharding-table typo
    with pytest.warns(UserWarning, match="pod"):
        s = sanitize_spec((8, 8), P("pod", "model"), amesh)
    assert tuple(s) == (None, "model")


def test_sanitize_strict_raises_on_missing_axis():
    amesh = _amesh(data=4, model=2)
    with pytest.raises(ValueError, match="pod"):
        sanitize_spec((8, 8), P("pod", "model"), amesh, strict=True)
    with pytest.raises(ValueError, match="pod"):
        sanitize_tree((jax.ShapeDtypeStruct((8, 8), jnp.float32),),
                      (P("pod", None),), amesh, strict=True)
    # present axes never trigger strict, dividing or not
    s = sanitize_spec((7, 8), P("data", "model"), amesh, strict=True)
    assert tuple(s) == (None, "model")


def test_sanitize_pads_short_specs():
    amesh = _amesh(data=2)
    s = sanitize_spec((4, 3, 5), P("data"), amesh)
    assert tuple(s) == ("data", None, None)


def test_sanitize_tree_maps_leaves():
    amesh = _amesh(data=4)
    tree = {"a": jax.ShapeDtypeStruct((8, 3), jnp.float32),
            "b": jax.ShapeDtypeStruct((7,), jnp.float32),
            "c": jax.ShapeDtypeStruct((2,), jnp.float32)}
    specs = {"a": P("data", None), "b": P("data"), "c": None}
    out = sanitize_tree(tree, specs, amesh)
    assert tuple(out["a"]) == ("data", None)
    assert tuple(out["b"]) == (None,)
    assert out["c"] is None          # None = replicated, as tree_shardings


def test_tree_shardings_roundtrip_on_1_device_mesh():
    mesh = _mesh1()
    specs = {"w": P("data", None), "b": None}
    sh = tree_shardings(mesh, specs)
    assert all(isinstance(s, NamedSharding) for s in jax.tree.leaves(sh))
    x = {"w": jnp.arange(8.0).reshape(4, 2), "b": jnp.ones(3)}
    placed = jax.tree.map(jax.device_put, x, sh)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                         np.asarray(b)),
                 placed, x)
    assert placed["w"].sharding.spec == P("data", None)


def test_shard_applies_constraint_under_mesh():
    """Under an active mesh the constraint path runs (a 1-device mesh
    normalizes output specs, so assert behaviour, not layout)."""
    mesh = _mesh1()
    r = gnn_rules(("data",))
    x = jnp.arange(16.0).reshape(8, 2)
    with mesh:
        y = jax.jit(lambda v: r.shard(v, "rows", None))(x)
        # non-dividing rows dim degrades to a no-op instead of erroring
        z = jax.jit(lambda v: r.shard(v, "rows", None))(jnp.ones((7, 2)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(x))
    assert isinstance(y.sharding, NamedSharding)
    assert z.shape == (7, 2)


# ---------------------------------------------------------------------------
# compress
# ---------------------------------------------------------------------------

def test_roundtrip_error_within_quantization_step():
    rng = np.random.default_rng(0)
    g = {"a": jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32)),
         "b": jnp.asarray(rng.normal(size=(64,)).astype(np.float32) * 5)}
    dec, state = compress.roundtrip(g)
    for k in g:
        bound = float(jnp.abs(g[k]).max()) / compress.LEVELS
        assert float(jnp.abs(dec[k] - g[k]).max()) <= bound + 1e-6


def test_state_is_exact_residual():
    rng = np.random.default_rng(1)
    g = {"a": jnp.asarray(rng.normal(size=(128,)).astype(np.float32))}
    dec, state = compress.roundtrip(g)
    np.testing.assert_allclose(np.asarray(dec["a"] + state["a"]),
                               np.asarray(g["a"]), rtol=0, atol=1e-6)
    # second step folds the residual in: emitted + residual == cumulative
    dec2, state2 = compress.roundtrip(g, state)
    np.testing.assert_allclose(
        np.asarray(dec["a"] + dec2["a"] + state2["a"]),
        np.asarray(2.0 * g["a"]), rtol=0, atol=1e-5)


def test_int_leaves_pass_through_untouched():
    g = {"w": jnp.ones((4,), jnp.float32),
         "count": jnp.arange(3, dtype=jnp.int32)}
    dec, state = compress.roundtrip(g)
    assert dec["count"].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(dec["count"]),
                                  np.asarray(g["count"]))
    assert state["count"].dtype == jnp.int32
    assert not np.asarray(state["count"]).any()


def test_roundtrip_zero_grads_no_nan():
    dec, state = compress.roundtrip({"a": jnp.zeros((16,))})
    assert not np.isnan(np.asarray(dec["a"])).any()
    assert not np.asarray(state["a"]).any()


def test_roundtrip_tuple_structured_grads():
    """Tuple containers in the gradient pytree must not be mistaken for
    internal (deq, residual) pairs (regression: the unzip once used
    is_leaf=isinstance-tuple)."""
    g = ({"a": jnp.ones((4,)) * 3.0}, jnp.ones((2,)) * 7.0)
    dec, state = compress.roundtrip(g)
    assert jax.tree.structure(dec) == jax.tree.structure(g)
    np.testing.assert_allclose(np.asarray(dec[0]["a"]), 3.0, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(dec[1]), 7.0, rtol=1e-2)
    assert jax.tree.structure(state) == jax.tree.structure(g)


def test_roundtrip_jittable_and_bf16():
    g = {"a": jnp.ones((8, 8), jnp.bfloat16) * 0.5}
    dec, state = jax.jit(compress.roundtrip)(g)
    assert dec["a"].dtype == jnp.bfloat16
    assert state["a"].dtype == jnp.float32
    assert float(jnp.abs(dec["a"].astype(jnp.float32) - 0.5).max()) < 0.01


def test_bf16_cast_error_is_fed_back():
    """The residual must measure the ACTUALLY emitted (post-bf16-cast)
    value, else the cast error accumulates as uncorrected bias."""
    rng = np.random.default_rng(7)
    g = {"a": jnp.asarray(rng.normal(size=(512,)), jnp.bfloat16)}
    true = np.asarray(g["a"], np.float32)
    acc = np.zeros_like(true)
    res = None
    for _ in range(50):
        dec, res = compress.roundtrip(g, res)
        acc += np.asarray(dec["a"], np.float32)
    bias = np.abs(acc + np.asarray(res["a"]) - 50 * true).max()
    assert bias < 1e-2, bias


# ---------------------------------------------------------------------------
# compress: per-block scales
# ---------------------------------------------------------------------------

def test_block_roundtrip_beats_flat_on_long_tailed_grads():
    """One huge entry under a flat scale wipes out the small entries'
    mantissa; per-block scales keep every other block at full int8
    resolution."""
    rng = np.random.default_rng(0)
    g = rng.normal(0, 1e-3, (4096,)).astype(np.float32)
    g[7] = 50.0                                  # the long tail
    tree = {"w": jnp.asarray(g)}
    _, res_flat = compress.roundtrip(tree)
    _, res_blk = compress.roundtrip(tree, block=256)
    err_flat = float(jnp.abs(res_flat["w"]).mean())
    err_blk = float(jnp.abs(res_blk["w"]).mean())
    assert err_blk < err_flat / 5.0, (err_blk, err_flat)


def test_block_none_is_the_legacy_flat_path():
    rng = np.random.default_rng(1)
    tree = {"w": jnp.asarray(rng.normal(size=(33, 7)).astype(np.float32))}
    dec_a, res_a = compress.roundtrip(tree)
    dec_b, res_b = compress.roundtrip(tree, block=None)
    np.testing.assert_array_equal(np.asarray(dec_a["w"]),
                                  np.asarray(dec_b["w"]))
    np.testing.assert_array_equal(np.asarray(res_a["w"]),
                                  np.asarray(res_b["w"]))


def test_block_residual_is_exact_and_shapes_survive_padding():
    """Non-multiple sizes are padded internally; the emitted leaf keeps
    the original shape and emitted + residual == input exactly."""
    rng = np.random.default_rng(2)
    tree = {"w": jnp.asarray(rng.normal(size=(37, 11)).astype(np.float32))}
    dec, res = compress.roundtrip(tree, block=64)
    assert dec["w"].shape == (37, 11)
    np.testing.assert_allclose(np.asarray(dec["w"] + res["w"]),
                               np.asarray(tree["w"]), rtol=0, atol=1e-6)


def test_block_validation_and_small_leaves():
    with pytest.raises(ValueError):
        compress.roundtrip({"w": jnp.ones((8,))}, block=100)
    with pytest.raises(ValueError):
        compress.roundtrip({"w": jnp.ones((8,))}, block=0)
    # leaves smaller than one block degrade to the flat path
    tree = {"w": jnp.ones((8,), jnp.float32) * 3.0}
    dec_b, _ = compress.roundtrip(tree, block=256)
    dec_f, _ = compress.roundtrip(tree)
    np.testing.assert_array_equal(np.asarray(dec_b["w"]),
                                  np.asarray(dec_f["w"]))


def test_block_roundtrip_jittable():
    import functools
    g = {"a": jnp.ones((300,), jnp.bfloat16) * 0.5}
    dec, state = jax.jit(functools.partial(compress.roundtrip,
                                           block=128))(g)
    assert dec["a"].dtype == jnp.bfloat16
    assert state["a"].dtype == jnp.float32


def test_make_train_step_threads_block_size():
    """grad_compress=<int> bakes the per-block scale size into the step;
    the signature matches grad_compress=True and the block actually
    changes the emitted gradients on long-tailed input."""
    from repro.optim import adamw
    from repro.train.steps import make_train_step

    rng = np.random.default_rng(3)
    w = rng.normal(0, 1e-3, (256, 2)).astype(np.float32)

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    params = {"w": jnp.asarray(w)}
    batch = {"x": jnp.asarray(rng.normal(0, 1, (8, 256)).astype(np.float32)
                              * np.concatenate([[100.0],
                                                np.ones(255)])[None, :]),
             "y": jnp.zeros((8, 2), jnp.float32)}
    ocfg = adamw.AdamWConfig(lr=1e-2, total_steps=4, warmup_steps=0)
    opt = adamw.init(params, ocfg)
    cstate = compress.init_state(params)
    step_flat = jax.jit(make_train_step(loss_fn, ocfg, grad_compress=True))
    step_blk = jax.jit(make_train_step(loss_fn, ocfg, grad_compress=64))
    pf, _, cf, _ = step_flat(params, opt, cstate, batch)
    pb, _, cb, _ = step_blk(params, opt, cstate, batch)
    assert pf["w"].shape == pb["w"].shape
    assert not np.allclose(np.asarray(cf["w"]), np.asarray(cb["w"]))
