"""repro.embed: sharded tables, hot-row cache, sparse updates, prefetch.

The pins the subsystem's docstrings promise: shard permutation is exact
(lookups through the permuted table bitwise-match the original), sparse /
masked / dense row updates are bitwise-identical, cache evictions never
lose a pending update (replicated() equals the dense oracle bit for
bit), hit rate is monotone in cache size, the prefetcher is
deterministic and genuinely overlaps, and the measured sharded + cached
traffic on ``tpu-mixed-32`` is strictly below the replicated baseline.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import embed
from repro.embed import (EmbedConfig, HotRowCache, PrefetchIterator,
                         RowAccessStats, ShardedEmbeddingTable,
                         dense_row_update, init_dense_opt,
                         init_embed_state, make_embed_train_step,
                         masked_row_update, plan_shards,
                         replicated_update_traffic, requester_of,
                         sparse_row_update)
from repro.kernels import ops as kops
from repro.kernels import ref as kref

MACHINE = "tpu-mixed-32"


def _zipf_stream(v, batch, hist, n_batches, seed=0, a=1.1):
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, v + 1, dtype=np.float64)
    probs = ranks ** (-a)
    probs /= probs.sum()
    out = []
    for _ in range(n_batches):
        ids = rng.choice(v, size=(batch, hist), p=probs)
        drop = rng.random(ids.shape) < 0.2
        out.append(np.where(drop, -1, ids).astype(np.int32))
    return out


def _stats_and_plan(v=300, machine=MACHINE, n_devices=None, seed=0):
    stats = RowAccessStats(v)
    for ids in _zipf_stream(v, 16, 8, 6, seed=seed):
        stats.record(ids)
    plan = plan_shards(stats, machine=machine, n_devices=n_devices)
    return stats, plan


# -- shard plans ----------------------------------------------------------

def test_shard_plan_invariants_and_coverage():
    stats, plan = _stats_and_plan()
    plan.check()
    # every row on exactly one device (no row in two shards)
    assert np.array_equal(np.sort(plan.order), np.arange(plan.n_rows))
    assert np.array_equal(
        np.bincount(plan.row_to_device, minlength=plan.n_devices),
        plan.shard_sizes)
    assert int(plan.shard_sizes.sum()) == plan.n_rows
    # the permuted table holds each device's rows contiguously
    assert np.array_equal(np.sort(plan.row_to_device),
                          plan.row_to_device[plan.order])


def test_shard_plan_capacity_proportional_on_hetero_machine():
    """Rows per leaf track the leaf's capacity share (the memory budget
    the ``_repair_capacity`` pass enforces): every leaf lands within the
    default 20% slack of its proportional row count, and the fast pod's
    leaves hold more rows than the slow pod's."""
    from repro.core import machine as machine_lib
    _, plan = _stats_and_plan(v=600)
    topo = machine_lib.resolve(MACHINE).tree()
    speed = np.asarray(topo.bin_speed, dtype=np.float64)
    targets = 600 * speed / speed.sum()
    sizes = plan.shard_sizes.astype(np.float64)
    assert (sizes >= np.maximum(np.floor(targets * 0.8), 1.0)).all(), \
        (sizes, targets)
    assert (sizes <= np.maximum(np.ceil(targets * 1.2), 1.0)).all(), \
        (sizes, targets)
    fast = speed > speed.mean()
    assert sizes[fast].mean() > sizes[~fast].mean()


def test_plan_shards_degenerate_no_edges():
    stats = RowAccessStats(40)
    stats.record(np.arange(40))        # point lookups: no co-access edges
    plan = plan_shards(stats, n_devices=4)
    plan.check()
    assert (plan.shard_sizes > 0).all()


def test_identity_plan_roundtrip():
    plan = embed.identity_plan(17, n_devices=3)
    plan.check()
    assert np.array_equal(plan.perm, np.arange(17))


# -- sharded table lookups ------------------------------------------------

def test_sharded_lookup_equals_original_table():
    _, plan = _stats_and_plan()
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(0, 1, (plan.n_rows, 16))
                        .astype(np.float32))
    st = ShardedEmbeddingTable(table, plan)
    ids = rng.integers(0, plan.n_rows, 50)
    assert np.array_equal(np.asarray(st.lookup(ids)),
                          np.asarray(table[ids]))
    assert np.array_equal(np.asarray(st.replicated()), np.asarray(table))


def test_placement_permutation_preserves_bag_lookups():
    """lookup_bags through the permuted table bitwise-matches
    embedding_bag on the original table (same einsum, translated ids)."""
    _, plan = _stats_and_plan()
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.normal(0, 1, (plan.n_rows, 32))
                        .astype(np.float32))
    st = ShardedEmbeddingTable(table, plan)
    ids = rng.integers(-1, plan.n_rows, (8, 6)).astype(np.int32)
    valid = ids >= 0
    w = jnp.asarray((valid / np.maximum(valid.sum(-1, keepdims=True), 1))
                    .astype(np.float32))
    got = st.lookup_bags(jnp.asarray(ids), w)
    want = kops.embedding_bag(table, jnp.maximum(jnp.asarray(ids), 0), w)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_gather_combine_interpret_matches_ref():
    rng = np.random.default_rng(3)
    for dtype in (jnp.float32, jnp.bfloat16):
        table = jnp.asarray(rng.normal(0, 1, (128, 96))).astype(dtype)
        idx = jnp.asarray(rng.integers(0, 128, (4, 5)).astype(np.int32))
        w = jnp.asarray(rng.random((4, 5)).astype(np.float32))
        got = kops.gather_combine(table, idx, w, interpret=True)
        want = kref.gather_combine_ref(table, idx,
                                       w.astype(table.dtype))
        tol = (dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16
               else dict(rtol=1e-6))
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **tol)


def test_gather_combine_chunked_bag_axis_matches_ref(monkeypatch):
    """Batches over the scalar-prefetch bound run as several bag chunks
    (the last one padded); the result is the unchunked reference."""
    from repro.kernels import gather_combine as gc
    monkeypatch.setattr(gc, "PREFETCH_SLOTS", 8)
    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.normal(0, 1, (64, 40)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 64, (7, 3)).astype(np.int32))
    w = jnp.asarray(rng.random((7, 3)).astype(np.float32))
    got = kops.gather_combine(table, idx, w, interpret=True)
    want = kref.gather_combine_ref(table, idx, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)


def test_embedding_bag_backend_dispatch_parity():
    """The kernel path _bag_lookup now dispatches to must match the XLA
    fallback it used to pin (interpret vs ref)."""
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.normal(0, 1, (64, 48)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 64, (6, 7)).astype(np.int32))
    w = jnp.asarray(rng.random((6, 7)).astype(np.float32))
    got = kops.embedding_bag(table, idx, w, interpret=True)
    want = kref.embedding_bag_ref(table, idx, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)
    xla = kops.embedding_bag(table, idx, w, pallas=False)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(want),
                               rtol=1e-6)


def test_row_pad_derives_from_device_count():
    from repro.models.recsys import _row_pad
    n_dev = max(len(jax.devices()), 1)
    for n in (1, 7, 1000, 4097):
        p = _row_pad(n)
        assert p >= n
        assert p % 8 == 0
        assert p % n_dev == 0
        assert p - n < 8 * n_dev      # no 512-row over-padding


def test_recsys_row_perm_is_transparent():
    """user/item embeddings through a permuted table + row_perm equal the
    unpermuted model's bitwise."""
    from repro import configs
    from repro.launch.steps import rules_for
    from repro.models import recsys as mdl
    arch = configs.get("two-tower-retrieval")
    cfg = arch.smoke_config()
    rules = rules_for("recsys", ("data",))
    params, _ = mdl.init(jax.random.PRNGKey(0), cfg, rules)
    v = params["item_table"].shape[0]
    stats = RowAccessStats(v)
    stream = _zipf_stream(min(v, 200), 8, cfg.hist_len, 4)
    for ids in stream:
        stats.record(ids)
    plan = plan_shards(stats, machine=MACHINE)
    permuted = dict(params)
    permuted["item_table"] = jnp.take(params["item_table"],
                                      jnp.asarray(plan.order), axis=0)
    row_perm = jnp.asarray(plan.perm)
    rng = np.random.default_rng(5)
    batch = {"user_hist": jnp.asarray(stream[0]),
             "user_dense": jnp.asarray(
                 rng.normal(0, 1, (8, cfg.d_dense)).astype(np.float32)),
             "item_id": jnp.asarray(
                 rng.integers(0, min(v, 200), 8).astype(np.int32))}
    batch["item_cat"] = jnp.asarray(
        rng.integers(0, cfg.n_cats, 8).astype(np.int32))
    u0 = mdl.user_embed(params, batch, cfg, rules)
    u1 = mdl.user_embed(permuted, batch, cfg, rules, row_perm)
    assert np.array_equal(np.asarray(u0), np.asarray(u1))
    v0 = mdl.item_embed(params, batch, cfg, rules)
    v1 = mdl.item_embed(permuted, batch, cfg, rules, row_perm)
    assert np.array_equal(np.asarray(v0), np.asarray(v1))


# -- sparse updates -------------------------------------------------------

def test_sparse_masked_dense_bitwise_identical():
    rng = np.random.default_rng(6)
    v, e = 80, 12
    table = jnp.asarray(rng.normal(0, 1, (v, e)).astype(np.float32))
    accum = jnp.asarray(rng.random(v).astype(np.float32))
    rows = np.unique(rng.integers(0, v, 20))
    gd = np.zeros((v, e), np.float32)
    gd[rows] = rng.normal(0, 1, (rows.shape[0], e))
    t_d, a_d = dense_row_update(table, accum, jnp.asarray(gd))
    t_m, a_m = masked_row_update(table, accum, jnp.asarray(gd))
    t_s, a_s = sparse_row_update(table, accum, jnp.asarray(rows),
                                 jnp.asarray(gd[rows]))
    for t, a in ((t_m, a_m), (t_s, a_s)):
        assert np.array_equal(np.asarray(t_d), np.asarray(t))
        assert np.array_equal(np.asarray(a_d), np.asarray(a))
    # untouched rows bitwise unchanged
    mask = np.ones(v, bool)
    mask[rows] = False
    assert np.array_equal(np.asarray(t_d)[mask], np.asarray(table)[mask])
    assert np.array_equal(np.asarray(a_d)[mask], np.asarray(accum)[mask])


def test_embed_train_step_sparse_matches_dense_bitwise():
    rng = np.random.default_rng(7)
    params = {
        "item_table": jnp.asarray(rng.normal(0, 0.1, (40, 8))
                                  .astype(np.float32)),
        "cat_table": jnp.asarray(rng.normal(0, 0.1, (10, 8))
                                 .astype(np.float32)),
        "w": jnp.asarray(rng.normal(0, 0.1, (8, 4)).astype(np.float32)),
    }
    batch = {"ids": jnp.asarray(rng.integers(0, 40, (4, 3))),
             "cats": jnp.asarray(rng.integers(0, 10, 4)),
             "y": jnp.asarray(rng.normal(0, 1, (4, 4))
                              .astype(np.float32))}

    def loss_fn(p, b):
        x = p["item_table"][b["ids"]].mean(1) + p["cat_table"][b["cats"]]
        err = x @ p["w"] - b["y"]
        return jnp.mean(err * err), {}

    from repro.optim import adamw
    ocfg = adamw.AdamWConfig(lr=1e-3, total_steps=4, warmup_steps=0)
    outs = []
    for sparse in (True, False):
        ecfg = EmbedConfig(tables=("item_table", "cat_table"),
                           sparse=sparse)
        opt = init_dense_opt(params, ecfg, ocfg)
        estate = init_embed_state(params, ecfg)
        step = jax.jit(make_embed_train_step(loss_fn, ocfg, ecfg))
        p = dict(params)
        for _ in range(3):
            p, opt, estate, metrics = step(p, opt, estate, batch)
        outs.append((p, estate, metrics))
    (p1, s1, m1), (p2, s2, m2) = outs
    for k in p1:
        assert np.array_equal(np.asarray(p1[k]), np.asarray(p2[k])), k
    for k in s1:
        assert np.array_equal(np.asarray(s1[k]), np.asarray(s2[k])), k
    assert float(m1["loss"]) == float(m2["loss"])
    # dense AdamW state excludes the tables
    assert set(s1) == {"item_table", "cat_table"}


# -- hot-row cache --------------------------------------------------------

def _drive_cache(cache, stream, accum, ref_tbl, ref_acc, seed=8):
    """Lookups + updates through the cache next to the dense oracle."""
    rng = np.random.default_rng(seed)
    v, e = ref_tbl.shape
    for ids in stream:
        flat = ids[ids >= 0]
        vals = cache.lookup(flat)
        assert np.array_equal(np.asarray(vals),
                              np.asarray(ref_tbl)[flat])
        rows = np.unique(flat)
        g = rng.normal(0, 1, (rows.shape[0], e)).astype(np.float32)
        accum = cache.apply_grads(rows, g, accum)
        gd = jnp.zeros((v, e), jnp.float32).at[jnp.asarray(rows)].set(
            jnp.asarray(g))
        ref_tbl, ref_acc = dense_row_update(ref_tbl, ref_acc, gd)
        cache.check_invariants()
    return accum, ref_tbl, ref_acc


def test_cache_eviction_never_loses_pending_update():
    """A 4-slot LRU under a churning stream: after flush, the table and
    accumulator bitwise-match the dense oracle."""
    _, plan = _stats_and_plan(v=60, machine=None, n_devices=4)
    rng = np.random.default_rng(9)
    table = jnp.asarray(rng.normal(0, 1, (60, 8)).astype(np.float32))
    st = ShardedEmbeddingTable(table, plan)
    cache = HotRowCache(st, n_cache=4, policy="lru")
    stream = _zipf_stream(60, 6, 5, 8, seed=10)
    accum, ref_tbl, ref_acc = _drive_cache(
        cache, stream, jnp.zeros(60, jnp.float32), table,
        jnp.zeros(60, jnp.float32))
    assert cache.evictions > 0, "stream never churned the cache"
    rep = cache.replicated()
    assert not cache.pending
    assert np.array_equal(np.asarray(rep), np.asarray(ref_tbl))
    assert np.array_equal(np.asarray(accum), np.asarray(ref_acc))


def test_cache_invariants_manual_sweep():
    """Seeded sweep standing in for the Hypothesis property when
    hypothesis is unavailable: many op sequences, invariants after every
    step, dense-oracle equality at the end."""
    for seed in range(5):
        rng = np.random.default_rng(100 + seed)
        v = int(rng.integers(20, 80))
        n_cache = int(rng.integers(0, 12))
        _, plan = _stats_and_plan(v=v, machine=None,
                                  n_devices=int(rng.integers(1, 6)),
                                  seed=seed)
        table = jnp.asarray(rng.normal(0, 1, (v, 4)).astype(np.float32))
        st = ShardedEmbeddingTable(table, plan)
        cache = HotRowCache(st, n_cache=n_cache, policy="lru")
        stream = _zipf_stream(v, 4, 4, 6, seed=200 + seed)
        accum, ref_tbl, ref_acc = _drive_cache(
            cache, stream, jnp.zeros(v, jnp.float32), table,
            jnp.zeros(v, jnp.float32), seed=300 + seed)
        assert cache.hits + cache.misses == cache.lookups
        assert np.array_equal(np.asarray(cache.replicated()),
                              np.asarray(ref_tbl))
        assert np.array_equal(np.asarray(accum), np.asarray(ref_acc))
        cache.check_invariants()


def test_hit_rate_monotone_in_cache_size():
    stats, plan = _stats_and_plan(v=200)
    rng = np.random.default_rng(11)
    table = jnp.asarray(rng.normal(0, 1, (200, 8)).astype(np.float32))
    stream = _zipf_stream(200, 16, 8, 6, seed=12)
    rates = {}
    for policy in ("static", "lru"):
        rates[policy] = []
        for n_cache in (0, 8, 32, 128):
            st = ShardedEmbeddingTable(table, plan)
            cache = HotRowCache(st, n_cache=n_cache, policy=policy)
            cache.warm(stats.top_rows(n_cache))
            for ids in stream:
                cache.lookup(ids[ids >= 0])
            rates[policy].append(cache.hit_rate)
        assert rates[policy] == sorted(rates[policy]), (policy,
                                                        rates[policy])
    assert rates["lru"][-1] > 0.3       # the Zipf head actually caches


def test_cache_traffic_is_lawful():
    from repro.analysis import shard_lint
    _, plan = _stats_and_plan(v=100, machine=None, n_devices=4)
    rng = np.random.default_rng(13)
    table = jnp.asarray(rng.normal(0, 1, (100, 8)).astype(np.float32))
    cache = HotRowCache(ShardedEmbeddingTable(table, plan), n_cache=8)
    for ids in _zipf_stream(100, 8, 6, 4, seed=14):
        cache.lookup(ids[ids >= 0])
    assert not shard_lint.lint_traffic(cache.traffic,
                                       subject="test:cache")
    assert cache.traffic_bytes() > 0


def test_traffic_sharded_cached_below_replicated_on_tpu_mixed_32():
    """The subsystem's end-to-end claim on the heterogeneous preset."""
    stats, plan = _stats_and_plan(v=400)
    assert plan.machine == MACHINE and plan.n_devices == 32
    rng = np.random.default_rng(15)
    table = jnp.asarray(rng.normal(0, 1, (400, 16)).astype(np.float32))
    st = ShardedEmbeddingTable(table, plan)
    cache = HotRowCache(st, n_cache=64, policy="lru")
    cache.warm(stats.top_rows(64))
    accum = jnp.zeros(400, jnp.float32)
    rep = np.zeros((32, 32))
    for ids in _zipf_stream(400, 16, 8, 6, seed=16):
        flat = ids[ids >= 0]
        req_row = requester_of(ids.shape[0], 32)
        req = np.broadcast_to(req_row[:, None], ids.shape)[ids >= 0]
        cache.lookup(flat, req)
        rows, first = np.unique(flat, return_index=True)
        g = rng.normal(0, 1, (rows.shape[0], 16)).astype(np.float32)
        accum = cache.apply_grads(rows, g, accum, req[first])
        rep += replicated_update_traffic(flat, req, 32, st.row_bytes)
    cache.flush()
    assert cache.traffic_bytes() < rep.sum() / 2
    cache.check_invariants()


# -- prefetch -------------------------------------------------------------

def test_prefetch_deterministic_and_overlaps():
    def gen():
        rng = np.random.default_rng(17)
        for _ in range(12):
            yield rng.integers(0, 100, 8)

    plain = list(gen())
    pf = PrefetchIterator(gen(), depth=2)
    got = []
    for x in pf:
        time.sleep(0.01)                    # slow consumer -> overlap
        got.append(x)
    assert len(got) == len(plain)
    assert all(np.array_equal(a, b) for a, b in zip(plain, got))
    s = pf.stats()
    assert s["max_occupancy"] >= 1, s       # producer ran ahead
    assert s["produced"] == s["consumed"] == 12
    pf.close()
    pf.close()                              # idempotent


def test_prefetch_propagates_producer_exception():
    def bad():
        yield 1
        raise RuntimeError("boom")

    pf = PrefetchIterator(bad(), depth=2)
    assert next(pf) == 1
    with pytest.raises(RuntimeError, match="boom"):
        while True:
            next(pf)
    pf.close()


def test_prefetch_close_stops_producer_thread():
    def slow():
        i = 0
        while True:
            yield i
            i += 1

    pf = PrefetchIterator(slow(), depth=2)
    next(pf)
    pf.close()
    assert not pf._thread.is_alive()
    assert threading.active_count() < 50    # no thread leak across tests


def test_loop_threads_embed_state_and_closes_prefetcher(tmp_path):
    from repro.optim import adamw
    from repro.train import loop as train_loop
    rng = np.random.default_rng(18)
    params = {"item_table": jnp.asarray(rng.normal(0, 0.1, (30, 4))
                                        .astype(np.float32)),
              "w": jnp.asarray(rng.normal(0, 0.1, (4, 2))
                               .astype(np.float32))}

    def loss_fn(p, b):
        err = p["item_table"][b["ids"]].mean(1) @ p["w"] - b["y"]
        return jnp.mean(err * err), {}

    def batches_gen():
        r = np.random.default_rng(19)
        while True:
            yield {"ids": jnp.asarray(r.integers(0, 30, (4, 3))),
                   "y": jnp.asarray(r.normal(0, 1, (4, 2))
                                    .astype(np.float32))}

    ocfg = adamw.AdamWConfig(lr=1e-2, total_steps=6, warmup_steps=0)
    ecfg = EmbedConfig(tables=("item_table",))
    opt = init_dense_opt(params, ecfg, ocfg)
    step = jax.jit(make_embed_train_step(loss_fn, ocfg, ecfg))
    pf = PrefetchIterator(batches_gen(), depth=2)
    lcfg = train_loop.LoopConfig(total_steps=6, ckpt_every=3,
                                 ckpt_dir=str(tmp_path),
                                 embed_sparse=ecfg)
    params, opt, res = train_loop.run(step, params, opt, pf, lcfg)
    assert res.steps_run == 6
    assert not pf._thread.is_alive()        # loop's finally closed it
    # resume restores the embed accumulator next to params/opt
    pf2 = PrefetchIterator(batches_gen(), depth=2)
    lcfg2 = train_loop.LoopConfig(total_steps=8, ckpt_every=4,
                                  ckpt_dir=str(tmp_path),
                                  embed_sparse=ecfg)
    params, opt, res2 = train_loop.run(step, params, opt, pf2, lcfg2)
    assert res2.resumed_from == 6
    assert res2.steps_run == 2


def test_loop_rejects_grad_compress_plus_embed():
    from repro.train import loop as train_loop
    lcfg = train_loop.LoopConfig(grad_compress=True,
                                 embed_sparse=EmbedConfig())
    with pytest.raises(ValueError, match="mutually exclusive"):
        train_loop.run(lambda *a: a, {}, {}, iter(()), lcfg)


# -- sample_fanout uniformity (the modulo-bias fix) ----------------------

def test_sample_fanout_uniform_over_neighbors():
    """Chi-square-ish: with the exact per-row bound every neighbor of the
    hub is sampled with equal probability."""
    from repro.data.pipeline import sample_fanout
    from repro.graph.graph import from_edges
    n, hub_deg = 12, 11
    u = np.zeros(hub_deg, np.int64)
    v = np.arange(1, hub_deg + 1)
    g = from_edges(n, u, v, np.ones(hub_deg, np.float32),
                   np.ones(n, np.float32))
    rng = np.random.default_rng(20)
    counts = np.zeros(n)
    trials, f = 400, 4
    for _ in range(trials):
        sub = sample_fanout(g, np.asarray([0]), (f,), rng)
        sampled = sub.nodes[sub.nodes != 0]
        # count arc draws, not unique nodes: recover per-draw frequencies
        # from the edge list (seeds first, hub is node 0)
        nbrs = sub.nodes[sub.receivers[:len(sub.receivers) // 2]]
        counts_i = np.bincount(nbrs[nbrs != 0], minlength=n)
        counts += counts_i
        assert sampled.min() >= 1
    observed = counts[1:hub_deg + 1]
    expected = observed.sum() / hub_deg
    chi2 = float(((observed - expected) ** 2 / expected).sum())
    # 10 dof, p=0.001 critical value ~29.6; a modulo-biased sampler over
    # a non-power-of-two degree drifts far beyond this at 1600 draws
    assert chi2 < 29.6, (chi2, observed)
