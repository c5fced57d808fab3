"""Serving correctness core (ISSUE 7): paged-vs-dense decode equivalence
(the load-bearing test), page-table round trips, allocator free-list
accounting, placement invariance, map_pages, and engine determinism."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.dist.sharding import lm_rules
from repro.launch.placement import PlacementSession
from repro.models import transformer as tr
from repro.serving import (EngineConfig, PagedKVCache, PagePoolExhausted,
                           ServingEngine)
from repro.serving.kv_cache import PageAllocator
from repro.serving.paged_decode import paged_decode_step

RULES = lm_rules(())


def _model(name="qwen2-1.5b"):
    arch = configs.get(name)
    cfg = arch.smoke_config()
    params, _ = tr.init(jax.random.PRNGKey(0), cfg, RULES)
    return cfg, params


def _pools(cfg, n_pages, page_size):
    shape = (cfg.n_layers, n_pages + 1, page_size, cfg.n_kv_heads,
             cfg.head_dim)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


# ---------------------------------------------------------------------------
# Allocator + page-table bookkeeping
# ---------------------------------------------------------------------------

def test_allocator_free_list_accounting():
    al = PageAllocator(8)
    a = al.alloc(3)
    b = al.alloc(2)
    assert al.n_free == 3
    assert len(set(a) | set(b)) == 5                 # disjoint
    al.free(a)
    assert al.n_free == 6
    c = al.alloc(3)
    assert set(c) == set(a)                          # LIFO reuse
    al.free(b)
    with pytest.raises(ValueError, match="double free"):
        al.free(b)
    with pytest.raises(PagePoolExhausted):
        al.alloc(al.n_free + 1)
    # a failed alloc must not leak pages
    before = al.n_free
    with pytest.raises(PagePoolExhausted):
        al.alloc(before + 1)
    assert al.n_free == before


def test_page_table_round_trip():
    cache = PagedKVCache(n_pages=12, page_size=4, n_slots=3,
                         max_pages_per_req=4)
    pages = cache.assign_slot(0, 10)                 # 3 pages
    assert len(pages) == 3
    row = cache.page_table[0]
    assert list(row[:3]) == pages and row[3] == cache.sentinel
    with pytest.raises(ValueError, match="already holds"):
        cache.assign_slot(0, 4)
    cache.assign_slot(1, 16)                         # 4 pages
    cache.check_invariants()
    freed = cache.release_slot(0)
    assert set(freed) == set(pages)
    assert (cache.page_table[0] == cache.sentinel).all()
    # alloc after free reuses the same physical pages
    again = cache.assign_slot(2, 10)
    assert set(again) == set(pages)
    cache.check_invariants()
    with pytest.raises(KeyError):
        cache.release_slot(0)                        # not held
    # capacity guards
    with pytest.raises(ValueError, match="max_pages_per_req"):
        cache.assign_slot(0, 100)
    assert not cache.can_admit(100)


def test_apply_placement_rewrites_all_bookkeeping():
    rng = np.random.default_rng(0)
    cache = PagedKVCache(n_pages=10, page_size=2, n_slots=2,
                         max_pages_per_req=5)
    cache.assign_slot(0, 6)
    cache.assign_slot(1, 4)
    cache.record_access({0: 6, 1: 4})
    before = cache.live_page_sets()
    asg = rng.integers(0, 3, 10)
    perm = cache.apply_placement(asg)
    cache.check_invariants()
    # device-contiguous: new labels sorted by device
    new_dev = np.empty(10, dtype=np.int64)
    new_dev[perm] = asg
    assert (np.diff(new_dev) >= 0).all()
    for slot, pages in before.items():
        assert cache.live_page_sets()[slot] == [int(perm[p])
                                                for p in pages]
    # traffic/access stats follow the relabeling (5 live pages, 1 step)
    assert cache.access_count.sum() == 5.0


# ---------------------------------------------------------------------------
# Paged-vs-dense decode equivalence (the load-bearing test)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["qwen2-1.5b", "chatglm3-6b"])
def test_paged_equals_dense_decode(name):
    """Same tokens through the paged path (fragmented physical pages) and
    the dense decode_step: logits allclose at every position."""
    cfg, params = _model(name)
    B, T, page = 2, 10, 4
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, cfg.vocab)
    cache, _ = tr.init_cache(cfg, B, T, RULES)
    dense = jax.jit(lambda p, c, t, pos: tr.decode_step(p, c, t, pos, cfg,
                                                        RULES))
    n_pages = 16
    kp, vp = _pools(cfg, n_pages, page)
    pt = np.full((B, 3), n_pages, np.int32)
    pt[0] = [7, 2, 11]                               # deliberately
    pt[1] = [0, 9, 3]                                # fragmented
    paged = jax.jit(lambda p, k, v, t2, ln, t: paged_decode_step(
        p, k, v, t2, ln, t, cfg, RULES))
    c = cache
    for t in range(T - 1):
        lg_d, c = dense(params, c, toks[:, t:t + 1], jnp.int32(t))
        lg_p, kp, vp = paged(params, kp, vp, jnp.asarray(pt),
                             jnp.full((B,), t, jnp.int32),
                             toks[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_d),
                                   rtol=1e-5, atol=1e-5)


def test_paged_mixed_lengths_match_per_request_dense():
    """Continuous-batching regime: slots join at staggered steps, so the
    batch mixes positions; every slot's logits must match its own
    single-request dense decode."""
    cfg, params = _model()
    B, T, page, n_pages = 3, 8, 2, 24
    starts = [0, 2, 5]
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab, (B, T)).astype(np.int32)
    dense = jax.jit(lambda p, c, t, pos: tr.decode_step(p, c, t, pos, cfg,
                                                        RULES))
    caches = [tr.init_cache(cfg, 1, T, RULES)[0] for _ in range(B)]
    kp, vp = _pools(cfg, n_pages, page)
    paged = jax.jit(lambda p, k, v, t2, ln, t: paged_decode_step(
        p, k, v, t2, ln, t, cfg, RULES))
    max_pages = T // page
    pt = np.full((B, max_pages), n_pages, np.int32)
    cache = PagedKVCache(n_pages, page, B, max_pages)
    pos = [0] * B
    for step in range(max(starts) + T):
        active = [b for b in range(B) if step >= starts[b] and pos[b] < T]
        if not active:
            break
        tokens = np.zeros((B, 1), np.int32)
        lengths = np.zeros((B,), np.int32)
        for b in active:
            if pos[b] == 0:
                pages = cache.assign_slot(b, T)
                pt[b, :len(pages)] = pages
            tokens[b, 0] = toks[b, pos[b]]
            lengths[b] = pos[b]
        lg_p, kp, vp = paged(params, kp, vp, jnp.asarray(pt),
                             jnp.asarray(lengths), jnp.asarray(tokens))
        for b in active:
            lg_d, caches[b] = dense(params, caches[b],
                                    jnp.asarray(toks[b:b + 1,
                                                     pos[b]:pos[b] + 1]),
                                    jnp.int32(pos[b]))
            np.testing.assert_allclose(np.asarray(lg_p[b]),
                                       np.asarray(lg_d[0]),
                                       rtol=1e-5, atol=1e-5)
            pos[b] += 1


def test_placement_permutation_preserves_logits():
    """apply_placement physically reorders the pool mid-stream; decode
    must not notice."""
    cfg, params = _model()
    B, T, page, n_pages = 2, 8, 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, T), 0, cfg.vocab)
    paged = jax.jit(lambda p, k, v, t2, ln, t: paged_decode_step(
        p, k, v, t2, ln, t, cfg, RULES))

    def run(with_placement):
        cache = PagedKVCache(n_pages, page, B, T // page, cfg=cfg)
        cache.assign_slot(0, T)
        cache.assign_slot(1, T)
        out = []
        for t in range(T - 1):
            lg, cache.k_pool, cache.v_pool = paged(
                params, cache.k_pool, cache.v_pool,
                jnp.asarray(cache.page_table),
                jnp.full((B,), t, jnp.int32), toks[:, t:t + 1])
            out.append(np.asarray(lg))
            if with_placement and t == 3:
                rng = np.random.default_rng(7)
                cache.apply_placement(rng.integers(0, 4, n_pages))
                cache.check_invariants()
        return out

    for a, b in zip(run(False), run(True)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_mla_cache_pages_latent():
    """MLA's page shape comes from the model: one latent pool of
    ``c_kv ‖ k_rope`` per token, which placement permutes and a device
    death zeroes, as the K and V pools are."""
    cfg = configs.get("deepseek-v2-lite-16b").smoke_config()
    cache = PagedKVCache(8, 4, 2, 4, cfg=cfg)
    assert cache.k_pool is None and cache.v_pool is None
    n_layers, width = cfg.n_layers, 128       # 16 + 8 padded to 128 lanes
    shape = (n_layers * 9, 4, width)
    assert cache.latent_pool.shape == shape
    assert cache.pools == (cache.latent_pool,)
    # [l * 9 + p] holds page p of layer l: mark each with p + 1
    marks = np.tile(np.arange(1, 10), n_layers)[:, None, None]
    cache.latent_pool = jnp.broadcast_to(jnp.asarray(marks, cfg.dtype),
                                         shape)
    perm = cache.apply_placement(np.asarray([1, 0, 1, 0, 1, 0, 1, 0]))
    by_page = np.asarray(cache.latent_pool).reshape(n_layers, 9, 4, width)
    assert (by_page[:, perm, 0, 0] == np.arange(1, 9)).all()
    assert (by_page[:, 8] == 9).all()
    cache.fail_pages([int(perm[3])])
    by_page = np.asarray(cache.latent_pool).reshape(n_layers, 9, 4, width)
    assert not by_page[:, perm[3]].any() and by_page[:, perm[2]].all()


# ---------------------------------------------------------------------------
# map_pages (pages-as-rows placement entry)
# ---------------------------------------------------------------------------

def test_map_pages_groups_coaccessed_pages():
    """Two co-access cliques on 4 devices: the searched placement must
    beat round-robin scatter on makespan, and requests' cliques must not
    be cut more than scatter cuts them."""
    n = 16
    traffic = np.zeros((n, n))
    for lo in (0, 8):
        idx = np.arange(lo, lo + 8)
        traffic[np.ix_(idx, idx)] = 10.0
    np.fill_diagonal(traffic, 0.0)
    session = PlacementSession(cache_dir="")
    pl = session.map_pages(traffic, n_devices=4)
    assert pl.page_to_device.shape == (n,)
    assert pl.n_devices == 4
    from repro.core import baselines
    from repro.core.topology import guess_tree
    from repro.graph.graph import from_edges
    iu = np.triu_indices(n, 1)
    nz = traffic[iu] > 0
    g = from_edges(n, iu[0][nz], iu[1][nz],
                   traffic[iu][nz].astype(np.float32))
    topo = guess_tree(4)
    scatter = np.arange(n) % 4
    ours = baselines.score_all(g, topo, pl.page_to_device)["makespan"]
    theirs = baselines.score_all(g, topo, scatter)["makespan"]
    assert ours <= theirs
    # drift pricing: the scatter as `current` must read as drifted
    pl2 = session.map_pages(traffic, n_devices=4, current=scatter)
    assert pl2.drift_ratio >= 1.0


def test_map_pages_lints_malformed_traffic():
    bad = np.zeros((4, 4))
    bad[0, 1] = 1.0                                  # asymmetric
    with pytest.raises(ValueError, match="page-traffic"):
        PlacementSession(cache_dir="").map_pages(bad, n_devices=2)
    with pytest.raises(ValueError, match="machine or n_devices"):
        PlacementSession(cache_dir="").map_pages(np.zeros((4, 4)))


def test_map_pages_empty_epoch_gives_balanced_blocks():
    pl = PlacementSession(cache_dir="").map_pages(np.zeros((8, 8)),
                                                  n_devices=4)
    assert (np.bincount(pl.page_to_device, minlength=4) == 2).all()
    assert pl.makespan == 0.0


# ---------------------------------------------------------------------------
# Engine: determinism, completion, metrics
# ---------------------------------------------------------------------------

def _workload(cfg, n=6, seed=11):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab, int(rng.integers(2, 7)),
                          dtype=np.int64).astype(np.int32),
             int(rng.integers(1, 5))) for _ in range(n)]


def _run_engine(cfg, params, workload, **kw):
    defaults = dict(n_slots=2, page_size=4, n_pages=16,
                    max_pages_per_req=4, temperature=0.8, seed=0,
                    replace_every=0)
    defaults.update(kw)
    eng = ServingEngine(params, cfg, RULES, EngineConfig(**defaults))
    for prompt, gen in workload:
        eng.submit(prompt, gen)
    return eng.run()


def test_engine_deterministic_across_concurrency():
    """Sampling keys are (rid, pos) functions: the generated tokens are
    identical at different slot counts / batch compositions — the --seed
    bugfix, strengthened."""
    cfg, params = _model()
    work = _workload(cfg)
    r2 = _run_engine(cfg, params, work, n_slots=2)
    r4 = _run_engine(cfg, params, work, n_slots=4, n_pages=32)
    gen2 = {r["rid"]: r["generated"] for r in r2.requests}
    gen4 = {r["rid"]: r["generated"] for r in r4.requests}
    assert gen2 == gen4
    assert r4.steps <= r2.steps                      # more slots, no slower


def test_engine_completes_all_and_reports():
    cfg, params = _model()
    work = _workload(cfg, n=5, seed=3)
    rep = _run_engine(cfg, params, work, replace_every=6, place_devices=4)
    assert rep.n_requests == len(work)
    assert rep.tokens_out == sum(g for _, g in work)
    for r in rep.requests:
        # one token per step after admission: TTFT is exactly the prompt
        assert r["first_token_step"] - r["admit_step"] == (
            r["prompt_len"] - 1)
        assert len(r["generated"]) == r["max_new_tokens"]
    assert rep.placements, "re-placement policy never ran"
    assert rep.latency_steps_p99 >= rep.latency_steps_p50 > 0
    # a request holds its slot for prompt + gen - 1 steps; 2 slots
    slot_steps = sum(len(p) + g - 1 for p, g in work)
    assert rep.mean_batch_occupancy == round(slot_steps / rep.steps / 2, 4)
    import json
    json.loads(rep.to_json())                        # trace round-trips


def test_engine_greedy_and_static_batching():
    """Greedy decoding is scheduling-invariant: 2 and 4 slots sample the
    same tokens, and more slots never take more steps."""
    cfg, params = _model()
    work = _workload(cfg, n=4, seed=9)
    r2 = _run_engine(cfg, params, work, temperature=0.0, n_slots=2)
    r4 = _run_engine(cfg, params, work, temperature=0.0, n_slots=4,
                     n_pages=32)
    assert ({r["rid"]: r["generated"] for r in r2.requests}
            == {r["rid"]: r["generated"] for r in r4.requests})
    assert r4.steps <= r2.steps


def test_engine_page_replacement_keeps_sampled_tokens():
    """Page re-placement is transparent: a stream re-placed every 8 steps
    over 4 bins samples the same tokens as the stream with placement off."""
    cfg, params = _model()
    work = _workload(cfg, n=12, seed=5)
    plain = _run_engine(cfg, params, work, n_slots=4, n_pages=32)
    placed = _run_engine(cfg, params, work, n_slots=4, n_pages=32,
                         replace_every=8, place_devices=4)
    assert any(p["replaced"] and p["pages_moved"]
               for p in placed.placements), "no page was ever moved"
    assert ({r["rid"]: r["generated"] for r in placed.requests}
            == {r["rid"]: r["generated"] for r in plain.requests})


def test_engine_infeasible_request_rejected_at_submit():
    cfg, params = _model()
    eng = ServingEngine(params, cfg, RULES,
                        EngineConfig(n_slots=1, page_size=2, n_pages=4,
                                     max_pages_per_req=4))
    with pytest.raises(ValueError, match="max_pages_per_req|never"):
        eng.submit(np.zeros(16, np.int32), 8)


def test_moe_config_paged_decode():
    """MoE layers (no MLA) go through the paged path: build a tiny moe
    GQA config and pin paged == dense."""
    base = configs.get("qwen2-1.5b").smoke_config()
    cfg = dataclasses.replace(base, moe=True, n_experts=4, n_shared=1,
                              top_k=2, d_ff_expert=32, n_dense_layers=1,
                              capacity_factor=64.0)
    params, _ = tr.init(jax.random.PRNGKey(0), cfg, RULES)
    B, T, page = 2, 6, 2
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, T), 0, cfg.vocab)
    cache, _ = tr.init_cache(cfg, B, T, RULES)
    kp, vp = _pools(cfg, 8, page)
    pt = np.asarray([[0, 1, 2], [5, 4, 3]], np.int32)
    dense = jax.jit(lambda p, c, t, pos: tr.decode_step(p, c, t, pos, cfg,
                                                        RULES))
    paged = jax.jit(lambda p, k, v, t2, ln, t: paged_decode_step(
        p, k, v, t2, ln, t, cfg, RULES))
    c = cache
    for t in range(T - 1):
        lg_d, c = dense(params, c, toks[:, t:t + 1], jnp.int32(t))
        lg_p, kp, vp = paged(params, kp, vp, jnp.asarray(pt),
                             jnp.full((B,), t, jnp.int32),
                             toks[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_d),
                                   rtol=2e-4, atol=2e-4)
