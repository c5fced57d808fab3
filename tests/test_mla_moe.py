"""DeepSeek-V2 served through the paged engine, at smoke size on the CPU:
the paged latent (MLA) decode against the dense absorbed decode, the
engine's served logits against the plain float32 reference
(``models/reference_mla_moe.py``), the expert shares against the uncut
expert layer, dropless routing, the YaRN rope, and the routing counters.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.dist.sharding import lm_rules
from repro.models import common
from repro.models import reference_mla_moe as ref
from repro.models import transformer as tr
from repro.serving import EngineConfig, PagedKVCache, ServingEngine
from repro.serving.paged_decode import paged_decode_step_mla

RULES = lm_rules(())

# Float32 on both sides, the program absorbed and batched, the reference
# expanded per head at "highest" precision: they differ only by float32
# rounding, 5.8e-7 to 8.3e-7 of the largest logit (about 4) on seeds 3-5.
# The tolerance is 2e-5 of it: the latent cache stored in float16, the
# smallest perturbation tested below, errs by 4.9e-4 to 5.5e-4 on the same
# seeds, and in float8 by 0.094 to 0.17.
REF_TOL = 2e-5


def _cfg(name="deepseek-v2-lite-16b", **kw):
    return dataclasses.replace(configs.get(name).smoke_config(), **kw)


def _params(cfg, seed=0):
    return tr.init(jax.random.PRNGKey(seed), cfg, RULES)[0]


# ---------------------------------------------------------------------------
# Paged latent decode == dense absorbed decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["deepseek-v2-lite-16b", "deepseek-v2-236b"])
def test_paged_mla_equals_dense_decode(name):
    """Slots join at staggered steps (mixed positions in one batch), and
    each slot's pages are scattered over the pool: every slot's logits
    match its own single-request dense ``decode_step`` at every position,
    and the pool rows it did not own stay zero."""
    cfg = _cfg(name)
    params = _params(cfg)
    B, T, page, n_pages = 3, 9, 2, 20
    starts = [0, 3, 5]
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab, (B, T)).astype(np.int32)
    max_pages = -(-T // page)
    pt = np.full((B, max_pages), n_pages, np.int32)
    pt[:] = rng.permutation(n_pages)[:B * max_pages].reshape(B, max_pages)
    cache = PagedKVCache(n_pages, page, B, max_pages, cfg=cfg)
    pool = cache.latent_pool
    assert cache.pools == (pool,) and cache.k_pool is None
    dense = jax.jit(lambda p, c, t, pos: tr.decode_step(p, c, t, pos, cfg,
                                                        RULES))
    paged = jax.jit(lambda p, lp, t2, ln, t: paged_decode_step_mla(
        p, lp, t2, ln, t, cfg, RULES))
    caches = [tr.init_cache(cfg, 1, T, RULES)[0] for _ in range(B)]
    pos = [0] * B
    for step in range(max(starts) + T):
        active = [b for b in range(B) if step >= starts[b] and pos[b] < T]
        if not active:
            break
        table = np.full_like(pt, n_pages)
        tokens = np.zeros((B, 1), np.int32)
        lengths = np.zeros((B,), np.int32)
        for b in active:
            table[b] = pt[b]
            tokens[b, 0] = toks[b, pos[b]]
            lengths[b] = pos[b]
        lg_p, pool, load = paged(params, pool, jnp.asarray(table),
                                 jnp.asarray(lengths), jnp.asarray(tokens))
        assert int(load.sum()) == len(active) * cfg.top_k \
            * (cfg.n_layers - cfg.n_dense_layers)
        for b in active:
            lg_d, caches[b] = dense(params, caches[b],
                                    jnp.asarray(toks[b:b + 1,
                                                     pos[b]:pos[b] + 1]),
                                    jnp.int32(pos[b]))
            np.testing.assert_allclose(np.asarray(lg_p[b]),
                                       np.asarray(lg_d[0]),
                                       rtol=1e-5, atol=1e-5)
            pos[b] += 1
    unowned = np.setdiff1d(np.arange(n_pages), pt)
    by_page = np.asarray(pool).reshape(cfg.n_layers, n_pages + 1, -1)
    assert not by_page[:, unowned].any() and by_page[:, pt].any()


# ---------------------------------------------------------------------------
# The engine's served logits == the plain reference's full forward
# ---------------------------------------------------------------------------

def _serve_and_compare(cfg, params, pool_dtype=None):
    """Serve five requests greedily through ``ServingEngine`` (3 slots,
    so batches mix prompt and generation positions) and return the
    largest gap, over every slot of every step, between the program's
    logits and the reference's full-forward logits of the same request at
    the same position, over the reference's largest logit."""
    eng = ServingEngine(params, cfg, RULES, EngineConfig(
        n_slots=3, page_size=4, n_pages=24, max_pages_per_req=6,
        temperature=0.0, replace_every=0))
    if pool_dtype is not None:
        eng.cache.latent_pool = eng.cache.latent_pool.astype(pool_dtype)
    rng = np.random.default_rng(7)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, int(n)).astype(np.int32),
                       int(g))
            for n, g in zip(rng.integers(3, 10, 5), rng.integers(3, 9, 5))]
    seen = []
    decode = eng._decode

    def spy(params, pool, table, lengths, tokens):
        out = decode(params, pool, table, lengths, tokens)
        ln = np.asarray(lengths)
        seen.append(({s: (r, int(ln[s])) for s, r in
                      eng.scheduler.active.items()}, np.asarray(out[0])))
        return out
    eng._decode = spy
    eng.run()
    refs = {r.rid: np.asarray(ref.forward(
        params, jnp.asarray(np.concatenate([r.prompt, r.generated])), cfg))
        for r in reqs}
    gap, n = 0.0, 0
    for slots, logits in seen:
        for s, (r, p) in slots.items():
            want = refs[r.rid][p]
            gap = max(gap, float(np.abs(logits[s] - want).max()
                                 / np.abs(want).max()))
            n += 1
    assert n == sum(r.prompt_len + len(r.generated) - 1 for r in reqs)
    return gap


def test_engine_decode_matches_reference():
    """A share of the experts (4 of 8, from the third), YaRN and the
    unrenormalised gate, as the chip cell runs them."""
    cfg = _cfg(experts_held=(2, 4))
    assert _serve_and_compare(cfg, _params(cfg, 3)) <= REF_TOL


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.float8_e4m3fn])
def test_low_precision_latent_cache_fails_the_tolerance(dtype):
    """The comparison is tight enough to see the latent cache stored in
    float16 or float8."""
    cfg = _cfg(experts_held=(2, 4))
    assert _serve_and_compare(cfg, _params(cfg, 3), dtype) > 10 * REF_TOL


# ---------------------------------------------------------------------------
# Expert shares, dropless routing
# ---------------------------------------------------------------------------

def _moe_params(cfg, seed=0):
    return _params(cfg, seed)["moe_layers"]["ffn"]


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts each, the shared experts counted once,
    add up to the reference's uncut layer, in the program and in the
    reference alike."""
    full = _cfg(n_experts=16, top_k=4)
    f = jax.tree.map(lambda a: a[0], _moe_params(full))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, full.d_model))
    uncut = np.asarray(ref.moe(f, x, full))
    shared = np.asarray(ref._swiglu(x, f["ws_gate"], f["ws_up"],
                                    f["ws_down"]))
    prog, plain = [], []
    for first in range(0, 16, 2):
        share = dataclasses.replace(full, experts_held=(first, 2))
        fs = dict(f, **{k: f[k][first:first + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        y, load = tr.expert_share_ffn(fs, x, share)
        prog.append(np.asarray(y))
        plain.append(np.asarray(ref.moe(fs, x, share)))
        assert load.shape == (2,)
    for parts in (prog, plain):
        np.testing.assert_allclose(sum(parts) - 7 * shared, uncut,
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="holds every"):
        tr.moe_ffn(fs, x, share, RULES)


def test_dropless_output_does_not_depend_on_batch_mates():
    """Every token of the batch routes first to expert 3: the expert-share
    layer gives each token what it gives the token alone, where the
    capacity dispatch drops pairs beyond its capacity."""
    cfg = _cfg()
    f = jax.tree.map(lambda a: a[0], _moe_params(cfg))
    kx, kc = jax.random.split(jax.random.PRNGKey(4))
    c = jax.random.normal(kc, (cfg.d_model,))
    x = 0.3 * jax.random.normal(kx, (32, cfg.d_model)) + c
    f["router"] = f["router"].at[:, 3].add(50.0 * c / (c @ c))
    _, _, top_i = tr.route(f["router"], x, cfg)
    assert (np.asarray(top_i[:, 0]) == 3).all()
    y, load = tr.expert_share_ffn(f, x, cfg)
    assert int(load[3]) == 32 and int(load.sum()) == 32 * cfg.top_k
    alone = np.stack([np.asarray(tr.expert_share_ffn(f, x[i:i + 1], cfg)[0])
                      [0] for i in range(32)])
    np.testing.assert_allclose(np.asarray(y), alone, rtol=1e-6, atol=1e-6)
    capped = dataclasses.replace(cfg, capacity_factor=1.0)
    y_cap, stats = tr.moe_ffn(f, x, capped, RULES)
    assert float(stats.dropped_frac) > 0
    assert np.abs(np.asarray(y_cap) - alone).max() > 1e-3


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def test_yarn_angles_and_scale_follow_the_formula():
    """DeepSeek-V2-Lite's rope (64 dims, theta 1e4, factor 40 over 4096):
    corr(32) = 10.47 and corr(1) = 22.49, so dims 0-9 keep the original
    frequency, 23-31 take it over 40, and 16 blends 6/13 of the way."""
    full = configs.get("deepseek-v2-lite-16b").make_config("decode_32k")
    yarn = full.yarn
    orig = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    got = yarn.inv_freq(64, 1e4)
    np.testing.assert_array_equal(got[:10], orig[:10])
    np.testing.assert_allclose(got[23:], orig[23:] / 40, rtol=1e-12)
    np.testing.assert_allclose(got[16], orig[16] * (1 - 6 / 13)
                               + orig[16] / 40 * 6 / 13, rtol=1e-12)
    np.testing.assert_allclose(ref.inv_freq(full), got, rtol=1e-12)
    ang = np.asarray(full.angles(100))
    np.testing.assert_allclose(ang[99], (99 * got).astype(np.float32),
                               rtol=1e-6)
    mscale = 0.1 * 0.707 * np.log(40) + 1
    assert full.mla_scale == pytest.approx(192 ** -0.5 * mscale ** 2,
                                           rel=1e-12)
    assert full.mla_scale == pytest.approx(0.11472, abs=1e-5)
    assert ref.softmax_scale(full) == pytest.approx(full.mla_scale,
                                                    rel=1e-12)


def test_unscaled_rope_is_unchanged_bit_for_bit():
    """Without rope scaling the angles are the plain RoPE ones, exactly:
    Qwen2's decode program sees the same constants."""
    qwen = configs.get("qwen2-1.5b").make_config("decode_32k")
    assert qwen.yarn is None
    d, n, theta = qwen.head_dim, 1408, qwen.rope_theta
    plain = np.outer(np.arange(n), 1.0 / (theta ** (np.arange(0, d, 2) / d)))
    want = jnp.asarray(plain, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(qwen.angles(n)),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(common.rope_freqs(d, n, theta)),
                                  np.asarray(want))


def test_yarn_rejects_a_rope_gain():
    y = common.Yarn(factor=40.0, original_max_position=4096, mscale=1.0,
                    mscale_all_dim=0.707)
    with pytest.raises(NotImplementedError, match="gain"):
        common.rope_freqs(64, 8, 1e4, y)


# ---------------------------------------------------------------------------
# Routing counters
# ---------------------------------------------------------------------------

def test_routing_counters_follow_the_step_load(tmp_path):
    """Under a trace the engine counts, from the load each step returns,
    the routed pairs of its active slots, those on held experts, the
    busiest held expert's pairs and the held experts hit."""
    cfg = _cfg(experts_held=(0, 4))
    params = _params(cfg)
    eng = ServingEngine(params, cfg, RULES, EngineConfig(
        n_slots=3, page_size=4, n_pages=24, max_pages_per_req=6,
        temperature=0.0, replace_every=0))
    rng = np.random.default_rng(1)
    for _ in range(2):
        eng.submit(rng.integers(0, cfg.vocab, 5).astype(np.int32), 6)
    decode, loads = eng._decode, []

    def spy(*args):
        out = decode(*args)
        loads.append((np.asarray(out[2]),
                      int((np.asarray(args[2])[:, 0] < 24).sum())))
        return out
    eng._decode = spy
    eng.step()                                         # compile
    loads.clear()
    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(4):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    t = obs.totals()
    obs.reset()
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert t["moe.pairs_routed"] == sum(a * cfg.top_k * n_moe
                                        for _, a in loads) == 4 * 2 * 2
    assert t["moe.pairs_local"] == sum(int(ld.sum()) for ld, _ in loads)
    assert t["moe.pairs_max"] == sum(int(ld.max(1).sum()) for ld, _ in loads)
    assert t["moe.experts_hit"] == sum(int((ld > 0).sum()) for ld, _ in loads)
    assert 0 < t["moe.pairs_local"] <= t["moe.pairs_routed"]
    assert t["decode.pages_gathered"] == 4 * 3 * 6
