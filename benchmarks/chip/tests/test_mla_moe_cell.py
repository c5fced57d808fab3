"""The DeepSeek-V2 (MLA + MoE) serving cell at a tiny size on the CPU: its
reference against the program's own, a whole run sound and with the
timed path broken underneath, the float8 control, the work counts by
hand, and the readers of its per-layer metrics."""
import functools
import time

import harness
import jax
import jax.numpy as jnp
import mla_moe_reference
import numpy as np
import pytest
import reduce_trace as rt
import work_mla_moe

ROOT = harness.ROOT
CELL = "serve.deepseek-v2-lite.ep8.chat"


def _quiet(_):
    pass


def mla_cell():
    cell = harness.load_cell(ROOT / "BENCHMARK.json", CELL)
    cell.config = dict(
        cell.config, num_hidden_layers=3, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=16,
        intermediate_size=128, moe_intermediate_size=32, vocab_size=4096,
        n_routed_experts=8, experts_held_first=4, num_experts_per_tok=4,
        published=dict(cell.config["published"], n_routed_experts=16),
        initializer_range=0.05)
    cell.traffic = dict(cell.traffic, rate_per_s=20.0, warm_s=1.0,
                        prompt={"median": 8, "sigma": 0.8, "min": 2,
                                "max": 24},
                        output={"median": 12, "sigma": 0.8, "min": 2,
                                "max": 16},
                        slots=4, page_size=4, pages_per_slot=10,
                        check_tokens=200, check_requests=4, drain_s=10.0)
    # at this size sound runs read gaps of 0.0040 to 0.0146 over 200 served
    # tokens, the float8 control 0.082 to 0.256 (CPU, seeds 1-4), and a
    # share shifted by one expert 0.068 to 0.075 (seeds 5, 6); the chip
    # cell's own limit is in limits/
    cell.limits = {"logit_gap": 0.04}
    return cell


def _driver():
    return harness.load_module(harness.HERE / "drivers" / "serve_mla_moe.py")


def run(cell, seed=5, seconds=2.0):
    return harness.run_cell(cell, seed, seconds, False,
                            t0=time.perf_counter(), devices=jax.devices(),
                            peaks={}, log=_quiet)


def controlled(cell, seed, seconds):
    """``(program correct, control correct)`` of one short window."""
    driver, st = harness.start(cell, seed, seconds, jax.devices(),
                               log=_quiet)
    win = driver.window(st, seconds)
    harness.finish(driver, st, win)
    _, program = harness.judge(driver, st, win, log=_quiet)
    _, control = harness.judge(driver, st, win, log=_quiet, control=True)
    return program, control


# -- the reference -----------------------------------------------------------

def test_reference_agrees_with_the_program_reference():
    """The benchmark's copy and the program's reference give the same
    float32 logits from the same weights (both at ``highest`` precision,
    one layer-scanned, the other layer by layer)."""
    from repro.models import reference_mla_moe
    cell = mla_cell()
    params = mla_moe_reference.make_params(cell.config, 3)
    pcfg = _driver().program_config(cell.config, cell.config_name)
    toks = np.random.default_rng(0).integers(0, 4096, 40)
    ours = mla_moe_reference.forward(params, cell.config, toks)
    theirs = np.asarray(reference_mla_moe.forward(params, jnp.asarray(toks),
                                                  pcfg))
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-5)


def test_program_config_refuses_what_it_cannot_run():
    cell = mla_cell()
    for bad in ({"q_lora_rank": 1536}, {"topk_method": "group_limited_greedy"},
                {"rms_norm_eps": 1e-5}):
        with pytest.raises(ValueError):
            _driver().program_config(dict(cell.config, **bad), "x")


# -- whole runs --------------------------------------------------------------

def test_sound_run_is_correct():
    res = run(mla_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 10
    assert res["metrics"]["itl_p95_ms"]["value"] > 0


def _altered_token(monkeypatch):
    from repro.serving import engine as engine_mod
    init = engine_mod.ServingEngine.__init__

    def patched(self, *args, **kwargs):
        init(self, *args, **kwargs)
        sample, vocab = self._sample, self.cfg.vocab
        self._sample = lambda lg, r, p: (sample(lg, r, p) + 1) % vocab
    monkeypatch.setattr(engine_mod.ServingEngine, "__init__", patched)


def _decode(monkeypatch, fault):
    import dataclasses

    from repro.serving import engine as engine_mod
    from repro.serving.paged_decode import paged_decode_step_mla

    def jitted(cfg, rules):
        if fault == "other_experts":
            first, count = cfg.held
            cfg = dataclasses.replace(cfg, experts_held=(first + 1, count))
        f = jax.jit(functools.partial(paged_decode_step_mla, cfg=cfg,
                                      rules=rules))

        def step(params, pool, table, lengths, tokens):
            logits, pool2, load = f(params, pool, table, lengths, tokens)
            if fault == "state_unchanged":
                return logits, pool, load
            if fault == "half_the_batch":
                n = logits.shape[0]
                logits = logits.at[n // 2:].set(logits[:n - n // 2])
            return logits, pool2, load
        return step
    monkeypatch.setattr(engine_mod, "_jitted_decode", jitted)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_the_batch", "other_experts"])
def test_fault_is_not_correct(monkeypatch, fault):
    if fault == "token_altered":
        _altered_token(monkeypatch)
    else:
        _decode(monkeypatch, fault)
    res = run(mla_cell())
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [1, 2])
def test_control_is_not_correct(seed):
    assert controlled(mla_cell(), seed, 2.0) == (True, False)


# -- work counts -------------------------------------------------------------

TINY = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 3,
        "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 5,
        "intermediate_size": 6, "moe_intermediate_size": 7,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "n_shared_experts": 2, "published": {"n_routed_experts": 10},
        "n_routed_experts": 4, "vocab_size": 11}


def test_attention_and_token_params_hand_count():
    # W_q 8*2*5 = 80, W_dkv 8*5 = 40, W_kr 8*2 = 16, W_uk 5*2*3 = 30,
    # W_uv 5*2*4 = 40, W_o 2*4*8 = 64
    assert work_mla_moe.attn_params(TINY) == 270
    assert work_mla_moe.expert_params(TINY) == 3 * 8 * 7
    # 3 attentions, one dense FFN 3*8*6, two MoE layers of router 8*10 and
    # two shared experts 2*168, the head 8*11
    assert work_mla_moe.token_params(TINY) == \
        3 * 270 + 144 + 2 * (80 + 336) + 88


def test_window_bytes_hand_count():
    per_step = 2 * (work_mla_moe.token_params(TINY) + 3 * (16 + 5) + 8)
    assert work_mla_moe.step_weight_bytes(TINY) == per_step
    assert work_mla_moe.latent_bytes(TINY) == 2 * 7
    # 2 steps, 5 expert reads, 6 live pages of 4, 3 tokens written
    assert work_mla_moe.window_bytes(TINY, 2, 5, 6, 4, 3) == \
        2 * per_step + 5 * 2 * 168 + 6 * 4 * 3 * 14 + 3 * 3 * 14


def test_flops_hand_count():
    # one token over a context of 5 with 2 local pairs: 2 * token params,
    # 2 * heads(2) * (2 * rank(5) + rope(2)) * 5 * layers(3), 2 * 2 * 168
    base = 2 * work_mla_moe.token_params(TINY)
    assert work_mla_moe.flops(TINY, 1, 5, 2) == \
        base + 2 * 2 * 12 * 5 * 3 + 2 * 2 * 168


# -- readers -----------------------------------------------------------------

MS = 1_000_000


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py")


def _red(modules):
    dev = {"modules": [rt.Ev(n, s * MS, e * MS) for n, s, e in modules],
           "ops": [rt.Ev("fusion", s * MS, e * MS) for _, s, e in modules]}
    return rt.Reduction({"devices": {"/device:TPU:0": dev},
                         "host": [rt.Ev("bench.window", 0, 1000 * MS)]})


@pytest.fixture
def totals(monkeypatch):
    import counters

    def use(d):
        monkeypatch.setattr(counters, "totals", lambda: dict(d))
    return use


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(**kw):
    red = _red([("jit_paged_decode_step_mla(1)", 0, 30),
                ("jit_paged_decode_step_mla(1)", 40, 70),
                ("jit_sample(2)", 70, 71)])
    win = harness.Window(metrics={}, attempted=0, failed=0, seconds=0.5)
    base = dict(trace=red, steps=2, tokens=6, contexts=40, window=win,
                model=TINY, page_size=4, peaks=PEAKS)
    base.update(kw)
    return base


def test_mla_step_ms():
    read = _reader("mla_step_ms").read
    assert read(_ctx()) == pytest.approx(30.0)
    assert read(_ctx(steps=0)) is None
    assert read(_ctx(trace=_red([("jit_sample(2)", 0, 5)]))) is None


def test_mla_step_roofline(totals):
    read = _reader("mla_step_roofline").read
    totals({"moe.experts_hit": 5, "decode.pages_live": 6})
    least = work_mla_moe.window_bytes(TINY, 2, 5, 6, 4, 6) / 819e9
    assert read(_ctx()) == pytest.approx(100 * least / 0.06)
    totals({"decode.pages_live": 6})
    assert read(_ctx()) is None


def test_moe_imbalance(totals):
    read = _reader("moe_imbalance").read
    # 4 held experts, 40 local pairs, busiest 16 over the steps and layers
    totals({"moe.pairs_local": 40, "moe.pairs_max": 16})
    assert read(_ctx()) == pytest.approx(160.0)
    totals({})
    assert read(_ctx()) is None


def test_mla_serve_mfu(totals):
    read = _reader("mla_serve_mfu").read
    totals({"moe.pairs_local": 9})
    want = 100 * work_mla_moe.flops(TINY, 6, 40, 9) / (0.5 * 197e12)
    assert read(_ctx()) == pytest.approx(want)
    totals({})
    assert read(_ctx()) is None


def test_readers_without_the_program_counters(monkeypatch):
    import sys

    import counters
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert counters.totals() == {}
    for name in ("mla_step_roofline", "moe_imbalance", "mla_serve_mfu"):
        assert _reader(name).read(_ctx()) is None
