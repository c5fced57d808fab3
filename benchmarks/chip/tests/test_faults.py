"""A whole run of each kind of cell, on the CPU at a tiny size with the
harness's look for a chip skipped, first sound and then with the timed
path broken underneath: ``correct`` has to come out false for every fault
the cell can have. Also the controls at these sizes, judged by the same
check in the program's place, and the exit of the command where JAX finds
no TPU."""
import functools
import os
import subprocess
import sys
import time

import harness
import jax
import numpy as np
import pytest

ROOT = harness.ROOT


def _quiet(_):
    pass


def engine_cell():
    cell = harness.load_cell(ROOT / "BENCHMARK.json",
                             "place.grid3d-fem.v5e256")
    cell.config = dict(cell.config, nx=16, ny=16, nz=16)
    return cell


def serve_cell():
    cell = harness.load_cell(ROOT / "BENCHMARK.json", "serve.qwen2-1.5b.chat")
    cell.config = dict(cell.config, num_hidden_layers=4, hidden_size=128,
                       num_attention_heads=4, num_key_value_heads=2,
                       intermediate_size=256, vocab_size=8192,
                       initializer_range=0.05)
    cell.traffic = dict(cell.traffic, rate_per_s=20.0, warm_s=1.0,
                        prompt={"median": 8, "sigma": 0.8, "min": 2,
                                "max": 24},
                        output={"median": 12, "sigma": 0.8, "min": 2,
                                "max": 16},
                        slots=4, page_size=4, pages_per_slot=10,
                        check_tokens=200, check_requests=4,
                        drain_s=10.0)
    # at this size sound runs read gaps of 0.0005 to 0.012 over 200 served
    # tokens and the float8 control 0.12 to 0.30 (CPU, seeds 1-4); the chip
    # cell's own limit is in limits/
    cell.limits = {"logit_gap": 0.04}
    return cell


def controlled(cell, seed, seconds):
    """``(program correct, control correct)`` of one short window."""
    driver, st = harness.start(cell, seed, seconds, jax.devices(),
                               log=_quiet)
    win = driver.window(st, seconds)
    harness.finish(driver, st, win)
    _, program = harness.judge(driver, st, win, log=_quiet)
    _, control = harness.judge(driver, st, win, log=_quiet, control=True)
    return program, control


def run(cell, seed=5, seconds=1.0):
    return harness.run_cell(cell, seed, seconds, False,
                            t0=time.perf_counter(), devices=jax.devices(),
                            peaks={}, log=_quiet)


# -- the placement engine --------------------------------------------------

def test_engine_sound_run_is_correct():
    res = run(engine_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["metrics"]["place_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def _moved_vertex(partition):
    def wrapped(g, topo, cfg=None):
        res = partition(g, topo, cfg)
        res.part = res.part.copy()
        res.part[0] = (res.part[0] + 1) % topo.k
        return res
    return wrapped


def _half_the_edges(partition):
    from repro.graph.graph import from_edges

    def wrapped(g, topo, cfg=None):
        keep = np.nonzero(g.senders < g.receivers)[0][::2]
        half = from_edges(g.n_nodes, g.senders[keep], g.receivers[keep],
                          g.edge_weight[keep], g.node_weight)
        return partition(half, topo, cfg)
    return wrapped


def _understated_map(search):
    def wrapped(*args, **kwargs):
        best = search(*args, **kwargs)
        best.bottleneck = best.bottleneck - 1.0
        return best
    return wrapped


@pytest.mark.parametrize("fault", ["answer_altered", "half_the_input",
                                   "map_answer_altered"])
def test_engine_fault_is_not_correct(monkeypatch, fault):
    from repro.core import mapping, partitioner
    if fault == "answer_altered":
        monkeypatch.setattr(partitioner, "partition",
                            _moved_vertex(partitioner.partition))
    elif fault == "half_the_input":
        monkeypatch.setattr(partitioner, "partition",
                            _half_the_edges(partitioner.partition))
    else:
        monkeypatch.setattr(mapping, "search",
                            _understated_map(mapping.search))
    res = run(engine_cell())
    assert not res["correct"], res["checks"]


def test_engine_control_is_not_correct():
    assert controlled(engine_cell(), 3, 0.2) == (True, False)


# -- serving ---------------------------------------------------------------

def test_serve_sound_run_is_correct():
    res = run(serve_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 10
    assert res["metrics"]["ttft_p95_ms"]["value"] > 0


def _altered_token(monkeypatch):
    from repro.serving import engine as engine_mod
    init = engine_mod.ServingEngine.__init__

    def patched(self, *args, **kwargs):
        init(self, *args, **kwargs)
        sample, vocab = self._sample, self.cfg.vocab
        self._sample = lambda lg, r, p: (sample(lg, r, p) + 1) % vocab
    monkeypatch.setattr(engine_mod.ServingEngine, "__init__", patched)


def _decode(monkeypatch, fault):
    from repro.serving import engine as engine_mod
    from repro.serving.paged_decode import paged_decode_step

    def jitted(cfg, rules):
        f = jax.jit(functools.partial(paged_decode_step, cfg=cfg,
                                      rules=rules))

        def step(params, k, v, table, lengths, tokens):
            logits, k2, v2 = f(params, k, v, table, lengths, tokens)
            if fault == "state_unchanged":
                return logits, k, v
            n = logits.shape[0]
            return logits.at[n // 2:].set(logits[:n - n // 2]), k2, v2
        return step
    monkeypatch.setattr(engine_mod, "_jitted_decode", jitted)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_the_batch"])
def test_serve_fault_is_not_correct(monkeypatch, fault):
    if fault == "token_altered":
        _altered_token(monkeypatch)
    else:
        _decode(monkeypatch, fault)
    res = run(serve_cell())
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [1, 2])
def test_serve_control_is_not_correct(seed):
    assert controlled(serve_cell(), seed, 2.0) == (True, False)


# -- the command -----------------------------------------------------------

def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "place.grid3d-fem.v5e256", "--seed",
                        "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no" in p.stderr.lower() or "tpu" in p.stderr.lower()
