"""The chip smoke script's phases at toy sizes on the CPU, and its refusal
to run without a TPU (the phases themselves are run full size on the chip
by ``python chip_smoke.py``)."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from repro.graph.generators import grid3d, rmat


@pytest.fixture(scope="module")
def smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_engine_phase_small_grid(smoke):
    rows = smoke.engine_phase(
        {"grid3d": grid3d(16, 16, 8), "rmat": rmat(2048, 8000, seed=0)},
        grid3d(16, 16, 8), machine="tpu-mixed-32", map_restarts=2)
    by_graph = {r["graph"]: r for r in rows}
    assert set(by_graph) == {"grid3d", "rmat", "host_check"}
    for name in ("grid3d", "rmat"):
        r = by_graph[name]
        assert r["k"] == 32 and r["makespan"] > 0
        assert r["map_searched"] <= r["map_identity"] * (1 + 1e-6)
    assert by_graph["host_check"]["ratio"] <= smoke.HOST_RATIO


def test_vcycle_custom_calls_report_the_xla_path_on_cpu(smoke):
    """Off the TPU the device V-cycle runs the XLA fallbacks, and the
    check says so instead of passing."""
    calls = smoke.vcycle_custom_calls(grid3d(8, 8, 8), 32)
    assert calls == {"coarsen_device": False,
                     "initial_partition_device": False}


def test_serving_phase_smoke_config(smoke):
    out = smoke.serving_phase(smoke=True, prompt_lens=(4, 12),
                              gen_lens=(2, 6), page_size=4, replace_every=4)
    assert out["requests"] == 8 and out["placement_epochs"] >= 1
    assert out["compared"] >= 8
    assert out["logits_rel_l2"] <= smoke.LOGITS_RTOL


def test_serving_phase_rejects_nan_logits(smoke, monkeypatch):
    """NaN compares false against any bound: the check must still fail."""
    import numpy as np
    dense = smoke._dense_logits
    monkeypatch.setattr(smoke, "_dense_logits", lambda *a: {
        key: v * np.nan for key, v in dense(*a).items()})
    with pytest.raises(AssertionError, match="logits"):
        smoke.serving_phase(smoke=True, prompt_lens=(4, 12), gen_lens=(2, 6),
                            page_size=4, replace_every=4)


def test_train_phase_smoke_config(smoke):
    out = smoke.train_phase(smoke=True, seq=32)
    assert len(out["searched"]) == 4
    assert out["rel"] <= smoke.LOSS_RTOL


def test_main_exits_nonzero_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """An outside JAX_COMPILATION_CACHE_DIR wins and nothing is set in
    code; unset, the cache is the checkout's fixed ``.jax_cache``."""
    import jax

    from repro.launch import compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: updates.append((name, val)))
    root = Path(__file__).resolve().parents[1]
    got = compile_cache.enable()
    if env_dir is None:
        assert got == str(root / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", got)]
    else:
        assert got == env_dir and updates == []
