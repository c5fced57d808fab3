"""The chip smoke script's training phase at toy size on the CPU, and its
refusal to run without a TPU (the phase itself runs full size on a
four-chip host by ``python chip_smoke.py``)."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest


@pytest.fixture(scope="module")
def smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_smoke_config(smoke):
    out = smoke.train_phase(smoke=True, seq=32)
    assert len(out["searched"]) == 4
    assert out["rel"] <= smoke.LOSS_RTOL


def test_main_exits_nonzero_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "nothing was run" in err


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
