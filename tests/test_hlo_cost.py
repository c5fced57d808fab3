"""The text-level HLO cost model: exact on loop-free modules, trip-scaled
on scans (where XLA's own analysis under-counts)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost


def _xla_cost(comp):
    return comp.cost_analysis() or {}


def test_loop_free_matches_xla():
    def f(w1, w2, x):
        return jnp.tanh(x @ w1) @ w2

    w1 = jnp.zeros((256, 512))
    w2 = jnp.zeros((512, 128))
    x = jnp.zeros((64, 256))
    comp = jax.jit(f).lower(w1, w2, x).compile()
    xla = _xla_cost(comp)
    mine = hlo_cost.analyze(comp.as_text())
    assert abs(mine["flops"] - xla["flops"]) / xla["flops"] < 0.05


def test_scan_trip_scaling():
    def g(ws, x):
        def body(x, w):
            return x @ w, ()
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()

    ws = jnp.zeros((6, 256, 256))
    x = jnp.zeros((64, 256))
    comp = jax.jit(g).lower(ws, x).compile()
    true_flops = 6 * 2 * 64 * 256 * 256
    mine = hlo_cost.analyze(comp.as_text())
    assert abs(mine["flops"] - true_flops) / true_flops < 0.05
    # XLA counts the body once -> must undercount by ~6x
    xla = _xla_cost(comp)
    assert xla["flops"] < 0.5 * true_flops


def test_nested_scans_compound():
    def h(x):
        def outer(x, _):
            def inner(x, _):
                return x @ jnp.eye(64), ()
            x, _ = jax.lax.scan(inner, x, None, length=4)
            return x, ()
        x, _ = jax.lax.scan(outer, x, None, length=3)
        return x.sum()

    x = jnp.zeros((32, 64))
    comp = jax.jit(h).lower(x).compile()
    true_flops = 3 * 4 * 2 * 32 * 64 * 64
    mine = hlo_cost.analyze(comp.as_text())
    assert abs(mine["flops"] - true_flops) / true_flops < 0.1


def test_bytes_counters_ordering():
    def f(w, x):
        return jax.nn.relu(x @ w).sum()

    comp = jax.jit(f).lower(jnp.zeros((128, 128)),
                            jnp.zeros((32, 128))).compile()
    out = hlo_cost.analyze(comp.as_text())
    assert out["bytes"] >= out["bytes_fused"] >= out["bytes_tight"] > 0


def test_collective_parse_on_sharded_module():
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    from repro.launch.dryrun import parse_collectives  # noqa
    # single-device module has no collectives
    comp = jax.jit(lambda x: x * 2).lower(jnp.zeros(8)).compile()
    out = parse_collectives(comp.as_text(), 1, [1])
    assert out["count"] == 0
