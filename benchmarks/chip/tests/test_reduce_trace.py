"""The trace reduction on hand-made events, on a recorded chip trace and
on a trace recorded here."""
import json
from pathlib import Path

import pytest
import reduce_trace as rt

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _events():
    ev = rt.Ev
    dev = {"modules": [ev("jit_step(1)", 10 * MS, 30 * MS),
                       ev("jit__refine_batch_jit(2)", 40 * MS, 80 * MS),
                       ev("jit_other(3)", 90 * MS, 95 * MS)],
           "ops": [ev("fusion.1", 10 * MS, 20 * MS),
                   ev("scatter.2", 15 * MS, 30 * MS),    # overlaps fusion.1
                   ev("fusion.1", 40 * MS, 80 * MS),
                   ev("copy.3", 90 * MS, 95 * MS)]}
    host = [ev("bench.window", 0, 100 * MS),
            ev("bench.place", 5 * MS, 85 * MS),
            ev("bench.map", 82 * MS, 85 * MS),
            ev("from_edges", 32 * MS, 38 * MS)]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_busy_is_the_union_and_idle_the_rest():
    red = rt.Reduction(_events())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.020 + 0.040 + 0.005)
    assert red.idle_share == pytest.approx(1 - 0.065 / 0.1)


def test_program_time_by_name():
    red = rt.Reduction(_events())
    assert red.program_time(["jit_step"]) == pytest.approx(0.020)
    assert red.program_time(["_refine_batch_jit"]) == pytest.approx(0.040)
    assert red.program_time(["nothing"]) == 0.0


def test_busy_within_a_span():
    red = rt.Reduction(_events())
    assert red.busy_within(0, 35 * MS) == pytest.approx(0.020)
    assert red.busy_within(25 * MS, 45 * MS) == pytest.approx(0.010)


def test_gaps_named_by_host_activity():
    bd = rt.Reduction(_events()).breakdown()
    gaps = dict((round(s, 6), n) for n, s in bd["idle_gaps"])
    assert gaps[0.01] in ("bench.place", "bench.window")   # 30..40 ms
    names = [n for n, _ in bd["idle_gaps"]]
    assert "from_edges" in names                           # inside 30..40
    ops = dict(bd["device_ops"])
    assert ops["jit__refine_batch_jit:fusion.1"] == pytest.approx(0.040)
    assert ops["jit_step:scatter.2"] == pytest.approx(0.015)


def test_json_round_trip():
    ev = _events()
    back = rt.from_json(json.loads(json.dumps(rt.to_json(ev))))
    assert rt.Reduction(back).busy_s == rt.Reduction(ev).busy_s


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e chip: a jitted bf16 matmul run three
    times inside ``bench.step`` spans within a ``bench.window`` span. The
    device's clock runs about 0.85 ms ahead of the host's spans there, so
    each execution starts just before the span that launched it."""
    ev = rt.from_json(json.loads((DATA / "trace_v5e_small.json")
                                 .read_text()))
    red = rt.Reduction(ev)
    assert len(red.devices) == 1
    assert len(red.spans("bench.step")) == 3
    assert 0 < red.busy_s < red.window_s
    (dev,) = red.devices.values()
    starts = [m.start for m in dev["modules"] if "jit__lambda" in m.name]
    steps = [s.start for s in red.spans("bench.step")]
    assert len(starts) == 3
    for launch, run in zip(steps, starts):
        assert 0 < launch - run < 2_000_000
    ops = dict(red.breakdown()["device_ops"])
    assert all(k.startswith("jit__lambda:") for k in ops)


def test_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = rt.reduce_dir(str(tmp_path))
    assert len(red.spans("bench.step")) == 1
    assert red.window_s > 0
