"""The readers of the program's own spans and counters, on hand-made
events and totals, and on a program that has neither."""
import sys

import counters
import harness
import pytest
import reduce_trace as rt

MS = 1_000_000


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py")


def _red(host, ops=()):
    dev = {"modules": [], "ops": [rt.Ev("fusion", s * MS, e * MS)
                                  for s, e in ops]}
    return rt.Reduction({"devices": {"/device:TPU:0": dev},
                         "host": [rt.Ev("bench.window", 0, 100 * MS)]
                         + [rt.Ev(n, s * MS, e * MS) for n, s, e in host]})


@pytest.fixture
def totals(monkeypatch):
    def use(d):
        monkeypatch.setattr(counters, "totals", lambda: dict(d))
    return use


def test_refine_useful_share(totals):
    read = _reader("refine_useful_share").read
    totals({"refine.rounds": 128, "refine.rounds_to_best": 96})
    assert read({}) == pytest.approx(75.0)
    totals({"refine.rounds": 64})
    assert read({}) == 0.0
    totals({})
    assert read({}) is None


def test_decode_page_share(totals):
    read = _reader("decode_page_share").read
    totals({"decode.pages_gathered": 5632, "decode.pages_live": 1408})
    assert read({}) == pytest.approx(25.0)
    totals({})
    assert read({}) is None


def test_queue_wait_ms(totals):
    read = _reader("queue_wait_ms").read
    totals({"serve.admitted": 4, "serve.queue_wait_s": 0.2})
    assert read({}) == pytest.approx(50.0)
    totals({"serve.queue_wait_s": 0.0})
    assert read({}) is None


def test_partition_idle_s():
    read = _reader("partition_idle_s").read
    # two placements; busy 25 of the first's 40 ms and 15 of the second's
    red = _red([("partition", 10, 50), ("partition", 60, 80)],
               ops=[(5, 20), (30, 45), (60, 75), (85, 90)])
    assert read({"trace": red, "placements": 2}) \
        == pytest.approx((0.015 + 0.005) / 2)
    assert read({"trace": _red([]), "placements": 2}) is None
    assert read({"trace": red, "placements": 0}) is None


def test_sched_ms():
    read = _reader("sched_ms").read
    host = []
    for t in (10, 50):             # two steps of 30 ms
        host += [("serve.step", t, t + 30), ("serve.admit", t, t + 1),
                 ("serve.inputs", t + 1, t + 3),
                 ("serve.dispatch", t + 3, t + 4),
                 ("serve.pull", t + 4, t + 25),
                 ("serve.advance", t + 25, t + 29),
                 ("serve.record_access", t + 25, t + 27)]
    assert read({"trace": _red(host)}) == pytest.approx(7.0)
    assert read({"trace": _red([("bench.step", 10, 40)])}) is None


def test_a_program_without_counters(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert counters.totals() == {}
    for name in ("refine_useful_share", "decode_page_share",
                 "queue_wait_ms"):
        assert _reader(name).read({}) is None
