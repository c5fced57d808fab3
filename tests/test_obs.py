"""Spans and counters of ``repro.obs``: the switch follows the profiler,
counters count only while a trace is collected, the V-cycle's and the
serving step's spans nest as their names say in a recorded trace, and each
counter equals the quantity it stands for, recomputed here."""
import contextlib
import sys
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import configs, obs
from repro.core import mapping
from repro.core.initial import random_partition
from repro.core.partitioner import PartitionConfig, partition
from repro.core.refine import RefineConfig, refine_batch, rounds_to_best
from repro.core.topology import balanced_tree
from repro.dist.sharding import lm_rules
from repro.graph.generators import grid2d
from repro.models import transformer as tr
from repro.serving import EngineConfig, ServingEngine

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip"))
import reduce_trace  # noqa: E402

RULES = lm_rules(())
TOPO = balanced_tree((2, 2))


@pytest.fixture(autouse=True)
def _fresh_totals():
    obs.reset()
    yield
    obs.reset()


@contextlib.contextmanager
def traced(trace_dir):
    """A profiler trace of the block, inside a ``bench.window`` span: the
    trace reducer keeps the host threads that carry a ``bench.`` span."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()


def _spans(trace_dir):
    host = reduce_trace.load_events(
        reduce_trace.find_xplane(str(trace_dir)))["host"]
    out = defaultdict(list)
    for e in host:
        out[e.name].append(e)
    return out


def _inside(child, parent):
    return parent.start <= child.start and child.end <= parent.end


def _within_one(children, parents):
    return all(sum(_inside(c, p) for p in parents) == 1 for c in children)


def test_on_follows_the_profiler(tmp_path):
    assert not obs.on()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.on()
    finally:
        jax.profiler.stop_trace()
    assert not obs.on()


def test_add_counts_only_while_tracing(tmp_path):
    obs.add("x", 3)
    assert obs.totals() == {}
    with traced(tmp_path):
        obs.add("x", 3)
        obs.add("x")
    obs.add("x", 5)
    assert obs.totals() == {"x": 4}
    obs.reset()
    assert obs.totals() == {}


def test_rounds_to_best():
    m = np.asarray([[5.0, 4.0, 3.0, 3.0, 4.0],     # best first at round 3
                    [9.0, 8.0, 9.0, 8.5, 8.2],     # start (7.0) stood
                    [6.0, 7.0, 6.0, 7.0, 7.0]])    # ties its start of 6.0
    best = np.asarray([3.0, 7.0, 6.0])
    assert rounds_to_best(m, best).tolist() == [3, 0, 1]


def test_refine_counters_match_the_returned_stats(tmp_path):
    g = grid2d(12, 12)
    parts0 = np.stack([random_partition(g.n_nodes, TOPO.k, g.node_weight,
                                        seed=s) for s in range(2)])
    cfg = RefineConfig(rounds=8)
    refine_batch(g, TOPO, parts0, cfg)                 # compile
    with traced(tmp_path):
        _, best_ms, stats = refine_batch(g, TOPO, parts0, cfg)
    want = 0
    for m, b in zip(stats.makespan, best_ms):
        want += int(np.argmin(m)) + 1 if m.min() <= b else 0
    assert obs.totals() == {"refine.rounds": 16,
                            "refine.rounds_to_best": want,
                            "refine.edge_rounds": 0}


def test_edge_rounds_count_every_sparse_round(tmp_path):
    """``refine.edge_rounds`` counts the slot-rounds priced over each edge
    once: all of a sparse call's, none of a dense call's."""
    g = grid2d(12, 12)
    parts0 = np.stack([random_partition(g.n_nodes, TOPO.k, g.node_weight,
                                        seed=s) for s in range(3)])
    dense = RefineConfig(rounds=5)
    sparse = RefineConfig(rounds=5, dense_threshold=0)
    for cfg in (dense, sparse):
        refine_batch(g, TOPO, parts0, cfg)             # compile
    with traced(tmp_path):
        refine_batch(g, TOPO, parts0, dense)
        refine_batch(g, TOPO, parts0, sparse)
    t = obs.totals()
    assert t["refine.rounds"] == 30 and t["refine.edge_rounds"] == 15


def test_partition_spans_nest(tmp_path):
    g = grid2d(24, 24)
    cfg = PartitionConfig(backend="device", refine=RefineConfig(rounds=4))
    partition(g, TOPO, cfg)                            # compile
    with traced(tmp_path):
        res = partition(g, TOPO, cfg)
        W = np.ones((TOPO.k, TOPO.k)) - np.eye(TOPO.k)
        mapping.search((2, 2), TOPO, W)
    sp = _spans(tmp_path)
    (whole,) = sp["partition"]
    (coarsen,) = sp["partition.coarsen"]
    (initial,) = sp["partition.initial"]
    (evaluate,) = sp["partition.evaluate"]
    n_levels = len(res.level_makespans)
    assert n_levels >= 2
    assert all(_inside(s, whole) for s in (coarsen, initial, evaluate))
    assert n_levels - 1 <= len(sp["coarsen.level"]) <= n_levels
    assert _within_one(sp["coarsen.level"], [coarsen])
    assert len(sp["partition.refine"]) == n_levels
    assert _within_one(sp["partition.refine"], [whole])
    assert len(sp["refine.pull"]) == n_levels
    assert _within_one(sp["refine.pull"], sp["partition.refine"])
    assert len(sp["partition.project"]) == n_levels - 1
    assert _within_one(sp["partition.project"], [whole])
    (search,) = sp["map.search"]
    assert search.start >= whole.end
    assert obs.totals()["refine.rounds"] == 4 * n_levels


def _engine():
    cfg = configs.get("qwen2-1.5b").smoke_config()
    params, _ = tr.init(jax.random.PRNGKey(0), cfg, RULES)
    eng = ServingEngine(params, cfg, RULES, EngineConfig(
        n_slots=2, page_size=4, n_pages=16, max_pages_per_req=4,
        temperature=0.0))
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, n).astype(np.int32), g)
            for n, g in ((6, 3), (3, 2), (5, 2))]
    return eng, reqs


def test_serving_counters_and_spans(tmp_path):
    eng, reqs = _engine()
    eng.step()                                         # compile
    steps, live = 6, 0
    with traced(tmp_path):
        for _ in range(steps):
            before = [r.pos for r in reqs]
            eng.step()
            # a request the step advanced attended to positions [0, pos]
            live += sum(-(-r.pos // 4) for r, p in zip(reqs, before)
                        if r.pos == p + 1)
    t = obs.totals()
    assert t["decode.pages_live"] == live > 0
    assert t["decode.pages_gathered"] == steps * 2 * 4
    assert t["serve.admitted"] == 1                    # the third request
    assert t["serve.queue_wait_s"] > 0
    sp = _spans(tmp_path)
    assert len(sp["serve.step"]) == steps
    for name in ("serve.admit", "serve.inputs", "serve.dispatch",
                 "serve.pull", "serve.advance"):
        assert len(sp[name]) == steps, name
        assert _within_one(sp[name], sp["serve.step"]), name
    assert _within_one(sp["serve.record_access"], sp["serve.advance"])


def test_no_counts_without_a_trace():
    partition(grid2d(24, 24), TOPO, PartitionConfig(
        backend="device", refine=RefineConfig(rounds=4)))
    eng, _ = _engine()
    for _ in range(4):
        eng.step()
    assert obs.totals() == {}
