"""Fault tolerance (ISSUE 8): deterministic fault plans, machine
degradation, page retirement, requeue/backoff recovery, chaos-stream
replay determinism, supervised training restarts, and checkpoint
tmp-dir hygiene (DESIGN.md §Fault-tolerance)."""
import json

import numpy as np
import pytest

from repro.core import machine as machine_lib
from repro.core.initial import initial_partition
from repro.graph.graph import from_edges
from repro.resilience import (ChaosHarness, DeviceFailure, FaultEvent,
                              FaultInjector, FaultPlan, parse_fault_plan,
                              run_chaos)
from repro.serving import PagedKVCache, Request, Scheduler
from repro.serving.kv_cache import PageAllocator


# ---------------------------------------------------------------------------
# Fault plans + injector
# ---------------------------------------------------------------------------

def test_fault_plan_sorted_and_indexed():
    plan = FaultPlan((FaultEvent(9, "leaf_death", 2),
                      FaultEvent(3, "straggler", 1, 0.5),
                      FaultEvent(3, "link_degrade", "dcn", 0.5)))
    assert [e.step for e in plan.events] == [3, 3, 9]
    assert len(plan.at(3)) == 2 and len(plan.at(4)) == 0
    assert plan.deaths() == (2,)


def test_fault_event_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultEvent(0, "meteor", 0)
    with pytest.raises(ValueError, match="factor"):
        FaultEvent(0, "straggler", 0, 1.5)
    with pytest.raises(ValueError, match="tree level by name"):
        FaultEvent(0, "link_degrade", 3)
    with pytest.raises(ValueError, match="step"):
        FaultEvent(-1, "leaf_death", 0)


def test_random_plan_deterministic_and_never_kills_all():
    p1 = FaultPlan.random(7, 50, 4, n_deaths=3)
    p2 = FaultPlan.random(7, 50, 4, n_deaths=3)
    assert p1.events == p2.events
    assert len(set(p1.deaths())) == 3 < 4
    with pytest.raises(ValueError, match="kill"):
        FaultPlan.random(0, 50, 4, n_deaths=4)


def test_parse_inline_and_json_round_trip(tmp_path):
    plan = parse_fault_plan("6:leaf_death:1,2:link_degrade:dcn:0.5")
    assert plan.events[0].kind == "link_degrade"
    assert plan.events[1].target == 1
    path = tmp_path / "plan.json"
    path.write_text(plan.to_json())
    again = parse_fault_plan(str(path))
    assert again.events == plan.events
    raw = json.loads(plan.to_json())
    assert {e["kind"] for e in raw["events"]} == {"leaf_death",
                                                 "link_degrade"}


def test_injector_fires_each_event_exactly_once():
    plan = FaultPlan((FaultEvent(2, "leaf_death", 0),
                      FaultEvent(5, "straggler", 1, 0.5)))
    inj = FaultInjector(plan)
    assert inj.fire(1) == []
    assert [e.step for e in inj.fire(3)] == [2]      # catches up past 2
    # a supervisor restart rewinds the step counter: the fired death
    # must NOT replay, or recovery would loop forever
    assert inj.fire(0) == []
    assert inj.fire(2) == []
    assert not inj.exhausted
    assert [e.kind for e in inj.fire(9)] == ["straggler"]
    assert inj.exhausted
    assert len(inj.history()) == 2


# ---------------------------------------------------------------------------
# MachineSpec.degrade
# ---------------------------------------------------------------------------

def test_degrade_masks_leaves_and_renormalizes():
    spec = machine_lib.resolve("tpu-mixed-32")
    deg = spec.degrade([FaultEvent(0, "leaf_death", 3)])
    assert deg.n_alive == spec.n_devices - 1
    topo = deg.topology()
    assert len(topo.compute_bins) == deg.n_alive
    speed = np.asarray(topo.bin_speed)
    assert (speed > 0).all() and speed.max() == pytest.approx(1.0)
    # degradation is cumulative and idempotent per leaf
    deg2 = deg.degrade([FaultEvent(1, "leaf_death", 3),
                        FaultEvent(1, "leaf_death", 7)])
    assert deg2.n_alive == spec.n_devices - 2
    assert 3 in deg2.dead_leaves and 7 in deg2.dead_leaves


def test_degrade_invalidates_placement_cache_token():
    spec = machine_lib.resolve("tpu_v5e-256")
    deg = spec.degrade([FaultEvent(0, "leaf_death", 0)])
    assert deg.cache_token() != spec.cache_token()
    slow = spec.degrade([FaultEvent(0, "link_degrade", "dcn", 0.5)])
    assert slow.cache_token() != spec.cache_token()
    assert slow.cache_token() != deg.cache_token()


def test_degrade_link_repricing_cumulative():
    spec = machine_lib.resolve("tpu_v5e-256")
    base = spec.tree()
    half = spec.degrade([FaultEvent(0, "link_degrade", "dcn", 0.5)])
    quarter = half.degrade([FaultEvent(1, "link_degrade", "dcn", 0.5)])
    # dcn is level 0; halving its bandwidth doubles its per-byte cost
    assert half.tree().F_l[0] == pytest.approx(2 * base.F_l[0])
    assert quarter.tree().F_l[0] == pytest.approx(4 * base.F_l[0])
    # repricing one level never cheapens another
    assert half.tree().F_l[-1] == pytest.approx(base.F_l[-1])


def test_degrade_refuses_to_kill_everything():
    spec = machine_lib.resolve("torus-2d")
    with pytest.raises(ValueError, match="torus"):
        spec.degrade([FaultEvent(0, "leaf_death", 0)])
    small = machine_lib.MachineSpec(
        name="pair", levels=(machine_lib.Level("link", 2, 100.0),),
        mesh_shape=(2,), axes=("data",))
    with pytest.raises(ValueError):
        small.degrade([FaultEvent(0, "leaf_death", 0),
                       FaultEvent(0, "leaf_death", 1)])


def test_zero_capacity_bin_never_reaches_partitioner():
    """Dead leaves must be MASKED, not zero-speed: the partitioner and
    the page mapper both refuse a zero-capacity bin outright."""
    topo = machine_lib.resolve("tpu-mixed-32").degrade(
        [FaultEvent(0, "leaf_death", 0)]).topology()
    g = from_edges(8, np.array([0, 1, 2]), np.array([1, 2, 3]))
    part = initial_partition(g, topo)                # masked topo: fine
    assert part.max() < len(topo.compute_bins)
    import dataclasses as dc
    bad = dc.replace(topo, bin_speed=np.asarray(topo.bin_speed).copy())
    bad.bin_speed[0] = 0.0
    with pytest.raises(ValueError, match="zero-capacity"):
        initial_partition(g, bad)


# ---------------------------------------------------------------------------
# Page retirement + scheduler recovery
# ---------------------------------------------------------------------------

def test_allocator_retire_accounting():
    al = PageAllocator(8)
    held = al.alloc(3)
    with pytest.raises(ValueError, match="release its slot"):
        al.retire(held[:1])
    al.retire([6, 7])
    assert al.n_usable == 6 and al.n_dead == 2
    with pytest.raises(ValueError, match="already retired"):
        al.retire([6])
    # retired pages never come back through alloc
    al.free(held)
    got = al.alloc(al.n_free)
    assert not set(got) & {6, 7}
    assert al.n_free == 0


def test_cache_fail_pages_zeroes_traffic():
    cache = PagedKVCache(n_pages=8, page_size=2, n_slots=2,
                         max_pages_per_req=4)
    cache.assign_slot(0, 8)
    cache.record_access({0: 8})
    assert cache.traffic.sum() > 0
    cache.release_slot(0)
    dead = [0, 1]
    cache.fail_pages(dead)
    assert cache.traffic[dead, :].sum() == 0
    assert cache.traffic[:, dead].sum() == 0
    assert cache.access_count[dead].sum() == 0
    cache.check_invariants()


def test_submit_rejects_infeasible_on_degraded_pool():
    cache = PagedKVCache(n_pages=8, page_size=2, n_slots=2,
                         max_pages_per_req=8)
    cache.fail_pages(list(range(5)))                 # 3 usable pages
    sched = Scheduler(cache)
    with pytest.raises(ValueError, match="never be admitted"):
        sched.submit(Request(rid=0, prompt=np.zeros(6, np.int32),
                             max_new_tokens=2))      # needs 4 pages


def test_admit_fails_infeasible_head_instead_of_blocking():
    """A queued request the shrunken pool can never fit must FAIL at
    admission — never head-block the feasible requests behind it."""
    cache = PagedKVCache(n_pages=8, page_size=2, n_slots=2,
                         max_pages_per_req=8)
    sched = Scheduler(cache)
    sched.submit(Request(rid=0, prompt=np.zeros(6, np.int32),
                         max_new_tokens=2), step=0)  # needs 4 pages
    sched.submit(Request(rid=1, prompt=np.zeros(2, np.int32),
                         max_new_tokens=2), step=0)  # needs 2 pages
    cache.fail_pages([0, 1, 2, 3, 4])                # 3 usable left
    admitted = sched.admit(step=1)
    assert [r.rid for r in admitted] == [1]
    assert [r.rid for r in sched.failed] == [0]
    assert "infeasible after degrade" in sched.failed[0].fail_reason


def test_handle_leaf_death_requeues_with_backoff_then_fails():
    cache = PagedKVCache(n_pages=8, page_size=2, n_slots=2,
                         max_pages_per_req=4)
    sched = Scheduler(cache)
    sched.submit(Request(rid=0, prompt=np.zeros(2, np.int32),
                         max_new_tokens=2), step=0)
    sched.admit(0)
    victim_page = cache.slot_pages[0][0]
    out = sched.handle_leaf_death([victim_page], step=3, max_retries=2)
    req = out["requeued"][0]
    assert req.retries == 1 and req.replay_gen == 0
    assert req.not_before == 3 + 2                   # backoff_base * 2^0
    assert cache.allocator.n_dead == 1
    # exhaust the retry budget: next death on its pages is terminal
    sched.admit(req.not_before)
    req.retries = 2
    page = cache.slot_pages[req.slot][0]
    out = sched.handle_leaf_death([page], step=9, max_retries=2)
    assert out["requeued"] == [] and out["failed"] == [req]
    assert "retries exhausted" in req.fail_reason


# ---------------------------------------------------------------------------
# Chaos harness: replay determinism without JAX
# ---------------------------------------------------------------------------

def test_chaos_harness_matches_clean_run():
    plan = FaultPlan((FaultEvent(4, "leaf_death", 1),))
    clean = run_chaos(8, seed=0)
    chaos = run_chaos(8, seed=0, plan=plan)
    assert not chaos.failed
    assert chaos.retried >= 1
    assert chaos.completed == clean.completed        # bit-identical
    assert chaos.recoveries[0]["n_alive"] == 3


def test_chaos_harness_seeded_sweep():
    """The manual stand-in for the Hypothesis property (hypothesis is an
    optional dependency): random plans x random streams, survivors always
    bit-identical, every request DONE or FAILED, pool never leaks."""
    for seed in range(25):
        plan = FaultPlan.random(seed, 40, 4, n_deaths=2)
        clean = run_chaos(6, seed=seed, n_pages=24)
        h = ChaosHarness(n_pages=24, plan=plan)
        rng = np.random.default_rng(seed)
        for rid in range(6):
            h.submit(rid, int(rng.integers(2, 9)), int(rng.integers(1, 9)))
        chaos = h.run()
        for rid, toks in chaos.completed.items():
            assert toks == clean.completed[rid], (seed, rid)
        assert len(chaos.completed) + len(chaos.failed) == 6
        alloc = h.scheduler.cache.allocator
        assert alloc.n_free + alloc.n_dead == alloc.n_pages


def test_chaos_unaffected_requests_keep_ttft():
    """Requests whose whole lifecycle precedes the death are untouched:
    identical TTFT and completion step as the clean run."""
    plan = FaultPlan((FaultEvent(30, "leaf_death", 0),))
    h = ChaosHarness(plan=plan)
    rng = np.random.default_rng(2)
    for rid in range(8):
        h.submit(rid, int(rng.integers(2, 9)), int(rng.integers(1, 9)))
    h.run()
    clean_h = ChaosHarness()
    rng = np.random.default_rng(2)
    for rid in range(8):
        clean_h.submit(rid, int(rng.integers(2, 9)),
                       int(rng.integers(1, 9)))
    clean_h.run()
    cdone = {r.rid: r for r in clean_h.scheduler.completed}
    for r in h.scheduler.completed:
        if r.retries == 0 and r.done_step < 30:
            assert r.first_token_step == cdone[r.rid].first_token_step
            assert r.done_step == cdone[r.rid].done_step


# ---------------------------------------------------------------------------
# Training: supervised restart + checkpoint hygiene
# ---------------------------------------------------------------------------

def _toy_step(params, opt_state, batch):
    g = float(batch["x"].mean())
    params = {"w": params["w"] - 0.1 * g}
    return params, opt_state, {"loss": float(params["w"].sum()) ** 2}


def _toy_factory(start):
    def gen():
        i = start
        while True:
            yield {"x": np.full((4,), float(i + 1), np.float32)}
            i += 1
    return gen()


def test_supervised_restart_preserves_loss_trajectory(tmp_path):
    """THE training acceptance check: a leaf death mid-run, restored from
    the newest checkpoint onto the degraded machine, reproduces the
    uninterrupted loss trajectory exactly."""
    import jax.numpy as jnp
    from repro.train import loop
    params0 = {"w": jnp.ones((3,))}
    cfg = loop.LoopConfig(total_steps=12, ckpt_every=4, log_every=100)
    _, _, clean = loop.run(_toy_step, dict(params0), None,
                           _toy_factory(0), cfg)
    ccfg = loop.LoopConfig(total_steps=12, ckpt_every=4,
                           ckpt_dir=str(tmp_path), log_every=100)
    plan = FaultPlan((FaultEvent(7, "leaf_death", 1),))
    p, _, sup = loop.run_supervised(_toy_step, dict(params0), None,
                                    _toy_factory, ccfg, plan,
                                    machine="tpu_v5e-256")
    assert sup.attempts == 2
    assert sup.recoveries[0]["resumed_from"] == 4
    assert sup.machine.n_alive == 255
    np.testing.assert_allclose(sup.losses, clean.losses, rtol=1e-6)
    assert sup.steps_run == 12


def test_supervised_restart_budget_exhausts(tmp_path):
    from repro.train import loop
    import jax.numpy as jnp
    cfg = loop.LoopConfig(total_steps=10, ckpt_every=4,
                          ckpt_dir=str(tmp_path), log_every=100)
    plan = FaultPlan((FaultEvent(2, "leaf_death", 0),
                      FaultEvent(5, "leaf_death", 1)))
    with pytest.raises(DeviceFailure):
        loop.run_supervised(_toy_step, {"w": jnp.ones((3,))}, None,
                            _toy_factory, cfg, plan, max_restarts=1)


def test_device_failure_carries_partial_trajectory():
    from repro.train import loop
    import jax.numpy as jnp
    cfg = loop.LoopConfig(total_steps=10, log_every=100)
    inj = FaultInjector(FaultPlan((FaultEvent(6, "leaf_death", 0),)))
    with pytest.raises(DeviceFailure) as exc_info:
        loop.run(_toy_step, {"w": jnp.ones((3,))}, None,
                 _toy_factory(0), cfg, injector=inj)
    assert len(exc_info.value.losses) == 6
    assert exc_info.value.start_step == 0
    assert exc_info.value.event.target == 0


def test_latest_step_skips_and_sweeps_tmp_dirs(tmp_path):
    """A crash mid-async-save leaves .tmp_<step> behind: it must never be
    counted as a checkpoint, and gc_tmp sweeps it on the restore path."""
    from repro.ckpt import checkpoint as ckpt
    ckpt.save(str(tmp_path), 4, {"w": np.ones(3)})
    orphan = tmp_path / ".tmp_8"
    orphan.mkdir()
    (orphan / "leaf_00000.npy").write_bytes(b"garbage")
    assert ckpt.latest_step(str(tmp_path)) == 4
    assert orphan.exists()                           # plain scan: kept
    assert ckpt.latest_step(str(tmp_path), gc_tmp=True) == 4
    assert not orphan.exists()                       # restore path: swept
    restored, step = ckpt.restore(str(tmp_path), {"w": np.zeros(3)})
    assert step == 4 and restored["w"].sum() == 3


@pytest.mark.parametrize("replace_every", [0, 8])
def test_engine_chaos_matches_clean_run(replace_every):
    """End-to-end serving acceptance: a real engine stream with one
    injected leaf death, with page placement off or on, completes every
    request with survivor tokens bit-identical to the clean run, reports
    the recovery, and takes no more extra steps than the replayed tokens
    plus backoff and admission refill."""
    import jax
    from repro import configs
    from repro.dist.sharding import lm_rules
    from repro.models import transformer as tr
    from repro.serving import EngineConfig, ServingEngine
    rules = lm_rules(())
    cfg = configs.get("qwen2-1.5b").smoke_config()
    params, _ = tr.init(jax.random.PRNGKey(0), cfg, rules)
    rng = np.random.default_rng(0)
    work = [(rng.integers(0, cfg.vocab, int(rng.integers(2, 7)),
                          dtype=np.int64).astype(np.int32),
             int(rng.integers(1, 5))) for _ in range(5)]

    def serve(injector=None):
        eng = ServingEngine(
            params, cfg, rules,
            EngineConfig(n_slots=2, page_size=4, n_pages=16,
                         max_pages_per_req=4, temperature=0.8, seed=0,
                         replace_every=replace_every, place_devices=4),
            injector=injector)
        for prompt, gen in work:
            eng.submit(prompt, gen)
        return eng.run()

    clean = serve()
    assert bool(clean.placements) == (replace_every > 0)
    plan = FaultPlan((FaultEvent(4, "leaf_death", 1),))
    chaos = serve(FaultInjector(plan))
    assert not chaos.failed
    gen_clean = {r["rid"]: r["generated"] for r in clean.requests}
    gen_chaos = {r["rid"]: r["generated"] for r in chaos.requests}
    assert gen_chaos == gen_clean
    assert chaos.recoveries and chaos.recoveries[0]["device"] == 1
    assert chaos.faults[0]["kind"] == "leaf_death"
    assert chaos.tokens_reprefilled >= 0
    assert chaos.steps <= (clean.steps + chaos.tokens_reprefilled
                           + 8 * chaos.requests_retried + 8)
    assert "faults:" in chaos.summary()
