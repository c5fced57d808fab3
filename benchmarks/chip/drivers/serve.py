"""Driver of the serving cells: an open-loop stream of requests through
the program's ``ServingEngine`` (scheduler, paged KV cache, jitted paged
decode step), at a rate fixed in the traffic file.

Set-up makes the weights from the seed (``lm_reference.make_params``),
builds the engine, and starts the arrivals ``warm_s`` seconds before the
window, so the window opens on a full batch; the first engine step
compiles, or loads from the cache, the one decode program and the sampler.
The window then goes on serving; it ends with the first engine step to
finish after ``seconds``. Sampling is greedy, so that the check can hold
every served token to the reference.

Each request is timed from when it was due. ``itl_p95_ms`` is over every
gap between two consecutive output tokens of a request whose later token
came in the window. ``ttft_p95_ms`` is over every request due in the
window: once the window has closed, the engine serves on (arrivals too)
until each of them has its first token, ``drain_s`` seconds at the most;
one still waiting then counts with the wait it has had. A token's time is
the end of the engine step that sampled it.

The check takes a sample of the requests the window finished, drawn from
the seed with the longest among them, and holds each served token to the
float32 reference (``lm_reference.served_gaps``): the widest gap by which
a served token's reference logit lies below the reference's best. The
control puts, at each of those positions, the token that the float8
forward puts first in the served token's place.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List, Optional

import lm_reference
import numpy as np
import traffic as traffic_gen
from harness import Check, Window

SPAN_STEP = "bench.step"


@dataclasses.dataclass
class Tracked:
    req: Any                       # the program's Request
    due: float                     # schedule seconds
    times: List[float] = dataclasses.field(default_factory=list)
    pos_seen: int = 0


@dataclasses.dataclass
class State:
    cell: Any
    seed: int
    params: Any
    engine: Any
    mesh: Any
    schedule: List[traffic_gen.Request]
    t_zero: float                  # perf_counter of schedule time 0
    next_i: int = 0
    inflight: List[Tracked] = dataclasses.field(default_factory=list)
    tracked: List[Tracked] = dataclasses.field(default_factory=list)
    tokens: int = 0                # tokens processed, for the utilization
    contexts: int = 0              # sum of their context lengths
    steps: int = 0                 # engine steps in the window
    window_lo: float = 0.0
    window_hi: float = 0.0
    sample: Optional[List[Any]] = None     # requests the check compares
    compared: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)


def program_config(cfg: Dict[str, Any], name: str):
    """The program's model config for a Hugging Face style ``cfg``;
    refuses what the program cannot run as the config states."""
    import jax.numpy as jnp

    from repro.models.transformer import TransformerConfig
    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6")
    if cfg["hidden_act"] != "silu" or cfg.get("use_sliding_window"):
        raise ValueError("the program runs SiLU MLPs and full attention")
    return TransformerConfig(
        name=name, n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=bool(cfg["qkv_bias"]), rope_theta=float(cfg["rope_theta"]),
        dtype={"bfloat16": jnp.bfloat16}[cfg["torch_dtype"]], remat=False)


def setup(cell, seed: int, seconds: float, devices, *, log) -> State:
    import jax
    from jax.sharding import Mesh

    from repro.launch.steps import rules_for
    from repro.serving import EngineConfig, ServingEngine
    cfg, mix = cell.config, cell.traffic
    pcfg = program_config(cfg, cell.config_name)
    mesh = Mesh(np.asarray(devices).reshape(len(devices)), ("data",))
    rules = rules_for("lm", mesh.axis_names, profile="2d")
    t = time.perf_counter()
    params = lm_reference.make_params(cfg, seed)
    jax.block_until_ready(params)
    log(f"[serve] weights {time.perf_counter() - t:.3f} s")
    slots, page = int(mix["slots"]), int(mix["page_size"])
    per_req = int(mix["pages_per_slot"])
    ecfg = EngineConfig(n_slots=slots, page_size=page,
                        n_pages=slots * per_req, max_pages_per_req=per_req,
                        temperature=0.0, seed=seed & 0x7FFFFFFF,
                        replace_every=int(mix["replace_every"]))
    with mesh:
        engine = ServingEngine(params, pcfg, rules, ecfg)
    phases = (float(mix["warm_s"]), float(seconds),
              float(mix["drain_s"]) + 5.0)
    schedule = traffic_gen.open_loop(mix, seed, phases, cfg["vocab_size"])
    st = State(cell=cell, seed=seed, params=params, engine=engine, mesh=mesh,
               schedule=schedule, t_zero=time.perf_counter())
    _serve_until(st, float(mix["warm_s"]), count=False)
    log(f"[serve] warm: {len(st.tracked)} requests arrived, "
        f"{len(st.engine.scheduler.active)} active, "
        f"{len(st.engine.scheduler.queue)} queued")
    return st


def _now(st: State) -> float:
    return time.perf_counter() - st.t_zero


def _serve_until(st: State, until: float, *, count: bool,
                 done=lambda: False) -> None:
    """Admit arrivals as they fall due and step the engine until the
    schedule clock passes ``until`` or ``done()`` holds (ending on a step
    boundary)."""
    eng = st.engine
    with st.mesh:
        _loop(st, eng, until, count, done)


def _loop(st: State, eng, until: float, count: bool, done) -> None:
    import jax
    while True:
        now = _now(st)
        while st.next_i < len(st.schedule) \
                and st.schedule[st.next_i].due_s <= now:
            r = st.schedule[st.next_i]
            tr = Tracked(eng.submit(r.prompt, r.max_new_tokens), r.due_s)
            st.tracked.append(tr)
            st.inflight.append(tr)
            st.next_i += 1
        if now >= until or done():
            return
        if not eng.scheduler.has_work():
            nxt = (st.schedule[st.next_i].due_s
                   if st.next_i < len(st.schedule) else until)
            time.sleep(max(0.0, min(nxt, until) - now))
            continue
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            eng.step()
        t = _now(st)
        st.steps += count
        keep = []
        for tr in st.inflight:
            req = tr.req
            if count and req.pos > tr.pos_seen:
                # contexts of the tokens this step processed
                st.tokens += req.pos - tr.pos_seen
                st.contexts += sum(range(tr.pos_seen + 1, req.pos + 1))
            tr.pos_seen = req.pos
            while len(tr.times) < len(req.generated):
                tr.times.append(t)
            if req.done_step < 0 and not req.failed:
                keep.append(tr)
        st.inflight = keep


def window(st: State, seconds: float) -> Window:
    st.window_lo = _now(st)
    st.tokens = st.contexts = st.steps = 0
    queued0 = len(st.engine.scheduler.queue)
    _serve_until(st, st.window_lo + seconds, count=True)
    lo = st.window_lo
    hi = st.window_hi = _now(st)
    queued1 = len(st.engine.scheduler.queue)
    due = _due(st)
    itl = [b - a for tr in st.tracked for a, b in zip(tr.times, tr.times[1:])
           if lo <= b <= hi]
    metrics = {"itl_p95_ms": 1e3 * float(np.percentile(itl, 95))}
    return Window(metrics=metrics, attempted=len(due), failed=0,
                  seconds=hi - lo,
                  context={"steps": st.steps, "tokens": st.tokens,
                           "contexts": st.contexts,
                           "itl_n": len(itl), "queued": (queued0, queued1),
                           "first_tokens": sum(1 for tr in due if tr.times)})


def _due(st: State) -> List[Tracked]:
    return [tr for tr in st.tracked
            if st.window_lo <= tr.due < st.window_hi]


def finish(st: State, win: Window) -> None:
    """Serve on until every request due in the window has its first
    token (``drain_s`` at the most), then take ``ttft_p95_ms``."""
    due = _due(st)
    limit = st.window_hi + float(st.cell.traffic["drain_s"])
    _serve_until(st, limit, count=False,
                 done=lambda: all(tr.times for tr in due))
    end = _now(st)
    ttft = [(tr.times[0] if tr.times else end) - tr.due for tr in due]
    win.metrics["ttft_p95_ms"] = 1e3 * float(np.percentile(ttft, 95))
    win.failed = sum(1 for tr in due if tr.req.failed)
    win.context["drain_s"] = end - st.window_hi
    win.context["waiting"] = sum(1 for tr in due if not tr.times)


def _sample(st: State) -> List[Any]:
    """Requests the window finished, to check: the longest, then others
    drawn from the seed, until ``check_requests`` requests and
    ``check_tokens`` served tokens are covered."""
    done = [tr.req for tr in st.tracked if tr.req.done_step >= 0
            and st.window_lo <= tr.times[-1] <= st.window_hi]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.prompt_len + len(r.generated)), r.rid))
    rng = np.random.default_rng(st.seed)
    order = [done[0]] + [done[i] for i in 1 + rng.permutation(len(done) - 1)]
    mix = st.cell.traffic
    want, least = int(mix["check_tokens"]), int(mix["check_requests"])
    out, n = [], 0
    for r in order:
        out.append(r)
        n += len(r.generated)
        if n >= want and len(out) >= least:
            break
    return out


def free_program_state(st: State) -> None:
    st.engine.cache.k_pool = st.engine.cache.v_pool = None
    st.engine = None
    gc.collect()


def _take_sample(st: State) -> List[Any]:
    """The requests to compare, drawn once; the program's state is freed
    then, so that the reference has the chip's memory."""
    if st.sample is None:
        st.sample = _sample(st)
        free_program_state(st)
    return st.sample


def _length(st: State) -> int:
    mix = st.cell.traffic
    return int(mix["pages_per_slot"]) * int(mix["page_size"])


def check(st: State, win: Window, *, log) -> List[Check]:
    reqs = _take_sample(st)
    log(f"[serve] window {win.seconds:.3f} s: {win.attempted} requests due, "
        f"{win.context['first_tokens']} with a first token at the close, "
        f"{win.context.get('waiting', 0)} still waiting after a drain of "
        f"{win.context.get('drain_s', 0.0):.3f} s, "
        f"{win.context['itl_n']} token gaps, {win.context['steps']} steps, "
        f"queue {win.context['queued'][0]} -> {win.context['queued'][1]}; "
        f"checking {len(reqs)} finished "
        f"requests, longest {reqs[0].prompt_len if reqs else 0}+"
        f"{len(reqs[0].generated) if reqs else 0} tokens")
    limit = float(st.cell.limits["logit_gap"])
    if not reqs:
        return [Check("logit_gap", float("inf"), limit)]
    g = np.concatenate([lm_reference.served_gaps(
        st.params, st.cell.config, r.prompt, r.generated, _length(st),
        st.compared.get(r.rid)) for r in reqs])
    log(f"[serve] {len(g)} served tokens compared")
    return [Check("logit_gap", float(g.max()), limit)]


def trace_context(st: State, win: Window) -> Dict[str, Any]:
    return {"model": st.cell.config}


def control(st: State) -> None:
    """Put the control's answer in the program's place: at each position
    of the requests the check compares, the token that the float8
    forward puts first."""
    st.compared = {r.rid: lm_reference.control_tokens(
        st.params, st.cell.config, r.prompt, r.generated, _length(st))
        for r in _take_sample(st)}
