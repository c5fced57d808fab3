"""Serving CLI: continuous-batching stream serving (default) or the
legacy one-shot batched decode.

    # stream: N mixed-length requests through the continuous-batching
    # engine with the placement-aware paged KV cache
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --stream --num-requests 16 --seed 0 [--trace serve_trace.json] \
        [--replace-every 16 --place-devices 4] [--machine tpu-mixed-32] \
        [--fault-plan "6:leaf_death:1"]

    # one-shot: the historical fixed-batch decode path
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --oneshot --batch 4 --prompt-len 16 --gen-len 32 \
        [--topology-aware] [--profile 2d]

The stream path is a thin front over ``repro.serving.ServingEngine``
(DESIGN.md §Serving): FIFO admission with page backpressure, one decode
step per token across every active stream, per-request sampling keys
derived from ``--seed`` (same outputs at any concurrency), and page ->
device re-placement through ``PlacementSession.map_pages`` when the
measured page traffic drifts. ``--trace`` dumps the full
:class:`ServeReport` (per-request lifecycle + placement epochs) as JSON.

Meshes still come from ``launch.placement.PlacementSession`` like every
other launcher; ``--topology-aware`` (one-shot path) probe-compiles a
decode step and rebuilds the mesh with the searched device order.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.placement import PlacementSession
from repro.launch.steps import rules_for


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for sampling (and the stream "
                         "workload) — decode output is deterministic "
                         "given a seed")
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--profile", default="2d",
                    help="lm sharding profile: 2d | fsdp | sp | expert")
    ap.add_argument("--machine", default=None,
                    help="machine-model preset (core.machine registry)")
    ap.add_argument("--map-restarts", type=int, default=32)
    # -- mode selection --
    ap.add_argument("--oneshot", action="store_true",
                    help="legacy fixed-batch decode instead of the "
                         "continuous-batching stream loop")
    ap.add_argument("--stream", action="store_true",
                    help="continuous-batching stream serving (default)")
    # -- one-shot knobs --
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--topology-aware", action="store_true",
                    help="search the logical->physical device order from "
                         "one probe-compiled decode step before serving "
                         "(one-shot path)")
    # -- stream knobs --
    ap.add_argument("--num-requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="max concurrent streams")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--n-pages", type=int, default=0,
                    help="KV pool pages (0 = sized from slots and "
                         "lengths)")
    ap.add_argument("--replace-every", type=int, default=16,
                    help="decode steps per page-placement epoch (0 = "
                         "placement off)")
    ap.add_argument("--drift-threshold", type=float, default=0.1)
    ap.add_argument("--place-devices", type=int, default=0,
                    help="placement bins (0 = machine/device count)")
    ap.add_argument("--static-batching", action="store_true",
                    help="admit only into an idle batch (the baseline "
                         "the bench compares against)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the ServeReport JSON (per-request "
                         "lifecycle + placement epochs)")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="inject faults into the stream loop: a JSON "
                         "file ({\"events\": [...]}) or inline "
                         "'step:kind:target[:factor]' items, comma-"
                         "separated — e.g. '6:leaf_death:1'. Survivor "
                         "outputs stay bit-identical to a clean run "
                         "(DESIGN.md §Fault-tolerance)")
    return ap


def _setup(args):
    arch = configs.get(args.arch)
    if arch.family != "lm":
        raise SystemExit("serve.py drives LM decode; use examples/"
                         "retrieval_serving.py for recsys")
    cfg = arch.smoke_config() if args.smoke else arch.make_config(
        "decode_32k")
    from repro.core import machine as machine_lib
    machine = machine_lib.resolve(args.machine)
    session = PlacementSession(map_restarts=args.map_restarts)
    if machine is not None:
        shape_m, axes_m = machine.mesh_spec()
        mesh = session.build_mesh(shape_m, axes_m)
    else:
        mesh = session.serving_mesh()
    rules = rules_for("lm", mesh.axis_names, profile=args.profile)
    from repro.models import transformer as tr
    params, _ = tr.init(jax.random.PRNGKey(0), cfg, rules)
    return cfg, machine, session, mesh, rules, params


def serve_stream(args) -> None:
    from repro.serving import EngineConfig, ServingEngine
    cfg, machine, session, mesh, rules, params = _setup(args)
    rng = np.random.default_rng(args.seed)
    max_prompt = max(args.prompt_len, 2)
    max_gen = max(args.gen_len, 2)
    # mixed prompt/gen lengths — the workload continuous batching exists
    # for
    prompts = [rng.integers(0, cfg.vocab,
                            int(rng.integers(2, max_prompt + 1)),
                            dtype=np.int64).astype(np.int32)
               for _ in range(args.num_requests)]
    gens = [int(rng.integers(1, max_gen + 1))
            for _ in range(args.num_requests)]
    longest = max(p.shape[0] + g for p, g in zip(prompts, gens))
    page = args.page_size
    max_pages = -(-longest // page)
    n_pages = args.n_pages or max_pages * max(args.slots, 2) * 2
    ecfg = EngineConfig(
        n_slots=args.slots, page_size=page, n_pages=n_pages,
        max_pages_per_req=max_pages, temperature=args.temperature,
        seed=args.seed, static_batching=args.static_batching,
        replace_every=args.replace_every,
        drift_threshold=args.drift_threshold,
        place_devices=args.place_devices, machine=args.machine)
    injector = None
    if args.fault_plan:
        from repro.resilience.faults import FaultInjector, parse_fault_plan
        injector = FaultInjector(parse_fault_plan(args.fault_plan))
    with mesh:
        engine = ServingEngine(params, cfg, rules, ecfg, session=session,
                               injector=injector)
        for p, g in zip(prompts, gens):
            engine.submit(p, g)
        report = engine.run()
    print(report.summary(), flush=True)
    for ev in report.placements:
        print(f"[SERVE]   placement step={ev['step']} "
              f"devices={ev['n_devices']} makespan={ev['makespan']:.3e} "
              f"drift={ev['drift_ratio']} replaced={ev['replaced']} "
              f"moved={ev['pages_moved']}", flush=True)
    for rec in report.recoveries:
        print(f"[SERVE]   recovery step={rec['step']} "
              f"device={rec['device']} pages_lost={rec['pages_lost']} "
              f"requeued={rec['requests_requeued']} "
              f"failed={rec['requests_failed']} n_alive={rec['n_alive']}",
              flush=True)
    if args.trace:
        with open(args.trace, "w") as f:
            f.write(report.to_json())
        print(f"[SERVE] wrote trace to {args.trace}", flush=True)


def serve_oneshot(args) -> None:
    cfg, machine, session, mesh, rules, params = _setup(args)
    from repro.models import transformer as tr
    n_dev = len(jax.devices())
    max_seq = args.prompt_len + args.gen_len
    key = jax.random.PRNGKey(args.seed)          # the --seed bugfix:
    key, tok_key = jax.random.split(key)         # sampling is pinned
    toks = jax.random.randint(tok_key, (args.batch, args.prompt_len), 0,
                              cfg.vocab)

    def decode_fn(p, c, t, pos):
        return tr.decode_step(p, c, t, pos, cfg, rules)

    decode = jax.jit(decode_fn)
    with mesh:
        cache, _ = tr.init_cache(cfg, args.batch, max_seq, rules)
    if args.topology_aware and n_dev > 1:
        probe = (params, cache, toks[:, :1], jnp.int32(0))
        mesh, rep = session.map_step(decode_fn, probe,
                                     mesh, [cfg.n_layers],
                                     tag="decode-step", machine=machine)
        print(rep.summary(), flush=True)
        with mesh:
            cache, _ = tr.init_cache(cfg, args.batch, max_seq, rules)
    with mesh:
        # prefill by stepping the decode cache (simple, exact)
        t0 = time.time()
        out = []
        tok = toks[:, :1]
        for pos in range(max_seq - 1):
            logits, cache = decode(params, cache, tok, jnp.int32(pos))
            if pos + 1 < args.prompt_len:
                tok = toks[:, pos + 1: pos + 2]
            else:
                key, sub = jax.random.split(key)
                if args.temperature <= 0:
                    nxt = jnp.argmax(logits, axis=-1)
                else:
                    nxt = jax.random.categorical(
                        sub, logits / args.temperature, axis=-1)
                tok = nxt[:, None]
                out.append(np.asarray(tok))
        dt = time.time() - t0
    gen = np.concatenate(out, axis=1)
    tput = args.batch * gen.shape[1] / dt
    print(f"generated {gen.shape} tokens in {dt:.2f}s "
          f"({tput:.1f} tok/s); sample row: {gen[0][:16].tolist()}")


def main() -> None:
    args = _parser().parse_args()
    if args.oneshot and args.stream:
        raise SystemExit("--oneshot and --stream are exclusive")
    if args.oneshot:
        serve_oneshot(args)
    else:
        serve_stream(args)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
