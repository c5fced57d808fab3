"""Training launcher: ``--arch`` selects the architecture, the mesh adapts
to whatever devices exist (1 CPU for smoke, 256/512 in production), and the
fault-tolerant loop does checkpoint/restart.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --smoke --steps 100 --ckpt-dir /tmp/ckpt

``--smoke`` runs the reduced config on local devices; without it the full
config is used (requires real accelerators). ``--profile`` picks the LM
sharding profile (2d | fsdp | sp | expert) from the DESIGN.md
§Sharding-profiles table.

``--topology-aware`` closes the partitioner loop at launch (DESIGN.md §6):
all meshes come from ``launch.placement.PlacementSession`` — the jitted
step is compiled once on the identity mesh, the compiled module's
collectives become a device-pair traffic matrix, and the session's mapping
search over the machine tree picks the logical -> physical device order
the final mesh is built with. With one local device this is a no-op.

``--grad-compress`` routes gradients through the int8 error-feedback round
trip (``--grad-compress-block N`` switches to one scale per N-element
block); the residual state is owned by the train loop (threaded per step,
checkpointed, restored on resume).

``--fault-plan "7:leaf_death:1"`` (with ``--ckpt-dir``) injects a device
failure and runs under ``loop.run_supervised``: the machine model is
degraded, the newest checkpoint is restored onto the survivors, and the
stitched loss trajectory stays continuous (DESIGN.md §Fault-tolerance).

``--embed-shard`` (recsys only) turns on the ``repro.embed`` subsystem
(DESIGN.md §Embedding): probe batches build the row co-access graph, the
makespan partitioner shards the item table capacity-proportionally over
the ``--embed-machine`` model (a modeling choice — it need not match the
local device count), the table is permuted device-contiguous and the
loop steps with touched-rows-only rowwise Adagad (mutually exclusive
with ``--grad-compress``). ``--embed-cache-rows N`` reports the measured
hot-row-cache traffic vs the replicated baseline; ``--prefetch D`` wraps
the batch stream in the async double-buffered sampler.
"""
from __future__ import annotations

import argparse
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import pipeline
from repro.launch.steps import rules_for
from repro.optim import adamw
from repro.train import loop
from repro.train.steps import make_train_step


def make_batches(arch, cfg, batch: int, seq: int):
    if arch.family == "lm":
        gen = pipeline.lm_batches(cfg.vocab, batch, seq)
    elif arch.family == "recsys":
        gen = pipeline.recsys_batches(cfg.n_items, cfg.n_cats, batch,
                                      cfg.hist_len, cfg.d_dense)
    else:
        def gnn_gen():
            b = arch.smoke_batch()
            while True:
                yield b
        gen = gnn_gen()
    for b in gen:
        yield {k: jnp.asarray(v) for k, v in b.items()}


def probe_embed_stats(cfg, n_rows: int, batch: int, n_batches: int):
    """Replay the training pipeline's first batches (same seed) into a
    row co-access measurement for the table partitioner."""
    from repro import embed
    stats = embed.RowAccessStats(n_rows)
    gen = pipeline.recsys_batches(cfg.n_items, cfg.n_cats, batch,
                                  cfg.hist_len, cfg.d_dense)
    for b in itertools.islice(gen, n_batches):
        stats.record(b["user_hist"])
        stats.record(b["item_id"])
    return stats


def embed_traffic_report(stats, plan, table, cfg, batch: int,
                         cache_rows: int, n_batches: int):
    """Drive the hot-row cache over the probe stream; returns the cache
    (measured [D, D] traffic inside) and the replicated baseline matrix."""
    from repro import embed
    st = embed.ShardedEmbeddingTable(table, plan, permuted=True)
    cache = embed.HotRowCache(st, n_cache=cache_rows, policy="lru")
    if cache_rows:
        cache.warm(stats.top_rows(cache_rows))
    rep = np.zeros((plan.n_devices, plan.n_devices))
    gen = pipeline.recsys_batches(cfg.n_items, cfg.n_cats, batch,
                                  cfg.hist_len, cfg.d_dense)
    for b in itertools.islice(gen, n_batches):
        hist = np.asarray(b["user_hist"])
        req_row = embed.requester_of(hist.shape[0], plan.n_devices)
        valid = hist >= 0
        ids = hist[valid]
        req = np.broadcast_to(req_row[:, None], hist.shape)[valid]
        cache.lookup(ids, req)
        rep += embed.replicated_update_traffic(ids, req, plan.n_devices,
                                               st.row_bytes)
    cache.check_invariants()
    return cache, rep


def searched_mesh(step, step_args, mesh, scan_lengths, map_restarts=32,
                  session=None, machine=None):
    """Thin wrapper over ``PlacementSession.map_step``: compile once on
    ``mesh``, search the logical->physical mapping over the machine model
    (``machine`` preset, else the tree guessed from the mesh shape), and
    return (mapped mesh, PlacementReport). The session owns the whole
    compile -> traffic -> search -> mesh loop (DESIGN.md §6)."""
    from repro.launch.placement import PlacementSession
    session = session or PlacementSession(map_restarts=map_restarts)
    return session.map_step(step, step_args, mesh, scan_lengths,
                            tag="train-step", machine=machine)


def _lint_gate(arch_name: str, profile: str, session) -> None:
    """``--lint``: kernel registry + this cell's sharding specs, plus any
    traffic matrices the session has already measured; errors abort."""
    from repro import analysis
    from repro.analysis import shard_lint
    findings = session.verify()
    findings.extend(shard_lint.lint_cell(arch_name, profile=profile))
    print(analysis.format_findings(findings), flush=True)
    errors = analysis.at_least(findings, "error")
    if errors:
        raise SystemExit(f"--lint: {len(errors)} error-severity "
                         "finding(s)")


def _onto(mesh, tree):
    """Re-place every array of ``tree`` on ``mesh`` under its current
    PartitionSpec (after the topology-aware search reorders the devices)."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(
            mesh, getattr(x.sharding, "spec", PartitionSpec()))), tree)


def main(argv=None):
    """Parse ``argv`` (default: the command line), train, and return the
    loop's result (``LoopResult``, or ``SupervisedResult`` under
    ``--fault-plan``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--profile", default="2d")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--grad-compress-block", type=int, default=0,
                    help="per-block compression scale size (power of two; "
                         "implies --grad-compress; 0 = one scale per "
                         "tensor)")
    ap.add_argument("--topology-aware", action="store_true")
    ap.add_argument("--lint", action="store_true",
                    help="before training, static-verify the Pallas kernel "
                         "registry and this arch/profile's sharding specs "
                         "(repro.analysis); error findings abort the run")
    ap.add_argument("--map-restarts", type=int, default=32,
                    help="random restarts appended to the mapping search")
    ap.add_argument("--machine", default=None,
                    help="machine-model preset (core.machine registry); "
                         "builds the preset's mesh — the local device "
                         "count must cover it — and scores the mapping "
                         "search against its topology")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="inject device failures: a JSON file or inline "
                         "'step:kind:target[:factor]' items, e.g. "
                         "'7:leaf_death:1'. Runs under the restart "
                         "supervisor: on a death the machine is degraded, "
                         "the newest checkpoint restored onto the "
                         "survivors, and training resumes (DESIGN.md "
                         "§Fault-tolerance). Requires --ckpt-dir for "
                         "loss-trajectory continuity")
    ap.add_argument("--max-restarts", type=int, default=4,
                    help="supervisor restart budget before the injected "
                         "failure propagates")
    ap.add_argument("--embed-shard", action="store_true",
                    help="recsys only: partition the item table by the "
                         "measured row co-access graph (repro.embed), "
                         "permute it device-contiguous, and train with "
                         "touched-rows-only sparse table updates")
    ap.add_argument("--embed-cache-rows", type=int, default=0,
                    help="with --embed-shard: hot-row cache slots for the "
                         "lookup-traffic report (0 = no cache)")
    ap.add_argument("--embed-probe-batches", type=int, default=4,
                    help="batches probed to build the co-access graph")
    ap.add_argument("--embed-machine", default=None,
                    help="machine model the table is sharded against "
                         "(defaults to --machine, else the local device "
                         "count); a modeling choice — its mesh need not "
                         "fit the local devices")
    ap.add_argument("--prefetch", type=int, default=0, metavar="DEPTH",
                    help="async batch prefetch depth (0 = off; 2 = "
                         "double buffering)")
    args = ap.parse_args(argv)
    grad_compress = args.grad_compress_block or args.grad_compress

    from repro.core import machine as machine_lib
    from repro.launch.placement import PlacementSession
    machine = machine_lib.resolve(args.machine)
    session = PlacementSession(map_restarts=args.map_restarts)
    arch = configs.get(args.arch)
    cfg = arch.smoke_config() if args.smoke else arch.make_config(
        next(iter(arch.shapes)))
    n_dev = len(jax.devices())
    if machine is not None:
        shape_m, axes_m = machine.mesh_spec()
        mesh = session.build_mesh(shape_m, axes_m)
    else:
        mesh = session.local_mesh()
    rules = rules_for(arch.family, mesh.axis_names, profile=args.profile)
    if args.lint:
        _lint_gate(args.arch, args.profile, session)

    if arch.family == "lm":
        from repro.models import transformer as mdl
    elif arch.family == "recsys":
        from repro.models import recsys as mdl
    elif arch.name == "equiformer-v2":
        from repro.models import equiformer as mdl
    else:
        from repro.models import gnn as mdl

    # parameters are born sharded by their spec tree: at full width the
    # state does not fit one chip, so nothing is built whole on device 0
    from repro.dist.sharding import sanitize_tree, tree_shardings
    from repro.launch.steps import eval_shape_with_specs
    key = jax.random.PRNGKey(0)
    params_sds, pspec = eval_shape_with_specs(
        lambda k: mdl.init(k, cfg, rules), key)
    pspec = sanitize_tree(params_sds, pspec, mesh)
    params = jax.jit(lambda k: mdl.init(k, cfg, rules)[0],
                     out_shardings=tree_shardings(mesh, pspec))(key)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"arch={arch.name} params={n_params/1e6:.1f}M devices={n_dev}")

    ocfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                             warmup_steps=min(20, args.steps // 10))
    ecfg = False
    if args.embed_shard:
        if arch.family != "recsys":
            raise SystemExit("--embed-shard requires a recsys arch")
        if grad_compress:
            raise SystemExit("--embed-shard and --grad-compress are "
                             "mutually exclusive")
        from repro import embed
        from repro.embed import training as embed_training
        stats = probe_embed_stats(cfg, params["item_table"].shape[0],
                                  args.batch, args.embed_probe_batches)
        emachine = machine_lib.resolve(args.embed_machine)
        if emachine is None:
            emachine = machine
        embed_plan = embed.plan_shards(
            stats, machine=emachine,
            n_devices=None if emachine is not None else n_dev)
        embed_plan.check()
        params["item_table"] = jnp.take(
            jnp.asarray(params["item_table"]),
            jnp.asarray(embed_plan.order), axis=0)
        row_perm = jnp.asarray(embed_plan.perm)
        ecfg = embed_training.EmbedConfig()
        opt = embed_training.init_dense_opt(params, ecfg, ocfg)
        step = jax.jit(embed_training.make_embed_train_step(
            lambda p, b: mdl.loss_fn(p, b, cfg, rules, row_perm),
            ocfg, ecfg))
        sizes = embed_plan.shard_sizes
        print(f"embed: {embed_plan.n_rows} rows over "
              f"{embed_plan.n_devices} leaves of "
              f"{embed_plan.machine or 'local'} (rows/leaf "
              f"{int(sizes.min())}..{int(sizes.max())}, makespan "
              f"{embed_plan.makespan:.3e})")
        cache, rep = embed_traffic_report(
            stats, embed_plan, params["item_table"], cfg, args.batch,
            args.embed_cache_rows, args.embed_probe_batches)
        print(f"embed traffic: replicated {rep.sum() / 2:.0f} B -> "
              f"sharded+cache({args.embed_cache_rows}) "
              f"{cache.traffic_bytes():.0f} B "
              f"(hit rate {cache.hit_rate:.2f})")
    else:
        opt = jax.jit(lambda p: adamw.init(p, ocfg), out_shardings=(
            tree_shardings(mesh, adamw.state_specs(pspec))))(params)
        step = jax.jit(make_train_step(
            lambda p, b: mdl.loss_fn(p, b, cfg, rules), ocfg,
            grad_compress=grad_compress))

    batches = make_batches(arch, cfg, args.batch, args.seq)
    if args.prefetch:
        from repro.embed import PrefetchIterator
        batches = PrefetchIterator(batches, depth=args.prefetch)
    if args.topology_aware and n_dev > 1:
        batch0 = next(batches)
        batches = itertools.chain([batch0], batches)
        if grad_compress:
            from repro.dist import compress
            probe_args = (params, opt, compress.init_state(params), batch0)
        elif ecfg:
            probe_args = (params, opt,
                          embed_training.init_embed_state(params, ecfg),
                          batch0)
        else:
            probe_args = (params, opt, batch0)
        scan_lengths = [getattr(cfg, "n_layers", 1)]
        mesh, rep = searched_mesh(step, probe_args, mesh, scan_lengths,
                                  session=session, machine=machine)
        print(f"topology-aware mapping: identity makespan "
              f"{rep.identity['makespan']:.3e} -> searched "
              f"{rep.searched['makespan']:.3e} "
              f"({rep.n_candidates} candidates)")
        params, opt = _onto(mesh, (params, opt))

    lcfg = loop.LoopConfig(total_steps=args.steps,
                           ckpt_every=args.ckpt_every,
                           ckpt_dir=args.ckpt_dir,
                           grad_compress=grad_compress,
                           embed_sparse=ecfg)
    if args.fault_plan:
        from repro.resilience.faults import parse_fault_plan
        plan = parse_fault_plan(args.fault_plan)
        # mesh_fn keeps the launcher-built mesh: the injected death is
        # logical (the machine model shrinks; local devices don't), so
        # the resumed attempt re-enters the same mesh while placement
        # decisions see only the survivors
        params, opt, sup = loop.run_supervised(
            step, params, opt, batches, lcfg, plan, machine=machine,
            mesh_fn=lambda n_alive: mesh,
            max_restarts=args.max_restarts)
        for rec in sup.recoveries:
            print(f"[TRAIN] recovery: device {rec['device']} died at "
                  f"step {rec['step']}; resumed from checkpoint "
                  f"{rec['resumed_from']} on {rec['n_alive']} leaves",
                  flush=True)
        print(f"steps={sup.steps_run} attempts={sup.attempts} "
              f"recoveries={len(sup.recoveries)} "
              f"loss {sup.losses[0]:.4f} -> {sup.losses[-1]:.4f}")
        return sup
    params, opt, result = loop.run(step, params, opt, batches, lcfg,
                                   mesh=mesh)
    print(f"steps={result.steps_run} resumed_from={result.resumed_from} "
          f"loss {result.losses[0]:.4f} -> {result.losses[-1]:.4f} "
          f"({result.seconds:.1f}s, stragglers={result.straggler_steps})")
    if getattr(batches, "is_prefetcher", False):
        s = batches.stats()
        print(f"prefetch: depth={s['depth']} produced={s['produced']} "
              f"ready_hits={s['ready_hits']} "
              f"max_occupancy={s['max_occupancy']}")
    return result


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
