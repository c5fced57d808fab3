"""Chip smoke test: drive the program's main paths once on a TPU.

    python chip_smoke.py [--seed 0]      # one chip: engine, then serving
    python chip_smoke.py --chips 4       # four chips: sharded training only

One chip runs two phases, in order:

* engine — the paper's main path through ``partition(...,
  PartitionConfig(backend="device"))`` and ``core.mapping.search`` on the
  ``tpu_v5e-256`` machine tree (k = 256), for an FEM-like ``grid3d`` mesh
  (~1.05M vertices, 3.1M edges) and a power-law ``rmat`` graph (~1M
  vertices, 4.1M edges), each checked by the path-walking oracle
  (``partitioner.verify``); plus the device result against the host
  backend's on a graph the host partitions in well under a minute.
* serving — qwen2-1.5b at its published widths through the stream engine
  (``launch/serve.py`` set-up, ``ServingEngine``): 8 greedy requests, 4
  slots, page placement on 4 bins, checked against the dense
  ``decode_step`` on the same tokens.

``--chips 4`` runs only the four-chip phase: full-width qwen2-1.5b training
through ``launch/train.py`` (``--profile fsdp``), 4 steps on the searched
mesh (``--topology-aware``) against the same 4 steps on the identity mesh.

Every phase prints one result line; a failed check raises, so the exit code
is nonzero. Wall seconds include compilation and host work: they are
set-up-inclusive, not device times. The last line printed is the JSON
device record. The script stops before any phase when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core import mapping, objective  # noqa: E402
from repro.core.machine import resolve  # noqa: E402
from repro.core.partitioner import PartitionConfig, partition, verify  # noqa: E402
from repro.graph.generators import grid3d, rmat  # noqa: E402

MACHINE = "tpu_v5e-256"
HOST_RATIO = 1.05      # device / host makespan, the bound pinned on CPU
# paged vs dense logits: relative L2 error per compared position. Both
# paths run the same bf16 einsums, but batch composition, padded lengths
# and fusion may differ, so a few bf16 roundings (2^-8 relative) are
# allowed; a wrong page or mask gives an error of order 1 or NaN. (One v5e
# chip gave bit-identical logits at seed 0.)
LOGITS_RTOL = 1e-2
# searched vs identity mesh losses: same program, device order permuted;
# only collective reduction order may differ.
LOSS_RTOL = 1e-3


def engine_graphs(seed: int):
    """The two engine deployments at full size, generated from ``seed``."""
    return {"grid3d": grid3d(128, 128, 64),
            "rmat": rmat(1 << 20, 4_200_000, seed=seed)}


def host_graph():
    """FEM-like mesh the host backend partitions in well under a minute."""
    return grid3d(64, 64, 32)


def _quotient(g, part, k):
    import jax.numpy as jnp
    W = np.array(objective.quotient_matrix(
        jnp.asarray(part, dtype=jnp.int32), jnp.asarray(g.senders),
        jnp.asarray(g.receivers), jnp.asarray(g.edge_weight), k))
    np.fill_diagonal(W, 0.0)
    return W


def vcycle_custom_calls(g, k: int):
    """Whether the device V-cycle's compiled programs at ``g``'s level-0
    shapes hold a Pallas kernel (``tpu_custom_call``): the coarsening step
    (``match_keys``) and the initial assignment (``bucket_assign``)."""
    import jax
    import jax.numpy as jnp

    from repro.core import coarsen
    from repro.kernels import ops
    n_pad, m_pad = coarsen._pow2(g.n_nodes), coarsen._pow2(g.n_arcs)
    i32, f32 = jnp.int32, jnp.float32
    arcs = jax.ShapeDtypeStruct((m_pad,), i32)
    step = coarsen._coarsen_step().lower(
        arcs, arcs, jax.ShapeDtypeStruct((m_pad,), f32),
        jax.ShapeDtypeStruct((n_pad,), f32), jnp.int32(0), jnp.int32(0),
        jax.random.PRNGKey(0), n_pad=n_pad).compile().as_text()
    initial = jax.jit(lambda c, b: ops.bucket_assign(c, b, k)).lower(
        jax.ShapeDtypeStruct((g.n_nodes,), f32),
        jax.ShapeDtypeStruct((k - 1,), f32)).compile().as_text()
    return {"coarsen_device": "tpu_custom_call" in step,
            "initial_partition_device": "tpu_custom_call" in initial}


def engine_phase(graphs, host_g, *, seed: int = 0, machine: str = MACHINE,
                 map_restarts: int = 8):
    """Device V-cycle + mapping search on each graph, oracle-verified, and
    the device/host makespan ratio on ``host_g``. Returns the result rows."""
    spec = resolve(machine)
    topo = spec.topology()
    mesh_shape, _ = spec.mesh_spec()
    rows = []
    for name, g in graphs.items():
        t0 = time.time()
        res = partition(g, topo, PartitionConfig(seed=seed, backend="device"))
        t1 = time.time()
        verify(g, topo, res)
        t2 = time.time()
        W = _quotient(g, res.part, topo.k)
        best = mapping.search(mesh_shape, topo, W, n_random=map_restarts,
                              seed=seed)
        ident = mapping.makespan_of_device_map(W, topo, np.arange(topo.k))
        t3 = time.time()
        if best.bottleneck > ident * (1 + 1e-6):
            raise AssertionError(f"{name}: searched map {best.bottleneck} "
                                 f"worse than identity {ident}")
        row = dict(graph=name, vertices=g.n_nodes, edges=g.n_arcs // 2,
                   k=topo.k, makespan=res.makespan,
                   map_identity=ident, map_searched=best.bottleneck,
                   partition_s=t1 - t0, verify_s=t2 - t1, map_s=t3 - t2)
        print(f"[engine] {name}: vertices={g.n_nodes} edges={g.n_arcs // 2} "
              f"k={topo.k} makespan={res.makespan} verify=ok "
              f"map bottleneck {ident} -> {best.bottleneck} | wall s "
              f"(set-up-inclusive): partition={t1 - t0:.3f} "
              f"verify={t2 - t1:.3f} map={t3 - t2:.3f}", flush=True)
        rows.append(row)
    t0 = time.time()
    dev = partition(host_g, topo, PartitionConfig(seed=seed, backend="device"))
    t1 = time.time()
    host = partition(host_g, topo, PartitionConfig(seed=seed, backend="host"))
    t2 = time.time()
    verify(host_g, topo, dev)
    verify(host_g, topo, host)
    ratio = dev.makespan / host.makespan
    print(f"[engine] host check: vertices={host_g.n_nodes} "
          f"edges={host_g.n_arcs // 2} makespan device={dev.makespan} "
          f"host={host.makespan} ratio={ratio:.4f} (bound {HOST_RATIO}) | "
          f"wall s (set-up-inclusive): device={t1 - t0:.3f} "
          f"host={t2 - t1:.3f}", flush=True)
    if not ratio <= HOST_RATIO:             # NaN fails too
        raise AssertionError(f"device/host makespan {ratio:.4f} > "
                             f"{HOST_RATIO}")
    rows.append(dict(graph="host_check", ratio=ratio))
    return rows


def _record_logits(engine, positions):
    """Keep the logits the engine samples from at ``positions``
    ({rid: set of token positions}); returns the dict it fills."""
    seen = {}
    sample = engine._sample

    def recording(logits, rids, poss):
        for slot, (rid, pos) in enumerate(zip(np.asarray(rids),
                                              np.asarray(poss))):
            if rid >= 0 and int(pos) in positions.get(int(rid), ()):
                seen[(int(rid), int(pos))] = np.asarray(logits[slot],
                                                        np.float32)
        return sample(logits, rids, poss)

    engine._sample = recording
    return seen


def _dense_logits(params, cfg, rules, seqs, positions):
    """Dense-cache decode of every sequence in one batch, one token per
    step; the logits at ``positions`` ({row: set of positions})."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tr
    n, t = len(seqs), max(len(s) for s in seqs)
    toks = np.zeros((n, t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    cache, _ = tr.init_cache(cfg, n, -(-t // 128) * 128, rules)
    decode = jax.jit(lambda p, c, x, pos: tr.decode_step(p, c, x, pos, cfg,
                                                         rules),
                     donate_argnums=(1,))
    out = {}
    last = max(max(p) for p in positions.values())
    for pos in range(last + 1):
        logits, cache = decode(params, cache, jnp.asarray(toks[:, pos:pos + 1]),
                               jnp.int32(pos))
        for row, want in positions.items():
            if pos in want:
                out[(row, pos)] = np.asarray(logits[row], np.float32)
    return out


def serving_phase(*, smoke: bool = False, seed: int = 0,
                  prompt_lens=(64, 512), gen_lens=(16, 64),
                  page_size: int = 16, replace_every: int = 128):
    """Stream-serve 8 greedy requests through the engine (4 slots, pages
    placed on 4 bins) and compare the logits of each request's first 4
    generated steps with the dense decode. Returns the result dict."""
    import jax

    from repro.launch import serve
    from repro.serving import EngineConfig, ServingEngine
    n_requests, slots, place_devices, n_compare = 8, 4, 4, 4
    argv = ["--arch", "qwen2-1.5b", "--seed", str(seed)]
    args = serve._parser().parse_args(argv + (["--smoke"] if smoke else []))
    t0 = time.time()
    cfg, _, session, mesh, rules, params = serve._setup(args)
    jax.block_until_ready(params)
    t_setup = time.time() - t0
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(
        prompt_lens[0], prompt_lens[1] + 1))).astype(np.int32)
        for _ in range(n_requests)]
    gens = [int(rng.integers(gen_lens[0], gen_lens[1] + 1))
            for _ in range(n_requests)]
    max_pages = -(-max(len(p) + g for p, g in zip(prompts, gens))
                  // page_size)
    ecfg = EngineConfig(n_slots=slots, page_size=page_size,
                        n_pages=max_pages * slots * 2,
                        max_pages_per_req=max_pages, temperature=0.0,
                        seed=seed, replace_every=replace_every,
                        place_devices=place_devices)
    # positions whose logits pick the first generated tokens
    positions = {rid: set(range(len(p) - 1,
                                len(p) - 1 + min(n_compare, g)))
                 for rid, (p, g) in enumerate(zip(prompts, gens))}
    with mesh:
        engine = ServingEngine(params, cfg, rules, ecfg, session=session)
        paged = _record_logits(engine, positions)
        for p, g in zip(prompts, gens):
            engine.submit(p, g)
        t0 = time.time()
        report = engine.run()
        t_serve = time.time() - t0
        generated = {r["rid"]: r["generated"] for r in report.requests}
        seqs = [np.concatenate([p, np.asarray(generated[rid][:n_compare - 1],
                                              np.int32)])
                for rid, p in enumerate(prompts)]
        t0 = time.time()
        dense = _dense_logits(params, cfg, rules, seqs, positions)
        t_dense = time.time() - t0
    if report.n_requests != n_requests or report.requests_failed:
        raise AssertionError(f"served {report.n_requests}/{n_requests}, "
                             f"failed {report.requests_failed}")
    if set(paged) != set(dense):
        raise AssertionError(f"compared positions differ: paged "
                             f"{len(paged)} vs dense {len(dense)}")
    rel = max(float(np.linalg.norm(paged[key] - dense[key])
                    / np.linalg.norm(dense[key])) for key in dense)
    max_abs = max(float(np.abs(paged[key] - dense[key]).max())
                  for key in dense)
    stats = jax.devices()[0].memory_stats() or {}
    out = dict(requests=report.n_requests, tokens_out=report.tokens_out,
               steps=report.steps, placement_epochs=len(report.placements),
               compared=len(dense), logits_rel_l2=rel,
               logits_max_abs=max_abs,
               peak_bytes_in_use=stats.get("peak_bytes_in_use"),
               setup_s=t_setup, serve_s=t_serve, dense_s=t_dense)
    print(f"[serving] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab} "
          f"requests={report.n_requests} tokens_out={report.tokens_out} "
          f"steps={report.steps} placement_epochs={len(report.placements)} "
          f"paged-vs-dense logits rel_l2={rel:.3e} max_abs={max_abs:.3e} "
          f"over {len(dense)} positions (bound {LOGITS_RTOL}) "
          f"peak_bytes_in_use={out['peak_bytes_in_use']} | wall s "
          f"(set-up-inclusive): setup={t_setup:.3f} serve={t_serve:.3f} "
          f"dense={t_dense:.3f}", flush=True)
    if report.placements == []:
        raise AssertionError("no page-placement epoch ran")
    if not rel <= LOGITS_RTOL:              # NaN fails too
        raise AssertionError(f"paged vs dense logits rel_l2 {rel:.3e} > "
                             f"{LOGITS_RTOL}")
    return out


def train_phase(*, smoke: bool = False, seq: int = 512):
    """4 fsdp training steps at batch 8 on the searched mesh against the
    identity mesh, through ``launch/train.py``; per-device peak memory
    after both."""
    import jax

    from repro.launch import train
    steps, batch = 4, 8
    argv = ["--arch", "qwen2-1.5b", "--profile", "fsdp", "--steps",
            str(steps), "--batch", str(batch), "--seq", str(seq)]
    argv += ["--smoke"] if smoke else []
    t0 = time.time()
    searched = train.main(argv + ["--topology-aware"])
    t1 = time.time()
    identity = train.main(argv)
    t2 = time.time()
    a, b = np.asarray(searched.losses), np.asarray(identity.losses)
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    print(f"[train] qwen2-1.5b fsdp devices={len(jax.devices())} "
          f"batch={batch} seq={seq} losses searched={a.tolist()} "
          f"identity={b.tolist()} max rel diff={rel:.3e} "
          f"(bound {LOSS_RTOL}) peak_bytes_in_use per device={peaks} | "
          f"wall s (set-up-inclusive): searched={t1 - t0:.3f} "
          f"identity={t2 - t1:.3f}", flush=True)
    if not (rel <= LOSS_RTOL and np.isfinite(a).all()):
        raise AssertionError(f"searched vs identity losses differ by "
                             f"{rel:.3e} > {LOSS_RTOL}")
    return dict(searched=a.tolist(), identity=b.tolist(), rel=rel,
                peak_bytes_in_use=peaks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated graphs, prompts and "
                         "sampling")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip training phase")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.kernels import ops
    from repro.launch import compile_cache
    compile_cache.enable()
    if not ops.use_pallas():
        raise AssertionError("Pallas kernels are off on a TPU")
    if args.chips == 4:
        train_phase()
    else:
        graphs = engine_graphs(args.seed)
        engine_phase(graphs, host_graph(), seed=args.seed)
        # after the phase, which compiled these programs already
        calls = vcycle_custom_calls(graphs["grid3d"],
                                    resolve(MACHINE).n_devices)
        print(f"[engine] device V-cycle programs hold tpu_custom_call: "
              f"{calls}", flush=True)
        if not all(calls.values()):
            raise AssertionError(f"device V-cycle programs without a Pallas "
                                 f"kernel: {calls}")
        serving_phase(seed=args.seed)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
