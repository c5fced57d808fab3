"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
512 placeholder host devices, and extract the three roofline terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
        --shape train_4k [--multi-pod] [--machine <preset>] \
        [--out results/dryrun] [--profile 2d|fsdp|sp|expert] \
        [--topology-aware] [--recompile]
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --mapping-grid

``--machine`` names a ``core.machine.MachineSpec`` preset (tpu_v5e-256/
tpu_v5e-512/gpu-superpod/torus-2d/tpu-mixed-32/...): mesh shape, axes,
scored topology and per-leaf roofline capacities all come from the spec —
heterogeneous machines report the slowest-bin-bound terms plus a per-bin
range (DESIGN.md §Machine-models).

Methodology (EXPERIMENTS.md §Roofline records the same):
  * collective bytes — parsed from the compiled SPMD module text by
    ``repro.launch.collectives``; each collective contributes a ring-model
    per-device *link-byte* estimate (all-gather F(S-1)/S, all-reduce
    2F(S-1)/S, reduce-scatter F(S-1)/S, all-to-all F(S-1)/S, permute F),
    scaled by the enclosing while-loops' ``known_trip_count``. Raw operand
    sums are reported alongside.
  * mapping search (``--topology-aware`` / ``--mapping-grid``) — owned by
    ``repro.launch.placement.PlacementSession``: the compiled module's
    replica groups become a [D, D] traffic matrix, ``core.mapping.search``
    scores logical -> physical assignments against the TPU-pod tree, and
    with ``--recompile`` the session recompiles under the searched order
    and diffs the two collective schedules to a fixed point (DESIGN.md §6
    "Recompilation fixed point"). Compiles are served from the session's
    keyed cell cache when the (arch, shape, profile, order) key repeats.
  * FLOPs / bytes — XLA's cost_analysis counts while bodies ONCE, so the
    per-device totals come from ``repro.launch.hlo_cost``: a text-level
    HLO cost model that multiplies every computation by its actual
    execution count (while ``known_trip_count`` compounded through the
    call graph). Validated against cost_analysis on loop-free modules.

This module is a CLI + grid iterator; the compile/measure/search machinery
lives in ``repro.launch.placement`` (one session shared by dryrun, train
and serve). The XLA_FLAGS line below MUST run before any jax import
(device count is locked at first init) — and only here, never globally.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import argparse            # noqa: E402
import dataclasses         # noqa: E402
import json                # noqa: E402
import traceback           # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

import jax                 # noqa: E402
import numpy as np         # noqa: E402

from repro import configs                  # noqa: E402
from repro.core import machine as machine_lib  # noqa: E402
from repro.launch import hlo_cost          # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch import placement         # noqa: E402
# HLO collective accounting lives in launch/collectives.py (import-safe
# without the XLA_FLAGS override); re-exported here for existing callers
# (scripts/diag_cell.py, tests) that historically imported from the dry-run.
from repro.launch.collectives import (_group_size, _link_bytes,  # noqa: F401,E402
                                      _shape_bytes, materialize_groups,
                                      parse_collectives)
from repro.launch.steps import build_cell, rules_for  # noqa: F401,E402


def _compile(arch, shape, mesh, overrides=None, grad_compress=False,
             profile="2d"):
    """Compile one cell on an explicit mesh (scripts/diag_cell.py's entry —
    the dry-run itself goes through the placement session's cached path)."""
    from repro.dist.sharding import sanitize_tree, tree_shardings
    rules = rules_for(arch.family, mesh.axis_names, profile=profile)
    cell = build_cell(arch, shape, rules, grad_compress=grad_compress,
                      overrides=overrides)
    specs = tuple(sanitize_tree(sds, spec, mesh) for sds, spec in
                  zip(cell["args_sds"], cell["args_specs"]))
    shardings = tuple(tree_shardings(mesh, spec) for spec in specs)
    with mesh:
        jitted = jax.jit(cell["step"], in_shardings=shardings)
        lowered = jitted.lower(*cell["args_sds"])
        compiled = lowered.compile()
    return cell, compiled


_FLASH_SCOPE = r"flash|_flash"


def attention_kernel_bytes(arch, shape) -> float:
    """Whole-network per-step HBM bytes of attention if executed as the
    fused Pallas flash kernel (kernels/flash_attention.py): Q/K/V read +
    O write (+dO/dQ/dK/dV in the backward), score tiles stay in VMEM.
    Replaces the XLA-level attention traffic in the roofline memory term.
    """
    if arch.family != "lm" or shape.kind not in ("train", "prefill"):
        return 0.0
    cfg = arch.make_config(shape.name)
    b, s = shape.meta["batch"], shape.meta["seq"]
    bpe = 2  # bf16
    if cfg.mla:
        dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        q = b * s * cfg.n_heads * dqk
        k = b * s * cfg.n_heads * dqk
        v = b * s * cfg.n_heads * cfg.v_head_dim
        o = v
    else:
        dh = cfg.head_dim
        q = b * s * cfg.n_heads * dh
        k = b * s * cfg.n_kv_heads * dh
        v = k
        o = q
    fwd = (q + k + v + o) * bpe
    factor = 3.0 if shape.kind == "train" else 1.0   # bwd rereads + writes
    return cfg.n_layers * fwd * factor


# ---------------------------------------------------------------------------
# One cell
# ---------------------------------------------------------------------------

def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None, grad_compress=False,
             tag: str = "", profile: str = "2d",
             overrides: Optional[Dict] = None,
             topology_aware: bool = False, map_restarts: int = 32,
             recompile: bool = False,
             session: Optional[placement.PlacementSession] = None,
             machine=None) -> Dict:
    """One (arch x shape x mesh) cell through the placement session:
    compile (or cache-hit), extract roofline terms, and — with
    ``topology_aware`` — run the searched-vs-identity mapping comparison,
    recompiling under the searched order when ``recompile`` is set.

    ``machine`` (MachineSpec or ``--machine`` preset name) selects the
    machine model; default is the TPU production preset named by
    ``multi_pod``. Roofline terms are sized per leaf, so a heterogeneous
    machine reports the binding (slowest-bin) time plus the per-bin range.
    """
    arch = configs.get(arch_name)
    shape = arch.shapes[shape_name]
    spec = (machine_lib.resolve(machine)
            or mesh_lib.production_machine(multi_pod))
    # mesh tag keys the emitted filename: the TPU production presets keep
    # the historical shape tags, every other machine tags by NAME so two
    # presets sharing a mesh shape (gpu-superpod / torus-2d, both 8x8)
    # cannot overwrite each other's results
    mesh_tag = ("x".join(str(s) for s in spec.mesh_shape)
                if spec.name in ("tpu_v5e-256", "tpu_v5e-512")
                else spec.name)
    result: Dict = {"arch": arch_name, "shape": shape_name, "mesh": mesh_tag,
                    "machine": spec.name, "kind": shape.kind, "tag": tag,
                    "profile": profile}
    if shape.kind == "skip":
        result["status"] = "skip"
        result["reason"] = shape.skip_reason
        return _emit(result, out_dir)

    session = session or placement.PlacementSession(
        map_restarts=map_restarts)
    topology_aware = topology_aware or recompile   # recompile implies it
    chips = spec.n_devices

    # production compile: collectives + memory + proof of compilability
    prod_overrides = dict(overrides or {})
    if arch.family == "lm" and shape.kind in ("train", "prefill"):
        prod_overrides.setdefault("q_chunk", 0)  # single q block (see doc)
    if topology_aware:
        res = session.place(arch_name, shape_name, machine=spec,
                            profile=profile, grad_compress=grad_compress,
                            overrides=prod_overrides, recompile=recompile)
        rec = res.record
        result["mapping"] = dataclasses.asdict(res.report)
    else:
        rec = session.measure(arch_name, shape_name, machine=spec,
                              profile=profile, grad_compress=grad_compress,
                              overrides=prod_overrides)
    cal, bytes_deep = rec.hlo_cal, rec.bytes_deep

    flops_dev = max(cal["flops"], rec.agg_flops)
    # HBM proxy = tight op set (GEMM I/O, data movement, collectives; see
    # hlo_cost._TIGHT_HBM), with f32 traffic halved (XLA:CPU upcasts the
    # bf16 policy path; the TPU target moves bf16). For LM train/prefill,
    # the flash-attention interior (everything nested deeper than the
    # layer scan = the kv-chunk loops) is swapped for the fused Pallas
    # kernel's Q/K/V/O traffic — score tiles live in VMEM on the target
    # (kernels/flash_attention.py).
    attn_dev = attention_kernel_bytes(arch, shape) / chips
    if arch.family == "lm" and shape.kind in ("train", "prefill"):
        bytes_dev = cal["bytes_tight"] - bytes_deep + attn_dev
    else:
        bytes_dev = cal["bytes_tight"]
        bytes_deep = 0.0
    bytes_all_dev = max(cal["bytes"], rec.agg_bytes)
    link_dev = float(sum(rec.link_bf16.values()))
    model_fl = arch.model_flops(shape.name)

    # per-leaf roofline: SPMD shards are equal, so a bin's time is the
    # shard cost over ITS capacity and the step is bound by the slowest
    # bin — on uniform machines this is exactly the historical scalar
    compute_s_bins = flops_dev / spec.peak_flops
    memory_s_bins = bytes_dev / spec.hbm_bw
    compute_s = float(compute_s_bins.max())
    memory_s = float(memory_s_bins.max())
    collective_s = link_dev / spec.link_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    if spec.heterogeneous:
        result["roofline_per_bin"] = {
            "compute_s_min": float(compute_s_bins.min()),
            "compute_s_max": compute_s,
            "memory_s_min": float(memory_s_bins.min()),
            "memory_s_max": memory_s,
            "slowest_bin": int(np.argmax(
                np.maximum(compute_s_bins, memory_s_bins))),
        }
    result.update({
        "status": "ok",
        "chips": chips,
        "compile_s": rec.compile_s, "calibrate_s": rec.calibrate_s,
        "cache_hit": rec.cached,
        "per_device": {"flops": flops_dev, "bytes": bytes_dev,
                       "bytes_unfused": bytes_all_dev,
                       "bytes_attn_xla": bytes_deep,
                       "bytes_attn_kernel": attn_dev,
                       "collective_link_bytes": rec.link_bf16,
                       "collective_link_bytes_raw_f32": rec.link,
                       "collective_operand_bytes": rec.operand,
                       "n_collectives": rec.n_collectives},
        "total": {"flops": flops_dev * chips, "bytes": bytes_dev * chips,
                  "collective_link_bytes": link_dev * chips},
        "agg_once": {"flops": rec.agg_flops, "bytes": rec.agg_bytes},
        "hlo_cost": cal,
        "memory_analysis": rec.memory,
        "model_flops": model_fl,
        "useful_ratio": (model_fl / (flops_dev * chips)
                         if flops_dev else None),
        "roofline_terms": terms,
        "dominant": dominant,
        "step_time_bound_s": bound,
        "roofline_fraction": (compute_s / bound if bound > 0 else None),
        "scan_lengths": rec.scan_lengths,
    })
    return _emit(result, out_dir)


def _emit(result: Dict, out_dir: Optional[str]) -> Dict:
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"__{result['tag']}" if result.get("tag") else ""
        name = (f"{result['arch']}__{result['shape']}"
                f"__{result['mesh']}{tag}.json")
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _report_of(result: Dict) -> placement.PlacementReport:
    return placement.PlacementReport(**result["mapping"])


def mapping_grid(arch_names: List[str], shape_name: str, out_dir: str,
                 overrides: Optional[Dict] = None,
                 map_restarts: int = 32, recompile: bool = False,
                 session: Optional[placement.PlacementSession] = None,
                 machine=None) -> int:
    """Searched-vs-identity mapping comparison over each arch's sharding
    profiles on the multi-pod mesh (or ``--machine`` preset), one shared
    placement session for the whole sweep (repeat invocations hit the
    compiled-cell cache; the table lands in EXPERIMENTS.md). Returns the
    failure count.
    """
    session = session or placement.PlacementSession(
        map_restarts=map_restarts)
    failures = 0
    for arch_name in arch_names:
        arch = configs.get(arch_name)
        for profile in arch.profiles:
            try:
                r = run_cell(arch_name, shape_name, multi_pod=True,
                             out_dir=out_dir, tag=f"map_{profile}",
                             profile=profile, overrides=overrides,
                             topology_aware=True, map_restarts=map_restarts,
                             recompile=recompile, session=session,
                             machine=machine)
                if r["status"] != "ok":
                    print(f"[SKIP] {arch_name}/{shape_name}/{profile}: "
                          f"{r.get('reason', '')[:60]}", flush=True)
                    continue
                rep = _report_of(r)
                print(rep.summary(), flush=True)
                if recompile:
                    print(rep.diff_summary(), flush=True)
            except Exception as e:
                failures += 1
                print(f"[FAIL] {arch_name}/{shape_name}/{profile}: {e}",
                      flush=True)
                traceback.print_exc()
            finally:
                jax.clear_caches()
    print(f"[CACHE] compiles={session.n_compiles} "
          f"hits={session.n_cache_hits} dir={session.cache_dir}",
          flush=True)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--machine", default=None,
                    help="machine-model preset (core.machine registry: "
                         + ", ".join(machine_lib.MachineSpec.presets())
                         + "); overrides --multi-pod/--single-pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--profile", default="2d",
                    help="lm sharding profile: 2d | fsdp | sp | expert")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--grad-compress-block", type=int, default=0,
                    help="per-block compression scale size (power of two; "
                         "implies --grad-compress; 0 = one scale per "
                         "tensor)")
    ap.add_argument("--topology-aware", action="store_true",
                    help="search the logical->physical device mapping over "
                         "the machine tree and report searched vs identity")
    ap.add_argument("--recompile", action="store_true",
                    help="recompile under the searched order and diff the "
                         "two XLA collective schedules to a fixed point "
                         "(implies --topology-aware)")
    ap.add_argument("--map-restarts", type=int, default=32,
                    help="random-restart candidates appended to the "
                         "structured mapping search (0 disables)")
    ap.add_argument("--cache-dir", default=None,
                    help="compiled-cell cache directory (default "
                         "$REPRO_PLACEMENT_CACHE or "
                         "results/placement_cache; '' disables)")
    ap.add_argument("--lint", action="store_true",
                    help="after the run, static-verify the Pallas kernel "
                         "registry and every measured traffic matrix "
                         "(repro.analysis); error findings fail the run")
    ap.add_argument("--mapping-grid", action="store_true",
                    help="multi-pod searched-vs-identity comparison for "
                         "every sharding profile of the given --arch "
                         "(default: qwen2-1.5b + deepseek-v2-lite-16b)")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (int), e.g. ep_shard_map=1")
    args = ap.parse_args()
    overrides = {}
    for kv in args.override:
        k, v = kv.split("=")
        overrides[k] = int(v)
    grad_compress = (args.grad_compress_block
                     or args.grad_compress)
    topology_aware = args.topology_aware or args.recompile
    session = placement.PlacementSession(cache_dir=args.cache_dir,
                                         map_restarts=args.map_restarts)

    machine = machine_lib.resolve(args.machine)

    if args.mapping_grid:
        archs = [args.arch] if args.arch else ["qwen2-1.5b",
                                               "deepseek-v2-lite-16b"]
        failures = mapping_grid(archs, args.shape or "train_4k", args.out,
                                overrides, map_restarts=args.map_restarts,
                                recompile=args.recompile, session=session,
                                machine=machine)
        if args.lint:
            _lint_gate(session)
        if failures:
            raise SystemExit(f"{failures} mapping-grid cells failed")
        return

    meshes = []
    if args.single_pod or not args.multi_pod:
        meshes.append(False)
    if args.multi_pod or args.all:
        meshes.append(True)
    if args.all:
        meshes = [False, True]
    if machine is not None:
        meshes = [False]          # the preset decides the mesh, not the flag

    cells: List[Tuple[str, str]] = []
    if args.all:
        for arch, shape in configs.all_cells():
            cells.append((arch.name, shape.name))
    else:
        arch = configs.get(args.arch)
        shapes = [args.shape] if args.shape else list(arch.shapes)
        cells = [(args.arch, s) for s in shapes]

    failures = 0
    for arch_name, shape_name in cells:
        for mp in meshes:
            mesh_tag = (machine.name if machine is not None
                        else ("2x16x16" if mp else "16x16"))
            try:
                r = run_cell(arch_name, shape_name, mp, args.out,
                             grad_compress=grad_compress, tag=args.tag,
                             profile=args.profile, overrides=overrides,
                             topology_aware=topology_aware,
                             map_restarts=args.map_restarts,
                             recompile=args.recompile, session=session,
                             machine=machine)
                if r["status"] == "skip":
                    print(f"[SKIP] {arch_name}/{shape_name}/{mesh_tag}: "
                          f"{r['reason'][:60]}", flush=True)
                else:
                    t = r["roofline_terms"]
                    hit = " (cache)" if r.get("cache_hit") else ""
                    print(f"[OK]   {arch_name}/{shape_name}/{mesh_tag} "
                          f"compile={r['compile_s']}s{hit} "
                          f"comp={t['compute_s']:.3e} "
                          f"mem={t['memory_s']:.3e} "
                          f"coll={t['collective_s']:.3e} "
                          f"dom={r['dominant']} "
                          f"roofline={r['roofline_fraction']:.2f}",
                          flush=True)
                    if "mapping" in r:
                        rep = _report_of(r)
                        print(rep.summary(), flush=True)
                        if args.recompile:
                            print(rep.diff_summary(), flush=True)
            except Exception as e:
                failures += 1
                print(f"[FAIL] {arch_name}/{shape_name}/{mesh_tag}: {e}",
                      flush=True)
                traceback.print_exc()
            finally:
                jax.clear_caches()
    print(f"[CACHE] compiles={session.n_compiles} "
          f"hits={session.n_cache_hits} dir={session.cache_dir}",
          flush=True)
    if args.lint:
        _lint_gate(session)
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


def _lint_gate(session: placement.PlacementSession) -> None:
    """``--lint``: session-wide static analysis; errors fail the run."""
    from repro import analysis
    findings = session.verify()
    print(analysis.format_findings(findings), flush=True)
    errors = analysis.at_least(findings, "error")
    if errors:
        raise SystemExit(f"--lint: {len(errors)} error-severity "
                         "finding(s)")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
