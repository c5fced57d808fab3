"""The launch layer's single placement brain (DESIGN.md §6).

``PlacementSession`` owns the whole compile -> measure -> search ->
recompile loop that used to be scattered across ``dryrun.py`` (cell
compiles + mapping report), ``train.py`` (``searched_mesh``) and nowhere
at all for ``serve.py``:

1. **compile** one ``(arch x shape x profile)`` cell on the identity mesh
   (``launch/steps.py:build_cell``) and extract everything the launch layer
   ever reads from the compiled module — per-op collective link bytes, the
   ``[D, D]`` device-pair traffic matrix (``launch/collectives.py``), XLA
   cost/memory analysis, and the loop-aware HLO byte calibration
   (``launch/hlo_cost.py``) — into one serializable :class:`CellRecord`;
2. **search** the logical -> physical device order with
   ``core.mapping.search`` (batched scoring, random restarts, recursive
   per-subtree pass) against the machine tree of the mesh;
3. **recompile** under the searched order and diff the two XLA collective
   schedules (per-op link bytes, bottleneck link, cross-pod DCN bytes),
   iterating to a fixed point: each round re-measures the actual
   post-placement schedule, feeds the prior winner back into the search as
   a warm start (monotone — a later round can never lose to an earlier
   one), and stops when the order stops changing or ``max_rounds`` is hit.

Every compile goes through a keyed cache — in-memory within the session,
and (``cache_dir``) on disk across processes — so ``--mapping-grid``
sweeps and the fixed-point loop amortize the per-cell XLA compile cost,
the one bottleneck ROADMAP names. The key covers everything that changes
the compiled module: (arch, shape, mesh shape/axes, profile,
grad-compress mode, config overrides, device order, jax version, and a
content hash of the repro package sources).

Consumers: ``dryrun.py`` (CLI + grid iteration), ``train.py``
(``searched_mesh`` is a thin wrapper over :meth:`map_step`), ``serve.py``
(``--topology-aware``). None of them talk to ``search_mesh_mapping`` or
build production meshes directly anymore.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import mapping, topology
from repro.core import machine as machine_lib
from repro.core.machine import MachineSpec
from repro.launch import hlo_cost
from repro.launch import mesh as mesh_lib
from repro.launch.collectives import parse_collectives

# Disk cache location: override with REPRO_PLACEMENT_CACHE; an empty value
# (or cache_dir="" / None at construction) disables the disk tier.
_CACHE_ENV = "REPRO_PLACEMENT_CACHE"
_DEFAULT_CACHE_DIR = os.path.join("results", "placement_cache")

_SRC_FINGERPRINT: Optional[str] = None


def _source_fingerprint() -> str:
    """Content hash over the repro package's .py sources, computed once
    per process and folded into every cache key: editing models, sharding
    rules or the HLO cost model must invalidate cached CellRecords — the
    compiled module they describe no longer matches the code."""
    global _SRC_FINGERPRINT
    if _SRC_FINGERPRINT is None:
        # this file lives at <root>/launch/placement.py; walking from the
        # package root covers models, dist, core, launch and kernels
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        h = hashlib.sha256()
        for dirpath, dirnames, filenames in sorted(os.walk(root)):
            dirnames.sort()
            for name in sorted(filenames):
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
        _SRC_FINGERPRINT = h.hexdigest()[:16]
    return _SRC_FINGERPRINT


# ---------------------------------------------------------------------------
# Records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CellRecord:
    """Everything the launch layer derives from ONE XLA compile of a cell.

    Cache-serializable (json metadata + the traffic array in one ``.npz``):
    a cache hit reconstructs the full dry-run roofline report without
    touching XLA. ``device_order=None`` is the identity compile; a list is
    the logical->physical permutation the mesh was built with.
    """
    arch: str
    shape: str
    mesh_shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    profile: str
    device_order: Optional[List[int]]
    compile_s: float
    calibrate_s: float
    scan_lengths: List[int]
    link: Dict[str, float]           # per-op per-device ring link bytes
    operand: Dict[str, float]
    link_bf16: Dict[str, float]      # bf16-corrected (the roofline input)
    n_collectives: int
    agg_flops: float                 # XLA cost_analysis (while bodies once)
    agg_bytes: float
    memory: Dict[str, Optional[int]]
    hlo_cal: Dict[str, float]        # loop-aware text cost model totals
    bytes_deep: float                # tight-HBM bytes inside nested whiles
    traffic: Any = None              # [D, D] np.ndarray device-pair bytes
    cached: bool = False             # served from cache, not compiled


def _json_sides(d: Dict[str, float]) -> Dict[str, float]:
    return {k: float(v) for k, v in d.items()}


@dataclasses.dataclass
class PlacementReport:
    """Searched-vs-identity placement comparison for one cell.

    All fields are JSON-native (lists/dicts/scalars), so
    ``to_json``/``from_json`` round-trip to an equal dataclass. ``rounds``
    records the fixed-point trajectory (round 0 is the identity-compile
    search; later rounds are recompiles under the then-best order);
    ``schedule_diff`` is the recompile diff (None without ``recompile``).
    """
    arch: str
    shape: str
    profile: str
    mesh: str                        # "2x16x16"
    identity: Dict[str, float]       # makespan / bottleneck_link_bytes /
    searched: Dict[str, float]       #   dcn_bytes of each side
    makespan_ratio: float
    axis_perm: List[int]
    axis_orders: List[int]
    n_candidates: int
    device_order: List[int]
    total_link_bytes: float
    search_s: float
    rounds: List[Dict[str, Any]]
    schedule_diff: Optional[Dict[str, Any]]
    n_compiles: int                  # compiles this place() actually ran
    cache_hits: int                  # cache hits this place() enjoyed

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "PlacementReport":
        return cls(**json.loads(s))

    def summary(self) -> str:
        i, s = self.identity, self.searched
        return (f"[MAP]  {self.arch}/{self.shape}/{self.profile} "
                f"makespan id={i['makespan']:.3e} "
                f"searched={s['makespan']:.3e} "
                f"(ratio {self.makespan_ratio:.3f}) "
                f"dcn_bytes id={i['dcn_bytes']:.3e} "
                f"searched={s['dcn_bytes']:.3e} "
                f"perm={tuple(self.axis_perm)} "
                f"compiles={self.n_compiles} cache_hits={self.cache_hits}")

    def diff_summary(self) -> str:
        d = self.schedule_diff
        if not d:
            return "[DIFF] (no recompile requested)"
        lines = [f"[DIFF] {self.arch}/{self.shape}/{self.profile} "
                 f"searched-vs-identity compiled schedule "
                 f"(recompiles={d['recompiles']}, "
                 f"fixed_point={d['fixed_point']})"]
        for op, v in sorted(d["per_op_link_bytes"].items()):
            lines.append(f"[DIFF]   {op:<19} id={v['identity']:.3e} "
                         f"searched={v['searched']:.3e} "
                         f"delta={v['delta']:+.3e}")
        for key in ("bottleneck_link_bytes", "dcn_bytes", "makespan"):
            v = d[key]
            lines.append(f"[DIFF]   {key:<19} id={v['identity']:.3e} "
                         f"searched={v['searched']:.3e} "
                         f"delta={v['delta']:+.3e}")
        return "\n".join(lines)


@dataclasses.dataclass
class PlacementResult:
    """What :meth:`PlacementSession.place` returns: the identity-order
    compile record (the roofline source), the searched-vs-identity report,
    and — when ``recompile`` ran — the record of the compile under the
    winning order."""
    record: CellRecord
    report: PlacementReport
    searched_record: Optional[CellRecord] = None


# ---------------------------------------------------------------------------
# Side metrics + schedule diff
# ---------------------------------------------------------------------------

def _link_depths(topo) -> Optional[np.ndarray]:
    """Tree-link depths (1 = cross-pod DCN), or None for routing
    topologies, whose links have no tree depth — their dcn_bytes report
    as 0."""
    if not isinstance(topo, topology.TreeTopology):
        return None
    return np.asarray([topo.depth(int(c)) for c in topo.link_nodes])


def _side_metrics(traffic: np.ndarray, topo, device_to_bin: np.ndarray,
                  depths: Optional[np.ndarray] = None) -> Dict[str, float]:
    """The paper's three observables of one placement under one measured
    schedule: F_l-weighted makespan, raw bottleneck-link bytes, and the
    bytes crossing the depth-1 (cross-pod DCN) tree links."""
    if depths is None:
        depths = _link_depths(topo)
    f_l = np.asarray(topo.F_l)
    loads = mapping.link_loads_of_device_map(traffic, topo, device_to_bin)
    return {"makespan": float((f_l * loads).max()),
            "bottleneck_link_bytes": float(loads.max()),
            "dcn_bytes": (float(loads[depths == 1].sum())
                          if depths is not None else 0.0)}


def schedule_diff(identity_rec: CellRecord, searched_rec: CellRecord,
                  topo, identity_order: np.ndarray,
                  searched_order: np.ndarray, *, recompiles: int = 1,
                  fixed_point: bool = True) -> Dict[str, Any]:
    """Diff two compiled XLA collective schedules under their placements.

    ``identity_rec`` is the identity-order compile, ``searched_rec`` the
    recompile under the searched order; each side's link metrics come from
    its OWN measured traffic matrix placed with its OWN order — the
    post-placement schedule, not the model's prediction. Identical records
    under identical orders diff to exactly zero everywhere
    (``max_abs_delta == 0``), which pins compile determinism in tests.
    """
    depths = _link_depths(topo)
    side_i = _side_metrics(identity_rec.traffic, topo,
                           np.asarray(identity_order), depths)
    side_s = _side_metrics(searched_rec.traffic, topo,
                           np.asarray(searched_order), depths)
    per_op: Dict[str, Dict[str, float]] = {}
    for op in sorted(set(identity_rec.link_bf16)
                     | set(searched_rec.link_bf16)):
        a = float(identity_rec.link_bf16.get(op, 0.0))
        b = float(searched_rec.link_bf16.get(op, 0.0))
        per_op[op] = {"identity": a, "searched": b, "delta": b - a}
    out: Dict[str, Any] = {"per_op_link_bytes": per_op,
                           "n_collectives": {
                               "identity": identity_rec.n_collectives,
                               "searched": searched_rec.n_collectives,
                               "delta": (searched_rec.n_collectives
                                         - identity_rec.n_collectives)},
                           "recompiles": int(recompiles),
                           "fixed_point": bool(fixed_point)}
    deltas = [v["delta"] for v in per_op.values()]
    for key in ("makespan", "bottleneck_link_bytes", "dcn_bytes"):
        out[key] = {"identity": side_i[key], "searched": side_s[key],
                    "delta": side_s[key] - side_i[key]}
        deltas.append(out[key]["delta"])
    deltas.append(float(out["n_collectives"]["delta"]))
    out["max_abs_delta"] = float(np.max(np.abs(np.asarray(deltas)))
                                 if deltas else 0.0)
    return out


# ---------------------------------------------------------------------------
# The session
# ---------------------------------------------------------------------------

class PlacementSession:
    """One compile->measure->search->recompile session with a keyed
    compiled-cell cache (see module docstring).

    ``cache_dir=None`` resolves ``$REPRO_PLACEMENT_CACHE`` (default
    ``results/placement_cache``); pass ``cache_dir=""`` to keep the cache
    in-memory only. ``map_restarts``/``recursive``/``seed`` parameterize
    every search the session runs; ``max_rounds`` bounds the recompile
    fixed-point loop.

    ``machine`` (a ``core.machine.MachineSpec`` or preset name) is the
    session's default machine model: it supplies mesh shape/axes, the
    scored topology and the cache-key token for every ``measure``/``place``
    that does not name one explicitly. Without it, the historical
    ``multi_pod`` flag selects the TPU production presets.
    """

    def __init__(self, cache_dir: Optional[str] = None,
                 map_restarts: int = 32, recursive: bool = True,
                 seed: int = 0, max_rounds: int = 2,
                 min_gain: float = 1e-3, verbose: bool = False,
                 machine: Optional[Any] = None):
        if cache_dir is None:
            cache_dir = os.environ.get(_CACHE_ENV, _DEFAULT_CACHE_DIR)
        self.cache_dir = cache_dir
        self.machine = machine_lib.resolve(machine)
        self.map_restarts = map_restarts
        self.recursive = recursive
        self.seed = seed
        self.max_rounds = max_rounds
        # relative makespan improvement below which a searched order is
        # NOT adopted: permuting 512 devices for a noise-level gain can
        # still shuffle raw per-link loads (e.g. off the weighted DCN link
        # onto a hotter ICI link), so sub-min_gain wins keep identity
        self.min_gain = min_gain
        self.verbose = verbose
        self._mem: Dict[str, CellRecord] = {}
        self.n_compiles = 0
        self.n_cache_hits = 0

    # -- mesh construction (the only place launch/ builds meshes) ---------

    def build_mesh(self, mesh_shape: Sequence[int], axes: Sequence[str],
                   device_order: Optional[np.ndarray] = None):
        """Mesh with an explicit logical->physical order (identity when
        ``device_order=None``) — the session-owned front to
        ``mesh_lib.make_mapped_mesh``."""
        return mesh_lib.make_mapped_mesh(tuple(mesh_shape), tuple(axes),
                                         device_order)

    def local_mesh(self):
        """Identity 1-D 'data' mesh over whatever devices exist — the
        starting mesh :meth:`map_step` permutes (train/serve smoke)."""
        import jax
        return self.build_mesh((len(jax.devices()),), ("data",))

    def serving_mesh(self, device_order: Optional[np.ndarray] = None):
        """Production mesh when the device count matches a known machine
        (256/512 chips), local 1-D data mesh otherwise."""
        shape, axes = mesh_lib.serving_mesh_spec()
        return self.build_mesh(shape, axes, device_order)

    # -- machine resolution ------------------------------------------------

    def _resolve_machine(self, machine, mesh_shape, axes, multi_pod):
        """(spec, mesh_shape, axes): the machine model of one call.

        Precedence: explicit ``machine`` arg > session default >
        (when no explicit mesh either) the TPU production preset the
        historical ``multi_pod`` flag names. An explicit ``mesh_shape``
        with no machine anywhere runs machine-less (``mesh_tree`` guess),
        exactly the pre-MachineSpec behavior."""
        spec = machine_lib.resolve(machine) or self.machine
        if spec is None:
            if mesh_shape is None:
                spec = mesh_lib.production_machine(multi_pod)
            else:
                return None, tuple(mesh_shape), tuple(axes)
        if mesh_shape is None:
            mesh_shape, axes = spec.mesh_spec()
        elif tuple(mesh_shape) != spec.mesh_shape:
            raise ValueError(f"mesh_shape {tuple(mesh_shape)} does not "
                             f"match machine {spec.name!r} "
                             f"({spec.mesh_shape})")
        return spec, tuple(mesh_shape), tuple(axes)

    # -- compiled-cell cache ----------------------------------------------

    def _key(self, arch: str, shape: str, mesh_shape: Tuple[int, ...],
             axes: Tuple[str, ...], profile: str, grad_compress,
             overrides: Optional[Dict], device_order,
             machine: Optional[MachineSpec] = None) -> str:
        import jax
        order_tag = None
        if device_order is not None:
            order = np.asarray(device_order, dtype=np.int64)
            order_tag = hashlib.sha256(order.tobytes()).hexdigest()[:16]
        payload = {"arch": arch, "shape": shape,
                   "mesh": list(mesh_shape), "axes": list(axes),
                   # str() keeps True (flat scale) distinct from 1 (block=1)
                   "profile": profile, "grad_compress": str(grad_compress),
                   "overrides": sorted((overrides or {}).items()),
                   "order": order_tag, "jax": jax.__version__,
                   # backend matters: a host-compiled record must never be
                   # served to a TPU run of the same checkout
                   "backend": jax.default_backend(),
                   "n_dev": len(jax.devices()),
                   # machine model: editing a registered spec must
                   # invalidate records keyed under its name
                   "machine": (machine.cache_token()
                               if machine is not None else None),
                   "src": _source_fingerprint()}
        return hashlib.sha256(
            json.dumps(payload, sort_keys=True).encode()).hexdigest()[:24]

    def _cache_path(self, key: str) -> str:
        return os.path.join(self.cache_dir, f"cell_{key}.npz")

    def _load(self, key: str) -> Optional[CellRecord]:
        if not self.cache_dir:
            return None
        path = self._cache_path(key)
        if not os.path.exists(path):
            return None
        try:
            with np.load(path, allow_pickle=False) as z:
                meta = json.loads(str(z["meta"]))
                traffic = np.asarray(z["traffic"])
            meta["mesh_shape"] = tuple(meta["mesh_shape"])
            meta["axes"] = tuple(meta["axes"])
            return CellRecord(**meta, traffic=traffic, cached=True)
        except Exception:     # corrupt or schema-stale entry: recompile
            return None

    def _store(self, key: str, rec: CellRecord) -> None:
        if not self.cache_dir:
            return
        os.makedirs(self.cache_dir, exist_ok=True)
        meta = dataclasses.asdict(rec)
        meta.pop("traffic")
        meta.pop("cached")
        path = self._cache_path(key)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez_compressed(f, meta=np.asarray(json.dumps(meta)),
                                traffic=np.asarray(rec.traffic))
        os.replace(tmp, path)             # atomic: readers never see halves

    # -- measure: one cell, cache-aware -----------------------------------

    def measure(self, arch_name: str, shape_name: str, *,
                mesh_shape: Optional[Sequence[int]] = None,
                axes: Optional[Sequence[str]] = None,
                multi_pod: bool = False, profile: str = "2d",
                grad_compress=False,
                overrides: Optional[Dict[str, Any]] = None,
                device_order: Optional[np.ndarray] = None,
                machine: Optional[Any] = None) -> CellRecord:
        """The compiled-cell entry: cache hit or compile-and-extract.

        Returns the :class:`CellRecord` of the cell compiled on the mesh
        built with ``device_order`` (identity when None). ``mesh_shape``/
        ``axes`` default to the mesh of ``machine`` (a MachineSpec or
        preset name; session default when unset), falling back to the TPU
        production preset selected by ``multi_pod``.
        """
        spec, mesh_shape, axes = self._resolve_machine(
            machine, mesh_shape, axes, multi_pod)
        key = self._key(arch_name, shape_name, mesh_shape, axes, profile,
                        grad_compress, overrides, device_order, spec)
        rec = self._mem.get(key)
        if rec is None:
            rec = self._load(key)
            if rec is not None:
                self._mem[key] = rec
        if rec is not None:
            self.n_cache_hits += 1
            if self.verbose:
                print(f"[PLACE] cache hit {arch_name}/{shape_name}/"
                      f"{profile} key={key}", flush=True)
            return dataclasses.replace(rec, cached=True)
        rec = self._compile_and_measure(arch_name, shape_name, mesh_shape,
                                        axes, profile, grad_compress,
                                        overrides, device_order)
        self.n_compiles += 1
        self._mem[key] = rec
        self._store(key, rec)
        if self.verbose:
            print(f"[PLACE] compiled {arch_name}/{shape_name}/{profile} "
                  f"in {rec.compile_s:.1f}s key={key}", flush=True)
        return rec

    def _compile_and_measure(self, arch_name, shape_name, mesh_shape, axes,
                             profile, grad_compress, overrides,
                             device_order) -> CellRecord:
        import jax

        from repro import configs
        from repro.dist.sharding import sanitize_tree, tree_shardings
        from repro.launch.steps import build_cell, rules_for

        arch = configs.get(arch_name)
        shape = arch.shapes[shape_name]
        order = (None if device_order is None
                 else np.asarray(device_order, dtype=np.int64))
        mesh = self.build_mesh(mesh_shape, axes, order)
        chips = int(np.prod(mesh.devices.shape))
        rules = rules_for(arch.family, mesh.axis_names, profile=profile)
        cell = build_cell(arch, shape, rules, grad_compress=grad_compress,
                          overrides=overrides)
        specs = tuple(sanitize_tree(sds, spec, mesh) for sds, spec in
                      zip(cell["args_sds"], cell["args_specs"]))
        shardings = tuple(tree_shardings(mesh, spec) for spec in specs)
        t0 = time.time()
        with mesh:
            jitted = jax.jit(cell["step"], in_shardings=shardings)
            compiled = jitted.lower(*cell["args_sds"]).compile()
        compile_s = time.time() - t0
        hlo = compiled.as_text()
        coll = parse_collectives(hlo, chips, cell["scan_lengths"],
                                 traffic=True)
        try:
            mem = compiled.memory_analysis()
            mem_info = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes",
                                          None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            }
        except Exception:                                # pragma: no cover
            mem_info = {}
        agg = compiled.cost_analysis() or {}
        agg_flops = float(agg.get("flops", 0.0))
        agg_bytes = float(agg.get("bytes accessed", 0.0))
        del compiled

        # loop-aware totals from the text cost model (hlo_cost.py)
        t0 = time.time()
        comps, entry = hlo_cost.parse(hlo)
        mult = (hlo_cost.multipliers(comps, entry) if entry else {})
        cal = {k: 0.0 for k in ("flops", "bytes", "bytes_fused",
                                "bytes_tight", "bytes_tight_f32",
                                "transcendentals")}
        bytes_deep = 0.0     # tight-HBM bytes strictly inside nested whiles
        deep_threshold = (max(cell["scan_lengths"]) if cell["scan_lengths"]
                          else 1)
        for name, m in mult.items():
            c = comps[name]
            cal["flops"] += m * c.flops
            cal["bytes"] += m * c.bytes
            cal["bytes_fused"] += m * c.bytes_fused
            cal["bytes_tight"] += m * (c.bytes_tight
                                       - 0.5 * c.bytes_tight_f32)
            cal["bytes_tight_f32"] += m * c.bytes_tight_f32
            cal["transcendentals"] += m * c.transcendentals
            if m > deep_threshold:
                bytes_deep += m * (c.bytes_tight - 0.5 * c.bytes_tight_f32)
        calibrate_s = time.time() - t0
        jax.clear_caches()

        return CellRecord(
            arch=arch_name, shape=shape_name, mesh_shape=mesh_shape,
            axes=axes, profile=profile,
            device_order=None if order is None else order.tolist(),
            compile_s=round(compile_s, 2),
            calibrate_s=round(calibrate_s, 2),
            scan_lengths=list(cell["scan_lengths"]),
            link=coll["link"], operand=coll["operand"],
            link_bf16=coll["link_bf16"], n_collectives=coll["count"],
            agg_flops=agg_flops, agg_bytes=agg_bytes, memory=mem_info,
            hlo_cal=cal, bytes_deep=bytes_deep, traffic=coll["traffic"])

    # -- place: the full searched-placement loop --------------------------

    def place(self, arch_name: str, shape_name: str, *,
              mesh_shape: Optional[Sequence[int]] = None,
              axes: Optional[Sequence[str]] = None,
              multi_pod: bool = False, profile: str = "2d",
              grad_compress=False,
              overrides: Optional[Dict[str, Any]] = None,
              recompile: bool = False,
              machine: Optional[Any] = None) -> PlacementResult:
        """Compile (cache-aware), search the device order, optionally
        recompile under it to a fixed point; return record + report.

        The monotone guard keeps the best-seen order by the makespan of
        the *latest measured schedule*: every round's search carries the
        prior winner as a warm start, identity is always candidate 0, and
        if the final searched schedule still loses to identity's the
        report falls back to the identity order — "searched <= identity"
        holds on measured schedules, not just on the round-0 model.

        ``machine`` (MachineSpec or preset name) supplies mesh + scored
        topology declaratively — tree machines search against their F_l
        tree, routing machines (torus presets) through the dense oracle.
        """
        if recompile and self.max_rounds < 1:
            raise ValueError("recompile=True needs max_rounds >= 1 — the "
                             "session never ships an order whose schedule "
                             "was not actually compiled")
        spec, mesh_shape, axes = self._resolve_machine(
            machine, mesh_shape, axes, multi_pod)
        d = int(np.prod(mesh_shape))
        topo = (spec.topology() if spec is not None
                else topology.mesh_tree(mesh_shape))
        depths = _link_depths(topo)
        ident = np.arange(d)
        compiles0, hits0 = self.n_compiles, self.n_cache_hits

        rec0 = self.measure(arch_name, shape_name, mesh_shape=mesh_shape,
                            axes=axes, profile=profile,
                            grad_compress=grad_compress,
                            overrides=overrides, machine=spec)
        t0 = time.time()
        best = mapping.search(mesh_shape, topo, rec0.traffic,
                              n_random=self.map_restarts,
                              recursive=self.recursive, seed=self.seed)
        identity_side = _side_metrics(rec0.traffic, topo, ident, depths)
        best_order = np.asarray(best.device_to_bin, dtype=np.int64)
        if best.bottleneck >= identity_side["makespan"] * (1.0
                                                          - self.min_gain):
            # sub-min_gain win: not worth perturbing the placement
            best_order = ident
        rounds: List[Dict[str, Any]] = [{
            "round": 0, "recompiled": False,
            # the makespan actually kept (identity's when the min_gain
            # guard rejected the searched order)
            "makespan": float(best.bottleneck
                              if not np.array_equal(best_order, ident)
                              else identity_side["makespan"]),
            "n_candidates": int(best.n_candidates),
            "order_changed": bool(not np.array_equal(best_order, ident))}]
        if np.array_equal(best_order, ident):
            axis_perm = list(range(len(mesh_shape)))
            axis_orders = [0] * len(mesh_shape)
        else:
            axis_perm = list(best.axis_perm)
            axis_orders = list(best.axis_orders)

        rec_s: Optional[CellRecord] = None
        fixed_point = True
        if recompile:
            for rnd in range(1, self.max_rounds + 1):
                if np.array_equal(best_order, ident):
                    # identity won: its recompile IS the identity compile
                    rec_s = rec0
                    break
                rec_r = self.measure(arch_name, shape_name,
                                     mesh_shape=mesh_shape, axes=axes,
                                     profile=profile,
                                     grad_compress=grad_compress,
                                     overrides=overrides,
                                     device_order=best_order,
                                     machine=spec)
                rec_s = rec_r
                # score the incumbent on the schedule it actually produced,
                # then search that schedule with the incumbent warm-started
                prev_cost = mapping.makespan_of_device_map(
                    rec_r.traffic, topo, best_order)
                cur = mapping.search(mesh_shape, topo, rec_r.traffic,
                                     warm_starts=[best_order],
                                     n_random=self.map_restarts,
                                     recursive=self.recursive,
                                     seed=self.seed)
                changed = not np.array_equal(cur.device_to_bin, best_order)
                improved = cur.bottleneck < prev_cost * (1.0
                                                         - self.min_gain)
                # adopt only while budget remains to recompile-and-measure
                # the new order next round: the session never ships an
                # order whose schedule was not actually compiled
                adopt = changed and improved and rnd < self.max_rounds
                rounds.append({
                    "round": rnd, "recompiled": True,
                    # the makespan actually kept: cur's when adopted, the
                    # measured incumbent's otherwise
                    "makespan": float(cur.bottleneck if adopt
                                      else prev_cost),
                    "n_candidates": int(cur.n_candidates),
                    "order_changed": bool(adopt)})
                if adopt:
                    best = cur
                    best_order = np.asarray(cur.device_to_bin,
                                            dtype=np.int64)
                    axis_perm = list(cur.axis_perm)
                    axis_orders = list(cur.axis_orders)
                else:
                    # fixed point when the search stopped moving; False
                    # when the budget ran out mid-descent (the incumbent,
                    # already measured, is kept)
                    fixed_point = not (changed and improved)
                    break

        # the searched side is judged on its own measured schedule
        rec_for_side = rec_s if rec_s is not None else rec0
        searched_side = _side_metrics(rec_for_side.traffic, topo,
                                      best_order, depths)
        if searched_side["makespan"] > identity_side["makespan"]:
            # monotone guard: never ship an order that loses to identity
            # on the measured schedule. Shipping identity means running
            # the identity compile, so the searched side IS rec0's.
            best_order = ident
            axis_perm = list(range(len(mesh_shape)))
            axis_orders = [0] * len(mesh_shape)
            rec_for_side = rec0
            searched_side = dict(identity_side)
        diff = None
        if recompile:
            diff = schedule_diff(rec0, rec_for_side, topo, ident,
                                 best_order,
                                 recompiles=sum(r["recompiled"]
                                                for r in rounds),
                                 fixed_point=fixed_point)
        report = PlacementReport(
            arch=arch_name, shape=shape_name, profile=profile,
            mesh="x".join(str(s) for s in mesh_shape),
            identity=_json_sides(identity_side),
            searched=_json_sides(searched_side),
            makespan_ratio=(searched_side["makespan"]
                            / identity_side["makespan"]
                            if identity_side["makespan"] > 0 else 1.0),
            axis_perm=[int(p) for p in axis_perm],
            axis_orders=[int(o) for o in axis_orders],
            n_candidates=int(best.n_candidates),
            device_order=[int(x) for x in best_order],
            total_link_bytes=float(np.asarray(rec0.traffic).sum() / 2.0),
            search_s=round(time.time() - t0, 2),
            rounds=rounds, schedule_diff=diff,
            n_compiles=self.n_compiles - compiles0,
            cache_hits=self.n_cache_hits - hits0)
        return PlacementResult(record=rec0, report=report,
                               searched_record=rec_s if recompile else None)

    # -- verify: the static-analysis hook ---------------------------------

    def verify(self, *, kernels: bool = True, traffic: bool = True):
        """Static analysis over everything this session touches
        (``repro.analysis``; DESIGN.md §Static-analysis): the registered
        Pallas kernel plans (grid/BlockSpec/VMEM/write-race proofs) and
        the measured traffic matrix of every cached :class:`CellRecord`
        (symmetry, non-negativity, zero diagonal). Returns the Finding
        list — ``--lint`` on the launchers gates on error severity."""
        from repro.analysis import kernels as akernels
        from repro.analysis import shard_lint
        findings = []
        if kernels:
            findings.extend(akernels.verify_all())
        if traffic:
            for rec in self._mem.values():
                if rec.traffic is None:
                    continue
                findings.extend(shard_lint.lint_traffic(
                    np.asarray(rec.traffic),
                    subject=f"{rec.arch}/{rec.shape}/{rec.profile}"))
        return findings

    # -- map_pages: place a paged KV pool (serving) -----------------------

    def map_pages(self, traffic: np.ndarray, *,
                  node_weight: Optional[np.ndarray] = None,
                  n_devices: Optional[int] = None,
                  machine: Optional[Any] = None,
                  current: Optional[np.ndarray] = None,
                  seeds: int = 1):
        """Pages-as-rows placement for the serving KV pool.

        ``traffic`` is the measured [n_pages, n_pages] co-access matrix
        (``serving.PagedKVCache.page_traffic``), ``node_weight`` the
        per-page access counts; vertices are pages and the bins are the
        leaves of the machine tree (``machine``/session default, else
        ``guess_tree(n_devices)``), so the full multilevel partitioner
        optimizes exactly the paper's capacity-normalized makespan over
        hot pages. The matrix is linted first (same invariants as device
        traffic: square, finite, symmetric, zero diagonal) — a malformed
        matrix is a serving bug, not a placement preference.

        ``current`` (the live assignment) prices drift:
        ``drift_ratio = makespan(current on this traffic) /
        makespan(searched)``; the engine re-places when it exceeds
        ``1 + drift_threshold``. Returns a
        ``serving.kv_cache.PagePlacement``.
        """
        from repro.analysis import shard_lint
        from repro.core import baselines
        from repro.core.partitioner import PartitionConfig, partition
        from repro.core.topology import guess_tree
        from repro.graph.graph import from_edges
        from repro.serving.kv_cache import PagePlacement

        traffic = np.asarray(traffic, dtype=np.float64)
        findings = shard_lint.lint_traffic(traffic, subject="page-traffic")
        errors = [f for f in findings if f.severity == "error"]
        if errors:
            raise ValueError("malformed page-traffic matrix: "
                             + "; ".join(f.message for f in errors))
        n = traffic.shape[0]
        spec = machine_lib.resolve(machine) or self.machine
        if spec is not None:
            topo = spec.tree()
        else:
            if not n_devices or n_devices < 1:
                raise ValueError("map_pages needs a machine or n_devices")
            topo = guess_tree(int(n_devices))
        if topo.bin_speed is not None and not (topo.bin_speed > 0).all():
            raise ValueError("zero-capacity bin reached the page mapper — "
                             "degrade() masks dead leaves; never zero a "
                             "bin_speed entry")
        k = topo.k
        nw = (np.asarray(node_weight, dtype=np.float64)
              if node_weight is not None else traffic.sum(axis=1))
        # every page gets a positive weight so cold pages still spread
        nw = np.maximum(nw, max(float(nw.max()), 1.0) * 1e-3)
        iu = np.triu_indices(n, 1)
        w = traffic[iu]
        nz = w > 0
        g = (from_edges(n, iu[0][nz], iu[1][nz], w[nz].astype(np.float32),
                        nw.astype(np.float32)) if nz.any() else None)
        if g is None or n <= k:
            # degenerate epochs (no co-access yet, or fewer pages than
            # bins): balanced contiguous blocks
            part = (np.arange(n) * k) // max(n, 1)
            makespan = (float(baselines.score_all(g, topo,
                                                  part)["makespan"])
                        if g is not None else 0.0)
        else:
            res = partition(g, topo, PartitionConfig(seed=self.seed,
                                                     seeds=seeds))
            part, makespan = res.part, float(res.makespan)
        drift = float("inf")
        if current is not None:
            current = np.asarray(current)
            if current.shape != (n,):
                raise ValueError(f"current assignment must be [{n}], got "
                                 f"{list(current.shape)}")
            if g is None:
                drift = 1.0
            else:
                cur_ms = baselines.score_all(g, topo, current)["makespan"]
                drift = (float(cur_ms) / makespan if makespan > 0
                         else (1.0 if cur_ms <= 0 else float("inf")))
        return PagePlacement(page_to_device=np.asarray(part,
                                                       dtype=np.int64),
                             n_devices=int(k), makespan=makespan,
                             drift_ratio=drift, replaced=False)

    # -- map_step: place an already-built step (train / serve) ------------

    def map_step(self, step, step_args, mesh, scan_lengths: Sequence[int],
                 *, tag: str = "step",
                 machine: Optional[Any] = None) -> Tuple[Any, PlacementReport]:
        """Compile a caller-built step on ``mesh`` (identity order), search
        the logical->physical mapping over the machine topology —
        ``machine`` (MachineSpec or preset name) when given, else the tree
        guessed from the mesh shape (``guess_tree`` for 1-D local meshes)
        — and return the mapped mesh plus the report. The trainer's
        ``searched_mesh`` and serve's ``--topology-aware`` are thin
        wrappers over this.
        """
        import jax
        mesh_shape = tuple(mesh.devices.shape)
        n_dev = int(np.prod(mesh_shape))
        spec = machine_lib.resolve(machine) or self.machine
        if spec is not None and spec.n_devices != n_dev:
            raise ValueError(f"machine {spec.name!r} has "
                             f"{spec.n_devices} devices, mesh has {n_dev}")
        t0 = time.time()
        with mesh:
            compiled = jax.jit(step).lower(*step_args).compile()
        compile_s = time.time() - t0
        coll = parse_collectives(compiled.as_text(), n_dev,
                                 list(scan_lengths), traffic=True)
        del compiled
        jax.clear_caches()
        self.n_compiles += 1
        topo = (spec.topology() if spec is not None
                else topology.mesh_tree(mesh_shape))
        depths = _link_depths(topo)
        t0 = time.time()
        best = mapping.search(mesh_shape, topo, coll["traffic"],
                              n_random=self.map_restarts,
                              recursive=self.recursive, seed=self.seed)
        ident = np.arange(n_dev)
        identity_side = _side_metrics(coll["traffic"], topo, ident, depths)
        if best.bottleneck >= identity_side["makespan"] * (1.0
                                                          - self.min_gain):
            # same min_gain policy as place(): noise-level wins keep the
            # identity mesh the caller already has
            best = dataclasses.replace(
                best, axis_perm=tuple(range(len(mesh_shape))),
                axis_orders=(0,) * len(mesh_shape),
                device_to_bin=ident, bottleneck=identity_side["makespan"])
        searched_side = _side_metrics(coll["traffic"], topo,
                                      best.device_to_bin, depths)
        mapped = self.build_mesh(mesh_shape, mesh.axis_names,
                                 best.device_to_bin)
        report = PlacementReport(
            arch=tag, shape="", profile="",
            mesh="x".join(str(s) for s in mesh_shape),
            identity=_json_sides(identity_side),
            searched=_json_sides(searched_side),
            makespan_ratio=(searched_side["makespan"]
                            / identity_side["makespan"]
                            if identity_side["makespan"] > 0 else 1.0),
            axis_perm=[int(p) for p in best.axis_perm],
            axis_orders=[int(o) for o in best.axis_orders],
            n_candidates=int(best.n_candidates),
            device_order=[int(x) for x in best.device_to_bin],
            total_link_bytes=float(coll["traffic"].sum() / 2.0),
            search_s=round(time.time() - t0 + compile_s, 2),
            rounds=[{"round": 0, "recompiled": False,
                     "makespan": float(best.bottleneck),
                     "n_candidates": int(best.n_candidates),
                     "order_changed": bool(not np.array_equal(
                         best.device_to_bin, ident))}],
            schedule_diff=None, n_compiles=1, cache_hits=0)
        return mapped, report
