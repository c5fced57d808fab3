"""Sharding & placement lint: static checks on spec trees, jaxprs and
measured traffic matrices (DESIGN.md §Static-analysis).

Three families of checks, all emitting :class:`repro.analysis.Finding`:

  * :func:`lint_spec_tree` — walks a (ShapeDtypeStruct tree, PartitionSpec
    tree) pair the way ``dist.sharding.sanitize_tree`` does and flags:
    ``unknown-mesh-axis`` (error) — a spec names an axis the mesh does not
    have, the static twin of ``sanitize_spec(strict=True)``;
    ``duplicate-mesh-axis`` (error) — one spec claims the same mesh axis
    twice (a GSPMD compile error caught before compile); and
    ``replicated-param`` — a large tensor left fully replicated (error at
    ``replicated_error_bytes``, warning at ``replicated_warn_bytes``): a
    236B-parameter table that silently replicates onto every device is the
    classic sharding-table typo.
  * :func:`lint_jaxpr` — recursively scans a jitted step's jaxpr (scan/
    cond/while bodies included) for large bf16 -> f32
    ``convert_element_type`` ops: each is 2x HBM traffic the roofline's
    memory term did not budget for (warning; totals as info).
  * :func:`lint_traffic` — sanity of a measured ``[D, D]`` device-pair
    traffic matrix (``CellRecord.traffic``): square, finite, non-negative,
    zero diagonal, symmetric. The mapping search treats traffic as an
    undirected edge weighting; an asymmetric or negative matrix means the
    collective parser mis-attributed bytes.

:func:`lint_cell` composes the first two for one (arch, shape, profile)
cell via ``launch.steps.build_cell`` under ``jax.eval_shape`` /
``jax.make_jaxpr`` — no devices, no XLA compile.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr

from repro.analysis import Finding

REPLICATED_ERROR_BYTES = 2**28        # 256 MiB fully replicated -> error
REPLICATED_WARN_BYTES = 2**24         # 16 MiB -> warning
UPCAST_WARN_ELEMENTS = 1 << 22        # 4M-element bf16->f32 convert


def _spec_axes(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


def _leaf_name(path) -> str:
    return jax.tree_util.keystr(path)


def lint_spec_tree(sds_tree: Any, spec_tree: Any,
                   mesh_axes: Sequence[str], *, subject: str = "",
                   replicated_error_bytes: int = REPLICATED_ERROR_BYTES,
                   replicated_warn_bytes: int = REPLICATED_WARN_BYTES,
                   ) -> List[Finding]:
    """Lint one argument's spec tree against the mesh axis names (see
    module docstring). ``spec_tree`` leaves are PartitionSpecs or None
    (replicated), mirroring ``sds_tree`` exactly like ``sanitize_tree``."""
    axes = set(mesh_axes)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(sds_tree)
    spec_leaves = treedef.flatten_up_to(spec_tree)
    out: List[Finding] = []
    for (path, sds), spec in zip(leaves, spec_leaves):
        name = f"{subject}:{_leaf_name(path)}"
        shape = tuple(getattr(sds, "shape", ()))
        nbytes = int(np.prod(shape, dtype=np.int64)) \
            * np.dtype(sds.dtype).itemsize
        entries = () if spec is None else tuple(spec)
        claimed: set = set()
        used_any = False
        for dim, entry in enumerate(entries):
            for ax in _spec_axes(entry):
                if ax not in axes:
                    out.append(Finding(
                        "unknown-mesh-axis", "error", name,
                        f"dim {dim} names mesh axis {ax!r} but the mesh "
                        f"only has {sorted(axes)} — the spec would "
                        "silently drop it at sanitize time",
                        {"dim": dim, "axis": ax,
                         "mesh_axes": sorted(axes)}))
                    continue
                if ax in claimed:
                    out.append(Finding(
                        "duplicate-mesh-axis", "error", name,
                        f"mesh axis {ax!r} appears twice in spec "
                        f"{entries!r} — GSPMD rejects double-claimed "
                        "axes at compile time",
                        {"axis": ax}))
                claimed.add(ax)
                used_any = True
        if not used_any and nbytes >= replicated_warn_bytes:
            sev = ("error" if nbytes >= replicated_error_bytes
                   else "warning")
            out.append(Finding(
                "replicated-param", sev, name,
                f"{nbytes / 2**20:.0f} MiB tensor {shape} is fully "
                "replicated — every device holds a full copy",
                {"bytes": nbytes, "shape": list(shape)}))
    return out


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for u in v:
                if isinstance(u, ClosedJaxpr):
                    yield u.jaxpr


def lint_jaxpr(jaxpr: Any, *, subject: str = "",
               upcast_warn_elements: int = UPCAST_WARN_ELEMENTS,
               ) -> List[Finding]:
    """Scan a jaxpr (``jax.make_jaxpr`` result or raw ``Jaxpr``) for
    bf16 -> f32 upcasts; recursive over scan/while/cond sub-jaxprs. Inner
    (scan body) upcasts execute once per trip, so they dominate — each
    large site is one warning, plus one info total."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out: List[Finding] = []
    sites: dict = {}                  # shape -> site count

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "convert_element_type":
                aval = eqn.invars[0].aval
                new = np.dtype(eqn.params.get("new_dtype", np.float32))
                if (np.dtype(aval.dtype) == np.dtype(jax.numpy.bfloat16)
                        and new == np.dtype(np.float32)):
                    shape = tuple(aval.shape)
                    sites[shape] = sites.get(shape, 0) + 1
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    total_elems = 0
    n_sites = 0
    for shape, count in sorted(sites.items(),
                               key=lambda kv: -int(np.prod(kv[0]))):
        elems = int(np.prod(shape, dtype=np.int64))
        total_elems += elems * count
        n_sites += count
        if elems >= upcast_warn_elements:
            out.append(Finding(
                "bf16-upcast", "warning", subject,
                f"bf16 -> f32 upcast of {list(shape)} ({elems} elements) "
                f"at {count} site(s) — 2x the HBM traffic the bf16 path "
                "budgets",
                {"shape": list(shape), "elements": elems,
                 "sites": count}))
    if n_sites:
        out.append(Finding(
            "bf16-upcast", "info", subject,
            f"{n_sites} bf16 -> f32 upcast site(s), "
            f"{total_elems} elements total",
            {"sites": n_sites, "elements": total_elems}))
    return out


def lint_traffic(traffic: Any, *, subject: str = "",
                 rtol: float = 1e-5) -> List[Finding]:
    """Sanity of one measured device-pair traffic matrix (see module
    docstring); all violations are errors — the mapping search's scoring
    is meaningless on a malformed matrix."""
    out: List[Finding] = []
    if traffic is None:
        return [Finding("traffic-missing", "warning", subject,
                        "no traffic matrix recorded for this cell")]
    t = np.asarray(traffic, dtype=np.float64)
    if t.ndim != 2 or t.shape[0] != t.shape[1]:
        return [Finding("traffic-shape", "error", subject,
                        f"traffic matrix must be square 2-d, got "
                        f"{list(t.shape)}", {"shape": list(t.shape)})]
    if not np.all(np.isfinite(t)):
        out.append(Finding("traffic-finite", "error", subject,
                           "traffic matrix contains NaN/inf"))
        return out
    scale = max(float(np.abs(t).max()), 1.0)
    if float(t.min()) < -rtol * scale:
        out.append(Finding(
            "traffic-negative", "error", subject,
            f"negative device-pair bytes (min {float(t.min()):.3e}) — "
            "the collective parser mis-attributed traffic",
            {"min": float(t.min())}))
    diag = float(np.abs(np.diag(t)).max()) if t.shape[0] else 0.0
    if diag > rtol * scale:
        out.append(Finding(
            "traffic-diagonal", "error", subject,
            f"nonzero self-traffic on the diagonal (max {diag:.3e}) — "
            "a device never pays link bytes to itself",
            {"max_diag": diag}))
    asym = float(np.abs(t - t.T).max())
    if asym > rtol * scale:
        out.append(Finding(
            "traffic-asymmetric", "error", subject,
            f"asymmetric traffic (max |T - T^T| = {asym:.3e}) — the "
            "mapping search scores undirected pair weights",
            {"max_asym": asym}))
    return out


def lint_cell(arch_name: str, shape_name: Optional[str] = None, *,
              profile: str = "2d",
              mesh_axes: Sequence[str] = ("pod", "data", "model"),
              trace: bool = True,
              overrides: Optional[dict] = None) -> List[Finding]:
    """Spec-tree + jaxpr lint for one (arch, shape, profile) cell, fully
    static (eval_shape / make_jaxpr; no devices, no compile). The default
    mesh axes are the multi-pod production axes. ``shape_name=None`` picks
    the arch's first non-skip shape."""
    from repro import configs
    from repro.launch.steps import build_cell, rules_for

    arch = configs.get(arch_name)
    if shape_name is None:
        shape_name = next(s.name for s in arch.shapes.values()
                          if s.kind != "skip")
    shape = arch.shapes[shape_name]
    subject = f"{arch_name}/{shape_name}/{profile}"
    if shape.kind == "skip":
        return [Finding("cell-skip", "info", subject,
                        f"shape is skipped: {shape.skip_reason}")]
    rules = rules_for(arch.family, tuple(mesh_axes), profile=profile)
    cell = build_cell(arch, shape, rules, overrides=overrides)
    out: List[Finding] = []
    for i, (sds, spec) in enumerate(zip(cell["args_sds"],
                                        cell["args_specs"])):
        out.extend(lint_spec_tree(sds, spec, mesh_axes,
                                  subject=f"{subject}:arg{i}"))
    if trace:
        # steps call with_sharding_constraint, which needs an ambient mesh
        # to resolve axis names; a unit mesh (size 1 per axis, one local
        # device) keeps the trace fully static while satisfying it
        dev = np.asarray(jax.devices()[:1]).reshape(
            (1,) * len(tuple(mesh_axes)))
        with jax.sharding.Mesh(dev, tuple(mesh_axes)):
            jxp = jax.make_jaxpr(cell["step"])(*cell["args_sds"])
        out.extend(lint_jaxpr(jxp, subject=subject))
    return out
