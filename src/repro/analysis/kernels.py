"""Static Pallas-kernel verifier: prove tiling/race properties of a
``KernelPlan`` without executing a single kernel step.

Because every ``pl.pallas_call`` in ``repro.kernels`` is constructed from
the same :class:`~repro.kernels.plan.KernelPlan` object that is registered
for verification (``KERNEL_REGISTRY``), a clean verdict here is a proof
about the *executed* tiling, not about a parallel description that can
drift.

Checks (check id -> what a clean pass proves):

  * ``grid`` — grid dims are positive static ints.
  * ``block-rank`` / ``block-divisibility`` — every BlockSpec's rank
    matches its operand and every block dim divides the (padded) operand
    dim: no partial edge blocks the kernel body doesn't expect.
  * ``index-purity`` — every index map evaluates under plain Python ints
    to plain ints: no index map closes over a traced value or array (the
    hazard ``flash_attention.py`` documents by convention), so the block
    schedule is compile-time static.
  * ``block-bounds`` — over the enumerated grid, every block index stays
    inside its operand: no out-of-bounds DMA.
  * ``tiling-alignment`` (warning) — block minor dim is a multiple of the
    128-lane register tile and the second-minor a multiple of the per-dtype
    sublane count (f32 8, bf16 16, int8 32), unless the block spans the
    whole operand dim (Pallas masks the edge; legal but slow).
  * ``vmem-budget`` — in/out blocks + scratch fit the per-kernel VMEM
    budget: the call cannot fail allocation at compile time on hardware.
  * ``write-race`` — two distinct grid points whose out-spec index maps
    collide on the same output block are an error unless the axes they
    differ in are declared sequential-revisit axes (``seq_axes``) carrying
    state (VMEM scratch, or in-place output accumulation) — the
    flash-attention ``nk`` / bsr accumulation pattern. ``seq_axes`` must be
    the trailing (innermost, sequentially executed) grid axes; declaring a
    non-trailing axis is itself an error, because only innermost revisits
    are consecutive on the TPU's sequential grid.

Grids larger than ``max_grid_points`` are verified on a per-axis boundary
sample (first/second/middle/last points) and flagged with an ``info``
finding — exhaustiveness is the default, sampling is never silent.
"""
from __future__ import annotations

import inspect
import itertools
import numbers
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.analysis import Finding
from repro.kernels import KERNEL_REGISTRY
from repro.kernels.plan import KernelPlan

# sublane multiple of the second-minor block dim, by operand itemsize
_SUBLANE = {8: 4, 4: 8, 2: 16, 1: 32}
_LANE = 128

MAX_GRID_POINTS = 65536


def _dtype_of(x) -> np.dtype:
    return np.dtype(getattr(x, "dtype", x))


def _is_static_int(v) -> bool:
    if isinstance(v, jax.core.Tracer):
        return False
    if isinstance(v, jax.Array):      # concrete device array: still traced
        return v.ndim == 0 and False  # never acceptable statically
    return isinstance(v, numbers.Integral) or (
        isinstance(v, np.generic) and np.issubdtype(v.dtype, np.integer))


def _closure_values(fn) -> List[Any]:
    fn = inspect.unwrap(fn)           # Pallas wraps index maps
    vals = list(fn.__defaults__ or ())
    for cell in fn.__closure__ or ():
        try:
            vals.append(cell.cell_contents)
        except ValueError:            # empty cell
            pass
    return vals


def _grid_points(grid: Sequence[int],
                 max_points: int) -> Tuple[List[Tuple[int, ...]], bool]:
    """All grid points, or a per-axis boundary sample when the full
    product exceeds ``max_points``. Returns (points, sampled)."""
    total = int(np.prod(grid)) if grid else 0
    if total <= max_points:
        return [tuple(p) for p in itertools.product(
            *(range(g) for g in grid))], False
    axes = []
    for g in grid:
        picks = sorted({0, 1, g // 2, g - 2, g - 1} & set(range(g)))
        axes.append(picks)
    return [tuple(p) for p in itertools.product(*axes)], True


def _block_bytes(specs, avals) -> int:
    return sum(int(np.prod(s.block_shape)) * _dtype_of(a).itemsize
               for s, a in zip(specs, avals))


def _scratch_bytes(scratch_shapes) -> int:
    total = 0
    for s in scratch_shapes:
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", np.float32)
        if shape is None:
            continue
        total += int(np.prod(shape)) * _dtype_of(dtype).itemsize
    return total


def verify_plan(plan: KernelPlan, *,
                max_grid_points: int = MAX_GRID_POINTS) -> List[Finding]:
    """Run every static check against one plan; findings, not exceptions."""
    subject = f"kernels/{plan.name}"
    out: List[Finding] = []

    # -- grid ------------------------------------------------------------
    if not plan.grid or not all(_is_static_int(g) and int(g) >= 1
                                for g in plan.grid):
        out.append(Finding("grid", "error", subject,
                           f"grid {plan.grid!r} must be non-empty "
                           "positive static ints"))
        return out
    grid = tuple(int(g) for g in plan.grid)

    # -- seq_axes declaration --------------------------------------------
    seq = tuple(sorted(int(a) for a in plan.seq_axes))
    if seq and seq != tuple(range(len(grid) - len(seq), len(grid))):
        out.append(Finding(
            "write-race", "error", subject,
            f"seq_axes {seq} are not the trailing grid axes of "
            f"{len(grid)}-d grid — only innermost revisits are "
            "consecutive on the sequential TPU grid",
            {"seq_axes": list(seq), "grid": list(grid)}))
    if seq and not plan.scratch_shapes and not plan.out_accumulate:
        out.append(Finding(
            "write-race", "error", subject,
            f"seq_axes {seq} declared but the kernel carries no state "
            "across revisits (no VMEM scratch, out_accumulate=False)",
            {"seq_axes": list(seq)}))

    # -- per-spec shape checks -------------------------------------------
    all_specs = list(zip(plan.in_specs, plan.operands,
                         itertools.repeat("in"))) \
        + list(zip(plan.out_specs, plan.outputs, itertools.repeat("out")))
    for idx, (spec, aval, side) in enumerate(all_specs):
        tag = f"{side}_specs[{idx if side == 'in' else idx - len(plan.in_specs)}]"
        block = tuple(spec.block_shape)
        shape = tuple(aval.shape)
        if len(block) != len(shape):
            out.append(Finding(
                "block-rank", "error", subject,
                f"{tag} block {block} has rank {len(block)} but operand "
                f"is rank {len(shape)} {shape}",
                {"spec": tag, "block": list(block),
                 "operand": list(shape)}))
            continue
        bad = [i for i, (b, s) in enumerate(zip(block, shape))
               if b <= 0 or s % b != 0]
        if bad:
            out.append(Finding(
                "block-divisibility", "error", subject,
                f"{tag} block {block} does not divide padded operand "
                f"{shape} on dims {bad}",
                {"spec": tag, "block": list(block), "operand": list(shape),
                 "dims": bad}))
        itemsize = _dtype_of(aval).itemsize
        sub = _SUBLANE.get(itemsize, 8)
        if len(block) >= 1 and block[-1] != shape[-1] \
                and block[-1] % _LANE != 0:
            out.append(Finding(
                "tiling-alignment", "warning", subject,
                f"{tag} minor block dim {block[-1]} is neither the whole "
                f"operand dim {shape[-1]} nor a multiple of {_LANE} lanes",
                {"spec": tag, "block": list(block), "lane": _LANE}))
        if len(block) >= 2 and block[-2] != shape[-2] \
                and block[-2] % sub != 0:
            out.append(Finding(
                "tiling-alignment", "warning", subject,
                f"{tag} second-minor block dim {block[-2]} is neither the "
                f"whole operand dim {shape[-2]} nor a multiple of the "
                f"{sub}-sublane tile for itemsize {itemsize}",
                {"spec": tag, "block": list(block), "sublane": sub}))

    # -- index-map purity: closures first --------------------------------
    for idx, (spec, _aval, side) in enumerate(all_specs):
        for v in _closure_values(spec.index_map):
            if isinstance(v, (jax.core.Tracer, jax.Array)):
                out.append(Finding(
                    "index-purity", "error", subject,
                    f"{side} index map closes over a traced/device value "
                    f"of type {type(v).__name__} — BlockSpec index maps "
                    "must be pure functions of the grid ids",
                    {"side": side, "index": idx}))

    # -- grid enumeration: bounds + purity + races -----------------------
    points, sampled = _grid_points(grid, max_grid_points)
    if sampled:
        out.append(Finding(
            "grid-sampled", "info", subject,
            f"grid of {int(np.prod(grid))} points exceeds "
            f"{max_grid_points}; verified on a {len(points)}-point "
            "boundary sample", {"points": len(points)}))

    def eval_map(spec, point):
        return spec.index_map(*point, *plan.index_args)

    impure = set()
    oob = 0
    writers: Dict[Tuple[int, Tuple[int, ...]], Tuple[int, ...]] = {}
    race_reported = False
    for point in points:
        for idx, (spec, aval, side) in enumerate(all_specs):
            key = (side, idx)
            if key in impure:
                continue
            try:
                bidx = eval_map(spec, point)
            except Exception as e:
                impure.add(key)
                out.append(Finding(
                    "index-purity", "error", subject,
                    f"{side} index map [{idx}] failed at grid point "
                    f"{point}: {type(e).__name__}: {e}",
                    {"side": side, "point": list(point)}))
                continue
            bidx = bidx if isinstance(bidx, tuple) else (bidx,)
            if not all(_is_static_int(b) for b in bidx):
                impure.add(key)
                out.append(Finding(
                    "index-purity", "error", subject,
                    f"{side} index map [{idx}] returned non-static block "
                    f"index {bidx!r} at grid point {point} — traced "
                    "values in index maps make the schedule dynamic",
                    {"side": side, "point": list(point)}))
                continue
            bidx = tuple(int(b) for b in bidx)
            block = tuple(spec.block_shape)
            shape = tuple(aval.shape)
            if len(bidx) != len(block):
                impure.add(key)
                out.append(Finding(
                    "block-rank", "error", subject,
                    f"{side} index map [{idx}] returned {len(bidx)} "
                    f"coords for a rank-{len(block)} block",
                    {"side": side, "point": list(point)}))
                continue
            if oob < 8 and any(
                    b < 0 or (b + 1) * blk > s
                    for b, blk, s in zip(bidx, block, shape)):
                oob += 1
                out.append(Finding(
                    "block-bounds", "error", subject,
                    f"{side} block index {bidx} at grid point {point} "
                    f"exceeds operand {shape} with block {block}",
                    {"side": side, "point": list(point),
                     "block_index": list(bidx)}))
            if side != "out":
                continue
            out_idx = idx - len(plan.in_specs)
            prev = writers.get((out_idx, bidx))
            if prev is None:
                writers[(out_idx, bidx)] = point
                continue
            diff_axes = tuple(a for a in range(len(grid))
                              if prev[a] != point[a])
            if not set(diff_axes) <= set(seq) and not race_reported:
                race_reported = True
                out.append(Finding(
                    "write-race", "error", subject,
                    f"grid points {prev} and {point} both write output "
                    f"block {bidx} of out_specs[{out_idx}] but differ on "
                    f"non-sequential axes {diff_axes} "
                    f"(seq_axes={seq}) — concurrent/unsynchronized "
                    "writes to the same block",
                    {"points": [list(prev), list(point)],
                     "block_index": list(bidx),
                     "diff_axes": list(diff_axes)}))

    # -- VMEM footprint ---------------------------------------------------
    vmem = (_block_bytes(plan.in_specs, plan.operands)
            + _block_bytes(plan.out_specs, plan.outputs)
            + _scratch_bytes(plan.scratch_shapes))
    if vmem > plan.vmem_budget:
        out.append(Finding(
            "vmem-budget", "error", subject,
            f"resident VMEM footprint {vmem} B (in/out blocks + scratch) "
            f"exceeds budget {plan.vmem_budget} B",
            {"vmem_bytes": vmem, "budget": plan.vmem_budget}))
    else:
        out.append(Finding(
            "vmem-budget", "info", subject,
            f"resident VMEM footprint {vmem} B within "
            f"{plan.vmem_budget} B budget",
            {"vmem_bytes": vmem, "budget": plan.vmem_budget}))
    return out


def verify_kernel(name: str, **kwargs) -> List[Finding]:
    """Verify one registered kernel by name."""
    if name not in KERNEL_REGISTRY:
        return [Finding("registry", "error", f"kernels/{name}",
                        f"kernel {name!r} is not registered; known: "
                        f"{sorted(KERNEL_REGISTRY)}")]
    try:
        plan = KERNEL_REGISTRY[name]()
    except Exception as e:
        return [Finding("registry", "error", f"kernels/{name}",
                        f"example_plan() raised {type(e).__name__}: {e}")]
    return verify_plan(plan, **kwargs)


def verify_all(names: Optional[Sequence[str]] = None,
               **kwargs) -> List[Finding]:
    """Verify every registered kernel (the CLI / CI / session entry)."""
    out: List[Finding] = []
    for name in (names if names is not None else sorted(KERNEL_REGISTRY)):
        out.extend(verify_kernel(name, **kwargs))
    return out
