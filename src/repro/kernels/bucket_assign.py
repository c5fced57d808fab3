"""Capacity-boundary bucket assignment for the device initial partition.

The device V-cycle's initial assignment (``core.initial
.initial_partition_device``) replaces the host's sequential greedy grow
with a capacity-proportional prefix split: vertex ``v`` with weight
midpoint ``cum[v]`` (inclusive prefix sum of node weights minus half its
own weight) lands in bin

    bin[v] = #{ i < k-1 : cum[v] >= boundary[i] }

where ``boundary`` holds the k-1 interior capacity prefix targets. On TPU
this is a ``[rows, 128]`` VPU tile streaming over a boundary table kept
whole in VMEM (every grid point reads block (0, 0)), accumulating the
comparison counts in an int32 register tile — a fused searchsorted that
never leaves VMEM. The table holds boundary ``i`` broadcast across the
128 lanes of row ``i``, so the loop reads one sublane row per boundary
(the TPU lowering has no dynamic lane slice). Padding boundary rows are
+inf so they never count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.plan import KernelPlan

_LANES = 128


def _kernel(cum_ref, bound_ref, out_ref, *, k_pad: int):
    cum = cum_ref[...]                       # [R, 128] f32
    r = cum.shape[0]

    def body(i, acc):
        b = bound_ref[pl.ds(i, 1), :]        # [1, 128] boundary i, +inf pad
        return acc + (cum >= b).astype(jnp.int32)

    out_ref[...] = jax.lax.fori_loop(
        0, k_pad, body, jnp.zeros((r, _LANES), jnp.int32))


def plan(n: int, k: int, *, row_blk: int = 256) -> KernelPlan:
    """Static call plan: one ``[row_blk, 128]`` vertex tile per grid point,
    the (padded) ``[k_pad, 128]`` boundary table resident whole-block, no
    output revisits."""
    rows = max((n + _LANES - 1) // _LANES, 1)
    rows_pad = ((rows + row_blk - 1) // row_blk) * row_blk
    k_pad = ((max(k - 1, 1) + 7) // 8) * 8
    return KernelPlan(
        name="bucket_assign",
        grid=(rows_pad // row_blk,),
        in_specs=(
            pl.BlockSpec((row_blk, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((k_pad, _LANES), lambda i: (0, 0)),
        ),
        out_specs=(pl.BlockSpec((row_blk, _LANES), lambda i: (i, 0)),),
        operands=(jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.float32),
                  jax.ShapeDtypeStruct((k_pad, _LANES), jnp.float32)),
        outputs=(jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.int32),),
        meta=dict(rows_pad=rows_pad, k_pad=k_pad),
    )


def example_plan() -> KernelPlan:
    return plan(n=4096, k=64)


@functools.partial(jax.jit, static_argnames=("k", "row_blk", "interpret"))
def bucket_assign_tiled(cum: jnp.ndarray, boundaries: jnp.ndarray, *,
                        k: int, row_blk: int = 256,
                        interpret: bool = False) -> jnp.ndarray:
    """Bin index of every vertex-weight midpoint. [n] int32 in [0, k-1]

    ``cum``: [n] midpoints; ``boundaries``: [k-1] interior capacity prefix
    targets (non-decreasing).
    """
    n = cum.shape[0]
    p = plan(n, k, row_blk=row_blk)
    rows_pad, k_pad = p.meta["rows_pad"], p.meta["k_pad"]
    cum2 = jnp.pad(cum.astype(jnp.float32),
                   (0, rows_pad * _LANES - n)).reshape(rows_pad, _LANES)
    b2 = jnp.broadcast_to(
        jnp.pad(boundaries.astype(jnp.float32),
                (0, k_pad - boundaries.shape[0]),
                constant_values=jnp.inf)[:, None], (k_pad, _LANES))
    out = pl.pallas_call(
        functools.partial(_kernel, k_pad=k_pad),
        grid=p.grid,
        in_specs=list(p.in_specs),
        out_specs=p.out_specs[0],
        out_shape=p.outputs[0],
        interpret=interpret,
    )(cum2, b2)
    return out.reshape(-1)[:n]
