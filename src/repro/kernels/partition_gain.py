"""Connectivity-row kernel for bottleneck refinement (ELL one-hot SpMM).

The dense refinement mode scores every vertex against every destination bin
(refine.py). Its hot spot is the connectivity matrix

    conn[v, j] = sum of w(v, u) over neighbors u with P(u) = j      [n, k]

— an SpMM of the adjacency with ``onehot(part)``. The graph is stored in ELL
form (fixed ``D`` neighbor slots per vertex, padded), so a row tile of
``conn`` is computed entirely in VMEM:

    acc[R, k] += nbr_w[:, d, None] * (nbr_bin[:, d, None] == iota_k)

over the D slots. The bin ids per slot (``part[nbr_idx]``) are gathered by
XLA before the call — bins change every refinement round, the ELL structure
never does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.plan import KernelPlan


def _kernel(nbr_bin_ref, nbr_w_ref, out_ref, *, k: int, d: int):
    bins = nbr_bin_ref[...]                # [R, D] int32, k = padding
    ws = nbr_w_ref[...]                    # [R, D] f32, 0 on padding
    r = bins.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1)
    acc = jnp.zeros((r, k), jnp.float32)
    # D is static and small (ELL width): unrolled static lane slices, since
    # the TPU lowering has no dynamic lane slice
    for i in range(d):
        acc += ws[:, i:i + 1] * (bins[:, i:i + 1] == iota).astype(jnp.float32)
    out_ref[...] = acc


def plan(n: int, d: int, k: int, *, row_blk: int = 256) -> KernelPlan:
    """Static call plan: one row tile per grid point, no output revisits."""
    n_pad = ((n + row_blk - 1) // row_blk) * row_blk
    return KernelPlan(
        name="partition_gain",
        grid=(n_pad // row_blk,),
        in_specs=(
            pl.BlockSpec((row_blk, d), lambda i: (i, 0)),
            pl.BlockSpec((row_blk, d), lambda i: (i, 0)),
        ),
        out_specs=(pl.BlockSpec((row_blk, k), lambda i: (i, 0)),),
        operands=(jax.ShapeDtypeStruct((n_pad, d), jnp.int32),
                  jax.ShapeDtypeStruct((n_pad, d), jnp.float32)),
        outputs=(jax.ShapeDtypeStruct((n_pad, k), jnp.float32),),
        meta=dict(n_pad=n_pad),
    )


def example_plan() -> KernelPlan:
    return plan(n=1000, d=8, k=8)


@functools.partial(jax.jit, static_argnames=("k", "row_blk", "interpret"))
def partition_gain_ell(nbr_bin: jnp.ndarray, nbr_w: jnp.ndarray, *, k: int,
                       row_blk: int = 256,
                       interpret: bool = False) -> jnp.ndarray:
    """conn[v, j] from ELL neighbor bins/weights. [n, k]

    ``nbr_bin``: [n, D] bin of each neighbor slot (k for padding slots);
    ``nbr_w``: [n, D] edge weight (0 for padding). Rows padded to row_blk.
    """
    n, d = nbr_bin.shape
    p = plan(n, d, k, row_blk=row_blk)
    n_pad = p.meta["n_pad"]
    nb = jnp.pad(nbr_bin.astype(jnp.int32), ((0, n_pad - n), (0, 0)),
                 constant_values=k)
    nw = jnp.pad(nbr_w.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, d=d),
        grid=p.grid,
        in_specs=list(p.in_specs),
        out_specs=p.out_specs[0],
        out_shape=p.outputs[0],
        interpret=interpret,
    )(nb, nw)
    return out[:n]
