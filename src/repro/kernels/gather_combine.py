"""Fused gather + bag-combine with scalar-prefetched row ids.

``bag_combine`` needs the caller to materialize the gathered ``[B, D, F]``
tensor in HBM before the reduction. For embedding-dim-256 bags of 50 that
is 50x the output bytes. This kernel fuses the gather into the BlockSpec
index map instead — the flat bag ids are scalar-prefetched (the
``bsr_spmm`` idiom), so each grid step DMAs exactly one table row tile
into VMEM and accumulates it into the resident output block:

    grid = (B, F // feat_blk, D)        # D innermost: out revisits
    out[b, f] += w[b*D + d] * table[ids[b*D + d], f]

The bag axis ``D`` is the trailing sequential grid axis and the kernel
accumulates into its own output block (``out_accumulate``), which is the
write-race shape the static verifier proves safe. Operands are lifted to
3-d ``[*, 1, F]`` so every block spans the second-minor dim (no sublane
penalty). Padding slots point at row 0 with weight 0.

Scalar-prefetched operands live whole in SMEM (1 MiB on v5e), so the
wrapper runs the call over chunks of at most ``PREFETCH_SLOTS`` bag slots
(``lax.map`` over bag chunks): a 4096 x 50 batch would otherwise need
1.6 MB of ids + weights there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.plan import KernelPlan

PREFETCH_SLOTS = 32768      # bag slots per call: 2 x 128 KiB of SMEM


def _kernel(ids_ref, w_ref, tbl_ref, out_ref, *, nd: int):
    b = pl.program_id(0)
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[b * nd + d]
    out_ref[...] += (w * tbl_ref[...].astype(jnp.float32)).astype(
        out_ref.dtype)


def plan(b: int, d: int, v: int, f: int, *, feat_blk: int = 128,
         dtype=jnp.float32, ids=None, weights=None) -> KernelPlan:
    """Static call plan. ``ids``/``weights`` are the scalar-prefetch
    operands the index maps / kernel body consume; the kernel leaves them
    traced (``index_args=()``), example plans pass host arrays so the
    verifier can enumerate the grid."""
    f_pad = ((f + feat_blk - 1) // feat_blk) * feat_blk
    index_args = (() if ids is None
                  else (np.asarray(ids, dtype=np.int32).ravel(),
                        np.asarray(weights, dtype=np.float32).ravel()))
    return KernelPlan(
        name="gather_combine",
        grid=(b, f_pad // feat_blk, d),
        in_specs=(
            pl.BlockSpec((1, 1, feat_blk),
                         lambda bi, j, di, ids, w: (ids[bi * d + di], 0,
                                                    j)),
        ),
        out_specs=(pl.BlockSpec((1, 1, feat_blk),
                                lambda bi, j, di, ids, w: (bi, 0, j)),),
        operands=(jax.ShapeDtypeStruct((v, 1, f_pad), dtype),),
        outputs=(jax.ShapeDtypeStruct((b, 1, f_pad), dtype),),
        seq_axes=(2,),
        out_accumulate=True,
        index_args=index_args,
        meta=dict(f_pad=f_pad, d=d),
    )


def example_plan() -> KernelPlan:
    """Zipf-ish bag ids over a 4096-row table (the verifier's registry
    entry): 64 bags of 8 slots, embed dim 256."""
    rng = np.random.default_rng(0)
    b, d, v, f = 64, 8, 4096, 256
    ids = rng.integers(0, v, (b, d))
    w = (rng.random((b, d)) < 0.8).astype(np.float32) / d
    return plan(b, d, v, f, ids=ids, weights=w)


@functools.partial(jax.jit, static_argnames=("feat_blk", "interpret"))
def gather_combine(table: jnp.ndarray, idx: jnp.ndarray,
                   weights: jnp.ndarray, *, feat_blk: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """[V, F] table, [B, D] row ids (pad slots anywhere with w = 0),
    [B, D] weights -> [B, F] without materializing [B, D, F]."""
    v, f = table.shape
    b, d = idx.shape
    chunk = max(1, min(b, PREFETCH_SLOTS // d))
    n_chunks = -(-b // chunk)
    p = plan(chunk, d, v, f, feat_blk=feat_blk, dtype=table.dtype)
    f_pad = p.meta["f_pad"]
    tbl = jnp.pad(table, ((0, 0), (0, f_pad - f)))[:, None, :]
    pad = ((0, n_chunks * chunk - b), (0, 0))
    ids = jnp.pad(idx.astype(jnp.int32), pad).reshape(n_chunks, chunk * d)
    ws = jnp.pad(weights.astype(jnp.float32), pad).reshape(n_chunks,
                                                           chunk * d)
    call = pl.pallas_call(
        functools.partial(_kernel, nd=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=p.grid,
            in_specs=list(p.in_specs),
            out_specs=p.out_specs[0],
        ),
        out_shape=p.outputs[0],
        interpret=interpret,
    )
    out = jax.lax.map(lambda iw: call(iw[0], iw[1], tbl), (ids, ws))
    return out.reshape(n_chunks * chunk, f_pad)[:b, :f]
