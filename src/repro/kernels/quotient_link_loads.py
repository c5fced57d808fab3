"""Fused makespan-communication kernel: arc list -> per-link loads.

The paper's objective needs, for every link l of the machine tree,

    comm(l) = sum over cut edges {u,v} of w_uv * [l on path(P(u), P(v))].

TPU-native formulation (DESIGN.md §2): accumulate the k x k quotient matrix
W from the arc list as *one-hot outer products on the MXU* —

    W += onehot(b_i)^T @ (w * onehot(b_j))        per arc block —

into a VMEM scratch accumulator across the (sequential) grid, then apply the
subtree-XOR epilogue in the final grid step:

    comm = 0.5 * (S @ rowsum + S @ colsum - 2 * diag(S W S^T))

Everything — scatter, GEMM, epilogue — is a single ``pallas_call``; no HBM
round-trip for W. Block sizes: ``m_blk`` arcs per step (one-hot tiles
``m_blk x k`` live in VMEM), W scratch is ``k x k`` (1 MiB at k = 512).
``m_blk`` is a multiple of 1024: the TPU compiler tiles 1-D 32-bit arrays
in HBM as ``T(1024)`` and refuses a 1-D block that does not match.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.plan import KernelPlan


def _kernel(bi_ref, bj_ref, w_ref, s_ref, fl_ref, out_ref, w_acc, *, k: int,
            n_blocks: int):
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        w_acc[...] = jnp.zeros_like(w_acc)

    bi = bi_ref[...]                       # [m_blk] int32 (k = padding)
    bj = bj_ref[...]
    w = w_ref[...]                         # [m_blk] f32 (0 on padding)
    iota = jax.lax.broadcasted_iota(jnp.int32, (bi.shape[0], k), 1)
    a = (bi[:, None] == iota).astype(jnp.float32)           # [m_blk, k]
    b = (bj[:, None] == iota).astype(jnp.float32) * w[:, None]
    w_acc[...] += jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pid == n_blocks - 1)
    def _epilogue():
        W = w_acc[...]
        S = s_ref[...]                     # [L, k]
        r = W.sum(axis=1)
        c = W.sum(axis=0)
        sw = jax.lax.dot_general(S, W, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        cross = (sw * S).sum(axis=1)       # diag(S W S^T)
        comm = 0.5 * (S @ r + S @ c - 2.0 * cross)
        out_ref[...] = fl_ref[...] * comm


def plan(m: int, k: int, L: int, *, m_blk: int = 1024) -> KernelPlan:
    """Static call plan: the single (arc-block) grid axis is sequential —
    every grid point writes the same [L] output block, carrying the k x k
    quotient accumulator in VMEM scratch; only the final step (epilogue)
    produces the real output."""
    m_pad = ((m + m_blk - 1) // m_blk) * m_blk
    n_blocks = m_pad // m_blk
    return KernelPlan(
        name="quotient_link_loads",
        grid=(n_blocks,),
        in_specs=(
            pl.BlockSpec((m_blk,), lambda i: (i,)),
            pl.BlockSpec((m_blk,), lambda i: (i,)),
            pl.BlockSpec((m_blk,), lambda i: (i,)),
            pl.BlockSpec((L, k), lambda i: (0, 0)),
            pl.BlockSpec((L,), lambda i: (0,)),
        ),
        out_specs=(pl.BlockSpec((L,), lambda i: (0,)),),
        operands=(jax.ShapeDtypeStruct((m_pad,), jnp.int32),
                  jax.ShapeDtypeStruct((m_pad,), jnp.int32),
                  jax.ShapeDtypeStruct((m_pad,), jnp.float32),
                  jax.ShapeDtypeStruct((L, k), jnp.float32),
                  jax.ShapeDtypeStruct((L,), jnp.float32)),
        outputs=(jax.ShapeDtypeStruct((L,), jnp.float32),),
        scratch_shapes=(pltpu.VMEM((k, k), jnp.float32),),
        seq_axes=(0,),
        meta=dict(m_pad=m_pad, n_blocks=n_blocks),
    )


def example_plan() -> KernelPlan:
    """k = 16 bins over a depth-2 machine tree (L = 20 links)."""
    return plan(m=2048, k=16, L=20)


@functools.partial(jax.jit, static_argnames=("k", "m_blk", "interpret"))
def quotient_link_loads(bin_i: jnp.ndarray, bin_j: jnp.ndarray,
                        weight: jnp.ndarray, subtree: jnp.ndarray,
                        F_l: jnp.ndarray, *, k: int, m_blk: int = 1024,
                        interpret: bool = False) -> jnp.ndarray:
    """Per-link communication cost ``F_l * comm(l)``. [L]

    ``bin_i/bin_j``: endpoints' bins per arc (symmetric arc list — each
    undirected edge appears twice; the 0.5 in the epilogue compensates).
    Arcs are padded to a multiple of ``m_blk`` with ``weight = 0``.
    """
    m = bin_i.shape[0]
    L = subtree.shape[0]
    p = plan(m, k, L, m_blk=m_blk)
    pad = p.meta["m_pad"] - m
    bi = jnp.pad(bin_i.astype(jnp.int32), (0, pad), constant_values=k)
    bj = jnp.pad(bin_j.astype(jnp.int32), (0, pad), constant_values=k)
    w = jnp.pad(weight.astype(jnp.float32), (0, pad))
    return pl.pallas_call(
        functools.partial(_kernel, k=k, n_blocks=p.meta["n_blocks"]),
        grid=p.grid,
        in_specs=list(p.in_specs),
        out_specs=p.out_specs[0],
        out_shape=p.outputs[0],
        scratch_shapes=list(p.scratch_shapes),
        interpret=interpret,
    )(bi, bj, w, subtree.astype(jnp.float32), F_l.astype(jnp.float32))
