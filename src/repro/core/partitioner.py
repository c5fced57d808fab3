"""Multilevel driver for graph-constrained makespan partitioning.

Pipeline (classic V-cycle, bottleneck objective throughout):

  coarsen (heavy-edge matching)  ->  initial (hierarchical greedy growing
  on the coarsest graph)  ->  uncoarsen: project + JAX bottleneck
  refinement at every level (dense all-bin gains on coarse levels, sampled
  candidates on fine levels).

``PartitionConfig.backend`` selects the V-cycle front end: ``"host"``
(numpy coarsening + greedy grow — the reference path) or ``"device"``
(jitted segment-op coarsening + capacity-prefix initial, so the whole
V-cycle runs on the accelerator; DESIGN.md §Device-V-cycle).

``partition`` is the single public entry point used by every consumer
(GNN data placement, MoE expert placement, embedding-shard placement,
logical-mesh mapping).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np

from repro import obs
from repro.core import objective, refine as refine_mod
from repro.core.coarsen import coarsen, coarsen_device
from repro.core.initial import (initial_partition, initial_partition_device,
                                random_partition)
from repro.core.reference import makespan_ref
from repro.core.refine import RefineConfig
from repro.core.topology import TreeTopology
from repro.graph.graph import Graph


@dataclasses.dataclass(frozen=True)
class PartitionConfig:
    refine: RefineConfig = dataclasses.field(default_factory=RefineConfig)
    coarse_factor: int = 24
    max_levels: int = 40
    seed: int = 0
    initial: str = "hierarchical"   # or "random"
    final_rounds: Optional[int] = None  # extra rounds on the finest level
    seeds: int = 1                  # best-of-S vmapped refinement (>= 1)
    # "host": numpy coarsening + greedy-grow initial (the reference path);
    # "device": jitted segment-op coarsening (coarsen_device) + the
    # capacity-prefix initial — the full V-cycle runs on the accelerator
    # (refinement is device-resident on both). Quality pinned within 1.05x
    # of host by test.
    backend: str = "host"


@dataclasses.dataclass
class PartitionResult:
    part: np.ndarray                # [n] bin per vertex
    makespan: float
    comp: np.ndarray                # [k] (comp/speed when topo.bin_speed set)
    comm: np.ndarray                # [L]
    comp_max: float
    comm_max: float
    total_cut: float
    seconds: float
    level_makespans: List[float]


def _evaluate(g: Graph, topo: TreeTopology, part: np.ndarray) -> PartitionResult:
    import jax.numpy as jnp
    speed = (None if topo.bin_speed is None
             else jnp.asarray(topo.bin_speed, dtype=jnp.float32))
    br = objective.makespan_tree(
        jnp.asarray(part, dtype=jnp.int32), jnp.asarray(g.senders),
        jnp.asarray(g.receivers), jnp.asarray(g.edge_weight),
        jnp.asarray(g.node_weight), jnp.asarray(topo.subtree),
        jnp.asarray(topo.F_l), k=topo.k, speed=speed)
    W = objective.quotient_matrix(
        jnp.asarray(part, dtype=jnp.int32), jnp.asarray(g.senders),
        jnp.asarray(g.receivers), jnp.asarray(g.edge_weight), topo.k)
    return PartitionResult(
        part=np.asarray(part), makespan=float(br.makespan),
        comp=np.asarray(br.comp), comm=np.asarray(br.comm),
        comp_max=float(br.comp_max), comm_max=float(br.comm_max),
        total_cut=float(objective.total_cut(W)), seconds=0.0,
        level_makespans=[])


def _initial_parts(coarsest: Graph, topo: TreeTopology,
                   cfg: PartitionConfig) -> np.ndarray:
    """[S, n_coarse] initial partitions. Slot 0 is exactly the ``seeds=1``
    start (same method, same seed); later slots alternate hierarchical
    growing and balanced random assignments at shifted seeds for
    diversity."""
    parts = []
    grow = (initial_partition_device if cfg.backend == "device"
            else initial_partition)
    for i in range(cfg.seeds):
        hier = (cfg.initial == "hierarchical") if i == 0 else (i % 2 == 1)
        if hier:
            parts.append(grow(coarsest, topo, seed=cfg.seed + i))
        else:
            parts.append(random_partition(coarsest.n_nodes, topo.k,
                                          coarsest.node_weight,
                                          seed=cfg.seed + i))
    return np.stack(parts)


def partition(g: Graph, topo: TreeTopology,
              cfg: Optional[PartitionConfig] = None) -> PartitionResult:
    cfg = cfg or PartitionConfig()
    if cfg.seeds < 1:
        raise ValueError(f"seeds must be >= 1, got {cfg.seeds}")
    if cfg.backend not in ("host", "device"):
        raise ValueError(f"backend must be 'host' or 'device', "
                         f"got {cfg.backend!r}")
    t0 = time.time()
    with obs.span("partition"):
        coarsen_fn = coarsen_device if cfg.backend == "device" else coarsen
        with obs.span("partition.coarsen"):
            levels = coarsen_fn(g, topo.k, seed=cfg.seed,
                                coarse_factor=cfg.coarse_factor,
                                max_levels=cfg.max_levels)
        coarsest = levels[-1].graph
        history: List[float] = []
        # uncoarsen: every level refines all S partitions in ONE vmapped
        # scan (refine_batch; seeds=1 is the classic single-trajectory
        # V-cycle — slot 0 is pinned to refine() by test). The refine
        # rounds are GEMM-bound, so S restarts cost far less than S
        # sequential runs; the winner is the seed with the smallest true
        # makespan on the finest graph.
        with obs.span("partition.initial"):
            parts = _initial_parts(coarsest, topo, cfg)
        ms = None
        for li in range(len(levels) - 1, -1, -1):
            lg = levels[li].graph
            rcfg = cfg.refine
            if li == 0 and cfg.final_rounds is not None:
                rcfg = dataclasses.replace(rcfg, rounds=cfg.final_rounds)
            with obs.span("partition.refine"):
                parts, ms, _ = refine_mod.refine_batch(lg, topo, parts, rcfg)
            history.append(float(ms.min()))
            if li > 0:
                with obs.span("partition.project"):
                    parts = parts[:, levels[li - 1].fine_to_coarse]
        part = parts[int(np.argmin(ms))]
        with obs.span("partition.evaluate"):
            res = _evaluate(g, topo, part)
    res.seconds = time.time() - t0
    res.level_makespans = history
    return res


def verify(g: Graph, topo: TreeTopology, res: PartitionResult,
           atol: float = 1e-3) -> None:
    """Cross-check the JAX evaluation against the path-walking oracle."""
    m_ref, comp_ref, comm_ref = makespan_ref(res.part, g, topo)
    if not np.allclose(res.comp, comp_ref, atol=atol):
        raise AssertionError("comp mismatch vs oracle")
    if not np.allclose(res.comm, comm_ref, atol=atol):
        raise AssertionError("comm mismatch vs oracle")
    if abs(res.makespan - m_ref) > atol * max(1.0, m_ref):
        raise AssertionError(f"makespan {res.makespan} != oracle {m_ref}")
