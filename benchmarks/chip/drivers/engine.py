"""Driver of the placement engine's cells.

One placement is what a user of the engine asks for, from the host graph
they hold: ``partition(g, topo, PartitionConfig(...,
backend="device"))``, the block-pair traffic of the result
(``objective.quotient_matrix``), and the mapping search of the blocks onto
the machine's leaves (``mapping.search(..., n_random=<map_restarts>)``).
The user's placement puts vertex ``v`` on leaf ``device_to_bin[part[v]]``.

The partitioner's own seed (``PartitionConfig.seed``: the matching of
coarsening and the initial split) is the configuration's
``partition_seed``, so that every run coarsens to the same level shapes
and, after a checkout's first run, finds every program in the compile
cache. The run's seed drives the randomised moves of refinement
(``RefineConfig.seed``) and the mapping search's restarts.

Set-up makes the configuration's graph and places it once, which compiles
(or loads) every program the placement runs. The window then repeats that
same placement back to back (closed loop, one at a time) and ends with the
first placement to finish after ``seconds``.

``place_s`` is the window's seconds over the placements completed in it.
``makespan_rel`` is the makespan of the user's placement, by the plain
oracle, over the balance bound (total vertex weight over total bin speed).

The check holds every placement of the window to the guarantees the
configuration states, against ``oracle.py``: the reported bin loads, link
loads and makespan are exact, the reported makespan of the user's
placement is exact, and the searched map is no worse than the identity.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List

import graphs
import numpy as np
import oracle
from harness import Check, Window

SPAN_PLACE = "bench.place"
SPAN_MAP = "bench.map"


@dataclasses.dataclass
class State:
    cell: Any
    seed: int
    edges: tuple                     # n, u, v, w (benchmark's own copy)
    node_weight: np.ndarray
    graph: Any                       # the program's host Graph
    topo: Any
    tree: oracle.Tree
    mesh_shape: tuple
    restarts: int
    pcfg: Any                        # the program's PartitionConfig
    records: List[Dict] = dataclasses.field(default_factory=list)


def _check_tree(topo, tree: oracle.Tree) -> None:
    """The program's machine must be the tree the configuration states."""
    same = (topo.k == tree.k
            and np.array_equal(np.asarray(topo.parent), tree.parent)
            and np.array_equal(np.asarray(topo.compute_bins), tree.leaves)
            and np.allclose(np.asarray(topo.F_l),
                            tree.cost[np.asarray(topo.link_nodes)]))
    if not same:
        raise ValueError("the program's machine tree is not the one the "
                         "configuration's levels describe")


def _seed31(seed: int) -> int:
    """A 31-bit seed that every bit of ``seed`` feeds."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF


def setup(cell, seed: int, seconds: float, devices, *, log) -> State:
    from repro.core import machine, partitioner, refine
    from repro.graph.graph import from_edges
    cfg = cell.config
    n, u, v, w = graphs.make(cfg)
    node_weight = np.ones(n)
    g = from_edges(n, u, v, w.astype(np.float32),
                   node_weight.astype(np.float32))
    tree = oracle.Tree(cfg["machine"]["levels"])
    spec = machine.resolve(cfg["machine"]["preset"])
    topo = spec.topology()
    _check_tree(topo, tree)
    st = State(cell=cell, seed=seed, edges=(n, u, v, w),
               node_weight=node_weight, graph=g, topo=topo, tree=tree,
               mesh_shape=tuple(spec.mesh_spec()[0]),
               restarts=int(cell.traffic["map_restarts"]),
               pcfg=partitioner.PartitionConfig(
                   seed=int(cfg["partition_seed"]), backend="device",
                   refine=dataclasses.replace(refine.RefineConfig(),
                                              seed=_seed31(seed))))
    log(f"[engine] {cell.config_name}: vertices={n} edges={len(u)} "
        f"k={topo.k} seed={seed}")
    t = time.perf_counter()
    place(st)
    log(f"[engine] warm-up placement {time.perf_counter() - t:.3f} s")
    return st


def place(st: State) -> Dict[str, Any]:
    """One placement, from the host graph to the searched map."""
    import jax
    import jax.numpy as jnp

    from repro.core import mapping, objective, partitioner
    g, topo = st.graph, st.topo
    res = partitioner.partition(g, topo, st.pcfg)
    with jax.profiler.TraceAnnotation(SPAN_MAP):
        W = np.array(objective.quotient_matrix(
            jnp.asarray(res.part, dtype=jnp.int32), jnp.asarray(g.senders),
            jnp.asarray(g.receivers), jnp.asarray(g.edge_weight), topo.k))
        np.fill_diagonal(W, 0.0)
        best = mapping.search(st.mesh_shape, topo, W, n_random=st.restarts,
                              seed=st.pcfg.refine.seed)
    return {"part": np.asarray(res.part), "comp": np.asarray(res.comp),
            "comm": np.asarray(res.comm), "makespan": float(res.makespan),
            "comp_max": float(res.comp_max),
            "device_to_bin": np.asarray(best.device_to_bin),
            "bottleneck": float(best.bottleneck)}


def window(st: State, seconds: float) -> Window:
    import jax
    st.records = []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation(SPAN_PLACE):
            st.records.append(place(st))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    n = len(st.records)
    return Window(metrics={"place_s": elapsed / n}, attempted=n, failed=0,
                  seconds=elapsed, context={"placements": n})


def _judge(st: State, rec: Dict[str, Any]) -> Dict[str, float]:
    n, u, v, w = st.edges
    tree, topo = st.tree, st.topo
    comp, comm, ms = oracle.loads(tree, rec["part"], u, v, w, st.node_weight)
    got = np.zeros(tree.n_nodes)
    got[np.asarray(topo.link_nodes)] = rec["comm"]
    load_gap = max(float(np.abs(rec["comp"] - comp).max()),
                   float(np.abs(got - comm).max()),
                   abs(rec["makespan"] - ms))
    placed = rec["device_to_bin"][rec["part"]]
    _, _, ms_placed = oracle.loads(tree, placed, u, v, w, st.node_weight)
    reported = max(rec["comp_max"], rec["bottleneck"])
    identity = float((tree.cost * comm).max())
    return {"load_gap": load_gap, "map_gap": abs(reported - ms_placed),
            "map_excess": max(0.0, rec["bottleneck"] - identity),
            "makespan_rel": ms_placed / (st.node_weight.sum() / tree.k)}


def check(st: State, win: Window, *, log) -> List[Check]:
    """Every placement of the window against the oracle (each distinct
    answer judged once); fills in ``makespan_rel``."""
    judged: Dict[bytes, Dict[str, float]] = {}
    rows = []
    for rec in st.records:
        key = rec["part"].tobytes() + rec["device_to_bin"].tobytes()
        if key not in judged:
            judged[key] = _judge(st, rec)
        rows.append(judged[key])
    log(f"[engine] {len(rows)} placements, {len(judged)} distinct; "
        f"makespan_rel {[r['makespan_rel'] for r in judged.values()]}")
    win.metrics["makespan_rel"] = float(np.mean([r["makespan_rel"]
                                                 for r in rows]))
    limits = st.cell.limits
    return [Check(name, max(r[name] for r in rows), float(limits[name]))
            for name in ("load_gap", "map_gap", "map_excess")]


def trace_context(st: State, win: Window) -> Dict[str, Any]:
    """What the per-layer readers need besides the trace: the level sizes
    of the V-cycle, the refinement rounds and the bin count."""
    from repro.core import coarsen
    pc = st.pcfg
    levels = coarsen.coarsen_device(st.graph, st.topo.k, seed=pc.seed,
                                    coarse_factor=pc.coarse_factor,
                                    max_levels=pc.max_levels)
    return {"refine_levels": [(lv.graph.n_nodes, lv.graph.n_arcs)
                              for lv in levels],
            "refine_rounds": pc.refine.rounds, "k": st.topo.k}


def control(st: State) -> None:
    """Put the control's answer in the program's place: every placement's
    loads and makespan as the oracle computes them with its sums carried
    in bfloat16, the precision below the float32 the configuration
    states."""
    import ml_dtypes
    n, u, v, w = st.edges
    links = np.asarray(st.topo.link_nodes)
    low = {}
    for rec in st.records:
        key = rec["part"].tobytes()
        if key not in low:
            low[key] = oracle.loads_low(st.tree, rec["part"], u, v, w,
                                        st.node_weight, ml_dtypes.bfloat16)
        comp, comm, ms = low[key]
        rec.update(comp=comp, comm=comm[links], makespan=ms)
