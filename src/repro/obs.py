"""Spans and work counters on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler trace is
being collected (``jax.profiler.start_trace`` or the profiler server) it
lands on the host plane of that trace, on the same clock as the device's
operations; otherwise it costs one check. Span names carry no arguments,
so a profile shows the plain name.

A counter adds to an in-process total only while a trace is being
collected, so the totals read after ``stop_trace`` cover exactly the
period the trace's spans cover. Like the profiler, the totals are one per
process. Call sites that compute a counter's value first ask ``on()``, so
that with no trace the only cost is that check.

Spans of the program:

* ``partition`` (one call of ``core.partitioner.partition``), holding
  ``partition.coarsen`` (all levels; one ``coarsen.level`` per level of
  the device path), ``partition.initial``, one ``partition.refine`` per
  level (holding ``refine.pull``, the wait for its result),
  ``partition.project`` between levels, and ``partition.evaluate``;
* ``map.search`` (``core.mapping.search``);
* ``serve.step`` (one ``ServingEngine.step``), holding ``serve.admit``,
  ``serve.inputs``, ``serve.dispatch``, ``serve.pull`` (the wait for the
  sampled tokens) and ``serve.advance`` (holding ``serve.record_access``).

Counters: ``refine.rounds`` and ``refine.rounds_to_best`` (refinement
rounds run, and those a result needed), ``decode.pages_gathered`` and
``decode.pages_live`` (KV or latent pages the decode step reads, and
those holding a position it attends to), ``serve.admitted`` and
``serve.queue_wait_s`` (requests admitted, and the seconds they waited in
the queue); and for a model whose expert layers hold a share of the
routed experts (``ServingEngine.step``, from the per-layer load the MLA
decode step returns and ``serve.pull`` reads with the sampled tokens):
``moe.pairs_routed`` (token-expert pairs of the active slots, tokens x
top-k x MoE layers), ``moe.pairs_local`` (those on held experts),
``moe.pairs_max`` (the busiest held expert's pairs, summed over steps and
layers) and ``moe.experts_hit`` (held experts with at least one pair,
summed likewise).
"""
from __future__ import annotations

from typing import Dict

import jax

_totals: Dict[str, float] = {}


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span called ``name`` in the trace being collected, if any."""
    return jax.profiler.TraceAnnotation(name)


def on() -> bool:
    """Whether a profiler trace is being collected now."""
    return jax.profiler.TraceAnnotation.is_enabled()


def add(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` while a trace is being collected."""
    if on():
        _totals[name] = _totals.get(name, 0) + n


def totals() -> Dict[str, float]:
    """A copy of every counter's total."""
    return dict(_totals)


def reset() -> None:
    _totals.clear()
