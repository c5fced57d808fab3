"""Continuous-batching LM serving on a placement-aware paged KV cache
(DESIGN.md §Serving).

The serving loop is the repo's first long-lived stateful subsystem: a
request stream with mixed prompt/gen lengths is decoded continuously
(admit/evict per decode step) against a paged KV cache whose page ->
device placement is computed by the SAME makespan objective the rest of
the repo owns — pages are the graph's rows, measured hot-page co-access
counts are its edges, and ``PlacementSession.map_pages`` re-places the
pool when the traffic drifts past a threshold.

Modules:
  * ``kv_cache``     — free-list page allocator, per-request page tables,
                       the pooled K/V arrays (or MLA's latent pool),
                       access-count traffic, and physical page reordering
                       under a placement.
  * ``scheduler``    — FIFO admit / completion-evict scheduler with
                       page-exhaustion backpressure (pure bookkeeping,
                       JAX-free, so invariants are property-testable).
  * ``paged_decode`` — one batched decode step that reads/writes K/V
                       (GQA) or the latent cache (MLA, absorbed) through
                       page tables with per-request positions; logits
                       match ``models.transformer.decode_step`` exactly
                       (the load-bearing equivalence tests).
  * ``engine``       — the stream loop tying the three together, with
                       request-level metrics (TTFT, p50/p99 latency,
                       tokens/s) and the drift re-placement policy.
"""
from repro.serving.engine import EngineConfig, ServeReport, ServingEngine
from repro.serving.kv_cache import (PageAllocator, PagedKVCache,
                                    PagePoolExhausted)
from repro.serving.scheduler import Request, Scheduler

__all__ = ["EngineConfig", "PageAllocator", "PagedKVCache",
           "PagePoolExhausted", "Request", "Scheduler", "ServeReport",
           "ServingEngine"]
