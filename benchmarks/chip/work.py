"""Work counts: the operations and bytes an algorithm needs, from its
shapes alone, so that a roofline or utilization share counts the same work
whatever implements it.
"""
from __future__ import annotations

from typing import Iterable, Mapping, Tuple

I32 = 4      # bytes of an int32 bin id, arc endpoint or float32 weight


def refine_round_bytes(n_vertices: int, n_arcs: int, k: int) -> int:
    """Least HBM bytes of one refinement round of a level with
    ``n_vertices`` vertices and ``n_arcs`` arcs (both directions of every
    edge) over ``k`` bins.

    A round must score the current assignment and move vertices, so it at
    least reads every arc's two endpoints and weight once (12 B), gathers
    the bins of both endpoints (8 B), reads every vertex's bin and weight
    and writes its new bin (12 B), and writes and reads back the ``k x k``
    bin-pair traffic it prices moves with (8 B per entry). Re-reads,
    candidate sampling and the makespan evaluation are left out, so the
    count is a floor of what any implementation moves.
    """
    return (3 * I32 + 2 * I32) * n_arcs + 3 * I32 * n_vertices \
        + 2 * I32 * k * k


def refine_bytes(levels: Iterable[Tuple[int, int]], k: int,
                 rounds: int) -> int:
    """Least HBM bytes of ``rounds`` refinement rounds on every level
    (``levels`` holds each level's ``(n_vertices, n_arcs)``)."""
    return sum(rounds * refine_round_bytes(n, m, k) for n, m in levels)


def lm_matmul_params(cfg: Mapping) -> int:
    """Weights a token multiplies through in a dense GQA decoder (the
    output projection onto the vocabulary included, the embedding lookup
    not), from a Hugging Face style ``config.json``."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kh = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    f = cfg["intermediate_size"]
    per_layer = d * h * dh + 2 * d * kh * dh + h * dh * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def lm_flops(cfg: Mapping, n_tokens: int, context_sum: int) -> int:
    """Forward FLOPs of ``n_tokens`` tokens whose context lengths (each
    token itself included) add up to ``context_sum``: 2 per multiply-add
    of every weight, plus the scores and the weighted sum of values over
    each token's context in every layer."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    per_ctx = 2 * 2 * h * dh * cfg["num_hidden_layers"]
    return n_tokens * 2 * lm_matmul_params(cfg) + per_ctx * context_sum
