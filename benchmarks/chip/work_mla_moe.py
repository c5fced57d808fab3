"""Work counts of the DeepSeek-V2 (MLA + MoE) serving cell: the FLOPs
and HBM bytes its decode steps need, from the configuration's shapes and
the program's routing counters, so that a roofline or utilization share
counts the same work whatever implements it. ``cfg`` is the cell's Hugging
Face style configuration (``n_routed_experts`` held here).
"""
from __future__ import annotations

from typing import Mapping

BF16 = 2     # bytes of a served weight or cache element


def _sizes(cfg: Mapping):
    h = cfg["num_attention_heads"]
    return (cfg["hidden_size"], h, cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def attn_params(cfg: Mapping) -> int:
    """Weights of one MLA layer (no q_lora): W_q, W_dkv, W_kr, W_uk, W_uv,
    W_o; the absorbed decode multiplies a token through each once."""
    d, h, dn, dr, dv, r = _sizes(cfg)
    return d * h * (dn + dr) + d * r + d * dr + r * h * dn + r * h * dv \
        + h * dv * d


def expert_params(cfg: Mapping) -> int:
    """Weights of one routed expert (SwiGLU of ``moe_intermediate_size``)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def n_moe_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def token_params(cfg: Mapping) -> int:
    """Weights every token multiplies through, routed experts left out:
    attention of every layer, the dense FFNs, each MoE layer's router and
    shared experts, and the output head (the embedding lookup not)."""
    d = cfg["hidden_size"]
    L, n_moe = cfg["num_hidden_layers"], n_moe_layers(cfg)
    dense = (L - n_moe) * 3 * d * cfg["intermediate_size"]
    moe = n_moe * (d * cfg["published"]["n_routed_experts"]
                   + cfg["n_shared_experts"] * expert_params(cfg))
    return L * attn_params(cfg) + dense + moe + d * cfg["vocab_size"]


def step_weight_bytes(cfg: Mapping) -> int:
    """HBM bytes of the weights one decode step must read, routed experts
    left out: :func:`token_params` and the norm gains."""
    d, r = cfg["hidden_size"], cfg["kv_lora_rank"]
    norms = cfg["num_hidden_layers"] * (2 * d + r) + d
    return BF16 * (token_params(cfg) + norms)


def latent_bytes(cfg: Mapping) -> int:
    """Bytes of one token's latent cache entry in one layer (c_kv and the
    rope key)."""
    return BF16 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def window_bytes(cfg: Mapping, steps: int, experts_hit: int,
                 pages_live: int, page_size: int, tokens: int) -> int:
    """Least HBM bytes of ``steps`` decode steps: the weights each step
    reads (:func:`step_weight_bytes`), each held expert a step routes a
    pair to once (``experts_hit`` summed over steps and layers), the live
    latent pages of every layer (``pages_live`` summed over steps), and
    the latent entry each of ``tokens`` writes in every layer."""
    L = cfg["num_hidden_layers"]
    return steps * step_weight_bytes(cfg) \
        + experts_hit * BF16 * expert_params(cfg) \
        + pages_live * page_size * L * latent_bytes(cfg) \
        + tokens * L * latent_bytes(cfg)


def flops(cfg: Mapping, n_tokens: int, context_sum: int,
          pairs_local: int) -> int:
    """Forward FLOPs of this chip's share for ``n_tokens`` tokens whose
    context lengths (each token itself included) add up to
    ``context_sum``, of which ``pairs_local`` token-expert pairs land on
    held experts: 2 per multiply-add of :func:`token_params`, the absorbed
    scores (latent and rope key) and latent weighted sum over each token's
    context in every layer, and each local pair's expert."""
    _, h, _, dr, _, r = _sizes(cfg)
    per_ctx = 2 * h * (2 * r + dr) * cfg["num_hidden_layers"]
    return 2 * n_tokens * token_params(cfg) + per_ctx * context_sum \
        + 2 * pairs_local * expert_params(cfg)
