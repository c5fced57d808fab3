"""DeepSeek-V2-Lite 16B [arXiv:2405.04434; hf]: 27L d_model=2048 16H MLA
(kv_lora=512, no q_lora), vocab=102400, MoE 2 shared + 64 routed top-6,
expert d_ff=1408, first layer dense (d_ff=10944); softmax gate, top-k
weights not renormalised (``norm_topk_prob`` false, scaling 1); YaRN rope
(factor 40 over 4096 original positions, beta 32/1, mscale 0.707)."""
import jax.numpy as jnp

from repro.configs.lm_common import make_lm_archdef
from repro.models.common import Yarn
from repro.models.transformer import TransformerConfig

YARN = Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)

FULL = TransformerConfig(
    name="deepseek-v2-lite-16b", n_layers=27, d_model=2048, n_heads=16,
    n_kv_heads=16, d_ff=10944, vocab=102400,
    moe=True, n_experts=64, n_shared=2, top_k=6, d_ff_expert=1408,
    n_dense_layers=1, mla=True, kv_lora_rank=512, q_lora_rank=0,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    norm_topk_prob=False, routed_scaling_factor=1.0, yarn=YARN,
    dtype=jnp.bfloat16, remat=True)

SMOKE = TransformerConfig(
    name="deepseek-v2-lite-16b-smoke", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=128, vocab=512,
    moe=True, n_experts=8, n_shared=2, top_k=2, d_ff_expert=32,
    n_dense_layers=1, mla=True, kv_lora_rank=16, q_lora_rank=0,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    norm_topk_prob=False, routed_scaling_factor=1.0, yarn=YARN,
    dtype=jnp.float32, remat=False, capacity_factor=4.0)

ARCH = make_lm_archdef(
    FULL, SMOKE,
    notes=("64 routed experts: the 'expert' sharding profile gives the "
           "expert dim its own mesh axis (pod), so routed FFN weights and "
           "dispatch buffers spread across pods — the mapping grid compares "
           "it against 2d/fsdp/sp under searched vs identity device "
           "orders (DESIGN.md §Sharding-profiles)."))
