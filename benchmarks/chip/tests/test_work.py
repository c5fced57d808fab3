"""Work counts against hand counts at tiny sizes."""
import work

QWEN_LIKE = {"hidden_size": 8, "num_attention_heads": 2,
             "num_key_value_heads": 1, "intermediate_size": 16,
             "num_hidden_layers": 3, "vocab_size": 10}


def test_refine_round_bytes_hand_count():
    # 5 vertices, 8 arcs, k = 2: arcs 8 * (12 + 8), vertices 5 * 12,
    # bin pairs 2 * 2 * 8
    assert work.refine_round_bytes(5, 8, 2) == 160 + 60 + 32


def test_refine_bytes_sums_levels_and_rounds():
    levels = [(5, 8), (3, 4)]
    one = work.refine_round_bytes(5, 8, 2) + work.refine_round_bytes(3, 4, 2)
    assert work.refine_bytes(levels, 2, 7) == 7 * one


def test_lm_matmul_params_hand_count():
    # head_dim 4: q 8*8, k 8*4, v 8*4, o 8*8, mlp 3*8*16 -> 576 per layer,
    # 3 layers, output projection 8*10
    assert work.lm_matmul_params(QWEN_LIKE) == 3 * 576 + 80


def test_lm_flops_hand_count():
    # one token over a context of 5: 2 * params + 2 * 2 * heads(2) *
    # head_dim(4) * 5 * layers(3)
    base = 2 * (3 * 576 + 80)
    assert work.lm_flops(QWEN_LIKE, 1, 5) == base + 2 * 2 * 2 * 4 * 5 * 3
    # tokens at contexts 1, 2, 3 cost the same as their sum of contexts
    assert work.lm_flops(QWEN_LIKE, 3, 6) == 3 * base + 2 * 2 * 2 * 4 * 3 * 6
