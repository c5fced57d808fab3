"""Plain float32 reference of the DeepSeek-V2 decoder (MLA + MoE) at the
share of the experts one chip holds, and the weights the cell serves,
made on the device from a seed.

The reference follows the published architecture (arXiv:2405.04434, the
Hugging Face ``modeling_deepseek.py``): token embedding; the leading
dense blocks, then MoE blocks, each pre-norm (RMSNorm; multi-head latent
attention; RMSNorm; FFN); a final RMSNorm and the untied output
projection. Attention: ``q = x W_q`` split per head into ``[nope |
rope]``, ``c = RMSNorm(x W_dkv)``, per head ``k = c W_uk`` and ``v = c
W_uv``, one rope key ``x W_kr`` shared by the heads, a causal softmax of
``(q_nope·k + rope(q_rope)·rope(k_rope))`` times ``192^-1/2 ·
mscale(40, 0.707)²``, and ``W_o``; rope frequencies YaRN-blended as the
config's ``rope_scaling`` says. MoE: a float32 softmax over all
``published.n_routed_experts`` experts, greedy top-k, the weights
renormalised only with ``norm_topk_prob`` (else times
``routed_scaling_factor``), the held experts' SwiGLU outputs weighted and
summed, plus the shared experts as one SwiGLU of their summed width.

Departures, the same in the program: the rope rotates halves (the
published model de-interleaves the rope dims first, a relabelling of the
rope columns of ``W_q`` and ``W_kr`` under random weights); only the held
experts (``experts_held_first``, ``n_routed_experts`` of them) add to a
layer, what the experts of other chips add being theirs; no dropout, no
auxiliary loss. K and V are expanded per head, where the program runs
absorbed in the latent basis.

It runs one whole sequence at a time, layer by layer in a scan with each
layer's weights made float32 in turn (so that it fits beside the
bfloat16 weights once the program's state is freed), with every matrix
product at ``highest`` precision, and imports nothing of the program.

The control (``control_tokens``) is the same forward with both operands
of every weight product rounded to float8 e4m3 (per-tensor scale), the
nearest precision below the bfloat16 that the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Mapping, Tuple

import numpy as np

from lm_reference import _key, _mm_exact, _mm_fp8, _padded


def dims(cfg: Mapping) -> Tuple:
    """The shapes the weights and the forward need, hashable."""
    rs = cfg["rope_scaling"]
    return (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_shared_experts"], cfg["published"]["n_routed_experts"],
            cfg["experts_held_first"], cfg["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"],
            float(cfg["rope_theta"]), float(rs["factor"]),
            int(rs["original_max_position_embeddings"]),
            float(rs["beta_fast"]), float(rs["beta_slow"]),
            float(rs["mscale_all_dim"]), bool(cfg["norm_topk_prob"]),
            float(cfg["routed_scaling_factor"]), float(cfg["rms_norm_eps"]))


def inv_freq(d: int, theta: float, factor: float, orig: int,
             beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN frequencies of ``d`` rotated dims."""
    base = 1.0 / theta ** (np.arange(0, d, 2) / d)

    def corr(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return base / factor * ramp + base * (1 - ramp)


def softmax_scale(dn: int, dr: int, factor: float, m: float) -> float:
    s = 1.0 / math.sqrt(dn + dr)
    if m and factor > 1:
        s *= (0.1 * m * math.log(factor) + 1.0) ** 2
    return s


def make_params(cfg: Mapping, seed: int):
    """Random weights in the program's serving layout, on the device, in
    bfloat16, from one jitted call: matrices (router included) ~ N(0,
    ``initializer_range``), norm gains 1 + the same noise."""
    return _param_maker(dims(cfg) + (float(cfg["initializer_range"]),))(
        _key(seed))


@functools.lru_cache(maxsize=None)
def _param_maker(static: Tuple):
    import jax
    import jax.numpy as jnp
    L, n_dense, d, h, dn, dr, dv, r, f, fe, n_sh, n_exp, _, held, _, V = \
        static[:16]
    std = static[-1]
    bf16 = jnp.bfloat16

    @jax.jit
    def build(key):
        ks = iter(jax.random.split(key, 40))

        def nrm(shape):
            return (std * jax.random.normal(next(ks), shape, jnp.float32)
                    ).astype(bf16)

        def gain(shape):
            return (1.0 + std * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(bf16)

        def stack(n, moe):
            attn = {"w_q": nrm((n, d, h * (dn + dr))),
                    "w_dkv": nrm((n, d, r)), "kv_norm": gain((n, r)),
                    "w_kr": nrm((n, d, dr)), "w_uk": nrm((n, r, h * dn)),
                    "w_uv": nrm((n, r, h * dv)), "w_o": nrm((n, h * dv, d))}
            if moe:
                fs = n_sh * fe
                ffn = {"router": nrm((n, d, n_exp)),
                       "w_gate": nrm((n, held, d, fe)),
                       "w_up": nrm((n, held, d, fe)),
                       "w_down": nrm((n, held, fe, d)),
                       "ws_gate": nrm((n, d, fs)), "ws_up": nrm((n, d, fs)),
                       "ws_down": nrm((n, fs, d))}
            else:
                ffn = {"w_gate": nrm((n, d, f)), "w_up": nrm((n, d, f)),
                       "w_down": nrm((n, f, d))}
            return {"attn": attn, "ffn": ffn, "ln1": gain((n, d)),
                    "ln2": gain((n, d))}

        out = {"embed": nrm((V, d)), "unembed": nrm((d, V)),
               "ln_f": gain((d,))}
        if n_dense:
            out["dense_layers"] = stack(n_dense, False)
        if L > n_dense:
            out["moe_layers"] = stack(L - n_dense, True)
        return out
    return build


def _forward(params, tokens, static: Tuple, mm):
    """Logits [T, V] of one sequence ``tokens`` [T] (float32)."""
    import jax
    import jax.numpy as jnp
    (L, n_dense, d, h, dn, dr, dv, r, f, fe, n_sh, n_exp, first, held, k,
     V, theta, factor, orig, bfast, bslow, mall, norm_topk, scaling,
     eps) = static
    t = tokens.shape[0]
    f32 = jnp.float32
    ang = np.outer(np.arange(t), inv_freq(dr, theta, factor, orig, bfast,
                                          bslow))[:, None, :]
    cos, sin = jnp.asarray(np.cos(ang), f32), jnp.asarray(np.sin(ang), f32)
    scale = softmax_scale(dn, dr, factor, mall)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * g

    def rope(x):
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def swiglu(x, g, u, o):
        return mm(jax.nn.silu(mm(x, g)) * mm(x, u), o)

    def attention(a, x):
        q = mm(x, a["w_q"]).reshape(t, h, dn + dr)
        c = norm(mm(x, a["w_dkv"]), a["kv_norm"])
        k_rope = rope(mm(x, a["w_kr"])[:, None, :])
        k_nope = mm(c, a["w_uk"]).reshape(t, h, dn)
        v = mm(c, a["w_uv"]).reshape(t, h, dv)
        s = (jnp.einsum("thd,shd->hts", q[..., :dn], k_nope)
             + jnp.einsum("thd,sxd->hts", rope(q[..., dn:]), k_rope)) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm(jnp.einsum("hts,shd->thd", p, v).reshape(t, h * dv),
                  a["w_o"])

    def moe(m, x):
        probs = jax.nn.softmax(mm(x, m["router"]), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = (top_p / top_p.sum(-1, keepdims=True) if norm_topk
                 else top_p * scaling)
        y = swiglu(x, m["ws_gate"], m["ws_up"], m["ws_down"]) if n_sh \
            else jnp.zeros_like(x)
        for e in range(held):
            w = jnp.where(top_i == first + e, top_p, 0.0).sum(-1)
            y = y + w[:, None] * swiglu(x, m["w_gate"][e], m["w_up"][e],
                                        m["w_down"][e])
        return y

    def layer(is_moe):
        def body(x, lp):
            lp = jax.tree.map(lambda a: a.astype(f32), lp)
            x = x + attention(lp["attn"], norm(x, lp["ln1"]))
            hn = norm(x, lp["ln2"])
            m = lp["ffn"]
            y = moe(m, hn) if is_moe else swiglu(hn, m["w_gate"], m["w_up"],
                                                 m["w_down"])
            return x + y, None
        return body

    x = params["embed"][tokens].astype(f32)
    if "dense_layers" in params:
        x, _ = jax.lax.scan(layer(False), x, params["dense_layers"])
    if "moe_layers" in params:
        x, _ = jax.lax.scan(layer(True), x, params["moe_layers"])
    x = norm(x, params["ln_f"].astype(f32))
    return mm(x, params["unembed"].astype(f32))


@functools.lru_cache(maxsize=None)
def _gap_fn(static: Tuple):
    """The jitted ``(params, tokens, positions, compared) -> gaps``: at
    each position, the reference's best logit less its logit of the
    compared token."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(params, tokens, positions, compared):
        with jax.default_matmul_precision("highest"):
            ref = _forward(params, tokens, static, _mm_exact)[positions]
        own = jnp.take_along_axis(ref, compared[:, None], 1)[:, 0]
        return ref.max(-1) - own
    return gaps


@functools.lru_cache(maxsize=None)
def _first_fn(static: Tuple):
    """The jitted ``(params, tokens, positions) -> tokens`` that the
    float8 forward puts first at each position."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def first(params, tokens, positions):
        with jax.default_matmul_precision("highest"):
            low = _forward(params, tokens, static, _mm_fp8)[positions]
        return jnp.argmax(low, -1).astype(jnp.int32)
    return first


def forward(params, cfg: Mapping, tokens) -> np.ndarray:
    """Reference logits [T, V] of one sequence, at ``highest`` precision."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        out = jax.jit(functools.partial(_forward, static=dims(cfg),
                                        mm=_mm_exact))(
            params, jnp.asarray(tokens, jnp.int32))
    return np.asarray(out)


def served_gaps(params, cfg: Mapping, prompt: np.ndarray, generated,
                length: int, compared=None) -> np.ndarray:
    """Per served token, how far the reference logit of ``compared`` (by
    default the served token itself) lies below the reference's best at
    its position, with the served tokens as the context."""
    import jax.numpy as jnp
    n = len(generated)
    t, p = _padded(prompt, generated, length)
    c = np.zeros(length, np.int32)
    c[:n] = generated if compared is None else compared
    out = _gap_fn(dims(cfg))(params, t, p, jnp.asarray(c))
    return np.asarray(out)[:n]


def control_tokens(params, cfg: Mapping, prompt: np.ndarray, generated,
                   length: int) -> np.ndarray:
    """The control's answer: at each position of the served sequence,
    the token that the float8 forward puts first."""
    t, p = _padded(prompt, generated, length)
    out = _first_fn(dims(cfg))(params, t, p)
    return np.asarray(out)[:len(generated)]
