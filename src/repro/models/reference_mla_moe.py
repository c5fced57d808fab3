"""Plain float32 reference of the DeepSeek-V2 decoder (MLA + MoE), one
whole sequence at a time.

It follows the published architecture (arXiv:2405.04434 and the Hugging
Face ``modeling_deepseek.py``): token embedding; ``n_dense_layers`` dense
blocks, then MoE blocks, each pre-norm (RMSNorm, attention, residual,
RMSNorm, FFN, residual); a final RMSNorm and the untied output
projection.

* Attention (MLA): ``q = x W_q`` (or ``RMSNorm(x W_dq) W_uq``) split per
  head into ``[nope | rope]``; ``c = RMSNorm(x W_dkv)``; per head
  ``k_nope = c W_uk`` and ``v = c W_uv``; one rope key ``x W_kr`` shared
  by every head; scores ``(q_nope·k_nope + rope(q_rope)·rope(k_rope)) ·
  scale`` under a causal softmax; output through ``W_o``. ``scale`` is
  ``qk_head_dim^-1/2``, times ``mscale(factor, mscale_all_dim)²`` under
  YaRN, with ``mscale(s, m) = 0.1 m ln s + 1``.
* YaRN frequencies: the original ``theta^(-2i/d)`` for dims below
  ``floor(corr(beta_fast))``, those divided by ``factor`` above
  ``ceil(corr(beta_slow))``, a linear blend between, where ``corr(r) = d
  ln(original_max_position / (2 pi r)) / (2 ln theta)``.
* MoE: a softmax over all routed experts in float32, greedy top-k,
  weights renormalised only with ``norm_topk_prob`` (else times
  ``routed_scaling_factor``); the routed experts' SwiGLU outputs weighted
  and summed, plus the shared experts as one SwiGLU of their summed
  width.

Departures, each the same in the program:

* The rope rotates halves (``x1 cos - x2 sin, x2 cos + x1 sin``). The
  published model de-interleaves the rope dims first; under random
  weights that is a relabelling of the rope columns of ``W_q`` and
  ``W_kr``.
* Only the experts of ``cfg.experts_held`` add to the output: what the
  experts held on other chips of an expert-parallel deployment would add
  is left out.
* Inference only: no dropout, no auxiliary loss.

Here K and V are expanded per head (the textbook form), where the
program's decode runs absorbed in the latent basis, so agreement checks
the absorption as well. It runs every matrix product at ``highest``
precision, imports nothing of the program's model code, and reads the
program's parameter layout (``models.transformer.init``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def inv_freq(cfg) -> np.ndarray:
    """Rope frequencies of the ``qk_rope_head_dim`` rotated dims."""
    d, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    base = 1.0 / theta ** (np.arange(0, d, 2) / d)
    y = cfg.yarn
    if y is None:
        return base

    def corr(rotations):
        return d * math.log(y.original_max_position
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(corr(y.beta_fast)), 0)
    hi = min(math.ceil(corr(y.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return base / y.factor * ramp + base * (1 - ramp)


def softmax_scale(cfg) -> float:
    s = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    y = cfg.yarn
    if y is not None and y.mscale_all_dim and y.factor > 1:
        m = 0.1 * y.mscale_all_dim * math.log(y.factor) + 1.0
        s *= m * m
    return s


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def attention(a, x, cfg, cos, sin):
    """MLA over the whole sequence ``x`` [T, D]."""
    t = x.shape[0]
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if cfg.q_lora_rank:
        q = _rms(x @ a["w_dq"], a["q_norm"]) @ a["w_uq"]
    else:
        q = x @ a["w_q"]
    q = q.reshape(t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cos, sin)
    c = _rms(x @ a["w_dkv"], a["kv_norm"])
    k_rope = _rope((x @ a["w_kr"])[:, None, :], cos, sin)    # [T, 1, dr]
    k_nope = (c @ a["w_uk"]).reshape(t, h, dn)
    v = (c @ a["w_uv"]).reshape(t, h, dv)
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
         + jnp.einsum("thd,sxd->hts", q_rope, k_rope)) * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v).reshape(t, h * dv)
    return o @ a["w_o"]


def moe(f, x, cfg):
    """The held routed experts' part plus the shared experts, [T, D]."""
    probs = jax.nn.softmax(x @ f["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    else:
        top_p = top_p * cfg.routed_scaling_factor
    first, count = cfg.experts_held or (0, cfg.n_experts)
    y = jnp.zeros_like(x)
    for e in range(count):
        w = jnp.where(top_i == first + e, top_p, 0.0).sum(-1)
        y = y + w[:, None] * _swiglu(x, f["w_gate"][e], f["w_up"][e],
                                     f["w_down"][e])
    if cfg.n_shared:
        y = y + _swiglu(x, f["ws_gate"], f["ws_up"], f["ws_down"])
    return y


def _layers(stacked):
    n = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda a, i=i: a[i], stacked) for i in range(n)]


def forward(params, tokens, cfg) -> jnp.ndarray:
    """Logits [T, V] (float32) of one sequence ``tokens`` [T]."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    t = tokens.shape[0]
    ang = np.outer(np.arange(t), inv_freq(cfg))[:, None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for kind in ("dense_layers", "moe_layers"):
            for lp in _layers(params[kind]) if kind in params else ():
                x = x + attention(lp["attn"], _rms(x, lp["ln1"]), cfg, cos,
                                  sin)
                hn = _rms(x, lp["ln2"])
                f = lp["ffn"]
                x = x + (moe(f, hn, cfg) if kind == "moe_layers" else
                         _swiglu(hn, f["w_gate"], f["w_up"], f["w_down"]))
        return _rms(x, params["ln_f"]) @ params["unembed"]
