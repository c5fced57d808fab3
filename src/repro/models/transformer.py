"""LM-family transformers: dense GQA (qwen2, chatglm3) and MoE + MLA
(deepseek-v2), one config-driven implementation.

Faithful pieces per the assigned configs:
  * GQA with grouped KV heads, optional QKV bias (qwen2), partial rotary
    (chatglm3 applies RoPE to half the head dim — "RoPE 2d").
  * MLA (DeepSeek-V2): low-rank compressed KV ``c_kv`` (kv_lora_rank) plus a
    shared single-head RoPE key; decode runs the *absorbed* path — the cache
    stores only ``[c_kv | k_rope]`` and ``W_uk``/``W_uv`` are folded into the
    query/output projections, so per-token KV bytes are rank-sized.
  * MoE (DeepSeek-V2): shared experts + routed top-k. Training runs
    sort-based capacity dispatch (no [T, E] cumsum tensors — O(T·k)
    memory) with an optional aux load-balance loss; decode runs the
    dropless expert-share layer (``expert_share_ffn``), which holds
    ``experts_held`` of the routed experts, routes over all of them and
    returns its share's part plus the shared experts. First
    ``n_dense_layers`` layers use a dense FFN.
  * YaRN rope scaling (``yarn``) with its softmax ``mscale²``.

Distribution: parameters/activations are annotated with *logical* axes via
``repro.dist.sharding.Rules``; the same code lowers on 1 device, the 256-chip
pod mesh and the 512-chip multi-pod mesh. Layers are stacked and scanned
(fast compiles, natural remat boundary); gradients all-reduce per layer by
construction of the scan.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import Rules, _ambient_mesh
from repro.models import common
from repro.models.common import (apply_rope, cross_entropy, dense_init,
                                 flash_attention, rms_norm, rope_freqs)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: Optional[int] = None          # default d_model // n_heads
    qkv_bias: bool = False
    rope_fraction: float = 1.0            # chatglm3: 0.5
    rope_theta: float = 1e4
    # --- MoE (deepseek-v2) ---
    moe: bool = False
    n_experts: int = 0                    # routed experts
    n_shared: int = 0                     # shared experts
    top_k: int = 0
    d_ff_expert: int = 0                  # per-expert hidden
    n_dense_layers: int = 0               # leading dense-FFN layers
    capacity_factor: float = 1.5
    aux_loss_coef: float = 0.003
    norm_topk_prob: bool = True           # renormalise the top-k weights
    routed_scaling_factor: float = 1.0    # else scale them by this
    # (first, count) of the routed experts this chip holds (expert
    # parallelism); None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    # --- MLA (deepseek-v2) ---
    mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0                  # 0 = direct q projection
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    yarn: Optional[common.Yarn] = None    # YaRN rope scaling
    # --- numerics / runtime ---
    dtype: Any = jnp.bfloat16
    remat: bool = True
    max_seq: int = 32768
    q_chunk: int = 512            # flash attention tiling (0 = full seq)
    kv_chunk: int = 512
    # Expert-parallel dispatch via shard_map (§Perf): tokens stay on their
    # data shard, every model-rank selects+computes only ITS experts, one
    # bf16 psum over 'model' combines — replaces the GSPMD global scatter
    # (which replicates the dispatch buffers). 0 = baseline pjit scatter.
    ep_shard_map: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def qk_head_dim(self) -> int:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim
                if self.mla else self.head_dim)

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts held here."""
        return self.experts_held or (0, self.n_experts)

    @property
    def mla_scale(self) -> float:
        """Softmax scale of MLA scores: ``qk_head_dim^-1/2``, times YaRN's
        ``mscale²`` when the rope is scaled."""
        gain = self.yarn.softmax_gain() if self.yarn else 1.0
        return gain / float(np.sqrt(self.qk_head_dim))

    def angles(self, max_len: int) -> jnp.ndarray:
        """Rope angles [max_len, rotated dims // 2]."""
        return rope_freqs(self.qk_rope_head_dim if self.mla
                          else self.head_dim, max_len, self.rope_theta,
                          self.yarn)

    def n_params(self) -> int:
        """Total parameter count (for 6ND model-FLOPs accounting)."""
        d, h, kh = self.d_model, self.n_heads, self.n_kv_heads
        dh = self.head_dim
        if self.mla:
            r, dr = self.kv_lora_rank, self.qk_rope_head_dim
            dn, dv = self.qk_nope_head_dim, self.v_head_dim
            attn = d * (self.q_lora_rank or 0)
            q_in = self.q_lora_rank if self.q_lora_rank else d
            attn += q_in * h * (dn + dr)          # q proj
            attn += d * (r + dr)                  # compressed kv + rope key
            attn += r * h * (dn + dv)             # up-projections
            attn += h * dv * d                    # out
        else:
            attn = d * (h + 2 * kh) * dh + h * dh * d
        per_layer = []
        for li in range(self.n_layers):
            ffn = 3 * d * self.d_ff
            if self.moe and li >= self.n_dense_layers:
                ffn = 3 * d * self.d_ff_expert * (self.n_experts + self.n_shared)
                ffn += d * self.n_experts         # router
            per_layer.append(attn + ffn + 2 * d)
        return sum(per_layer) + 2 * self.vocab * d + d

    def n_active_params(self) -> int:
        """Activated parameters per token (MoE: only routed top-k count)."""
        if not self.moe:
            return self.n_params()
        d = self.d_model
        total = self.n_params()
        inactive = (self.n_experts - self.top_k) * 3 * d * self.d_ff_expert \
            * (self.n_layers - self.n_dense_layers)
        return total - inactive


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _attn_init(key, cfg: TransformerConfig, rules: Rules):
    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    p: Params = {}
    s: Params = {}
    if cfg.mla:
        r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
        if cfg.q_lora_rank:
            p["w_dq"] = dense_init(ks[0], d, cfg.q_lora_rank, cfg.dtype)
            p["q_norm"] = jnp.ones((cfg.q_lora_rank,), cfg.dtype)
            p["w_uq"] = dense_init(ks[1], cfg.q_lora_rank, h * (dn + dr), cfg.dtype)
            s["w_dq"] = rules.spec("fsdp", "model")
            s["q_norm"] = rules.spec(None)
            s["w_uq"] = rules.spec("fsdp", "model")
        else:
            p["w_q"] = dense_init(ks[0], d, h * (dn + dr), cfg.dtype)
            s["w_q"] = rules.spec("fsdp", "model")
        p["w_dkv"] = dense_init(ks[2], d, r, cfg.dtype)
        p["kv_norm"] = jnp.ones((r,), cfg.dtype)
        p["w_kr"] = dense_init(ks[3], d, dr, cfg.dtype)
        p["w_uk"] = dense_init(ks[4], r, h * dn, cfg.dtype)
        p["w_uv"] = dense_init(ks[5], r, h * dv, cfg.dtype)
        p["w_o"] = dense_init(ks[6], h * dv, d, cfg.dtype)
        s.update(w_dkv=rules.spec("fsdp", None), kv_norm=rules.spec(None),
                 w_kr=rules.spec("fsdp", None), w_uk=rules.spec(None, "model"),
                 w_uv=rules.spec(None, "model"), w_o=rules.spec("model", "fsdp"))
    else:
        p["w_q"] = dense_init(ks[0], d, h * dh, cfg.dtype)
        p["w_k"] = dense_init(ks[1], d, kh * dh, cfg.dtype)
        p["w_v"] = dense_init(ks[2], d, kh * dh, cfg.dtype)
        p["w_o"] = dense_init(ks[3], h * dh, d, cfg.dtype)
        s.update(w_q=rules.spec("fsdp", "model"), w_k=rules.spec("fsdp", "model"),
                 w_v=rules.spec("fsdp", "model"), w_o=rules.spec("model", "fsdp"))
        if cfg.qkv_bias:
            p["b_q"] = jnp.zeros((h * dh,), cfg.dtype)
            p["b_k"] = jnp.zeros((kh * dh,), cfg.dtype)
            p["b_v"] = jnp.zeros((kh * dh,), cfg.dtype)
            s.update(b_q=rules.spec("model"), b_k=rules.spec("model"),
                     b_v=rules.spec("model"))
    return p, s


def _ffn_init(key, cfg: TransformerConfig, rules: Rules, moe_layer: bool):
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p: Params = {}
    s: Params = {}
    if moe_layer:
        e, f = cfg.held[1], cfg.d_ff_expert
        p["router"] = dense_init(ks[0], d, cfg.n_experts, jnp.float32)
        p["w_gate"] = (jax.random.normal(ks[1], (e, d, f))
                       / np.sqrt(d)).astype(cfg.dtype)
        p["w_up"] = (jax.random.normal(ks[2], (e, d, f))
                     / np.sqrt(d)).astype(cfg.dtype)
        p["w_down"] = (jax.random.normal(ks[3], (e, f, d))
                       / np.sqrt(f)).astype(cfg.dtype)
        s.update(router=rules.spec("fsdp", None),
                 w_gate=rules.spec("expert", None, "fsdp"),
                 w_up=rules.spec("expert", None, "fsdp"),
                 w_down=rules.spec("expert", "fsdp", None))
        if cfg.n_shared:
            fs = cfg.n_shared * f
            p["ws_gate"] = dense_init(ks[4], d, fs, cfg.dtype)
            p["ws_up"] = dense_init(ks[5], d, fs, cfg.dtype)
            p["ws_down"] = dense_init(ks[0], fs, d, cfg.dtype)
            s.update(ws_gate=rules.spec("fsdp", "model"),
                     ws_up=rules.spec("fsdp", "model"),
                     ws_down=rules.spec("model", "fsdp"))
    else:
        f = cfg.d_ff
        p["w_gate"] = dense_init(ks[0], d, f, cfg.dtype)
        p["w_up"] = dense_init(ks[1], d, f, cfg.dtype)
        p["w_down"] = dense_init(ks[2], f, d, cfg.dtype)
        s.update(w_gate=rules.spec("fsdp", "model"),
                 w_up=rules.spec("fsdp", "model"),
                 w_down=rules.spec("model", "fsdp"))
    return p, s


def _layer_init(key, cfg: TransformerConfig, rules: Rules, moe_layer: bool):
    k1, k2 = jax.random.split(key)
    pa, sa = _attn_init(k1, cfg, rules)
    pf, sf = _ffn_init(k2, cfg, rules, moe_layer)
    p = {"attn": pa, "ffn": pf,
         "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
         "ln2": jnp.ones((cfg.d_model,), cfg.dtype)}
    s = {"attn": sa, "ffn": sf, "ln1": rules.spec(None), "ln2": rules.spec(None)}
    return p, s


def init(key, cfg: TransformerConfig, rules: Rules) -> Tuple[Params, Params]:
    """Returns (params, spec tree of PartitionSpec)."""
    ke, kl, ko = jax.random.split(key, 3)
    n_dense = cfg.n_dense_layers if cfg.moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense

    p: Params = {"embed": dense_init(ke, cfg.vocab, cfg.d_model, cfg.dtype,
                                     scale=1.0),
                 "unembed": dense_init(ko, cfg.d_model, cfg.vocab, cfg.dtype),
                 "ln_f": jnp.ones((cfg.d_model,), cfg.dtype)}
    s: Params = {"embed": rules.spec("vocab", "fsdp"),
                 "unembed": rules.spec("fsdp", "vocab"),
                 "ln_f": rules.spec(None)}

    def stack(key, n, moe_layer):
        keys = jax.random.split(key, n)
        ps = [(_layer_init(k, cfg, rules, moe_layer)) for k in keys]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[x[0] for x in ps])
        spec = jax.tree.map(
            lambda sp: jax.sharding.PartitionSpec(None, *sp), ps[0][1],
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        return stacked, spec

    if n_dense:
        p["dense_layers"], s["dense_layers"] = stack(kl, n_dense, False)
    if n_moe:
        kl2 = jax.random.fold_in(kl, 1)
        p["moe_layers"], s["moe_layers"] = stack(kl2, n_moe, True)
    return p, s


# ---------------------------------------------------------------------------
# MoE dispatch (sort-based, fixed capacity)
# ---------------------------------------------------------------------------

class MoEStats(NamedTuple):
    aux_loss: jnp.ndarray
    dropped_frac: jnp.ndarray


def route(router: jnp.ndarray, x: jnp.ndarray, cfg: TransformerConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Softmax routing over all ``n_experts`` in float32, greedy top-k:
    ``(probs [T, E], weights [T, k], experts [T, k])``. The weights are
    renormalised with ``norm_topk_prob``, else scaled by
    ``routed_scaling_factor`` (DeepSeek-V2's gate)."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    elif cfg.routed_scaling_factor != 1.0:
        top_p = top_p * cfg.routed_scaling_factor
    return probs, top_p, top_i


def expert_share_ffn(p: Params, x: jnp.ndarray, cfg: TransformerConfig,
                     active: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless routed experts of the share held here, plus the shared
    experts: x [T, D] -> (y [T, D], load [held] int32).

    Tokens route over all ``n_experts`` (:func:`route`); this layer adds
    ``sum over e in top-k and held of w_e * SwiGLU_e(x)`` and the shared
    experts, and what experts held elsewhere would add is left to their
    chips. Every held expert runs on every token of the batch, weighted
    by the token's routing weight for it (0 where not routed), so no
    token-expert pair is ever dropped and a token's output does not
    depend on its batch-mates. The held experts' weights are read once a
    step either way; at a chip's share of an expert-parallel deployment
    (8 of 64 experts) the batch's extra FLOPs cost less than that read.
    ``load`` counts the pairs of ``active`` tokens (all by default) that
    land on each held expert.
    """
    first, count = cfg.held
    _, top_p, top_i = route(p["router"], x, cfg)
    hit = (top_i[:, :, None] - first) == jnp.arange(count)   # [T, k, e]
    w = jnp.where(hit, top_p[:, :, None], 0.0).sum(1)        # [T, e]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, p["w_gate"])) * \
        jnp.einsum("td,edf->etf", x, p["w_up"])
    out = jnp.einsum("etf,efd->etd", h, p["w_down"])
    # the weighting and the sum over held experts stay float32 elementwise
    # (an f32 matmul would round the weights to bfloat16 on the TPU)
    y = (w.T[:, :, None] * out.astype(jnp.float32)).sum(0).astype(x.dtype)
    if cfg.n_shared:
        y = y + common.swiglu(x, p["ws_gate"], p["ws_up"], p["ws_down"])
    if active is not None:
        hit = hit & active[:, None, None]
    return y, hit.sum(axis=(0, 1), dtype=jnp.int32)


def _moe_routed_shardmap(p: Params, x: jnp.ndarray, cfg: TransformerConfig,
                         rules: Rules, mesh) -> Tuple[jnp.ndarray, MoEStats]:
    """Expert-parallel routed experts under shard_map.

    Token activations are replicated over 'model' (they are sharded over
    the dp axes only), so dispatch needs NO communication: each model-rank
    locally selects the token->slot assignments that target its own expert
    slice, computes them, and one bf16 psum over 'model' combines the
    top-k partial outputs. Expert FFN weights stay ZeRO-sharded over the
    fsdp axis and are all-gathered per layer (explicit FSDP).
    """
    from jax.sharding import PartitionSpec as P

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    ep = "model"
    ep_size = mesh.shape[ep]
    e, k = cfg.n_experts, cfg.top_k
    e_l = e // ep_size
    t, d = x.shape
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    t_l = t // dp_size
    cap = int(np.ceil(cfg.capacity_factor * t_l * k / e))
    cap = max(8, (cap + 7) // 8 * 8)
    fsdp_axes = tuple(a for a in ("data",) if a in mesh.axis_names)

    def body(x_l, router, wg, wu, wd):
        idx = jax.lax.axis_index(ep)
        probs, top_p, top_i = route(router, x_l, cfg)
        flat_e = top_i.reshape(-1).astype(jnp.int32)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        tok_of = (order // k).astype(jnp.int32)
        starts = jnp.searchsorted(sorted_e, jnp.arange(e, dtype=jnp.int32))
        pos = jnp.arange(t_l * k, dtype=jnp.int32) - starts[sorted_e]
        valid = pos < cap
        e_off = idx * e_l
        local = valid & (sorted_e >= e_off) & (sorted_e < e_off + e_l)
        slot = jnp.where(local, (sorted_e - e_off) * cap + pos, e_l * cap)
        buf = jnp.zeros((e_l * cap + 1, d), x_l.dtype).at[slot].set(
            x_l[tok_of])
        buf = buf[: e_l * cap].reshape(e_l, cap, d)
        # explicit FSDP: gather this rank's expert slice over the fsdp axis
        if fsdp_axes:
            wg = jax.lax.all_gather(wg, fsdp_axes, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_axes, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axes, axis=1, tiled=True)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_l * cap, d)
        gathered = jnp.where(local[:, None],
                             out[jnp.minimum(slot, e_l * cap - 1)], 0.0)
        weight = top_p.reshape(-1)[order].astype(x_l.dtype)
        y = jax.ops.segment_sum(gathered * weight[:, None], tok_of,
                                num_segments=t_l)
        y = jax.lax.psum(y, ep)                              # combine top-k
        me = probs.mean(axis=0)
        ce = jax.ops.segment_sum(jnp.ones_like(flat_e, jnp.float32),
                                 flat_e, num_segments=e) / (t_l * k)
        aux = e * jnp.sum(me * ce) * cfg.aux_loss_coef
        drop = 1.0 - valid.mean()
        return y, aux[None], drop[None]

    batch_spec = P(dp_axes if len(dp_axes) != 1 else dp_axes[0], None)
    w_in_spec = P(ep, None, fsdp_axes[0] if fsdp_axes else None)
    wd_spec = P(ep, fsdp_axes[0] if fsdp_axes else None, None)
    y, aux, drop = jax.shard_map(
        body, mesh=mesh,
        in_specs=(batch_spec, P(None, None), w_in_spec, w_in_spec, wd_spec),
        out_specs=(batch_spec, P(dp_axes), P(dp_axes)),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, MoEStats(aux_loss=aux.mean(), dropped_frac=drop.mean())


def moe_ffn(p: Params, x: jnp.ndarray, cfg: TransformerConfig,
            rules: Rules) -> Tuple[jnp.ndarray, MoEStats]:
    """Routed top-k experts + shared experts. x: [T, D] -> [T, D].

    Dispatch is sort-based: token-expert pairs are sorted by expert id, the
    within-expert position is ``arange - start(expert)``, and pairs beyond
    the per-expert capacity are dropped (classic capacity-factor semantics)
    — no [T, E] position tensors are ever built. Training's dispatch:
    it holds every expert (decode uses :func:`expert_share_ffn`).
    """
    if cfg.held != (0, cfg.n_experts):
        raise ValueError("capacity dispatch holds every routed expert; "
                         f"this config holds {cfg.held}")
    if cfg.ep_shard_map:
        mesh = _ambient_mesh()
        if mesh is not None and "model" in mesh.axis_names \
                and cfg.n_experts % mesh.shape["model"] == 0:
            y, stats = _moe_routed_shardmap(p, x, cfg, rules, mesh)
            if cfg.n_shared:
                y = y + common.swiglu(x, p["ws_gate"], p["ws_up"],
                                      p["ws_down"])
            return y, stats

    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(np.ceil(cfg.capacity_factor * t * k / e))
    cap = max(8, ((cap + 7) // 8) * 8)

    probs, top_p, top_i = route(p["router"], x, cfg)    # [T, E], [T, k]

    flat_e = top_i.reshape(-1).astype(jnp.int32)          # [T*k]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    tok_of = (order // k).astype(jnp.int32)
    starts = jnp.searchsorted(sorted_e, jnp.arange(e, dtype=jnp.int32))
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    valid = pos < cap
    slot = jnp.where(valid, sorted_e * cap + pos, e * cap)  # overflow -> dump row

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(x[tok_of])
    buf = rules.shard(buf[: e * cap].reshape(e, cap, d), "expert", None, None)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out = rules.shard(out, "expert", None, None).reshape(e * cap, d)

    gathered = jnp.where(valid[:, None], out[jnp.minimum(slot, e * cap - 1)], 0.0)
    weight = top_p.reshape(-1)[order].astype(x.dtype)
    y = jax.ops.segment_sum(gathered * weight[:, None], tok_of, num_segments=t)

    # load-balance aux (Switch-style): E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jax.ops.segment_sum(jnp.ones_like(flat_e, jnp.float32), flat_e,
                             num_segments=e) / (t * k)
    aux = e * jnp.sum(me * ce) * cfg.aux_loss_coef
    stats = MoEStats(aux_loss=aux,
                     dropped_frac=1.0 - valid.mean())

    if cfg.n_shared:
        y = y + common.swiglu(x, p["ws_gate"], p["ws_up"], p["ws_down"])
    return y, stats


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _partial_rope(x: jnp.ndarray, angles: jnp.ndarray, frac: float) -> jnp.ndarray:
    """Rotate the first ``frac`` of the head dim (chatglm3 uses 0.5)."""
    if frac >= 1.0:
        return apply_rope(x, angles)
    d = x.shape[-1]
    dr = int(d * frac) // 2 * 2
    return jnp.concatenate(
        [apply_rope(x[..., :dr], angles[..., : dr // 2]), x[..., dr:]], axis=-1)


def gqa_attention(p: Params, x: jnp.ndarray, cfg: TransformerConfig,
                  rules: Rules, angles: jnp.ndarray) -> jnp.ndarray:
    b, sq, d = x.shape
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["w_q"]
    kk = x @ p["w_k"]
    v = x @ p["w_v"]
    if cfg.qkv_bias:
        q, kk, v = q + p["b_q"], kk + p["b_k"], v + p["b_v"]
    # head-dim shardings are left to propagation from the weight shardings:
    # explicit constraints here fight GSPMD when n_(kv_)heads < |model| and
    # force full rematerialization copies (observed in the dry-run).
    q = q.reshape(b, sq, h, dh)
    kk = kk.reshape(b, sq, kh, dh)
    v = v.reshape(b, sq, kh, dh)
    q = _partial_rope(q, angles[:sq], cfg.rope_fraction)
    kk = _partial_rope(kk, angles[:sq], cfg.rope_fraction)
    o = flash_attention(q, kk, v, causal=True,
                        q_chunk=cfg.q_chunk or sq,
                        kv_chunk=cfg.kv_chunk or sq)
    o = o.reshape(b, sq, h * dh)
    return rules.shard(o @ p["w_o"], "batch", "seq", None)


def mla_attention(p: Params, x: jnp.ndarray, cfg: TransformerConfig,
                  rules: Rules, angles: jnp.ndarray) -> jnp.ndarray:
    """Training/prefill MLA: materialize per-head K from c_kv (flash over
    concat [nope | rope] dims). Decode uses the absorbed path instead."""
    b, s, d = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if cfg.q_lora_rank:
        q = rms_norm(x @ p["w_dq"], p["q_norm"]) @ p["w_uq"]
    else:
        q = x @ p["w_q"]
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, angles[:s])

    c_kv = rms_norm(x @ p["w_dkv"], p["kv_norm"])         # [B, S, r]
    k_rope = apply_rope((x @ p["w_kr"])[:, :, None, :], angles[:s])  # [B,S,1,dr]
    k_nope = (c_kv @ p["w_uk"]).reshape(b, s, h, dn)
    v = (c_kv @ p["w_uv"]).reshape(b, s, h, dv)

    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    if cfg.yarn is not None:       # flash scales by qk_head_dim^-1/2 only
        q_cat = q_cat * cfg.yarn.softmax_gain()
    k_cat = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))],
                            axis=-1)
    o = flash_attention(q_cat, k_cat, v, causal=True,
                        q_chunk=cfg.q_chunk or s,
                        kv_chunk=cfg.kv_chunk or s)
    o = o.reshape(b, s, h * dv)
    return rules.shard(o @ p["w_o"], "batch", "seq", None)


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def _layer_fwd(p: Params, x: jnp.ndarray, cfg: TransformerConfig, rules: Rules,
               angles: jnp.ndarray, moe_layer: bool):
    attn = mla_attention if cfg.mla else gqa_attention
    x = x + attn(p["attn"], rms_norm(x, p["ln1"]), cfg, rules, angles)
    hn = rms_norm(x, p["ln2"])
    if moe_layer:
        b, s, d = hn.shape
        y, stats = moe_ffn(p["ffn"], hn.reshape(b * s, d), cfg, rules)
        return x + y.reshape(b, s, d), stats.aux_loss
    y = common.swiglu(hn, p["ffn"]["w_gate"], p["ffn"]["w_up"],
                      p["ffn"]["w_down"])
    return x + y, jnp.zeros((), jnp.float32)


def forward(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig,
            rules: Rules) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B, S] -> (logits [B, S, V], aux_loss scalar)."""
    b, s = tokens.shape
    angles = cfg.angles(s)
    x = rules.shard(params["embed"][tokens], "batch", "seq", None)

    aux_total = jnp.zeros((), jnp.float32)

    def scan_stack(x, stacked, moe_layer, aux_total):
        def body(carry, layer_p):
            xc, aux = carry
            fn = _layer_fwd
            if cfg.remat:
                fn = jax.checkpoint(
                    functools.partial(_layer_fwd, cfg=cfg, rules=rules,
                                      angles=angles, moe_layer=moe_layer),
                    prevent_cse=False)
                xn, a = fn(layer_p, xc)
            else:
                xn, a = fn(layer_p, xc, cfg, rules, angles, moe_layer)
            return (xn, aux + a), None
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), stacked)
        return x, aux_total

    if "dense_layers" in params:
        x, aux_total = scan_stack(x, params["dense_layers"], False, aux_total)
    if "moe_layers" in params:
        x, aux_total = scan_stack(x, params["moe_layers"], True, aux_total)

    x = rms_norm(x, params["ln_f"])
    logits = rules.shard(x @ params["unembed"], "batch", None, "vocab")
    return logits, aux_total


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray],
            cfg: TransformerConfig, rules: Rules) -> Tuple[jnp.ndarray, Dict]:
    logits, aux = forward(params, batch["tokens"], cfg, rules)
    ce = cross_entropy(logits, batch["labels"], batch.get("mask"))
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Decode (KV cache, one token)
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, max_seq: int,
               rules: Rules) -> Tuple[Params, Params]:
    """Cache pytree + PartitionSpec tree. The sequence axis of the cache is
    sharded over 'model' (sequence-parallel KV) — at 32k context the cache,
    not the weights, is the footprint that must scale with chips."""
    n = cfg.n_layers
    if cfg.mla:
        r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        cache = {
            "c_kv": jnp.zeros((n, batch, max_seq, r), cfg.dtype),
            "k_rope": jnp.zeros((n, batch, max_seq, dr), cfg.dtype),
        }
        spec = {
            "c_kv": rules.spec(None, "batch", "kv_seq", None),
            "k_rope": rules.spec(None, "batch", "kv_seq", None),
        }
    else:
        kh, dh = cfg.n_kv_heads, cfg.head_dim
        cache = {
            "k": jnp.zeros((n, batch, max_seq, kh, dh), cfg.dtype),
            "v": jnp.zeros((n, batch, max_seq, kh, dh), cfg.dtype),
        }
        spec = {
            "k": rules.spec(None, "batch", "kv_seq", None, None),
            "v": rules.spec(None, "batch", "kv_seq", None, None),
        }
    return cache, spec


def _decode_attn_gqa(p, x, layer_cache, pos, cfg: TransformerConfig, rules,
                     angles):
    b, _, d = x.shape                                     # [B, 1, D]
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kh
    q = x @ p["w_q"]
    kk = x @ p["w_k"]
    v = x @ p["w_v"]
    if cfg.qkv_bias:
        q, kk, v = q + p["b_q"], kk + p["b_k"], v + p["b_v"]
    ang = jax.lax.dynamic_slice_in_dim(angles, pos, 1, axis=0)
    q = _partial_rope(q.reshape(b, 1, h, dh), ang, cfg.rope_fraction)
    kk = _partial_rope(kk.reshape(b, 1, kh, dh), ang, cfg.rope_fraction)
    v = v.reshape(b, 1, kh, dh)

    k_cache = jax.lax.dynamic_update_slice_in_dim(layer_cache["k"], kk, pos, 1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(layer_cache["v"], v, pos, 1)
    max_s = k_cache.shape[1]
    mask = (jnp.arange(max_s) <= pos)[None, :, None, None, None]

    qh = q.reshape(b, 1, kh, g, dh)
    s = jnp.einsum("bqhgd,bkhd->bkhgq", qh, k_cache,
                   preferred_element_type=jnp.float32) / np.sqrt(dh)
    s = jnp.where(mask, s, -jnp.inf)
    pmax = s.max(axis=1, keepdims=True)
    e = jnp.exp(s - pmax)
    num = jnp.einsum("bkhgq,bkhd->bqhgd", e.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    den = e.sum(axis=1).reshape(b, kh, g, 1)[:, None]
    o = (num / den).astype(x.dtype).reshape(b, 1, h * dh)
    return o @ p["w_o"], {"k": k_cache, "v": v_cache}


def _decode_attn_mla(p, x, layer_cache, pos, cfg: TransformerConfig, rules,
                     angles):
    """Absorbed MLA decode: scores/values live in the kv_lora_rank basis."""
    b, _, d = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    if cfg.q_lora_rank:
        q = rms_norm(x @ p["w_dq"], p["q_norm"]) @ p["w_uq"]
    else:
        q = x @ p["w_q"]
    q = q.reshape(b, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    ang = jax.lax.dynamic_slice_in_dim(angles, pos, 1, axis=0)
    q_rope = apply_rope(q_rope[:, None], ang)[:, 0]       # [B, h, dr]

    # absorb W_uk: q_eff[b,h,r] so scores dot against c_kv directly
    w_uk = p["w_uk"].reshape(r, h, dn)
    q_eff = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)

    c_new = rms_norm(x @ p["w_dkv"], p["kv_norm"])        # [B, 1, r]
    kr_new = apply_rope((x @ p["w_kr"])[:, :, None, :], ang)[:, :, 0]  # [B,1,dr]
    c_cache = jax.lax.dynamic_update_slice_in_dim(layer_cache["c_kv"], c_new,
                                                  pos, 1)
    kr_cache = jax.lax.dynamic_update_slice_in_dim(layer_cache["k_rope"],
                                                   kr_new, pos, 1)
    max_s = c_cache.shape[1]
    scale = cfg.mla_scale
    s = (jnp.einsum("bhr,bsr->bhs", q_eff, c_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_rope, kr_cache,
                      preferred_element_type=jnp.float32)) * scale
    mask = (jnp.arange(max_s) <= pos)[None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", pr.astype(c_cache.dtype), c_cache,
                     preferred_element_type=jnp.float32)   # [B, h, r]
    w_uv = p["w_uv"].reshape(r, h, dv)
    o = jnp.einsum("bhr,rhv->bhv", ctx.astype(x.dtype), w_uv)
    o = o.reshape(b, 1, h * dv)
    return o @ p["w_o"], {"c_kv": c_cache, "k_rope": kr_cache}


def decode_step(params: Params, cache: Params, tokens: jnp.ndarray,
                pos: jnp.ndarray, cfg: TransformerConfig,
                rules: Rules) -> Tuple[jnp.ndarray, Params]:
    """One decode step. tokens [B, 1] int32; pos scalar int32 (current
    length). Returns (logits [B, V], updated cache)."""
    b = tokens.shape[0]
    max_seq = (cache["c_kv"] if cfg.mla else cache["k"]).shape[2]
    angles = cfg.angles(max_seq)
    x = rules.shard(params["embed"][tokens], "batch", None, None)

    decode_attn = _decode_attn_mla if cfg.mla else _decode_attn_gqa

    n_dense = cfg.n_dense_layers if cfg.moe else cfg.n_layers
    new_cache = jax.tree.map(lambda c: c, cache)

    def run_stack(x, stacked, cache_slice, layer_offset, moe_layer):
        def body(carry, inp):
            xc = carry
            layer_p, layer_c = inp
            hn = rms_norm(xc, layer_p["ln1"])
            o, new_c = decode_attn(layer_p["attn"], hn, layer_c, pos, cfg,
                                   rules, angles)
            xc = xc + o
            hn2 = rms_norm(xc, layer_p["ln2"])
            if moe_layer:
                y, _ = expert_share_ffn(layer_p["ffn"], hn2.reshape(b, -1),
                                        cfg)
                y = y.reshape(xc.shape)
            else:
                y = common.swiglu(hn2, layer_p["ffn"]["w_gate"],
                                  layer_p["ffn"]["w_up"],
                                  layer_p["ffn"]["w_down"])
            return xc + y, new_c

        return jax.lax.scan(body, x, (stacked, cache_slice))

    def cache_slice(lo, hi):
        return jax.tree.map(lambda c: c[lo:hi], cache)

    if "dense_layers" in params:
        x, cd = run_stack(x, params["dense_layers"], cache_slice(0, n_dense),
                          0, False)
    else:
        cd = None
    if "moe_layers" in params:
        x, cm = run_stack(x, params["moe_layers"],
                          cache_slice(n_dense, cfg.n_layers), n_dense, True)
    else:
        cm = None
    if cd is not None and cm is not None:
        new_cache = jax.tree.map(lambda a, b2: jnp.concatenate([a, b2]), cd, cm)
    else:
        new_cache = cd if cd is not None else cm

    x = rms_norm(x, params["ln_f"])
    logits = rules.shard(x[:, 0] @ params["unembed"], "batch", "vocab")
    return logits, new_cache


def prefill(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig,
            rules: Rules) -> jnp.ndarray:
    """Prefill forward — logits for every position (cache fill is modeled by
    the same forward; the dry-run shape of interest is the compute)."""
    logits, _ = forward(params, tokens, cfg, rules)
    return logits
