"""One continuous-batching decode step over the paged cache.

``paged_decode_step`` mirrors ``models.transformer.decode_step`` (GQA
path) with two changes:

  * per-request positions: ``lengths[b]`` is the number of tokens already
    cached for slot ``b`` — the new token is written there and the causal
    mask is per-row, so mixed prompt/gen lengths batch together;
  * K/V live in page pools ``[n_layers, n_pages + 1, page_size, kh, dh]``
    and are addressed through per-slot page tables, so any physical page
    order (fragmented, placement-permuted) produces the same logits.

The arithmetic (einsum contractions, masked softmax, f32 accumulation) is
kept operation-for-operation identical to ``_decode_attn_gqa`` — the
paged-vs-dense equivalence test in ``tests/test_serving.py`` pins the
logits allclose, which is what makes the paged cache a drop-in serving
substrate rather than a lookalike.

``paged_decode_step_mla`` is the same step for MLA (DeepSeek-V2): one
latent pool ``[n_layers * (n_pages + 1), page_size, width]`` holds each
token's ``c_kv ‖ k_rope``, padded to ``width`` (a multiple of 128 lanes,
``kv_cache.latent_width``); page ``p`` of layer ``l`` is
``[l * (n_pages + 1) + p]``. Attention runs absorbed in the rank basis as
``_decode_attn_mla`` does, with a position per slot. The pool is carried
whole through the layer scan, and each layer writes its new entries with
one scatter, in place. That shape is what keeps the pool in place and
its pages whole tiles on the TPU: a 576-wide entry (not a multiple of
128 lanes), or a layer axis of its own at a layer count that is a
multiple of 4 or 8, gets a transposed device layout, and every step then
copies the pool twice; and a page of 16 padded rows is a run of whole
(16, 128) tiles, which the step gathers without relaying them out. Its
expert layers are the dropless share (``expert_share_ffn``), and the
step returns their per-expert load.

Idle slots are harmless by construction: the engine points them at the
sentinel page (index ``n_pages``) with ``lengths = 0``, so they write
only the sentinel, attend over exactly one finite position, and their
logits are discarded.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import Rules
from repro.models import common
from repro.models.common import apply_rope, rms_norm
from repro.models.transformer import (Params, TransformerConfig, _partial_rope,
                                      expert_share_ffn)


def _paged_attn_gqa(p: Params, x: jnp.ndarray, k_l: jnp.ndarray,
                    v_l: jnp.ndarray, page_table: jnp.ndarray,
                    lengths: jnp.ndarray, cfg: TransformerConfig,
                    angles: jnp.ndarray):
    """x: [B, 1, D]; k_l/v_l: [n_pages + 1, P, kh, dh]; returns the
    attention output and the updated layer pools."""
    b, _, d = x.shape
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kh
    page = k_l.shape[1]
    q = x @ p["w_q"]
    kk = x @ p["w_k"]
    v = x @ p["w_v"]
    if cfg.qkv_bias:
        q, kk, v = q + p["b_q"], kk + p["b_k"], v + p["b_v"]
    ang = angles[lengths][:, None, :]                     # [B, 1, dh/2]
    q = _partial_rope(q.reshape(b, 1, h, dh), ang, cfg.rope_fraction)
    kk = _partial_rope(kk.reshape(b, 1, kh, dh), ang, cfg.rope_fraction)
    v = v.reshape(b, 1, kh, dh)

    # write the new token through the page table, then read the full
    # (updated) history back through it — scatter before gather
    phys = page_table[jnp.arange(b), lengths // page]     # [B]
    off = lengths % page
    k_l = k_l.at[phys, off].set(kk[:, 0])
    v_l = v_l.at[phys, off].set(v[:, 0])
    k_cache = k_l[page_table].reshape(b, -1, kh, dh)      # [B, max_s, ...]
    v_cache = v_l[page_table].reshape(b, -1, kh, dh)
    max_s = k_cache.shape[1]
    mask = (jnp.arange(max_s)[None, :]
            <= lengths[:, None])[:, :, None, None, None]

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
    return o @ p["w_o"], k_l, v_l


def paged_decode_step(params: Params, k_pool: jnp.ndarray,
                      v_pool: jnp.ndarray, page_table: jnp.ndarray,
                      lengths: jnp.ndarray, tokens: jnp.ndarray,
                      cfg: TransformerConfig, rules: Rules
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """tokens [B, 1] int32, lengths [B] int32, page_table [B, max_pages]
    int32 -> (logits [B, V], new k_pool, new v_pool)."""
    b = tokens.shape[0]
    max_seq = page_table.shape[1] * k_pool.shape[2]
    angles = cfg.angles(max_seq)
    x = rules.shard(params["embed"][tokens], "batch", None, None)
    n_dense = cfg.n_dense_layers if cfg.moe else cfg.n_layers

    def run_stack(x, stacked, k_slice, v_slice, moe_layer):
        def body(carry, inp):
            xc = carry
            layer_p, k_l, v_l = inp
            hn = rms_norm(xc, layer_p["ln1"])
            o, k_l, v_l = _paged_attn_gqa(layer_p["attn"], hn, k_l, v_l,
                                          page_table, lengths, cfg, angles)
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
            return xc + y, (k_l, v_l)

        return jax.lax.scan(body, x, (stacked, k_slice, v_slice))

    ks, vs = [], []
    if "dense_layers" in params:
        x, (kd, vd) = run_stack(x, params["dense_layers"],
                                k_pool[:n_dense], v_pool[:n_dense], False)
        ks.append(kd)
        vs.append(vd)
    if "moe_layers" in params:
        x, (km, vm) = run_stack(x, params["moe_layers"], k_pool[n_dense:],
                                v_pool[n_dense:], True)
        ks.append(km)
        vs.append(vm)
    new_k = ks[0] if len(ks) == 1 else jnp.concatenate(ks)
    new_v = vs[0] if len(vs) == 1 else jnp.concatenate(vs)

    x = rms_norm(x, params["ln_f"])
    logits = rules.shard(x[:, 0] @ params["unembed"], "batch", "vocab")
    return logits, new_k, new_v


# one token's latent entry: r + dr elements at (page, slot in the page, 0)
_TOKEN_ENTRY = jax.lax.ScatterDimensionNumbers(
    update_window_dims=(1,), inserted_window_dims=(0, 1),
    scatter_dims_to_operand_dims=(0, 1, 2))


def _paged_attn_mla(p: Params, x: jnp.ndarray, pool: jnp.ndarray,
                    layer: jnp.ndarray, page_table: jnp.ndarray,
                    lengths: jnp.ndarray, cfg: TransformerConfig,
                    angles: jnp.ndarray):
    """Absorbed MLA over the page table. x: [B, 1, D]; pool: the whole
    latent pool, of which layer ``layer``'s rows are read and written;
    returns the attention output and the updated pool."""
    b = x.shape[0]
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    page = pool.shape[1]
    rows = page_table + layer * (pool.shape[0] // cfg.n_layers)
    if cfg.q_lora_rank:
        q = rms_norm(x @ p["w_dq"], p["q_norm"]) @ p["w_uq"]
    else:
        q = x @ p["w_q"]
    q = q.reshape(b, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    ang = angles[lengths][:, None, :]                     # [B, 1, dr/2]
    q_rope = apply_rope(q_rope[:, None], ang)[:, 0]       # [B, h, dr]
    w_uk = p["w_uk"].reshape(r, h, dn)
    q_eff = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)

    # write the new token's latent through the page table, then read the
    # slot's whole (updated) history back through it
    c_new = rms_norm(x @ p["w_dkv"], p["kv_norm"])        # [B, 1, r]
    kr_new = apply_rope((x @ p["w_kr"])[:, :, None, :], ang)[:, :, 0]
    start = jnp.stack([rows[jnp.arange(b), lengths // page],
                       lengths % page, jnp.zeros_like(lengths)], axis=1)
    pool = jax.lax.scatter(
        pool, start,
        jnp.concatenate([c_new, kr_new], axis=-1)[:, 0].astype(pool.dtype),
        _TOKEN_ENTRY, indices_are_sorted=False, unique_indices=False)
    lat = pool[rows].reshape(b, -1, pool.shape[2])        # [B, max_s, width]
    c_cache, kr_cache = lat[..., :r], lat[..., r:r + dr]
    max_s = lat.shape[1]
    s = (jnp.einsum("bhr,bsr->bhs", q_eff, c_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_rope, kr_cache,
                      preferred_element_type=jnp.float32)) * cfg.mla_scale
    mask = (jnp.arange(max_s)[None, :] <= lengths[:, None])[:, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", pr.astype(c_cache.dtype), c_cache,
                     preferred_element_type=jnp.float32)   # [B, h, r]
    w_uv = p["w_uv"].reshape(r, h, dv)
    o = jnp.einsum("bhr,rhv->bhv", ctx.astype(x.dtype), w_uv)
    return o.reshape(b, 1, h * dv) @ p["w_o"], pool


def paged_decode_step_mla(params: Params, latent_pool: jnp.ndarray,
                          page_table: jnp.ndarray, lengths: jnp.ndarray,
                          tokens: jnp.ndarray, cfg: TransformerConfig,
                          rules: Rules
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """tokens [B, 1] int32, lengths [B] int32, page_table [B, max_pages]
    int32 -> (logits [B, V], new latent_pool, load [n_moe_layers, held]
    int32: each MoE layer's token-expert pairs on each held expert, over
    the slots whose page table is not the sentinel's)."""
    b = tokens.shape[0]
    angles = cfg.angles(page_table.shape[1] * latent_pool.shape[1])
    x = rules.shard(params["embed"][tokens], "batch", None, None)
    sentinel = latent_pool.shape[0] // cfg.n_layers - 1
    active = page_table[:, 0] != sentinel
    n_dense = cfg.n_dense_layers if cfg.moe else cfg.n_layers

    def run_stack(x, pool, stacked, first_layer, moe_layer):
        def body(carry, inp):
            xc, pool = carry
            layer_p, layer = inp
            o, pool = _paged_attn_mla(layer_p["attn"],
                                      rms_norm(xc, layer_p["ln1"]), pool,
                                      layer, page_table, lengths, cfg,
                                      angles)
            xc = xc + o
            hn2 = rms_norm(xc, layer_p["ln2"])
            if moe_layer:
                y, load = expert_share_ffn(layer_p["ffn"],
                                           hn2.reshape(b, -1), cfg, active)
                y = y.reshape(xc.shape)
            else:
                y = common.swiglu(hn2, layer_p["ffn"]["w_gate"],
                                  layer_p["ffn"]["w_up"],
                                  layer_p["ffn"]["w_down"])
                load = None
            return (xc + y, pool), load

        n = jax.tree.leaves(stacked)[0].shape[0]
        return jax.lax.scan(body, (x, pool),
                            (stacked, first_layer + jnp.arange(n)))

    pool = latent_pool
    load = jnp.zeros((0, cfg.held[1]), jnp.int32)
    if "dense_layers" in params:
        (x, pool), _ = run_stack(x, pool, params["dense_layers"], 0, False)
    if "moe_layers" in params:
        (x, pool), load = run_stack(x, pool, params["moe_layers"], n_dense,
                                    True)
    x = rms_norm(x, params["ln_f"])
    logits = rules.shard(x[:, 0] @ params["unembed"], "batch", "vocab")
    return logits, pool, load
