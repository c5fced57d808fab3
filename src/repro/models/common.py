"""Shared model components: norms, activations, RoPE, init, flash attention.

Parameters are plain nested dicts of jnp arrays; every init function returns
``(params, specs)`` where ``specs`` mirrors the params tree with
``PartitionSpec`` leaves (consumed by the launcher for in_shardings and by
``with_sharding_constraint`` inside forward passes).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


Params = Dict[str, Any]


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32,
               scale: Optional[float] = None) -> jnp.ndarray:
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMS statistics in f32, normalization on the x-dtype path.

    Keeping the multiply in x.dtype keeps every activation COTANGENT in
    bf16 too — the earlier f32-path version dragged the whole backward
    chain (activation grads, FSDP weight all-gathers, gradient
    all-reduces) into f32, doubling collective and HBM bytes (§Perf)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * gamma


def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling as DeepSeek-V2 configures it (``rope_scaling``
    with ``type: yarn``)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def inv_freq(self, d_head: int, theta: float) -> np.ndarray:
        """Blend of interpolated (``/ factor``) and original frequencies,
        the original kept above ``beta_fast`` rotations over the original
        context and the interpolated below ``beta_slow``."""
        extra = 1.0 / (theta ** (np.arange(0, d_head, 2) / d_head))
        inter = extra / self.factor

        def corr(rotations):
            return d_head * np.log(self.original_max_position
                                   / (rotations * 2 * np.pi)) \
                / (2 * np.log(theta))
        low = max(int(np.floor(corr(self.beta_fast))), 0)
        high = min(int(np.ceil(corr(self.beta_slow))), d_head - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(d_head // 2) - low) / (high - low), 0, 1)
        return inter * ramp + extra * (1.0 - ramp)

    def softmax_gain(self) -> float:
        """What multiplies ``1/sqrt(qk_head_dim)``: ``mscale(factor,
        mscale_all_dim)²``."""
        m = _yarn_mscale(self.factor, self.mscale_all_dim) \
            if self.mscale_all_dim else 1.0
        return m * m


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * float(np.log(factor)) + 1.0


def rope_freqs(d_head: int, max_len: int, theta: float = 1e4,
               yarn: Optional[Yarn] = None) -> jnp.ndarray:
    """[max_len, d_head // 2] angles; YaRN-scaled frequencies with
    ``yarn``."""
    if yarn is None:
        inv = 1.0 / (theta ** (np.arange(0, d_head, 2) / d_head))
    else:
        if _yarn_mscale(yarn.factor, yarn.mscale) != _yarn_mscale(
                yarn.factor, yarn.mscale_all_dim):
            raise NotImplementedError("a YaRN cos/sin gain other than 1 "
                                      "(mscale != mscale_all_dim)")
        inv = yarn.inv_freq(d_head, theta)
    t = np.arange(max_len)
    return jnp.asarray(np.outer(t, inv), dtype=jnp.float32)


def apply_rope(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """x: [..., S, H, D]; angles: [S, D//2] (already offset for decode)."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    cos = jnp.cos(angles)[..., :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[..., :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# ---------------------------------------------------------------------------
# Flash attention (pure JAX online softmax) with a FlashAttention-2-style
# custom VJP: the backward recomputes per-block scores from (q, k, v, o,
# lse) instead of letting scan-AD stack O(S^2) residuals — without this the
# compiled HLO materializes the full attention matrix per layer in f32
# (observed: 1.5 TB of dynamic-update-slice traffic in the dry-run).
# ---------------------------------------------------------------------------

def _flash_fwd_impl(q, k, v, causal, q_chunk, kv_chunk):
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    dv = v.shape[-1]
    g = h // kh
    scale = 1.0 / np.sqrt(d)
    q_chunk = min(q_chunk or sq, sq)
    kv_chunk = min(kv_chunk or sk, sk)
    nq = (sq + q_chunk - 1) // q_chunk
    nk = (sk + kv_chunk - 1) // kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, nq * q_chunk - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kv_chunk - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kv_chunk - sk), (0, 0), (0, 0)))
    qb = qp.reshape(b, nq, q_chunk, kh, g, d)
    kb = kp.reshape(b, nk, kv_chunk, kh, d)
    vb = vp.reshape(b, nk, kv_chunk, kh, dv)

    def q_block(qi, q_i):
        def kv_step(carry, kj):
            acc, m, l = carry
            k_j, v_j = kb[:, kj], vb[:, kj]
            s = jnp.einsum("bqhgd,bkhd->bqhgk", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            q_pos = qi * q_chunk + jnp.arange(q_chunk)
            k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            mask = k_pos[None, :] < sk
            if causal:
                mask = mask & (k_pos[None, :] <= q_pos[:, None])
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(jnp.isfinite(m_new)[..., None], p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, q_chunk, kh, g, dv), jnp.float32)
        m0 = jnp.full((b, q_chunk, kh, g), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, q_chunk, kh, g), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-20)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf)
        return out.astype(q.dtype), lse

    out, lse = jax.lax.map(lambda qi: q_block(qi, qb[:, qi]),
                           jnp.arange(nq))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * q_chunk, kh, g, dv)
    lse = jnp.moveaxis(lse, 0, 1).reshape(b, nq * q_chunk, kh, g)
    return (out[:, :sq].reshape(b, sq, h, dv),
            lse[:, :sq])                                   # [B,Sq,Kh,G]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, q_chunk, kv_chunk):
    out, _ = _flash_fwd_impl(q, k, v, causal, q_chunk, kv_chunk)
    return out


def _flash_fwd(q, k, v, causal, q_chunk, kv_chunk):
    out, lse = _flash_fwd_impl(q, k, v, causal, q_chunk, kv_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_chunk, kv_chunk, res, do):
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    dv = v.shape[-1]
    g = h // kh
    scale = 1.0 / np.sqrt(d)
    q_chunk = min(q_chunk or sq, sq)
    kv_chunk = min(kv_chunk or sk, sk)
    nq = (sq + q_chunk - 1) // q_chunk
    nk = (sk + kv_chunk - 1) // kv_chunk
    pad_q = nq * q_chunk - sq
    pad_k = nk * kv_chunk - sk

    qb = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) \
        .reshape(b, nq, q_chunk, kh, g, d)
    dob = jnp.pad(do, ((0, 0), (0, pad_q), (0, 0), (0, 0))) \
        .reshape(b, nq, q_chunk, kh, g, dv)
    ob = jnp.pad(out, ((0, 0), (0, pad_q), (0, 0), (0, 0))) \
        .reshape(b, nq, q_chunk, kh, g, dv)
    lseb = jnp.pad(lse, ((0, 0), (0, pad_q), (0, 0), (0, 0)),
                   constant_values=-jnp.inf) \
        .reshape(b, nq, q_chunk, kh, g)
    kb = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) \
        .reshape(b, nk, kv_chunk, kh, d)
    vb = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) \
        .reshape(b, nk, kv_chunk, kh, dv)

    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)                               # [B,nq,qc,Kh,G]
    q_pos = (jnp.arange(nq)[:, None] * q_chunk
             + jnp.arange(q_chunk)[None, :])               # [nq, qc]

    def j_step(dq_acc, kj):
        k_j, v_j = kb[:, kj], vb[:, kj]                    # [B,kc,Kh,*]
        s = jnp.einsum("bnqhgd,bkhd->bnqhgk", qb, k_j,
                       preferred_element_type=jnp.float32) * scale
        k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
        mask = k_pos[None, None, :] < sk
        if causal:
            mask = mask & (k_pos[None, None, :] <= q_pos[..., None])
        s = jnp.where(mask[None, :, :, None, None, :], s, -jnp.inf)
        p = jnp.exp(s - lseb[..., None])
        p = jnp.where(jnp.isfinite(lseb)[..., None], p, 0.0)
        dv_j = jnp.einsum("bnqhgk,bnqhgd->bkhd", p.astype(jnp.float32),
                          dob.astype(jnp.float32))
        dp = jnp.einsum("bnqhgd,bkhd->bnqhgk", dob.astype(v.dtype), v_j,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bnqhgk,bkhd->bnqhgd",
                                     ds.astype(k.dtype), k_j,
                                     preferred_element_type=jnp.float32)
        dk_j = jnp.einsum("bnqhgk,bnqhgd->bkhd", ds.astype(q.dtype), qb,
                          preferred_element_type=jnp.float32)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((b, nq, q_chunk, kh, g, d), jnp.float32)
    dq, (dk, dv_) = jax.lax.scan(j_step, dq0, jnp.arange(nk))
    dq = dq.reshape(b, nq * q_chunk, h, d)[:, :sq].astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 1).reshape(b, nk * kv_chunk, kh, d)[:, :sk] \
        .astype(k.dtype)
    dv_out = jnp.moveaxis(dv_, 0, 1).reshape(b, nk * kv_chunk, kh, dv)[:, :sk] \
        .astype(v.dtype)
    return dq, dk, dv_out


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    *, causal: bool = True, q_chunk: int = 512,
                    kv_chunk: int = 512) -> jnp.ndarray:
    """Memory-bounded attention: O(S * chunk) live scores instead of O(S^2).

    q: [B, Sq, H, D]; k: [B, Sk, Kh, D]; v: [B, Sk, Kh, Dv] with H a
    multiple of Kh (GQA — query heads are grouped onto KV heads). Dv may
    differ from D (MLA). Returns [B, Sq, H, Dv]. Chunk of 0 = full length.
    """
    return _flash(q, k, v, causal, q_chunk, kv_chunk)


def attention_ref(q, k, v, causal=True):
    """Quadratic oracle for flash_attention tests."""
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    g = h // kh
    kf = jnp.repeat(k, g, axis=2)
    vf = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vf)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean CE over (masked) tokens; logits [.., V], labels [..] int."""
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
