"""Plain float32 reference of a dense GQA decoder (the Qwen2 family), and
the weights the serving cells run, made on the device from a seed.

The reference follows the published architecture: token embedding,
``num_hidden_layers`` pre-norm blocks (RMSNorm; attention with biased
q/k/v projections, rotary position embedding of the rotate-half form,
grouped key/value heads, causal softmax; RMSNorm; SwiGLU MLP), a final
RMSNorm and the output projection, tied to the embedding when the config
says so. It runs one whole sequence at a time with every matrix product at
``highest`` precision, and imports nothing of the program.

The control (``control_tokens``) is the same forward with both operands of
every matrix product rounded to float8 e4m3 (per-tensor scale), the
nearest precision below the bfloat16 that the configuration states.
"""
from __future__ import annotations

import functools
from typing import Mapping, Tuple

import numpy as np

FP8_MAX = 448.0          # largest finite float8 e4m3fn


def dims(cfg: Mapping) -> Tuple[int, int, int, int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    return (cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"], dh,
            cfg["intermediate_size"], cfg["vocab_size"])


def make_params(cfg: Mapping, seed: int):
    """Random weights in the serving layout, on the device, in the dtype
    served (bfloat16), from one jitted call: matrices ~ N(0, std) with the
    config's ``initializer_range``, biases likewise, norm gains 1 + the
    same noise (so a dropped gain or bias shows). The output projection
    is the embedding's transpose when the embeddings are tied."""
    return param_builder(cfg)(_key(seed))


def param_builder(cfg: Mapping):
    """The jitted ``key -> params`` function of :func:`make_params`."""
    return _builder(dims(cfg) + (float(cfg["initializer_range"]),
                                 bool(cfg["tie_word_embeddings"])))


@functools.lru_cache(maxsize=None)
def _builder(static: Tuple):
    import jax
    import jax.numpy as jnp
    L, d, h, kh, dh, f, V, std, tied = static
    bf16 = jnp.bfloat16

    @jax.jit
    def build(key):
        ks = iter(jax.random.split(key, 16))

        def nrm(shape):
            return (std * jax.random.normal(next(ks), shape, jnp.float32)
                    ).astype(bf16)

        def gain(shape):
            return (1.0 + std * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(bf16)

        embed = nrm((V, d))
        layers = {"attn": {"w_q": nrm((L, d, h * dh)),
                           "w_k": nrm((L, d, kh * dh)),
                           "w_v": nrm((L, d, kh * dh)),
                           "w_o": nrm((L, h * dh, d)),
                           "b_q": nrm((L, h * dh)),
                           "b_k": nrm((L, kh * dh)),
                           "b_v": nrm((L, kh * dh))},
                  "ffn": {"w_gate": nrm((L, d, f)), "w_up": nrm((L, d, f)),
                          "w_down": nrm((L, f, d))},
                  "ln1": gain((L, d)), "ln2": gain((L, d))}
        return {"embed": embed,
                "unembed": embed.T if tied else nrm((d, V)),
                "ln_f": gain((d,)), "dense_layers": layers}
    return build


def _key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _rope(x, cos, sin):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm_exact(a, b):
    return a @ b


def _mm_fp8(a, b):
    """a @ b with both operands rounded to float8 e4m3 (per-tensor
    scale), products summed in float32."""
    import jax.numpy as jnp

    def q(x):
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return q(a) @ q(b)


def _forward(params, tokens, cfg: Tuple, mm):
    """Logits [T, V] of one sequence ``tokens`` [T] (float32)."""
    import jax
    import jax.numpy as jnp
    L, d, h, kh, dh, f, V, theta, eps, tied = cfg
    T = tokens.shape[0]
    f32 = jnp.float32
    inv = 1.0 / (theta ** (np.arange(0, dh, 2) / dh))
    ang = jnp.asarray(np.outer(np.arange(T), inv), f32)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * g.astype(f32)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        a = lp["attn"]
        hn = norm(x, lp["ln1"])
        q = (mm(hn, a["w_q"]) + a["b_q"]).reshape(T, h, dh)
        k = (mm(hn, a["w_k"]) + a["b_k"]).reshape(T, kh, dh)
        v = (mm(hn, a["w_v"]) + a["b_v"]).reshape(T, kh, dh)
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        k = jnp.repeat(k, h // kh, axis=1)
        v = jnp.repeat(v, h // kh, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v).reshape(T, h * dh)
        x = x + mm(o, a["w_o"])
        hn = norm(x, lp["ln2"])
        m = lp["ffn"]
        x = x + mm(jax.nn.silu(mm(hn, m["w_gate"])) * mm(hn, m["w_up"]),
                   m["w_down"])
        return x, None

    x = params["embed"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, params["dense_layers"])
    x = norm(x, params["ln_f"])
    out_w = (params["embed"].T if tied else params["unembed"]).astype(f32)
    return mm(x, out_w)


def _static(cfg: Mapping) -> Tuple:
    return dims(cfg) + (float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]),
                        bool(cfg["tie_word_embeddings"]))


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


def _padded(prompt: np.ndarray, generated, length: int):
    """The served sequence (prompt, then every served token but the last)
    and the positions whose next token was served, padded to ``length``
    so that one program serves every request."""
    import jax.numpy as jnp
    gen = np.asarray(generated, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), gen[:-1]])
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} tokens > {length}")
    t = np.zeros(length, np.int32)
    t[:len(seq)] = seq
    p = np.zeros(length, np.int32)
    p[:len(gen)] = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    return jnp.asarray(t), jnp.asarray(p)


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
    out = _gap_fn(_static(cfg))(params, t, p, jnp.asarray(c))
    return np.asarray(out)[:n]


def control_tokens(params, cfg: Mapping, prompt: np.ndarray, generated,
                   length: int) -> np.ndarray:
    """The control's answer: at each position of the served sequence,
    the token that the float8 forward puts first."""
    t, p = _padded(prompt, generated, length)
    out = _first_fn(_static(cfg))(params, t, p)
    return np.asarray(out)[:len(generated)]
