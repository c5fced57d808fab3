"""Two-tower retrieval (YouTube RecSys'19): huge embedding tables ->
tower MLPs -> dot-product -> in-batch sampled softmax with logQ correction.

The embedding LOOKUP is the hot path: JAX has no native EmbeddingBag, so the
lookup is ``jnp.take`` (XLA hardware gather) + the ``bag_combine`` Pallas
kernel / segment-sum fallback — built here, not stubbed (per assignment).

Tables are sharded over rows on the flattened ("data", "model") axis; the
paper's technique enters as *table-shard placement*: rows are permuted by
the makespan partitioner over the machine tree (co-access edges, access
frequency as vertex weight) so the hottest device / hottest link during the
lookup all-to-all is minimized (``repro.embed.plan_shards``).

Batch dicts:
  train:      user_hist [B, H] int32 (item-id bags, -1 pad),
              user_dense [B, F_d], item_id [B], item_cat [B]
  serve:      same minus the in-batch softmax (pointwise score)
  retrieval:  one user + cand_emb [N_cand, D] precomputed item embeddings
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import Rules
from repro.kernels import ops as kops
from repro.models.gnn import mlp_apply, mlp_init, _mlp_spec

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    name: str
    n_items: int = 1_000_000
    n_cats: int = 10_000
    embed_dim: int = 256
    tower_mlp: Tuple[int, ...] = (1024, 512, 256)
    hist_len: int = 50
    d_dense: int = 16
    temperature: float = 0.05
    dtype: Any = jnp.float32

    def n_params(self) -> int:
        e = self.embed_dim
        emb = (self.n_items + self.n_cats) * e
        dims_u = [e + self.d_dense] + list(self.tower_mlp)
        dims_i = [2 * e] + list(self.tower_mlp)
        mlps = sum(a * b + b for a, b in zip(dims_u[:-1], dims_u[1:]))
        mlps += sum(a * b + b for a, b in zip(dims_i[:-1], dims_i[1:]))
        return emb + mlps


def _row_pad(n: int, m: Optional[int] = None) -> int:
    """Tables padded so row sharding divides the actual device count AND
    rows stay 8-sublane aligned (the old hardcoded 512 over-padded tiny
    smoke tables ~50x on a 1-device host)."""
    if m is None:
        m = math.lcm(max(len(jax.devices()), 1), 8)
    return (n + m - 1) // m * m


def init(key, cfg: TwoTowerConfig, rules: Rules) -> Tuple[Params, Params]:
    ks = jax.random.split(key, 4)
    e = cfg.embed_dim
    p: Params = {
        "item_table": (jax.random.normal(ks[0], (_row_pad(cfg.n_items), e))
                       * 0.01).astype(cfg.dtype),
        "cat_table": (jax.random.normal(ks[1], (_row_pad(cfg.n_cats), e))
                      * 0.01).astype(cfg.dtype),
        "user_tower": mlp_init(ks[2], tuple([e + cfg.d_dense]
                                            + list(cfg.tower_mlp)), cfg.dtype),
        "item_tower": mlp_init(ks[3], tuple([2 * e] + list(cfg.tower_mlp)),
                               cfg.dtype),
    }
    s: Params = {
        "item_table": rules.spec("rows", None),
        "cat_table": rules.spec("rows", None),
        "user_tower": _mlp_spec(p["user_tower"], rules),
        "item_tower": _mlp_spec(p["item_tower"], rules),
    }
    return p, s


def _bag_lookup(table: jnp.ndarray, ids: jnp.ndarray, rules: Rules,
                row_perm: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean-combine embedding bag; ids [B, H] with -1 padding.
    ``row_perm`` [V] maps original -> physical row when the table has been
    permuted device-contiguous by an embed shard plan."""
    valid = (ids >= 0)
    safe = jnp.maximum(ids, 0)
    if row_perm is not None:
        safe = row_perm[safe]
    lens = jnp.maximum(valid.sum(-1, keepdims=True), 1)
    w = valid.astype(table.dtype) / lens.astype(table.dtype)
    return kops.embedding_bag(table, safe, w)


def user_embed(p: Params, batch, cfg: TwoTowerConfig, rules: Rules,
               row_perm: Optional[jnp.ndarray] = None):
    hist = _bag_lookup(p["item_table"], batch["user_hist"], rules, row_perm)
    z = jnp.concatenate([hist, batch["user_dense"].astype(cfg.dtype)], -1)
    u = mlp_apply(p["user_tower"], z)
    return u / jnp.maximum(jnp.linalg.norm(u, axis=-1, keepdims=True), 1e-6)


def item_embed(p: Params, batch, cfg: TwoTowerConfig, rules: Rules,
               row_perm: Optional[jnp.ndarray] = None):
    item_id = batch["item_id"]
    if row_perm is not None:
        item_id = row_perm[item_id]
    it = jnp.take(p["item_table"], item_id, axis=0)
    ct = jnp.take(p["cat_table"], batch["item_cat"], axis=0)
    v = mlp_apply(p["item_tower"], jnp.concatenate([it, ct], -1))
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-6)


def loss_fn(params: Params, batch, cfg: TwoTowerConfig, rules: Rules,
            row_perm: Optional[jnp.ndarray] = None):
    """In-batch sampled softmax with logQ correction (Yi et al. '19)."""
    u = rules.shard(user_embed(params, batch, cfg, rules, row_perm),
                    "batch", None)
    v = rules.shard(item_embed(params, batch, cfg, rules, row_perm),
                    "batch", None)
    logits = (u @ v.T) / cfg.temperature                 # [B, B]
    logits = rules.shard(logits, "batch", "model")
    # logQ: in-batch negatives are sampled ∝ item frequency
    logq = batch.get("log_q")
    if logq is not None:
        logits = logits - logq[None, :]
    b = logits.shape[0]
    labels = jnp.arange(b)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               labels[:, None], 1)[:, 0]
    loss = (logz - gold).mean()
    acc = (logits.argmax(-1) == labels).mean()
    return loss, {"ce": loss, "acc": acc}


def score(params: Params, batch, cfg: TwoTowerConfig, rules: Rules,
          row_perm: Optional[jnp.ndarray] = None):
    """Pointwise serving: score[b] = <u_b, v_b>. [B]"""
    u = user_embed(params, batch, cfg, rules, row_perm)
    v = item_embed(params, batch, cfg, rules, row_perm)
    return jnp.sum(u * v, axis=-1)


def retrieve(params: Params, batch, cfg: TwoTowerConfig, rules: Rules,
             top_k: int = 1024,
             row_perm: Optional[jnp.ndarray] = None):
    """One query against a precomputed candidate matrix [N_cand, D]:
    batched dot + top-k (no loops; candidates row-sharded)."""
    u = user_embed(params, batch, cfg, rules, row_perm)  # [1, D]
    cand = rules.shard(batch["cand_emb"].astype(cfg.dtype), "cand", None)
    scores = (cand @ u[0]).astype(jnp.float32)           # [N_cand]
    vals, idx = jax.lax.top_k(scores, top_k)
    return vals, idx
