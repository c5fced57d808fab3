"""Hot-row device cache + touched-rows-only (sparse) optimizer updates.

The cache models what a multi-device embedding deployment keeps resident
next to the compute: the measured-hottest rows (static policy) or an LRU
admission set. Hits cost nothing; a miss fetches the row from its owning
shard — ``traffic[requester, owner] += row_bytes`` into the same
``[D, D]`` symmetric zero-diagonal matrix shape the mapping search scores
(``shard_lint.lint_traffic`` lawful) — and the replicated baseline's cost
model (:func:`replicated_update_traffic`: every touched row's gradient
broadcast to the other ``D - 1`` replicas) is what the launcher's
traffic report and the tests compare against.

Sparse optimizer: rowwise Adagrad (one accumulator scalar per row).
Chosen over AdamW for the tables because a zero-gradient row is an exact
no-op — weight decay / moment decay would mutate untouched rows — so the
touched-rows-only scatter update is *bitwise* identical to the dense
full-table update (pinned by test). Three call shapes share one core
formula so the pin holds by construction:

* :func:`dense_row_update`   — full table, grads dense;
* :func:`masked_row_update`  — full table, jit-friendly where-mask
  (what ``make_embed_train_step`` uses: no dynamic shapes under jit);
* :func:`sparse_row_update`  — gather/scatter over explicit unique rows
  (the host-driven cache path).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Optional, Set

import numpy as np


# ---------------------------------------------------------------------------
# rowwise Adagrad (the sparse-friendly table optimizer)
# ---------------------------------------------------------------------------

def _row_step(vals, accum_rows, grads, lr: float, eps: float):
    """One rowwise-Adagrad step on a stack of rows. Single source of
    truth: every update path calls this, so dense/masked/sparse agree
    bitwise wherever the gradient is nonzero (and a zero gradient leaves
    both the row and its accumulator exactly unchanged)."""
    import jax.numpy as jnp
    g32 = grads.astype(jnp.float32)
    g2 = jnp.mean(jnp.square(g32), axis=-1)               # [U]
    accum_new = accum_rows + g2
    scale = lr / (jnp.sqrt(accum_new) + eps)
    vals_new = (vals.astype(jnp.float32)
                - scale[..., None] * g32).astype(vals.dtype)
    return vals_new, accum_new


def dense_row_update(table, accum, grads, *, lr: float = 0.05,
                     eps: float = 1e-8):
    """Full-table reference: (table', accum'). Zero-gradient rows come
    back bitwise unchanged (x - 0.0 == x, accum + 0.0 == accum)."""
    return _row_step(table, accum, grads, lr, eps)


def masked_row_update(table, accum, grads, *, lr: float = 0.05,
                      eps: float = 1e-8):
    """Jit-friendly sparse form: rows with an all-zero gradient are
    *selected* unchanged (a where-mask, no dynamic shapes). Bitwise equal
    to :func:`dense_row_update` by test."""
    import jax.numpy as jnp
    touched = jnp.any(grads != 0, axis=-1)
    vals_new, accum_new = _row_step(table, accum, grads, lr, eps)
    return (jnp.where(touched[..., None], vals_new, table),
            jnp.where(touched, accum_new, accum))


def sparse_row_update(table, accum, rows, grads, *, lr: float = 0.05,
                      eps: float = 1e-8):
    """Touched-rows-only gather/scatter: ``rows`` [U] UNIQUE row ids,
    ``grads`` [U, E]. Bitwise equal to the dense update whose gradient is
    zero outside ``rows`` (by test)."""
    vals_new, accum_new = _row_step(table[rows], accum[rows], grads,
                                    lr, eps)
    return (table.at[rows].set(vals_new),
            accum.at[rows].set(accum_new))


def requester_of(n: int, n_devices: int) -> np.ndarray:
    """[n] requesting device per example — contiguous blocks, the
    row-major data-parallel batch split every launcher mesh uses."""
    return (np.arange(n) * n_devices) // max(n, 1)


def replicated_update_traffic(ids: np.ndarray, requester: np.ndarray,
                              n_devices: int, row_bytes: float
                              ) -> np.ndarray:
    """[D, D] cost of keeping a replicated table consistent for one batch:
    each unique touched row's gradient leaves its requester for the other
    ``D - 1`` replicas (the sparse all-gather a replicated deployment
    cannot avoid)."""
    T = np.zeros((n_devices, n_devices), dtype=np.float64)
    ids = np.asarray(ids).ravel()
    requester = np.asarray(requester).ravel()
    valid = ids >= 0
    # one broadcast per unique (row, requester) touch
    key = np.unique(ids[valid].astype(np.int64) * n_devices
                    + requester[valid].astype(np.int64))
    req = key % n_devices
    for r in req:
        for d in range(n_devices):
            if d != r:
                T[r, d] += row_bytes
                T[d, r] += row_bytes
    return T


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

class HotRowCache:
    """Static-or-LRU hot-row cache over a :class:`ShardedEmbeddingTable`.

    Bookkeeping is host-side (dict + OrderedDict LRU); row values live in
    a device array. A cached row's value is authoritative — updates land
    in the cache slot and are flushed to the backing shard on eviction
    (``pending`` tracks dirty slots), so an eviction never loses an
    update (Hypothesis property + sweep test).

    Counters: ``lookups == hits + misses`` (per id occurrence),
    ``evictions``, ``flushes``; ``traffic`` is the measured ``[D, D]``
    matrix (miss fetches + update writebacks between requester and
    owner). ``check_invariants`` raises on any violation — the
    ``repro.analysis --suite embed`` lint drives it.
    """

    def __init__(self, table, n_cache: int, policy: str = "lru"):
        import jax.numpy as jnp
        if policy not in ("lru", "static"):
            raise ValueError(f"policy must be 'lru' or 'static', "
                             f"got {policy!r}")
        if n_cache < 0:
            raise ValueError(f"n_cache must be >= 0, got {n_cache}")
        self.table = table
        self.policy = policy
        self.n_cache = int(n_cache)
        self.n_devices = table.plan.n_devices
        dim = table.dim
        self.cache = (jnp.zeros((self.n_cache, dim), table.data.dtype)
                      if self.n_cache else None)
        self.row_of = np.full(self.n_cache, -1, dtype=np.int64)
        self.slot_of: Dict[int, int] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._free = list(range(self.n_cache - 1, -1, -1))
        self.pending: Set[int] = set()
        # requester that last wrote each slot (writeback attribution)
        self._writer = np.zeros(self.n_cache, dtype=np.int64)
        self.lookups = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.flushes = 0
        self.traffic = np.zeros((self.n_devices, self.n_devices),
                                dtype=np.float64)

    # -- admission -------------------------------------------------------

    def _admit(self, row: int, requester: int) -> int:
        """Install ``row`` (original id) into a slot, evicting LRU if
        full. Returns the slot."""
        if not self._free:
            self._evict_one()
        slot = self._free.pop()
        self.slot_of[row] = slot
        self.row_of[slot] = row
        self._lru[row] = None
        self._writer[slot] = requester
        self.cache = self.cache.at[slot].set(self.table.lookup(
            np.asarray([row]))[0])
        return slot

    def _evict_one(self) -> None:
        row, _ = self._lru.popitem(last=False)
        slot = self.slot_of.pop(row)
        if slot in self.pending:
            self._flush_slot(slot, row)
        self.row_of[slot] = -1
        self._free.append(slot)
        self.evictions += 1

    def _flush_slot(self, slot: int, row: int) -> None:
        self.table.update_rows(np.asarray([row]), self.cache[slot][None])
        self.pending.discard(slot)
        self.flushes += 1
        owner = int(self.table.plan.row_to_device[row])
        writer = int(self._writer[slot])
        if owner != writer:
            rb = float(self.table.row_bytes)
            self.traffic[writer, owner] += rb
            self.traffic[owner, writer] += rb

    def warm(self, rows: np.ndarray) -> None:
        """Preload rows (hottest-first from ``RowAccessStats.top_rows``)
        without counting traffic — the static policy's working set."""
        for row in np.asarray(rows)[:self.n_cache]:
            row = int(row)
            if row not in self.slot_of:
                self._admit(row, requester=int(
                    self.table.plan.row_to_device[row]))

    # -- the hot path ----------------------------------------------------

    def lookup(self, ids, requester: Optional[np.ndarray] = None):
        """[N] original ids (>= 0) -> [N, E] rows. ``requester`` [N]
        device issuing each lookup (defaults to the contiguous
        data-parallel split). Bookkeeping per occurrence; values come
        from the cache for hits (authoritative under pending updates) and
        from the owning shard for misses."""
        import jax.numpy as jnp
        ids = np.asarray(ids).ravel()
        if requester is None:
            requester = requester_of(ids.shape[0], self.n_devices)
        requester = np.asarray(requester).ravel()
        owners = self.table.plan.row_to_device[ids]
        rb = float(self.table.row_bytes)
        for i, row in enumerate(ids.tolist()):
            self.lookups += 1
            if row in self.slot_of:
                self.hits += 1
                self._lru.move_to_end(row)
                continue
            self.misses += 1
            req, owner = int(requester[i]), int(owners[i])
            if owner != req:
                self.traffic[req, owner] += rb
                self.traffic[owner, req] += rb
            if self.n_cache and self.policy == "lru":
                self._admit(row, req)
        # resolve values against the FINAL slot map: a slot recorded
        # mid-loop can be recycled by a later admission in the same call,
        # and a row evicted mid-call was flushed, so the backing table is
        # authoritative for everything not cached right now
        vals = self.table.lookup(ids)
        hit_pos, hit_slot = [], []
        for i, row in enumerate(ids.tolist()):
            slot = self.slot_of.get(row)
            if slot is not None:
                hit_pos.append(i)
                hit_slot.append(slot)
        if hit_pos:
            vals = vals.at[jnp.asarray(hit_pos)].set(
                self.cache[jnp.asarray(hit_slot)])
        return vals

    # -- updates ---------------------------------------------------------

    def apply_grads(self, rows: np.ndarray, grads, accum,
                    requester: Optional[np.ndarray] = None, *,
                    lr: float = 0.05, eps: float = 1e-8):
        """Sparse rowwise-Adagrad over UNIQUE original ``rows`` [U] with
        ``grads`` [U, E]; returns the updated ``accum`` [V]. Cached rows
        update in place (marked pending, flushed on eviction); uncached
        rows scatter straight into the shard with a writeback charge."""
        import jax.numpy as jnp
        rows = np.asarray(rows).ravel()
        if np.unique(rows).shape[0] != rows.shape[0]:
            raise ValueError("apply_grads needs unique rows (aggregate "
                             "duplicate ids first)")
        if requester is None:
            requester = requester_of(rows.shape[0], self.n_devices)
        grads = jnp.asarray(grads)
        accum = jnp.asarray(accum)
        cached = np.asarray([r in self.slot_of for r in rows.tolist()])
        rb = float(self.table.row_bytes)
        if cached.any():
            idx = np.nonzero(cached)[0]
            slots = np.asarray([self.slot_of[int(rows[i])] for i in idx])
            vals_new, acc_new = _row_step(
                self.cache[jnp.asarray(slots)],
                accum[jnp.asarray(rows[idx])], grads[jnp.asarray(idx)],
                lr, eps)
            self.cache = self.cache.at[jnp.asarray(slots)].set(vals_new)
            accum = accum.at[jnp.asarray(rows[idx])].set(acc_new)
            for i, slot in zip(idx, slots.tolist()):
                self.pending.add(int(slot))
                self._writer[slot] = int(requester[i])
        if (~cached).any():
            idx = np.nonzero(~cached)[0]
            sub = rows[idx]
            # accum is keyed by ORIGINAL id; table rows by physical slot
            phys = jnp.asarray(self.table.plan.perm[sub])
            vals_new, acc_new = _row_step(
                self.table.data[phys], accum[jnp.asarray(sub)],
                grads[jnp.asarray(idx)], lr, eps)
            self.table.data = self.table.data.at[phys].set(vals_new)
            accum = accum.at[jnp.asarray(sub)].set(acc_new)
            for i in idx:
                req = int(requester[i])
                owner = int(self.table.plan.row_to_device[rows[i]])
                if owner != req:
                    self.traffic[req, owner] += rb
                    self.traffic[owner, req] += rb
        return accum

    def flush(self) -> None:
        """Write every pending cached row back to its shard."""
        for slot in sorted(self.pending):
            self._flush_slot(slot, int(self.row_of[slot]))

    def replicated(self):
        """Full table in original order with all cached updates applied
        (flushes first) — the ground truth tests compare against."""
        self.flush()
        return self.table.replicated()

    # -- probes ----------------------------------------------------------

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    @property
    def n_cached(self) -> int:
        return len(self.slot_of)

    def traffic_bytes(self) -> float:
        return float(self.traffic.sum()) / 2.0

    def check_invariants(self) -> None:
        """Raised on violation: slot/row maps are a bijection bounded by
        the pool, LRU tracks exactly the occupied rows, pending slots are
        occupied, hits + misses == lookups, free + occupied partitions
        the pool, and the traffic matrix is lawful."""
        if len(self.slot_of) > self.n_cache:
            raise AssertionError(
                f"{len(self.slot_of)} rows cached in a "
                f"{self.n_cache}-slot pool")
        for row, slot in self.slot_of.items():
            if self.row_of[slot] != row:
                raise AssertionError(
                    f"slot {slot} maps to row {self.row_of[slot]}, "
                    f"expected {row}")
        occupied = set(self.slot_of.values())
        if len(occupied) != len(self.slot_of):
            raise AssertionError("two rows share a cache slot")
        if set(self._lru.keys()) != set(self.slot_of.keys()):
            raise AssertionError("LRU book does not match cached rows")
        if not self.pending <= occupied:
            raise AssertionError(
                f"pending slots {sorted(self.pending - occupied)} are "
                "not occupied")
        if len(self._free) + len(occupied) != self.n_cache:
            raise AssertionError("free + occupied != pool size")
        if self.hits + self.misses != self.lookups:
            raise AssertionError(
                f"hits {self.hits} + misses {self.misses} != lookups "
                f"{self.lookups}")
        t = self.traffic
        if not np.all(np.isfinite(t)) or float(t.min()) < 0.0:
            raise AssertionError("traffic matrix has negative/NaN bytes")
        if float(np.abs(np.diag(t)).max(initial=0.0)) > 0.0:
            raise AssertionError("nonzero self-traffic on the diagonal")
        if float(np.abs(t - t.T).max(initial=0.0)) > 0.0:
            raise AssertionError("traffic matrix is not symmetric")
