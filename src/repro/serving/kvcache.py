"""KV-cache pools for the serving engine: slot-granular and block-paged.

Two designs live here, both XLA-friendly (every physical buffer is
allocated ahead of time; only *indices* change at runtime):

``CachePool`` — the original slot pool: one ``[max_seqs, max_len]``
region per cache leaf, admit/evict at whole-slot granularity
(JetStream-style).  It remains the path for every model family,
including the state-carrying ones (ssm/hybrid) that have no
per-position KV to page.

``PagedCachePool`` — the TPU adaptation of vLLM's PagedAttention
proper: each cache leaf is a ``[num_blocks, block_size, ...]`` physical
store, a sequence is a *block table* (list of physical block ids), and
``BlockAllocator`` hands out blocks with per-block refcounts.  Multiple
sequences sharing a prompt prefix point their tables at the same
physical blocks (refcount > 1); the first divergent write triggers
copy-on-write of just the boundary block.  Admission is by free-block
count, eviction is block-granular, and the engine's radix residency
index becomes real memory headroom instead of whole-slot duplication.
Decode runs DIRECTLY on the physical store: the engine's default
``paged_decode_mode="direct"`` writes each new token's K/V into its
sequence's tail block (one cell per row) and attends through the block
table — the Pallas paged-decode kernel on a TPU, a jnp table-gather
on the CPU — so the per-step cost scales with the
blocks a sequence actually occupies, not ``max_len``.  Only chunked
prefill/extend still reassembles a contiguous ``[B, max_len, ...]``
view (``gather_block_view``) and scatters the newly produced positions
back (``scatter_block_writes``), because extend consumes a whole chunk
of positions at once; ``paged_decode_mode="gather"`` keeps that
round-trip on decode too, as the A/B baseline.  Block 0 is reserved as
a null block: padded batch rows and padded chunk positions write
there, so bucketing never needs masking logic inside the model.

Leaf batch dims are located by the same path rules the dry-run uses for
cache shardings.
"""
from __future__ import annotations

import math
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.launch import specs as sp
from repro.models.config import ModelConfig


def _path_keys(path):
    return [getattr(k, "key", getattr(k, "idx", None)) for k in path]


def batch_dim_for(keys, rank: int) -> int:
    name = keys[-1]
    if name in ("k", "v", "cross_k", "cross_v"):
        return rank - 4
    if name == "len":
        return rank - 1
    if name == "wkv":
        return rank - 4
    if name == "shift":
        return rank - 2
    if name == "ssm":
        return rank - 4
    if len(keys) >= 2 and keys[-2] == "conv":
        return rank - 3
    raise ValueError(f"unknown cache leaf {keys}")


class CachePool:
    """Zero-initialized cache for ``max_seqs`` slots + residency-aware
    slot allocator.

    A freed slot may stay *resident*: its KV still covers a token sequence
    the engine's radix residency index remembers, so a later prompt can
    resume it.  ``allocate()`` therefore prefers blank free slots (FIFO)
    and only recycles a resident one when no blank slot is left — evicting
    reusable KV while a never-used slot sits idle would throw away prefill
    work for nothing.  Among resident slots, free order approximates
    least-recent retirement, so the coldest cache is evicted first.

    The free list is kept as two deques (blank FIFO / resident FIFO), so
    ``allocate()`` is O(1) instead of the old O(n) scan with an O(n)
    ``pop(i)`` inside it."""

    def __init__(self, cfg: ModelConfig, max_seqs: int, max_len: int):
        self.cfg = cfg
        self.max_seqs = max_seqs
        self.max_len = max_len
        tmpl = sp.cache_template(cfg, max_seqs, max_len)
        self.cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tmpl)
        self._free_blank: deque[int] = deque(range(max_seqs))
        self._free_resident: deque[int] = deque()
        self._resident: set[int] = set()

    # -- slot allocation ------------------------------------------------
    def allocate(self) -> Optional[int]:
        """Pop a free slot, blank ones first; the caller must drop any
        residency bookkeeping for the returned slot (its cache is about
        to be replaced)."""
        if self._free_blank:
            return self._free_blank.popleft()
        if self._free_resident:  # no blank slot left: evict the coldest
            slot = self._free_resident.popleft()
            self._resident.discard(slot)
            return slot
        return None

    def free(self, slot: int, resident: bool = False):
        """Return a slot to the pool; ``resident=True`` marks its KV as
        still covering a resumable sequence (prefix reuse)."""
        if resident:
            self._resident.add(slot)
            self._free_resident.append(slot)
        else:
            self._resident.discard(slot)
            self._free_blank.append(slot)

    def take(self, slot: int) -> bool:
        """Claim a SPECIFIC free slot (prefix-reuse admission: the engine
        wants the slot whose cache already holds a matching prefix, not
        whichever the allocator would pop).  Returns False if taken."""
        for q in (self._free_resident, self._free_blank):
            try:
                q.remove(slot)
            except ValueError:
                continue
            self._resident.discard(slot)
            return True
        return False

    @property
    def n_free(self) -> int:
        return len(self._free_blank) + len(self._free_resident)

    @property
    def n_free_blank(self) -> int:
        """Free slots with no resident cache (allocate() serves these
        first)."""
        return len(self._free_blank)

    # -- data movement ----------------------------------------------------
    def insert(self, slot: int, prefill_cache):
        """Write a single-request prefill cache (batch=1) into ``slot``."""

        def upd(path, pool_leaf, new_leaf):
            keys = _path_keys(path)
            bdim = batch_dim_for(keys, pool_leaf.ndim)
            # move batch to front, set, move back
            pool_t = jnp.moveaxis(pool_leaf, bdim, 0)
            src = jnp.moveaxis(new_leaf, batch_dim_for(keys, new_leaf.ndim), 0)
            src0 = src[0]
            # prefill cache may cover fewer positions than the pool
            if src0.shape != pool_t.shape[1:]:
                pad = [(0, p - s) for p, s in zip(pool_t.shape[1:], src0.shape)]
                src0 = jnp.pad(src0, pad)
            pool_t = pool_t.at[slot].set(src0.astype(pool_t.dtype))
            return jnp.moveaxis(pool_t, 0, bdim)

        self.cache = jax.tree_util.tree_map_with_path(
            upd, self.cache, prefill_cache)

    def set_len(self, slot: int, n: int):
        """Fix the true sequence length of a right-padded bucketed prefill."""

        def upd(path, leaf):
            keys = _path_keys(path)
            if keys[-1] != "len":
                return leaf
            bdim = batch_dim_for(keys, leaf.ndim)
            t = jnp.moveaxis(leaf, bdim, 0)
            t = t.at[slot].set(jnp.full_like(t[slot], n))
            return jnp.moveaxis(t, 0, bdim)

        self.cache = jax.tree_util.tree_map_with_path(upd, self.cache)

    def set_lens(self, updates: dict):
        """Batch ``set_len``: one cache-tree rebuild for many slots.  The
        speculative-decode rewind uses this — a verify forward advances
        EVERY slot's length by the chunk width, so all tracked slots
        rewind together in one pass instead of one tree walk per slot."""
        if not updates:
            return

        def upd(path, leaf):
            keys = _path_keys(path)
            if keys[-1] != "len":
                return leaf
            bdim = batch_dim_for(keys, leaf.ndim)
            t = jnp.moveaxis(leaf, bdim, 0)
            for slot, n in updates.items():
                t = t.at[slot].set(jnp.full_like(t[slot], n))
            return jnp.moveaxis(t, 0, bdim)

        self.cache = jax.tree_util.tree_map_with_path(upd, self.cache)

    def reset_slot(self, slot: int):
        def zero(path, pool_leaf):
            keys = _path_keys(path)
            bdim = batch_dim_for(keys, pool_leaf.ndim)
            pool_t = jnp.moveaxis(pool_leaf, bdim, 0)
            pool_t = pool_t.at[slot].set(jnp.zeros_like(pool_t[slot]))
            return jnp.moveaxis(pool_t, 0, bdim)

        self.cache = jax.tree_util.tree_map_with_path(zero, self.cache)


# ---------------------------------------------------------------------------
# Block-paged pool
# ---------------------------------------------------------------------------


NULL_BLOCK = 0  # physical block 0 is never allocated: padded rows write here


class BlockAllocator:
    """Refcounted free-block allocator over ``num_blocks`` physical blocks.

    Block 0 is reserved as the null block (padded batch rows and padded
    chunk positions are redirected there), so ``capacity`` is
    ``num_blocks - 1``.  ``allocate()`` and ``free()`` are O(1);
    ``fork()`` adds a reference so several block tables (or residency
    entries) can share one physical block, and the last ``free()``
    returns it to the free list.  Double frees and forks of unallocated
    blocks raise — a block table pointing at a recycled block silently
    corrupts another sequence's KV, so the invariant is enforced here."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        self.num_blocks = num_blocks
        self._free: deque[int] = deque(range(1, num_blocks))
        self._ref = [0] * num_blocks

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        return self.capacity - len(self._free)

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def allocate(self) -> Optional[int]:
        """Pop a free block with refcount 1, or None when exhausted."""
        if not self._free:
            return None
        b = self._free.popleft()
        self._ref[b] = 1
        return b

    def fork(self, block: int):
        """Add a reference: a second block table now points at ``block``."""
        if block <= NULL_BLOCK or block >= self.num_blocks:
            raise ValueError(f"fork of invalid block {block}")
        if self._ref[block] <= 0:
            raise ValueError(f"fork of unallocated block {block}")
        self._ref[block] += 1

    def free(self, block: int) -> bool:
        """Drop one reference; returns True when the block became free."""
        if block <= NULL_BLOCK or block >= self.num_blocks:
            raise ValueError(f"free of invalid block {block}")
        if self._ref[block] <= 0:
            raise ValueError(f"double free of block {block}")
        self._ref[block] -= 1
        if self._ref[block] == 0:
            self._free.append(block)
            return True
        return False

    def block_savings(self) -> int:
        """Physical blocks saved by sharing: sum of (refcount - 1) over
        live blocks — each extra reference is a block the slot design
        would have duplicated."""
        return sum(r - 1 for r in self._ref if r > 1)


def _kv_write_rows(view_leaf, bdim, write_pos):
    """Rows of a contiguous view at per-sequence positions: [B, T, rest]."""
    v2 = jnp.moveaxis(view_leaf, (bdim, bdim + 1), (0, 1))  # [B, S, rest]
    B = v2.shape[0]
    return v2[jnp.arange(B)[:, None], write_pos]


def gather_block_view(store, block_tables, lens):
    """Reassemble a contiguous cache view from a blocked store.

    ``store``: cache tree with leaves ``[..., num_blocks, block_size, ...]``
    (the batch/seq dims of ``cache_template``); ``block_tables``:
    ``[B, max_blocks]`` int32 physical block ids; ``lens``: ``[B]`` int32
    valid lengths.  Returns a tree shaped like a ``[B, max_blocks *
    block_size, ...]`` slot cache, with ``len`` leaves broadcast from
    ``lens`` — exactly what ``ModelApi.decode`` / ``extend`` expect.
    """
    B, mb = block_tables.shape

    def g(path, leaf):
        keys = _path_keys(path)
        bdim = batch_dim_for(keys, leaf.ndim)
        if keys[-1] == "len":
            lead = leaf.shape[:bdim]
            return jnp.broadcast_to(lens.astype(jnp.int32), lead + (B,))
        s2 = jnp.moveaxis(leaf, (bdim, bdim + 1), (0, 1))  # [N, bs, rest]
        bs = s2.shape[1]
        v = s2[block_tables]  # [B, mb, bs, rest]
        v = v.reshape((B, mb * bs) + s2.shape[2:])
        return jnp.moveaxis(v, (0, 1), (bdim, bdim + 1))

    return jax.tree_util.tree_map_with_path(g, store)


def scatter_block_writes(store, view, write_phys, write_off, write_pos):
    """Write the view rows at ``write_pos[b, t]`` into store blocks
    ``(write_phys[b, t], write_off[b, t])``.

    Only the positions actually produced this step move back — the rest
    of the gathered view is a read-only copy.  Padded (b, t) entries are
    redirected to the null block by the caller (phys 0), so collisions
    there are harmless.  ``len`` leaves of the store are untouched (the
    engine tracks logical lengths host-side)."""

    def s(path, sleaf, vleaf):
        keys = _path_keys(path)
        if keys[-1] == "len":
            return sleaf
        bdim = batch_dim_for(keys, sleaf.ndim)
        written = _kv_write_rows(vleaf, bdim, write_pos)  # [B, T, rest]
        s2 = jnp.moveaxis(sleaf, (bdim, bdim + 1), (0, 1))  # [N, bs, rest]
        s2 = s2.at[write_phys, write_off].set(written.astype(s2.dtype))
        return jnp.moveaxis(s2, (0, 1), (bdim, bdim + 1))

    return jax.tree_util.tree_map_with_path(s, store, view)


def extract_blocks(store, blocks):
    """Serialize physical blocks out of a paged store for migration.

    Returns ``{leaf_path: host_array}`` where each array is the leaf's
    rows at ``blocks`` with the block dim moved to the front —
    ``[n_blocks, block_size, ...]`` — exactly what ``insert_blocks``
    writes back on the receiving engine.  ``len`` leaves are omitted
    (logical lengths are engine host state, carried in the handoff
    metadata, not in the store)."""
    idx = jnp.asarray(list(blocks), jnp.int32)
    out = {}

    def g(path, leaf):
        keys = tuple(_path_keys(path))
        if keys[-1] == "len":
            return leaf
        bdim = batch_dim_for(keys, leaf.ndim)
        t = jnp.moveaxis(leaf, bdim, 0)
        out[keys] = jax.device_get(t[idx])
        return leaf

    jax.tree_util.tree_map_with_path(g, store)
    return out


def insert_blocks(store, leaves, dst_blocks):
    """Write serialized block rows (from ``extract_blocks``, possibly on
    another engine) into this store at ``dst_blocks``.  Leaf paths must
    match — both pools were built from the same ``cache_template`` — and
    ``len`` leaves are untouched."""
    idx = jnp.asarray(list(dst_blocks), jnp.int32)

    def s(path, leaf):
        keys = tuple(_path_keys(path))
        src = leaves.get(keys)
        if src is None:
            return leaf
        bdim = batch_dim_for(keys, leaf.ndim)
        t = jnp.moveaxis(leaf, bdim, 0)
        t = t.at[idx].set(jnp.asarray(src).astype(t.dtype))
        return jnp.moveaxis(t, 0, bdim)

    return jax.tree_util.tree_map_with_path(s, store)


class PagedCachePool:
    """Block-paged physical KV store + allocator.

    Each cache leaf is allocated once as ``[num_blocks, block_size, ...]``
    (via ``cache_template`` with batch=num_blocks, max_len=block_size);
    sequences own *block tables* mapping logical block index ->
    physical block id.  The pool only moves data: the engine owns
    tables, refcount policy (via ``alloc``), and scheduling."""

    def __init__(self, cfg: ModelConfig, num_blocks: int, block_size: int,
                 max_len: int):
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"paged KV cache requires per-position KV (dense/moe), "
                f"not family {cfg.family!r}")
        self.cfg = cfg
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_len = max_len
        self.max_blocks = -(-max_len // block_size)  # blocks per sequence
        if self.max_blocks > num_blocks - 1:
            raise ValueError(
                f"num_blocks={num_blocks} cannot hold one max_len={max_len} "
                f"sequence ({self.max_blocks} blocks of {block_size})")
        tmpl = sp.cache_template(cfg, num_blocks, block_size)
        self.cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tmpl)
        self.alloc = BlockAllocator(num_blocks)

        def copy_fn(store, src, dst):
            def cp(path, leaf):
                keys = _path_keys(path)
                if keys[-1] == "len":
                    return leaf
                bdim = batch_dim_for(keys, leaf.ndim)
                t = jnp.moveaxis(leaf, bdim, 0)
                t = t.at[dst].set(t[src])
                return jnp.moveaxis(t, 0, bdim)

            return jax.tree_util.tree_map_with_path(cp, store)

        self._copy = jax.jit(copy_fn, donate_argnums=(0,))

    def copy_block(self, src: int, dst: int):
        """Copy-on-write: duplicate physical block ``src`` into ``dst``."""
        self.cache = self._copy(self.cache, src, dst)

    @property
    def n_free(self) -> int:
        return self.alloc.n_free

    def block_savings(self) -> int:
        return self.alloc.block_savings()
