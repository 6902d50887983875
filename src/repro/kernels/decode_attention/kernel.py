"""Flash-decode — single-token GQA attention over a long KV cache.

Grid ``(B, n_k_blocks)``: each program streams one KV block of one
sequence, ALL kv heads at once, and updates the online-softmax state of
every query head in VMEM scratch.  A block is ``[block, Hkv, D]``: its
last two dimensions are the cache's own, which is what the TPU's tiling
asks of a block, so the kernel compiles for any head count and head
width.  KV-length masking handles the ragged valid region of the cache;
blocks at or past a sequence's length are predicated off.

The scores are elementwise products reduced over ``D`` on the vector
unit (a decode query is one row per head, too thin for the MXU), in
float32 whatever the cache dtype.  This is the memory-roofline kernel:
per block it moves ``2 * block * Hkv * D`` cache elements and does
``O(G * block * Hkv * D)`` MACs — arithmetic intensity ~G.

Two variants share the online-softmax body:

* ``decode_attention_grouped`` — contiguous caches ``[B, S, Hkv, D]``
  (the slot-pool layout); the KV block index IS the grid index.
* ``paged_decode_attention_grouped`` — block-paged stores
  ``[num_blocks, block_size, Hkv, D]`` plus per-sequence block tables:
  the tables ride in scalar-prefetch SMEM (``PrefetchScalarGridSpec``)
  so each grid step's BlockSpec index map dereferences ``table[b, ki]``
  and the DMA engine fetches the right *physical* block — the gather
  costs no extra copy.  Logical blocks at or past a sequence's length
  are predicated off (their table entries point at the null block 0).

Both take the lengths in scalar prefetch as well.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_body(kv_len, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                 *, block: int, n_blocks: int, sm_scale: float):
    """One KV block of one sequence.  q/o: [1, G, Hkv, D]; k/v:
    [1, block, Hkv, D]; scratch acc [G, Hkv, D], m/l [G, Hkv, 1]."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = ki * block

    @pl.when(start < kv_len)
    def _compute():
        k = k_ref[0].astype(jnp.float32)  # [block, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (block, k.shape[1], 1), 0)
        valid = pos < kv_len
        for g in range(q_ref.shape[1]):  # query heads sharing a kv head
            q = q_ref[0, g].astype(jnp.float32)  # [Hkv, D]
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * sm_scale
            s = jnp.where(valid, s, NEG_INF)  # [block, Hkv, 1]
            m_prev = m_ref[g]  # [Hkv, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=0)
            acc_ref[g] = acc_ref[g] * alpha + jnp.sum(p * v, axis=0)
            m_ref[g] = m_new

    @pl.when(ki == n_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _decode_kernel(len_ref, *refs, **kw):
    _decode_body(len_ref[pl.program_id(0)], *refs, **kw)


def _paged_decode_kernel(bt_ref, len_ref, *refs, **kw):
    del bt_ref  # consumed by the index maps
    _decode_body(len_ref[pl.program_id(0)], *refs, **kw)


def _call(kernel, q, caches, scalars, kv_index_map, *, block: int,
          n_blocks: int, interpret: bool, name: str):
    """Shared pallas_call: q [B, Hkv, G, D] is laid out [B, G, Hkv, D]
    so each grid step's q/out block is every head of one sequence.
    ``name`` names the kernel's operation in a device trace, where
    readers find it by that name."""
    B, Hkv, G, D = q.shape
    qt = jnp.swapaxes(q, 1, 2)
    q_spec = pl.BlockSpec((1, G, Hkv, D),
                          lambda b, ki, *_: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, block, Hkv, D), kv_index_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, n_blocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, Hkv, D), jnp.float32),
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(kernel, block=block, n_blocks=n_blocks,
                               sm_scale=1.0 / math.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
        name=name,
    )(*scalars, qt, *caches)
    return jnp.swapaxes(out, 1, 2)


def decode_attention_grouped(q, k_cache, v_cache, kv_length, *,
                             block_k: int = 256, interpret: bool = False):
    """q: [B, Hkv, G, D]; caches: [B, S, Hkv, D]; kv_length: [B] int32.

    Returns [B, Hkv, G, D].
    """
    S = k_cache.shape[1]
    block_k = min(block_k, S)
    if S % block_k:
        raise ValueError(f"cache len {S} % block_k {block_k} != 0")
    return _call(_decode_kernel, q, (k_cache, v_cache),
                 (kv_length.astype(jnp.int32),),
                 lambda b, ki, ln: (b, ki, 0, 0),
                 block=block_k, n_blocks=S // block_k, interpret=interpret,
                 name="decode_attention")


def paged_decode_attention_grouped(q, k_store, v_store, block_tables,
                                   kv_length, *, interpret: bool = False):
    """Paged flash-decode through block-table indirection.

    q: [B, Hkv, G, D]; stores: [num_blocks, block_size, Hkv, D];
    block_tables: [B, max_blocks] int32 physical block ids (entries at or
    past ceil(kv_length/block_size) must point at a valid — conventionally
    the null — block; they are compute-predicated off); kv_length: [B].
    Returns [B, Hkv, G, D].

    The tables/lengths are scalar-prefetched: the k/v BlockSpec index maps
    receive them AFTER the grid indices and return
    ``(table[b, ki], 0, 0, 0)``, so the physical block is resolved at DMA
    issue time — the paged gather is free relative to the contiguous
    kernel, which is the point of paging on a machine that cannot
    reallocate buffers dynamically.
    """
    return _call(_paged_decode_kernel, q, (k_store, v_store),
                 (block_tables.astype(jnp.int32),
                  kv_length.astype(jnp.int32)),
                 lambda b, ki, bt, ln: (bt[b, ki], 0, 0, 0),
                 block=k_store.shape[1], n_blocks=block_tables.shape[1],
                 interpret=interpret, name="paged_decode_attention")
