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
* ``paged_decode_attention_grouped`` — block-paged stores stacked over
  layers, ``[L, num_blocks, block_size, Hkv, D]``, plus a layer index
  and per-sequence block tables: the index and the tables ride in
  scalar-prefetch SMEM (``PrefetchScalarGridSpec``) so each grid step's
  BlockSpec index map returns ``(layer, table[b, ki])`` and the DMA
  engine fetches the right *physical* block of the right layer straight
  from the stack — neither the layer slice nor the gather costs a copy.
  Logical blocks at or past a sequence's length are predicated off
  (their table entries point at the null block 0).

Both take the lengths in scalar prefetch as well.

``paged_decode_latent`` is their sibling for latent attention (MLA):
every query head of a sequence against ONE shared latent per position.
The stacked store is ``[L, num_blocks, block_size/2, 2*(r+rope)]``,
two positions to a lane-dense row, ``[c(2i) | c(2i+1) | k_pe(2i) |
k_pe(2i+1)]``.  Each head comes twice, once laid out against a row's
even position and once against its odd one, so that one MXU product
per grid step scores all the positions of the step's blocks (q_lat . c
+ q_pe . k_pe, over r + rope) and a second accumulates the
softmax-weighted rows, whose latent halves are the values: the latent is
never duplicated as per-head keys or values.  A grid step reads several
blocks of the table (each its own BlockSpec of the same store), which
both amortises the step and widens the products.  The layer, the tables and the lengths ride in
scalar prefetch as for the GQA kernel.
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


def _paged_decode_kernel(layer_ref, bt_ref, len_ref, *refs, **kw):
    del layer_ref, bt_ref  # consumed by the index maps
    _decode_body(len_ref[pl.program_id(0)], *refs, **kw)


def _call(kernel, q, caches, scalars, kv_index_map, *, block: int,
          n_blocks: int, interpret: bool, name: str):
    """Shared pallas_call: q [B, Hkv, G, D] is laid out [B, G, Hkv, D]
    so each grid step's q/out block is every head of one sequence.
    Cache dimensions before the last four (a stacked store's layer) are
    squeezed out of the K/V blocks, so the body sees
    ``[1, block, Hkv, D]`` either way.  ``name`` names the kernel's
    operation in a device trace, where readers find it by that name."""
    B, Hkv, G, D = q.shape
    qt = jnp.swapaxes(q, 1, 2)
    q_spec = pl.BlockSpec((1, G, Hkv, D),
                          lambda b, ki, *_: (b, 0, 0, 0))
    lead = (None,) * (caches[0].ndim - 4)
    kv_spec = pl.BlockSpec(lead + (1, block, Hkv, D), kv_index_map)
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


def paged_decode_attention_grouped(q, k_store, v_store, layer, block_tables,
                                   kv_length, *, interpret: bool = False):
    """Paged flash-decode of one layer of a stacked store, through
    block-table indirection.

    q: [B, Hkv, G, D]; stores: [L, num_blocks, block_size, Hkv, D];
    layer: int32 scalar, the layer read; block_tables: [B, max_blocks]
    int32 physical block ids (entries at or past
    ceil(kv_length/block_size) must point at a valid — conventionally
    the null — block; they are compute-predicated off); kv_length: [B].
    Returns [B, Hkv, G, D].

    The layer, tables and lengths are scalar-prefetched: the k/v
    BlockSpec index maps receive them AFTER the grid indices and return
    ``(layer, table[b, ki], 0, 0, 0)``, so the layer and the physical
    block are resolved at DMA issue time.  The kernel reads the stack
    where it lies: a caller that carries the whole store through its
    layer loop never slices a layer out, and the paged gather is free
    relative to the contiguous kernel, which is the point of paging on a
    machine that cannot reallocate buffers dynamically.
    """
    return _call(_paged_decode_kernel, q, (k_store, v_store),
                 (jnp.reshape(layer, (1,)).astype(jnp.int32),
                  block_tables.astype(jnp.int32),
                  kv_length.astype(jnp.int32)),
                 lambda b, ki, ly, bt, ln: (ly[0], bt[b, ki], 0, 0, 0),
                 block=k_store.shape[2], n_blocks=block_tables.shape[1],
                 interpret=interpret, name="paged_decode_attention")


# KV blocks one grid step of the latent kernel reads (256 positions), so
# that a step's two MXU products are more than slivers: at Moonlight's
# decode shapes (batch 128, 512-block tables, one layer) a TPU v5e took
# 21.8, 7.9, 5.5 and 4.4 ms a call at 1, 4, 8 and 16 blocks a step
LATENT_BLOCKS_PER_STEP = 16


def _latent_kernel(layer_ref, bt_ref, len_ref, q_ref, *refs, rank: int,
                   n_steps: int, sm_scale: float):
    """``blocks`` blocks of one sequence.  q: [1, 2H, W], the H
    even-position queries ``[q_lat | 0 | q_pe | 0]`` over the H
    odd-position ones ``[0 | q_lat | 0 | q_pe]``; refs: the blocks, each
    [rows, W] (rows = block_size/2), then o [1, H, r], then scratch acc
    [2H, W] and m/l [2H, 1], one max per head kept alike in its even and
    odd row."""
    del layer_ref, bt_ref  # consumed by the index maps
    *kv_refs, o_ref, acc_ref, m_ref, l_ref = refs
    kv_len = len_ref[pl.program_id(0)]
    ki = pl.program_id(1)
    H = o_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    rows = kv_refs[0].shape[0] * len(kv_refs)
    start = ki * 2 * rows

    @pl.when(start < kv_len)
    def _compute():
        # the blocks one under another (through float32, whose 8-row
        # tiles stack where bfloat16's 16-row ones would not)
        kv = jnp.concatenate([r[...].astype(jnp.float32) for r in kv_refs],
                             axis=0).astype(kv_refs[0].dtype)
        s = jax.lax.dot_general(q_ref[0], kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = (start + 2 * jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
               + (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) >= H))
        s = jnp.where(pos < kv_len, s * sm_scale, NEG_INF)  # [2H, rows]
        m_rows = jnp.max(s, axis=1, keepdims=True)
        m_head = jnp.maximum(m_rows[:H], m_rows[H:])
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.concatenate([m_head, m_head], 0))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(kv.dtype), kv, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_steps - 1)
    def _finish():
        acc = acc_ref[...]
        denom = jnp.maximum(l_ref[:H] + l_ref[H:], 1e-30)
        out = acc[:H, :rank] + acc[H:, rank:2 * rank]
        o_ref[0] = (out / denom).astype(o_ref.dtype)


def paged_decode_latent(q, store, layer, block_tables, kv_length, *,
                        rank: int, sm_scale: float, interpret: bool = False,
                        blocks_per_step: int = LATENT_BLOCKS_PER_STEP):
    """Paged latent flash-decode of one layer of a stacked store.

    q: [B, 2H, W], per sequence the H even-position queries
    ``[q_lat | 0 | q_pe | 0]`` over the H odd-position ones ``[0 | q_lat
    | 0 | q_pe]`` (W = 2*(r+rope), a store row's width); store: [L,
    num_blocks, block_size/2, W]; layer: int32 scalar; block_tables: [B,
    max_blocks] int32 (entries past a sequence's length point at a valid
    block, conventionally the null one, and are predicated off);
    kv_length: [B].  Each grid step reads ``blocks_per_step`` blocks of
    the table (fewer where that does not divide max_blocks).  Returns the
    softmax-weighted latent [B, H, r] in q's dtype."""
    B, H2, W = q.shape
    rows = store.shape[2]
    n_blocks = block_tables.shape[1]
    k = max(d for d in range(1, blocks_per_step + 1) if n_blocks % d == 0)

    def kv_map(j):
        return lambda b, ki, ly, bt, ln: (ly[0], bt[b, ki * k + j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_blocks // k),
        in_specs=[pl.BlockSpec((1, H2, W), lambda b, ki, *_: (b, 0, 0))]
        + [pl.BlockSpec((None, None, rows, W), kv_map(j)) for j in range(k)],
        out_specs=pl.BlockSpec((1, H2 // 2, rank),
                               lambda b, ki, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H2, W), jnp.float32),
            pltpu.VMEM((H2, 1), jnp.float32),
            pltpu.VMEM((H2, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_latent_kernel, rank=rank,
                               n_steps=n_blocks // k, sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H2 // 2, rank), q.dtype),
        interpret=interpret,
        name="paged_decode_latent",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_tables.astype(jnp.int32), kv_length.astype(jnp.int32), q,
      *([store] * k))
