"""Flash-decode — single-token GQA attention over a long KV cache.

Grid ``(B, n_k_blocks)``: each program streams one KV block (the paged
kernel: several) of one sequence, ALL kv heads at once, and updates the
online-softmax state of every query head in VMEM scratch.  A block's
last dimensions are the cache's own, which is what the TPU's tiling asks
of a block, so the kernels compile for any head count and head width.
KV-length masking handles the ragged valid region of the cache; blocks
at or past a sequence's length are predicated off.

The contiguous kernel's scores are elementwise products reduced over
each head's ``D`` on the vector unit (a decode query is one row per
head, too thin for the MXU), in float32 whatever the cache dtype.  This
is the memory-roofline kernel: per block it moves ``2 * block * Hkv *
D`` cache elements and does ``O(G * block * Hkv * D)`` MACs —
arithmetic intensity ~G.

Two variants:

* ``decode_attention_grouped`` — contiguous caches ``[B, S, Hkv, D]``
  (the slot-pool layout); the KV block index IS the grid index.
* ``paged_decode_attention_grouped`` — block-paged stores stacked over
  layers and laid out lane-dense, ``[L, num_blocks, block_size,
  Hkv*D]``, plus a layer index and per-sequence block tables: the index
  and the tables ride in scalar-prefetch SMEM (``PrefetchScalarGridSpec``)
  so each grid step's BlockSpec index maps return ``(layer, table[b,
  j])`` and the DMA engine fetches the right *physical* blocks of the
  right layer straight from the stack — neither the layer slice nor the
  gather costs a copy.  A block is ``[block_size, Hkv*D]``: the heads
  side by side on the lane axis, which no head width pads (a store
  ``[..., Hkv, D]`` at D=64 pads each row to 128 lanes in the kernel's
  row-major layout, so the chip keeps it in another and relays the
  whole stack on every step).  The heads are split on the MXU, whatever
  D is: each query head is a row holding its D values on its own
  head's lanes and zeros elsewhere, so one product scores every head
  against every position of a grid step's blocks, and a second
  accumulates the softmax-weighted rows, of which each query head keeps
  its own head's lanes.  A grid step reads several blocks of the table,
  as the latent kernel does.  Logical blocks at or past a sequence's
  length are predicated off (their table entries point at the null
  block 0).

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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, block: int, n_blocks: int, sm_scale: float):
    """One KV block of one sequence.  q/o: [1, G, Hkv, D]; k/v:
    [1, block, Hkv, D]; scratch acc [G, Hkv, D], m/l [G, Hkv, 1]."""
    kv_len = len_ref[pl.program_id(0)]
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


def decode_attention_grouped(q, k_cache, v_cache, kv_length, *,
                             block_k: int = 256, interpret: bool = False):
    """q: [B, Hkv, G, D]; caches: [B, S, Hkv, D]; kv_length: [B] int32.

    Returns [B, Hkv, G, D].  q is laid out [B, G, Hkv, D] so each grid
    step's q/out block is every head of one sequence.
    """
    B, Hkv, G, D = q.shape
    S = k_cache.shape[1]
    block_k = min(block_k, S)
    if S % block_k:
        raise ValueError(f"cache len {S} % block_k {block_k} != 0")
    q_spec = pl.BlockSpec((1, G, Hkv, D), lambda b, ki, *_: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, block_k, Hkv, D),
                           lambda b, ki, ln: (b, ki, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, Hkv, D), jnp.float32),
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, block=block_k,
                               n_blocks=S // block_k,
                               sm_scale=1.0 / math.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, Hkv, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(kv_length.astype(jnp.int32), jnp.swapaxes(q, 1, 2), k_cache, v_cache)
    return jnp.swapaxes(out, 1, 2)


# KV blocks one grid step of a paged kernel reads (256 positions at the
# engine's 16-position blocks), so that a step's two MXU products are
# more than slivers: at Moonlight's decode shapes (batch 128, 512-block
# tables, one layer) a TPU v5e took 21.8, 7.9, 5.5 and 4.4 ms a call of
# the latent kernel at 1, 4, 8 and 16 blocks a step
BLOCKS_PER_STEP = 16


def _blocks_per_step(n_blocks: int, most: int) -> int:
    """The most blocks, up to ``most``, that divide a table's length."""
    return max(d for d in range(1, most + 1) if n_blocks % d == 0)


def _weighted(p, v):
    """``p`` [n, rows] float32 times ``v`` [rows, W], in float32.  A
    bfloat16 ``v`` takes ``p`` as two bfloat16 parts, its leading bits
    and the rest, stacked so that one MXU product weighs both (16 of
    p's 24 mantissa bits, against 8 in one part)."""
    if v.dtype == jnp.float32:
        return jnp.dot(p, v, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype).astype(jnp.float32)
    both = jnp.dot(jnp.concatenate([hi, p - hi], axis=0).astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return both[:p.shape[0]] + both[p.shape[0]:]


def _paged_decode_kernel(layer_ref, bt_ref, len_ref, q_ref, *refs,
                         n_steps: int, sm_scale: float):
    """Several KV blocks of one sequence, lane-dense.  q: [1, G*Hkv,
    Hkv*D], query head (g, h) on row g*Hkv + h, its D values on head h's
    lanes and zeros elsewhere; refs: the step's K blocks, then its V
    blocks, each [block_size, Hkv*D], then o [1, G*Hkv, Hkv*D], then
    scratch acc [G*Hkv, Hkv*D] and m/l [G*Hkv, 1]."""
    del layer_ref, bt_ref  # consumed by the index maps
    *kv_refs, o_ref, acc_ref, m_ref, l_ref = refs
    n = len(kv_refs) // 2
    kv_len = len_ref[pl.program_id(0)]
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = ki * n * kv_refs[0].shape[0]

    @pl.when(start < kv_len)
    def _compute():
        q = q_ref[0]
        k = jnp.concatenate([r[...] for r in kv_refs[:n]], axis=0)
        v = jnp.concatenate([r[...] for r in kv_refs[n:]], axis=0)
        # every head's scores in one product: a row's zeros drop the
        # other heads' lanes.  Bfloat16 products are exact in float32.
        exact = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
        s = jax.lax.dot_general(q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                                precision=exact,
                                preferred_element_type=jnp.float32)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s * sm_scale, NEG_INF)  # [G*Hkv, rows]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _weighted(p, v)
        m_ref[...] = m_new

    @pl.when(ki == n_steps - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_decode_attention_grouped(q, k_store, v_store, layer, block_tables,
                                   kv_length, *, interpret: bool = False,
                                   blocks_per_step: int = BLOCKS_PER_STEP):
    """Paged flash-decode of one layer of a stacked store, through
    block-table indirection.

    q: [B, Hkv, G, D]; stores: [L, num_blocks, block_size, Hkv*D], head
    h of a row on lanes [h*D, (h+1)*D); layer: int32 scalar, the layer
    read; block_tables: [B, max_blocks] int32 physical block ids
    (entries at or past ceil(kv_length/block_size) must point at a
    valid — conventionally the null — block; they are
    compute-predicated off); kv_length: [B].  Each grid step reads
    ``blocks_per_step`` blocks of the table (fewer where that does not
    divide max_blocks).  Returns [B, Hkv, G, D].

    The layer, tables and lengths are scalar-prefetched: the k/v
    BlockSpec index maps receive them AFTER the grid indices and return
    ``(layer, table[b, ...], 0, 0)``, so the layer and the physical
    block are resolved at DMA issue time.  The kernel reads the stack
    where it lies: a caller that carries the whole store through its
    layer loop never slices a layer out, and the paged gather is free
    relative to the contiguous kernel, which is the point of paging on a
    machine that cannot reallocate buffers dynamically.
    """
    B, Hkv, G, D = q.shape
    width = Hkv * D
    if k_store.shape[-1] != width:
        raise ValueError(f"store rows of {k_store.shape[-1]} lanes do not "
                         f"hold {Hkv} heads of {D}")
    bs, n_blocks = k_store.shape[2], block_tables.shape[1]
    n = _blocks_per_step(n_blocks, blocks_per_step)

    def kv_map(j):
        return lambda b, ki, ly, bt, ln: (ly[0], bt[b, ki * n + j], 0, 0)

    kv_specs = [pl.BlockSpec((None, None, bs, width), kv_map(j))
                for j in range(n)]
    row_spec = pl.BlockSpec((1, G * Hkv, width), lambda b, ki, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_blocks // n),
        in_specs=[row_spec] + kv_specs + kv_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((G * Hkv, width), jnp.float32),
            pltpu.VMEM((G * Hkv, 1), jnp.float32),
            pltpu.VMEM((G * Hkv, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, n_steps=n_blocks // n,
                               sm_scale=1.0 / math.sqrt(D))
    # query head (g, h) on row g*Hkv + h, on head h's lanes only
    own = jnp.eye(Hkv, dtype=bool)[:, :, None]
    qx = jnp.where(own, jnp.swapaxes(q, 1, 2)[:, :, :, None, :], 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G * Hkv, width), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_tables.astype(jnp.int32), kv_length.astype(jnp.int32),
      qx.reshape(B, G * Hkv, width).astype(jnp.promote_types(
          q.dtype, k_store.dtype)),
      *([k_store] * n), *([v_store] * n))
    # each row's own head: [B, G, Hkv, Hkv, D] -> [B, Hkv, G, D]
    out = jnp.diagonal(out.reshape(B, G, Hkv, Hkv, D), axis1=2, axis2=3)
    return jnp.moveaxis(out, -1, 1)


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
                        blocks_per_step: int = BLOCKS_PER_STEP):
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
    k = _blocks_per_step(n_blocks, blocks_per_step)

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
