"""Pure-jnp oracle for flash-decode."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_ref(q, k_cache, v_cache, kv_length):
    """q [B,Hkv,G,D]; caches [B,S,Hkv,D]; kv_length [B] -> [B,Hkv,G,D]."""
    B, Hkv, G, D = q.shape
    S = k_cache.shape[1]
    s = jnp.einsum("bhgd,bkhd->bhgk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / math.sqrt(D)
    valid = jnp.arange(S)[None, :] < kv_length[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def gather_kv(store, block_tables):
    """Materialize contiguous caches from a paged store (oracle gather).

    store [num_blocks, block_size, Hkv, D]; block_tables [B, max_blocks]
    -> [B, max_blocks * block_size, Hkv, D]."""
    B, mb = block_tables.shape
    _, bs, Hkv, D = store.shape
    return store[block_tables].reshape(B, mb * bs, Hkv, D)


def gather_blocks(store, block_tables):
    """Contiguous rows from a paged store of any row width: store
    [num_blocks, rows, ...]; block_tables [B, max_blocks] -> [B,
    max_blocks * rows, ...]."""
    B, mb = block_tables.shape
    rows = store.shape[1]
    return store[block_tables].reshape((B, mb * rows) + store.shape[2:])


def paged_decode_latent_ref(q_lat, q_pe, store, block_tables, kv_length,
                            scale):
    """Paged latent (MLA) oracle, in float32: q_lat [B,H,r], q_pe
    [B,H,rope]; store [num_blocks, block_size/2, 2*(r+rope)], rows
    ``[c(2i) | c(2i+1) | k_pe(2i) | k_pe(2i+1)]``; block_tables [B,
    max_blocks]; kv_length [B] -> the weighted latent [B,H,r]."""
    r = q_lat.shape[-1]
    rows = gather_blocks(store, block_tables).astype(jnp.float32)
    B, n = rows.shape[:2]
    c = rows[..., :2 * r].reshape(B, 2 * n, r)
    k_pe = rows[..., 2 * r:].reshape(B, 2 * n, -1)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32), c)
         + jnp.einsum("bhp,bsp->bhs", q_pe.astype(jnp.float32), k_pe)) * scale
    valid = jnp.arange(2 * n)[None, :] < kv_length[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    out = jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, axis=-1), c)
    return out.astype(q_lat.dtype)


def paged_decode_ref(q, k_store, v_store, block_tables, kv_length):
    """Paged oracle: gather through the block tables, then ``decode_ref``.

    q [B,Hkv,G,D]; stores [num_blocks, block_size, Hkv, D]; block_tables
    [B, max_blocks]; kv_length [B] -> [B,Hkv,G,D]."""
    return decode_ref(q, gather_kv(k_store, block_tables),
                      gather_kv(v_store, block_tables), kv_length)
