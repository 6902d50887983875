"""jit'd wrapper for flash-decode on model-layout tensors."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import decode_attention_grouped, paged_decode_attention_grouped


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, kv_length, *, block_k: int = 256,
                     interpret: bool = False):
    """q [B,1,Hq,D]; caches [B,S,Hkv,D]; kv_length [B] -> [B,1,Hq,D]."""
    B, _, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    qg = q[:, 0].reshape(B, Hkv, Hq // Hkv, D)
    out = decode_attention_grouped(qg, k_cache, v_cache,
                                   kv_length.astype(jnp.int32),
                                   block_k=block_k, interpret=interpret)
    return out.reshape(B, 1, Hq, D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_store, v_store, layer, block_tables,
                           kv_length, *, interpret: bool = False):
    """Paged flash-decode of layer ``layer`` of a stacked store, on
    model-layout tensors.

    q [B,1,Hq,D]; stores [L, num_blocks, block_size, Hkv, D]; layer an
    int32 scalar; block_tables [B, max_blocks] int32; kv_length [B]
    -> [B,1,Hq,D]."""
    B, _, Hq, D = q.shape
    Hkv = k_store.shape[3]
    qg = q[:, 0].reshape(B, Hkv, Hq // Hkv, D)
    out = paged_decode_attention_grouped(qg, k_store, v_store, layer,
                                         block_tables.astype(jnp.int32),
                                         kv_length.astype(jnp.int32),
                                         interpret=interpret)
    return out.reshape(B, 1, Hq, D)
