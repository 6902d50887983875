"""jit'd wrapper for flash-decode on model-layout tensors."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _kernel
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

    q [B,1,Hq,D]; stores [L, num_blocks, block_size, Hkv*D]; layer an
    int32 scalar; block_tables [B, max_blocks] int32; kv_length [B]
    -> [B,1,Hq,D]."""
    B, _, Hq, D = q.shape
    Hkv = k_store.shape[3] // D
    qg = q[:, 0].reshape(B, Hkv, Hq // Hkv, D)
    out = paged_decode_attention_grouped(qg, k_store, v_store, layer,
                                         block_tables.astype(jnp.int32),
                                         kv_length.astype(jnp.int32),
                                         interpret=interpret)
    return out.reshape(B, 1, Hq, D)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_latent(q_lat, q_pe, store, layer, block_tables, kv_length,
                        *, scale: float, interpret: bool = False):
    """Paged latent (MLA) flash-decode of layer ``layer`` of a stacked
    store.

    q_lat [B,H,r] (the query absorbed into the latent space); q_pe
    [B,H,rope] (roped); store [L, num_blocks, block_size/2, 2*(r+rope)];
    layer an int32 scalar; block_tables [B, max_blocks] int32; kv_length
    [B]; scale the softmax scale.  Returns the weighted latent [B,H,r]."""
    q_pe = q_pe.astype(q_lat.dtype)
    zl, zp = jnp.zeros_like(q_lat), jnp.zeros_like(q_pe)
    q = jnp.concatenate([  # against a row's even, then its odd position
        jnp.concatenate([q_lat, zl, q_pe, zp], axis=-1),
        jnp.concatenate([zl, q_lat, zp, q_pe], axis=-1)], axis=1)
    return _kernel.paged_decode_latent(
        q, store, layer, block_tables, kv_length.astype(jnp.int32),
        rank=q_lat.shape[-1], sm_scale=scale, interpret=interpret)
