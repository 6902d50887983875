"""Attention: GQA + RoPE (+ optional qk-norm / qkv-bias), three impls.

Implementations
  * ``full``     — materialized scores; fine for short sequences & smoke tests.
  * ``chunked``  — block-wise causal attention in pure jnp: python loop over
                   query blocks, each attending only to its prefix.  This keeps
                   HLO FLOPs at flash levels (lower triangle only) and bounds
                   live memory to one ``[B, H, block_q, kv_len]`` score tile —
                   it is both the long-context dry-run path and the oracle
                   shape for the Pallas flash kernel.
  * ``pallas``   — ``repro.kernels.flash_attention`` (compiled on a TPU;
                   interpret mode on the CPU, see ``repro.kernels``).

Decode attends one new token against a (possibly sequence-sharded) KV cache;
softmax over the sharded axis lowers to partial-reduce + all-reduce under
GSPMD, i.e. flash-decode semantics for free.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import kernels

from . import nn
from .config import ModelConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attention_init(key, cfg: ModelConfig, *, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    nhp = cfg.padded_heads
    ks = jax.random.split(key, 6)
    dt = cfg.pdtype
    p = {
        "q": nn.linear_init(ks[0], d, nhp * hd, axes=("embed", "q_proj"),
                            dtype=dt, bias=cfg.qkv_bias, bias_axis="q_proj"),
        "k": nn.linear_init(ks[1], d, nkv * hd, axes=("embed", "kv_proj"),
                            dtype=dt, bias=cfg.qkv_bias, bias_axis="kv_proj"),
        "v": nn.linear_init(ks[2], d, nkv * hd, axes=("embed", "kv_proj"),
                            dtype=dt, bias=cfg.qkv_bias, bias_axis="kv_proj"),
        "o": nn.linear_init(ks[3], nhp * hd, d, axes=("q_proj", "embed"),
                            dtype=dt, stddev=1.0 / math.sqrt(nh * hd)),
    }
    if nhp != nh:
        # TP head padding: heads are laid out per kv-group [real..., pad...];
        # pad heads' o-rows are zeroed, so their contribution is exactly 0.
        mask = _pad_head_mask(cfg)  # [nhp] bool, True = real
        o = p["o"]["w"].value.reshape(nhp, hd, d)
        p["o"]["w"].value = (o * mask[:, None, None]).reshape(nhp * hd, d)
    if cfg.qk_norm:
        p["q_norm"] = nn.rmsnorm_init(hd, axis="head_dim", dtype=dt)
        p["k_norm"] = nn.rmsnorm_init(hd, axis="head_dim", dtype=dt)
    return p


def _pad_head_mask(cfg: ModelConfig):
    """[padded_heads] bool mask; heads grouped per kv head with pads last."""
    nkv = cfg.n_kv_heads
    g_real = cfg.n_heads // nkv
    g_pad = cfg.padded_heads // nkv
    m = jnp.zeros((nkv, g_pad), bool).at[:, :g_real].set(True)
    return m.reshape(-1)


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------


def _tp_ok(cfg: ModelConfig, mesh) -> bool:
    return (cfg.explicit_tp and mesh is not None
            and "model" in getattr(mesh, "axis_names", ())
            and cfg.padded_heads % mesh.shape["model"] == 0)


def _project_qkv(p, x, x_kv, cfg: ModelConfig, q_positions, kv_positions,
                 *, rope: bool, mesh=None):
    """Return q [B,S,Hq,D], k/v [B,Skv,Hkv,D]."""
    B, S, _ = x.shape
    Skv = x_kv.shape[1]
    cd = cfg.cdtype
    if _tp_ok(cfg, mesh):
        q = nn.linear_apply_tp(p["q"], x, "column", mesh, cd,
                               fsdp=cfg.fsdp_params)
    else:
        q = nn.linear_apply(p["q"], x, cd)
    q = q.reshape(B, S, cfg.padded_heads, cfg.head_dim)
    k = nn.linear_apply(p["k"], x_kv, cd).reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    v = nn.linear_apply(p["v"], x_kv, cd).reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = nn.rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
        k = nn.rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    if rope:
        q = nn.apply_rope(q, q_positions, cfg.rope_theta)
        k = nn.apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k, n_q):
    """GQA repeat-KV: [B,S,Hkv,D] -> [B,S,Hq,D].

    Keeps every attention einsum sharded uniformly on the (TP-sharded) q-head
    dim; the repeat is comm-free under GSPMD because the kv-head dim is
    replicated over the model axis.
    """
    B, S, Hkv, D = k.shape
    if Hkv == n_q:
        return k
    return jnp.repeat(k, n_q // Hkv, axis=2)


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def full_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                   kv_mask: Optional[jnp.ndarray] = None):
    """Materialized-scores attention.

    q: [B,Sq,Hq,D]  k,v: [B,Sk,Hkv,D] with Hq % Hkv == 0.
    kv_mask: optional [B,Sk] validity mask.
    """
    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    k = _repeat_kv(k, Hq)
    v = _repeat_kv(v, Hq)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        kpos = jnp.arange(Sk)
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    if kv_mask is not None:
        logits = jnp.where(kv_mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def chunked_causal_attention(q, k, v, *, block_q: int, block_k: int):
    """Block-wise causal attention: python loop over query blocks.

    Each query block i attends only to keys [0, (i+1)*block_q), so compiled
    FLOPs match causal flash attention (half of dense) and live memory is one
    score tile.  Differentiable (plain jnp ops throughout).
    """
    B, S, Hq, D = q.shape
    if S % block_q != 0:
        raise ValueError(f"seq {S} not divisible by block_q {block_q}")
    k = _repeat_kv(k, Hq)
    v = _repeat_kv(v, Hq)
    nq = S // block_q
    scale = 1.0 / math.sqrt(D)
    outs = []
    for i in range(nq):
        q_blk = jax.lax.slice_in_dim(q, i * block_q, (i + 1) * block_q, axis=1)
        kv_len = (i + 1) * block_q
        k_pre = jax.lax.slice_in_dim(k, 0, kv_len, axis=1)
        v_pre = jax.lax.slice_in_dim(v, 0, kv_len, axis=1)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_blk.astype(jnp.float32),
                            k_pre.astype(jnp.float32)) * scale
        # mask only the diagonal block's upper triangle
        qpos = i * block_q + jnp.arange(block_q)
        kpos = jnp.arange(kv_len)
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v_pre)
        outs.append(out)
    return jnp.concatenate(outs, axis=1)


def decode_attention(q, k_cache, v_cache, kv_length):
    """One-step decode: q [B,1,Hq,D] vs caches [B,Smax,Hkv,D].

    ``kv_length``: [B] number of valid cache entries (includes current token).
    """
    B, _, Hq, D = q.shape
    Smax = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    # grouped (no repeat-KV): decode reads the cache once; the cache is
    # sequence-sharded at scale, so softmax over the sharded KV axis lowers to
    # partial-reduce + all-reduce (flash-decode semantics under GSPMD).
    # KV stays in its storage dtype: the einsums accumulate in f32 via
    # preferred_element_type WITHOUT materializing f32 copies of the cache
    # (which would triple the memory-bound decode's HBM traffic).
    qg = q.reshape(B, 1, Hkv, Hq // Hkv, D)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(Smax)[None, :] < kv_length[:, None]  # [B,Smax]
    logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Top-level apply (prefill / train forward)
# ---------------------------------------------------------------------------


def _pick_impl(cfg: ModelConfig, seq: int) -> str:
    if cfg.attention_impl != "auto":
        return cfg.attention_impl
    return "chunked" if seq > 2048 else "full"


def _head_spec(cfg: ModelConfig, mesh, batch: int):
    """P(batch, None, "model", None) when q-heads divide the model axis."""
    if mesh is None or "model" not in mesh.axis_names:
        return None
    if cfg.padded_heads % mesh.shape["model"]:
        return None
    from repro.models import nn as _nn

    bspec = _nn.batch_pspec(mesh, batch, extra_dims=1)
    from jax.sharding import PartitionSpec as P

    return P(*bspec, "model", None)


def _constrain_heads(q, k, v, cfg, mesh):
    """Pin q and (repeated) k/v to head-sharded layouts so the blockwise
    attention loop never re-gathers KV per block (GSPMD propagation
    otherwise resolves the repeat ambiguously and inserts per-block
    all-gathers)."""
    spec = _head_spec(cfg, mesh, q.shape[0])
    if spec is None:
        return q, k, v
    from repro.models import nn as _nn

    q = _nn.constrain(q, mesh, spec)
    k = _nn.constrain(_repeat_kv(k, cfg.padded_heads), mesh, spec)
    v = _nn.constrain(_repeat_kv(v, cfg.padded_heads), mesh, spec)
    return q, k, v


def attention_apply(p, x, cfg: ModelConfig, *, causal=True, positions=None,
                    x_kv=None, kv_positions=None, rope=True, mesh=None,
                    seq_shard=False):
    """Self (or cross, via x_kv) attention over a full sequence."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    x_kv = x if x_kv is None else x_kv
    if kv_positions is None:
        kv_positions = jnp.arange(x_kv.shape[1])[None, :]
    q, k, v = _project_qkv(p, x, x_kv, cfg, positions, kv_positions,
                           rope=rope, mesh=mesh)
    q, k, v = _constrain_heads(q, k, v, cfg, mesh)

    impl = _pick_impl(cfg, S)
    if impl == "pallas" and causal and x_kv is x:
        from repro.kernels.flash_attention import ops as fa_ops

        out = fa_ops.flash_attention(q, k, v, causal=True,
                                     block_q=cfg.attn_chunk_q,
                                     block_k=cfg.attn_chunk_k,
                                     interpret=kernels.pallas_interpret())
    elif impl == "chunked" and causal and x_kv is x and S % cfg.attn_chunk_q == 0:
        out = chunked_causal_attention(q, k, v, block_q=cfg.attn_chunk_q,
                                       block_k=cfg.attn_chunk_k)
    else:
        out = full_attention(q, k, v, causal=causal and x_kv is x)
    out = out.reshape(B, S, cfg.padded_heads * cfg.head_dim)
    if _tp_ok(cfg, mesh):
        return nn.linear_apply_tp(p["o"], out, "row", mesh, cfg.cdtype,
                                  fsdp=cfg.fsdp_params, seq_shard=seq_shard)
    return nn.linear_apply(p["o"], out, cfg.cdtype)


def attention_prefill(p, x, cfg: ModelConfig, *, positions=None, mesh=None):
    """Prefill: forward + return (output, (k_cache_entries, v_cache_entries))."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, x, cfg, positions, positions,
                           rope=cfg.positions == "rope", mesh=mesh)
    qc, kc, vc = _constrain_heads(q, k, v, cfg, mesh)
    impl = _pick_impl(cfg, S)
    if impl == "chunked" and S % cfg.attn_chunk_q == 0:
        out = chunked_causal_attention(qc, kc, vc, block_q=cfg.attn_chunk_q,
                                       block_k=cfg.attn_chunk_k)
    else:
        out = full_attention(qc, kc, vc, causal=True)
    out = out.reshape(B, S, cfg.padded_heads * cfg.head_dim)
    if _tp_ok(cfg, mesh):
        return nn.linear_apply_tp(p["o"], out, "row", mesh, cfg.cdtype,
                                  fsdp=cfg.fsdp_params), (k, v)
    return nn.linear_apply(p["o"], out, cfg.cdtype), (k, v)


def attention_extend(p, x, cache_k, cache_v, kv_length, cfg: ModelConfig):
    """Multi-token cache extension (chunked prefill).

    x: [B,T,d] new tokens appended at positions kv_length..kv_length+T-1;
    cache_k/v: [B,Smax,Hkv,D]; kv_length: [B] valid entries *before* this
    chunk.  Returns (out [B,T,d], new_k, new_v, new_len).

    The T=chunk generalization of ``attention_decode``: the chunk's K/V
    are scattered into the cache at their absolute positions, then each
    chunk query attends to the cache prefix plus the chunk's own causal
    triangle.  The score math mirrors ``full_attention`` (f32 einsum,
    NEG_INF mask, softmax) so a prompt prefilled in chunks produces
    bit-identical KV and logits to a single full-sequence prefill —
    masked positions underflow to exactly 0.0 in the softmax, so the
    extra (masked) cache columns never perturb the f32 sums.
    """
    B, T, _ = x.shape
    Smax = cache_k.shape[1]
    pos = kv_length[:, None] + jnp.arange(T)[None, :]  # [B,T] abs positions
    q, k_new, v_new = _project_qkv(p, x, x, cfg, pos, pos,
                                   rope=cfg.positions == "rope")
    bidx = jnp.arange(B)[:, None]
    cache_k = cache_k.at[bidx, pos].set(k_new)
    cache_v = cache_v.at[bidx, pos].set(v_new)
    new_len = kv_length + T
    Hq = q.shape[2]
    k = _repeat_kv(cache_k, Hq)
    v = _repeat_kv(cache_v, Hq)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    # key j is valid for chunk query t iff j <= its absolute position
    mask = jnp.arange(Smax)[None, None, :] <= pos[:, :, None]  # [B,T,Smax]
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    out = out.reshape(B, T, cfg.padded_heads * cfg.head_dim)
    return nn.linear_apply(p["o"], out, cfg.cdtype), cache_k, cache_v, new_len


def attention_decode_paged(p, x, k_store, v_store, block_tables, kv_length,
                           write_phys, write_off, cfg: ModelConfig):
    """Single-token decode directly against a block-paged KV store.

    x: [B,1,d]; k_store/v_store: [num_blocks, block_size, Hkv, D] physical
    stores shared by every sequence; block_tables: [B, max_blocks] int32
    physical block ids per sequence; kv_length: [B] valid positions
    *before* this token; write_phys/write_off: [B] the (physical block,
    in-block offset) cell where this token's K/V lands (padded batch rows
    point at the null block (0, 0), where collisions are harmless).

    Unlike ``attention_decode`` this never materializes a contiguous
    [B, Smax] cache view: the new K/V row is written into ONLY its tail
    block, and attention reads K/V through the block table.  On a TPU
    that is the scalar-prefetch Pallas kernel (``paged_decode_attention``),
    so the gather happens at DMA issue time and per-token HBM traffic is
    O(blocks-touched) instead of O(Smax).  On the CPU it gathers through
    the table in jnp (the ``paged_decode_ref`` oracle shape) and reuses
    ``decode_attention``, so greedy outputs are bit-identical to the slot
    path; ``attention_impl="pallas"`` runs the kernel there instead, in
    interpret mode.

    Returns (out [B,1,d], k_store, v_store).
    """
    B = x.shape[0]
    pos = kv_length[:, None]  # [B,1] this token's absolute position
    q, k_new, v_new = _project_qkv(p, x, x, cfg, pos, pos,
                                   rope=cfg.positions == "rope")
    k_store = k_store.at[write_phys, write_off].set(
        k_new[:, 0].astype(k_store.dtype))
    v_store = v_store.at[write_phys, write_off].set(
        v_new[:, 0].astype(v_store.dtype))
    new_len = kv_length + 1
    interpret = kernels.pallas_interpret()
    if cfg.attention_impl == "pallas" or not interpret:
        from repro.kernels.decode_attention import ops as da_ops

        out = da_ops.paged_decode_attention(q, k_store, v_store,
                                            block_tables, new_len,
                                            interpret=interpret)
    else:
        from repro.kernels.decode_attention.ref import gather_kv

        out = decode_attention(q, gather_kv(k_store, block_tables),
                               gather_kv(v_store, block_tables), new_len)
    out = out.reshape(B, 1, cfg.padded_heads * cfg.head_dim)
    return nn.linear_apply(p["o"], out, cfg.cdtype), k_store, v_store


def attention_decode(p, x, cache_k, cache_v, kv_length, cfg: ModelConfig):
    """Single-token decode step.

    x: [B,1,d]; cache_k/v: [B,Smax,Hkv,D]; kv_length: [B] valid entries
    *before* this token.  Returns (out [B,1,d], new_k, new_v, new_len).
    """
    B = x.shape[0]
    pos = kv_length[:, None]  # [B,1] this token's position
    q, k_new, v_new = _project_qkv(p, x, x, cfg, pos, pos,
                                   rope=cfg.positions == "rope")
    # write new kv at position kv_length (per batch element)
    idx = kv_length  # [B]
    bidx = jnp.arange(B)
    cache_k = cache_k.at[bidx, idx].set(k_new[:, 0])
    cache_v = cache_v.at[bidx, idx].set(v_new[:, 0])
    new_len = kv_length + 1
    out = decode_attention(q, cache_k, cache_v, new_len)
    out = out.reshape(B, 1, cfg.padded_heads * cfg.head_dim)
    return nn.linear_apply(p["o"], out, cfg.cdtype), cache_k, cache_v, new_len
