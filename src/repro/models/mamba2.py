"""Mamba2 (SSD) blocks and the Zamba2 hybrid model.

Training path uses the chunked SSD algorithm (quadratic only within a chunk,
linear across chunks via a small ``lax.scan``); all decay exponents are
differences of a *decreasing* cumulative log-decay, hence <= 0 and numerically
safe.  Decode is a single-step recurrence carrying ``[B,H,N,P]`` SSM state +
a ``[B,W-1,conv_dim]`` conv tail — O(1) per token, which is what makes
``long_500k`` runnable for the hybrid/ssm archs.

Zamba2 wiring: groups of ``attn_every`` Mamba2 blocks followed by one *shared*
full-attention transformer block (one weight copy reused at every application,
the Zamba trick).  Implemented as an outer scan over groups (stacked params)
with the shared block closed over as a scan constant.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from . import nn
from . import transformer as tfm
from .config import ModelConfig


# ---------------------------------------------------------------------------
# Dims helper
# ---------------------------------------------------------------------------


def ssm_dims(cfg: ModelConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    N = cfg.ssm_state
    conv_dim = d_in + 2 * N
    return d_in, H, N, conv_dim


# ---------------------------------------------------------------------------
# Block params
# ---------------------------------------------------------------------------


def mamba_block_init(key, cfg: ModelConfig):
    """Projections are split (z / x / B / C / dt + per-stream convs) so every
    tensor-parallel dim is a clean logical axis — no slicing of sharded dims.
    Mathematically identical to the fused in_proj/conv of the reference impl.
    """
    d = cfg.d_model
    d_in, H, N, conv_dim = ssm_dims(cfg)
    W = cfg.ssm_conv
    dt = cfg.pdtype
    ks = jax.random.split(key, 10)
    # dt bias: inverse softplus of dt ~ U[1e-3, 1e-1]
    u = jax.random.uniform(ks[2], (H,), minval=math.log(1e-3),
                           maxval=math.log(1e-1))
    dt0 = jnp.exp(u)
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    return {
        "ln": nn.rmsnorm_init(d, dtype=dt),
        "in_z": nn.linear_init(ks[0], d, d_in, axes=("embed", "ssm_inner"),
                               dtype=dt),
        "in_x": nn.linear_init(ks[1], d, d_in, axes=("embed", "ssm_inner"),
                               dtype=dt),
        "in_B": nn.linear_init(ks[5], d, N, axes=("embed", "ssm_state"),
                               dtype=dt),
        "in_C": nn.linear_init(ks[6], d, N, axes=("embed", "ssm_state"),
                               dtype=dt),
        "in_dt": nn.linear_init(ks[7], d, H, axes=("embed", "ssm_heads"),
                                dtype=dt),
        "conv_x": nn.Px(nn.normal_init(ks[1], (W, d_in), dt,
                                       1.0 / math.sqrt(W)),
                        ("conv_w", "ssm_inner")),
        "conv_x_b": nn.Px(jnp.zeros((d_in,), dt), ("ssm_inner",)),
        "conv_B": nn.Px(nn.normal_init(ks[8], (W, N), dt,
                                       1.0 / math.sqrt(W)),
                        ("conv_w", "ssm_state")),
        "conv_B_b": nn.Px(jnp.zeros((N,), dt), ("ssm_state",)),
        "conv_C": nn.Px(nn.normal_init(ks[9], (W, N), dt,
                                       1.0 / math.sqrt(W)),
                        ("conv_w", "ssm_state")),
        "conv_C_b": nn.Px(jnp.zeros((N,), dt), ("ssm_state",)),
        "A_log": nn.Px(jnp.log(jax.random.uniform(
            ks[3], (H,), minval=1.0, maxval=16.0)).astype(jnp.float32),
            ("ssm_heads",)),
        "D": nn.Px(jnp.ones((H,), jnp.float32), ("ssm_heads",)),
        "dt_bias": nn.Px(dt_bias.astype(jnp.float32), ("ssm_heads",)),
        "norm": nn.rmsnorm_init(d_in, axis="ssm_inner", dtype=dt),
        "out_proj": nn.linear_init(ks[4], d_in, d,
                                   axes=("ssm_inner", "embed"), dtype=dt),
    }


# ---------------------------------------------------------------------------
# Causal depthwise conv
# ---------------------------------------------------------------------------


def causal_conv(x, w, b, *, tail=None):
    """x [B,T,C]; w [W,C]; optional tail [B,W-1,C] from previous tokens.

    Returns (y [B,T,C], new_tail [B,W-1,C]).
    """
    W = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)  # [B, T+W-1, C]
    y = sum(
        xp[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(W)
    )
    y = jax.nn.silu(y + b[None, None, :])
    new_tail = xp[:, -(W - 1):, :] if W > 1 else tail
    return y, new_tail


# ---------------------------------------------------------------------------
# SSD (chunked + recurrent)
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, h0=None):
    """Chunked SSD.

    x [B,T,H,P]; dt [B,T,H]; A [H] (negative); Bm/Cm [B,T,N].
    Returns (y [B,T,H,P], h_final [B,H,N,P]).
    """
    B_, T, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, T)
    if T % L:
        raise ValueError(f"T={T} not divisible by chunk={L}")
    nc = T // L
    f32 = jnp.float32

    a = (dt.astype(f32) * A.astype(f32)[None, None, :])  # [B,T,H] <= 0
    xc = x.reshape(B_, nc, L, H, P)
    dtc = dt.reshape(B_, nc, L, H).astype(f32)
    ac = a.reshape(B_, nc, L, H)
    Bc = Bm.reshape(B_, nc, L, N).astype(f32)
    Cc = Cm.reshape(B_, nc, L, N).astype(f32)
    cum = jnp.cumsum(ac, axis=2)  # inclusive, decreasing

    # ---- intra-chunk (quadratic within chunk) ----
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # [B,nc,L,L]
    delta = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,nc,i,j,H]
    mask = jnp.tril(jnp.ones((L, L), bool))
    dec = jnp.where(mask[None, None, :, :, None], jnp.exp(delta), 0.0)
    scores = CB[..., None] * dec * dtc[:, :, None, :, :]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores.astype(x.dtype), xc)

    # ---- chunk-boundary states ----
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [B,nc,L,H] <= 1
    Sc = jnp.einsum("bcjh,bcjn,bcjhp->bchnp",
                    (decay_to_end * dtc).astype(x.dtype), Bc.astype(x.dtype),
                    xc)  # [B,nc,H,N,P]

    # ---- inter-chunk recurrence ----
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # [B,nc,H]
    h_init = (jnp.zeros((B_, H, N, P), x.dtype) if h0 is None
              else h0.astype(x.dtype))

    def scan_f(h, inp):
        cd, s = inp  # cd [B,H], s [B,H,N,P]
        h_new = cd[:, :, None, None].astype(h.dtype) * h + s
        return h_new, h  # emit previous-chunk state

    h_final, h_prevs = jax.lax.scan(
        scan_f, h_init,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(Sc, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # [B,nc,H,N,P]

    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp", Cc.astype(x.dtype),
                         jnp.exp(cum).astype(x.dtype), h_prevs)
    y = (y_intra + y_inter).reshape(B_, T, H, P)
    return y, h_final


def ssd_recurrent(x, dt, A, Bm, Cm, h0=None):
    """Step-by-step oracle; same signature/returns as ssd_chunked."""
    B_, T, H, P = x.shape
    N = Bm.shape[-1]
    h = jnp.zeros((B_, H, N, P), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h, y = ssd_step(h, x_t, dt_t, A, B_t, C_t)
        return h, y

    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(Bm, 1, 0).astype(jnp.float32),
          jnp.moveaxis(Cm, 1, 0).astype(jnp.float32))
    h, ys = jax.lax.scan(step, h, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h


def ssd_step(h, x_t, dt_t, A, B_t, C_t):
    """h [B,H,N,P]; x_t [B,H,P]; dt_t [B,H]; B_t/C_t [B,N]."""
    da = jnp.exp(dt_t.astype(jnp.float32) * A.astype(jnp.float32))  # [B,H]
    upd = jnp.einsum("bh,bn,bhp->bhnp", dt_t.astype(jnp.float32),
                     B_t.astype(jnp.float32), x_t.astype(jnp.float32))
    h = da[:, :, None, None] * h.astype(jnp.float32) + upd
    y = jnp.einsum("bn,bhnp->bhp", C_t.astype(jnp.float32), h)
    return h, y


# ---------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------


def _project_streams(p, u, cfg, state):
    """Shared projection + conv path for train/prefill/decode."""
    cd = cfg.cdtype
    z = nn.linear_apply(p["in_z"], u, cd)
    x = nn.linear_apply(p["in_x"], u, cd)
    Bm = nn.linear_apply(p["in_B"], u, cd)
    Cm = nn.linear_apply(p["in_C"], u, cd)
    dt = nn.linear_apply(p["in_dt"], u, cd)
    tails = state["conv"] if state is not None else {"x": None, "B": None,
                                                     "C": None}
    x, tx = causal_conv(x, p["conv_x"].astype(x.dtype),
                        p["conv_x_b"].astype(x.dtype), tail=tails["x"])
    Bm, tb = causal_conv(Bm, p["conv_B"].astype(x.dtype),
                         p["conv_B_b"].astype(x.dtype), tail=tails["B"])
    Cm, tc = causal_conv(Cm, p["conv_C"].astype(x.dtype),
                         p["conv_C_b"].astype(x.dtype), tail=tails["C"])
    new_tails = {"x": tx, "B": tb, "C": tc}
    return z, x, Bm, Cm, dt, new_tails


def mamba_block_apply(p, u, cfg: ModelConfig, *, state=None,
                      return_state: bool = False, recurrent_oracle=False):
    """Full-sequence Mamba2 block. u [B,T,d].

    state (optional): {"conv": {x,B,C tails}, "ssm": [B,H,N,P]}.
    Returns y or (y, new_state).
    """
    d_in, H, N, conv_dim = ssm_dims(cfg)
    P_ = cfg.ssm_head_dim
    B_, T, _ = u.shape
    x_res = u
    u = nn.rmsnorm_apply(p["ln"], u, cfg.norm_eps)
    z, x, Bm, Cm, dt, new_tails = _project_streams(p, u, cfg, state)
    x = x.reshape(B_, T, H, P_)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"])
    h0 = state["ssm"] if state is not None else None
    if recurrent_oracle:
        y, h = ssd_recurrent(x, dt, A, Bm, Cm, h0=h0)
    else:
        y, h = ssd_chunked(x, dt, A, Bm, Cm, cfg.ssm_chunk, h0=h0)
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * x
    y = y.reshape(B_, T, d_in)
    y = nn.rmsnorm_apply(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = x_res + nn.linear_apply(p["out_proj"], y, cfg.cdtype)
    if return_state:
        return out, {"conv": new_tails, "ssm": h}
    return out


def mamba_block_step(p, u, state, cfg: ModelConfig):
    """Single-token decode. u [B,1,d]. Returns (y [B,1,d], new_state)."""
    d_in, H, N, conv_dim = ssm_dims(cfg)
    P_ = cfg.ssm_head_dim
    B_ = u.shape[0]
    x_res = u
    u = nn.rmsnorm_apply(p["ln"], u, cfg.norm_eps)
    z, x, Bm, Cm, dt, new_tails = _project_streams(p, u, cfg, state)
    x = x[:, 0].reshape(B_, H, P_)
    dt_t = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"][None, :])
    A = -jnp.exp(p["A_log"])
    h, y = ssd_step(state["ssm"], x, dt_t, A, Bm[:, 0], Cm[:, 0])
    y = y + p["D"].astype(y.dtype)[None, :, None] * x
    y = y.reshape(B_, 1, d_in).astype(z.dtype)
    y = nn.rmsnorm_apply(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = x_res + nn.linear_apply(p["out_proj"], y, cfg.cdtype)
    return out, {"conv": new_tails, "ssm": h}


def init_ssm_state(cfg: ModelConfig, batch: int):
    d_in, H, N, conv_dim = ssm_dims(cfg)
    W = cfg.ssm_conv
    return {
        "conv": {
            "x": jnp.zeros((batch, W - 1, d_in), cfg.cdtype),
            "B": jnp.zeros((batch, W - 1, N), cfg.cdtype),
            "C": jnp.zeros((batch, W - 1, N), cfg.cdtype),
        },
        "ssm": jnp.zeros((batch, H, N, cfg.ssm_head_dim), jnp.float32),
    }


# ---------------------------------------------------------------------------
# Zamba2 hybrid model (groups of mamba blocks + one shared attention block)
# ---------------------------------------------------------------------------


def hybrid_init(key, cfg: ModelConfig):
    if cfg.attn_every <= 0 or cfg.n_layers % cfg.attn_every:
        raise ValueError("hybrid needs n_layers % attn_every == 0")
    G = cfg.n_layers // cfg.attn_every
    K = cfg.attn_every
    ks = jax.random.split(key, 5)
    dt = cfg.pdtype
    layer_keys = jax.random.split(ks[1], G * K)
    groups = [
        nn.stack_layers([mamba_block_init(layer_keys[g * K + i], cfg)
                         for i in range(K)])
        for g in range(G)
    ]
    p = {
        "embed": nn.embedding_init(ks[0], cfg.vocab, cfg.d_model, dtype=dt),
        "groups": nn.stack_layers(groups),  # leading axes [G, K, ...]
        "shared": tfm.block_init(ks[2], cfg, layer_idx=0),
        "ln_f": nn.rmsnorm_init(cfg.d_model, dtype=dt),
        "unembed": nn.linear_init(ks[3], cfg.d_model, cfg.vocab,
                                  axes=("embed", "vocab"), dtype=dt),
    }
    return p


def hybrid_forward(p, batch, cfg: ModelConfig, *, mesh=None):
    tokens = batch["tokens"]
    x = nn.embedding_apply(p["embed"], tokens, cfg.cdtype, mesh=mesh)
    T = x.shape[1]
    positions = jnp.arange(T)[None, :]
    shared = p["shared"]
    aspec = nn.batch_pspec(mesh, x.shape[0])
    x = nn.constrain(x, mesh, aspec)

    def group_body(x, group_params):
        def inner(x, bp):
            x = nn.constrain(x, mesh, aspec)
            return nn.constrain(mamba_block_apply(bp, x, cfg), mesh,
                                aspec), None

        x, _ = jax.lax.scan(inner, x, group_params)
        y, _ = tfm.block_apply(shared, x, cfg, causal=True,
                               positions=positions, mesh=mesh)
        return nn.constrain(y, mesh, aspec), None

    x, _ = jax.lax.scan(tfm.remat_wrap(group_body, cfg), x, p["groups"])
    x = nn.rmsnorm_apply(p["ln_f"], x, cfg.norm_eps)
    logits = nn.linear_apply(p["unembed"], x, jnp.float32)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        logits = nn.constrain(
            logits, mesh,
            P(aspec[0], None, "model" if "model" in mesh.axis_names else None))
    return logits, jnp.zeros((), jnp.float32)


def hybrid_loss(p, batch, cfg: ModelConfig, *, mesh=None):
    logits, aux = hybrid_forward(p, batch, cfg, mesh=mesh)
    return tfm._ce_from_logits(logits, batch, aux, cfg, mesh=mesh)


def hybrid_prefill(p, batch, cfg: ModelConfig, *, max_len: int, mesh=None):
    tokens = batch["tokens"]
    B_, S = tokens.shape
    x = nn.embedding_apply(p["embed"], tokens, cfg.cdtype, mesh=mesh)
    positions = jnp.arange(S)[None, :]
    shared = p["shared"]

    def group_body(x, group_params):
        def inner(x, bp):
            y, st = mamba_block_apply(bp, x, cfg, return_state=True)
            return y, st

        x, states = jax.lax.scan(inner, x, group_params)
        y, cache = tfm.block_prefill(shared, x, cfg, max_len=max_len,
                                     positions=positions, mesh=mesh)
        return y, (states, cache)

    x, (ssm_states, attn_caches) = jax.lax.scan(group_body, x, p["groups"])
    x = nn.rmsnorm_apply(p["ln_f"], x, cfg.norm_eps)
    logits = nn.linear_apply(p["unembed"], x[:, -1:, :], jnp.float32)[:, 0]
    cache = {"ssm": ssm_states, "attn": attn_caches}
    return cache, logits


def hybrid_decode_step(p, cache, tokens, cfg: ModelConfig, *, mesh=None):
    x = nn.embedding_apply(p["embed"], tokens[:, None], cfg.cdtype, mesh=mesh)
    shared = p["shared"]

    def group_body(x, inp):
        group_params, states, attn_cache = inp

        def inner(x, bp_st):
            bp, st = bp_st
            y, st2 = mamba_block_step(bp, x, st, cfg)
            return y, st2

        x, new_states = jax.lax.scan(inner, x, (group_params, states))
        y, new_attn, _ = tfm.block_decode(shared, x, attn_cache, cfg,
                                          mesh=mesh)
        return y, (new_states, new_attn)

    x, (new_ssm, new_attn) = jax.lax.scan(
        group_body, x, (p["groups"], cache["ssm"], cache["attn"]))
    x = nn.rmsnorm_apply(p["ln_f"], x, cfg.norm_eps)
    logits = nn.linear_apply(p["unembed"], x, jnp.float32)[:, 0]
    return {"ssm": new_ssm, "attn": new_attn}, logits
