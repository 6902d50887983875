"""Plain float32 references for latent attention and the routed expert
layer, and a whole-model forward built from them: straightforward
``jax.numpy`` at ``Precision.HIGHEST``, with no kernel, cache, batching
or sorting, over the program's own parameters.  The tests compare the
served path with these.

Latent attention is the published, expanded form (DeepSeek-V2/V3 as
Hugging Face ``transformers`` writes it, q_lora_rank null): per-head keys
and values from the normed latent, rotary embeddings on the 64-wide
parts, scale 1/sqrt(qk_nope + qk_rope).  The one departure, shared with
the program: rotary dimensions pair half and half, not interleaved;
with seeded weights that is the same model up to a fixed permutation of
the rotary columns.  The routed layer scores all experts, picks the top
k by score plus correction bias, weights them by the unbiased scores
(renormalised, scaled), and adds what each expert in the held range
gives, one expert at a time, then the shared experts once.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .config import ModelConfig

HI = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _dot(a, b):
    return jnp.dot(a, b, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x [S, H, D]: rotate the pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla_reference(p, x, cfg: ModelConfig):
    """Causal latent attention over one sequence x [S, d] (the normed
    residual), positions 0..S-1: its output projection [S, d]."""
    p, x = _f32(p), jnp.asarray(x, jnp.float32)
    S = x.shape[0]
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope_d, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)
    pos = jnp.arange(S)
    q = _dot(x, p["q"]["w"]).reshape(S, H, nope + rope_d)
    kv = _dot(x, p["kv_a"]["w"])
    c = rmsnorm(kv[:, :r], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = rope(kv[:, None, r:], pos, cfg.rope_theta)  # [S, 1, rope]
    kvb = _dot(c, p["kv_b"]["w"]).reshape(S, H, nope + vd)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (S, H, rope_d))], -1)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos,
                                             cfg.rope_theta)], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI)
    s = s / math.sqrt(nope + rope_d)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kvb[..., nope:],
                   precision=HI)
    return _dot(o.reshape(S, H * vd), p["o"]["w"])


def _swiglu(h, p):
    a = jax.nn.silu(_dot(h, p["gate"]["w"])) * _dot(h, p["up"]["w"])
    return _dot(a, p["down"]["w"])


def route_reference(router, h, cfg: ModelConfig):
    """Weights [T, E] over all experts, zero where not chosen."""
    logits = _dot(h, router["w"])
    scores = (jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid"
              else jax.nn.softmax(logits, -1))
    choice = scores + router.get("bias", 0.0)
    _, top = jax.lax.top_k(choice, cfg.top_k)
    chosen = jnp.zeros_like(scores, bool).at[
        jnp.arange(h.shape[0])[:, None], top].set(True)
    w = jnp.where(chosen, scores, 0.0)
    return w / w.sum(-1, keepdims=True) * cfg.routed_scaling_factor


def moe_reference(p, h, cfg: ModelConfig):
    """The routed layer over tokens h [T, d] (normed): the held experts'
    part, ``[experts_first, experts_first + n_held)`` of the router's
    experts, and the shared experts'."""
    p, h = _f32(p), jnp.asarray(h, jnp.float32)
    w = route_reference(p["router"], h, cfg)
    y = jnp.zeros_like(h)
    for j in range(p["up"].shape[0]):
        e = cfg.experts_first + j
        a = jax.nn.silu(_dot(h, p["gate"][j])) * _dot(h, p["up"][j])
        y = y + w[:, e:e + 1] * _dot(a, p["down"][j])
    if "shared" in p:
        y = y + _swiglu(h, p["shared"])
    return y


def lm_reference(params, tokens, cfg: ModelConfig):
    """Logits [S, vocab] of one token sequence through a latent-attention
    MoE model (the first ``first_dense_layers`` with a dense MLP)."""
    if not (cfg.is_mla and cfg.is_moe):
        raise ValueError("lm_reference runs latent-attention MoE models")
    params = _f32(params)
    eps = cfg.norm_eps
    x = params["embed"]["table"][jnp.asarray(tokens)]
    layers = [params["pre"][f"layer_{i}"]
              for i in range(cfg.first_dense_layers)]
    n_scan = cfg.n_layers - cfg.first_dense_layers
    layers += [jax.tree.map(lambda a, i=i: a[i], params["blocks"])
               for i in range(n_scan)]
    for lp in layers:
        x = x + mla_reference(lp["attn"], rmsnorm(x, lp["ln_attn"]["scale"],
                                                  eps), cfg)
        h = rmsnorm(x, lp["ln_mlp"]["scale"], eps)
        x = x + (moe_reference(lp["moe"], h, cfg) if "moe" in lp
                 else _swiglu(h, lp["mlp"]))
    x = rmsnorm(x, params["ln_f"]["scale"], eps)
    return _dot(x, params["unembed"]["w"])
