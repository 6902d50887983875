"""Abstract input specs for the dry-run: ShapeDtypeStruct stand-ins.

Every model input (train batch, prefill batch, decode token + cache) is
described without allocating anything.  Cache templates are constructed
directly per family (validated structurally against ``jax.eval_shape`` of the
real prefill in tests).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import ShapeDtypeStruct as SDS

from repro.models import ModelApi
from repro.models.config import ModelConfig
from repro.models.mamba2 import ssm_dims


# The assigned LM shape grid: name -> (seq_len, global_batch, kind)
SHAPES = {
    "train_4k": (4096, 256, "train"),
    "prefill_32k": (32768, 32, "prefill"),
    "decode_32k": (32768, 128, "decode"),
    "long_500k": (524288, 1, "decode"),
}

# long_500k needs sub-quadratic sequence state; only hybrid/ssm run it.
LONG_CONTEXT_FAMILIES = ("hybrid", "ssm")

WHISPER_FRAMES = 1500  # fixed audio context (frontend stub length)


def cell_supported(cfg: ModelConfig, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and cfg.family not in LONG_CONTEXT_FAMILIES:
        return False, ("full-attention arch: 500k-context requires "
                       "sub-quadratic attention (skip noted in DESIGN.md)")
    return True, ""


# ---------------------------------------------------------------------------
# Abstract init (no allocation)
# ---------------------------------------------------------------------------


def abstract_params(api: ModelApi, cfg: ModelConfig):
    """(SDS params tree, logical axes tree) without allocating params."""
    from repro.models import nn

    captured = {}

    def f(key):
        px = api.init(key, cfg)
        vals, axes = nn.split(px)
        captured["axes"] = axes
        return vals

    vals = jax.eval_shape(f, jax.random.PRNGKey(0))
    return vals, captured["axes"]


def abstract_opt_state(params_sds, opt_cfg):
    from repro.training.optim import adamw_init

    return jax.eval_shape(functools.partial(adamw_init, cfg=opt_cfg),
                          params_sds)


# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------


def train_batch_specs(cfg: ModelConfig, batch: int, seq: int):
    tok = SDS((batch, seq), jnp.int32)
    out = {
        "tokens": tok,
        "targets": tok,
        "loss_mask": SDS((batch, seq), jnp.float32),
    }
    if cfg.family == "encdec":
        out["frame_embeds"] = SDS((batch, WHISPER_FRAMES, cfg.d_model),
                                  jnp.float32)
    if cfg.family == "vlm":
        out["patch_embeds"] = SDS((batch, cfg.vision_tokens, cfg.d_model),
                                  jnp.float32)
    return out


def prefill_batch_specs(cfg: ModelConfig, batch: int, seq: int):
    out = {"tokens": SDS((batch, seq), jnp.int32)}
    if cfg.family == "encdec":
        out["frame_embeds"] = SDS((batch, WHISPER_FRAMES, cfg.d_model),
                                  jnp.float32)
    if cfg.family == "vlm":
        out["patch_embeds"] = SDS((batch, cfg.vision_tokens, cfg.d_model),
                                  jnp.float32)
    return out


# ---------------------------------------------------------------------------
# Cache templates (must mirror the runtime prefill cache structure)
# ---------------------------------------------------------------------------


def cache_template(cfg: ModelConfig, batch: int, max_len: int):
    cd = cfg.cdtype
    if cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        K = cfg.attn_every
        d_in, H, N, _ = ssm_dims(cfg)
        W = cfg.ssm_conv
        return {
            "ssm": {
                "conv": {
                    "x": SDS((G, K, batch, W - 1, d_in), cd),
                    "B": SDS((G, K, batch, W - 1, N), cd),
                    "C": SDS((G, K, batch, W - 1, N), cd),
                },
                "ssm": SDS((G, K, batch, H, N, cfg.ssm_head_dim), jnp.float32),
            },
            "attn": {
                "k": SDS((G, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cd),
                "v": SDS((G, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cd),
                "len": SDS((G, batch), jnp.int32),
            },
        }
    if cfg.family == "ssm":
        H = cfg.d_model // cfg.rwkv_head_dim
        L = cfg.n_layers
        d = cfg.d_model
        return {
            "att": {
                "shift": SDS((L, batch, d), cd),
                "wkv": SDS((L, batch, H, cfg.rwkv_head_dim,
                            cfg.rwkv_head_dim), jnp.float32),
            },
            "ffn": {"shift": SDS((L, batch, d), cd)},
        }
    # transformer families
    n_dec = cfg.dec_layers or cfg.n_layers
    n_pre = cfg.first_dense_layers if cfg.is_moe else 0
    n_scan = n_dec - n_pre

    def layer_cache(lead=()):
        if cfg.is_mla:  # the latent rows, two positions to a row
            if max_len % 2:
                raise ValueError(f"a latent cache packs positions in "
                                 f"pairs; max_len {max_len} is odd")
            return {"ckv": SDS(lead + (batch, max_len // 2,
                                       2 * cfg.latent_width), cd),
                    "len": SDS(lead + (batch,), jnp.int32)}
        c = {
            "k": SDS(lead + (batch, max_len, cfg.n_kv_heads, cfg.head_dim), cd),
            "v": SDS(lead + (batch, max_len, cfg.n_kv_heads, cfg.head_dim), cd),
            "len": SDS(lead + (batch,), jnp.int32),
        }
        if cfg.family == "encdec":
            c["cross_k"] = SDS(lead + (batch, WHISPER_FRAMES, cfg.n_kv_heads,
                                       cfg.head_dim), cd)
            c["cross_v"] = SDS(lead + (batch, WHISPER_FRAMES, cfg.n_kv_heads,
                                       cfg.head_dim), cd)
        return c

    cache = {"scan": layer_cache((n_scan,))}
    if n_pre:
        cache["pre"] = {f"layer_{i}": layer_cache() for i in range(n_pre)}
    return cache


# ---------------------------------------------------------------------------
# Cache partition specs (path-based)
# ---------------------------------------------------------------------------


def cache_specs(cfg: ModelConfig, mesh, batch: int, max_len: int):
    """PartitionSpec tree for the cache: batch -> (pod,data) when divisible,
    KV sequence / head-like dims -> "model" (when divisible)."""
    import math

    from jax.sharding import PartitionSpec as P

    b_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b_size = math.prod(mesh.shape[a] for a in b_axes) if b_axes else 1
    batch_entry = b_axes if (b_axes and batch % b_size == 0) else None
    model_size = mesh.shape.get("model", 1)

    def model_if(divisible_dim: int):
        return "model" if divisible_dim % model_size == 0 else None

    def spec_for(path, leaf):
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        name = keys[-1]
        rank = len(leaf.shape)
        ent = [None] * rank
        if name in ("k", "v", "cross_k", "cross_v"):
            bdim = rank - 4
            ent[bdim] = batch_entry
            ent[bdim + 1] = model_if(leaf.shape[bdim + 1])  # kv sequence
        elif name == "ckv":
            ent[rank - 3] = batch_entry
            ent[rank - 2] = model_if(leaf.shape[rank - 2])  # kv sequence
        elif name == "len":
            ent[rank - 1] = batch_entry
        elif name == "wkv":
            ent[rank - 4] = batch_entry
            ent[rank - 3] = model_if(leaf.shape[rank - 3])  # rwkv heads
        elif name == "shift":
            ent[rank - 2] = batch_entry
        elif name == "ssm":
            ent[rank - 4] = batch_entry
            ent[rank - 3] = model_if(leaf.shape[rank - 3])  # ssm heads
        elif len(keys) >= 2 and keys[-2] == "conv":
            ent[rank - 3] = batch_entry
            if name == "x":
                ent[rank - 1] = model_if(leaf.shape[rank - 1])  # d_in
        return P(*ent)

    tmpl = cache_template(cfg, batch, max_len)
    return jax.tree_util.tree_map_with_path(spec_for, tmpl)
