"""Model registry: a uniform API over all assigned architecture families."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from . import mamba2, rwkv6
from . import transformer as tfm
from .config import ModelConfig


@dataclasses.dataclass(frozen=True)
class ModelApi:
    """Uniform model surface used by training, serving, and the dry-run."""

    init: Callable  # (key, cfg) -> Px tree
    loss: Callable  # (params, batch, cfg, *, mesh=None) -> (loss, metrics)
    forward: Callable  # (params, batch, cfg, *, mesh=None) -> (logits, aux)
    prefill: Callable  # (params, batch, cfg, *, max_len, mesh=None) -> (cache, logits)
    decode: Callable  # (params, cache, tokens, cfg, *, mesh=None) -> (cache, logits)
    # chunked cache extension (paged serving); None for state-carrying
    # families whose recurrent state has no per-position KV to extend
    # (a MoE model given ``valid``, the real tokens, also returns the held
    # experts' assignment counts per MoE layer third; so do decode and
    # decode_paged)
    extend: Optional[Callable] = None  # (params, cache, tokens [B,T], cfg, *, mesh=None, valid=None) -> (cache, logits [B,T,V])
    # single-token decode directly on a block-paged physical store; None
    # for families without per-position KV (and unused by encdec/vlm,
    # whose cross/prefix handling the paged engine does not support)
    decode_paged: Optional[Callable] = None  # (params, store, block_tables, lens, tokens [B], write_phys, write_off, cfg, *, mesh=None, valid=None) -> (store, logits [B,V])


def get_model(cfg: ModelConfig) -> ModelApi:
    if cfg.family == "hybrid":
        return ModelApi(
            init=mamba2.hybrid_init,
            loss=mamba2.hybrid_loss,
            forward=mamba2.hybrid_forward,
            prefill=mamba2.hybrid_prefill,
            decode=mamba2.hybrid_decode_step,
        )
    if cfg.family == "ssm":
        return ModelApi(
            init=rwkv6.rwkv_init,
            loss=rwkv6.rwkv_loss,
            forward=rwkv6.rwkv_forward,
            prefill=rwkv6.rwkv_prefill,
            decode=rwkv6.rwkv_decode_step,
        )
    # dense / moe / encdec / vlm all run through the transformer stack
    return ModelApi(
        init=tfm.lm_init,
        loss=tfm.loss_fn,
        forward=tfm.forward,
        prefill=tfm.prefill,
        decode=tfm.decode_step,
        extend=tfm.extend_step,
        decode_paged=tfm.paged_decode_step,
    )


# ---------------------------------------------------------------------------
# Synthetic batches (smoke tests / examples); frontends are stubs per spec
# ---------------------------------------------------------------------------


def make_batch(cfg: ModelConfig, batch: int, seq: int, key=None,
               *, frontend_len: Optional[int] = None) -> dict[str, Any]:
    """Random token batch with the right extra inputs per family.

    [audio]/[vlm] archs get stubbed frontend embeddings (the assignment says
    the modality frontend is a STUB providing precomputed frame/patch
    embeddings).
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    tokens = jax.random.randint(k1, (batch, seq), 0, cfg.vocab, jnp.int32)
    out = {
        "tokens": tokens,
        "targets": jnp.roll(tokens, -1, axis=1),
        "loss_mask": jnp.ones((batch, seq), jnp.float32),
    }
    if cfg.family == "encdec":
        n = frontend_len if frontend_len is not None else seq
        out["frame_embeds"] = jax.random.normal(
            k2, (batch, n, cfg.d_model), jnp.float32) * 0.02
    if cfg.family == "vlm":
        n = frontend_len if frontend_len is not None else cfg.vision_tokens or 16
        out["patch_embeds"] = jax.random.normal(
            k3, (batch, n, cfg.d_model), jnp.float32) * 0.02
    return out
