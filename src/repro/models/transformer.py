"""Decoder-only and encoder-decoder transformer LMs.

Covers the dense archs (qwen1.5-0.5b, qwen3-8b, llama3.2-3b, nemotron-4-340b),
the MoE archs (via ``repro.models.moe`` FFN plug-in; moonlight-16b-a3b also
with latent attention, ``attention.mla_*``), whisper-small (enc-dec) and
internvl2-1b (vision-prefix LM).

Stack layout: an optional short list of "pre" blocks (e.g. deepseek's first
dense layer) followed by a homogeneous stack of blocks applied with
``jax.lax.scan`` over stacked params — HLO size and remat-checkpointed memory
stay O(one layer) regardless of depth.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from . import moe as moe_lib
from . import nn
from .config import ModelConfig


# ---------------------------------------------------------------------------
# Remat policies
# ---------------------------------------------------------------------------


def remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # "full": save only layer boundaries


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------


def block_init(key, cfg: ModelConfig, *, layer_idx: int = 0,
               cross: bool = False):
    ks = jax.random.split(key, 4)
    dt = cfg.pdtype
    p = {
        "ln_attn": nn.rmsnorm_init(cfg.d_model, dtype=dt),
        "attn": (attn.mla_init(ks[0], cfg) if cfg.is_mla
                 else attn.attention_init(ks[0], cfg)),
        "ln_mlp": nn.rmsnorm_init(cfg.d_model, dtype=dt),
    }
    use_moe = cfg.is_moe and layer_idx >= cfg.first_dense_layers
    if use_moe:
        p["moe"] = moe_lib.moe_init(ks[1], cfg)
    else:
        ff = cfg.dense_ff or cfg.d_ff
        p["mlp"] = nn.mlp_init(ks[1], cfg.d_model, ff, gated=cfg.gated_mlp,
                               dtype=dt)
    if cross:
        p["ln_cross"] = nn.rmsnorm_init(cfg.d_model, dtype=dt)
        p["cross"] = attn.attention_init(ks[2], cfg, cross=True)
    return p


def _sp_on(cfg, mesh, x):
    return (cfg.seq_shard_activations and mesh is not None
            and "model" in mesh.axis_names
            and x.ndim == 3 and x.shape[1] % mesh.shape["model"] == 0)


def _gather_seq(x, cfg, mesh):
    """Megatron-SP: gather the seq-sharded residual before a block (bf16)."""
    if not _sp_on(cfg, mesh, x):
        return x
    return nn.constrain(x, mesh, nn.batch_pspec(mesh, x.shape[0]))


def _ffn(p, x, cfg: ModelConfig, mesh, valid=None):
    """The FFN sublayer: (x + out, aux loss, held experts' assignment
    counts [n_held] or, for a dense MLP, None).  ``valid`` [B,S] marks
    the real tokens, which alone a MoE layer routes."""
    sp = _sp_on(cfg, mesh, x)
    h = nn.rmsnorm_apply(p["ln_mlp"], _gather_seq(x, cfg, mesh), cfg.norm_eps)
    counts = None
    if "moe" in p:
        h, aux, counts = moe_lib.moe_apply(p["moe"], h, cfg, mesh=mesh,
                                           valid=valid)
        if sp:
            from jax.sharding import PartitionSpec as P

            h = nn.constrain(
                h, mesh, P(nn.batch_pspec(mesh, x.shape[0])[0], "model", None))
    else:
        h = nn.mlp_apply(p["mlp"], h, activation=cfg.activation,
                         compute_dtype=cfg.cdtype, mesh=mesh,
                         explicit_tp=cfg.explicit_tp, fsdp=cfg.fsdp_params,
                         seq_shard=sp)
        aux = jnp.zeros((), jnp.float32)
    return x + h, aux, counts


def block_apply(p, x, cfg: ModelConfig, *, causal=True, positions=None,
                enc_out=None, mesh=None):
    """Full-sequence block forward.  Returns (y, aux_loss)."""
    sp = _sp_on(cfg, mesh, x)
    h = nn.rmsnorm_apply(p["ln_attn"], _gather_seq(x, cfg, mesh),
                         cfg.norm_eps)
    if cfg.is_mla:
        h, _ = attn.mla_apply(p["attn"], h, cfg, positions=positions)
    else:
        h = attn.attention_apply(p["attn"], h, cfg, causal=causal,
                                 positions=positions,
                                 rope=cfg.positions == "rope", mesh=mesh,
                                 seq_shard=sp)
    x = x + h
    if "cross" in p and enc_out is not None:
        h = nn.rmsnorm_apply(p["ln_cross"], x, cfg.norm_eps)
        h = attn.attention_apply(p["cross"], h, cfg, causal=False,
                                 x_kv=enc_out, rope=False, mesh=mesh)
        x = x + h
    y, aux, _ = _ffn(p, x, cfg, mesh)
    return y, aux


def block_prefill(p, x, cfg: ModelConfig, *, max_len: int, positions=None,
                  enc_out=None, mesh=None):
    """Prefill forward; returns (y, cache dict with padded KV)."""
    B, S, _ = x.shape
    h = nn.rmsnorm_apply(p["ln_attn"], x, cfg.norm_eps)
    pad = ((0, 0), (0, max_len - S), (0, 0))
    if cfg.is_mla:
        h, (c, k_pe) = attn.mla_apply(p["attn"], h, cfg, positions=positions)
        cache = {"ckv": attn.pack_latent(jnp.pad(c, pad), jnp.pad(k_pe, pad))}
    else:
        h, (k, v) = attn.attention_prefill(p["attn"], h, cfg,
                                           positions=positions, mesh=mesh)
        cache = {"k": jnp.pad(k, pad + ((0, 0),)),
                 "v": jnp.pad(v, pad + ((0, 0),))}
    cache["len"] = jnp.full((B,), S, jnp.int32)
    x = x + h
    if "cross" in p and enc_out is not None:
        h = nn.rmsnorm_apply(p["ln_cross"], x, cfg.norm_eps)
        hq, (ck, cv) = _cross_prefill(p["cross"], h, enc_out, cfg)
        cache["cross_k"] = ck
        cache["cross_v"] = cv
        x = x + hq
    y, _, _ = _ffn(p, x, cfg, mesh)
    return y, cache


def block_decode(p, x, cache, cfg: ModelConfig, *, mesh=None, valid=None):
    """Single-token decode; cross-attn reads precomputed cross K/V.
    Returns (y, cache, held experts' counts or None)."""
    h = nn.rmsnorm_apply(p["ln_attn"], x, cfg.norm_eps)
    if cfg.is_mla:
        h, ckv, clen = attn.mla_decode(p["attn"], h, cache["ckv"],
                                       cache["len"], cfg)
        cache = dict(cache, ckv=ckv, len=clen)
    else:
        h, ck, cv, clen = attn.attention_decode(
            p["attn"], h, cache["k"], cache["v"], cache["len"], cfg)
        cache = dict(cache, k=ck, v=cv, len=clen)
    x = x + h
    if "cross" in p and "cross_k" in cache:
        h = nn.rmsnorm_apply(p["ln_cross"], x, cfg.norm_eps)
        B = x.shape[0]
        zero = jnp.zeros((B, 1), jnp.int32)
        q, _, _ = attn._project_qkv(p["cross"], h, h, cfg, zero, zero,
                                    rope=False)
        kv_len = jnp.full((B,), cache["cross_k"].shape[1], jnp.int32)
        o = attn.decode_attention(q, cache["cross_k"], cache["cross_v"], kv_len)
        o = o.reshape(B, 1, cfg.padded_heads * cfg.head_dim)
        x = x + nn.linear_apply(p["cross"]["o"], o, cfg.cdtype)
    y, _, counts = _ffn(p, x, cfg, mesh, valid)
    return y, cache, counts


def block_decode_paged(p, x, store, layer, block_tables, lens, write_phys,
                       write_off, cfg: ModelConfig, *, mesh=None,
                       valid=None):
    """Single-token decode of layer ``layer`` against the stacked paged
    store.

    ``store`` holds the block-paged k/v of every layer, shape [L,
    num_blocks, block_size, Hkv*D] (or, for latent attention, the
    packed latent rows ``ckv`` [L, num_blocks, block_size/2,
    2*(r+rope)]); this layer's token entry is written into it and
    attention reads the layer from it, so the store is returned whole
    (any other leaf, such as the template's "len", passes through
    untouched: lengths live host-side in the engine and arrive as
    ``lens``).  Only dense/moe stacks run paged, so there is no
    cross-attention branch.  Returns (y, store, held experts' counts or
    None)."""
    h = nn.rmsnorm_apply(p["ln_attn"], x, cfg.norm_eps)
    if cfg.is_mla:
        h, ckv = attn.mla_decode_paged(
            p["attn"], h, store["ckv"], layer, block_tables, lens,
            write_phys, write_off, cfg)
        store = dict(store, ckv=ckv)
    else:
        h, ck, cv = attn.attention_decode_paged(
            p["attn"], h, store["k"], store["v"], layer, block_tables, lens,
            write_phys, write_off, cfg)
        store = dict(store, k=ck, v=cv)
    x = x + h
    y, _, counts = _ffn(p, x, cfg, mesh, valid)
    return y, store, counts


def block_extend(p, x, cache, cfg: ModelConfig, *, mesh=None, valid=None):
    """Multi-token cache extension (chunked prefill): x [B,T,d] appended
    at cache positions len..len+T-1.  Cross-attn reads precomputed cross
    K/V, mirroring ``block_decode``.  Returns (y, cache, held experts'
    counts or None)."""
    h = nn.rmsnorm_apply(p["ln_attn"], x, cfg.norm_eps)
    if cfg.is_mla:
        h, ckv, clen = attn.mla_extend(p["attn"], h, cache["ckv"],
                                       cache["len"], cfg)
        cache = dict(cache, ckv=ckv, len=clen)
    else:
        h, ck, cv, clen = attn.attention_extend(
            p["attn"], h, cache["k"], cache["v"], cache["len"], cfg)
        cache = dict(cache, k=ck, v=cv, len=clen)
    x = x + h
    if "cross" in p and "cross_k" in cache:
        h = nn.rmsnorm_apply(p["ln_cross"], x, cfg.norm_eps)
        B, T, _ = x.shape
        zero = jnp.zeros((B, T), jnp.int32)
        q, _, _ = attn._project_qkv(p["cross"], h, h, cfg, zero, zero,
                                    rope=False)
        o = attn.full_attention(q, cache["cross_k"], cache["cross_v"],
                                causal=False)
        o = o.reshape(B, T, cfg.padded_heads * cfg.head_dim)
        x = x + nn.linear_apply(p["cross"]["o"], o, cfg.cdtype)
    y, _, counts = _ffn(p, x, cfg, mesh, valid)
    return y, cache, counts


def _cross_prefill(p, x, enc_out, cfg):
    B, S, _ = x.shape
    q, k, v = attn._project_qkv(
        p, x, enc_out, cfg,
        jnp.arange(S)[None, :], jnp.arange(enc_out.shape[1])[None, :],
        rope=False)
    out = attn.full_attention(q, k, v, causal=False)
    out = out.reshape(B, S, cfg.padded_heads * cfg.head_dim)
    return nn.linear_apply(p["o"], out, cfg.cdtype), (k, v)


# ---------------------------------------------------------------------------
# LM init
# ---------------------------------------------------------------------------


def lm_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    dt = cfg.pdtype
    p: dict[str, Any] = {
        "embed": nn.embedding_init(ks[0], cfg.vocab, cfg.d_model, dtype=dt),
        "ln_f": nn.rmsnorm_init(cfg.d_model, dtype=dt),
    }
    n_dec = cfg.dec_layers or cfg.n_layers
    n_pre = cfg.first_dense_layers if cfg.is_moe else 0
    layer_keys = jax.random.split(ks[1], n_dec)
    pre = {
        f"layer_{i}": block_init(layer_keys[i], cfg, layer_idx=i,
                                 cross=cfg.cross_attention)
        for i in range(n_pre)
    }
    blocks = [
        block_init(layer_keys[i], cfg, layer_idx=i, cross=cfg.cross_attention)
        for i in range(n_pre, n_dec)
    ]
    if pre:
        p["pre"] = pre
    p["blocks"] = nn.stack_layers(blocks)
    if not cfg.tie_embeddings:
        p["unembed"] = nn.linear_init(ks[2], cfg.d_model, cfg.vocab,
                                      axes=("embed", "vocab"), dtype=dt)
    if cfg.family == "encdec":
        enc_keys = jax.random.split(ks[3], cfg.enc_layers)
        enc_blocks = [
            block_init(enc_keys[i], cfg, layer_idx=i, cross=False)
            for i in range(cfg.enc_layers)
        ]
        p["enc_blocks"] = nn.stack_layers(enc_blocks)
        p["enc_ln_f"] = nn.rmsnorm_init(cfg.d_model, dtype=dt)
    if cfg.positions == "learned":
        p["pos_embed"] = {
            "table": nn.Px(
                nn.normal_init(ks[4], (cfg.max_seq, cfg.d_model), dt, 0.01),
                ("pos", "embed"),
            )
        }
    return p


def _pre_names(p):
    if "pre" not in p:
        return []
    return sorted(p["pre"], key=lambda s: int(s.split("_")[1]))


# ---------------------------------------------------------------------------
# Forward (training / full sequence)
# ---------------------------------------------------------------------------


def _embed_tokens(p, tokens, cfg, *, prefix_embeds=None, mesh=None):
    x = nn.embedding_apply(p["embed"], tokens, cfg.cdtype, mesh=mesh)
    if prefix_embeds is not None:  # vlm: prepend vision patch embeddings
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    if cfg.positions == "learned":
        x = x + p["pos_embed"]["table"].astype(x.dtype)[:S][None]
    elif cfg.positions == "sinusoidal":
        x = x + nn.sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]
    return x, positions


def _residual_spec(cfg, mesh, batch, seq):
    """Residual-stream sharding: batch over DP; + Megatron-SP over model
    on the sequence dim when ``seq_shard_activations`` (shrinks remat-saved
    activations by the TP degree; the gather back is bf16)."""
    from jax.sharding import PartitionSpec as P

    bspec = nn.batch_pspec(mesh, batch)
    if (cfg.seq_shard_activations and mesh is not None
            and "model" in mesh.axis_names
            and seq % mesh.shape["model"] == 0):
        return P(bspec[0], "model", None)
    return bspec


def _run_blocks(p, x, cfg: ModelConfig, *, positions=None, enc_out=None,
                mesh=None):
    body = functools.partial(block_apply, cfg=cfg, causal=True,
                             positions=positions, enc_out=enc_out, mesh=mesh)
    aspec = _residual_spec(cfg, mesh, x.shape[0], x.shape[1])
    aux = jnp.zeros((), jnp.float32)
    for name in _pre_names(p):
        fn = remat_wrap(lambda q, v: body(q, v), cfg)
        x, a = fn(p["pre"][name], nn.constrain(x, mesh, aspec))
        aux = aux + a

    def scan_body(carry, layer_params):
        x, aux = carry
        x = nn.constrain(x, mesh, aspec)
        y, a = body(layer_params, x)
        return (nn.constrain(y, mesh, aspec), aux + a), None

    scan_fn = remat_wrap(scan_body, cfg)
    (x, aux), _ = jax.lax.scan(scan_fn, (x, aux), p["blocks"])
    return x, aux


def _logits(p, x, cfg: ModelConfig):
    x = nn.rmsnorm_apply(p["ln_f"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = nn.embedding_attend(p["embed"], x)
    else:
        logits = nn.linear_apply(p["unembed"], x, jnp.float32)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


def encode(p, frame_embeds, cfg: ModelConfig, *, mesh=None):
    """Encoder stack over stubbed modality embeddings (whisper)."""
    x = frame_embeds.astype(cfg.cdtype)
    S = x.shape[1]
    x = x + nn.sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]
    aspec = nn.batch_pspec(mesh, x.shape[0])

    def scan_body(x, layer_params):
        x = nn.constrain(x, mesh, aspec)
        y, _ = block_apply(layer_params, x, cfg, causal=False, mesh=mesh)
        return nn.constrain(y, mesh, aspec), None

    x, _ = jax.lax.scan(remat_wrap(scan_body, cfg), x, p["enc_blocks"])
    return nn.rmsnorm_apply(p["enc_ln_f"], x, cfg.norm_eps)


def forward(p, batch, cfg: ModelConfig, *, mesh=None):
    tokens = batch["tokens"]
    enc_out = (encode(p, batch["frame_embeds"], cfg, mesh=mesh)
               if cfg.family == "encdec" else None)
    prefix = batch.get("patch_embeds") if cfg.family == "vlm" else None
    x, positions = _embed_tokens(p, tokens, cfg, prefix_embeds=prefix,
                                 mesh=mesh)
    x = nn.constrain(x, mesh, nn.batch_pspec(mesh, x.shape[0]))
    x, aux = _run_blocks(p, x, cfg, positions=positions, enc_out=enc_out,
                         mesh=mesh)
    if prefix is not None:  # only score text positions
        x = x[:, prefix.shape[1]:]
    logits = _logits(p, x, cfg)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        bspec = nn.batch_pspec(mesh, x.shape[0])
        logits = nn.constrain(
            logits, mesh,
            P(bspec[0], None, "model" if "model" in mesh.axis_names else None))
    return logits, aux


def _sharded_loglik(logits, targets, mesh, batch_size: int):
    """Per-token target log-likelihood with vocab sharded over "model".

    Runs inside shard_map so every vocab-shard computes its local max /
    sum-exp / target logit and combines with tiny [B,S] psums — no
    full-logits collectives, no one-hot materialization.
    """
    from jax.sharding import PartitionSpec as P

    bspec = nn.batch_pspec(mesh, batch_size, extra_dims=1)
    lspec = P(*bspec, "model")
    v_local = logits.shape[-1] // mesh.shape["model"]

    def local(lg, tg):
        j = jax.lax.axis_index("model")
        lg = lg.astype(jnp.float32)
        # stop_gradient BEFORE pmax: max-shift is gradient-invariant for
        # logsumexp, and pmax has no JVP rule (zero tangents bypass it)
        lmax = jax.lax.pmax(
            jax.lax.stop_gradient(jnp.max(lg, axis=-1)), "model")  # [B,S]
        sumexp = jnp.sum(jnp.exp(lg - lmax[..., None]), axis=-1)
        gsum = jax.lax.psum(sumexp, "model")
        local_t = tg - j * v_local
        in_range = (local_t >= 0) & (local_t < v_local)
        idx = jnp.clip(local_t, 0, v_local - 1)
        tl = jnp.take_along_axis(lg, idx[..., None], axis=-1)[..., 0]
        tl = jax.lax.psum(jnp.where(in_range, tl, 0.0), "model")
        return tl - lmax - jnp.log(gsum)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(lspec, P(*bspec)),
                         out_specs=P(*bspec))(logits, targets)


def _ce_from_logits(logits, batch, aux, cfg: ModelConfig, *, mesh=None):
    """Shared next-token CE loss used by every model family."""
    targets = batch["targets"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    if (mesh is not None and "model" in mesh.axis_names
            and logits.shape[-1] % mesh.shape["model"] == 0):
        ll = _sharded_loglik(logits, targets, mesh, logits.shape[0])
    else:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = -(ll * mask).sum() / denom
    total = ce + cfg.router_aux_weight * aux
    return total, {"ce": ce, "aux": aux, "tokens": mask.sum()}


def loss_fn(p, batch, cfg: ModelConfig, *, mesh=None):
    logits, aux = forward(p, batch, cfg, mesh=mesh)
    return _ce_from_logits(logits, batch, aux, cfg, mesh=mesh)


# ---------------------------------------------------------------------------
# Prefill / decode (serving)
# ---------------------------------------------------------------------------


def prefill(p, batch, cfg: ModelConfig, *, max_len: int, mesh=None,
            last_only: bool = True):
    """Prefill caches; returns (cache, logits).

    ``last_only=True`` -> logits [B, vocab] at the final position (dry-run /
    exact-length serving); ``False`` -> logits [B, S, vocab] so the engine can
    read the true last prompt position of right-padded bucketed prompts."""
    tokens = batch["tokens"]
    enc_out = (encode(p, batch["frame_embeds"], cfg, mesh=mesh)
               if cfg.family == "encdec" else None)
    prefix = batch.get("patch_embeds") if cfg.family == "vlm" else None
    x, positions = _embed_tokens(p, tokens, cfg, prefix_embeds=prefix,
                                 mesh=mesh)
    aspec = nn.batch_pspec(mesh, x.shape[0])
    x = nn.constrain(x, mesh, aspec)

    pre_cache = {}
    for name in _pre_names(p):
        x, c = block_prefill(p["pre"][name], x, cfg, max_len=max_len,
                             positions=positions, enc_out=enc_out, mesh=mesh)
        pre_cache[name] = c

    def scan_body(x, layer_params):
        x = nn.constrain(x, mesh, aspec)
        y, c = block_prefill(layer_params, x, cfg, max_len=max_len,
                             positions=positions, enc_out=enc_out, mesh=mesh)
        return nn.constrain(y, mesh, aspec), c

    x, scan_cache = jax.lax.scan(scan_body, x, p["blocks"])
    cache = {"scan": scan_cache}
    if pre_cache:
        cache["pre"] = pre_cache
    if last_only:
        logits = _logits(p, x[:, -1:, :], cfg)[:, 0]
    else:
        logits = _logits(p, x, cfg)
    return cache, logits


def extend_step(p, cache, tokens, cfg: ModelConfig, *, mesh=None,
                valid=None):
    """Chunked cache extension; tokens [B, T] -> (cache, logits [B, T, vocab]).

    With ``valid`` [B, T] (the real tokens; a MoE model's paged steps
    give it) padded tokens route to no expert and a third output is the
    held experts' assignment counts per MoE layer, [n_moe, n_held].

    The T-token generalization of ``decode_step``: the chunk is written
    into the cache at positions len..len+T-1 and logits come back for
    every chunk position (the engine reads the last *real* one).  Feeding
    a prompt through successive extend calls produces the same cache and
    final-position logits as one full prefill, which is what lets the
    paged engine interleave long-prompt prefill with decode steps without
    perturbing outputs."""
    B, T = tokens.shape
    x = nn.embedding_apply(p["embed"], tokens, cfg.cdtype, mesh=mesh)
    if cfg.positions == "learned":
        lens = cache["scan"]["len"]  # [L, B]
        pos = lens[0][:, None] + jnp.arange(T)[None, :]  # [B, T]
        tab = p["pos_embed"]["table"].astype(x.dtype)
        x = x + jnp.take(tab, pos, axis=0)

    new_pre = {}
    for name in _pre_names(p):
        x, c, _ = block_extend(p["pre"][name], x, cache["pre"][name], cfg,
                               mesh=mesh, valid=valid)
        new_pre[name] = c

    def scan_body(x, layer):
        layer_params, layer_cache = layer
        y, c, counts = block_extend(layer_params, x, layer_cache, cfg,
                                    mesh=mesh, valid=valid)
        return y, (c, counts)

    x, (new_scan, counts) = jax.lax.scan(scan_body, x,
                                         (p["blocks"], cache["scan"]))
    new_cache = {"scan": new_scan}
    if new_pre:
        new_cache["pre"] = new_pre
    logits = _logits(p, x, cfg)
    if valid is not None:
        return new_cache, logits, counts
    return new_cache, logits


def decode_step(p, cache, tokens, cfg: ModelConfig, *, mesh=None,
                valid=None):
    """One decode step; tokens [B] int32 -> (cache, logits [B, vocab]);
    ``valid`` [B] as for ``extend_step``, with the same third output."""
    x = nn.embedding_apply(p["embed"], tokens[:, None], cfg.cdtype, mesh=mesh)
    if cfg.positions == "learned":
        # current position = cache length of first scanned layer
        lens = cache["scan"]["len"]  # [L, B]
        pos = lens[0]  # [B]
        tab = p["pos_embed"]["table"].astype(x.dtype)
        x = x + jnp.take(tab, pos, axis=0)[:, None, :]

    vrow = None if valid is None else valid[:, None]
    new_pre = {}
    for name in _pre_names(p):
        x, c, _ = block_decode(p["pre"][name], x, cache["pre"][name], cfg,
                               mesh=mesh, valid=vrow)
        new_pre[name] = c

    def scan_body(x, layer):
        layer_params, layer_cache = layer
        y, c, counts = block_decode(layer_params, x, layer_cache, cfg,
                                    mesh=mesh, valid=vrow)
        return y, (c, counts)

    x, (new_scan, counts) = jax.lax.scan(scan_body, x,
                                         (p["blocks"], cache["scan"]))
    new_cache = {"scan": new_scan}
    if new_pre:
        new_cache["pre"] = new_pre
    logits = _logits(p, x, cfg)[:, 0]
    if valid is not None:
        return new_cache, logits, counts
    return new_cache, logits


def paged_decode_step(p, store, block_tables, lens, tokens, write_phys,
                      write_off, cfg: ModelConfig, *, mesh=None, valid=None):
    """One decode step directly on the block-paged physical store.

    The paged analogue of ``decode_step``: ``store`` is the engine's
    physical cache tree (k/v leaves [L, num_blocks, block_size, Hkv*D]),
    ``block_tables`` [B, max_blocks] maps each sequence's logical blocks
    to physical ones, ``lens`` [B] is each sequence's valid length before
    this token, and ``write_phys``/``write_off`` [B] name the single
    physical cell the new token's K/V is written into.  No contiguous
    [B, Smax] view is ever materialized — attention reads K/V through the
    block table (see ``attention_decode_paged``).

    The layer scan carries the stacked store and scans only the layer
    params and the layer index: each layer scatters its row into the
    carried stack and the kernel reads its layer from it, so the donated
    store is written in place, with no per-layer slice or update-slice
    and no second stacked store.  An unscanned ``pre`` layer is a stack
    of one.  Returns (store, logits [B, vocab]); ``valid`` [B] as for
    ``extend_step``, with the same third output."""
    x = nn.embedding_apply(p["embed"], tokens[:, None], cfg.cdtype, mesh=mesh)
    if cfg.positions == "learned":
        tab = p["pos_embed"]["table"].astype(x.dtype)
        x = x + jnp.take(tab, lens, axis=0)[:, None, :]
    step = functools.partial(
        block_decode_paged, block_tables=block_tables, lens=lens,
        write_phys=write_phys, write_off=write_off, cfg=cfg, mesh=mesh,
        valid=None if valid is None else valid[:, None])

    new_pre = {}
    for name in _pre_names(p):
        one = jax.tree.map(lambda a: a[None], store["pre"][name])
        x, one, _ = step(p["pre"][name], x, one, 0)
        new_pre[name] = jax.tree.map(lambda a: a[0], one)

    def scan_body(carry, layer):
        x, scan_store = carry
        layer_params, idx = layer
        x, scan_store, counts = step(layer_params, x, scan_store, idx)
        return (x, scan_store), counts

    n_scan = store["scan"]["len"].shape[0]
    (x, new_scan), counts = jax.lax.scan(
        scan_body, (x, store["scan"]),
        (p["blocks"], jnp.arange(n_scan, dtype=jnp.int32)))
    new_store = {"scan": new_scan}
    if new_pre:
        new_store["pre"] = new_pre
    logits = _logits(p, x, cfg)[:, 0]
    if valid is not None:
        return new_store, logits, counts
    return new_store, logits
