"""The dense decoder block: a Qwen-style layer, as Qwen2 and Qwen3
publish it in Hugging Face ``transformers``.

RMSNorm before attention and MLP, q/k/v projections (with bias where
``qkv_bias``), RMSNorm of each query and key head where ``qk_norm``,
rotary embeddings (theta from the config), causal grouped-query softmax
attention scaled by 1/sqrt(head_dim), and a SwiGLU MLP; every layer the
same.  The model around it has an input embedding, a final RMSNorm and
an untied vocabulary projection.

What a block kind provides is listed in ``chipbench/spec.py::load_block``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import HI, causal_attention, rmsnorm, rope

# published config key -> the program's ModelConfig field
_WIDTH_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


def dims(config: dict) -> dict:
    """The sizes the yardstick computes with (FLOPs, bytes, the plain
    reference, the weights), from the configuration file alone."""
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    return {
        "layers": config["num_hidden_layers"],
        "d_model": d,
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim", d // heads),
        "d_ff": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "qkv_bias": bool(config["qkv_bias"]),
        "qk_norm": bool(config["qk_norm"]),
    }


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the repo
    arch named by ``arch``, with every size set from the file.  Fails when
    the program's config would run something else than the file says."""
    from repro.configs import get_config

    if config["tie_word_embeddings"]:
        raise ValueError(f"{config['name']}: tied embeddings; the "
                         f"benchmark's seeded weights and plain reference "
                         f"keep a separate output head")
    dm = dims(config)
    over = {field: config[key] for key, field in _WIDTH_KEYS.items()}
    over.update(head_dim=dm["head_dim"], qkv_bias=dm["qkv_bias"],
                qk_norm=dm["qk_norm"], tie_embeddings=False)
    cfg = get_config(config["arch"], **over)
    if (cfg.family != "dense" or not cfg.gated_mlp
            or cfg.activation != "silu" or cfg.positions != "rope"
            or cfg.param_dtype != config["torch_dtype"]
            or cfg.compute_dtype != config["torch_dtype"]):
        raise ValueError(f"{config['name']}: the program's {config['arch']} "
                         f"config is not a bf16 gated-silu rope decoder")
    return cfg


# Seeded weights (``chipbench/weights.py``): uniform with the standard
# deviation of a LeCun initialisation (1/sqrt(fan-in)), the embedding at
# 1, biases at 0.1, and norm scales at 1 + 0.1 noise, so that every part
# of the block (qkv bias, qk-norm, the norm scales) changes the logits.

def specs(dims: dict, layer) -> dict:
    """name -> (shape, kind, std) of the weights of layer ``layer``, or
    with ``layer`` None of the model's own."""
    d, h, kv, hd, ff, v = (dims["d_model"], dims["heads"], dims["kv_heads"],
                           dims["head_dim"], dims["d_ff"], dims["vocab"])
    if layer is None:
        return {
            "embed": ((v, d), "w", 1.0),
            "unembed": ((d, v), "w", 1 / math.sqrt(d)),
            "ln_f": ((d,), "scale", 0.1),
        }
    out = {
        "ln_attn": ((d,), "scale", 0.1),
        "ln_mlp": ((d,), "scale", 0.1),
        "wq": ((d, h * hd), "w", 1 / math.sqrt(d)),
        "wk": ((d, kv * hd), "w", 1 / math.sqrt(d)),
        "wv": ((d, kv * hd), "w", 1 / math.sqrt(d)),
        "wo": ((h * hd, d), "w", 1 / math.sqrt(h * hd)),
        "w_up": ((d, ff), "w", 1 / math.sqrt(d)),
        "w_gate": ((d, ff), "w", 1 / math.sqrt(d)),
        "w_down": ((ff, d), "w", 1 / math.sqrt(ff)),
    }
    if dims["qkv_bias"]:
        out.update(bq=((h * hd,), "w", 0.1), bk=((kv * hd,), "w", 0.1),
                   bv=((kv * hd,), "w", 0.1))
    if dims["qk_norm"]:
        out.update(q_norm=((hd,), "scale", 0.1), k_norm=((hd,), "scale", 0.1))
    return out


# the program's parameter paths -> the weight each one holds; "blocks/..."
# carries a leading axis over every layer from 0
_LAYOUT = {
    "embed/table": ("embed", None),
    "ln_f/scale": ("ln_f", None),
    "unembed/w": ("unembed", None),
    "blocks/ln_attn/scale": ("ln_attn", 0),
    "blocks/ln_mlp/scale": ("ln_mlp", 0),
    "blocks/attn/q/w": ("wq", 0),
    "blocks/attn/q/b": ("bq", 0),
    "blocks/attn/k/w": ("wk", 0),
    "blocks/attn/k/b": ("bk", 0),
    "blocks/attn/v/w": ("wv", 0),
    "blocks/attn/v/b": ("bv", 0),
    "blocks/attn/o/w": ("wo", 0),
    "blocks/attn/q_norm/scale": ("q_norm", 0),
    "blocks/attn/k_norm/scale": ("k_norm", 0),
    "blocks/mlp/up/w": ("w_up", 0),
    "blocks/mlp/gate/w": ("w_gate", 0),
    "blocks/mlp/down/w": ("w_down", 0),
}


def program_layout(dims: dict) -> dict:
    return _LAYOUT


def attention(w, h, dims: dict, block: int):
    """The attention sublayer over the normed sequence h [S, d]: its
    output projection, to add to the residual."""
    S = h.shape[0]
    h_, kv, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    eps = dims["norm_eps"]
    pos = jnp.arange(S)
    q = jnp.dot(h, w["wq"], precision=HI)
    k = jnp.dot(h, w["wk"], precision=HI)
    v = jnp.dot(h, w["wv"], precision=HI)
    if dims["qkv_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k, v = (q.reshape(S, h_, hd), k.reshape(S, kv, hd),
               v.reshape(S, kv, hd))
    if dims["qk_norm"]:
        q = rmsnorm(q, w["q_norm"], eps)
        k = rmsnorm(k, w["k_norm"], eps)
    q = rope(q, pos, dims["rope_theta"]) / math.sqrt(hd)
    k = rope(k, pos, dims["rope_theta"])
    o = causal_attention(q, k, v, block)
    return jnp.dot(o, w["wo"], precision=HI)


def swiglu(h, gate, up, down):
    a = jax.nn.silu(jnp.dot(h, gate, precision=HI))
    a = a * jnp.dot(h, up, precision=HI)
    return jnp.dot(a, down, precision=HI)


def layer(w, x, dims: dict, i, block: int):
    """One decoder layer over one sequence x [S, d] (S a multiple of
    ``block``), positions 0..S-1."""
    eps = dims["norm_eps"]
    x = x + attention(w, rmsnorm(x, w["ln_attn"], eps), dims, block)
    h = rmsnorm(x, w["ln_mlp"], eps)
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


# Counts (``metrics/mfu.py``, ``metrics/decode_attn_roofline.py``, the KV
# pool's size): of the multiply-adds the model needs (2 operations each),
# the projections and MLP of every layer, attention over the positions a
# token really attends (its own included), and the vocabulary projection
# only where a token is sampled.  Norms, rotary embeddings, biases and
# softmax are left out: they are under 1% of the operations at these
# widths.  Bytes are of bfloat16 (2 bytes).

BYTES = 2  # bfloat16


def layer_params(dims: dict) -> int:
    """Weights of one layer's matrix multiplications."""
    d, h, kv, hd, ff = (dims["d_model"], dims["heads"], dims["kv_heads"],
                        dims["head_dim"], dims["d_ff"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def flops_per_token(dims: dict) -> int:
    """Projections and MLP of every layer, for one token."""
    return 2 * dims["layers"] * layer_params(dims)


def attention_flops(dims: dict, attended: int) -> int:
    """Scores and weighted values over ``attended`` positions, summed over
    every layer, for one token (q.k and p.v, 2 operations per
    multiply-add each)."""
    return 4 * dims["layers"] * attended * dims["heads"] * dims["head_dim"]


def logits_flops(dims: dict) -> int:
    return 2 * dims["d_model"] * dims["vocab"]


def kv_bytes_per_position(dims: dict) -> int:
    """K and V of one position, one layer."""
    return 2 * dims["kv_heads"] * dims["head_dim"] * BYTES


def decode_attention_bytes(dims: dict, attended: int) -> int:
    """Least bytes the decode attention kernel moves for one sequence in
    one layer: K and V of every attended position, the query in and the
    output out."""
    qo = 2 * dims["heads"] * dims["head_dim"] * BYTES
    return attended * kv_bytes_per_position(dims) + qo
