"""A MoE-shaped block kind at toy widths: the harness's test that a new
kind of layer is files only (this module, a configuration and a cell).
It stands for no published model and is never benchmarked.

Its layers run through the program's ``moe`` family: the first
``first_k_dense_replace`` layers are dense (attention and a SwiGLU MLP),
the rest attention and a mixture of experts: a router, softmax over the
experts, the top ``num_experts_per_tok`` renormalised, each a SwiGLU
expert, beside ``n_shared_experts`` shared experts run as one SwiGLU
MLP.  Attention is causal multi-head with rotary embeddings, no bias and
no qk-norm.  ``model_config`` sets both capacity factors to
experts / experts-per-token, so that the program's capacity dispatch can
hold every token at every expert and drops none: the check is then
exact.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import HI, causal_attention, rmsnorm, rope

_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "dense_ff",
    "moe_intermediate_size": "d_ff",
    "n_routed_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_dense_layers",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


def dims(config: dict) -> dict:
    out = {field: config[key] for key, field in _KEYS.items()}
    out["layers"] = out.pop("n_layers")
    return out


def model_config(config: dict):
    from repro.configs import get_config

    cf = config["n_routed_experts"] / config["num_experts_per_tok"]
    dtype = config["torch_dtype"]
    cfg = get_config(config["arch"],
                     **{f: config[k] for k, f in _KEYS.items()},
                     qkv_bias=False, qk_norm=False, tie_embeddings=False,
                     capacity_factor=cf, decode_capacity_factor=cf,
                     param_dtype=dtype, compute_dtype=dtype)
    if (cfg.family != "moe" or not cfg.gated_mlp
            or cfg.activation != "silu" or cfg.positions != "rope"):
        raise ValueError(f"{config['name']}: the program's {config['arch']} "
                         f"config is not a gated-silu rope MoE decoder")
    return cfg


def specs(dims: dict, layer) -> dict:
    d, v = dims["d_model"], dims["vocab"]
    if layer is None:
        return {"embed": ((v, d), "w", 1.0),
                "unembed": ((d, v), "w", 1 / math.sqrt(d)),
                "ln_f": ((d,), "scale", 0.1)}
    hd = dims["n_heads"] * dims["head_dim"]
    kvd = dims["n_kv_heads"] * dims["head_dim"]
    out = {"ln_attn": ((d,), "scale", 0.1), "ln_mlp": ((d,), "scale", 0.1),
           "q": ((d, hd), "w", 1 / math.sqrt(d)),
           "k": ((d, kvd), "w", 1 / math.sqrt(d)),
           "v": ((d, kvd), "w", 1 / math.sqrt(d)),
           "o": ((hd, d), "w", 1 / math.sqrt(hd))}
    if layer < dims["first_dense_layers"]:
        ff = dims["dense_ff"]
        return dict(out, up=((d, ff), "w", 1 / math.sqrt(d)),
                    gate=((d, ff), "w", 1 / math.sqrt(d)),
                    down=((ff, d), "w", 1 / math.sqrt(ff)))
    E, ff = dims["n_experts"], dims["d_ff"]
    sff = dims["n_shared_experts"] * ff
    return dict(out, router=((d, E), "w", 1 / math.sqrt(d)),
                expert_up=((E, d, ff), "w", 1 / math.sqrt(d)),
                expert_gate=((E, d, ff), "w", 1 / math.sqrt(d)),
                expert_down=((E, ff, d), "w", 1 / math.sqrt(ff)),
                shared_up=((d, sff), "w", 1 / math.sqrt(d)),
                shared_gate=((d, sff), "w", 1 / math.sqrt(d)),
                shared_down=((sff, d), "w", 1 / math.sqrt(sff)))


_ATTN = {"ln_attn/scale": "ln_attn", "ln_mlp/scale": "ln_mlp",
         "attn/q/w": "q", "attn/k/w": "k", "attn/v/w": "v", "attn/o/w": "o"}
_MLP = {"mlp/up/w": "up", "mlp/gate/w": "gate", "mlp/down/w": "down"}
_MOE = {"moe/router/w": "router", "moe/up": "expert_up",
        "moe/gate": "expert_gate", "moe/down": "expert_down",
        "moe/shared/up/w": "shared_up", "moe/shared/gate/w": "shared_gate",
        "moe/shared/down/w": "shared_down"}


def program_layout(dims: dict) -> dict:
    first = dims["first_dense_layers"]
    out = {"embed/table": ("embed", None), "ln_f/scale": ("ln_f", None),
           "unembed/w": ("unembed", None)}
    for i in range(first):
        out.update({f"pre/layer_{i}/{p}": (n, i)
                    for p, n in {**_ATTN, **_MLP}.items()})
    out.update({f"blocks/{p}": (n, first)
                for p, n in {**_ATTN, **_MOE}.items()})
    return out


def _swiglu(h, gate, up, down):
    a = jax.nn.silu(jnp.dot(h, gate, precision=HI))
    return jnp.dot(a * jnp.dot(h, up, precision=HI), down, precision=HI)


def layer(w, x, dims: dict, i, block: int):
    S = x.shape[0]
    H, G, D = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    eps = dims["norm_eps"]
    pos = jnp.arange(S)
    h = rmsnorm(x, w["ln_attn"], eps)
    q = jnp.dot(h, w["q"], precision=HI).reshape(S, H, D)
    k = jnp.dot(h, w["k"], precision=HI).reshape(S, G, D)
    v = jnp.dot(h, w["v"], precision=HI).reshape(S, G, D)
    q = rope(q, pos, dims["rope_theta"]) / math.sqrt(D)
    k = rope(k, pos, dims["rope_theta"])
    x = x + jnp.dot(causal_attention(q, k, v, block), w["o"], precision=HI)
    h = rmsnorm(x, w["ln_mlp"], eps)
    if "router" not in w:
        return x + _swiglu(h, w["gate"], w["up"], w["down"])
    probs = jax.nn.softmax(jnp.dot(h, w["router"], precision=HI), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, dims["top_k"])
    top_p = top_p / top_p.sum(-1, keepdims=True)
    share = jnp.zeros_like(probs).at[pos[:, None], top_i].set(top_p)
    a = jax.nn.silu(jnp.einsum("sd,edf->esf", h, w["expert_gate"],
                               precision=HI))
    a = a * jnp.einsum("sd,edf->esf", h, w["expert_up"], precision=HI)
    out = jnp.einsum("esf,efd->esd", a, w["expert_down"], precision=HI)
    y = jnp.einsum("se,esd->sd", share, out, precision=HI)
    y = y + _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    return x + y


BYTES = 2  # bfloat16


def flops_per_token(dims: dict) -> int:
    """Projections and FFN of every layer for one token: the routed
    experts it uses, not the rest."""
    d, L, first = dims["d_model"], dims["layers"], dims["first_dense_layers"]
    attn = 2 * d * (dims["n_heads"] + dims["n_kv_heads"]) * dims["head_dim"]
    moe = d * dims["n_experts"] + 3 * d * dims["d_ff"] * (
        dims["top_k"] + dims["n_shared_experts"])
    return 2 * (L * attn + first * 3 * d * dims["dense_ff"]
                + (L - first) * moe)


def attention_flops(dims: dict, attended: int) -> int:
    return 4 * dims["layers"] * attended * dims["n_heads"] * dims["head_dim"]


def logits_flops(dims: dict) -> int:
    return 2 * dims["d_model"] * dims["vocab"]


def kv_bytes_per_position(dims: dict) -> int:
    return 2 * dims["n_kv_heads"] * dims["head_dim"] * BYTES


def decode_attention_bytes(dims: dict, attended: int) -> int:
    qo = 2 * dims["n_heads"] * dims["head_dim"] * BYTES
    return attended * kv_bytes_per_position(dims) + qo
