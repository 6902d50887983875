"""The DeepSeek-V3 block (``model_type: deepseek_v3``, as Moonlight-16B-A3B
publishes it): latent attention and a mixture of experts routed by
sigmoid scores with a correction bias.

Each layer: RMSNorm, latent attention (MLA), RMSNorm, then a SwiGLU MLP
in the first ``first_k_dense_replace`` layers and the expert layer in
the rest.  The model around it has an input embedding, a final RMSNorm
and an untied vocabulary projection.

Latent attention (``q_lora_rank`` null): queries from one projection,
``qk_nope_head_dim`` + ``qk_rope_head_dim`` per head; ``kv_a`` gives the
latent (``kv_lora_rank``) and one rotary key for every head; the latent
is normed (``kv_a_layernorm``) and expanded by ``kv_b`` into each head's
key part and value (``v_head_dim``); rotary embeddings on the rotary
parts (theta from the config, pairs half and half, the file's
``assumed``); causal softmax scaled by 1/sqrt(nope + rope).  The
reference is this published, expanded form.

The expert layer: sigmoid scores over all ``published.n_routed_experts``
experts, the top ``num_experts_per_tok`` chosen by score plus the
correction bias, weighted by the scores renormalised (``norm_topk_prob``
true; the program refuses false) and scaled (``routed_scaling_factor``);
of them only the ``n_routed_experts`` held here (from ``experts_first``)
contribute, each a SwiGLU of ``moe_intermediate_size``; then the
``n_shared_experts`` shared experts as one SwiGLU MLP.

What a block kind provides is listed in ``chipbench/spec.py::load_block``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import HI, rmsnorm, rope


def dims(config: dict) -> dict:
    """The sizes the yardstick computes with, from the configuration file
    alone.  ``n_experts`` is the router's width (the published count),
    ``n_held`` the experts held here."""
    published = config.get("published", {})
    return {
        "layers": config["num_hidden_layers"],
        "first_dense": config["first_k_dense_replace"],
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope": config["qk_nope_head_dim"],
        "qk_rope": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "dense_ff": config["intermediate_size"],
        "d_ff": config["moe_intermediate_size"],
        "n_experts": published.get("n_routed_experts",
                                   config["n_routed_experts"]),
        "n_held": config["n_routed_experts"],
        "experts_first": config.get("experts_first", 0),
        "top_k": config["num_experts_per_tok"],
        "n_shared": config["n_shared_experts"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "vocab": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
    }


# what the program does not follow, and is refused: (key, the value
# the program runs, why)
_FOLLOWED = (
    ("q_lora_rank", None, "queries through a low-rank projection"),
    ("n_group", 1, "grouped expert selection"),
    ("topk_group", 1, "grouped expert selection"),
    ("topk_method", "noaux_tc", "another top-k method"),
    ("scoring_func", "sigmoid", "another score function"),
    ("rope_scaling", None, "scaled rotary embeddings"),
    ("tie_word_embeddings", False, "tied embeddings; the benchmark's "
                                   "seeded weights and plain reference "
                                   "keep a separate output head"),
    ("attention_bias", False, "biased attention projections"),
    ("hidden_act", "silu", "another activation"),
    ("moe_layer_freq", 1, "dense layers between expert layers"),
    ("norm_topk_prob", True, "chosen weights left unnormalised"),
)


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the repo
    arch named by ``arch``, with every size set from the file.  Fails
    where the file asks for what the program does not run."""
    from repro.configs import get_config

    for key, runs, what in _FOLLOWED:
        if config.get(key, runs) != runs:
            raise ValueError(f"{config['name']}: {key} = {config[key]!r} "
                             f"asks for {what}, which the program does not "
                             f"run")
    dm = dims(config)
    if not 0 <= dm["experts_first"] <= dm["n_experts"] - dm["n_held"]:
        raise ValueError(f"{config['name']}: experts {dm['experts_first']}"
                         f" .. +{dm['n_held']} are not among the "
                         f"{dm['n_experts']} routed")
    dtype = config["torch_dtype"]
    cfg = get_config(
        config["arch"], n_layers=dm["layers"], d_model=dm["d_model"],
        n_heads=dm["heads"], n_kv_heads=dm["heads"],
        head_dim=dm["v_head_dim"], kv_lora_rank=dm["kv_lora_rank"],
        qk_nope_head_dim=dm["qk_nope"], qk_rope_head_dim=dm["qk_rope"],
        v_head_dim=dm["v_head_dim"], d_ff=dm["d_ff"],
        dense_ff=dm["dense_ff"], first_dense_layers=dm["first_dense"],
        n_experts=dm["n_experts"], experts_held=dm["n_held"],
        experts_first=dm["experts_first"], top_k=dm["top_k"],
        n_shared_experts=dm["n_shared"], router_score="sigmoid",
        router_bias=True, routed_scaling_factor=dm["routed_scaling_factor"],
        vocab=dm["vocab"], rope_theta=dm["rope_theta"],
        norm_eps=dm["norm_eps"], tie_embeddings=False,
        param_dtype=dtype, compute_dtype=dtype)
    if (cfg.family != "moe" or not cfg.is_mla or not cfg.gated_mlp
            or cfg.activation != "silu" or cfg.positions != "rope"):
        raise ValueError(f"{config['name']}: the program's {config['arch']} "
                         f"config is not a latent-attention gated-silu MoE "
                         f"decoder")
    return cfg


# Seeded weights (``chipbench/weights.py``): uniform with the standard
# deviation of a LeCun initialisation (1/sqrt(fan-in)), the embedding at
# 1, norm scales at 1 + 0.1 noise, and the router's correction bias at
# 0.05, where it moves the chosen experts of some tokens (sigmoid scores
# of these router inputs spread by about 0.1 around their top six).

def specs(dims: dict, layer) -> dict:
    """name -> (shape, kind, std) of the weights of layer ``layer``, or
    with ``layer`` None of the model's own."""
    d, v = dims["d_model"], dims["vocab"]
    if layer is None:
        return {"embed": ((v, d), "w", 1.0),
                "unembed": ((d, v), "w", 1 / math.sqrt(d)),
                "ln_f": ((d,), "scale", 0.1)}
    h, r = dims["heads"], dims["kv_lora_rank"]
    qk = dims["qk_nope"] + dims["qk_rope"]
    kvb = h * (dims["qk_nope"] + dims["v_head_dim"])
    hv = h * dims["v_head_dim"]
    out = {"ln_attn": ((d,), "scale", 0.1), "ln_mlp": ((d,), "scale", 0.1),
           "q": ((d, h * qk), "w", 1 / math.sqrt(d)),
           "kv_a": ((d, r + dims["qk_rope"]), "w", 1 / math.sqrt(d)),
           "kv_norm": ((r,), "scale", 0.1),
           "kv_b": ((r, kvb), "w", 1 / math.sqrt(r)),
           "o": ((hv, d), "w", 1 / math.sqrt(hv))}
    if layer < dims["first_dense"]:
        ff = dims["dense_ff"]
        return dict(out, up=((d, ff), "w", 1 / math.sqrt(d)),
                    gate=((d, ff), "w", 1 / math.sqrt(d)),
                    down=((ff, d), "w", 1 / math.sqrt(ff)))
    E, El, ff = dims["n_experts"], dims["n_held"], dims["d_ff"]
    sff = dims["n_shared"] * ff
    return dict(out, router=((d, E), "w", 1 / math.sqrt(d)),
                router_bias=((E,), "w", 0.05),
                expert_up=((El, d, ff), "w", 1 / math.sqrt(d)),
                expert_gate=((El, d, ff), "w", 1 / math.sqrt(d)),
                expert_down=((El, ff, d), "w", 1 / math.sqrt(ff)),
                shared_up=((d, sff), "w", 1 / math.sqrt(d)),
                shared_gate=((d, sff), "w", 1 / math.sqrt(d)),
                shared_down=((sff, d), "w", 1 / math.sqrt(sff)))


_ATTN = {"ln_attn/scale": "ln_attn", "ln_mlp/scale": "ln_mlp",
         "attn/q/w": "q", "attn/kv_a/w": "kv_a",
         "attn/kv_norm/scale": "kv_norm", "attn/kv_b/w": "kv_b",
         "attn/o/w": "o"}
_MLP = {"mlp/up/w": "up", "mlp/gate/w": "gate", "mlp/down/w": "down"}
_MOE = {"moe/router/w": "router", "moe/router/bias": "router_bias",
        "moe/up": "expert_up", "moe/gate": "expert_gate",
        "moe/down": "expert_down", "moe/shared/up/w": "shared_up",
        "moe/shared/gate/w": "shared_gate",
        "moe/shared/down/w": "shared_down"}


def program_layout(dims: dict) -> dict:
    first = dims["first_dense"]
    out = {"embed/table": ("embed", None), "ln_f/scale": ("ln_f", None),
           "unembed/w": ("unembed", None)}
    for i in range(first):
        out.update({f"pre/layer_{i}/{p}": (n, i)
                    for p, n in {**_ATTN, **_MLP}.items()})
    out.update({f"blocks/{p}": (n, first)
                for p, n in {**_ATTN, **_MOE}.items()})
    return out


def _attention(w, h, dims: dict, block: int):
    """Latent attention over the normed sequence h [S, d], expanded as
    published: its output projection, to add to the residual.  Queries
    run in blocks of ``block`` rows (S a multiple of it)."""
    S = h.shape[0]
    H, r = dims["heads"], dims["kv_lora_rank"]
    nope, rp, vd = dims["qk_nope"], dims["qk_rope"], dims["v_head_dim"]
    pos = jnp.arange(S)
    q = jnp.dot(h, w["q"], precision=HI).reshape(S, H, nope + rp)
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], pos, dims["rope_theta"])], -1)
    q = q / math.sqrt(nope + rp)
    kv = jnp.dot(h, w["kv_a"], precision=HI)
    c = rmsnorm(kv[:, :r], w["kv_norm"], dims["norm_eps"])
    k_pe = rope(kv[:, None, r:], pos, dims["rope_theta"])
    kvb = jnp.dot(c, w["kv_b"], precision=HI).reshape(S, H, nope + vd)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (S, H, rp))], -1)
    v = kvb[..., nope:]

    def attend(args):
        qb, start = args  # [block, H, nope + rope]
        s = jnp.einsum("qhd,shd->hqs", qb, k, precision=HI)
        causal = pos[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", p, v, precision=HI)

    nb = S // block
    o = jax.lax.map(attend, (q.reshape(nb, block, H, nope + rp),
                             jnp.arange(nb) * block))
    return jnp.dot(o.reshape(S, H * vd), w["o"], precision=HI)


def _swiglu(h, gate, up, down):
    a = jax.nn.silu(jnp.dot(h, gate, precision=HI))
    return jnp.dot(a * jnp.dot(h, up, precision=HI), down, precision=HI)


def _experts(w, h, dims: dict):
    """Routing over every expert; the held ones' part, then the shared
    experts'."""
    scores = jax.nn.sigmoid(jnp.dot(h, w["router"], precision=HI))
    _, top = jax.lax.top_k(scores + w["router_bias"], dims["top_k"])
    rows = jnp.arange(h.shape[0])[:, None]
    chosen = jnp.zeros_like(scores, bool).at[rows, top].set(True)
    weight = jnp.where(chosen, scores, 0.0)
    weight = weight / weight.sum(-1, keepdims=True)
    first = dims["experts_first"]
    held = weight[:, first:first + dims["n_held"]]
    held = held * dims["routed_scaling_factor"]
    a = jax.nn.silu(jnp.einsum("sd,edf->esf", h, w["expert_gate"],
                               precision=HI))
    a = a * jnp.einsum("sd,edf->esf", h, w["expert_up"], precision=HI)
    out = jnp.einsum("esf,efd->esd", a, w["expert_down"], precision=HI)
    y = jnp.einsum("se,esd->sd", held, out, precision=HI)
    return y + _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])


def layer(w, x, dims: dict, i, block: int):
    """One layer over one sequence x [S, d] (S a multiple of ``block``),
    positions 0..S-1; ``w`` names tell a dense layer from an expert one."""
    eps = dims["norm_eps"]
    x = x + _attention(w, rmsnorm(x, w["ln_attn"], eps), dims, block)
    h = rmsnorm(x, w["ln_mlp"], eps)
    if "router" not in w:
        return x + _swiglu(h, w["gate"], w["up"], w["down"])
    return x + _experts(w, h, dims)


# Counts (``metrics/mfu.py``, ``metrics/decode_attn_roofline.py``,
# ``metrics/expert_ffn_roofline.py``, the KV pool's size): of the
# multiply-adds the model needs (2 operations each), the projections and
# FFN of every layer, of the routed experts the share a token sends to
# the held ones on average (top_k * n_held / n_experts experts),
# attention over the positions a token really attends (its own included)
# at the model's per-head widths, and the vocabulary projection only
# where a token is sampled.  Norms, rotary embeddings, the router's
# scores and softmax are left out.  Bytes are of bfloat16 (2 bytes).

BYTES = 2  # bfloat16


def _attention_params(dims: dict) -> int:
    d, h, r = dims["d_model"], dims["heads"], dims["kv_lora_rank"]
    nope, rp, vd = dims["qk_nope"], dims["qk_rope"], dims["v_head_dim"]
    return (d * h * (nope + rp) + d * (r + rp) + r * h * (nope + vd)
            + h * vd * d)


def _expert_params(dims: dict) -> int:
    return 3 * dims["d_model"] * dims["d_ff"]


def flops_per_token(dims: dict) -> int:
    """Projections and FFN of every layer, for one token: the routed
    experts it sends to the held ones on average, not the rest."""
    d, L, first = dims["d_model"], dims["layers"], dims["first_dense"]
    routed = (_expert_params(dims) * dims["top_k"] * dims["n_held"]
              // dims["n_experts"])
    moe = (d * dims["n_experts"] + routed
           + _expert_params(dims) * dims["n_shared"])
    return 2 * (L * _attention_params(dims)
                + first * 3 * d * dims["dense_ff"] + (L - first) * moe)


def attention_flops(dims: dict, attended: int) -> int:
    """Scores over nope + rope and weighted values over v, summed over
    every layer, for one token attending ``attended`` positions."""
    per_head = dims["qk_nope"] + dims["qk_rope"] + dims["v_head_dim"]
    return 2 * dims["layers"] * attended * dims["heads"] * per_head


def logits_flops(dims: dict) -> int:
    return 2 * dims["d_model"] * dims["vocab"]


def kv_bytes_per_position(dims: dict) -> int:
    """The normed latent and the rotary key of one position, one layer."""
    return (dims["kv_lora_rank"] + dims["qk_rope"]) * BYTES


def expert_weight_bytes(dims: dict) -> int:
    """One of a MoE layer's three held-expert stacks (up, gate or down:
    ``n_held`` matrices of d_model x d_ff), which each grouped matmul of
    the expert layer reads whole (``metrics/expert_ffn_roofline.py``)."""
    return dims["n_held"] * dims["d_model"] * dims["d_ff"] * BYTES


def decode_attention_bytes(dims: dict, attended: int) -> int:
    """Least bytes the latent decode kernel moves for one sequence in one
    layer: the latent rows of every attended position, the query
    (r + rope a head) in and the weighted latent (r a head) out."""
    h, r = dims["heads"], dims["kv_lora_rank"]
    qo = h * (r + dims["qk_rope"]) * BYTES + h * r * BYTES
    return attended * kv_bytes_per_position(dims) + qo
