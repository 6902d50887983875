"""Fine-grained mixture-of-experts FFN (DeepSeek-MoE / DeepSeek-V3 style).

Routing runs over all ``n_experts``: softmax or sigmoid scores in float32,
the top-k chosen by score (plus a per-expert correction bias where the
config has one, DeepSeek-V3's ``noaux_tc``), their weights the unbiased
scores, renormalised and scaled (``routed_scaling_factor``).

The layer is told which experts it holds, ``[experts_first,
experts_first + n_held)``, and is dropless: every (token, expert) pair
that falls on a held expert is computed, whatever else is in the batch,
and the others add nothing.  The assignments are sorted by expert and
the held experts run as one grouped matrix product over them: on a TPU
the Pallas grouped matmul (``megablox.gmm``, a ``gmm`` kernel in the
device trace), elsewhere ``jax.lax.ragged_dot``.  No capacity buffer,
nothing padded to a capacity.

Expert parallelism: under tensor parallelism the block input is
*replicated* over the ``model`` mesh axis, so each model-axis device
runs the held experts it owns over all locally-visible tokens and one
``psum`` over ``model`` adds their parts (the same collective cost as a
dense TP MLP).  One chip holding an expert-parallel shard runs the same
layer without the exchange.

Shared experts (DeepSeek: 2) are a dense MLP with
``ff = n_shared * d_ff``, added once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import kernels

from . import nn
from .config import ModelConfig

BATCH_AXES = ("pod", "data")


def moe_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    d, ff, E, El = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_held
    dt = cfg.pdtype
    # expert-internal dims get their own (replicated) logical axes: the
    # expert dim itself carries the "model" sharding (EP), so d/ff must not
    # also map to "model"
    router = {"w": nn.Px(nn.lecun_init(ks[0], (d, E), jnp.float32, d),
                         ("embed", "router_experts"))}
    if cfg.router_bias:
        router["bias"] = nn.Px(jnp.zeros((E,), jnp.float32),
                               ("router_experts",))
    p = {
        "router": router,
        "up": nn.Px(nn.lecun_init(ks[1], (El, d, ff), dt, d),
                    ("experts", "expert_in", "expert_ff")),
        "down": nn.Px(nn.lecun_init(ks[2], (El, ff, d), dt, ff),
                      ("experts", "expert_ff", "expert_in")),
    }
    if cfg.gated_mlp:
        p["gate"] = nn.Px(nn.lecun_init(ks[3], (El, d, ff), dt, d),
                          ("experts", "expert_in", "expert_ff"))
    if cfg.n_shared_experts > 0:
        p["shared"] = nn.mlp_init(ks[4], d, cfg.n_shared_experts * ff,
                                  gated=cfg.gated_mlp, dtype=dt)
    return p


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def route(router, x_flat, cfg: ModelConfig):
    """Returns (weights [T,k], idx [T,k], aux_loss scalar); ``router`` is
    the router's params (``w`` [d, E], and ``bias`` [E] where the config
    has one)."""
    logits = x_flat.astype(jnp.float32) @ router["w"].astype(jnp.float32)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)  # [T, E]
    choice = scores + router["bias"] if "bias" in router else scores
    _, top_i = jax.lax.top_k(choice, cfg.top_k)
    top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    top_p = top_p * cfg.routed_scaling_factor
    # load-balancing aux (Switch-style): E * sum_e f_e * p_e
    E = cfg.n_experts
    T = x_flat.shape[0]
    f = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = f / (T * cfg.top_k)
    pbar = (scores / scores.sum(-1, keepdims=True)).mean(axis=0)
    aux = E * jnp.sum(f * pbar)
    return top_p, top_i, aux


# ---------------------------------------------------------------------------
# Held experts, dropless
# ---------------------------------------------------------------------------

GMM_ROWS = 128  # the grouped kernel's row tile


def _gmm_tiles(k: int, n: int) -> tuple:
    """Row, contraction and output tiles of the grouped kernel: the whole
    of k and n where one bf16 weight tile stays under 3 MiB, else halves."""
    tk, tn = k, n
    while tk * tn * 2 > 3 * 2**20 and tk % 256 == 0:
        tk //= 2
    while tk * tn * 2 > 3 * 2**20 and tn % 256 == 0:
        tn //= 2
    return GMM_ROWS, tk, tn


def grouped_matmul(x, w, sizes):
    """Rows of ``x`` [m, k], sorted by group, times their group's matrix
    of ``w`` [G, k, n]: group g holds the ``sizes[g]`` rows after those of
    the groups before it; rows past all groups come out as zeros or
    garbage, for the caller to mask.  Returns [m, n] in x's dtype."""
    if kernels.pallas_interpret():
        return jax.lax.ragged_dot(x, w, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(x, w, sizes, preferred_element_type=x.dtype,
               tiling=_gmm_tiles(w.shape[1], w.shape[2]))


def held_experts(x_flat, top_w, top_i, up, gate, down, *, first,
                 cfg: ModelConfig, valid=None):
    """The held experts' part of the layer, dropless.

    x_flat [T,d]; top_w/top_i [T,k] over all experts; up/gate [El,d,ff],
    down [El,ff,d], the experts ``first .. first + El - 1``; ``valid``
    [T] bool, where False a token routes to none of them (a padded row).
    Returns (y [T,d] in the compute dtype, counts [El] int32: the
    (token, expert) pairs each held expert computed)."""
    T, d = x_flat.shape
    k = top_i.shape[1]
    El = up.shape[0]
    cd = cfg.cdtype
    local = top_i.reshape(-1) - first  # [T*k]
    held = (local >= 0) & (local < El)
    if valid is not None:
        held = held & jnp.repeat(valid, k)
    group = jnp.where(held, local, El)  # not held: after every group
    order = jnp.argsort(group)
    counts = jnp.bincount(group, length=El + 1)[:El].astype(jnp.int32)
    rows = -(-T * k // GMM_ROWS) * GMM_ROWS
    tok = jnp.pad(order // k, (0, rows - T * k))
    mask = jnp.pad(held[order], (0, rows - T * k))[:, None]
    xs = x_flat.astype(cd)[tok]  # [rows, d], sorted by held expert
    act = nn.ACTIVATIONS[cfg.activation]
    h = grouped_matmul(xs, up.astype(cd), counts)
    if gate is not None:
        h = act(grouped_matmul(xs, gate.astype(cd), counts)) * h
    else:
        h = act(h)
    out = grouped_matmul(h, down.astype(cd), counts)  # [rows, d]
    w = jnp.pad(top_w.reshape(-1)[order], (0, rows - T * k))
    out = jnp.where(mask, out * w.astype(cd)[:, None], 0)
    y = jnp.zeros((T, d), cd).at[tok].add(out)
    return y, counts


# ---------------------------------------------------------------------------
# Public apply
# ---------------------------------------------------------------------------


def moe_apply(p, x, cfg: ModelConfig, *, mesh=None, valid=None):
    """x [B,S,d] -> (y [B,S,d], aux scalar, counts [n_held] int32).

    ``valid`` [B,S] bool marks the real tokens (padded ones route to no
    held expert); ``counts`` are the (token, expert) pairs each held
    expert computed."""
    with jax.named_scope("moe"):
        B, S, d = x.shape
        T = B * S
        x_flat = x.reshape(T, d)
        top_w, top_i, aux = route(p["router"], x_flat, cfg)
        vflat = None if valid is None else valid.reshape(T)
        gate = p.get("gate")
        n_model = (mesh.shape["model"] if mesh is not None
                   and "model" in mesh.axis_names else 1)
        use_ep = n_model > 1 and cfg.n_held % n_model == 0
        if use_ep:
            n_local = cfg.n_held // n_model
            batch_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)

            def local_fn(xf, tw, ti, up, gt, dn):
                j = jax.lax.axis_index("model")
                y, c = held_experts(
                    xf, tw, ti, up, gt if gate is not None else None, dn,
                    first=cfg.experts_first + j * n_local, cfg=cfg)
                if batch_axes:
                    c = jax.lax.psum(c, batch_axes)
                return jax.lax.psum(y, "model"), c

            tok = P(batch_axes if batch_axes else None, None)
            espec = P("model", None, None)
            gate_arg = gate if gate is not None else p["up"]  # unused
            y_flat, counts = jax.shard_map(
                local_fn, mesh=mesh,
                in_specs=(tok, tok, tok, espec, espec, espec),
                out_specs=(tok, P("model")),
            )(x_flat, top_w, top_i, p["up"], gate_arg, p["down"])
        else:
            y_flat, counts = held_experts(
                x_flat, top_w, top_i, p["up"], gate, p["down"],
                first=cfg.experts_first, cfg=cfg, valid=vflat)

        y = y_flat.reshape(B, S, d)
        if "shared" in p:
            y = y + nn.mlp_apply(p["shared"], x, activation=cfg.activation,
                                 compute_dtype=cfg.cdtype)
        return y.astype(x.dtype), aux, counts
