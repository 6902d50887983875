"""Seeded weights, made on the device in one jitted call.

Each weight is named by what it is (``wq``, ``w_down``, ...), and its
values are a function of the seed, that name and the layer alone.  The
program gets them in its own parameter layout (``program_params``); the
plain reference makes the same values one layer at a time (``layer``),
so it takes nothing that the program holds.

Values are uniform with the standard deviation of a LeCun initialisation
(1/sqrt(fan-in)), the embedding at 1, biases at 0.1, and norm scales at
1 + 0.1 noise, so that every part of the block (qkv bias, qk-norm, the
norm scales) changes the logits.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

# the program's parameter paths -> the weight each one holds; layered
# ones ("blocks/...") carry a leading layer axis in the program
PROGRAM_LAYOUT = {
    "embed/table": "embed",
    "ln_f/scale": "ln_f",
    "unembed/w": "unembed",
    "blocks/ln_attn/scale": "ln_attn",
    "blocks/ln_mlp/scale": "ln_mlp",
    "blocks/attn/q/w": "wq",
    "blocks/attn/q/b": "bq",
    "blocks/attn/k/w": "wk",
    "blocks/attn/k/b": "bk",
    "blocks/attn/v/w": "wv",
    "blocks/attn/v/b": "bv",
    "blocks/attn/o/w": "wo",
    "blocks/attn/q_norm/scale": "q_norm",
    "blocks/attn/k_norm/scale": "k_norm",
    "blocks/mlp/up/w": "w_up",
    "blocks/mlp/gate/w": "w_gate",
    "blocks/mlp/down/w": "w_down",
}


def specs(dims: dict) -> dict:
    """name -> (shape, kind, std) of every weight; layered weights are
    given per layer."""
    d, h, kv, hd, ff, v = (dims["d_model"], dims["heads"], dims["kv_heads"],
                           dims["head_dim"], dims["d_ff"], dims["vocab"])
    out = {
        "embed": ((v, d), "w", 1.0),
        "unembed": ((d, v), "w", 1 / math.sqrt(d)),
        "ln_f": ((d,), "scale", 0.1),
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


GLOBAL = ("embed", "unembed", "ln_f")


def seed_key(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    key = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(key, (seed // 2**32) % 2**32)


def make(key, name: str, layer, dims: dict):
    """One weight in bfloat16, from the key, its name and its layer."""
    shape, kind, std = specs(dims)[name]
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    a = std * math.sqrt(3.0)
    x = jax.random.uniform(k, shape, jnp.float32, -a, a)
    if kind == "scale":
        x = 1.0 + x
    return x.astype(jnp.bfloat16)


def layer(key, i, dims: dict) -> dict:
    """Every weight of layer ``i`` (jit this; ``i`` may be traced)."""
    return {n: make(key, n, i, dims) for n in specs(dims)
            if n not in GLOBAL}


def program_params(program_shapes, dims: dict, seed: int):
    """The program's parameter tree, in bfloat16 on the default device,
    made in one jitted call.  ``program_shapes`` is the tree of shapes the
    program's own initialiser gives (``jax.eval_shape``)."""
    names = specs(dims)
    used = set()

    def build(key):
        def leaf(path, sds):
            p = "/".join(str(getattr(k, "key", k)) for k in path)
            name = PROGRAM_LAYOUT.get(p)
            if name is None or name not in names:
                raise KeyError(f"program parameter {p!r} has no seeded "
                               f"weight for this configuration")
            used.add(name)
            if p.startswith("blocks/"):
                x = jax.vmap(lambda i: make(key, name, i, dims))(
                    jnp.arange(dims["layers"]))
            else:
                x = make(key, name, None, dims)
            if x.shape != sds.shape or x.dtype != sds.dtype:
                raise ValueError(f"{p}: the program holds {sds.shape} "
                                 f"{sds.dtype}, the weight is {x.shape} "
                                 f"{x.dtype}")
            return x

        tree = jax.tree_util.tree_map_with_path(leaf, program_shapes)
        if used != set(names):
            raise KeyError(f"the program holds no {sorted(set(names) - used)}"
                           f", which the configuration has")
        return tree

    return jax.jit(build)(seed_key(seed))
