"""Seeded weights, made on the device in one jitted call.

Each weight is named by what it is, and its values are a function of the
seed, that name and the layer alone.  The block kind
(``blocks/<kind>.py``) gives each weight's shape, kind and standard
deviation (``specs``) and where the program holds it
(``program_layout``).  The program gets the weights in its own parameter
layout (``program_params``); the plain reference makes the same values
one layer at a time (``layer``), so it takes nothing that the program
holds.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    key = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(key, (seed // 2**32) % 2**32)


def make(key, name: str, layer, spec: tuple):
    """One weight in bfloat16, from the key, its name and its layer (None
    for the model's own); ``spec`` is its (shape, kind, std): uniform
    with that standard deviation, and 1 + that for a ``scale``."""
    shape, kind, std = spec
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    a = std * math.sqrt(3.0)
    x = jax.random.uniform(k, shape, jnp.float32, -a, a)
    if kind == "scale":
        x = 1.0 + x
    return x.astype(jnp.bfloat16)


def frozen(specs: dict) -> tuple:
    """``specs`` as a hashable argument (for ``jax.jit``'s static ones)."""
    return tuple(sorted(specs.items()))


def layer(key, i, specs: tuple) -> dict:
    """Every weight of layer ``i`` (jit this with ``specs``, from
    ``frozen``, static; ``i`` may be traced)."""
    return {n: make(key, n, i, s) for n, s in specs}


def program_params(program_shapes, block, dims: dict, seed: int):
    """The program's parameter tree on the default device, made in one
    jitted call.  ``program_shapes`` is the tree of shapes the program's
    own initialiser gives (``jax.eval_shape``); ``block`` is the block
    kind.  Every program leaf has to hold a seeded weight, and every
    weight of every layer has to be held once."""
    layout = block.program_layout(dims)
    layers = {i: block.specs(dims, i) for i in range(dims["layers"])}
    layers[None] = block.specs(dims, None)
    held = set()

    def build(key):
        def leaf(path, sds):
            p = "/".join(str(getattr(k, "key", k)) for k in path)
            name, first = layout.get(p, (None, None))
            spec = layers.get(first, {}).get(name)
            if spec is None:
                raise KeyError(f"program parameter {p!r} has no seeded "
                               f"weight for this configuration")
            if first is None or sds.ndim == len(spec[0]):
                covers = [first]
                x = make(key, name, first, spec)
            else:  # a leading axis over layers first, first + 1, ...
                covers = list(range(first, first + sds.shape[0]))
                if any(layers.get(i, {}).get(name) != spec for i in covers):
                    raise ValueError(f"{p}: layers {covers} do not all hold "
                                     f"{name} as {spec}")
                x = jax.vmap(lambda i: make(key, name, i, spec))(
                    jnp.arange(first, first + sds.shape[0]))
            for i in covers:
                if (name, i) in held:
                    raise ValueError(f"{p}: {name} of layer {i} is held "
                                     f"twice")
                held.add((name, i))
            # a float32 leaf (a MoE router, or a program run in float32)
            # gets the same bfloat16 values, exactly
            if sds.dtype == jnp.float32:
                x = x.astype(jnp.float32)
            if x.shape != sds.shape or x.dtype != sds.dtype:
                raise ValueError(f"{p}: the program holds {sds.shape} "
                                 f"{sds.dtype}, the weight is {x.shape} "
                                 f"{x.dtype}")
            return x

        tree = jax.tree_util.tree_map_with_path(leaf, program_shapes)
        want = {(n, i) for i, s in layers.items() for n in s}
        if held != want:
            missing = sorted(want - held, key=str)
            raise KeyError(f"the program holds no {missing}, which the "
                           f"configuration has")
        return tree

    return jax.jit(build)(seed_key(seed))
