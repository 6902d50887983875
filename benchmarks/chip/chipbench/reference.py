"""The plain reference: the model in float32 jax.numpy.

The layers are the block kind's (``layer`` in ``blocks/<kind>.py``);
this module runs them over whole sequences, between the input embedding
and the final RMSNorm and untied vocabulary projection, and holds the
building blocks a kind's layer is made of.  Every matrix product runs at
``Precision.HIGHEST``, since a TPU otherwise multiplies float32 in
bfloat16.

It imports nothing of the program.  Its weights are the benchmark's own
seeded weights (``weights.layer``), upcast from bfloat16 one layer at a
time; attention runs in blocks of query rows, so an 8k-token sequence of
the 4096-wide configuration fits beside nothing else on one chip.

``quantize`` puts every weight matrix through a lower precision first:
the control that the comparison has to fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

HI = jax.lax.Precision.HIGHEST


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


def causal_attention(q, k, v, block: int):
    """Causal softmax attention over positions 0..S-1: q [S, H, D], scaled
    already; k and v [S, G, D], each of the G heads read by H/G query
    heads in turn.  Runs in blocks of ``block`` query rows (S a multiple
    of it).  Returns [S, H * D]."""
    S, h_, hd = q.shape
    kv = k.shape[1]
    g = h_ // kv
    pos = jnp.arange(S)

    def attend(args):
        qb, start = args  # [block, kv, g, hd]
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI)
        causal = pos[None, :] <= (start + jnp.arange(block))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    nb = S // block
    o = jax.lax.map(attend, (q.reshape(nb, block, kv, g, hd),
                             jnp.arange(nb) * block))
    return o.reshape(S, h_ * hd)


def quantize(w, mode: str):
    """Quantize-dequantize a weight matrix [..., in, out] per output
    column, each matrix of a stack (experts') on its own.  The rounding
    is explicit (``round``, ``reduce_precision``): a cast to a narrow
    type and back may be dropped by the compiler, which is allowed excess
    precision, and was on the chip."""
    if mode == "int8":
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.round(w / s).clip(-127, 127) * s
    if mode == "fp8":  # e4m3: 4 exponent and 3 mantissa bits, max 240
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 240.0
        return jax.lax.reduce_precision(w / s, exponent_bits=4,
                                        mantissa_bits=3) * s
    raise ValueError(f"unknown precision {mode!r}")


def _pow2(n: int, least: int) -> int:
    p = least
    while p < n:
        p *= 2
    return p


def padded_len(n: int) -> int:
    """Positions a sequence of ``n`` is run at: few distinct sizes, so
    that the layer program compiles a few times per checkout and is then
    found in the compile cache (powers of two from 1024, then multiples
    of 2048 past 8192)."""
    return _pow2(n, 1024) if n <= 8192 else -(-n // 2048) * 2048


class Reference:
    """Logits of the plain model at chosen positions of given sequences.
    ``kind`` is the configuration's block kind (``blocks/<kind>.py``)."""

    def __init__(self, kind, dims: dict, seed: int, *, quantize_mode=None,
                 block: int = 512):
        self.kind, self.dims = kind, dims
        self.key = weights.seed_key(seed)
        self._w = jax.jit(functools.partial(self._weights, mode=quantize_mode),
                          static_argnums=2)
        self._apply = jax.jit(functools.partial(kind.layer, dims=dims,
                                                block=block))
        self._head = jax.jit(functools.partial(_head, eps=dims["norm_eps"]))

    @staticmethod
    def _weights(key, i, specs, *, mode):
        w = {n: a.astype(jnp.float32)
             for n, a in weights.layer(key, i, specs).items()}
        if mode:
            w = {n: quantize(a, mode) if a.ndim >= 2 else a
                 for n, a in w.items()}
        return w

    def _own(self, name: str):
        """One of the model's own weights (``embed``, ``ln_f``,
        ``unembed``)."""
        spec = self.kind.specs(self.dims, None)[name]
        return self._w(self.key, None, weights.frozen({name: spec}))[name]

    def logits(self, seqs: list, rows: list) -> list:
        """``seqs``: token lists; ``rows[i]``: positions of ``seqs[i]``
        whose next-token logits are wanted.  Returns device arrays
        [R, vocab], R a power of two at or above ``len(rows[i])``: the
        rows past it are padding."""
        embed = self._own("embed")
        xs = []
        for s in seqs:
            ids = np.zeros(padded_len(len(s)), np.int32)
            ids[:len(s)] = s
            xs.append(embed[jnp.asarray(ids)])
        del embed
        for i in range(self.dims["layers"]):
            w = self._w(self.key, i,
                        weights.frozen(self.kind.specs(self.dims, i)))
            xs = [self._apply(w, x, i=i) for x in xs]
        ln_f, unembed = self._own("ln_f"), self._own("unembed")
        out = []
        for x, r in zip(xs, rows):
            idx = np.zeros(_pow2(len(r), 64), np.int32)
            idx[:len(r)] = r
            out.append(self._head(x, jnp.asarray(idx), ln_f, unembed))
        return out


def _head(x, rows, ln_f, unembed, *, eps):
    return jnp.dot(rmsnorm(x[rows], ln_f, eps), unembed, precision=HI)


def served_rows(prompt_len: int, n_out: int) -> list:
    """Positions whose logits choose the served tokens: the last prompt
    position, then each fed output but the last."""
    return list(range(prompt_len - 1, prompt_len - 1 + n_out))


@jax.jit
def _gaps(ref_logits, tokens):
    at = jnp.take_along_axis(ref_logits, tokens[:, None], axis=1)[:, 0]
    return jnp.max(ref_logits, axis=1) - at


def logit_gaps(ref_logits, tokens) -> np.ndarray:
    """For each of ``tokens`` (one per row, from the first), how far its
    reference logit lies below the reference's best (0 where it is the
    best).  ``ref_logits`` may have padding rows past ``len(tokens)``."""
    t = np.zeros(ref_logits.shape[0], np.int32)
    t[:len(tokens)] = tokens
    return np.asarray(_gaps(ref_logits, jnp.asarray(t)))[:len(tokens)]


@jax.jit
def _top(logits):
    return jnp.argmax(logits, axis=1).astype(jnp.int32)


def top_tokens(logits, n: int) -> np.ndarray:
    """The token each of the first ``n`` rows puts first."""
    return np.asarray(_top(logits))[:n]
