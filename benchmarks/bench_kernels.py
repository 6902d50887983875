"""Pallas kernel micro-benchmarks: compiled on a TPU, interpret mode on
the CPU (``repro.kernels.pallas_interpret``).

Each row names the backend it ran on.  A CPU row times the Pallas
interpreter (a correctness artifact, NOT device perf); the derived column
reports the kernel's work so the TPU roofline can be cross-checked: flops,
bytes, and the arithmetic intensity the BlockSpec tiling achieves.  The
rwkv6 and mamba2 kernels do not compile for a TPU yet, so on a TPU this
suite fails at them.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import pallas_interpret

from .common import Reporter


def _time(fn, *args, n=3):
    fn(*args).block_until_ready() if hasattr(fn(*args), "block_until_ready") \
        else None
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main(rep: Reporter) -> dict:
    key = jax.random.PRNGKey(0)
    interpret = pallas_interpret()
    where = f"({jax.default_backend()})"
    out = {}

    # flash attention
    from repro.kernels.flash_attention.ops import flash_attention

    B, S, H, D = 1, 256, 4, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    dt = _time(lambda a, b, c: flash_attention(
        a, b, c, causal=True, block_q=128, block_k=128,
        interpret=interpret), q, k, v)
    flops = 2 * B * H * (S * S // 2) * D * 2
    rep.add("kernel_flash_attention", dt * 1e6,
            f"S={S} D={D} causal flops={flops:.2e} {where}")
    out["flash"] = dt

    # decode attention
    from repro.kernels.decode_attention.ops import decode_attention

    B2, S2, Hq, Hkv = 4, 512, 8, 2
    q2 = jax.random.normal(ks[0], (B2, 1, Hq, D))
    kc = jax.random.normal(ks[1], (B2, S2, Hkv, D))
    vc = jax.random.normal(ks[2], (B2, S2, Hkv, D))
    lens = jnp.full((B2,), S2, jnp.int32)
    dt = _time(lambda a, b, c: decode_attention(
        a, b, c, lens, block_k=256, interpret=interpret), q2, kc, vc)
    bytes_moved = 2 * B2 * S2 * Hkv * D * 4
    rep.add("kernel_decode_attention", dt * 1e6,
            f"S={S2} G={Hq // Hkv} bytes={bytes_moved:.2e} AI~{Hq // Hkv} "
            f"{where}")
    out["decode"] = dt

    # rwkv6 wkv
    from repro.kernels.rwkv6.ops import wkv

    B3, T3, H3, hd = 1, 128, 4, 32
    ks2 = jax.random.split(key, 5)
    r = jax.random.normal(ks2[0], (B3, T3, H3, hd))
    k3 = jax.random.normal(ks2[1], (B3, T3, H3, hd))
    v3 = jax.random.normal(ks2[2], (B3, T3, H3, hd))
    lw = -jnp.exp(jax.random.normal(ks2[3], (B3, T3, H3, hd)) - 1.0)
    u = jax.random.normal(ks2[4], (H3, hd)) * 0.1
    dt = _time(lambda a, b, c: wkv(a, b, c, lw, u, chunk=32,
                                   interpret=interpret), r, k3, v3)
    rep.add("kernel_rwkv6_wkv", dt * 1e6, f"T={T3} hd={hd} chunk=32 {where}")
    out["wkv"] = dt

    # mamba2 ssd
    from repro.kernels.mamba2.ops import ssd

    B4, T4, H4, P4, N4 = 1, 128, 4, 16, 8
    x = jax.random.normal(ks2[0], (B4, T4, H4, P4))
    dts = jax.nn.softplus(jax.random.normal(ks2[1], (B4, T4, H4)))
    A = -jnp.exp(jax.random.normal(ks2[2], (H4,)))
    Bm = jax.random.normal(ks2[3], (B4, T4, N4))
    Cm = jax.random.normal(ks2[4], (B4, T4, N4))
    dt = _time(lambda a: ssd(a, dts, A, Bm, Cm, chunk=32,
                             interpret=interpret), x)
    rep.add("kernel_mamba2_ssd", dt * 1e6, f"T={T4} N={N4} chunk=32 {where}")
    out["ssd"] = dt
    return out


if __name__ == "__main__":
    main(Reporter())
