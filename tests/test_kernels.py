"""Pallas kernel validation: shape/dtype sweeps, allclose vs ref oracles
(interpret mode on CPU; TPU is the target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention)
from repro.kernels.decode_attention.ref import (decode_ref, gather_kv,
                                                paged_decode_ref)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2.ops import ssd
from repro.kernels.mamba2.ref import ssd_ref
from repro.kernels.rwkv6.ops import wkv
from repro.kernels.rwkv6.ref import wkv_ref


@pytest.mark.parametrize("B,S,Hq,Hkv,D,bq,bk", [
    (2, 128, 4, 2, 32, 32, 32),
    (1, 256, 2, 2, 64, 64, 128),
    (2, 64, 8, 2, 16, 64, 32),
    (1, 128, 4, 1, 32, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, Hq, Hkv, D, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    kr = jnp.repeat(k, Hq // Hkv, 2)
    vr = jnp.repeat(v, Hq // Hkv, 2)
    qf = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * Hq, S, D)
    kf = jnp.transpose(kr, (0, 2, 1, 3)).reshape(B * Hq, S, D)
    vf = jnp.transpose(vr, (0, 2, 1, 3)).reshape(B * Hq, S, D)
    ref = jnp.transpose(attention_ref(qf, kf, vf, causal=True)
                        .reshape(B, Hq, S, D), (0, 2, 1, 3))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,Hq,Hkv,D,bk", [
    (2, 256, 4, 2, 32, 64),
    (3, 128, 8, 4, 16, 128),
    (1, 512, 2, 1, 64, 256),
])
def test_decode_attention(B, S, Hq, Hkv, D, bk):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    kc = jax.random.normal(ks[1], (B, S, Hkv, D))
    vc = jax.random.normal(ks[2], (B, S, Hkv, D))
    lens = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = decode_attention(q, kc, vc, lens, block_k=bk, interpret=True)
    ref = decode_ref(q[:, 0].reshape(B, Hkv, Hq // Hkv, D), kc, vc,
                     lens).reshape(B, 1, Hq, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _paged_setup(key, B, num_blocks, bs, mb, Hq, Hkv, D, *, permute=True):
    """Random paged stores + per-sequence block tables with DISTINCT,
    permuted physical blocks and ragged lengths (including lengths not a
    multiple of block_size)."""
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    k_store = jax.random.normal(ks[1], (num_blocks, bs, Hkv, D))
    v_store = jax.random.normal(ks[2], (num_blocks, bs, Hkv, D))
    # physical blocks 1..num_blocks-1 dealt without repeats (block 0 is
    # the null block), shuffled so tables are non-contiguous
    perm = np.arange(1, num_blocks)
    if permute:
        perm = np.asarray(jax.random.permutation(ks[3], perm))
    bt = np.zeros((B, mb), np.int32)
    flat = perm[:B * mb]
    bt[:, :] = flat.reshape(B, mb)
    lens = np.asarray(jax.random.randint(ks[4], (B,), 1, mb * bs + 1),
                      np.int32)
    # logical blocks past each length point at the null block, as the
    # engine guarantees
    for b in range(B):
        used = -(-int(lens[b]) // bs)
        bt[b, used:] = 0
    return q, k_store, v_store, jnp.asarray(bt), jnp.asarray(lens)


def _paged(q, k_store, v_store, bt, lens):
    """The paged kernel on a one-layer stack: ``[N, bs, Hkv, D]`` stores
    read as layer 0 of ``[1, N, bs, Hkv, D]``."""
    return paged_decode_attention(q, k_store[None], v_store[None], 0, bt,
                                  lens, interpret=True)


@pytest.mark.parametrize("B,num_blocks,bs,mb,Hq,Hkv,D", [
    (2, 17, 16, 4, 4, 2, 32),     # ragged lens, permuted tables
    (3, 32, 8, 6, 8, 4, 16),      # small blocks, more heads
    (1, 9, 32, 8, 2, 1, 64),      # single sequence, MHA-degenerate
])
def test_paged_decode_attention(B, num_blocks, bs, mb, Hq, Hkv, D):
    """Paged kernel vs the gather-then-dense oracle."""
    q, ks_, vs_, bt, lens = _paged_setup(
        jax.random.PRNGKey(5), B, num_blocks, bs, mb, Hq, Hkv, D)
    out = _paged(q, ks_, vs_, bt, lens)
    ref = paged_decode_ref(q[:, 0].reshape(B, Hkv, Hq // Hkv, D),
                           ks_, vs_, bt, lens).reshape(B, 1, Hq, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("L,layer", [(1, 0), (3, 0), (3, 1), (3, 2)])
@pytest.mark.parametrize("Hq,Hkv,D", [
    (16, 16, 64),   # MHA, qwen1.5-0.5b's heads
    (24, 8, 128),   # GQA (G=3) at D=128, qwen3-8b's head width
])
def test_paged_decode_reads_layer_of_stack(L, layer, Hq, Hkv, D):
    """The kernel reading layer ``layer`` of a stacked [L, N, bs, Hkv, D]
    store equals the oracle on that layer's store alone.  Every layer is
    drawn apart, so reading the wrong one fails."""
    B, num_blocks, bs, mb = 2, 9, 16, 4
    q, _, _, bt, lens = _paged_setup(
        jax.random.PRNGKey(10), B, num_blocks, bs, mb, Hq, Hkv, D)
    kk, kv = jax.random.split(jax.random.PRNGKey(11))
    shape = (L, num_blocks, bs, Hkv, D)
    # layer l is offset by 2l as well: no two layers share a value
    off = 2.0 * jnp.arange(L, dtype=jnp.float32)[:, None, None, None, None]
    k_stack = jax.random.normal(kk, shape) + off
    v_stack = jax.random.normal(kv, shape) - off
    out = paged_decode_attention(q, k_stack, v_stack, layer, bt, lens,
                                 interpret=True)

    def ref(l):
        return paged_decode_ref(q[:, 0].reshape(B, Hkv, Hq // Hkv, D),
                                k_stack[l], v_stack[l], bt,
                                lens).reshape(B, 1, Hq, D)

    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(layer)),
                               rtol=2e-5, atol=2e-5)
    for other in set(range(L)) - {layer}:
        assert not np.allclose(np.asarray(out), np.asarray(ref(other)),
                               rtol=2e-5, atol=2e-5)


def test_paged_matches_contiguous_kernel():
    """The paged kernel on a blocked store equals the contiguous kernel on
    the gathered caches — the two engine paths agree bit-for-bit up to
    float tolerance, whatever the block-table permutation."""
    B, num_blocks, bs, mb, Hq, Hkv, D = 2, 13, 16, 3, 4, 2, 32
    q, ks_, vs_, bt, lens = _paged_setup(
        jax.random.PRNGKey(6), B, num_blocks, bs, mb, Hq, Hkv, D)
    paged = _paged(q, ks_, vs_, bt, lens)
    kc, vc = gather_kv(ks_, bt), gather_kv(vs_, bt)
    contig = decode_attention(q, kc, vc, lens, block_k=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(contig),
                               rtol=2e-6, atol=2e-6)


def test_paged_decode_block_size_edges():
    """Lengths straddling block boundaries: 1, block_size-1, block_size,
    block_size+1, and full capacity all mask correctly."""
    num_blocks, bs, mb, Hq, Hkv, D = 23, 8, 4, 4, 2, 16
    edge_lens = [1, bs - 1, bs, bs + 1, mb * bs]
    B = len(edge_lens)
    q, ks_, vs_, _, _ = _paged_setup(
        jax.random.PRNGKey(7), B, num_blocks, bs, mb, Hq, Hkv, D)
    lens = jnp.asarray(edge_lens, jnp.int32)
    # deal fresh full tables (distinct shuffled physical blocks), then
    # null exactly the logical blocks past each edge length
    perm = np.asarray(jax.random.permutation(jax.random.PRNGKey(17),
                                             np.arange(1, num_blocks)))
    bt_np = perm[:B * mb].reshape(B, mb).astype(np.int32).copy()
    for b in range(B):
        bt_np[b, -(-edge_lens[b] // bs):] = 0
    bt = jnp.asarray(bt_np)
    out = _paged(q, ks_, vs_, bt, lens)
    ref = paged_decode_ref(q[:, 0].reshape(B, Hkv, Hq // Hkv, D),
                           ks_, vs_, bt, lens).reshape(B, 1, Hq, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_table_permutation_invariance():
    """Physically relocating blocks (and rewriting the tables to match)
    must not change the output: attention depends only on the logical
    sequence the table reconstructs."""
    B, num_blocks, bs, mb, Hq, Hkv, D = 2, 11, 8, 4, 4, 2, 16
    q, ks_, vs_, bt, lens = _paged_setup(
        jax.random.PRNGKey(8), B, num_blocks, bs, mb, Hq, Hkv, D,
        permute=False)
    out1 = _paged(q, ks_, vs_, bt, lens)
    # relocate: physical block p -> perm[p], stores shuffled to match
    perm = np.concatenate([[0], 1 + np.asarray(
        jax.random.permutation(jax.random.PRNGKey(9), num_blocks - 1))])
    inv = np.argsort(perm)
    ks2 = jnp.asarray(np.asarray(ks_)[inv])
    vs2 = jnp.asarray(np.asarray(vs_)[inv])
    bt2 = jnp.asarray(perm[np.asarray(bt)])
    out2 = _paged(q, ks2, vs2, bt2, lens)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=0, atol=0)


@pytest.mark.parametrize("B,T,H,hd,chunk", [
    (2, 64, 3, 16, 16),
    (1, 96, 2, 32, 32),
    (2, 128, 4, 8, 32),
])
def test_rwkv6_wkv(B, T, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, hd)) - 1.0)
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    out = wkv(r, k, v, lw, u, chunk=chunk, interpret=True)
    ref = wkv_ref(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("B,T,H,P,N,chunk", [
    (2, 64, 3, 8, 4, 16),
    (1, 128, 4, 16, 8, 32),
    (2, 96, 2, 32, 16, 32),
])
def test_mamba2_ssd(B, T, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, T, N))
    Cm = jax.random.normal(ks[4], (B, T, N))
    out = ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=1e-4)


def test_chunked_matches_recurrent_models():
    """The model-internal chunked paths match their recurrent oracles."""
    from repro.models.mamba2 import ssd_chunked, ssd_recurrent
    from repro.models.rwkv6 import wkv_chunked, wkv_recurrent

    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    B, T, H, hd = 2, 50, 2, 8
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, hd)))
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    y1, s1 = wkv_chunked(r, k, v, lw, u, 16)
    y2, s2 = wkv_recurrent(r, k, v, lw, u)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,num_blocks,bs,mb,H,r,rope", [
    (3, 17, 16, 4, 4, 32, 8),    # ragged lengths across block edges
    (2, 13, 8, 6, 2, 16, 16),    # small blocks, rope as wide as r / 2
])
def test_paged_decode_latent(B, num_blocks, bs, mb, H, r, rope):
    """The latent (MLA) kernel, in interpret mode, against its
    gather-then-dense oracle, reading one layer of a stacked store;
    lengths land mid-block, on a block's last and first cell, and at
    the table's end."""
    from repro.kernels.decode_attention.ops import paged_decode_latent
    from repro.kernels.decode_attention.ref import paged_decode_latent_ref

    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    store = jax.random.normal(ks[0], (2, num_blocks, bs // 2,
                                      2 * (r + rope)), jnp.float32)
    q_lat = jax.random.normal(ks[1], (B, H, r), jnp.float32)
    q_pe = jax.random.normal(ks[2], (B, H, rope), jnp.float32)
    bt = jax.random.permutation(ks[3], num_blocks - 1)[:B * mb]
    bt = (bt + 1).reshape(B, mb).astype(jnp.int32)
    lens = jnp.asarray([bs * mb, bs + 1, bs - 1][:B], jnp.int32)
    scale = 1.0 / np.sqrt(r + rope)
    out = paged_decode_latent(q_lat, q_pe, store, jnp.int32(1), bt, lens,
                              scale=scale, interpret=True)
    ref = paged_decode_latent_ref(q_lat, q_pe, store[1], bt, lens, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
