"""Compile the served path's kernels and steps for a TPU v5e, without one.

The TPU compiler is installed wherever JAX is; it compiles for a chip that
is described (``v5e:2x2``) and not attached.  Nothing here runs: a compile
that passes shows the chip's compiler accepts the block shapes, memory and
lowering, which interpret mode on the CPU cannot.  Shapes are the published
widths of qwen1.5-0.5b (16 heads of 64, block_size 16) and qwen3-8b (8 KV
heads of 128), with model depth cut to 2 layers.

The topology is described inside a module fixture only, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs import get_config
from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention)
from repro.kernels.flash_attention.ops import flash_attention
from repro.models import get_model, nn
from repro.serving.engine import paged_decode_step, paged_extend_step
from repro.serving.kvcache import paged_template

BLOCK_SIZE = 16
MAX_LEN = 128
NUM_BLOCKS = 4 * MAX_LEN // BLOCK_SIZE + 1  # the engine's default pool
# a pool whose 2-layer K stack (158 MB at either model's widths) exceeds
# the v5e's 128 MiB of VMEM, as a deployed pool does: the compiler stages
# a toy pool there whole.  No dimension of either model is 2411.
LARGE_POOL = 2411


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shape/dtype structs of ``tree`` placed on the described chip."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("B,Hq,Hkv,D", [
    (8, 16, 16, 64),   # qwen1.5-0.5b: MHA, 16 heads of 64
    (4, 24, 8, 128),   # grouped-query heads (G=3) at D=128
    (4, 16, 8, 192),   # heads that tile no 128 lanes
])
def test_paged_decode_kernel_compiles(one_chip, B, Hq, Hkv, D):
    mb = MAX_LEN // BLOCK_SIZE
    stack = _sds(one_chip, (2, NUM_BLOCKS, BLOCK_SIZE, Hkv * D))
    compiled = _compile(
        functools.partial(paged_decode_attention, interpret=False),
        _sds(one_chip, (B, 1, Hq, D)), stack, stack,
        _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (B, mb), jnp.int32), _sds(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_contiguous_decode_kernel_compiles(one_chip):
    B, S, H, D = 8, 1024, 16, 64
    compiled = _compile(
        functools.partial(decode_attention, block_k=256, interpret=False),
        _sds(one_chip, (B, 1, H, D)), _sds(one_chip, (B, S, H, D)),
        _sds(one_chip, (B, S, H, D)), _sds(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    q = _sds(one_chip, (1, 2048, 16, 64))
    compiled = _compile(
        functools.partial(flash_attention, causal=True, block_q=1024,
                          block_k=1024, interpret=False), q, q, q)
    assert "tpu_custom_call" in compiled.as_text()


def _two_layers(sharding, arch, num_blocks):
    """``arch`` at published widths, depth cut to 2 layers: the model
    api, config, and its parameter and paged-store shapes on the chip."""
    cfg = get_config(arch).scaled(n_layers=2)
    api = get_model(cfg)
    params = jax.eval_shape(
        lambda k: nn.split(api.init(k, cfg))[0], jax.random.PRNGKey(0))
    store = paged_template(cfg, num_blocks, BLOCK_SIZE)
    return api, cfg, _on(sharding, params), _on(sharding, store)


@pytest.fixture(scope="module")
def qwen_two_layers(one_chip):
    return _two_layers(one_chip, "qwen1.5-0.5b", NUM_BLOCKS)


def _compile_step(step, api, cfg, params, store, *args):
    # the engine jits each step this way, donating the paged store
    fn = jax.jit(functools.partial(step, api, cfg), donate_argnums=(1,))
    return fn.lower(params, store, *args).compile()


def test_qwen_paged_decode_step_compiles(one_chip, qwen_two_layers,
                                         monkeypatch):
    # what a TPU backend would choose; this process's backend is the CPU
    monkeypatch.setattr(kernels, "pallas_interpret", lambda: False)
    api, cfg, params, store = qwen_two_layers
    B, mb = 8, MAX_LEN // BLOCK_SIZE
    vec = _sds(one_chip, (B,), jnp.int32)
    compiled = _compile_step(paged_decode_step, api, cfg, params, store,
                             _sds(one_chip, (B, mb), jnp.int32),
                             vec, vec, vec, vec)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "qwen3-8b"])
def test_qwen_paged_extend_step_compiles(one_chip, monkeypatch, arch):
    monkeypatch.setattr(kernels, "pallas_interpret", lambda: False)
    api, cfg, params, store = _two_layers(one_chip, arch, NUM_BLOCKS)
    T, mb = 64, MAX_LEN // BLOCK_SIZE
    chunk = _sds(one_chip, (1, T), jnp.int32)
    compiled = _compile_step(paged_extend_step, api, cfg, params, store,
                             _sds(one_chip, (1, mb), jnp.int32),
                             _sds(one_chip, (1,), jnp.int32),
                             chunk, chunk, chunk)
    assert compiled.memory_analysis() is not None


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")


def _hlo_instructions(hlo):
    """{computation: [(name, result shape, opcode, line)]} of HLO text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m is None or cur is None:
            continue
        rest = line[m.end():]
        if rest.startswith("("):  # a tuple shape, which may nest
            depth = 0
            for end, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            shape, rest = rest[:end + 1], rest[end + 1:].lstrip()
        else:
            shape, _, rest = rest.partition(" ")
        op = re.match(r"[\w-]*", rest).group(0)
        cur.append((m.group(1), shape, op, line))
    return comps


def _store_copies(hlo, num_blocks):
    """The copies, slices and update-slices whose result shape holds the
    pool's block count, split into those inside a while loop's body (or
    a computation it calls) and those outside: [(opcode, name)] each."""
    comps = _hlo_instructions(hlo)
    stack = [re.search(r"body=%([\w.\-]+)", line).group(1)
             for ins in comps.values() for _, _, op, line in ins
             if op == "while"]
    in_loop = set()
    while stack:
        c = stack.pop()
        if c not in in_loop:
            in_loop.add(c)
            stack += [n for _, _, _, line in comps[c]
                      for n in re.findall(r"%([\w.\-]+)", line)
                      if n in comps]
    ops = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice",
           "slice", "slice-start")
    found = {True: [], False: []}
    for c, ins in comps.items():
        for name, shape, op, _ in ins:
            if op in ops and re.search(rf"[\[,]{num_blocks}[,\]]", shape):
                found[c in in_loop].append((op, name))
    return found[True], found[False]


@pytest.mark.parametrize("arch", ["qwen3-8b", "qwen1.5-0.5b"])
def test_paged_decode_writes_store_in_place(one_chip, monkeypatch, arch):
    """The decode step carries the stacked store through its layer loop
    and the kernel reads its layer from it: no layer of the store is
    sliced out, copied or updated back inside the loop, nothing copies
    or relays the store outside it, at head_dim 64 as at 128, and the
    step needs less scratch than one layer of the store.  The chip holds
    the lane-dense store unpadded: the donated store is aliased whole at
    its own size (the int32 length leaves round up to their tiles)."""
    monkeypatch.setattr(kernels, "pallas_interpret", lambda: False)
    api, cfg, params, store = _two_layers(one_chip, arch, LARGE_POOL)
    B, mb = 8, MAX_LEN // BLOCK_SIZE
    vec = _sds(one_chip, (B,), jnp.int32)
    compiled = _compile_step(paged_decode_step, api, cfg, params, store,
                             _sds(one_chip, (B, mb), jnp.int32),
                             vec, vec, vec, vec)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and " while(" in hlo
    assert _store_copies(hlo, LARGE_POOL) == ([], [])
    k = store["scan"]["k"]
    layer_bytes = k.size // k.shape[0] * k.dtype.itemsize
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < layer_bytes
    want = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(store))
    assert want <= ma.alias_size_in_bytes < want * 1.001


def test_moonlight_decode_step_holds_the_latent_store_in_place(one_chip,
                                                               monkeypatch):
    """moonlight-16b-a3b at the benchmark cell's widths and cut (9 layers,
    8 of 64 experts held), decode batch 8: the step compiles with the
    latent kernel and the grouped expert kernel, copies and relays no
    store-shaped array, and the chip holds the latent store in exactly
    blocks x 16 x 9 x 1152 bytes (two positions to an unpadded row)."""
    monkeypatch.setattr(kernels, "pallas_interpret", lambda: False)
    cfg = get_config("moonlight-16b-a3b").scaled(n_layers=9, experts_held=8)
    api = get_model(cfg)
    params = _on(one_chip, jax.eval_shape(
        lambda k: nn.split(api.init(k, cfg))[0], jax.random.PRNGKey(0)))
    store = _on(one_chip, paged_template(cfg, LARGE_POOL, BLOCK_SIZE))
    B, mb = 8, MAX_LEN // BLOCK_SIZE
    vec = _sds(one_chip, (B,), jnp.int32)
    compiled = _compile_step(paged_decode_step, api, cfg, params, store,
                             _sds(one_chip, (B, mb), jnp.int32),
                             vec, vec, vec, vec)
    hlo = compiled.as_text()
    assert "paged_decode_latent" in hlo and "%gmm" in hlo
    assert _store_copies(hlo, LARGE_POOL) == ([], [])
    latent = [x for x in jax.tree.leaves(store) if x.dtype == jnp.bfloat16]
    want = LARGE_POOL * BLOCK_SIZE * 9 * 1152
    assert sum(x.size * x.dtype.itemsize for x in latent) == want
    lens = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(store)
               if x.dtype == jnp.int32)
    # the donated store is aliased whole; the chip's layout pads nothing
    # (the int32 length leaves round up to their tiles, under 0.1%)
    alias = compiled.memory_analysis().alias_size_in_bytes
    assert want + lens <= alias < (want + lens) * 1.001


def test_moonlight_extend_attention_takes_the_softmax_max_by_row(one_chip):
    """moonlight-16b-a3b's chunked-prefill attention at the benchmark
    cell's sizes (a 512-token chunk over an 8192-position view): the
    softmax's max over each score row is a plain reduce.  With the shared
    rope key concatenated onto every head's key, the compiler took it as
    a reduce-window of 16383 positions over the padded row, which cost
    ~23.5 ms a layer on a v5e."""
    from repro.models import attention

    cfg = get_config("moonlight-16b-a3b")
    params = _on(one_chip, jax.eval_shape(
        lambda k: nn.split(attention.mla_init(k, cfg))[0],
        jax.random.PRNGKey(0)))
    width = 2 * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    compiled = _compile(
        lambda p, x, ckv, n: attention.mla_extend(p, x, ckv, n, cfg),
        params, _sds(one_chip, (1, 512, cfg.d_model)),
        _sds(one_chip, (1, 8192 // 2, width)),
        _sds(one_chip, (1,), jnp.int32))
    hlo = compiled.as_text()
    assert re.search(r"f32\[16,512\]\S* reduce\(", hlo)
    assert "reduce-window" not in hlo
