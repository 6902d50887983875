"""Compile the served path's kernels and steps for a TPU v5e, without one.

The TPU compiler is installed wherever JAX is; it compiles for a chip that
is described (``v5e:2x2``) and not attached.  Nothing here runs: a compile
that passes shows the chip's compiler accepts the block shapes, memory and
lowering, which interpret mode on the CPU cannot.  Shapes are the published
widths of qwen1.5-0.5b (16 heads of 64, block_size 16), with model depth
cut to 2 layers.

The topology is described inside a module fixture only, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs import get_config
from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention)
from repro.kernels.flash_attention.ops import flash_attention
from repro.launch import specs
from repro.models import get_model, nn
from repro.serving.engine import paged_decode_step, paged_extend_step

BLOCK_SIZE = 16
MAX_LEN = 128
NUM_BLOCKS = 4 * MAX_LEN // BLOCK_SIZE + 1  # the engine's default pool


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
])
def test_paged_decode_kernel_compiles(one_chip, B, Hq, Hkv, D):
    mb = MAX_LEN // BLOCK_SIZE
    compiled = _compile(
        functools.partial(paged_decode_attention, interpret=False),
        _sds(one_chip, (B, 1, Hq, D)),
        _sds(one_chip, (NUM_BLOCKS, BLOCK_SIZE, Hkv, D)),
        _sds(one_chip, (NUM_BLOCKS, BLOCK_SIZE, Hkv, D)),
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


@pytest.fixture(scope="module")
def qwen_two_layers(one_chip):
    """qwen1.5-0.5b at published widths, depth cut to 2 layers: the model
    api, config, and its parameter and paged-store shapes on the chip."""
    cfg = get_config("qwen1.5-0.5b").scaled(n_layers=2)
    api = get_model(cfg)
    params = jax.eval_shape(
        lambda k: nn.split(api.init(k, cfg))[0], jax.random.PRNGKey(0))
    store = specs.cache_template(cfg, NUM_BLOCKS, BLOCK_SIZE)
    return api, cfg, _on(one_chip, params), _on(one_chip, store)


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


def test_qwen_paged_extend_step_compiles(one_chip, qwen_two_layers,
                                         monkeypatch):
    monkeypatch.setattr(kernels, "pallas_interpret", lambda: False)
    api, cfg, params, store = qwen_two_layers
    T, mb = 64, MAX_LEN // BLOCK_SIZE
    chunk = _sds(one_chip, (1, T), jnp.int32)
    compiled = _compile_step(paged_extend_step, api, cfg, params, store,
                             _sds(one_chip, (1, mb), jnp.int32),
                             _sds(one_chip, (1,), jnp.int32),
                             chunk, chunk, chunk)
    assert compiled.memory_analysis() is not None
