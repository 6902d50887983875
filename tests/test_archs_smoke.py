"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, output shapes + finite values (assignment requirement f)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config, list_archs
from repro.models import get_model, make_batch, nn
from repro.training.optim import OptimizerConfig
from repro.training.train import TrainConfig, make_train_step, init_state

ARCHS = list_archs()


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    api = get_model(cfg)
    params, axes = nn.split(api.init(jax.random.PRNGKey(0), cfg))
    B, S = 2, 16
    batch = make_batch(cfg, B, S)
    logits, aux = api.forward(params, batch, cfg)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    cfg = get_smoke_config(arch)
    api = get_model(cfg)
    tcfg = TrainConfig(global_batch=4, seq_len=16, microbatches=2,
                       optimizer=OptimizerConfig(lr=1e-3, warmup_steps=1,
                                                 decay_steps=10))
    state, axes = init_state(jax.random.PRNGKey(0), api, cfg, tcfg.optimizer)
    step = make_train_step(api, cfg, tcfg, donate=False)
    batch = make_batch(cfg, 4, 16)
    state2, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually changed
    a0 = jax.tree.leaves(state["params"])[0] if False else None
    changed = jax.tree.map(
        lambda a, b: bool(jnp.any(a != b)), state["params"], state2["params"])
    assert any(jax.tree.leaves(changed))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_parity(arch):
    # the expert layer is dropless, so every path routes alike
    cfg = get_smoke_config(arch).scaled(attention_impl="full")
    api = get_model(cfg)
    params, _ = nn.split(api.init(jax.random.PRNGKey(0), cfg))
    B, S = 2, 12
    batch = make_batch(cfg, B, S)
    logits_full, _ = api.forward(params, batch, cfg)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :8]
    kw = {"max_len": 32} if cfg.family != "ssm" else {}
    cache, lg = api.prefill(params, pre, cfg, **kw)
    assert float(jnp.max(jnp.abs(lg - logits_full[:, 7]))) < 5e-3
    for t in range(8, 12):
        cache, lg = api.decode(params, cache, batch["tokens"][:, t], cfg)
        err = float(jnp.max(jnp.abs(lg - logits_full[:, t])))
        assert err < 5e-3, (arch, t, err)
