"""Block kinds: the seeded weights and the plain reference of the dense
kind are pinned, the generic code refuses a program and a kind that do
not match, the control rounds a stack of expert matrices one expert at a
time, and a MoE-shaped kind added as files only (``fixtures/blocks/``,
``configs/``, ``cells/``) runs whole: correct, with its control and a
planted fault incorrect."""
import hashlib
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, spec, weights

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SEED = 2**31 + 12345


def _program_shapes(block, config):
    from repro.models import get_model, nn

    cfg = block.model_config(config)
    api = get_model(cfg)
    return jax.eval_shape(lambda k: nn.split(api.init(k, cfg))[0],
                          jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny():
    config = spec.load_config("tiny", FIXTURES)
    block = spec.load_block(config, FIXTURES)
    return config, block, block.dims(config)


def test_the_dense_kinds_weights_and_reference_are_pinned(tiny):
    config, block, dims = tiny
    params = weights.program_params(_program_shapes(block, config), block,
                                    dims, SEED)
    h = hashlib.sha256()
    for path, x in sorted(jax.tree_util.tree_leaves_with_path(params),
                          key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).view(np.uint16).tobytes())
    assert h.hexdigest() == ("7a5b56e269da66ee9e8eee1da1de6565"
                             "bdc2866c9800651d16ce012a0d8ac412")

    rng = np.random.default_rng(0)
    seqs = [list(rng.integers(0, 256, 100)), list(rng.integers(0, 256, 37))]
    rows = [list(range(90, 100)), list(range(30, 37))]
    out = reference.Reference(block, dims, SEED).logits(seqs, rows)
    a = np.concatenate([np.asarray(o)[:len(r)] for o, r in zip(out, rows)])
    assert a.shape == (17, 256)
    np.testing.assert_allclose(
        [a.sum(), (a * a).sum(), *a[0, :4], *a[-1, -3:]],
        [-107.2193374633789, 4245.2333984375, 0.03160538151860237,
         -1.903565526008606, 0.31747525930404663, -1.2594232559204102,
         1.353619933128357, -1.0708661079406738, -0.779771625995636],
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("change,error", [
    ("leaf without a weight", "has no seeded weight"),
    ("weight without a leaf", "the program holds no"),
])
def test_every_leaf_holds_a_weight_and_every_weight_a_leaf(tiny, change,
                                                           error):
    config, block, dims = tiny
    layout, specs = dict(block.program_layout(dims)), block.specs
    if change == "leaf without a weight":
        del layout["blocks/attn/q/w"]
    else:
        def specs(d, layer):
            out = block.specs(d, layer)
            if layer is not None:
                out["extra"] = ((4,), "w", 0.1)
            return out
    kind = types.SimpleNamespace(program_layout=lambda d: layout,
                                 specs=specs)
    with pytest.raises(KeyError, match=error):
        weights.program_params(_program_shapes(block, config), kind, dims,
                               SEED)


@pytest.mark.parametrize("mode", ["fp8", "int8"])
def test_the_control_rounds_each_expert_per_column(mode):
    rng = np.random.default_rng(3)
    # experts at scales four orders apart, columns at scales two apart
    w = rng.standard_normal((3, 16, 8)).astype(np.float32)
    w *= np.array([1e-2, 1.0, 1e2])[:, None, None]
    w *= np.logspace(-1, 1, 8)[None, None, :]
    got = np.asarray(reference.quantize(jnp.asarray(w), mode))
    for e in range(3):
        alone = np.asarray(reference.quantize(jnp.asarray(w[e]), mode))
        np.testing.assert_array_equal(got[e], alone)
        for c in range(8):
            col = np.asarray(reference.quantize(
                jnp.asarray(w[e, :, c:c + 1]), mode))[:, 0]
            np.testing.assert_array_equal(got[e, :, c], col)
    assert not np.array_equal(got, w)  # it rounded
    rel = np.abs(got - w) / np.abs(w).max(axis=-2, keepdims=True)
    assert rel.max() < (1 / 16 if mode == "fp8" else 1 / 254)


def _run_moe(**kw):
    return harness.run_cell(spec.load_cell("toymoe.tinychat", FIXTURES),
                            SEED, 1.5, False, t_start=time.perf_counter(),
                            require_chip=False, **kw)


def test_a_moe_kind_added_as_files_runs_correct_and_its_control_does_not():
    r = _run_moe(controls=("fp8",))
    assert r["attempted"] > 10 and r["failed"] == 0
    assert r["correct"] is True
    assert r["control_correct"] == {"fp8": False}
    gap, ctl = r["check"]["mean_logit_gap"], \
        r["check"]["control_fp8_mean_logit_gap"]
    assert gap["value"] <= gap["limit"] < ctl["value"]


def test_a_planted_fault_makes_the_moe_kinds_run_incorrect():
    r = _run_moe(fault="half_batch")
    assert r["correct"] is False
    gap = r["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
