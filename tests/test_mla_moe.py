"""Latent attention and the sigmoid-routed, dropless expert layer
(moonlight-16b-a3b at toy widths, float32), against the plain float32
references of ``repro.models.reference``: chunked prefill and paged
decode through the latent store, the routing formula, a token's output
whatever its batch, the expert shares of an expert-parallel layer, a
served run through ``LLMServicer``, and the bfloat16 routing flips."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import get_model, moe, nn
from repro.models.reference import (lm_reference, moe_reference,
                                    route_reference)
from repro.serving.engine import paged_decode_step, paged_extend_step
from repro.serving.kvcache import PagedCachePool

# Program (float32, default matmul precision on the CPU) against the
# reference (float32, Precision.HIGHEST): they differ in summation order
# and in the absorbed decode's association (q . W_UK . c against
# q . (c . W_UK)), which at these widths and magnitudes (logits of order
# 1) moves a logit by ~1e-6.  1e-4 leaves room for that and fails on any
# mistake of mathematics (a wrong position, rotary pair, mask or expert
# moves logits by 1e-2 or more).
LOGIT_TOL = 1e-4


@pytest.fixture(scope="module")
def moonlight():
    cfg = get_smoke_config("moonlight-16b-a3b")
    api = get_model(cfg)
    params, _ = nn.split(api.init(jax.random.PRNGKey(0), cfg))
    # a non-zero correction bias, so that the reference must follow it
    router = params["blocks"]["moe"]["router"]
    router["bias"] = jax.random.uniform(jax.random.PRNGKey(1),
                                        router["bias"].shape,
                                        minval=-0.3, maxval=0.3)
    return cfg, api, params


def test_chunked_prefill_then_paged_decode_matches_the_reference(moonlight):
    """Prefill in 16-token chunks, then decode one token at a time,
    straight on a paged latent store of 8-position blocks: every
    position's logits agree with the reference's full forward pass."""
    cfg, api, params = moonlight
    bs, chunk, n_new = 8, 16, 6
    prompt = list(np.random.RandomState(3).randint(1, cfg.vocab, 37))
    pool = PagedCachePool(cfg, num_blocks=12, block_size=bs, max_len=64)
    table = [3, 7, 1, 10, 5, 2, 9, 11]  # permuted blocks
    bt = jnp.asarray([table], jnp.int32)
    store, got, seq = pool.cache, [], list(prompt)

    def cells(positions):
        return (jnp.asarray([[table[p // bs] for p in positions]], jnp.int32),
                jnp.asarray([[p % bs for p in positions]], jnp.int32))

    for start in range(0, len(prompt), chunk):
        toks = prompt[start:start + chunk]
        pad = chunk - len(toks)
        wphys, woff = cells(range(start, start + len(toks)))
        wphys = jnp.pad(wphys, ((0, 0), (0, pad)))  # padding: null block
        woff = jnp.pad(woff, ((0, 0), (0, pad)))
        store, logits, _ = paged_extend_step(
            api, cfg, params, store, bt, jnp.asarray([start], jnp.int32),
            jnp.asarray([toks + [0] * pad], jnp.int32), wphys, woff)
        got += list(np.asarray(logits[0, :len(toks)]))
    for _ in range(n_new):
        pos = len(seq)
        seq.append(int(np.argmax(got[-1])))
        wphys, woff = cells([pos])
        store, logits, counts = paged_decode_step(
            api, cfg, params, store, bt, jnp.asarray([pos], jnp.int32),
            jnp.asarray([seq[-1]], jnp.int32), wphys[0], woff[0])
        got.append(np.asarray(logits[0]))
    # one real token, top 2 of 8 held experts, in each of 2 MoE layers
    assert counts.shape == (2, 8) and int(counts.sum()) == 4
    ref = np.asarray(lm_reference(params, seq, cfg))
    np.testing.assert_allclose(np.stack(got), ref, atol=LOGIT_TOL,
                               rtol=0)


def test_routing_follows_the_formula_and_the_bias_changes_the_choice():
    cfg = get_smoke_config("moonlight-16b-a3b")
    E, k = cfg.n_experts, cfg.top_k
    rng = np.random.RandomState(0)
    x = rng.randn(32, cfg.d_model).astype(np.float32)
    w = rng.randn(cfg.d_model, E).astype(np.float32) / 8
    bias = np.zeros(E, np.float32)
    bias[5] = 4.0  # expert 5 wins the choice, not the weights
    for b in (np.zeros(E, np.float32), bias):
        top_w, top_i, _ = moe.route({"w": jnp.asarray(w),
                                     "bias": jnp.asarray(b)},
                                    jnp.asarray(x), cfg)
        s = 1 / (1 + np.exp(-(x @ w)))
        want_i = np.argsort(-(s + b), axis=1)[:, :k]
        want_w = np.take_along_axis(s, want_i, 1)
        want_w = want_w / want_w.sum(1, keepdims=True) * 2.446
        np.testing.assert_array_equal(np.sort(np.asarray(top_i), 1),
                                      np.sort(want_i, 1))
        order = np.argsort(np.asarray(top_i), 1)
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(top_w), order, 1),
            np.take_along_axis(want_w, np.argsort(want_i, 1), 1),
            rtol=1e-5)
    unbiased = np.argsort(-s, axis=1)[:, :k]
    assert (unbiased != 5).all(axis=1).any()  # the bias changed sets
    assert (np.asarray(top_i) == 5).any(axis=1).all()


def test_a_tokens_output_is_the_same_whatever_its_batch(moonlight):
    """Dropless: nothing caps an expert's tokens, so a token's output
    does not depend on what else is routed with it."""
    cfg, _, params = moonlight
    p = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, cfg.d_model))
    whole, _, _ = moe.moe_apply(p, x, cfg)
    for lo, hi in ((0, 1), (7, 8), (3, 30), (39, 40)):
        part, _, _ = moe.moe_apply(p, x[:, lo:hi], cfg)
        np.testing.assert_allclose(np.asarray(part), np.asarray(whole[:,
                                   lo:hi]), rtol=0, atol=1e-6)
    _, _, counts = moe.moe_apply(p, x, cfg,
                                 valid=jnp.arange(40)[None] < 10)
    assert int(counts.sum()) == 10 * cfg.top_k  # padded tokens route nowhere


def test_the_expert_shares_add_up_to_the_whole_layer():
    """16 experts over 8 shards of 2, as expert parallelism holds them:
    each shard routes over all 16 and computes its own experts' part;
    the parts, with the shared experts counted once, add up to the
    reference's whole layer."""
    cfg = get_smoke_config("moonlight-16b-a3b").scaled(n_experts=16,
                                                       top_k=6)
    p = nn.split(moe.moe_init(jax.random.PRNGKey(2), cfg))[0]
    p["router"]["bias"] = jax.random.uniform(jax.random.PRNGKey(3), (16,),
                                             minval=-0.2, maxval=0.2)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    total = 0.0
    for j in range(8):
        shard = dict(p, up=p["up"][2 * j:2 * j + 2],
                     gate=p["gate"][2 * j:2 * j + 2],
                     down=p["down"][2 * j:2 * j + 2])
        if j:  # the shared experts run on every chip; count them once
            del shard["shared"]
        y, _, _ = moe.moe_apply(
            shard, x, cfg.scaled(experts_held=2, experts_first=2 * j))
        total = total + y
    whole = moe_reference(p, x.reshape(-1, cfg.d_model), cfg)
    np.testing.assert_allclose(np.asarray(total).reshape(whole.shape),
                               np.asarray(whole), rtol=0, atol=1e-5)


def test_a_served_run_agrees_with_the_reference(moonlight):
    """Through the servicer and its paged engine, every served token is
    the reference's best, to the tolerance above."""
    from repro.serving.client import LLMServicer

    cfg, _, params = moonlight
    s = LLMServicer(cfg, params, max_num_seqs=4, max_len=64,
                    prefill_buckets=(16, 32), block_size=8)
    assert s.engine.paged
    rng = np.random.RandomState(6)
    prompts = [list(map(int, rng.randint(1, cfg.vocab, n)))
               for n in (5, 19, 33)]
    uids = {s.submit({"prompt": p, "max_new_tokens": 8}): p
            for p in prompts}
    done = {}
    while len(done) < len(uids):
        for uid, res in s.step():
            done[uid] = res["tokens"]
    for uid, prompt in uids.items():
        out = done[uid]
        ref = np.asarray(lm_reference(params, prompt + out[:-1], cfg))
        rows = ref[len(prompt) - 1:]
        gaps = rows.max(1) - rows[np.arange(len(out)), out]
        assert gaps.max() <= LOGIT_TOL, gaps
    st = s.engine.stats
    assert st.expert_assignments > 0
    assert st.expert_assignments_max * cfg.n_held >= st.expert_assignments


def test_bfloat16_routing_flips_are_reported(moonlight, capsys):
    """How often rounding the router's input to bfloat16 changes a
    token's chosen experts against float32: reported, not asserted (a
    near tie at the top-k boundary flips on rounding by nature)."""
    cfg, _, params = moonlight
    p = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])["router"]
    h = jax.random.normal(jax.random.PRNGKey(8), (4096, cfg.d_model))
    chosen = []
    for dt in (jnp.float32, jnp.bfloat16):
        w = route_reference(p, h.astype(dt).astype(jnp.float32), cfg)
        chosen.append(np.asarray(w > 0))
    rate = float((chosen[0] != chosen[1]).any(axis=1).mean())
    with capsys.disabled():
        print(f"\nbf16 routing flips: {rate:.2%} of {h.shape[0]} tokens "
              f"({cfg.n_experts} experts, top {cfg.top_k})")
    assert 0.0 <= rate < 1.0


def test_parameter_counts_follow_latent_attention_and_held_experts():
    from repro.configs import get_config

    cfg = get_config("moonlight-16b-a3b")
    # 27 layers: MLA 13.76 M each, one dense MLP of 3 x 2048 x 11264, 26
    # expert layers of 64 + 2 experts of 3 x 2048 x 1408 and a router
    # with its bias; embedding and head 2 x 163840 x 2048
    assert cfg.param_count_analytical() == 15_959_997_568
    assert round(cfg.active_param_count() / 1e9, 2) == 2.91  # "A3B"
    cut = cfg.scaled(n_layers=9, experts_held=8)
    assert cut.param_count_analytical() == 1_557_271_552
