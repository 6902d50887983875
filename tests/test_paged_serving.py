"""Block-paged engine: exact greedy equivalence with the slot-pool engine
and the from-scratch oracle, block-table sharing / copy-on-write behavior,
chunked-prefill interleaving, and block conservation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import get_model, nn
from repro.serving.engine import InferenceEngine
from repro.serving.kvcache import BlockAllocator, PagedCachePool


def _build(name):
    if name == "dense":
        cfg = get_config("rhapsody-demo").scaled(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab=512)
    else:
        cfg = get_smoke_config("deepseek-moe-16b")
    api = get_model(cfg)
    params, _ = nn.split(api.init(jax.random.PRNGKey(0), cfg))
    return cfg, api, params


@pytest.fixture(scope="module")
def dense_lm():
    return _build("dense")


@pytest.fixture(scope="module")
def moe_lm():
    return _build("moe")


def _ref_generate(api, params, cfg, prompt, steps):
    cache, logits = api.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, cfg,
        max_len=128)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(steps - 1):
        cache, lg = api.decode(params, cache,
                               jnp.asarray([out[-1]], jnp.int32), cfg)
        out.append(int(jnp.argmax(lg[0])))
    return out


def _drive(eng, prompts, new_tokens, *, uids=None):
    uids = uids or [eng.submit(p, max_new_tokens=new_tokens)
                    for p in prompts]
    done = {}
    for _ in range(100000):
        if not eng.has_work():
            break
        eng.step()
        for r in eng.collect_finished():
            done[r.uid] = r
    return [done[u].output for u in uids]


ENGINE_KW = dict(max_num_seqs=4, max_num_batched_tokens=256, max_len=64,
                 prefill_buckets=(16, 32), seed=0)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_paged_matches_monolithic_and_ref(family, dense_lm, moe_lm):
    """Greedy outputs are token-for-token identical across the paged
    engine, the slot-pool engine, and the from-scratch incremental oracle
    — mixed prompt lengths spanning chunk and block boundaries."""
    cfg, api, params = dense_lm if family == "dense" else moe_lm
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(1, cfg.vocab, size=n))
               for n in (3, 8, 9, 17, 30)]
    mono = InferenceEngine(cfg, params, **ENGINE_KW)
    paged = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                            block_size=8)
    out_m = _drive(mono, prompts, 6)
    out_p = _drive(paged, prompts, 6)
    assert out_p == out_m
    for p, o in zip(prompts, out_p):
        assert o == _ref_generate(api, params, cfg, p, 6)


def test_paged_prefix_resume_chain(dense_lm):
    """Multi-turn chain: each turn extends the previous transcript, so
    every turn after the first forks resident blocks — outputs still match
    the from-scratch oracle exactly."""
    cfg, api, params = dense_lm
    eng = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                          block_size=4)
    prompt = [11, 12, 13, 14, 15, 16]
    for _ in range(3):
        uid = eng.submit(prompt, max_new_tokens=4)
        out = _drive(eng, [], 0, uids=[uid])[0]
        assert out == _ref_generate(api, params, cfg, prompt, 4)
        prompt = prompt + out + [9]
    assert eng.stats.prefix_reuse_hits >= 2
    assert eng.stats.prefix_cached_tokens > 0


def test_paged_divergence_rewind_cow(dense_lm):
    """Branch prompts sharing a stem with a resident transcript but
    diverging mid-way: the resume forks the shared blocks (PARTIAL hit)
    and the divergent write triggers copy-on-write — and each branch's
    output still matches the from-scratch oracle."""
    cfg, api, params = dense_lm
    eng = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                          block_size=4)
    stem = [5, 4, 3, 2, 1, 2, 3, 4, 5, 6, 7, 8]
    u = eng.submit(stem, max_new_tokens=4)
    _drive(eng, [], 0, uids=[u])
    branches = [stem[:9] + [100 + i, 101, 102] for i in range(3)]
    outs = _drive(eng, branches, 4)
    for p, o in zip(branches, outs):
        assert o == _ref_generate(api, params, cfg, p, 4)
    assert eng.stats.prefix_partial_hits >= 1
    assert eng.stats.cow_copies >= 1
    assert eng.stats.shared_block_peak > 0


def test_paged_concurrency_exceeds_slot_ceiling(dense_lm):
    """At memory parity (default num_blocks = the slot pool's KV cells),
    short sequences no longer pin whole max_len slots: the paged engine
    admits well past max_num_seqs, with identical outputs."""
    cfg, api, params = dense_lm
    rng = np.random.RandomState(2)
    prompts = [list(rng.randint(1, cfg.vocab, size=6)) for _ in range(10)]
    mono = InferenceEngine(cfg, params, **ENGINE_KW)
    paged = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                            block_size=8)
    # parity: 4 slots * 64 positions == 32 blocks of 8 (+ null block)
    assert paged.num_blocks == 33
    out_m = _drive(mono, prompts, 4)
    out_p = _drive(paged, prompts, 4)
    assert out_p == out_m
    assert paged.stats.peak_running > ENGINE_KW["max_num_seqs"]


def test_paged_chunked_prefill_interleaves_decode(dense_lm):
    """A long prompt prefills in chunks without stalling decode: a short
    request submitted alongside finishes BEFORE the long prompt emits its
    first token, and both match the oracle."""
    cfg, api, params = dense_lm
    eng = InferenceEngine(cfg, params, max_num_seqs=4,
                          max_num_batched_tokens=8, max_len=64,
                          prefill_buckets=(16, 32), seed=0, paged=True,
                          block_size=8, prefill_chunk=8)
    rng = np.random.RandomState(3)
    short = list(rng.randint(1, cfg.vocab, size=5))
    long = list(rng.randint(1, cfg.vocab, size=40))
    u_short = eng.submit(short, max_new_tokens=4)
    u_long = eng.submit(long, max_new_tokens=4)
    first_emit = {}
    done = {}
    for step in range(10000):
        if not eng.has_work():
            break
        for uid, _ in eng.step():
            first_emit.setdefault(uid, step)
        for r in eng.collect_finished():
            done[r.uid] = (r.output, step)
    out_s, t_short_done = done[u_short]
    out_l, _ = done[u_long]
    assert out_s == _ref_generate(api, params, cfg, short, 4)
    assert out_l == _ref_generate(api, params, cfg, long, 4)
    # the 40-token prompt needs 5 chunk steps at budget 8; the short
    # request decoded to completion inside that window
    assert t_short_done < first_emit[u_long]


def test_paged_residency_eviction(dense_lm):
    """When free blocks run out, the coldest residency is evicted at
    block granularity and the drop listener fires — and evicted prefixes
    simply miss (fresh prefill), never corrupt."""
    cfg, api, params = dense_lm
    eng = InferenceEngine(cfg, params, max_num_seqs=2,
                          max_num_batched_tokens=128, max_len=32,
                          prefill_buckets=(16, 32), seed=0, paged=True,
                          block_size=8, num_blocks=9)  # capacity: 8 blocks
    drops = []
    eng.on_residency_drop = lambda: drops.append(1)
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(1, cfg.vocab, size=20)) for _ in range(3)]
    for p in prompts:
        u = eng.submit(p, max_new_tokens=4)
        out = _drive(eng, [], 0, uids=[u])[0]
        assert out == _ref_generate(api, params, cfg, p, 4)
    # 3 retired sequences x 3 blocks each > 8-block capacity: the first
    # residency must have been evicted to admit the third sequence
    assert eng.stats.evicted_residencies >= 1
    assert drops
    assert len(eng._residency) < 3


def test_paged_prefix_reuse_disabled_frees_blocks(dense_lm):
    """With reuse off, retirement frees every block immediately — the
    allocator returns to full capacity after each drain."""
    cfg, api, params = dense_lm
    eng = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                          block_size=8, enable_prefix_reuse=False)
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, cfg.vocab, size=10)) for _ in range(3)]
    outs = _drive(eng, prompts, 4)
    for p, o in zip(prompts, outs):
        assert o == _ref_generate(api, params, cfg, p, 4)
    assert eng.stats.prefix_reuse_hits == 0
    assert eng.pool.alloc.n_free == eng.pool.alloc.capacity
    assert eng._reserved == 0


def test_paged_block_conservation_after_drain(dense_lm):
    """After serving a branching load and force-evicting every residency,
    all blocks return to the free list and no reservation leaks."""
    cfg, _, params = dense_lm
    eng = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                          block_size=8)
    stem = [7, 6, 5, 4, 3, 2, 1, 2, 3]
    _drive(eng, [stem], 4)
    _drive(eng, [stem + [10 + i] for i in range(5)], 4)
    assert eng.stats.shared_block_peak > 0
    while eng._residency:
        eng._evict_residency()
    assert eng.pool.alloc.n_free == eng.pool.alloc.capacity
    assert eng.pool.block_savings() == 0
    assert eng._reserved == 0
    assert eng._res_holds == {}


def test_paged_rejects_state_carrying_families():
    """ssm/hybrid have no per-position KV: paged mode must refuse."""
    cfg = get_smoke_config("rwkv6-1.6b")
    with pytest.raises(ValueError, match="paged"):
        PagedCachePool(cfg, num_blocks=8, block_size=4, max_len=16)
    api = get_model(cfg)
    params, _ = nn.split(api.init(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError):
        InferenceEngine(cfg, params, paged=True, max_len=16,
                        prefill_buckets=(16,))


def test_block_allocator_error_paths():
    """The allocator enforces the invariants CoW safety rests on."""
    alloc = BlockAllocator(4)
    with pytest.raises(ValueError):
        BlockAllocator(1)  # no room for the null block + one real block
    b = alloc.allocate()
    alloc.free(b)
    with pytest.raises(ValueError):
        alloc.free(b)  # double free
    with pytest.raises(ValueError):
        alloc.fork(b)  # fork of an unallocated block
    with pytest.raises(ValueError):
        alloc.fork(0)  # the null block is never refcounted
    with pytest.raises(ValueError):
        alloc.free(99)  # out of range


def test_paged_pool_rejects_undersized_budget(dense_lm):
    """A pool that cannot hold even one max_len sequence is a config
    error, not a runtime deadlock."""
    cfg, _, _ = dense_lm
    with pytest.raises(ValueError, match="num_blocks"):
        PagedCachePool(cfg, num_blocks=4, block_size=8, max_len=64)


def test_paged_sampling_smoke(dense_lm):
    """temperature > 0 runs through the paged prefill/decode sampling
    paths and terminates (no equivalence claim — key streams differ)."""
    cfg, _, params = dense_lm
    eng = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                          block_size=8)
    u = eng.submit([3, 1, 4, 1, 5, 9], max_new_tokens=5, temperature=0.8)
    out = _drive(eng, [], 0, uids=[u])[0]
    assert len(out) == 5
    assert all(0 <= t < cfg.vocab for t in out)


# ---------------------------------------------------------------------------
# Direct paged decode (the kernel-on-the-block-store path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_paged_decode_modes_equivalent(family, dense_lm, moe_lm):
    """Direct paged decode (K/V written straight into the tail block,
    attention through the block table) is token-identical to the legacy
    gather round-trip AND the slot pool — ragged lengths straddling block
    boundaries (block_size 8: 7/8/9 and 15/16/17)."""
    cfg, api, params = dense_lm if family == "dense" else moe_lm
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, cfg.vocab, size=n))
               for n in (7, 8, 9, 15, 16, 17)]
    outs = {}
    for mode in ("slot", "direct", "gather"):
        kw = dict(ENGINE_KW)
        if mode != "slot":
            kw.update(paged=True, block_size=8, paged_decode_mode=mode)
        outs[mode] = _drive(InferenceEngine(cfg, params, **kw), prompts, 6)
    assert outs["direct"] == outs["gather"] == outs["slot"]


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_paged_kernel_decode_matches_slot(family, dense_lm, moe_lm):
    """Direct decode through the Pallas paged kernel (interpret mode here;
    the path a TPU takes with the shipped configs) is token-identical to
    the slot pool, across block-boundary lengths."""
    cfg, api, params = dense_lm if family == "dense" else moe_lm
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(1, cfg.vocab, size=n))
               for n in (7, 8, 9, 16, 17, 31)]
    slot = _drive(InferenceEngine(cfg, params, **ENGINE_KW), prompts, 6)
    kernel = _drive(InferenceEngine(cfg.scaled(attention_impl="pallas"),
                                    params, **ENGINE_KW, paged=True,
                                    block_size=8), prompts, 6)
    assert kernel == slot


def test_paged_decode_modes_agree_on_divergence(dense_lm):
    """Partial-hit resume plus copy-on-write divergence produce identical
    greedy tokens under the direct kernel and the gather round-trip, and
    both match the from-scratch oracle."""
    cfg, api, params = dense_lm
    stem = [5, 4, 3, 2, 1, 2, 3, 4, 5, 6, 7, 8]
    branches = [stem[:9] + [100 + i, 101, 102] for i in range(3)]
    outs = {}
    for mode in ("direct", "gather"):
        eng = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                              block_size=4, paged_decode_mode=mode)
        _drive(eng, [stem], 4)
        outs[mode] = _drive(eng, branches, 4)
        assert eng.stats.prefix_partial_hits >= 1
        assert eng.stats.cow_copies >= 1
    assert outs["direct"] == outs["gather"]
    for p, o in zip(branches, outs["direct"]):
        assert o == _ref_generate(api, params, cfg, p, 4)


def test_direct_decode_never_gathers(dense_lm, monkeypatch):
    """The tentpole invariant: in direct mode the decode step NEVER
    reassembles a contiguous view — ``gather_block_view`` is extend-only.
    The gather-mode engine run through the same spy proves the spy sees
    decode-phase gathers when they happen."""
    import repro.serving.engine as engine_mod
    cfg, _, params = dense_lm
    in_decode = []
    decode_gathers = {"direct": 0, "gather": 0}
    real_gather = engine_mod.gather_block_view
    current = ["direct"]

    def spy(*a, **k):
        if in_decode:
            decode_gathers[current[0]] += 1
        return real_gather(*a, **k)

    monkeypatch.setattr(engine_mod, "gather_block_view", spy)
    rng = np.random.RandomState(8)
    prompts = [list(rng.randint(1, cfg.vocab, size=n)) for n in (5, 11, 19)]
    for mode in ("direct", "gather"):
        current[0] = mode
        eng = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                              block_size=8, paged_decode_mode=mode)
        real_decode = eng._paged_decode

        def wrapped(*a, __real=real_decode, **k):
            in_decode.append(1)
            try:
                return __real(*a, **k)
            finally:
                in_decode.pop()

        eng._paged_decode = wrapped
        _drive(eng, prompts, 6)
    assert decode_gathers["direct"] == 0
    assert decode_gathers["gather"] > 0


def test_paged_rejects_unknown_decode_mode(dense_lm):
    cfg, _, params = dense_lm
    with pytest.raises(ValueError, match="paged_decode_mode"):
        InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                        block_size=8, paged_decode_mode="telepathy")


# ---------------------------------------------------------------------------
# Chunk-budget accounting (bugfix: charge the padded bucket, not T)
# ---------------------------------------------------------------------------


def test_paged_chunk_budget_charges_padded_bucket(dense_lm):
    """Regression: the prefill scheduler must charge the PADDED bucket
    that actually runs, so one step's batched prefill tokens never exceed
    ``max_num_batched_tokens`` under ragged chunk mixes.  (The old code
    charged the real token count: three 9-token chunks padded to bucket 16
    fit a 24-token budget on paper while running 48.)"""
    cfg, api, params = dense_lm
    eng = InferenceEngine(cfg, params, max_num_seqs=8,
                          max_num_batched_tokens=24, max_len=64,
                          prefill_buckets=(8, 16), seed=0, paged=True,
                          block_size=8)
    real = eng._paged_extend
    widths = []

    def spy(params, store, bt, lens, tokens, wphys, woff):
        widths.append(int(tokens.shape[1]))
        return real(params, store, bt, lens, tokens, wphys, woff)

    eng._paged_extend = spy
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(1, cfg.vocab, size=n))
               for n in (9, 9, 9, 13, 21, 30)]
    uids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    done = {}
    per_step = []
    for _ in range(100000):
        if not eng.has_work():
            break
        widths.clear()
        eng.step()
        per_step.append(sum(widths))
        for r in eng.collect_finished():
            done[r.uid] = r
    assert max(per_step) <= 24
    # splitting a chunk to fit the remaining budget stays correct
    for p, u in zip(prompts, uids):
        assert done[u].output == _ref_generate(api, params, cfg, p, 4)


# ---------------------------------------------------------------------------
# Live pool gauges + telemetry + servicer paged default
# ---------------------------------------------------------------------------


def test_paged_live_gauges_and_telemetry(dense_lm):
    """free/reserved gauges track the pool every step (not just peaks),
    and block_telemetry() bundles the router-facing numbers."""
    cfg, _, params = dense_lm
    eng = InferenceEngine(cfg, params, **ENGINE_KW, paged=True,
                          block_size=8)
    assert eng.stats.free_blocks == eng.pool.n_free
    _drive(eng, [[1, 2, 3, 4, 5], [1, 2, 3, 9, 9, 9, 9]], 4)
    assert eng.stats.free_blocks == eng.pool.n_free
    assert eng.stats.reserved_blocks == eng._reserved == 0
    tel = eng.block_telemetry()
    assert tel["free_blocks"] == eng.pool.n_free
    assert tel["total_blocks"] == eng.pool.alloc.capacity
    assert {"reserved_blocks", "shared_blocks", "cow_copies",
            "evicted_residencies"} <= set(tel)
    # slot-pool engines report no block telemetry
    mono = InferenceEngine(cfg, params, **ENGINE_KW)
    assert mono.block_telemetry() is None


def test_llm_servicer_paged_auto_default(dense_lm):
    """LLMServicer defaults dense/moe replicas to the paged engine
    (direct decode); explicit paged=False forces the slot pool; families
    without per-position KV auto-resolve to the slot pool with the
    paged-only knobs stripped."""
    from repro.serving.client import LLMServicer
    cfg, _, params = dense_lm
    s = LLMServicer(cfg, params, max_num_seqs=2, max_len=32,
                    prefill_buckets=(16,))
    assert s.engine.paged
    assert s.engine.paged_decode_mode == "direct"
    assert s.block_telemetry()["total_blocks"] > 0
    s = LLMServicer(cfg, params, max_num_seqs=2, max_len=32,
                    prefill_buckets=(16,), paged=False, block_size=8)
    assert not s.engine.paged
    assert s.block_telemetry() is None
    ssm = get_smoke_config("rwkv6-1.6b")
    sapi = get_model(ssm)
    sparams, _ = nn.split(sapi.init(jax.random.PRNGKey(0), ssm))
    s = LLMServicer(ssm, sparams, max_num_seqs=2, max_len=16,
                    prefill_buckets=(16,), block_size=8, num_blocks=16)
    assert not s.engine.paged
    assert s.block_telemetry() is None
