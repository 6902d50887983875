"""Disaggregated prefill/decode serving: paged-KV export/import handoff
(token identity, slot/pool rejection, recompute-on-miss fallback),
streaming TTFT stamping, phase-pure latency windows, borrow-limited
donation, and warm-handoff rebalance ordering."""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core import (ExecutionPolicy, ModelGroup, ResourceDescription,
                        ResourceRequirements, Rhapsody, ServiceDescription,
                        WeightedCapacityAutoscaler)
from repro.core.request import InferenceRequest
from repro.core.service import _Future
from repro.models import get_model, nn
from repro.serving.client import LLMServicer, llm_model_group
from repro.serving.engine import InferenceEngine


def _build(name):
    if name == "dense":
        cfg = get_config("rhapsody-demo").scaled(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab=512)
    elif name == "moe":
        cfg = get_smoke_config("deepseek-moe-16b")
    else:  # latent attention, sigmoid-routed experts
        cfg = get_smoke_config("moonlight-16b-a3b")
    api = get_model(cfg)
    params, _ = nn.split(api.init(jax.random.PRNGKey(0), cfg))
    return cfg, api, params


@pytest.fixture(scope="module")
def dense_lm():
    return _build("dense")


@pytest.fixture(scope="module")
def moe_lm():
    return _build("moe")


@pytest.fixture(scope="module")
def mla_lm():
    return _build("mla")


ENGINE_KW = dict(max_num_seqs=4, max_num_batched_tokens=256, max_len=64,
                 prefill_buckets=(16, 32), seed=0, paged=True, block_size=8)


def _prefill_export_all(pre, n, max_steps=200):
    """Pump a prefill-role paged engine until ``n`` sequences exported."""
    payloads = {}
    for _ in range(max_steps):
        if len(payloads) >= n:
            break
        pre.step_prefill_only()
        for uid in pre.exportable():
            payloads[uid] = pre.export_sequence(uid)
    assert len(payloads) == n, "prefill engine never exported every seq"
    return payloads


# ---------------------------------------------------------------------------
# Engine-level export/import round trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["dense", "moe", "mla"])
def test_export_import_round_trip_token_identity(family, dense_lm, moe_lm,
                                                 mla_lm):
    """Greedy outputs survive the prefill->decode migration bit-for-bit:
    prefill on engine A, export, import into engine B, finish there —
    token-identical to the same prompts decoded on one unified engine."""
    cfg, api, params = {"dense": dense_lm, "moe": moe_lm,
                         "mla": mla_lm}[family]
    rng = np.random.RandomState(0)
    prompts = [list(map(int, rng.randint(1, cfg.vocab, size=n)))
               for n in (5, 12, 23)]
    pre = InferenceEngine(cfg, params, **ENGINE_KW)
    dec = InferenceEngine(cfg, params, **ENGINE_KW)
    uids = [pre.submit(p, max_new_tokens=6) for p in prompts]
    payloads = _prefill_export_all(pre, len(prompts))
    assert not pre.running  # exports retire on the prefill side
    moved = {}
    for uid, pay in payloads.items():
        nuid = dec.import_sequence(pay)
        assert nuid is not None
        moved[uid] = (nuid, pay)
    done = dec.run()
    ref = InferenceEngine(cfg, params, **ENGINE_KW)
    ref_uids = [ref.submit(p, max_new_tokens=6) for p in prompts]
    ref_done = ref.run()
    for uid, ruid in zip(uids, ref_uids):
        nuid, pay = moved[uid]
        out = done[nuid].output
        assert out == ref_done[ruid].output
        # the prefill-side tokens are the prefix of the final output and
        # the original submit stamp survives the migration
        assert out[:len(pay["output"])] == pay["output"]
        assert done[nuid].submitted_at == pay["submitted_at"]


def test_import_refused_on_full_slots_then_lands_elsewhere(dense_lm):
    """A decode engine at its max_running cap refuses the import (None,
    no reservation leak); the untouched payload still imports cleanly
    into a roomier engine and finishes token-identically."""
    cfg, api, params = dense_lm
    pre = InferenceEngine(cfg, params, **ENGINE_KW)
    tight = InferenceEngine(cfg, params, **ENGINE_KW, max_running=1)
    tight.submit([3] * 10, max_new_tokens=30)
    tight.step()  # occupant admitted: running == max_num_seqs
    prompt = [5, 6, 7, 8, 9]
    pre.submit(prompt, max_new_tokens=4)
    pay = list(_prefill_export_all(pre, 1).values())[0]
    free0, res0 = tight.pool.n_free, tight._reserved
    assert tight.import_sequence(pay) is None
    assert (tight.pool.n_free, tight._reserved) == (free0, res0)
    roomy = InferenceEngine(cfg, params, **ENGINE_KW)
    nuid = roomy.import_sequence(pay)
    assert nuid is not None
    out = roomy.run()[nuid].output
    ref = InferenceEngine(cfg, params, **ENGINE_KW)
    ruid = ref.submit(prompt, max_new_tokens=4)
    assert out == ref.run()[ruid].output


def test_import_refused_on_exhausted_block_pool(dense_lm):
    """Admission-gated import: with the whole pool reserved by a live
    occupant, import_sequence refuses instead of over-committing —
    and leaves the free/reserved gauges untouched."""
    cfg, api, params = dense_lm
    pre = InferenceEngine(cfg, params, **ENGINE_KW)
    # num_blocks=9: one blank + 8 usable == exactly one max_len sequence
    dec = InferenceEngine(cfg, params, **{**ENGINE_KW, "num_blocks": 9})
    dec.submit([3] * 30, max_new_tokens=30)  # reserves all 8 blocks
    dec.step()
    pre.submit([7, 8, 9, 10, 11], max_new_tokens=4)
    pay = list(_prefill_export_all(pre, 1).values())[0]
    free0, res0 = dec.pool.n_free, dec._reserved
    assert dec.import_sequence(pay) is None
    assert (dec.pool.n_free, dec._reserved) == (free0, res0)


# ---------------------------------------------------------------------------
# Servicer-level handoff: counters and recompute fallback
# ---------------------------------------------------------------------------

SV_KW = dict(max_num_seqs=4, max_num_batched_tokens=256, max_len=64,
             paged=True, block_size=8, num_blocks=64,
             prefill_buckets=(16, 32))


def test_servicer_recompute_fallback_token_identity(dense_lm):
    """Every handoff denied by a block-exhausted decode pool degrades to
    a recompute on the decode replica — counted, flagged in the result,
    and still token-identical to a unified reference engine."""
    cfg, api, params = dense_lm
    pre = LLMServicer(cfg, params, phase="prefill", **SV_KW)
    dec = LLMServicer(cfg, params, phase="decode",
                      **{**SV_KW, "max_num_batched_tokens": 64,
                         "num_blocks": 9})
    dec.engine.submit([3] * 30, max_new_tokens=30)  # pins the pool
    dec.engine.step()
    prompts = [[7, 8, 9, 10, 11], [1, 2, 3], [4] * 9]
    for p in prompts:
        pre.submit({"prompt": p, "max_new_tokens": 5})
    handoffs = []
    for _ in range(200):
        if len(handoffs) == len(prompts):
            break
        for _uid, res in pre.step():
            assert res.get("role") == "prefill"
            assert res.get("handoff_export") is not None
            handoffs.append(res["handoff_export"])
    assert pre.handoff_stats() == {"role": "prefill",
                                   "exports": len(prompts),
                                   "imports": 0, "recomputes": 0}
    new_uids = [dec.submit({"prompt": list(pay["prompt"])},
                           envelope=InferenceRequest(
                               payload={"prompt": list(pay["prompt"])},
                               handoff=pay))
                for pay in handoffs]
    hs = dec.handoff_stats()
    assert hs["imports"] == 0 and hs["recomputes"] == len(prompts)
    results = {}
    for _ in range(2000):
        if len(results) == len(prompts) + 1:  # + the occupant
            break
        for uid, res in dec.step():
            results[uid] = res
    ref = InferenceEngine(cfg, params, **ENGINE_KW)
    ref_uids = [ref.submit(p, max_new_tokens=5) for p in prompts]
    ref_done = ref.run()
    for pay, nuid, ruid in zip(handoffs, new_uids, ref_uids):
        res = results[nuid]
        assert res.get("handoff") is True and res.get("recompute") is True
        assert res.get("role") == "decode"
        assert res["tokens"] == ref_done[ruid].output
        # end-to-end latency still spans the whole migration
        assert res["latency_s"] >= 0 and res["ttft_s"] is not None


# ---------------------------------------------------------------------------
# generate_stream / ttft_s
# ---------------------------------------------------------------------------


def test_generate_stream_tokens_then_final(dense_lm):
    """Tokens stream in generation order; the final event repeats them
    with the step()-shaped latency keys, matching a non-streamed run."""
    cfg, api, params = dense_lm
    sv = LLMServicer(cfg, params, **SV_KW)
    events = list(sv.generate_stream({"prompt": [5, 6, 7],
                                      "max_new_tokens": 6}))
    toks = [e["token"] for e in events[:-1]]
    final = events[-1]
    assert final["done"] is True
    assert final["tokens"] == toks and len(toks) == 6
    assert final["ttft_s"] is not None and final["ttft_s"] > 0
    assert final["itl_s"] is not None and final["latency_s"] > 0
    ref = InferenceEngine(cfg, params, **ENGINE_KW)
    u = ref.submit([5, 6, 7], max_new_tokens=6)
    assert ref.run()[u].output == toks


def test_generate_stream_empty_generation_has_no_ttft(dense_lm):
    """max_new_tokens<=0 yields only the final event with ttft_s None —
    an empty generation has no first token to stamp."""
    cfg, api, params = dense_lm
    sv = LLMServicer(cfg, params, **SV_KW)
    events = list(sv.generate_stream({"prompt": [5, 6],
                                      "max_new_tokens": 0}))
    assert len(events) == 1
    assert events[0]["done"] is True
    assert events[0]["tokens"] == [] and events[0]["ttft_s"] is None


def test_generate_stream_resumed_sequence_stamps_ttft(dense_lm):
    """A follow-up turn resuming resident prefix KV skips prefill
    entirely — its first token must still stamp ttft_s (the stamp lives
    on first-token emission, not on the prefill path)."""
    cfg, api, params = dense_lm
    sv = LLMServicer(cfg, params, **SV_KW)
    prompt = [11, 12, 13, 14, 15, 16]
    out1 = list(sv.generate_stream({"prompt": prompt,
                                    "max_new_tokens": 4}))[-1]
    prompt2 = prompt + out1["tokens"] + [9]
    out2 = list(sv.generate_stream({"prompt": prompt2,
                                    "max_new_tokens": 4}))[-1]
    assert sv.engine.stats.prefix_reuse_hits >= 1
    assert out2["ttft_s"] is not None and out2["ttft_s"] > 0
    ref = InferenceEngine(cfg, params, **ENGINE_KW)
    u = ref.submit(prompt2, max_new_tokens=4)
    assert out2["tokens"] == ref.run()[u].output


def test_generate_stream_refused_on_prefill_replicas(dense_lm):
    cfg, api, params = dense_lm
    sv = LLMServicer(cfg, params, phase="prefill", **SV_KW)
    with pytest.raises(ValueError, match="prefill"):
        next(sv.generate_stream({"prompt": [1, 2], "max_new_tokens": 2}))


# ---------------------------------------------------------------------------
# _Future.add_done_callback
# ---------------------------------------------------------------------------


def test_future_add_done_callback_orders_and_errors():
    f = _Future()
    seen = []
    f.add_done_callback(lambda fut: seen.append(fut.result(0)))
    f.add_done_callback(lambda fut: 1 / 0)  # callback errors swallowed
    f.set_result(42)
    assert seen == [42]
    f.add_done_callback(lambda fut: seen.append("late"))
    assert seen == [42, "late"]  # already-done future fires immediately
    g = _Future()
    errs = []

    def chain(fut):
        try:
            fut.result(0)
        except RuntimeError as e:
            errs.append(str(e))

    g.add_done_callback(chain)
    g.set_error(RuntimeError("boom"))
    assert errs == ["boom"]


# ---------------------------------------------------------------------------
# WeightedCapacityAutoscaler: borrow_limit floor + per-phase directions
# ---------------------------------------------------------------------------


class FakeGroupRS:
    """Just the group surface desired_groups() consumes, plus the
    optional borrow/role hooks the scaler probes with getattr."""

    multi_model = True

    def __init__(self, counts, p95_s, depths, headroom=None, weights=None,
                 borrows=None, roles=None):
        self._counts = dict(counts)
        self._p95 = dict(p95_s)  # group (or (group, phase)) -> seconds
        self._depths = dict(depths)
        self._headroom = headroom
        self._weights = weights or {g: 1.0 for g in counts}
        self._borrows = borrows
        self._roles = roles
        self.denied = 0
        self.phase_calls = []
        if borrows is not None:
            self.group_borrow_limit = lambda g: self._borrows.get(g)
        if roles is not None:
            self.group_role = lambda g: self._roles.get(g, "serve")

    def group_counts(self):
        return dict(self._counts)

    def group_weight(self, g):
        return self._weights[g]

    def group_slo_ms(self, g):
        return 100.0

    def latency_p95(self, window_s=None, started_after=None, group=None,
                    phase=None):
        self.phase_calls.append((group, phase))
        key = (group, phase) if (group, phase) in self._p95 else group
        return self._p95[key]

    def mean_depth(self, group=None):
        return self._depths[group]

    def capacity_headroom(self, group=None):
        return self._headroom

    def _note_admission_denied(self, where, once_per_episode=False):
        self.denied += 1


def _scaler(**kw):
    kw.setdefault("autoscaler", "weighted_capacity")
    kw.setdefault("autoscale_sustain_up", 1)
    kw.setdefault("autoscale_sustain_down", 1)
    kw.setdefault("autoscale_max_replicas", 4)
    kw.setdefault("autoscale_low_depth", 0.5)
    kw.setdefault("slo_p95_ms", 100.0)
    return WeightedCapacityAutoscaler(ExecutionPolicy(**kw))


def test_borrow_limit_floors_the_donor():
    """borrow_limit=0 pins the donor at its weight-anchored entitlement
    (ceil(2.0) - 0 = 2): the burst group cannot borrow, the scaler holds
    and notes the denial; borrow_limit=1 releases one replica."""
    a = _scaler()
    # "a" is mid-band (no idle-shrink signal of its own): the ONLY way
    # it loses a replica is being picked as b's donor
    rs = FakeGroupRS({"a": 2, "b": 2}, {"a": 0.06, "b": 0.2},
                     {"a": 1.0, "b": 5.0}, headroom=0,
                     borrows={"a": 0, "b": None})
    assert a.desired_groups("s", rs) is None
    assert rs.denied == 1
    a2 = _scaler()
    rs2 = FakeGroupRS({"a": 2, "b": 2}, {"a": 0.06, "b": 0.2},
                      {"a": 1.0, "b": 5.0}, headroom=0,
                      borrows={"a": 1, "b": None})
    assert a2.desired_groups("s", rs2) == {"a": 1, "b": 3}


def test_per_phase_directions_grow_prefill_on_ttft_violation():
    """A prefill-role group is judged on its TTFT window and a decode
    group on its ITL window: TTFT breach grows prefill at the quiet
    decode group's expense, even though no unified p95 is hot."""
    a = _scaler()
    rs = FakeGroupRS({"pre": 1, "dec": 2},
                     {("pre", "ttft"): 0.3, ("dec", "itl"): None},
                     {"pre": 4.0, "dec": 0.0}, headroom=0,
                     roles={"pre": "prefill", "dec": "decode"})
    assert a.desired_groups("s", rs) == {"pre": 2, "dec": 1}
    assert ("pre", "ttft") in rs.phase_calls
    assert ("dec", "itl") in rs.phase_calls


# ---------------------------------------------------------------------------
# scale_groups warm-handoff ordering (grow-first with headroom)
# ---------------------------------------------------------------------------


class _Tagged:
    def __init__(self, tag):
        self.tag = tag

    def handle(self, payload):
        return {"served_by": self.tag}


def _two_group_rh(nodes, replicas_a=2, replicas_b=1):
    rh = Rhapsody(ResourceDescription(nodes=nodes, cores_per_node=1),
                  policy=ExecutionPolicy(), n_workers=1)
    rs = rh.add_service(ServiceDescription(
        name="llm",
        requirements=ResourceRequirements(ranks=1, cores_per_rank=1),
        models=[ModelGroup(name="a", factory=lambda: _Tagged("a"),
                           replicas=replicas_a, borrow_limit=1),
                ModelGroup(name="b", factory=lambda: _Tagged("b"),
                           replicas=replicas_b)]))
    return rh, rs


def _record_scale_order(rs):
    order = []
    orig = rs._scale_group_locked

    def wrapped(g, n, t):
        order.append(g)
        return orig(g, n, t)

    rs._scale_group_locked = wrapped
    return order


def test_scale_groups_grow_first_when_headroom_admits_the_grow():
    """Warm handoff: one free core covers the single grow, so the
    growing group spawns (and warms) BEFORE the donor drains."""
    rh, rs = _two_group_rh(nodes=4)  # 3 claimed, 1 free core
    try:
        assert rs.group_borrow_limit("a") == 1  # ModelGroup passthrough
        assert rs.group_borrow_limit("b") is None
        order = _record_scale_order(rs)
        rs.scale_groups({"a": 1, "b": 2})
        assert order == ["b", "a"]  # grow first, then the shrink
        assert rs.group_counts() == {"a": 1, "b": 2}
        assert rs.request({"model": "b"}).result(10.0)["served_by"] == "b"
    finally:
        rh.close()


def test_scale_groups_shrink_first_in_a_full_partition():
    """Zero free cores: the grow could not be admitted before the donor
    releases its claim, so the order stays shrink-first."""
    rh, rs = _two_group_rh(nodes=3)  # 3 claimed, 0 free
    try:
        order = _record_scale_order(rs)
        rs.scale_groups({"a": 1, "b": 2})
        assert order == ["a", "b"]  # shrink frees the claim the grow uses
        assert rs.group_counts() == {"a": 1, "b": 2}
    finally:
        rh.close()


# ---------------------------------------------------------------------------
# End-to-end: disagg pair behind one ReplicaSet, phase-pure stats
# ---------------------------------------------------------------------------


def test_disagg_service_handoff_and_phase_pure_stats(dense_lm):
    """Prompts addressed to the prefill group come back decoded by the
    decode group, token-identical to a unified engine; TTFT samples land
    only in the prefill group's window and ITL only in the decode
    group's, and the handoff counters reconcile."""
    cfg, api, params = dense_lm
    engine_kw = dict(max_num_seqs=4, max_len=64, paged=True, block_size=8,
                     num_blocks=64, prefill_buckets=(16, 32))
    rh = Rhapsody(ResourceDescription(nodes=1, cores_per_node=8),
                  policy=ExecutionPolicy(routing="radix_affinity"),
                  n_workers=1)
    try:
        rs = rh.add_service(ServiceDescription(
            name="llm", replicas=2,
            requirements=ResourceRequirements(ranks=1, cores_per_rank=1),
            models=[
                llm_model_group("pre", cfg, params, role="prefill",
                                paired_with="dec", replicas=1,
                                max_num_batched_tokens=256, **engine_kw),
                llm_model_group("dec", cfg, params, role="decode",
                                replicas=1, max_num_batched_tokens=64,
                                **engine_kw),
            ]))
        assert rs.group_role("pre") == "prefill"
        rng = np.random.RandomState(0)
        prompts = [list(map(int, rng.randint(1, cfg.vocab, size=n)))
                   for n in (20, 12, 33)]
        futs = [rs.request({"prompt": p, "max_new_tokens": 6,
                            "model": "pre"}) for p in prompts]
        results = [f.result(60.0) for f in futs]
        ref = InferenceEngine(cfg, params, max_num_batched_tokens=256,
                              **engine_kw)
        ref_uids = [ref.submit(p, max_new_tokens=6) for p in prompts]
        ref_done = ref.run()
        for res, ruid in zip(results, ref_uids):
            assert res["tokens"] == ref_done[ruid].output
            assert res.get("handoff") is True
            assert res.get("role") == "decode"
            assert res["ttft_s"] is not None and res["itl_s"] is not None
        deadline = time.perf_counter() + 10
        while time.perf_counter() < deadline:
            tot = rs.handoff_totals()
            if tot["imports"] + tot["recomputes"] >= len(prompts):
                break
            time.sleep(0.05)
        tot = rs.handoff_totals()
        assert tot["exports"] == len(prompts)
        assert tot["imports"] + tot["recomputes"] == len(prompts)
        pg = rs.stats()["per_group"]
        assert pg["pre"]["role"] == "prefill"
        assert pg["pre"]["handoff_exports"] == len(prompts)
        assert pg["pre"]["ttft_p95_ms"] is not None
        assert pg["pre"]["itl_p95_ms"] is None  # never decodes
        assert pg["dec"]["itl_p95_ms"] is not None
        assert pg["dec"]["ttft_p95_ms"] is None  # phase-pure windows
        assert rs.latency_p95(group="pre", phase="ttft") is not None
        with pytest.raises(ValueError):
            rs.latency_p95(group="pre", phase="nope")
    finally:
        rh.close()
