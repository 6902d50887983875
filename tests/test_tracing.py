"""The served path's spans and engine counters, on the CPU.

A toy paged ``LLMServicer`` behind ``Rhapsody`` serves a few INFERENCE
tasks under ``jax.profiler.trace``; the trace, read back with
``ProfileData``, must hold every span of the served path by name, the
engine's spans inside the servicer step that ran them, and one
``engine.dispatch`` per jitted call with its arguments.  The engine's
counters must equal what a wrapper around the jitted steps records, as
the chip benchmark's ``_record_calls`` does, also in a speculative
session, and the step programs must carry stable names.
"""
import gc
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import (ResourceDescription, Rhapsody, ServiceDescription,
                        TaskDescription, TaskKind)
from repro.models import get_model, nn
from repro.serving.client import llm_service_factory
from repro.serving.engine import InferenceEngine, SpecDecodeSession

SPANS = ("middleware.dispatch", "replica_set.route", "service.admit",
         "service.resolve", "servicer.step", "engine.admit",
         "engine.prefill", "engine.decode", "engine.collect",
         "engine.prepare", "engine.dispatch", "engine.sample", "python.gc")
ENGINE_KW = dict(paged=True, max_num_seqs=4, max_len=64,
                 prefill_buckets=(8, 16), max_num_batched_tokens=32,
                 block_size=8)
COUNTERS = ("decode_calls", "decode_kv_positions", "prefill_chunks",
            "prefill_padded_tokens")


def _toy():
    cfg = get_config("rhapsody-demo").scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=512)
    params, _ = nn.split(get_model(cfg).init(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _record(engine, calls: dict):
    """Wrap the engine's jitted steps as the chip benchmark does,
    recording each call's lengths and chunk width."""
    extend, decode = engine._paged_extend, engine._paged_decode

    def rec_extend(params, store, bt, lens, tokens, wphys, woff):
        calls["extend"].append(int(tokens.shape[1]))
        return extend(params, store, bt, lens, tokens, wphys, woff)

    def rec_decode(params, store, bt, lens, tokens, wphys, woff):
        calls["decode"].append(np.asarray(lens))
        return decode(params, store, bt, lens, tokens, wphys, woff)

    engine._paged_extend, engine._paged_decode = rec_extend, rec_decode
    return extend, decode


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Host events of a traced run, the calls recorded, the counters'
    change, and the engine with its own jitted steps."""
    cfg, params = _toy()
    rh = Rhapsody(ResourceDescription(nodes=1, cores_per_node=8),
                  n_workers=2)
    try:
        rh.add_service(ServiceDescription(
            name="llm", replicas=1,
            factory=llm_service_factory(cfg, params, **ENGINE_KW)))
        engine = rh.services.instances["llm"].servicer.engine
        calls = {"extend": [], "decode": []}
        steps = _record(engine, calls)
        rng = np.random.RandomState(3)
        # prompts from one chunk to several, in one stream
        descs = [TaskDescription(
            kind=TaskKind.INFERENCE, service="llm", task_type="inference",
            payload={"prompt": list(rng.randint(1, cfg.vocab, size=n)),
                     "max_new_tokens": 5}) for n in (5, 12, 19, 40)]
        before = {k: getattr(engine.stats, k) for k in COUNTERS}
        out = tmp_path_factory.mktemp("trace")
        with jax.profiler.trace(str(out)):
            uids = rh.submit(descs)
            assert rh.wait(uids, timeout=300)
            gc.collect()
        after = {k: getattr(engine.stats, k) for k in COUNTERS}
        assert all(rh.result(u)["tokens"] for u in uids)
    finally:
        rh.close()
    path = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0]
    host = [{"name": e.name, "start": e.start_ns,
             "end": e.start_ns + e.duration_ns, "stats": dict(e.stats)}
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events if e.name in SPANS]
    return {"host": host, "calls": calls, "engine": engine, "steps": steps,
            "delta": {k: after[k] - before[k] for k in COUNTERS}}


def test_every_span_of_the_served_path_is_in_the_trace(traced):
    names = {e["name"] for e in traced["host"]}
    assert set(SPANS) <= names


def test_engine_spans_lie_inside_a_servicer_step(traced):
    steps = [e for e in traced["host"] if e["name"] == "servicer.step"]
    engine = [e for e in traced["host"] if e["name"].startswith("engine.")]
    assert steps and engine
    for e in engine:
        assert any(s["start"] <= e["start"] and e["end"] <= s["end"]
                   for s in steps), e


def test_one_dispatch_per_jitted_call_with_its_arguments(traced):
    disp = [e for e in traced["host"] if e["name"] == "engine.dispatch"]
    ext = [e["stats"] for e in disp if e["stats"]["program"] == "extend"]
    dec = [e["stats"] for e in disp if e["stats"]["program"] == "decode"]
    calls = traced["calls"]
    assert len(ext) == len(calls["extend"]) >= 7  # 1 + 1 + 2 + 3 chunks
    assert len(dec) == len(calls["decode"]) > 0
    assert len(ext) + len(dec) == len(disp)
    assert sorted(s["bucket"] for s in ext) == sorted(calls["extend"])
    assert all({"uid", "kv"} <= set(s) for s in ext)
    assert sorted((s["batch"], s["kv"]) for s in dec) == sorted(
        (len(lens), int((lens[lens > 0] + 1).sum()))
        for lens in calls["decode"])
    keys = {"engine.admit": "admitted", "engine.prefill": "chunks",
            "engine.decode": "batch", "engine.collect": "finished",
            "middleware.dispatch": "task", "service.admit": "requests",
            "service.resolve": "results", "python.gc": "generation"}
    for e in traced["host"]:
        if e["name"] in keys:
            assert keys[e["name"]] in e["stats"], e


def test_engine_counters_equal_the_recorded_calls(traced):
    calls, delta = traced["calls"], traced["delta"]
    assert delta == {
        "decode_calls": len(calls["decode"]),
        "decode_kv_positions": sum(int((lens[lens > 0] + 1).sum())
                                   for lens in calls["decode"]),
        "prefill_chunks": len(calls["extend"]),
        "prefill_padded_tokens": sum(calls["extend"])}


def test_step_programs_carry_stable_names(traced):
    engine = traced["engine"]
    extend, decode = traced["steps"]
    mb = engine.pool.max_blocks
    z = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    ext = extend.lower(engine.params, engine.pool.cache, z(1, mb), z(1),
                       z(1, 8), z(1, 8), z(1, 8)).as_text()
    dec = decode.lower(engine.params, engine.pool.cache, z(2, mb), z(2),
                       z(2), z(2), z(2)).as_text()
    assert "jit_paged_extend" in ext and "paged_decode" not in ext
    assert "jit_paged_decode" in dec and "_unknown" not in ext + dec


@pytest.mark.parametrize("paged", [True, False])
def test_decode_counters_mean_the_same_in_either_pool(paged):
    """One 7-token prompt, five tokens out: the first comes from prefill,
    then four decode calls attend 8, 9, 10 and 11 positions."""
    cfg, params = _toy()
    eng = InferenceEngine(cfg, params, **{**ENGINE_KW, "paged": paged})
    eng.submit(list(range(1, 8)), max_new_tokens=5)
    out = eng.run()
    assert len(next(iter(out.values())).output) == 5
    assert eng.stats.decode_calls == 4
    assert eng.stats.decode_kv_positions == 8 + 9 + 10 + 11


def _spec_counters(paged: bool, calls=None) -> dict:
    """The decode counters of both engines of a speculative session whose
    one-layer draft, of other weights, falls under the acceptance floor
    after its first round, so that the target finishes with plain decode
    steps.  ``calls`` gets each engine's recorded calls."""
    cfg, params = _toy()
    dcfg = cfg.scaled(n_layers=1)
    dparams, _ = nn.split(get_model(dcfg).init(jax.random.PRNGKey(1), dcfg))
    kw = {**ENGINE_KW, "paged": paged}
    engines = {"target": InferenceEngine(cfg, params, **kw),
               "draft": InferenceEngine(dcfg, dparams, **kw)}
    if calls is not None:
        for who, eng in engines.items():
            calls[who] = {"extend": [], "decode": []}
            _record(eng, calls[who])
    sess = SpecDecodeSession(engines["target"], engines["draft"], k=2,
                             min_acceptance=0.9, probe_proposals=2)
    uid = sess.submit(list(range(1, 8)), max_new_tokens=12)
    out = sess.run()
    assert len(out[uid].output) == 12 and not sess.spec_enabled
    return {who: (eng.stats.decode_calls, eng.stats.decode_kv_positions)
            for who, eng in engines.items()}


@pytest.mark.parametrize("paged", [True, False])
def test_speculative_decode_calls_are_counted_alike_in_either_pool(paged):
    """The draft's proposals, and the target's plain steps once
    speculation has stopped, count as a wrapper on the paged decode step
    records them, in either pool."""
    calls = {}
    _spec_counters(True, calls)
    recorded = {who: (len(c["decode"]),
                      sum(int((lens[lens > 0] + 1).sum())
                          for lens in c["decode"]))
                for who, c in calls.items()}
    assert recorded["draft"][0] > 0 and recorded["target"][0] > 0
    assert _spec_counters(paged) == recorded
