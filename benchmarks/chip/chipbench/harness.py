"""One run of one cell: set-up, the load, the window, the check.

The timed path is the program's served path, as ``launch/serve.py`` uses
it: INFERENCE tasks submitted to a ``Rhapsody``, routed by the replica set
to an ``LLMServicer`` whose paged ``InferenceEngine`` runs chunked
prefill and paged decode.  The benchmark makes the weights, sizes the KV
pool, warms every program shape the cell's traffic uses, offers the load
from the seeded schedule, and reads the program's request stamps and
engine counters.  With ``trace`` it also takes a device trace of a few
seconds inside the window and reads the per-layer metrics from it.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import glob
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, Optional

import numpy as np

from . import metrics, reference, spec, stats, traffic, weights

COUNTERS = ("decode_tokens", "prefill_tokens", "prefix_cached_tokens")
BLOCK_SIZE = 16  # positions per KV block, the engine's default
HBM_UTILIZATION = 0.9  # share of the chip's memory weights, step
#                        transients and the KV pool may fill (vLLM's default)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Backend compiles JAX performs, with the time each ended."""

    def __init__(self):
        import jax

        self.ends: list = []
        self.seconds = 0.0

        def on_event(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.ends.append(time.perf_counter())
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.ends if lo <= t < hi)


@dataclasses.dataclass
class Calls:
    """What the engine's jitted steps were called with while traced:
    device arrays kept by reference (read after the trace, so recording
    adds no device sync)."""

    decode: list = dataclasses.field(default_factory=list)  # (lens,)
    extend: list = dataclasses.field(default_factory=list)  # (lens, wphys)


def _counters(engine) -> dict:
    return {k: getattr(engine.stats, k) for k in COUNTERS}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def device_check(chips: int):
    """The first device and the count; a run needs a TPU with ``chips``
    devices and never falls back to another backend."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (first device: "
                         f"{devs[0].platform} {devs[0].device_kind}); "
                         f"nothing was measured")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}; nothing was measured")
    return devs[0], len(devs)


def step_temps(cfg, api, params, eng: dict, num_blocks: int,
               sharding=None) -> dict:
    """Transient bytes (``memory_analysis().temp_size_in_bytes``) of the
    engine's extend step at its largest chunk bucket and decode step at
    its largest batch, with a pool of ``num_blocks``.  ``params`` may be
    arrays or shapes; ``sharding`` places the shapes (a described chip)."""
    import jax
    import jax.numpy as jnp

    from repro.launch import specs
    from repro.serving import engine as eng_mod

    mb = -(-int(eng["max_len"]) // BLOCK_SIZE)
    store = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        specs.cache_template(cfg, num_blocks, BLOCK_SIZE))

    def i32(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    chunk = max(chunk_buckets(eng))
    batch = decode_batches(eng)[-1]
    temp = {}
    for name, step, args in (
            ("extend", eng_mod.paged_extend_step,
             (i32((1, mb)), i32((1,)), i32((1, chunk)), i32((1, chunk)),
              i32((1, chunk)))),
            ("decode", eng_mod.paged_decode_step,
             (i32((batch, mb)), i32((batch,)), i32((batch,)), i32((batch,)),
              i32((batch,))))):
        fn = jax.jit(functools.partial(step, api, cfg), donate_argnums=(1,))
        ma = fn.lower(params, store, *args).compile().memory_analysis()
        temp[name] = int(ma.temp_size_in_bytes)
    return temp


def chunk_buckets(eng: dict) -> list:
    """The extend step's chunk sizes, as the engine derives them."""
    max_len = int(eng["max_len"])
    buckets = [b for b in eng.get("prefill_buckets", (32, 64, 128, 256, 512))
               if b <= max_len] or [max_len]
    chunk = min(max(buckets), int(eng.get("max_num_batched_tokens", 2048)))
    return [b for b in buckets if b <= chunk] or [chunk]


def decode_batches(eng: dict) -> list:
    """The decode step's padded batch sizes: powers of two up to the
    first at or above ``max_running``."""
    out = [1]
    while out[-1] < int(eng["max_running"]):
        out.append(out[-1] * 2)
    return out


def pool_blocks(temp_at, budget: int, block_bytes: int, n1: int) -> tuple:
    """The most blocks ``n`` with ``n * block_bytes + temp(n) <= budget``.
    A step's transient grows with the pool (the layer scan writes a new
    copy of the store, or two), so it is measured at ``n1`` blocks and at
    a quarter of what the budget could hold, and taken as linear."""
    n2 = max(2 * n1, int(budget // block_bytes) // 4)
    t1, t2 = temp_at(n1), temp_at(n2)
    slope = max(0.0, (t2 - t1) / (n2 - n1))
    n = int((budget - t1 + slope * n1) // (block_bytes + slope))
    return n, {"temp": {n1: t1, n2: t2}}


def kv_block_bytes(block, dims: dict) -> int:
    """Bytes of one KV block of ``BLOCK_SIZE`` positions, every layer."""
    return dims["layers"] * block.kv_bytes_per_position(dims) * BLOCK_SIZE


def _kv_pool_blocks(cell, cfg, api, params, dims, device) -> tuple:
    """Blocks the paged KV pool gets: what ``HBM_UTILIZATION`` of the
    chip's memory leaves after what is in use (the weights) and the
    largest transient of the engine's two step programs.  A cell's
    ``num_blocks`` sets the pool instead; only the tests' toy cells have
    one, since a CPU reports no memory to size from."""
    eng = cell.engine
    if "num_blocks" in eng:
        return int(eng["num_blocks"]), {}
    block_bytes = kv_block_bytes(cell.block, dims)
    ms = device.memory_stats()
    budget = HBM_UTILIZATION * ms["bytes_limit"] - ms["bytes_in_use"]
    n, info = pool_blocks(
        lambda n: max(step_temps(cfg, api, params, eng, n).values()),
        budget, block_bytes, -(-int(eng["max_len"]) // BLOCK_SIZE) + 1)
    info.update(bytes_limit=ms["bytes_limit"], in_use=ms["bytes_in_use"],
                block_bytes=block_bytes)
    return n, info


def warm(engine) -> int:
    """Run every program shape the engine will use on this traffic: each
    chunk bucket of the extend step and each power-of-two decode batch up
    to ``max_running``, with the eager reads that follow each call.  All
    writes go to the null block 0.  Returns the shapes warmed."""
    import jax.numpy as jnp

    mb = engine.pool.max_blocks
    z = functools.partial(np.zeros, dtype=np.int32)
    n = 0
    for bucket in engine._chunk_buckets:
        engine.pool.cache, logits = engine._paged_extend(
            engine.params, engine.pool.cache, jnp.asarray(z((1, mb))),
            jnp.asarray([0], jnp.int32), jnp.asarray(z((1, bucket))),
            jnp.asarray(z((1, bucket))), jnp.asarray(z((1, bucket))))
        int(jnp.argmax(logits[0, bucket - 1]))
        n += 1
    B = 1
    while True:
        engine.pool.cache, logits = engine._paged_decode(
            engine.params, engine.pool.cache, jnp.asarray(z((B, mb))),
            jnp.asarray(z(B)), jnp.asarray(z(B)), jnp.asarray(z(B)),
            jnp.asarray(z(B)))
        np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        n += 1
        if B >= engine.max_running:
            break
        B *= 2
    return n


def _record_calls(engine, box: list, fault: Optional[str]):
    """Wrap the engine's jitted steps: while ``box[0]`` holds a ``Calls``,
    record each call's arguments under a trace annotation; where
    ``fault`` names one, plant that fault in the timed path (the tests
    use this to see ``correct`` come out false)."""
    import jax
    import jax.numpy as jnp

    extend, decode = engine._paged_extend, engine._paged_decode

    def rec_extend(params, store, bt, lens, tokens, wphys, woff):
        calls = box[0]
        if calls is None:
            return extend(params, store, bt, lens, tokens, wphys, woff)
        calls.extend.append((lens, wphys))
        with jax.profiler.TraceAnnotation("bench.extend"):
            return extend(params, store, bt, lens, tokens, wphys, woff)

    def rec_decode(params, store, bt, lens, tokens, wphys, woff):
        if fault == "kv_unwritten":  # the step leaves the KV store as it was
            wphys = jnp.zeros_like(wphys)
            woff = jnp.zeros_like(woff)
        calls = box[0]
        if calls is None:
            out = decode(params, store, bt, lens, tokens, wphys, woff)
        else:
            calls.decode.append((lens,))
            with jax.profiler.TraceAnnotation("bench.decode"):
                out = decode(params, store, bt, lens, tokens, wphys, woff)
        store, logits = out
        if fault == "token_altered":  # row 0's token is replaced
            logits = logits.at[0, 7].add(1e4)
        elif fault == "half_batch":  # rows past the first half left out:
            #                          they get row 0's logits
            half = -(-logits.shape[0] // 2)
            logits = logits.at[half:].set(logits[0])
        return store, logits

    engine._paged_extend, engine._paged_decode = rec_extend, rec_decode


# the engine's host phases in one step; the traced run names the span
# of each, so that an idle gap of the device says what the host was doing
HOST_PHASES = ("_admit_paged", "_prefill_step_paged", "_decode_step_paged",
               "_collect_finished_paged")


def _span_phases(obj, names, box: list):
    """While ``box[0]`` holds a ``Calls``, run each of ``obj``'s methods
    ``names`` under a trace annotation ``bench.<name>``."""
    import jax

    def wrap(name, fn):
        span = "bench." + name.strip("_")

        def spanned(*a, **kw):
            if box[0] is None:
                return fn(*a, **kw)
            with jax.profiler.TraceAnnotation(span):
                return fn(*a, **kw)
        return spanned

    for name in names:
        setattr(obj, name, wrap(name, getattr(obj, name)))


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             fault: Optional[str] = None, controls: tuple = (),
             chips: int = 1, wanted: Optional[dict] = None,
             keep_trace: Optional[str] = None,
             on_window: Optional[Callable] = None) -> dict:
    """One run; returns the result line (a dict) with the compared
    numbers under ``check``.  ``wanted``: per-layer metric name -> unit,
    read in a traced run."""
    import jax

    if require_chip:
        dev, count = device_check(chips)
    else:
        dev, count = jax.devices()[0], len(jax.devices())
    log(f"device {dev.platform} {dev.device_kind}, {count} device(s)")
    clock = CompileClock()

    from repro.core import ServiceDescription, TaskDescription, TaskKind
    from repro.core.task import TaskState
    from repro.launch import serve
    from repro.models import get_model, nn
    from repro.serving.client import llm_service_factory

    block = cell.block
    cfg = block.model_config(cell.config)
    dims = block.dims(cell.config)
    api = get_model(cfg)
    shapes = jax.eval_shape(lambda k: nn.split(api.init(k, cfg))[0],
                            jax.random.PRNGKey(0))
    params = weights.program_params(shapes, block, dims, seed)
    jax.block_until_ready(params)
    num_blocks, sizing = _kv_pool_blocks(cell, cfg, api, params, dims, dev)
    log(f"KV pool: {num_blocks} blocks of {BLOCK_SIZE} positions; sizing "
        f"{sizing}")

    eng = cell.engine
    engine_kw = dict(paged=True, max_len=int(eng["max_len"]),
                     max_running=int(eng["max_running"]),
                     block_size=BLOCK_SIZE,
                     num_blocks=num_blocks, seed=seed)
    for k in ("prefill_buckets", "max_num_batched_tokens"):
        if k in eng:
            engine_kw[k] = eng[k]
    args = serve.build_parser().parse_args(
        ["--replicas", "1", "--seed", str(seed)])
    rh = serve.make_rhapsody(args)
    try:
        replica_set = rh.add_service(ServiceDescription(
            name="llm", replicas=1,
            factory=llm_service_factory(cfg, params, **engine_kw),
            ready_timeout=serve.READY_TIMEOUT_S))
        replica = replica_set.instances[0]
        servicer = replica.servicer
        engine = servicer.engine
        del params
        n_warm = warm(engine)

        # the engine requests behind each benchmark request, by index
        engine_reqs: dict = {}
        submit = servicer.submit

        def recording_submit(payload, **kw):
            uid = submit(payload, **kw)
            engine_reqs[payload["bench_idx"]] = servicer._find_request(uid)
            return uid

        servicer.submit = recording_submit
        box = [None]  # a Calls while the trace runs
        if trace or fault:
            _record_calls(engine, box, fault)
            _span_phases(engine, HOST_PHASES, box)
            _span_phases(servicer, ("step",), box)
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.3f} s: weights, KV pool, service, "
            f"{n_warm} warmed shapes; {len(clock.ends)} backend compiles "
            f"in {clock.seconds:.1f} s")

        load = _Load(rh, cell, seed, seconds, engine, dims,
                     TaskDescription, TaskKind, TaskState)
        tr = _Tracer(engine, box, cell.run, keep_trace) if trace else None
        load.run(tr)
        t_end = load.t_end
        ws, we = load.window
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        recs = load.records(engine_reqs)
        log(f"device memory peak_bytes_in_use {peak}")
        log(f"backend compiles inside the window: "
            f"{clock.between(ws, we)}; during load and drain: "
            f"{clock.between(load.t0, t_end)}")
        if on_window is not None:
            on_window(load, recs)
    finally:
        rh.close()
    # the program's state goes before the reference runs
    replica.join(timeout=60)
    if replica.is_alive():
        raise RuntimeError("the replica did not stop within 60 s")
    for x in jax.tree.leaves((engine.pool.cache, engine.params)):
        x.delete()
    del engine, servicer, replica, replica_set, rh
    load.rh = load.engine = None
    gc.collect()
    log(f"device memory in use once the program's state is freed: "
        f"{(dev.memory_stats() or {}).get('bytes_in_use', 0)}")

    in_win = [r for r in recs if ws <= r["due"] < we]
    attempted = len(in_win)
    # a closed loop stops at the window's close, its requests in flight
    failed = sum(1 for r in in_win if not r["ok"]
                 and (r["terminal"] or not load.schedule.closed))
    lag = [1e3 * (r["submit"] - r["due"]) for r in in_win]
    log(f"requests due in the window: attempted {attempted}, failed "
        f"{failed}; generator lag ms p50 {np.median(lag):.3f} max "
        f"{max(lag):.3f}")

    result = {"correct": None, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": {
                  "platform": dev.platform, "kind": dev.device_kind,
                  "count": count, "memory_peak_bytes": int(peak)}}
    ctx = metrics.Context(cell=cell, block=block, dims=dims, records=recs,
                          window=(ws, we), load=load, device=dev)
    if trace:
        tr.reduce(ctx, result, wanted or {})
    else:
        result["metrics"] = end_to_end(ctx, setup_s, t_end)
    check = correctness(cell, dims, seed, recs, (ws, we), load.schedule,
                        controls)
    result["correct"] = check["correct"]
    result["check"] = check["numbers"]
    if controls:
        result["control_correct"] = check["controls"]
    return result


def end_to_end(ctx, setup_s: float, t_end: float) -> dict:
    ws, we = ctx.window
    recs = ctx.records
    out = {"setup_s": {"value": setup_s, "unit": "s"}}
    firsts = [r["engine_first"] for r in recs]
    toks = stats.output_tokens(
        ctx.load.snap_we["decode_tokens"] - ctx.load.snap_ws["decode_tokens"],
        firsts, (ctx.load.t_ws, ctx.load.t_we))
    out["output_tokens_per_s"] = {
        "value": toks / (ctx.load.t_we - ctx.load.t_ws), "unit": "tokens/s"}
    if not ctx.load.schedule.closed:
        ttft = stats.ttft_ms(recs, (ws, we), t_end)
        tpot = stats.tpot_ms(recs, (ws, we))
        for q in (50, 95):
            out[f"ttft_p{q}_ms"] = {"value": stats.percentile(ttft, q),
                                    "unit": "ms"}
            out[f"tpot_p{q}_ms"] = {"value": stats.percentile(tpot, q),
                                    "unit": "ms"}
    return out


def gap_numbers(gaps: list) -> dict:
    """The compared numbers of one set of served tokens: the widest and
    the mean gap by which a token's reference logit lies below the
    reference's best."""
    flat = np.concatenate(gaps)
    return {"max_logit_gap": float(flat.max()),
            "mean_logit_gap": float(flat.mean())}


def correctness(cell, dims, seed, recs, window, schedule,
                controls: tuple = ()) -> dict:
    """Compare the served tokens of a seeded sample of the window's
    finished requests, the longest among them, with the plain reference:
    the widest and the mean gap by which a served token's reference logit
    lies below the reference's best, each against its limit in the
    cell's ``check``.  Each of ``controls`` (a lower precision) puts the
    reference in that precision in the program's place on the same
    prompts and tokens, reads the same numbers for the token it puts
    first at each position, and is judged by the same limits: it has to
    come out not correct."""
    ws, we = window
    if schedule.closed:  # every request the window finished
        done = [r for r in recs if r["ok"] and r["finish"] >= ws]
    else:  # every request due in the window, followed after it
        done = [r for r in recs if ws <= r["due"] < we and r["ok"]]
    limits = {k: float(cell.check[k]) for k in ("max_logit_gap",
                                                 "mean_logit_gap")}
    if not done:
        log("no finished request to compare")
        return {"correct": False, "numbers": {
            k: {"value": None, "limit": v} for k, v in limits.items()},
            "controls": {m: None for m in controls}}
    n = min(int(cell.check["sample"]), len(done))
    longest = max(done, key=lambda r: r["n_prompt"] + r["n_out"])
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed % 2**63, 3])
    pick = [longest] + [rest[i] for i in
                        sorted(rng.choice(len(rest), n - 1, replace=False))]
    seqs, rows, served = [], [], []
    for r in pick:
        prompt = schedule.prompt(schedule.requests[r["idx"]])
        seqs.append(prompt + r["tokens"][:-1])
        rows.append(reference.served_rows(len(prompt), len(r["tokens"])))
        served.append(r["tokens"])
    t0 = time.perf_counter()
    picks = {}
    for mode in controls:
        ctl = reference.Reference(cell.block, dims, seed,
                                  quantize_mode=mode)
        picks[mode] = [reference.top_tokens(x, len(r))
                       for x, r in zip(ctl.logits(seqs, rows), rows)]
        del ctl
    t1 = time.perf_counter()
    ref = reference.Reference(cell.block, dims, seed)
    gaps = []
    ctl_gaps = {m: [] for m in controls}
    for i, x in enumerate(ref.logits(seqs, rows)):
        gaps.append(reference.logit_gaps(x, served[i]))
        for m in controls:
            ctl_gaps[m].append(reference.logit_gaps(x, picks[m][i]))
    ntok = sum(len(g) for g in gaps)
    top1 = sum(int((g == 0).sum()) for g in gaps)
    log(f"check: {len(pick)} requests, {ntok} served tokens "
        f"({sum(len(s) for s in seqs)} positions); served token is the "
        f"reference's best at {top1}/{ntok}; the reference took "
        f"{time.perf_counter() - t1:.1f} s"
        + (f", the controls {t1 - t0:.1f} s" if controls else ""))
    def judged(got: dict) -> bool:
        return all(got[k] <= limits[k] for k in limits)

    got = gap_numbers(gaps)
    numbers = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    verdicts = {}
    for m in controls:
        ctl = gap_numbers(ctl_gaps[m])
        verdicts[m] = judged(ctl)
        for k, v in ctl.items():
            numbers[f"control_{m}_{k}"] = {"value": v, "limit": limits[k]}
    return {"correct": judged(got), "numbers": numbers,
            "controls": verdicts}


class _Load:
    """Offers the seeded schedule to the service and keeps the stamps."""

    def __init__(self, rh, cell, seed, seconds, engine, dims,
                 TaskDescription, TaskKind, TaskState):
        self.rh, self.cell, self.engine = rh, cell, engine
        self.TD, self.TK, self.TS = TaskDescription, TaskKind, TaskState
        run = cell.run
        self.ramp = float(run["ramp_s"])
        self.seconds = float(seconds)
        self.drain_cap = float(run.get("drain_cap_s", 0.0))
        horizon = self.ramp + self.seconds + self.drain_cap
        self.schedule = traffic.schedule(cell.traffic, seed, dims["vocab"],
                                         window=(self.ramp, self.seconds),
                                         horizon_s=horizon,
                                         min_requests=1024)
        self.sent: dict = {}  # idx -> (due, submit time, task uid)

    def _submit(self, req, due: float):
        prompt = self.schedule.prompt(req)
        desc = self.TD(kind=self.TK.INFERENCE, service="llm",
                       payload={"prompt": prompt,
                                "max_new_tokens": req.out_len,
                                "bench_idx": req.idx},
                       task_type="inference")
        now = time.perf_counter()
        uid = self.rh.submit(desc)[0]
        self.sent[req.idx] = (due, now, uid)

    def _done(self, idx) -> bool:
        return self.rh.tasks[self.sent[idx][2]].state.terminal

    def run(self, tracer=None):
        sch = self.schedule
        self.t0 = t0 = time.perf_counter()
        self.t_ws_nominal = ws = t0 + self.ramp
        we = ws + self.seconds
        self.window = (ws, we)
        hard_end = we + self.drain_cap
        reqs = iter(sch.requests)
        nxt = next(reqs)
        clients: list = []  # closed loop: [idx in flight or None, release]
        if sch.closed:
            clients = [[None, t0] for _ in range(sch.clients)]
        self.snap_ws = self.snap_we = None
        while True:
            now = time.perf_counter()
            if self.snap_ws is None and now >= ws:
                self.t_ws, self.snap_ws = now, _counters(self.engine)
            if self.snap_we is None and now >= we:
                self.t_we, self.snap_we = now, _counters(self.engine)
            if tracer is not None:
                tracer.tick(now, ws)
            if now >= hard_end:
                break
            # an open loop follows the requests due in the window to
            # their end; a closed loop's window has no tails to follow
            if self.snap_we is not None and (sch.closed or all(
                    self._done(i) for i, (due, _, _) in self.sent.items()
                    if ws <= due < we)):
                break
            wake = now + 0.002
            if sch.closed:
                for c in clients:
                    if c[0] is not None and self._done(c[0]):
                        task = self.rh.tasks[self.sent[c[0]][2]]
                        c[0], c[1] = None, task.finished_at
                    if c[0] is None:
                        if c[1] <= now:
                            self._submit(nxt, c[1])
                            c[0] = nxt.idx
                            nxt = next(reqs)
                        else:
                            wake = min(wake, c[1])
            else:
                while t0 + nxt.offset_s <= now:
                    self._submit(nxt, t0 + nxt.offset_s)
                    nxt = next(reqs)
                wake = min(wake, t0 + nxt.offset_s)
            time.sleep(max(0.0, wake - time.perf_counter()))
        self.t_end = time.perf_counter()
        if tracer is not None:
            tracer.stop()

    def records(self, engine_reqs: dict) -> list:
        """One record per request sent: due, submit, client-side first
        token and finish, the engine's own stamps, sizes and tokens."""
        out = []
        for idx, (due, sub, uid) in self.sent.items():
            task = self.rh.tasks[uid]
            er = engine_reqs.get(idx)
            ok = task.state == self.TS.DONE
            terminal = task.state.terminal
            rec = {"idx": idx, "due": due, "submit": sub, "ok": ok,
                   "terminal": terminal,
                   "n_prompt": self.schedule.requests[idx].prompt_len,
                   "n_out": 0, "first": None, "finish": None,
                   "engine_submit": er.submitted_at if er else None,
                   "engine_first": er.first_token_at if er else None,
                   "engine_ttft": None, "tokens": None}
            if ok:
                res = task.result
                rec["tokens"] = list(res["tokens"])
                rec["n_out"] = len(res["tokens"])
                rec["finish"] = task.finished_at
                rec["first"] = task.finished_at - (res["latency_s"]
                                                   - res["ttft_s"])
                rec["engine_ttft"] = res["ttft_s"]
            out.append(rec)
        return out


class _Tracer:
    """The device trace of a few seconds inside the window, and the
    per-layer metrics read from it."""

    def __init__(self, engine, box: list, run: dict, keep: Optional[str]):
        self.engine, self.box, self.run, self.keep = engine, box, run, keep
        self.calls = Calls()
        self.state = "wait"
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")

    def tick(self, now, ws):
        """Called by the load loop: starts and stops the trace on a
        thread of its own, so that the load is never late for it."""
        run = self.run
        if self.state == "wait" and now >= ws + float(run["trace_offset_s"]):
            self.state = "starting"
            self._in_thread(self._start)
        elif self.state == "on" and now >= self.t0 + float(run["trace_s"]):
            self.state = "stopping"
            self._in_thread(self._stop)

    def _in_thread(self, fn):
        self.thread = threading.Thread(target=fn, name="chipbench-trace")
        self.thread.start()

    def _start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # a span per Python call would slow
        #                               the host loop under trace
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()
        self.snap0 = _counters(self.engine)
        self.box[0] = self.calls
        self.state = "on"

    def _stop(self):
        import jax

        self.box[0] = None
        self.t1 = time.perf_counter()
        self.snap1 = _counters(self.engine)
        jax.profiler.stop_trace()
        self.state = "done"

    def stop(self):
        """At the load's end: finish the trace if it is still on."""
        if self.state in ("starting", "stopping"):
            self.thread.join()
        if self.state == "on":
            self._stop()

    def reduce(self, ctx, result, wanted: dict):
        from . import trace as trace_mod

        if self.state != "done":
            raise RuntimeError("the trace never started: the window is "
                               "shorter than trace_offset_s")
        path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
        dev = trace_mod.reduce(path[0], self.t1 - self.t0)
        if self.keep:
            shutil.copy(path[0], self.keep)
        shutil.rmtree(self.dir, ignore_errors=True)
        ctx.trace = dev
        ctx.traced = (self.t0, self.t1)
        ctx.counters = _delta(self.snap0, self.snap1)
        ctx.calls = self.calls
        result["metrics"] = metrics.read_all(ctx, wanted)
        result["device"]["busy_s"] = dev.busy_s
        result["device"]["window_s"] = dev.window_s
        result["breakdown"] = dev.breakdown()
