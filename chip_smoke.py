"""Chip smoke: serve qwen1.5-0.5b at its published widths on one TPU.

    python chip_smoke.py [--seed N]

Runs in one process, from the repository root, on a machine whose JAX
backend is a TPU; anywhere else it exits non-zero before doing any work.
Phases, each of which must pass:

1. ``serve``: a 2-replica ``qwen1.5-0.5b`` service (24 layers, published
   widths, seeded random bf16 weights) is launched with warm-up through
   the same construction as ``python -m repro.launch.serve`` and answers
   16 seeded greedy requests of 16 to 100 prompt tokens, 16 new tokens
   each, through Rhapsody -> ReplicaSet -> router -> LLMServicer ->
   paged InferenceEngine -> the Pallas paged-decode kernel.  Beside it,
   in the same Rhapsody, the JAX backend runs one ``heat_stencil``
   simulation task.  No request may fail, no replica may crash or replay
   a request, no task may be retried, and the simulation's grid must be
   finite and inside the initial value range.
2. ``logits``: one engine with replica 0's weights serves two more greedy
   requests alone, and every logits row it computes (chunked prefill,
   then paged decode) is compared with the model's plain float32 forward
   pass over the same tokens (jnp attention, matmul precision
   ``highest``), within the tolerances below.

Timings printed are smoke timings, not benchmark numbers.  The last line
of standard output is one JSON object: ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
REPLICAS = 2
N_REQUESTS = 16
NEW_TOKENS = 16
PROMPT_LENS = (16, 100)
HEAT = {"n": 256, "steps": 50}

# Logits tolerances, engine vs the float32 reference.  The engine keeps
# weights, activations and the KV cache in bfloat16 (8 significant bits,
# relative rounding up to 2**-9), and the rounding compounds through 24
# residual layers; the reference sees the same bf16-valued weights but
# computes in float32.  With seeded random weights the reference logits
# have a standard deviation near 1, so errors are bounded relative to it:
#   MEAN_TOL: the mean |engine - reference| over every row and vocab
#     entry, a few bf16 roundings of a unit-scale logit;
#   MAX_TOL: the worst single entry among ~22M, a tail several times the
#     mean.
# A wrong mask, position or KV block moves whole rows by O(1) instead.
# (A CPU rehearsal at 24 layers x d_model 256 measured mean 0.013, max
# 0.083; the tolerances leave about 4x room above that.)
MEAN_TOL = 0.05
MAX_TOL = 0.5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu():
    """The first device, which must be a TPU: this script never falls
    back to another backend."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (first device: {dev.platform}, "
            f"{dev.device_kind}); nothing was run")
    return dev


def compile_clock():
    """Running totals of the backend compiles JAX performs."""
    import jax

    tot = {"seconds": 0.0, "count": 0}

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tot["seconds"] += secs
            tot["count"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return tot


def seeded_prompts(seed: int, vocab: int) -> list:
    """N_REQUESTS prompts whose lengths span PROMPT_LENS, in seeded order."""
    rng = np.random.default_rng(seed)
    lens = rng.permutation(
        np.linspace(*PROMPT_LENS, N_REQUESTS).astype(int))
    return [rng.integers(0, vocab, int(n)).tolist() for n in lens]


def phase_serve(args, cfg, seed: int, clock) -> dict:
    """Phase 1: the service answers every request, beside a simulation."""
    from repro.core import ResourceRequirements, TaskDescription, TaskKind
    from repro.launch import serve
    from repro.substrate.simulation import heat_stencil

    rh = serve.make_rhapsody(args, jax_nodes=1)
    try:
        t0 = time.perf_counter()
        replica_set, _ = serve.launch_service(rh, args, cfg)
        setup_s = time.perf_counter() - t0
        log(f"set-up {setup_s:.1f} s for {REPLICAS} replicas (weights + "
            f"warm-up), backend compiles so far {clock['count']} in "
            f"{clock['seconds']:.1f} s [smoke timing]")

        prompts = seeded_prompts(seed, cfg.vocab)
        sim = TaskDescription(
            kind=TaskKind.EXECUTABLE, fn=heat_stencil,
            kwargs=dict(HEAT, seed=seed), partition="jax",
            requirements=ResourceRequirements(ranks=1, cores_per_rank=1),
            task_type="heat_stencil")
        descs = serve.request_descs(args, prompts) + [sim]
        t0 = time.perf_counter()
        uids = rh.submit(descs)
        if not rh.wait(uids, timeout=900):
            raise SystemExit("chip_smoke: serve phase timed out")
        serve_s = time.perf_counter() - t0

        failed = serve.failed_tasks(rh, uids)
        if failed:
            raise SystemExit(f"chip_smoke: {len(failed)} task(s) failed; "
                             f"first: {failed[0][1]!r}")
        retried = [u for u in uids if rh.tasks[u].retries]
        if retried:
            raise SystemExit(f"chip_smoke: {len(retried)} task(s) retried")
        stats = replica_set.stats()
        for key in ("errors", "crashes", "replays", "dead_replicas"):
            if stats[key]:
                raise SystemExit(f"chip_smoke: replica set {key} = "
                                 f"{stats[key]}")
        results = [rh.result(u) for u in uids[:-1]]
        short = [i for i, r in enumerate(results)
                 if len(r["tokens"]) != NEW_TOKENS]
        if short:
            raise SystemExit(f"chip_smoke: requests {short} did not get "
                             f"{NEW_TOKENS} tokens")
        grid = rh.result(sim.uid)
        if not (grid.shape == (HEAT["n"], HEAT["n"])
                and np.all(np.isfinite(grid))
                and grid.min() >= 0.0 and grid.max() <= 1.0):
            raise SystemExit("chip_smoke: heat_stencil grid is not finite "
                             "in [0, 1]")

        n_prompt = sum(r["n_prompt"] for r in results)
        n_new = sum(len(r["tokens"]) for r in results)
        log(f"serve: {len(results)} requests, {n_prompt} prompt + {n_new} "
            f"generated tokens in {serve_s:.1f} s, per replica "
            f"{[p['completed'] for p in stats['per_replica']]}; "
            f"errors 0, crashes 0, replays 0, retries 0 [smoke timing]")
        log(f"heat_stencil {HEAT['n']}x{HEAT['n']}x{HEAT['steps']} on the "
            f"JAX backend: finite, in [{grid.min():.3f}, {grid.max():.3f}]")
        log("phase serve: PASSED")
        return replica_set.instances[0].servicer.engine.params
    finally:
        rh.close()


def engine_logits(engine, prompt: list, new_tokens: int):
    """Serve one greedy request on ``engine`` alone, keeping every logits
    row the engine computes: the tokens it was fed, and [len, vocab]
    logits, row i predicting the token after fed token i."""
    import jax.numpy as jnp

    rows = {}
    extend, decode = engine._paged_extend, engine._paged_decode

    def record_extend(params, store, bt, lens, tokens, wphys, woff):
        store, logits = extend(params, store, bt, lens, tokens, wphys, woff)
        pos = int(lens[0])
        # real chunk positions write to allocated blocks; padding writes
        # to the null block 0
        for t in range(int(np.count_nonzero(np.asarray(wphys)[0]))):
            rows[pos + t] = logits[0, t]
        return store, logits

    def record_decode(params, store, bt, lens, tokens, wphys, woff):
        args = (params, store, bt, lens, tokens, wphys, woff)
        if int(lens[0]) == len(prompt) and "tpu_custom_call" not in \
                decode.lower(*args).as_text():  # first decode step
            raise SystemExit("chip_smoke: the paged decode step holds no "
                             "Pallas kernel")
        store, logits = decode(*args)
        rows[int(lens[0])] = logits[0]
        return store, logits

    engine._paged_extend, engine._paged_decode = record_extend, record_decode
    try:
        uid = engine.submit(prompt, max_new_tokens=new_tokens)
        req = engine.run()[uid]
    finally:
        engine._paged_extend, engine._paged_decode = extend, decode
    fed = prompt + req.output[:-1]
    if sorted(rows) != list(range(len(fed))):
        raise SystemExit(f"chip_smoke: engine computed logits at "
                         f"{sorted(rows)}, expected 0..{len(fed) - 1}")
    return fed, jnp.stack([rows[i] for i in range(len(fed))])


def phase_logits(cfg, params, engine_kw: dict, seed: int) -> dict:
    """Phase 2: engine logits agree with the plain float32 forward."""
    import jax
    import jax.numpy as jnp

    from repro.models import get_model
    from repro.serving.client import LLMServicer

    engine = LLMServicer(cfg, params, **engine_kw).engine
    rng = np.random.default_rng(seed + 1)
    served = [engine_logits(engine, rng.integers(0, cfg.vocab, n).tolist(),
                            NEW_TOKENS) for n in PROMPT_LENS]
    del engine

    api = get_model(cfg)
    ref_cfg = cfg.scaled(attention_impl="full", param_dtype="float32",
                         compute_dtype="float32")
    ref_params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    ref_forward = jax.jit(
        lambda p, t: api.forward(p, {"tokens": t}, ref_cfg)[0][0])
    errs, agree, n_rows, ref_sd = [], 0, 0, []
    for fed, got in served:
        with jax.default_matmul_precision("highest"):
            ref = ref_forward(ref_params, jnp.asarray([fed], jnp.int32))
        err = jnp.abs(got - ref)
        errs.append((float(err.mean()), float(err.max())))
        agree += int(jnp.sum(jnp.argmax(got, -1) == jnp.argmax(ref, -1)))
        n_rows += len(fed)
        ref_sd.append(float(ref.std()))
    mean_err = float(np.mean([e[0] for e in errs]))
    max_err = max(e[1] for e in errs)
    log("paged decode step lowers to the Pallas kernel (tpu_custom_call)")
    log(f"logits: {n_rows} rows over prompts of {PROMPT_LENS} tokens + "
        f"{NEW_TOKENS - 1} decode steps each; reference std "
        f"{min(ref_sd):.3f}..{max(ref_sd):.3f}; mean |err| {mean_err:.5f} "
        f"(tol {MEAN_TOL}), max |err| {max_err:.5f} (tol {MAX_TOL}); "
        f"top-1 agreement {agree}/{n_rows}")
    if not (mean_err <= MEAN_TOL and max_err <= MAX_TOL):
        raise SystemExit("chip_smoke: engine logits disagree with the "
                         "float32 reference")
    log("phase logits: PASSED")
    return {"mean_abs_err": mean_err, "max_abs_err": max_err}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights, prompts and simulation")
    opts = ap.parse_args(argv)

    dev = require_tpu()
    import jax

    from repro.configs import get_arch_config
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import nn

    count = len(jax.devices())
    log(f"device {dev.platform} {dev.device_kind}, {count} device(s); "
        f"compile cache {enable_compile_cache()}")
    clock = compile_clock()
    args = serve.build_parser().parse_args(
        ["--arch", ARCH, "--replicas", str(REPLICAS), "--warmup",
         "--requests", str(N_REQUESTS), "--max-new-tokens", str(NEW_TOKENS),
         "--max-len", "128", "--seed", str(opts.seed)])
    cfg = get_arch_config(args.arch, smoke=args.smoke)
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}x{cfg.head_dim} heads ({cfg.n_kv_heads} kv), d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab}, {cfg.param_dtype}, "
        f"~{cfg.param_count_analytical() / 1e6:.0f}M params per replica")
    params = phase_serve(args, cfg, opts.seed, clock)
    log(f"weights in use: {nn.param_count(params) / 1e6:.1f}M params")
    phase_logits(cfg, params, serve.engine_kwargs(args), opts.seed)
    log(f"backend compiles: {clock['count']} in {clock['seconds']:.1f} s "
        f"[smoke timing]")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
