"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

``--arch`` serves that model at its published widths and depth (seeded,
random weights); ``--smoke`` serves its reduced smoke config instead.
The process exits non-zero if any request failed.

Brings up ONE replicated inference service (``--replicas N``) through the
RHAPSODY middleware and drives a synthetic request stream as INFERENCE
tasks, so every request is routed to a replica by the policy router
(``--routing``: random | round_robin | balanced | least_loaded |
prefix_affinity).  With ``prefix_affinity``, requests sharing a prompt
prefix stick to one replica (``--affinity-prefix-len`` tokens hashed into
the session key, spilling to the least-loaded replica past
``--affinity-spill-factor``), and the engines skip prefill for resident
prefixes; per-replica ``prefix_hits``/``prefix_misses`` are reported.
Replicas claim cores from the middleware's resource ledger
(admission-controlled), ``--warmup`` primes each replica before it becomes
routable, and ``--autoscale`` turns on the pluggable autoscaler
(``--autoscaler queue_depth|latency_slo|weighted_capacity``,
``--slo-p95-ms`` target) bounded by the partition's free capacity.

``--models NAME:WEIGHT [NAME:WEIGHT ...]`` launches a MULTI-MODEL set:
several model groups behind the one service name, each replica tagged with
its group, requests addressed by tagging the payload (``{"model": ...}``)
so the router only considers that group's replicas.  ``--replicas`` then
names the TOTAL, split across groups proportionally to weight; a two-model
launch is just::

    python -m repro.launch.serve --smoke --models chat:2 draft:1 \
        --replicas 3 --requests 24

``--disagg`` launches DISAGGREGATED serving instead: ``--replicas`` is
split into a prefill pool (large chunked-prefill budget, no decode
interleave; ``--prefill-replicas`` overrides the half-split) and a decode
pool behind one service name.  Every request is addressed to the prefill
group; on first token the sequence's paged KV blocks are exported and
imported into a decode replica (recompute fallback when its pool is
full), and per-phase TTFT/ITL p95s are reported per group.

Reports aggregate + per-replica (and per-group) throughput, latency, the
engines' mean decode batch, attended KV per decoded token, mean prefill
chunk and its padded share — the runnable end of the inference-at-scale
path the dry-run lowers at production shapes.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.backends.jaxrt import JaxBackend
from repro.backends.local import PoolBackend
from repro.configs import get_arch_config, list_archs
from repro.core import (ExecutionPolicy, ResourceDescription, Rhapsody,
                        ServiceDescription, TaskDescription, TaskKind,
                        TaskState)
from repro.core.router import ROUTERS
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.client import llm_model_group, llm_service_factory

# a published-width replica builds its weights and compiles its first
# programs before it is ready; a factory that fails is reported at once
READY_TIMEOUT_S = 600.0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rhapsody-demo",
                    choices=list_archs() + ["rhapsody-demo"],
                    help="model at its published widths and depth")
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config instead")
    ap.add_argument("--replicas", "--services", dest="replicas", type=int,
                    default=2, help="service replica count (scaling unit)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the random weights and the request stream")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-num-seqs", type=int, default=4)
    ap.add_argument("--max-num-batched-tokens", type=int, default=512)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--paged", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="block-paged KV cache: admission by free-block "
                         "count, chunked prefill, copy-on-write prefix "
                         "sharing, direct paged decode.  Default: auto "
                         "(ON for dense/moe archs, slot pool otherwise); "
                         "--no-paged forces the slot pool")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV positions per physical block (--paged)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="physical KV blocks; default matches the slot "
                         "pool's memory budget (--paged)")
    ap.add_argument("--routing", default="balanced",
                    choices=tuple(ROUTERS))
    ap.add_argument("--affinity-prefix-len", type=int, default=32,
                    help="prompt tokens hashed into the sticky-session key "
                         "(prefix_affinity routing)")
    ap.add_argument("--affinity-spill-factor", type=float, default=2.0,
                    help="sticky replica sheds load when its queue exceeds "
                         "factor * (min depth + 1); <=0 never spills")
    ap.add_argument("--warmup", action="store_true",
                    help="prime each replica (compile + a token of decode) "
                         "before the router may route to it")
    ap.add_argument("--autoscale", action="store_true",
                    help="let the autoscaler grow/shrink the replica set "
                         "within the partition's free capacity")
    ap.add_argument("--autoscaler", default="queue_depth",
                    choices=("queue_depth", "latency_slo",
                             "weighted_capacity"))
    ap.add_argument("--slo-p95-ms", type=float, default=250.0,
                    help="latency_slo autoscaler: p95 end-to-end target")
    ap.add_argument("--models", nargs="*", metavar="NAME:WEIGHT",
                    help="serve SEVERAL model groups from one replica set "
                         "(e.g. --models chat:2 draft:1); --replicas "
                         "becomes the total, split by weight")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: split --replicas into a "
                         "prefill pool (large chunked-prefill budget, no "
                         "decode interleave) and a decode pool; sequences "
                         "migrate on first token via a paged-KV handoff. "
                         "Requires the paged cache; incompatible with "
                         "--models")
    ap.add_argument("--prefill-replicas", type=int, default=None,
                    help="--disagg: prefill pool size (default: half of "
                         "--replicas, at least 1)")
    return ap


def make_rhapsody(args, *, jax_nodes: int = 0) -> Rhapsody:
    """The middleware the service runs in: one node per replica.  With
    ``jax_nodes``, that many more nodes form a ``"jax"`` partition whose
    tasks run on the JAX backend, beside the service."""
    backends = partitions = None
    if jax_nodes:
        backends = {"pool": PoolBackend(n_workers=2), "jax": JaxBackend()}
        partitions = {"pool": args.replicas, "jax": jax_nodes}
    return Rhapsody(ResourceDescription(nodes=args.replicas + jax_nodes,
                                        cores_per_node=16),
                    policy=ExecutionPolicy(
                        routing=args.routing,
                        affinity_prefix_len=args.affinity_prefix_len,
                        affinity_spill_factor=args.affinity_spill_factor,
                        warmup=args.warmup,
                        autoscale=args.autoscale,
                        autoscaler=args.autoscaler,
                        autoscale_max_replicas=max(4, args.replicas),
                        slo_p95_ms=args.slo_p95_ms),
                    backends=backends, partitions=partitions, n_workers=2)


def engine_kwargs(args) -> dict:
    """The engine settings every replica of the service is built with."""
    return dict(max_num_seqs=args.max_num_seqs,
                max_num_batched_tokens=args.max_num_batched_tokens,
                max_len=args.max_len, prefill_buckets=(16, 32, 64),
                # None = auto: LLMServicer resolves to paged for
                # dense/moe, slot pool for state-carrying families
                paged=args.paged, block_size=args.block_size,
                num_blocks=args.num_blocks, seed=args.seed)


def launch_service(rh: Rhapsody, args, cfg):
    """Launch the ``llm`` service in ``rh`` as ``args`` describe it.
    Returns (replica set, model group names; empty unless --models)."""
    engine_kw = engine_kwargs(args)
    model_names: list = []
    if args.disagg:
        n_pre = args.prefill_replicas or max(1, args.replicas // 2)
        n_dec = max(1, args.replicas - n_pre)
        disagg_kw = dict(engine_kw, paged=True)
        groups = [
            llm_model_group(
                "prefill", cfg, role="prefill", paired_with="decode",
                replicas=n_pre, slo_p95_ms=args.slo_p95_ms,
                **dict(disagg_kw,
                       # prefill replicas never interleave decode:
                       # run the whole prompt in as few chunks as
                       # possible
                       max_num_batched_tokens=max(
                           args.max_num_batched_tokens, args.max_len))),
            llm_model_group(
                "decode", cfg, role="decode", replicas=n_dec,
                slo_p95_ms=args.slo_p95_ms, **disagg_kw),
        ]
        replica_set = rh.add_service(ServiceDescription(
            name="llm", replicas=args.replicas, models=groups,
            ready_timeout=READY_TIMEOUT_S))
        print(f"[serve] {cfg.name} disaggregated "
              f"{replica_set.group_counts()} ready:",
              rh.services.list())
    elif args.models:
        groups = []
        for spec in args.models:
            name, _, w = spec.partition(":")
            groups.append(llm_model_group(
                name, cfg, weight=float(w) if w else 1.0, **engine_kw))
        model_names = [g.name for g in groups]
        replica_set = rh.add_service(ServiceDescription(
            name="llm", replicas=args.replicas, models=groups,
            ready_timeout=READY_TIMEOUT_S))
        print(f"[serve] {cfg.name} x {args.replicas} replicas "
              f"across groups {replica_set.group_counts()} ready:",
              rh.services.list())
    else:
        replica_set = rh.add_service(ServiceDescription(
            name="llm", replicas=args.replicas,
            factory=llm_service_factory(cfg, **engine_kw),
            ready_timeout=READY_TIMEOUT_S))
        print(f"[serve] {cfg.name} x {args.replicas} replicas ready:",
              rh.services.list())
    return replica_set, model_names


def request_descs(args, prompts, model_names=()) -> list:
    """One INFERENCE task per prompt, addressed to the ``llm`` service."""

    def payload(i, p):
        out = {"prompt": p, "max_new_tokens": args.max_new_tokens}
        if args.disagg:  # clients always address the prefill pool;
            #              the set migrates each sequence to a decode
            #              replica on first token
            out["model"] = "prefill"
        elif model_names:  # address models round-robin across stream
            out["model"] = model_names[i % len(model_names)]
        return out

    return [TaskDescription(kind=TaskKind.INFERENCE, service="llm",
                            payload=payload(i, p), task_type="inference")
            for i, p in enumerate(prompts)]


def failed_tasks(rh: Rhapsody, uids) -> list:
    """(uid, error) of every task among ``uids`` that failed."""
    return [(u, rh.tasks[u].error) for u in uids
            if rh.state(u) == TaskState.FAILED]


def main(argv=None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.disagg and args.models:
        ap.error("--disagg and --models are mutually exclusive")
    if args.disagg and args.paged is False:
        ap.error("--disagg requires the paged KV cache (drop --no-paged)")

    enable_compile_cache()
    cfg = get_arch_config(args.arch, smoke=args.smoke)
    rh = make_rhapsody(args)
    try:
        replica_set, model_names = launch_service(rh, args, cfg)
        rng = np.random.RandomState(args.seed)
        lens = np.clip(np.exp(rng.normal(3.0, 0.7, args.requests)), 4,
                       args.max_len - args.max_new_tokens - 1).astype(int)
        prompts = [list(rng.randint(0, cfg.vocab, size=int(L)))
                   for L in lens]
        t0 = time.perf_counter()
        uids = rh.submit(request_descs(args, prompts, model_names))
        if not rh.wait(uids, timeout=1200):
            raise TimeoutError("inference stream timed out")
        dt = time.perf_counter() - t0
        stats = replica_set.stats()
        failed = failed_tasks(rh, uids)
        print(f"[serve] replica crashes {stats['crashes']}, replayed "
              f"requests {stats['replays']}")
        if failed:
            print(f"[serve] {len(failed)}/{len(uids)} requests FAILED; "
                  f"first: {failed[0][1]!r}")
            return 1
        results = [rh.result(u) for u in uids]
        tokens = sum(len(r["tokens"]) + r["n_prompt"] for r in results)
        lat = sorted(r["latency_s"] for r in results)
        engines = [inst.servicer.stats for inst in replica_set.instances]

        def ratio(num, den, fmt, of=lambda x: x):
            n, d = (sum(getattr(st, k) for st in engines) for k in (num, den))
            return format(of(n / d), fmt) if d else "n/a"

        batch = ratio("decode_tokens", "decode_calls", ".1f")
        kv = ratio("decode_kv_positions", "decode_tokens", ".0f")
        # paged engines count real prefill tokens and the padded buckets
        # that ran; slot-pool engines count no chunks
        chunk = ratio("prefill_padded_tokens", "prefill_chunks", ".0f")
        pad = ratio("prefill_tokens", "prefill_padded_tokens", ".2f",
                    lambda real: 1 - real)
        print(f"[serve] {len(results)} requests, {dt:.2f}s, "
              f"{tokens / dt:.0f} tok/s, routing={args.routing}")
        print(f"[serve] latency p50 {lat[len(lat) // 2]:.2f}s "
              f"p95 {lat[int(len(lat) * 0.95)]:.2f}s; "
              f"mean decode batch {batch}, attended KV per decoded token "
              f"{kv}; mean prefill chunk {chunk} tokens, padded share "
              f"{pad}")
        print("[serve] per-replica requests:",
              [p["requests"] for p in stats["per_replica"]])
        btel = {g: s.get("block_telemetry")
                for g, s in stats["per_group"].items()}
        if any(t is not None for t in btel.values()):
            print("[serve] paged-block telemetry per group:",
                  {g: {"free": t["free_blocks"], "total": t["total_blocks"],
                       "shared": t["shared_blocks"],
                       "cow": t["cow_copies"]}
                   for g, t in btel.items() if t is not None})
        if args.disagg:
            handed = sum(1 for r in results if r.get("handoff"))
            print(f"[serve] disagg: {handed}/{len(results)} sequences "
                  f"migrated prefill->decode; handoff totals:",
                  replica_set.handoff_totals())
            print("[serve] per-phase groups:",
                  {g: {"replicas": s["replicas"],
                       "role": s["role"],
                       "requests": s["requests"],
                       "ttft_p95_ms": s["ttft_p95_ms"]
                       and round(s["ttft_p95_ms"], 1),
                       "itl_p95_ms": s["itl_p95_ms"]
                       and round(s["itl_p95_ms"], 1)}
                   for g, s in stats["per_group"].items()})
        if model_names:
            print("[serve] per-model groups:",
                  {g: {"replicas": s["replicas"],
                       "requests": s["requests"],
                       "cores": s["cores"],
                       "p95_ms": s["latency_p95_ms"]
                       and round(s["latency_p95_ms"], 1)}
                   for g, s in stats["per_group"].items()})
        ledger = rh.utilization()
        print("[serve] shared ledger:",
              {k: {"cores": round(v["cores"], 2),
                   "service_cores": v["service_cores"],
                   "service_replicas": v["service_replicas"]}
               for k, v in ledger.items()},
              f"admission_denied={stats['admission_denied']}")
        if args.routing == "prefix_affinity":
            hits, misses = stats["prefix_hits"], stats["prefix_misses"]
            reuse = [inst.servicer.stats.prefix_cached_tokens
                     for inst in replica_set.instances]
            print(f"[serve] prefix-affinity: {hits} hits / {misses} misses "
                  f"(rate {hits / max(1, hits + misses):.2f}); "
                  f"engine prefill tokens skipped per replica: {reuse}")
        return 0
    finally:
        rh.close()


if __name__ == "__main__":
    sys.exit(main())
