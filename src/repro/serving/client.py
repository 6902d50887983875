"""LLM servicer + client helpers: the glue between the middleware service
abstraction and the continuous-batching engine (Figs. 1-2: AI workers)."""
from __future__ import annotations

import time
from typing import Any, Optional

from repro.core.service import ModelGroup
from repro.core.tracing import span
from repro.models.config import ModelConfig
from .engine import (InferenceEngine, SpecDecodeSession,
                     make_engine_from_scratch)
from .qos import WFQScheduler


def _resolve_paged(cfg: ModelConfig, engine_kw: dict) -> dict:
    """Paged-by-default policy for replicas: dense/moe engines get the
    block-paged pool unless the caller opts out (``paged=False``);
    state-carrying and prefix-offset families (ssm/hybrid/vlm/encdec)
    keep the slot pool.  ``paged=None`` (or absent) means "auto"."""
    kw = dict(engine_kw)
    if kw.get("paged") is None:
        kw["paged"] = cfg.family in ("dense", "moe")
    if not kw["paged"]:
        # the slot-pool engine does not take paged-only tuning knobs
        for k in ("block_size", "num_blocks", "prefill_chunk",
                  "max_running", "paged_decode_mode"):
            kw.pop(k, None)
    return kw


def _resolve_draft_engine(spec, *, seed: int = 0) -> InferenceEngine:
    """``draft_group`` resolution: accept the co-located draft in any of
    the shapes a launcher naturally holds — an ``InferenceEngine``, a
    built ``LLMServicer``, a ``ModelGroup`` (whose factory builds one; the
    ``--multi-model`` path hands exactly this), or a bare ``ModelConfig``
    (a fresh engine with auto-resolved pool)."""
    if isinstance(spec, InferenceEngine):
        return spec
    if isinstance(spec, LLMServicer):
        return spec.engine
    if isinstance(spec, ModelGroup):
        if spec.factory is None:
            raise ValueError(
                f"draft_group {spec.name!r} has no factory to build a "
                f"draft servicer from")
        servicer = spec.factory()
        engine = getattr(servicer, "engine", None)
        if engine is None:
            raise TypeError(
                f"draft_group {spec.name!r} factory built "
                f"{type(servicer).__name__}, which exposes no .engine")
        return engine
    if isinstance(spec, ModelConfig):
        return make_engine_from_scratch(spec, seed=seed,
                                        **_resolve_paged(spec, {}))
    raise TypeError(f"cannot resolve a draft engine from {type(spec)}")


class LLMServicer:
    """Servicer protocol (submit/step) around an InferenceEngine.

    Request payload: {"prompt": [ids...], "max_new_tokens": int,
                      "temperature": float}.
    Result: {"tokens": [...], "n_prompt": int, "ttft_s": float,
             "latency_s": float}.

    Replicas default to the block-paged engine for dense/moe configs
    (``paged=None`` auto-resolves via ``_resolve_paged``); pass
    ``paged=False`` to force the slot pool.

    ``draft_group`` arms cross-group speculative decoding: a co-located
    draft engine (resolved from a ``ModelGroup``/``ModelConfig``/engine,
    see ``_resolve_draft_engine``) proposes ``spec_k`` tokens per round
    and this replica's target engine verifies them in one extend forward
    (``SpecDecodeSession``).  Greedy output stays token-for-token
    identical to target-only decode; sampled requests are refused by the
    session.  ``spec_stats()`` exposes the proposed/accepted counters the
    replica set aggregates per group for the autoscaler.

    ``phase`` selects the replica's disaggregated-serving role:

    * ``"serve"`` (default) — unified prefill+decode, as before.
    * ``"prefill"`` — the replica ONLY chunk-prefills (no decode
      interleave: ``engine.step_prefill_only``); the moment a sequence's
      first token is out it is exported (``engine.export_sequence``) and
      the step result carries the serialized KV under ``"handoff_export"``
      for the replica set to re-dispatch to the paired decode group.
    * ``"decode"`` — ``submit`` accepts envelopes whose ``handoff``
      field carries an exported sequence and adopts the KV via
      ``engine.import_sequence``; a full pool falls back to recomputing
      the prompt here (counted in ``handoff_stats()``), never to
      failure.

    Both disagg phases require the paged engine (the handoff moves
    physical KV blocks) and are incompatible with ``draft_group``.

    ``qos=True`` (or an explicit ``qos_class_weights`` dict) arms a
    per-replica ``WFQScheduler``: admission is ordered by weighted-fair
    virtual finish times over (tenant, priority-class) flows, and — on
    paged engines with ``qos_preempt`` — a blocked heavier-class head
    preempts lighter decoding sequences (KV retires to residency and
    resumes token-identically).  Tenant/class identity arrives on the
    ``InferenceRequest`` envelope (``accepts_envelope``).
    """

    accepts_envelope = True  # submit() takes the envelope keyword

    def __init__(self, cfg: ModelConfig, params=None, *, seed: int = 0,
                 draft_group=None, spec_k: int = 4,
                 spec_min_acceptance: float = 0.0,
                 spec_probe_proposals: int = 64, phase: str = "serve",
                 qos: bool = False, qos_class_weights=None,
                 qos_preempt: bool = True, **engine_kw):
        if phase not in ("serve", "prefill", "decode"):
            raise ValueError(
                f"phase must be 'serve', 'prefill' or 'decode', "
                f"not {phase!r}")
        if phase != "serve" and draft_group is not None:
            raise ValueError(
                "speculative decoding and disaggregated phases do not "
                "compose: a prefill/decode replica cannot host a draft")
        self.phase = phase
        engine_kw = _resolve_paged(cfg, engine_kw)
        if params is None:
            self.engine = make_engine_from_scratch(cfg, seed=seed, **engine_kw)
        else:
            self.engine = InferenceEngine(cfg, params, **engine_kw)
        if phase != "serve" and not self.engine.paged:
            raise ValueError(
                f"phase={phase!r} requires the block-paged engine (the "
                f"KV handoff moves physical blocks)")
        self.session = None
        if draft_group is not None:
            draft = _resolve_draft_engine(draft_group, seed=seed)
            self.session = SpecDecodeSession(
                self.engine, draft, k=spec_k,
                min_acceptance=spec_min_acceptance,
                probe_proposals=spec_probe_proposals)
        # everything below drives this one surface: the session when
        # speculating, the bare engine otherwise (identical protocol)
        self._driver = self.session or self.engine
        self._handoff_exports = 0
        self._handoff_imports = 0
        self._handoff_recomputes = 0
        self._imported: set = set()
        self._recomputed: set = set()
        self._stream_leftovers: list = []
        self._qos = None
        if qos or qos_class_weights is not None:
            self._qos = WFQScheduler(class_weights=qos_class_weights,
                                     preempt=qos_preempt)

    def submit(self, payload, *, envelope=None, **meta) -> int:
        tenant = envelope.tenant if envelope is not None else None
        qos_class = envelope.priority if envelope is not None else "normal"
        handoff = envelope.handoff if envelope is not None else None
        if handoff is not None and self.phase != "prefill":
            uid = self.engine.import_sequence(handoff)
            if uid is not None:
                self._handoff_imports += 1
                self._imported.add(uid)
            else:
                # decode pool full (or incompatible blocks): recompute
                # the prompt here instead of failing the request — the
                # original submit stamp is preserved so end-to-end
                # latency still spans the whole migration
                self._handoff_recomputes += 1
                uid = self.engine.submit(
                    handoff["prompt"],
                    max_new_tokens=handoff["max_new_tokens"],
                    temperature=handoff["temperature"],
                    eos_id=handoff["eos_id"],
                    tenant=tenant, qos_class=qos_class)
                self.engine.queue[-1].submitted_at = handoff["submitted_at"]
                self._recomputed.add(uid)
        else:
            uid = self._driver.submit(
                payload["prompt"],
                max_new_tokens=payload.get("max_new_tokens", 16),
                temperature=payload.get("temperature", 0.0),
                eos_id=payload.get("eos_id"),
                tenant=tenant, qos_class=qos_class,
            )
        if self._qos is not None:
            req = self._find_request(uid)
            if req is not None:
                self._qos.on_submit(req)
        return uid

    def _result(self, req) -> dict:
        itl = None
        if (req.first_token_at is not None and req.finished_at is not None
                and len(req.output) > 1):
            itl = ((req.finished_at - req.first_token_at)
                   / (len(req.output) - 1))
        res = {
            "tokens": req.output,
            "n_prompt": req.n_prompt,
            "ttft_s": (req.first_token_at - req.submitted_at
                       if req.first_token_at else None),
            "itl_s": itl,
            "latency_s": req.finished_at - req.submitted_at,
        }
        if req.uid in self._imported:
            self._imported.discard(req.uid)
            res["handoff"] = True
            res["role"] = "decode"
        elif req.uid in self._recomputed:
            self._recomputed.discard(req.uid)
            res["handoff"] = True
            res["recompute"] = True
            res["role"] = "decode"
        elif self.phase != "serve":
            res["role"] = self.phase
        return res

    def step(self):
        out = []
        if self._stream_leftovers:
            out, self._stream_leftovers = self._stream_leftovers, []
        if not self._driver.has_work():
            if not out:
                time.sleep(1e-4)
            return out
        with span("servicer.step"):
            if self.phase == "prefill":
                return out + self._step_prefill()
            if self._qos is not None:
                self._qos.schedule(self.engine)
            self._driver.step()
            for req in self._driver.collect_finished():
                if self._qos is not None:
                    self._qos.on_finish(req.uid)
                out.append((req.uid, self._result(req)))
        return out

    def _step_prefill(self):
        """Prefill-role step: chunk-prefill only, then export every
        sequence whose first token is out.  The handoff result keeps the
        normal result shape (so a crash-replay or a drain still resolves
        the future sanely) plus the serialized KV under
        ``"handoff_export"`` for the replica set's re-dispatch hook."""
        eng = self.engine
        if self._qos is not None:
            self._qos.schedule(eng)
        eng.step_prefill_only()
        out = []
        for req in eng.collect_finished():  # finished AT prefill
            if self._qos is not None:  # (e.g. max_new_tokens=1)
                self._qos.on_finish(req.uid)
            out.append((req.uid, self._result(req)))
        for uid in eng.exportable():
            pay = eng.export_sequence(uid)
            self._handoff_exports += 1
            if self._qos is not None:
                self._qos.on_finish(uid)
            now = time.perf_counter()
            out.append((uid, {
                "handoff_export": pay,
                "tokens": list(pay["output"]),
                "n_prompt": len(pay["prompt"]),
                "ttft_s": (pay["first_token_at"] - pay["submitted_at"]
                           if pay["first_token_at"] else None),
                "itl_s": None,
                "latency_s": now - pay["submitted_at"],
                "role": "prefill",
            }))
        return out

    def generate_stream(self, payload, *, max_steps: int = 100000, **meta):
        """Synchronously drive ONE request to completion, yielding
        ``{"token": t}`` per generated token and finally ``{"done":
        True, **result}`` with the same keys ``step()`` reports
        (``ttft_s``/``itl_s``/``latency_s``/``tokens``).  A
        ``max_new_tokens <= 0`` payload yields only the final event with
        ``ttft_s: None`` — an empty generation has no first token.

        This drives the WHOLE engine (a convenience for tests, examples
        and single-tenant tools, not the replica-set path); results of
        other in-flight requests completing meanwhile are buffered and
        returned by the next ``step()`` call rather than dropped."""
        if self.phase == "prefill":
            raise ValueError(
                "generate_stream runs prefill+decode; a prefill-role "
                "replica hands sequences off instead of decoding them")
        n_prompt = len(payload.get("prompt", ()))
        if payload.get("max_new_tokens", 16) <= 0:
            yield {"done": True, "tokens": [], "n_prompt": n_prompt,
                   "ttft_s": None, "itl_s": None, "latency_s": 0.0}
            return
        uid = self.submit(payload, **meta)
        req = self._find_request(uid)
        sent = 0
        final = None
        for _ in range(max_steps):
            self._driver.step()
            for r in self._driver.collect_finished():
                res = self._result(r)
                if r.uid == uid:
                    final = res
                else:
                    self._stream_leftovers.append((r.uid, res))
            if req is not None:
                while sent < len(req.output):
                    yield {"token": req.output[sent]}
                    sent += 1
            if final is not None:
                break
        if final is None:
            raise RuntimeError(
                f"generate_stream: request {uid} did not finish within "
                f"{max_steps} steps")
        yield {"done": True, **final}

    def _find_request(self, uid):
        eng = self.engine
        for r in eng.queue:
            if r.uid == uid:
                return r
        for r in eng.running.values():
            if r.uid == uid:
                return r
        return None

    def residency_summary(self, max_len: int = 128):
        """Resident prefix sequences for router gossip (thread-safe: the
        engine's radix index locks internally, so the replica set may
        snapshot while the engine thread serves).  ``max_len`` is the
        router's match fidelity (``affinity_max_prefix``)."""
        return self.engine.residency_summary(max_len=max_len)

    def set_residency_listener(self, cb):
        """Gossip push: the replica set's callback fires on KV eviction so
        the router's residency view refreshes without waiting for the next
        pull tick."""
        self.engine.on_residency_drop = cb

    def warmup(self):
        """Prime the replica before it becomes routable: run one tiny
        request end-to-end so prefill/decode are compiled and the first
        real request pays no compilation tail (autoscale warm-up).  A
        decode-role replica warms with max_new_tokens=2 — one real
        decode step — because its working path is the batched decode an
        imported sequence lands in, which a prefill-terminal
        single-token warmup would never compile."""
        mnt = 2 if self.phase == "decode" else 1
        self.engine.submit([1, 2, 3, 4], max_new_tokens=mnt)
        self.engine.run(max_steps=64)

    @property
    def stats(self):
        return self.engine.stats

    def spec_stats(self):
        """Speculative-decoding counters (k, proposed, accepted,
        acceptance_rate, rounds, enabled) when a draft is armed; None on
        plain replicas.  The replica set sums these per group and the
        ``weighted_capacity`` autoscaler turns the set-wide acceptance
        rate into the draft group's capacity entitlement."""
        return self.session.spec_stats() if self.session else None

    def block_telemetry(self):
        """Live paged-pool gauges (free/total/reserved/shared blocks, CoW
        copies, evictions) the replica set aggregates per group and
        gossips to headroom-aware routers; None for slot-pool engines."""
        return self.engine.block_telemetry()

    def qos_stats(self):
        """WFQ scheduler counters (scheduler-initiated preemptions, the
        virtual clock, live flow count) plus the engine's preemption /
        resume totals; None when QoS is not armed on this replica."""
        if self._qos is None:
            return None
        out = self._qos.stats()
        out["engine_preemptions"] = self.engine.stats.preemptions
        out["engine_preempt_resumes"] = self.engine.stats.preempt_resumes
        return out

    def handoff_stats(self):
        """Disaggregation counters (exports on prefill replicas, imports
        + recompute fallbacks on decode replicas), aggregated per group
        by ``ReplicaSet.stats()``; None on unified replicas."""
        if self.phase == "serve":
            return None
        return {
            "role": self.phase,
            "exports": self._handoff_exports,
            "imports": self._handoff_imports,
            "recomputes": self._handoff_recomputes,
        }


def llm_service_factory(cfg: ModelConfig, params=None, **engine_kw):
    """Factory suitable for ServiceDescription(factory=...).

    Engine kwargs pass through; ``paged`` defaults to auto (block-paged
    pool for dense/moe, slot pool otherwise — see ``_resolve_paged``)."""

    def make():
        return LLMServicer(cfg, params, **engine_kw)

    return make


def llm_model_group(name: str, cfg: ModelConfig, params=None, *,
                    weight: float = 1.0, replicas: Optional[int] = None,
                    slo_p95_ms: Optional[float] = None,
                    requirements=None, role: str = "serve",
                    paired_with: Optional[str] = None,
                    min_replicas: Optional[int] = None,
                    max_replicas: Optional[int] = None,
                    borrow_limit: Optional[int] = None, **engine_kw):
    """One model config of a multi-model service: a ``ModelGroup`` whose
    factory builds an ``LLMServicer`` for ``cfg``.

    Several of these behind ONE ``ServiceDescription(models=[...])`` share
    a replica set, router, and partition ledger; clients address a model
    by tagging the payload (``{"prompt": ..., "model": name}``) or passing
    ``ReplicaSet.request(payload, model=name)`` — the router only
    considers that group's replicas, so a request can never land on a
    wrong-model engine.  ``weight`` anchors the group's share of the
    set's capacity; ``slo_p95_ms`` gives it its own latency target under
    the ``weighted_capacity`` autoscaler.  Engine kwargs (including the
    auto-defaulting ``paged`` flag and its ``block_size``/``num_blocks``
    knobs, plus the spec-decode ``draft_group``/``spec_k`` servicer
    kwargs) apply to every replica of the group.

    ``role="draft"`` marks the group as the proposer side of a
    speculative pair: ``paired_with`` names the target group (routing
    aliases both onto one affinity namespace so drafts land where the
    target's KV prefix is resident), and the ``weighted_capacity``
    autoscaler scales the group's entitlement by the measured acceptance
    rate.  ``min_replicas``/``max_replicas`` bound autoscaling per group;
    an explicit ``min_replicas=0`` allows a cold draft group to be
    scaled away entirely.  ``borrow_limit`` caps how many replicas the
    group may lend below its weight-anchored entitlement when the
    ``weighted_capacity`` autoscaler picks it as the donor of a
    capacity-neutral rebalance.

    ``role="prefill"`` / ``role="decode"`` declare a DISAGGREGATED pair
    sharing this set: clients address the prefill group, whose replicas
    only chunk-prefill (``phase="prefill"`` servicers, typically with a
    large ``max_num_batched_tokens``); on first token each sequence's KV
    is exported and re-dispatched to the ``paired_with`` decode group
    (named on the prefill group), whose replicas import it and serve
    pure decode.  The prefill group's ``slo_p95_ms`` is then a TTFT
    target and the decode group's an ITL target — the two-SLO split the
    ``weighted_capacity`` autoscaler rebalances independently.
    """
    if role in ("prefill", "decode"):
        engine_kw.setdefault("phase", role)
    return ModelGroup(name=name,
                      factory=llm_service_factory(cfg, params, **engine_kw),
                      weight=weight, replicas=replicas,
                      slo_p95_ms=slo_p95_ms, requirements=requirements,
                      role=role, paired_with=paired_with,
                      min_replicas=min_replicas, max_replicas=max_replicas,
                      borrow_limit=borrow_limit)
