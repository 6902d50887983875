"""Continuous-batching inference engine (the vLLM building block).

The engine owns model params + a KV cache pool and exposes the two knobs
the paper sweeps (Fig. 5c): ``max_num_seqs`` (decode slot count) and
``max_num_batched_tokens`` (prefill admission budget per step).  Each
``step()``:

  1. admits queued requests while slots + prefill-token budget allow
     (prompt lengths are bucketed to bound recompilation),
  2. runs one batched decode over all slots,
  3. emits new tokens, retiring finished requests and freeing slots.

Prefix reuse (the serving half of prefix-affinity routing): a freed slot's
KV cache stays resident until the slot is recycled, and the token sequence
it covers is indexed in a per-engine ``RadixIndex`` (``repro.core.prefix``).
Admission asks the index for the deepest common prefix across ALL resident
slots in one O(len(prompt)) descent — replacing the old per-slot linear
scan — and resumes the best slot: its length is rewound to the covered
prefix and only the remaining suffix is fed through the (already batched)
decode path.  The match may be *partial*: a branching turn that shares a
stem with a resident sequence but diverges mid-way rewinds to the
divergence point instead of missing entirely (stale KV past the rewind is
never attended and is overwritten as the suffix feeds in).  The same index
exports ``residency_summary()``, which the replica set gossips to the
router so spill decisions know which replica holds which prefix.  Hits,
partial hits, and skipped tokens are tracked in ``EngineStats``.

``paged=True`` switches to the block-paged pool (``PagedCachePool``):

  * sequences hold *block tables* over a ``[num_blocks, block_size, ...]``
    physical store, so concurrency is bounded by free BLOCKS, not by a
    fixed slot count — short sequences no longer pin a whole
    ``max_len`` slot and the engine admits well past ``max_num_seqs``;
  * prompts prefill in CHUNKS (``api.extend``) interleaved with decode
    steps — a long prompt no longer stalls the decode batch, and the
    per-step chunk budget is ``max_num_batched_tokens``;
  * a radix residency hit FORKS the resident blocks (refcount++) instead
    of exclusively taking a slot: many live sequences share one physical
    copy of a common prefix, and the first divergent write triggers
    copy-on-write of just the boundary block;
  * admission reserves ``ceil(len/block_size)`` blocks against
    free + reclaimable-resident capacity, so a mid-flight sequence can
    always grow (block-granular residency eviction, coldest first,
    supplies the reserve);
  * decode runs DIRECTLY on the physical store
    (``paged_decode_mode="direct"``, the default): the new token's K/V is
    written into only its tail block and attention reads K/V through the
    block table (``api.decode_paged`` -> the scalar-prefetch Pallas
    kernel on a TPU), so per-token HBM traffic is
    O(blocks-touched) instead of the O(B*Smax*H*D) gather/scatter
    round-trip.  ``paged_decode_mode="gather"`` keeps the old
    reassembled-view decode for A/B benchmarking; chunked *extend*
    (prefill) still gathers — it touches the whole prefix anyway.

Both paths produce token-for-token identical greedy output: chunked
extend is bit-exact versus one full prefill (masked softmax columns
underflow to exact zeros), and both the gathered block view and the
direct path's table-gathered read are bit-identical to a contiguous
slot cache (masked columns underflow to exact zeros in the softmax).

Telemetry (``EngineStats``: tokens, decode calls and the KV positions
they attend, prefill chunks and their padded tokens, live free/reserved
block gauges) feeds the paper's throughput experiments and the replica
set's headroom-aware routing.  The paged step's phases run under
profiler spans (``engine.admit``, ``engine.prefill``, ``engine.decode``,
``engine.collect``, and within them ``engine.prepare``,
``engine.dispatch`` and ``engine.sample``; see ``repro.core.tracing``).

Speculative decoding (``SpecDecodeSession``) couples TWO engines into a
propose/verify/rewind cycle — the first cross-group *pipeline* (cheap
surrogate proposes, expensive model validates).  Each round: (1) the
DRAFT engine proposes ``k`` tokens per active sequence via its ordinary
batched greedy decode (feeding any catch-up tokens it missed first);
(2) the TARGET engine verifies all ``k+1`` positions in ONE forward
through the chunked-extend path (``ModelApi.extend`` — on the paged pool
the verified chunk's K/V is scattered straight into the block store, no
per-token decode round-trips); (3) the leftover-token acceptance rule
(``repro.serving.sampling.speculative_accept``) emits the longest
matching proposal prefix PLUS the target's own pick at the first
divergence, so greedy output is token-for-token identical to target-only
decode; (4) both engines REWIND past the rejected suffix — the slot pool
by batch-resetting cache lengths (``set_lens``), the paged pool by
truncating the block table (tail blocks free back to the admission
reserve; stale K/V below the rewind is never attended and is overwritten
by the next round's writes).  A session whose measured acceptance rate
stays under ``min_acceptance`` turns speculation off and degenerates to
plain target-engine stepping — the same graceful-off signal the
``weighted_capacity`` autoscaler consumes fleet-wide.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import OrderedDict
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.prefix import RadixIndex
from repro.core.tracing import span
from repro.models import ModelApi, get_model
from repro.models.config import ModelConfig
from .kvcache import (CachePool, PagedCachePool, extract_blocks,
                      gather_block_view, insert_blocks,
                      scatter_block_writes)
from .sampling import sample


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list
    max_new_tokens: int = 16
    temperature: float = 0.0
    eos_id: Optional[int] = None
    # multi-tenant QoS identity (accounting + weighted-fair queueing +
    # preemption order; see repro.serving.qos)
    tenant: Optional[str] = None
    qos_class: str = "normal"
    # filled by the engine
    output: list = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    slot: Optional[int] = None
    # prefix-reuse resume: prompt suffix still to be fed through decode
    # (one token per step); no output is emitted while any remain
    pending_prefix: list = dataclasses.field(default_factory=list)
    cached_prefix: int = 0  # prompt tokens whose prefill was skipped
    truncated: bool = False  # prompt exceeded max_len/bucket at prefill:
    #                          the cache does not cover the full prompt
    pos: int = 0  # cache positions holding valid KV (the slot pool's
    #               count may run past max_len, where its writes clamp)
    # paged engine state
    table: list = dataclasses.field(default_factory=list)  # physical blocks
    pending_tokens: list = dataclasses.field(default_factory=list)  # unfed
    reserve_left: int = 0  # admission-reserved blocks not yet allocated
    last_token: Optional[int] = None  # next decode feed

    @property
    def done(self) -> bool:
        return self.finished_at is not None

    @property
    def n_prompt(self) -> int:
        return len(self.prompt)


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


@dataclasses.dataclass
class _Residency:
    """A retired sequence whose blocks stay allocated for prefix resume."""

    blocks: tuple
    length: int  # tokens of the sequence (KV covers length - 1)


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    decode_tokens: int = 0
    prefill_tokens: int = 0
    # the jitted steps' work, counted where each call is made: calls of
    # the decode program (a speculative session's draft proposals too)
    # and the KV positions they attend (each real row's length + 1, the
    # new token's own, up to the pool's positions per sequence); prompt
    # chunks of the paged pool and the padded bucket tokens they ran (a
    # speculative verify is no prompt chunk: the session counts rounds)
    decode_calls: int = 0
    decode_kv_positions: int = 0
    prefill_chunks: int = 0
    prefill_padded_tokens: int = 0
    prefix_reuse_hits: int = 0  # admissions that resumed a resident slot
    prefix_partial_hits: int = 0  # resumes that rewound PAST a divergence
    #                               (resident sequence != prompt prefix)
    prefix_cached_tokens: int = 0  # prompt tokens whose prefill was skipped
    # paged-pool telemetry
    cow_copies: int = 0  # shared blocks duplicated on first divergent write
    peak_running: int = 0  # high-water concurrent admitted sequences
    shared_block_peak: int = 0  # max physical blocks saved by sharing
    evicted_residencies: int = 0  # resident sequences dropped for space
    preemptions: int = 0  # decoding sequences requeued by the WFQ
    #                       scheduler (KV retired to residency)
    preempt_resumes: int = 0  # preempted sequences re-admitted (resumed
    #                           from residency or re-prefilled)
    # live gauges (refreshed every paged step, not cumulative): the
    # pool's unallocated blocks and the admission-reserved blocks not
    # yet allocated — the "why is admission stalling" signal operators
    # and the autoscaler were missing when only peaks were reported
    free_blocks: int = 0
    reserved_blocks: int = 0


def paged_extend_step(api: ModelApi, cfg: ModelConfig, params, store, bt,
                      lens, tokens, wphys, woff, *, mesh=None):
    """One prefill chunk on the paged store: gather the sequences' block
    views, extend them by ``tokens`` [B, T], and scatter the T new K/V
    rows into their (``wphys``, ``woff``) cells.  Returns (store, logits
    [B, T, vocab])."""
    view = gather_block_view(store, bt, lens)
    view, logits = api.extend(params, view, tokens, cfg, mesh=mesh)
    T = tokens.shape[1]
    wpos = lens[:, None] + jnp.arange(T)[None, :]
    return scatter_block_writes(store, view, wphys, woff, wpos), logits


def paged_decode_step(api: ModelApi, cfg: ModelConfig, params, store, bt,
                      lens, tokens, wphys, woff, *, mesh=None):
    """One decode token per sequence straight on the paged store: no
    gathered view — the model writes the token's K/V into its tail block
    and reads K/V through the block table (the Pallas paged kernel on a
    TPU, a jnp table-gather on the CPU).  Returns (store, logits [B,
    vocab])."""
    return api.decode_paged(params, store, bt, lens, tokens, wphys, woff,
                            cfg, mesh=mesh)


class InferenceEngine:
    """Single-model continuous-batching engine."""

    def __init__(self, cfg: ModelConfig, params, *, max_num_seqs: int = 8,
                 max_num_batched_tokens: int = 2048, max_len: int = 512,
                 prefill_buckets=(32, 64, 128, 256, 512), seed: int = 0,
                 mesh=None, enable_prefix_reuse: bool = True,
                 paged: bool = False, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 max_running: Optional[int] = None,
                 paged_decode_mode: str = "direct"):
        self.cfg = cfg
        self.api: ModelApi = get_model(cfg)
        self.params = params
        self.max_num_seqs = max_num_seqs
        self.max_num_batched_tokens = max_num_batched_tokens
        self.max_len = max_len
        self.buckets = tuple(b for b in prefill_buckets if b <= max_len) or (max_len,)
        self.mesh = mesh
        self.queue: list[Request] = []
        self.running: dict[int, Request] = {}  # slot (or uid) -> request
        # radix index over token sequences whose KV is still resident
        # (value = slot id, or a residency id in paged mode); admission
        # finds the deepest resident common prefix in one O(len(prompt))
        # descent.  State-carrying families (ssm/hybrid) have no
        # per-position KV to rewind, so the fast path is gated off below.
        self._prefix_index = RadixIndex()
        self._resident_len: dict[int, int] = {}  # slot -> covered seq len
        # residency gossip PUSH channel: called (no args) whenever resident
        # KV is dropped (evicted or reclaimed), so the replica set can
        # refresh the router's residency view immediately instead of
        # leaving a staleness window until the next pull tick
        self.on_residency_drop: Optional[Callable[[], None]] = None
        self.stats = EngineStats()
        self._uid = itertools.count()
        self._key = jax.random.PRNGKey(seed)
        self._last_tokens = jnp.zeros((max_num_seqs,), jnp.int32)

        api = self.api
        self.paged = paged

        # KV-cache families: right-pad prompts into buckets, fix cache "len"
        # afterwards, read logits at the true last position.  State-carrying
        # families (ssm/hybrid) need exact-length prefill (order-dependent
        # state), which recompiles per distinct prompt length.
        self._exact_prefill = cfg.family in ("ssm", "hybrid")
        # prefix reuse needs prompt token i <-> cache position i: true for
        # pure text decoders, not for ssm/hybrid (monolithic state, nothing
        # to rewind) or vlm/encdec (vision/audio prefix offsets positions)
        self._prefix_reuse = (enable_prefix_reuse
                              and cfg.family in ("dense", "moe"))

        if paged:
            if cfg.family not in ("dense", "moe") or api.extend is None:
                raise ValueError(
                    f"paged=True requires a pure text-decoder family with "
                    f"chunked extend (dense/moe), not {cfg.family!r}")
            self.block_size = block_size
            # memory parity by default: same KV cells as the slot pool
            # (+1 for the reserved null block)
            if num_blocks is None:
                num_blocks = max_num_seqs * (-(-max_len // block_size)) + 1
            self.num_blocks = num_blocks
            self.pool: Any = PagedCachePool(cfg, num_blocks, block_size,
                                            max_len)
            self.prefill_chunk = min(prefill_chunk or max(self.buckets),
                                     max_num_batched_tokens)
            self._chunk_buckets = tuple(
                b for b in self.buckets if b <= self.prefill_chunk) \
                or (self.prefill_chunk,)
            # concurrency is block-bounded; max_running only caps the
            # decode batch (and its gathered-view footprint)
            self.max_running = max_running or self.pool.alloc.capacity
            self._prefill_order: list[Request] = []  # FIFO chunk scheduling
            self._residency: "OrderedDict[int, _Residency]" = OrderedDict()
            self._res_holds: dict[int, int] = {}  # block -> residency refs
            self._res_counter = itertools.count()
            self._reserved = 0  # admission-reserved, not-yet-allocated

            if paged_decode_mode not in ("direct", "gather"):
                raise ValueError(
                    f"paged_decode_mode must be 'direct' or 'gather', "
                    f"not {paged_decode_mode!r}")
            self.paged_decode_mode = paged_decode_mode

            # named functions, so that the compiled programs carry the
            # names ``jit_paged_extend`` and ``jit_paged_decode`` in a
            # device trace (a jitted ``functools.partial`` is anonymous)
            def paged_extend(params, store, bt, lens, tokens, wphys, woff):
                return paged_extend_step(api, cfg, params, store, bt, lens,
                                         tokens, wphys, woff, mesh=mesh)

            if paged_decode_mode == "direct":
                def paged_decode(params, store, bt, lens, tokens, wphys,
                                 woff):
                    return paged_decode_step(api, cfg, params, store, bt,
                                             lens, tokens, wphys, woff,
                                             mesh=mesh)
            else:
                # legacy A/B path: reassemble a contiguous [B, Smax] view,
                # run the slot-pool decode, scatter the one new row back
                def paged_decode(params, store, bt, lens, tokens, wphys,
                                 woff):
                    view = gather_block_view(store, bt, lens)
                    view, logits = api.decode(params, view, tokens, cfg,
                                              mesh=mesh)
                    store = scatter_block_writes(store, view, wphys[:, None],
                                                 woff[:, None], lens[:, None])
                    return store, logits

            self._paged_extend = jax.jit(paged_extend, donate_argnums=(1,))
            self._paged_decode = jax.jit(paged_decode, donate_argnums=(1,))
            self.stats.free_blocks = self.pool.n_free
            return

        self.pool = CachePool(cfg, max_num_seqs, max_len)

        def decode_fn(params, cache, tokens):
            return api.decode(params, cache, tokens, cfg, mesh=mesh)

        self._decode = jax.jit(decode_fn, donate_argnums=(1,))

        def prefill_fn(params, batch):
            kw = {"max_len": max_len}
            if not self._exact_prefill:
                kw["last_only"] = False
            return api.prefill(params, batch, cfg, mesh=mesh, **kw)

        self._prefill = jax.jit(prefill_fn)

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------
    def submit(self, prompt, *, max_new_tokens=16, temperature=0.0,
               eos_id=None, tenant=None, qos_class="normal") -> int:
        req = Request(uid=next(self._uid), prompt=list(prompt),
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      eos_id=eos_id, submitted_at=time.perf_counter(),
                      tenant=tenant, qos_class=qos_class or "normal")
        self.queue.append(req)
        return req.uid

    def has_work(self) -> bool:
        return bool(self.queue or self.running)

    def step(self) -> list:
        """One engine iteration. Returns [(uid, token), ...] emitted."""
        if self.paged:
            return self._step_paged()
        self._admit()
        events = []
        if self.running:
            events = self._decode_step()
        self.stats.steps += 1
        return events

    def collect_finished(self) -> list:
        """Retire finished requests, freeing their slots.  With prefix
        reuse on, the freed slot's KV stays resident (it is only memory
        already allocated) and the sequence it covers is remembered so a
        later prompt extending it can skip that prefill."""
        if self.paged:
            return self._collect_finished_paged()
        done = []
        for slot, req in list(self.running.items()):
            if req.done:
                del self.running[slot]
                if self._prefix_reuse and not req.truncated:
                    seq = tuple(req.prompt) + tuple(req.output)
                    self._drop_residency(slot)  # stale entry, if any
                    self._prefix_index.insert(seq, slot)
                    self._resident_len[slot] = len(seq)
                    self.pool.free(slot, resident=True)
                else:
                    self.pool.free(slot)
                done.append(req)
        return done

    def block_telemetry(self) -> Optional[dict]:
        """Live physical-block telemetry for a paged engine (None for the
        slot pool).  The replica set aggregates this per model group and
        gossips (free, total) to headroom-aware routers, so a deep prefix
        match on a memory-starved replica stops winning placement."""
        if not self.paged:
            return None
        return {
            "free_blocks": self.pool.n_free,
            "total_blocks": self.pool.alloc.capacity,
            "reserved_blocks": self._reserved,
            "shared_blocks": self.pool.block_savings(),
            "cow_copies": self.stats.cow_copies,
            "evicted_residencies": self.stats.evicted_residencies,
            "preemptions": self.stats.preemptions,
            "preempt_resumes": self.stats.preempt_resumes,
        }

    def step_prefill_only(self) -> list:
        """One PREFILL-ROLE iteration (disaggregated serving): admit and
        chunk-prefill, but never decode — a dedicated prefill replica
        spends every step's full token budget on prompt chunks instead
        of interleaving them with decode steps it will never own.
        Sequences whose first token is out (and that are not already
        done) sit in ``running`` awaiting ``export_sequence()``."""
        if not self.paged:
            raise ValueError("step_prefill_only requires a paged engine")
        self._admit_paged()
        self.stats.peak_running = max(self.stats.peak_running,
                                      len(self.running))
        self._prefill_step_paged()
        self.stats.steps += 1
        self.stats.shared_block_peak = max(self.stats.shared_block_peak,
                                           self.pool.block_savings())
        self.stats.free_blocks = self.pool.n_free
        self.stats.reserved_blocks = self._reserved
        return []

    def exportable(self) -> list:
        """Uids of running sequences ready for a prefill->decode handoff:
        past prefill (first token emitted), not finished."""
        if not self.paged:
            return []
        return [r.uid for r in self.running.values()
                if r.output and not r.pending_tokens and not r.done]

    def export_sequence(self, uid: int) -> dict:
        """Export a running sequence for migration to another paged
        engine (the disaggregated prefill->decode KV handoff).

        The sequence must be past prefill: its KV covers positions
        ``[0, pos)`` and the first generated token(s) are in ``output``.
        Returns serialized ``[n_blocks, block_size, ...]`` K/V leaves
        (``extract_blocks``) plus the metadata ``import_sequence`` needs
        to resume decode bit-for-bit.  The request then RETIRES here:
        its admission reserve is released and its blocks either transfer
        to a residency entry (prefix reuse on — a follow-up turn hitting
        this prefill replica resumes the prompt's KV for free) or free,
        exactly mirroring ``_collect_finished_paged``."""
        if not self.paged:
            raise ValueError("export_sequence requires a paged engine")
        req = self.running.get(uid)
        if req is None:
            raise KeyError(f"no running request {uid}")
        if not req.output or req.pending_tokens:
            raise ValueError(f"request {uid} has not finished prefill")
        payload = {
            "leaves": extract_blocks(self.pool.cache, req.table),
            "block_size": self.block_size,
            "n_blocks": len(req.table),
            "pos": req.pos,
            "prompt": list(req.prompt),
            "output": list(req.output),
            "last_token": req.last_token,
            "max_new_tokens": req.max_new_tokens,
            "temperature": req.temperature,
            "eos_id": req.eos_id,
            "cached_prefix": req.cached_prefix,
            "truncated": req.truncated,
            "submitted_at": req.submitted_at,
            "first_token_at": req.first_token_at,
        }
        # retire the exported request (mirrors _collect_finished_paged):
        # release the unconsumed reserve, keep the prompt KV resident
        # when prefix reuse allows so later turns skip this prefill
        del self.running[uid]
        if req in self._prefill_order:
            self._prefill_order.remove(req)
        self._reserved -= req.reserve_left
        req.reserve_left = 0
        if self._prefix_reuse and not req.truncated and req.table:
            seq = tuple(req.prompt) + tuple(req.output)
            res_id = next(self._res_counter)
            self._residency[res_id] = _Residency(tuple(req.table), len(seq))
            for b in req.table:
                self._res_holds[b] = self._res_holds.get(b, 0) + 1
            self._prefix_index.insert(seq, res_id)
        else:
            for b in req.table:
                self.pool.alloc.free(b)
        req.table = []
        self.stats.free_blocks = self.pool.n_free
        self.stats.reserved_blocks = self._reserved
        return payload

    def import_sequence(self, payload: dict) -> Optional[int]:
        """Adopt an exported sequence into freshly reserved blocks and
        resume its decode here (the receiving half of the handoff).

        Admission-gated exactly like ``_admit_paged``: the full
        remaining generation must be covered by free + reclaimable
        blocks net of existing reservations, or the import is REFUSED
        (returns None) and the caller falls back to recomputing the
        prompt — a full decode pool degrades to recompute-on-miss, never
        to a deadlock.  Block-size mismatches are likewise refused (the
        block rows cannot be remapped 1:1).  On success the request
        joins ``running`` ready for the next decode batch, keeping the
        original submit/first-token stamps so TTFT/ITL accounting spans
        the migration."""
        if not self.paged:
            raise ValueError("import_sequence requires a paged engine")
        if payload["block_size"] != self.block_size:
            return None
        pos = int(payload["pos"])
        out = list(payload["output"])
        if pos >= self.max_len:
            return None
        if len(self.running) >= self.max_running:
            return None
        remaining = max(0, int(payload["max_new_tokens"]) - len(out))
        need = self._blocks_needed(pos + remaining, 0)
        if not self._reserve(need):
            return None
        req = Request(uid=next(self._uid), prompt=list(payload["prompt"]),
                      max_new_tokens=int(payload["max_new_tokens"]),
                      temperature=float(payload["temperature"]),
                      eos_id=payload["eos_id"], output=out,
                      submitted_at=payload["submitted_at"],
                      first_token_at=payload["first_token_at"],
                      cached_prefix=int(payload.get("cached_prefix", 0)),
                      truncated=bool(payload.get("truncated", False)),
                      pos=pos, last_token=payload["last_token"])
        req.reserve_left = need
        n_blocks = int(payload["n_blocks"])
        req.table = [self._alloc_block(req) for _ in range(n_blocks)]
        self.pool.cache = insert_blocks(self.pool.cache, payload["leaves"],
                                        req.table)
        self.running[req.uid] = req
        self._check_done(req)
        self.stats.free_blocks = self.pool.n_free
        self.stats.reserved_blocks = self._reserved
        return req.uid

    def run(self, *, max_steps: int = 100000) -> dict:
        """Drain the queue; returns completed requests keyed by uid."""
        done: dict[int, Request] = {}
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
            for req in self.collect_finished():
                done[req.uid] = req
        return done

    # ------------------------------------------------------------------
    # Internals (slot pool)
    # ------------------------------------------------------------------
    def _admit(self):
        budget = self.max_num_batched_tokens
        while self.queue and self.pool.n_free > 0:
            req = self.queue[0]
            if self._prefix_reuse and self._try_resume(req):
                self.queue.pop(0)  # resumed: no prefill, no budget charge
                continue
            n = min(req.n_prompt, self.max_len - 1)
            bucket = n if self._exact_prefill else _bucket(n, self.buckets)
            n = min(n, bucket)  # over-long prompts keep their last n tokens
            if bucket > budget:
                break
            self.queue.pop(0)
            slot = self.pool.allocate()  # blank-preferring: resident KV is
            self._drop_residency(slot)  # only evicted when no blank is left
            req.truncated = n < req.n_prompt
            budget -= bucket
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n] = req.prompt[-n:]  # right-pad into the bucket
            batch = {"tokens": jnp.asarray(tokens)}
            if self.cfg.family == "encdec":
                batch["frame_embeds"] = jnp.zeros(
                    (1, 64, self.cfg.d_model), jnp.float32)
            if self.cfg.family == "vlm":
                batch["patch_embeds"] = jnp.zeros(
                    (1, self.cfg.vision_tokens or 16, self.cfg.d_model),
                    jnp.float32)
            cache, logits = self._prefill(self.params, batch)
            self.pool.insert(slot, cache)
            if not self._exact_prefill:
                self.pool.set_len(slot, n)
                logits_last = logits[0, n - 1]
            else:
                logits_last = logits[0]
            self.stats.prefill_tokens += bucket
            if req.temperature > 0:
                # match the decode path's temperature gating: the first
                # generated token must follow the same sampling policy
                # whether it comes from a fresh prefill or a resumed slot
                self._key, sub = jax.random.split(self._key)
                tok = int(sample(logits_last[None, :], sub,
                                 temperature=req.temperature)[0])
            else:
                tok = int(jnp.argmax(logits_last))
            req.slot = slot
            req.pos = n
            req.output.append(tok)
            req.first_token_at = time.perf_counter()
            self._last_tokens = self._last_tokens.at[slot].set(tok)
            self.running[slot] = req
            self._check_done(req)

    def _drop_residency(self, slot: Optional[int], notify: bool = True):
        """Forget a slot's resident sequence (its cache is being replaced
        or re-claimed), notifying the push listener when coverage the
        router may rely on actually disappeared.  The prefix-reuse resume
        path passes ``notify=False``: a take-for-resume is a HIT (the
        consuming request is already routed here), and pushing on every
        hit would re-arm a near-continuous gossip loop on the hot path."""
        if slot is None:
            return
        had = self._resident_len.pop(slot, None) is not None
        self._prefix_index.remove_value(slot)
        if notify and had and self.on_residency_drop is not None:
            try:
                self.on_residency_drop()
            except Exception:
                pass  # gossip is best-effort; serving must not care

    def residency_summary(self, max_entries: Optional[int] = None,
                          max_len: int = 128) -> list:
        """Resident token sequences (newest first, truncated), the payload
        the replica set gossips to the router's residency index."""
        return self._prefix_index.summary(
            max_entries=max_entries or self.max_num_seqs, max_len=max_len)

    def _try_resume(self, req: Request) -> bool:
        """Prefix-reuse fast path: claim the freed slot whose resident KV
        shares the deepest usable prefix with ``req.prompt`` and skip
        prefill for that prefix.

        The radix index answers the best common-prefix length per resident
        slot in one O(len(prompt)) descent.  A resident sequence of length
        L has KV for its first L-1 tokens (the final emitted token was
        never fed back), and the prompt's first d tokens match the resident
        sequence, so positions < min(d, L-1) hold valid KV — including
        *partial* matches where the resident transcript diverges from the
        prompt at d < L (a branching turn).  The resume rewinds the slot's
        length to that point and feeds the remaining prompt through the
        batched decode — one token per step, exactly the incremental path —
        with the last feed's logits producing the first new token.  Stale
        KV at positions >= the rewind (divergence junk, or junk appended
        while the slot idled) is never attended and is overwritten by those
        feeds.
        """
        m = req.n_prompt
        if m >= self.max_len:  # would be truncated: prefix math breaks
            return False
        # minimum-benefit gate: the uncovered suffix is fed one token per
        # decode step, so resuming must cover at least half the prompt —
        # a short shared stem on a long fresh prompt is cheaper to prefill
        # in one bucketed call than to drip through hundreds of decodes
        threshold = max(1, (m + 1) // 2)
        candidates = []
        for slot, d in self._prefix_index.match_lengths(req.prompt).items():
            L = self._resident_len.get(slot)
            if L is None:
                continue
            covered = min(d, L - 1, m - 1)
            if covered >= threshold:
                candidates.append((covered, slot, L, d))
        candidates.sort(reverse=True)  # deepest usable rewind first
        for covered, slot, L, d in candidates:
            if not self.pool.take(slot):
                continue  # defensively skip a slot that is no longer free
            self._drop_residency(slot, notify=False)  # resume hit, not an
            #                                           eviction
            self.pool.set_len(slot, covered)
            self._last_tokens = self._last_tokens.at[slot].set(
                req.prompt[covered])
            req.pending_prefix = list(req.prompt[covered + 1:])
            req.cached_prefix = covered
            req.slot = slot
            req.pos = covered
            self.running[slot] = req
            self.stats.prefix_reuse_hits += 1
            if d < L and d < m:  # the resident transcript and the prompt
                #                  genuinely diverge (not a mere replay of
                #                  a shorter prefix): a true partial resume
                self.stats.prefix_partial_hits += 1
            self.stats.prefix_cached_tokens += covered
            self.stats.prefill_tokens += 1  # the feed queued into
            #                  _last_tokens; the rest count as they are fed
            return True
        return False

    def _decode_step(self):
        self.pool.cache, logits = self._decode(
            self.params, self.pool.cache, self._last_tokens)
        live = [req for req in self.running.values() if not req.done]
        self._count_decode([req.pos for req in live])
        for req in live:
            req.pos += 1
        temps = np.zeros((self.max_num_seqs,), np.float32)
        for slot, req in self.running.items():
            temps[slot] = req.temperature
        # greedy for temp==0 slots, sampled otherwise; an all-greedy batch
        # (the common serving case) skips the sampled path AND the key
        # split entirely instead of paying for tokens it discards
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if np.any(temps > 0):
            self._key, sub = jax.random.split(self._key)
            sampled = sample(logits, sub, temperature=1.0)
            tokens = jnp.where(jnp.asarray(temps) > 0, sampled, greedy)
        else:
            tokens = greedy
        tokens_np = np.asarray(tokens)
        # only a resumed request forces the host-side token rewrite (and
        # the device re-upload below); the common all-decode step keeps the
        # device array as-is
        has_pending = any(req.pending_prefix
                          for req in self.running.values())
        if has_pending:
            tokens_np = tokens_np.copy()
        events = []
        for slot, req in list(self.running.items()):
            if req.done:
                continue
            if req.pending_prefix:
                # resumed request still catching up on its prompt suffix:
                # force-feed the next prompt token instead of the model's
                # prediction, and emit nothing until the prompt is consumed
                tokens_np[slot] = req.pending_prefix.pop(0)
                self.stats.prefill_tokens += 1
                continue
            tok = int(tokens_np[slot])
            req.output.append(tok)
            if req.first_token_at is None:  # resumed: first real token
                req.first_token_at = time.perf_counter()
            events.append((req.uid, tok))
            self.stats.decode_tokens += 1
            self._check_done(req)
        self._last_tokens = jnp.asarray(tokens_np) if has_pending else tokens
        return events

    def _count_decode(self, lens) -> int:
        """Count one call of the decode program whose real rows hold
        ``lens`` cache positions: each row attends those and its new
        token's own, up to the pool's positions per sequence, where writes
        clamp.  Returns the KV positions counted."""
        cap = (self.pool.max_blocks * self.block_size if self.paged
               else self.max_len)
        kv = int(np.minimum(np.asarray(lens) + 1, cap).sum())
        self.stats.decode_calls += 1
        self.stats.decode_kv_positions += kv
        return kv

    def _check_done(self, req: Request):
        if req.done:
            return
        hit_eos = req.eos_id is not None and req.output and \
            req.output[-1] == req.eos_id
        if len(req.output) >= req.max_new_tokens or hit_eos:
            req.finished_at = time.perf_counter()

    # ------------------------------------------------------------------
    # Internals (paged pool)
    # ------------------------------------------------------------------
    def _step_paged(self) -> list:
        self._admit_paged()
        self.stats.peak_running = max(self.stats.peak_running,
                                      len(self.running))
        self._prefill_step_paged()
        events = self._decode_step_paged()
        self.stats.steps += 1
        self.stats.shared_block_peak = max(self.stats.shared_block_peak,
                                           self.pool.block_savings())
        self.stats.free_blocks = self.pool.n_free
        self.stats.reserved_blocks = self._reserved
        return events

    def _blocks_needed(self, total_len: int, covered: int) -> int:
        """Blocks a sequence of ``total_len`` tokens must be able to
        allocate, given ``covered`` resumed positions: blocks strictly
        before ``covered // block_size`` are shared read-only and never
        written; a partial boundary block still counts (its first write
        may need a copy-on-write replacement)."""
        bs = self.block_size
        total = min(total_len, self.pool.max_blocks * bs)
        return max(1, -(-total // bs) - covered // bs)

    def _reclaimable_blocks(self) -> int:
        """Blocks whose every reference is a residency hold — freeing
        them needs only eviction, no live sequence loses KV."""
        alloc = self.pool.alloc
        return sum(1 for b, h in self._res_holds.items()
                   if h > 0 and alloc.refcount(b) == h)

    def _reserve(self, need: int, pinned: int = 0) -> bool:
        """Admission control: admit only when ``need`` blocks are covered
        by free + reclaimable capacity net of earlier reservations (and of
        ``pinned`` reclaimable blocks this admission is about to share),
        so an admitted sequence can ALWAYS grow to its full length —
        over-admitting would deadlock: every running sequence blocked on a
        block none of them can free."""
        avail = (self.pool.n_free + self._reclaimable_blocks()
                 - pinned - self._reserved)
        if avail < need:
            return False
        self._reserved += need
        return True

    def _admit_paged(self):
        with span("engine.admit") as sp:
            queued = len(self.queue)
            while self.queue and len(self.running) < self.max_running:
                req = self.queue[0]
                if req.output:  # preempted mid-generation: dedicated resume
                    if not self._readmit_preempted(req):
                        break
                    self.queue.pop(0)
                    continue
                if self._prefix_reuse and self._try_resume_paged(req):
                    self.queue.pop(0)
                    continue
                m = min(req.n_prompt, self.max_len - 1)
                need = self._blocks_needed(m + req.max_new_tokens, 0)
                if not self._reserve(need):
                    break
                self.queue.pop(0)
                req.truncated = m < req.n_prompt
                req.pending_tokens = list(req.prompt[-m:])
                req.reserve_left = need
                self.running[req.uid] = req
                self._prefill_order.append(req)
            sp.set_metadata(admitted=queued - len(self.queue))

    def _try_resume_paged(self, req: Request) -> bool:
        """Prefix resume by block sharing: fork (refcount++) the resident
        blocks covering the prompt's deepest resident prefix instead of
        exclusively claiming a slot.  The residency entry SURVIVES the
        resume — that is the paging win: any number of concurrent
        sequences extend one physical copy of a shared stem, and only
        boundary blocks are duplicated (copy-on-write) when they write.

        Gate: at least one full block must be covered — sharing only a
        partial boundary block would be immediately copied-on-write,
        costing a block copy to save less than one block of prefill."""
        m = req.n_prompt
        if m >= self.max_len:
            return False
        bs = self.block_size
        best = None
        for res_id, d in self._prefix_index.match_lengths(req.prompt).items():
            ent = self._residency.get(res_id)
            if ent is None:
                continue
            covered = min(d, ent.length - 1, m - 1)
            if covered >= bs and (best is None or covered > best[0]):
                best = (covered, res_id, ent, d)
        if best is None:
            return False
        covered, res_id, ent, d = best
        shared = ent.blocks[:-(-covered // bs)]
        need = self._blocks_needed(m + req.max_new_tokens, covered)
        # the shared blocks stop being reclaimable the moment this
        # sequence pins them: account for that in the reservation check
        alloc = self.pool.alloc
        pinned = sum(1 for b in set(shared)
                     if self._res_holds.get(b, 0) > 0
                     and alloc.refcount(b) == self._res_holds[b])
        if not self._reserve(need, pinned=pinned):
            return False
        for b in shared:
            alloc.fork(b)
        req.table = list(shared)
        req.pos = covered
        req.pending_tokens = list(req.prompt[covered:])
        req.reserve_left = need
        req.cached_prefix = covered
        self.running[req.uid] = req
        self._prefill_order.append(req)
        self._residency.move_to_end(res_id)  # hit: refresh retirement order
        self.stats.prefix_reuse_hits += 1
        if d < ent.length and d < m:
            self.stats.prefix_partial_hits += 1
        self.stats.prefix_cached_tokens += covered
        return True

    def preempt_sequence(self, uid: int) -> bool:
        """Preempt a DECODING sequence: retire its paged KV to a residency
        entry (block references move, exactly like finish-time retirement)
        and push the request back onto the queue, where the WFQ scheduler
        re-orders it by virtual finish time.  Resuming is cheap — the
        readmit path forks the residency back (usually the sequence's own,
        still warm) and catches up from the last covered position, so the
        resumed transcript is token-identical to uninterrupted decode.

        Only decode-phase sequences are preemptable: mid-prefill requests
        hold no emitted tokens worth preserving (the scheduler simply
        won't admit them), finished ones retire normally, and truncated
        ones cannot retire to residency (their KV does not cover the
        prompt).  Returns False when ``uid`` is not preemptable."""
        if not self.paged:
            return False
        req = self.running.get(uid)
        if (req is None or req.done or req.pending_tokens
                or not req.output or req.truncated or not req.table):
            return False
        del self.running[uid]
        if req in self._prefill_order:
            self._prefill_order.remove(req)
        self._reserved -= req.reserve_left
        req.reserve_left = 0
        if self._prefix_reuse:
            seq = tuple(req.prompt) + tuple(req.output)
            res_id = next(self._res_counter)
            self._residency[res_id] = _Residency(tuple(req.table), len(seq))
            for b in req.table:
                self._res_holds[b] = self._res_holds.get(b, 0) + 1
            self._prefix_index.insert(seq, res_id)
        else:
            for b in req.table:
                self.pool.alloc.free(b)
        req.table = []
        req.pos = 0
        req.last_token = None
        self.queue.append(req)
        self.stats.preemptions += 1
        self.stats.free_blocks = self.pool.n_free
        self.stats.reserved_blocks = self._reserved
        return True

    def _readmit_preempted(self, req: Request) -> bool:
        """Re-admit a preempted request: the catch-up 'prompt' is the full
        transcript so far (prompt + emitted output, ending with the last
        emitted token).  The deepest resident prefix — normally the
        sequence's own retirement, unless eviction claimed it — is forked
        back and only the tail is re-fed; the catch-up chunk's final
        logits row then produces exactly the token uninterrupted decode
        would have produced next (greedy), so preemption is invisible in
        the transcript."""
        seq = list(req.prompt) + list(req.output)
        L = len(seq)
        remaining = req.max_new_tokens - len(req.output)
        bs = self.block_size
        best = None
        if self._prefix_reuse:
            for res_id, d in self._prefix_index.match_lengths(seq).items():
                ent = self._residency.get(res_id)
                if ent is None:
                    continue
                covered = min(d, ent.length - 1, L - 1)
                if covered >= bs and (best is None or covered > best[0]):
                    best = (covered, res_id, ent)
        covered, shared, pinned = 0, (), 0
        if best is not None:
            covered, res_id, ent = best
            shared = ent.blocks[:-(-covered // bs)]
            alloc = self.pool.alloc
            pinned = sum(1 for b in set(shared)
                         if self._res_holds.get(b, 0) > 0
                         and alloc.refcount(b) == self._res_holds[b])
        need = self._blocks_needed(L + remaining, covered)
        if not self._reserve(need, pinned=pinned):
            return False
        for b in shared:
            self.pool.alloc.fork(b)
        if best is not None:
            self._residency.move_to_end(res_id)
            self.stats.prefix_cached_tokens += covered
        req.table = list(shared)
        req.pos = covered
        req.pending_tokens = list(seq[covered:])
        req.reserve_left = need
        self.running[req.uid] = req
        self._prefill_order.append(req)
        self.stats.preempt_resumes += 1
        return True

    def _alloc_block(self, req: Request) -> int:
        """Allocate one physical block for ``req``, evicting resident
        sequences (coldest first) as needed; consumes the request's
        admission reserve.  Admission control guarantees this succeeds."""
        b = self.pool.alloc.allocate()
        while b is None and self._residency:
            self._evict_residency()
            b = self.pool.alloc.allocate()
        if b is None:
            raise RuntimeError(
                "paged KV pool exhausted despite admission reservation")
        if req.reserve_left > 0:
            req.reserve_left -= 1
            self._reserved -= 1
        return b

    def _evict_residency(self):
        """Drop the coldest resident sequence: decref its blocks (shared
        ones survive under their live references) and forget its index
        entry, notifying the residency-gossip listener."""
        res_id, ent = self._residency.popitem(last=False)
        for b in ent.blocks:
            self._res_holds[b] -= 1
            if self._res_holds[b] == 0:
                del self._res_holds[b]
            self.pool.alloc.free(b)
        self._prefix_index.remove_value(res_id)
        self.stats.evicted_residencies += 1
        if self.on_residency_drop is not None:
            try:
                self.on_residency_drop()
            except Exception:
                pass  # gossip is best-effort; serving must not care

    def _ensure_writable(self, req: Request, start: int, n: int):
        """Make positions [start, start+n) writable: grow the block table
        (append-only) and copy-on-write any shared block about to be
        written — writing a block another table points at would corrupt
        the other sequence's (or the residency's) KV."""
        bs = self.block_size
        alloc = self.pool.alloc
        # past-capacity writes clamp to the final position, mirroring the
        # slot pool's clamped scatter when generation outruns max_len
        cap = self.pool.max_blocks * bs - 1
        start = min(start, cap)
        for lb in range(start // bs, (min(start + n - 1, cap)) // bs + 1):
            if lb < len(req.table):
                b = req.table[lb]
                if alloc.refcount(b) > 1:  # shared: copy before write
                    nb = self._alloc_block(req)
                    self.pool.copy_block(b, nb)
                    alloc.free(b)  # drop only OUR reference
                    req.table[lb] = nb
                    self.stats.cow_copies += 1
            else:
                assert lb == len(req.table), "non-contiguous block write"
                req.table.append(self._alloc_block(req))

    def _prefill_step_paged(self):
        """Feed one prompt chunk per prefilling sequence (admission FIFO)
        until the per-step token budget runs out.  Chunk lengths are
        bucketed to bound recompilation; the final chunk's last real
        logits row produces the first generated token.

        The budget is charged at the BUCKETED size — the padded bucket is
        what actually runs through ``_paged_extend``, so charging only the
        real tokens would let a step of many short ragged chunks exceed
        ``max_num_batched_tokens`` of compute and stall interleaved
        decode.  Each chunk therefore picks the largest bucket that still
        fits the remaining budget (the smallest chunk bucket always fits a
        fresh budget, so prefill never stalls)."""
        with span("engine.prefill") as sp:
            budget = self.max_num_batched_tokens
            mb = self.pool.max_blocks
            bs = self.block_size
            chunks = 0
            for req in list(self._prefill_order):
                if req.done or not req.pending_tokens:
                    self._prefill_order.remove(req)
                    continue
                fitting = [b for b in self._chunk_buckets if b <= budget]
                if not fitting:
                    break
                T = min(len(req.pending_tokens), self.prefill_chunk,
                        fitting[-1])
                bucket = _bucket(T, self._chunk_buckets)
                T = min(T, bucket)
                with span("engine.prepare"):
                    self._ensure_writable(req, req.pos, T)
                    bt = np.zeros((1, mb), np.int32)
                    bt[0, :len(req.table)] = req.table
                    tokens = np.zeros((1, bucket), np.int32)
                    tokens[0, :T] = req.pending_tokens[:T]
                    # padded chunk positions scatter into the null block
                    wphys = np.zeros((1, bucket), np.int32)
                    woff = np.zeros((1, bucket), np.int32)
                    for t in range(T):
                        p = req.pos + t
                        wphys[0, t] = req.table[p // bs]
                        woff[0, t] = p % bs
                with span("engine.dispatch", program="extend", uid=req.uid,
                          bucket=bucket, kv=req.pos + T):
                    self.pool.cache, logits = self._paged_extend(
                        self.params, self.pool.cache, jnp.asarray(bt),
                        jnp.asarray([req.pos], jnp.int32), jnp.asarray(tokens),
                        jnp.asarray(wphys), jnp.asarray(woff))
                chunks += 1
                self.stats.prefill_chunks += 1
                self.stats.prefill_padded_tokens += bucket
                req.pending_tokens = req.pending_tokens[T:]
                req.pos += T
                budget -= bucket  # charge the padded size that actually ran
                self.stats.prefill_tokens += T
                if not req.pending_tokens:  # prompt complete: first token
                    self._prefill_order.remove(req)
                    with span("engine.sample"):
                        logits_last = logits[0, T - 1]
                        if req.temperature > 0:
                            self._key, sub = jax.random.split(self._key)
                            tok = int(sample(logits_last[None, :], sub,
                                             temperature=req.temperature)[0])
                        else:
                            tok = int(jnp.argmax(logits_last))
                    req.output.append(tok)
                    req.last_token = tok
                    if req.first_token_at is None:
                        # preempted-and-resumed sequences re-run this path
                        # (their catch-up "prompt" ends mid-generation); TTFT
                        # must keep the ORIGINAL first-token stamp
                        req.first_token_at = time.perf_counter()
                    self._check_done(req)
            sp.set_metadata(chunks=chunks)

    def _decode_step_paged(self) -> list:
        """One batched decode over every sequence past prefill.  The
        batch is padded to a power of two (padding rows carry the null
        block table and length 0, so their writes land in the null
        block), bounding recompilation to O(log max_running) shapes."""
        active = [r for r in self.running.values()
                  if not r.pending_tokens and not r.done and r.output]
        if not active:
            return []
        with span("engine.decode", batch=len(active)):
            with span("engine.prepare"):
                for r in active:
                    self._ensure_writable(r, r.pos, 1)
                B = 1
                while B < len(active):
                    B *= 2
                mb = self.pool.max_blocks
                bs = self.block_size
                bt = np.zeros((B, mb), np.int32)
                lens = np.zeros((B,), np.int32)
                tokens = np.zeros((B,), np.int32)
                # padding rows write to the null block's cell (0, 0); duplicate
                # writes there are harmless because masked positions are never
                # attended
                wphys = np.zeros((B,), np.int32)
                woff = np.zeros((B,), np.int32)
                temps = np.zeros((B,), np.float32)
                for i, r in enumerate(active):
                    bt[i, :len(r.table)] = r.table
                    lens[i] = r.pos
                    tokens[i] = r.last_token
                    p = min(r.pos, mb * bs - 1)  # clamp like the slot pool
                    wphys[i] = r.table[p // bs]
                    woff[i] = p % bs
                    temps[i] = r.temperature
                kv = self._count_decode(lens[:len(active)])
            with span("engine.dispatch", program="decode", batch=B, kv=kv):
                self.pool.cache, logits = self._paged_decode(
                    self.params, self.pool.cache, jnp.asarray(bt),
                    jnp.asarray(lens), jnp.asarray(tokens), jnp.asarray(wphys),
                    jnp.asarray(woff))
            with span("engine.sample"):
                # all-greedy batches skip the sampled path and the key split
                # (the same fast path as the slot pool's _decode_step)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if np.any(temps > 0):
                    self._key, sub = jax.random.split(self._key)
                    sampled = sample(logits, sub, temperature=1.0)
                    toks = np.asarray(jnp.where(jnp.asarray(temps) > 0,
                                                sampled, greedy))
                else:
                    toks = np.asarray(greedy)
            events = []
            for i, r in enumerate(active):
                tok = int(toks[i])
                r.output.append(tok)
                r.last_token = tok
                r.pos += 1
                events.append((r.uid, tok))
                self.stats.decode_tokens += 1
                self._check_done(r)
        return events

    def _collect_finished_paged(self) -> list:
        """Retire finished requests.  With prefix reuse on, the block
        table transfers to a residency entry (no refcount change — the
        references move, they are not duplicated), so the blocks stay
        shareable until block-granular eviction reclaims them."""
        with span("engine.collect") as sp:
            done = []
            for uid, req in list(self.running.items()):
                if not req.done:
                    continue
                del self.running[uid]
                if req in self._prefill_order:
                    self._prefill_order.remove(req)
                self._reserved -= req.reserve_left
                req.reserve_left = 0
                if self._prefix_reuse and not req.truncated and req.table:
                    seq = tuple(req.prompt) + tuple(req.output)
                    res_id = next(self._res_counter)
                    self._residency[res_id] = _Residency(tuple(req.table),
                                                         len(seq))
                    for b in req.table:
                        self._res_holds[b] = self._res_holds.get(b, 0) + 1
                    self._prefix_index.insert(seq, res_id)
                else:
                    for b in req.table:
                        self.pool.alloc.free(b)
                req.table = []
                done.append(req)
            self.stats.free_blocks = self.pool.n_free
            self.stats.reserved_blocks = self._reserved
            sp.set_metadata(finished=len(done))
        return done


@dataclasses.dataclass
class _SpecSeq:
    """One sequence's coupled state across the draft and target engines."""

    treq: Request  # target-engine request (owns the emitted transcript)
    dreq: Optional[Request]  # draft-engine request (proposal KV)
    max_new: int  # real token budget (treq's is inflated until pairing)
    ready: bool = False  # both engines prefilled; in the propose rotation
    t_cov: int = 0  # target cache positions holding valid KV
    d_cov: int = 0  # draft cache positions holding valid KV
    last_tok: int = 0  # last emitted token (target's next verify feed)
    # sequence tokens the draft has not fed yet (ends with last_tok);
    # normally one token, two after a fully-accepted round
    draft_pending: list = dataclasses.field(default_factory=list)


class SpecDecodeSession:
    """Cross-engine speculative decoding: DRAFT proposes, TARGET verifies.

    Wraps two ``InferenceEngine``s (any mix of slot-pool and paged) behind
    the engine's own submit/step/collect_finished surface.  Per round the
    draft runs ``k`` batched greedy decode steps to propose ``k`` tokens
    per active sequence, then the target verifies all ``k+1`` positions in
    ONE ``extend`` forward; the leftover-token rule emits the longest
    matching proposal prefix plus the target's pick at the first
    divergence (so greedy output is token-for-token identical to
    target-only decode), and both caches rewind past the rejected suffix
    (paged: block-table truncation, tail blocks return to the admission
    reserve; slot: batched length reset).

    Greedy only: sampled requests need the rejection-sampling acceptance
    rule and are refused at ``submit``.  ``min_acceptance`` > 0 arms the
    graceful-off path: once ``probe_proposals`` proposals have been
    measured, a session whose acceptance rate sits below the floor stops
    speculating permanently and every subsequent ``step()`` is a plain
    target-engine step (identical call pattern and cost to vanilla
    decode).  ``proposed``/``accepted`` counters feed the per-group stats
    the ``weighted_capacity`` autoscaler uses to shrink a low-acceptance
    draft group's entitlement fleet-wide.
    """

    def __init__(self, target: InferenceEngine, draft: InferenceEngine, *,
                 k: int = 4, min_acceptance: float = 0.0,
                 probe_proposals: int = 64):
        if target.api.extend is None or \
                target.cfg.family not in ("dense", "moe"):
            raise ValueError(
                "speculative decoding needs a target family with chunked "
                f"extend (dense/moe), not {target.cfg.family!r}")
        if draft.cfg.family not in ("dense", "moe"):
            raise ValueError(
                "speculative decoding needs a positional-KV draft family "
                f"(dense/moe), not {draft.cfg.family!r}")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.target = target
        self.draft = draft
        self.k = k
        self.min_acceptance = float(min_acceptance)
        self.probe_proposals = int(probe_proposals)
        self.spec_enabled = True
        self.proposed = 0
        self.accepted = 0
        self.rounds = 0
        self._seqs: "OrderedDict[int, _SpecSeq]" = OrderedDict()
        self._extend_jits: dict = {}  # id(engine) -> jitted slot extend

    # ------------------------------------------------------------------
    # Engine-compatible surface
    # ------------------------------------------------------------------
    @property
    def stats(self) -> EngineStats:
        return self.target.stats

    def spec_stats(self) -> dict:
        return {
            "k": self.k,
            "proposed": self.proposed,
            "accepted": self.accepted,
            "acceptance_rate": (self.accepted / self.proposed
                                if self.proposed else None),
            "rounds": self.rounds,
            "enabled": self.spec_enabled,
        }

    def submit(self, prompt, *, max_new_tokens=16, temperature=0.0,
               eos_id=None, tenant=None, qos_class="normal") -> int:
        if temperature and temperature > 0:
            raise ValueError(
                "SpecDecodeSession serves greedy (temperature=0) requests "
                "only; the leftover-token rule does not cover sampling")
        prompt = list(prompt)
        m = len(prompt)
        # the verify forward writes up to k+1 positions past the accepted
        # prefix, so the full budget must fit both caches with that slack
        need = m + max_new_tokens + self.k + 1
        for eng, who in ((self.target, "target"), (self.draft, "draft")):
            if need >= eng.max_len:
                raise ValueError(
                    f"prompt ({m}) + max_new_tokens ({max_new_tokens}) + "
                    f"k+1 must fit the {who} engine max_len ({eng.max_len})")
            if not eng.paged and m > max(eng.buckets):
                raise ValueError(
                    f"prompt ({m}) exceeds the {who} engine's largest "
                    f"prefill bucket ({max(eng.buckets)}): the truncated "
                    f"prefill would break the verify position math")
        if not self.spec_enabled:
            # speculation permanently off: plain target submit — the
            # inflated budget below is only ever restored by
            # _pair_ready, which a disabled session never runs
            return self.target.submit(prompt,
                                      max_new_tokens=max_new_tokens,
                                      eos_id=eos_id, tenant=tenant,
                                      qos_class=qos_class)
        # inflate the target budget so admission (paged: the block
        # reservation; both: _check_done) covers the speculative
        # overshoot; restored to the real budget when the pair activates
        uid = self.target.submit(prompt,
                                 max_new_tokens=max_new_tokens + self.k + 1,
                                 eos_id=eos_id, tenant=tenant,
                                 qos_class=qos_class)
        treq = self.target.queue[-1]
        dreq = None
        if self.spec_enabled:
            self.draft.submit(prompt,
                              max_new_tokens=max_new_tokens + self.k + 2,
                              eos_id=None)  # the draft never self-finishes
            dreq = self.draft.queue[-1]
        self._seqs[uid] = _SpecSeq(treq=treq, dreq=dreq,
                                   max_new=max_new_tokens)
        return uid

    def has_work(self) -> bool:
        return self.target.has_work()

    def step(self) -> list:
        t = self.target
        if not self.spec_enabled:
            # degenerate mode: EXACTLY a vanilla engine step (same calls,
            # same cost) — speculation is off, not merely idle
            return t.step()
        if t.paged:
            t._admit_paged()
            t.stats.peak_running = max(t.stats.peak_running, len(t.running))
            t._prefill_step_paged()
        else:
            t._admit()
            self._complete_slot_resumes(t)
        d = self.draft
        if d.paged:
            d._admit_paged()
            d._prefill_step_paged()
        else:
            d._admit()
            self._complete_slot_resumes(d)
        self._pair_ready()
        active = [s for s in self._seqs.values()
                  if s.ready and not s.treq.done]
        events = self._spec_round(active) if active else []
        t.stats.steps += 1
        if t.paged:
            t.stats.shared_block_peak = max(t.stats.shared_block_peak,
                                            t.pool.block_savings())
            t.stats.free_blocks = t.pool.n_free
            t.stats.reserved_blocks = t._reserved
        if self.min_acceptance > 0 and self.proposed >= self.probe_proposals \
                and self.accepted < self.min_acceptance * self.proposed:
            self._disable_spec()
        return events

    def collect_finished(self) -> list:
        done = self.target.collect_finished()
        for req in done:
            seq = self._seqs.pop(req.uid, None)
            if seq is not None and seq.dreq is not None:
                self._retire_draft(seq)
        if self.spec_enabled:
            self.draft.collect_finished()
        return done

    def run(self, *, max_steps: int = 100000) -> dict:
        done: dict[int, Request] = {}
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
            for req in self.collect_finished():
                done[req.uid] = req
        return done

    # ------------------------------------------------------------------
    # Pairing and teardown
    # ------------------------------------------------------------------
    @staticmethod
    def _prefilled(eng: InferenceEngine, req: Request) -> bool:
        if not req.output:
            return False
        if eng.paged:
            return not req.pending_tokens
        return req.slot is not None and not req.pending_prefix

    def _pair_ready(self):
        for s in self._seqs.values():
            if s.ready or s.treq.done:
                continue
            if not self._prefilled(self.target, s.treq):
                continue
            if s.dreq is None or not self._prefilled(self.draft, s.dreq):
                continue
            m = s.treq.n_prompt
            s.t_cov = s.treq.pos if self.target.paged else m
            s.d_cov = s.dreq.pos if self.draft.paged else m
            s.last_tok = s.treq.output[-1]
            s.draft_pending = [s.last_tok]
            s.treq.max_new_tokens = s.max_new  # restore the real budget
            self.target._check_done(s.treq)
            s.ready = True

    def _retire_draft(self, seq: _SpecSeq):
        """Finish the draft-side request so its engine frees (or retains
        as residency) the proposal KV.  The residency transcript is
        truncated to what the draft cache actually covers — claiming the
        full emitted sequence would let a later resume attend garbage."""
        d = self.draft
        dreq = seq.dreq
        for i, r in enumerate(d.queue):  # identity, not dataclass ==
            if r is dreq:
                del d.queue[i]
                return
        if not self._prefilled(d, dreq):
            dreq.truncated = True  # mid-prefill: no residency claim
        else:
            transcript = (list(seq.treq.prompt) + list(seq.treq.output))
            d_cov = seq.d_cov if seq.ready else dreq.n_prompt
            dreq.output = transcript[dreq.n_prompt:d_cov + 1]
            if not dreq.output:
                dreq.truncated = True
        dreq.finished_at = time.perf_counter()

    def _disable_spec(self):
        """Acceptance collapsed: stop speculating for good.  Draft-side
        requests retire (their KV frees), inflated target budgets are
        restored, and every later step() is a plain target-engine step."""
        self.spec_enabled = False
        slot_tokens = {}
        for s in self._seqs.values():
            if not s.ready:
                s.treq.max_new_tokens = s.max_new
                self.target._check_done(s.treq)
            elif not self.target.paged and s.treq.slot is not None:
                slot_tokens[s.treq.slot] = s.last_tok
            if s.dreq is not None:
                self._retire_draft(s)
                s.dreq = None
        if slot_tokens:  # hand the feeds to the vanilla decode loop
            lt = np.asarray(self.target._last_tokens).copy()
            for slot, tok in slot_tokens.items():
                lt[slot] = tok
            self.target._last_tokens = jnp.asarray(lt)
        self.draft.collect_finished()

    # ------------------------------------------------------------------
    # Slot-pool prefix-resume completion (chunked, via extend)
    # ------------------------------------------------------------------
    def _extend_for(self, eng: InferenceEngine):
        fn = self._extend_jits.get(id(eng))
        if fn is None:
            api, cfg, mesh = eng.api, eng.cfg, eng.mesh

            def extend_fn(params, cache, tokens):
                return api.extend(params, cache, tokens, cfg, mesh=mesh)

            fn = jax.jit(extend_fn, donate_argnums=(1,))
            self._extend_jits[id(eng)] = fn
        return fn

    def _complete_slot_resumes(self, eng: InferenceEngine):
        """A slot-pool prefix resume leaves the prompt suffix to drip in
        one token per decode step; the session instead feeds the whole
        suffix through ONE bucketed extend (the same chunked path verify
        uses), so a resumed sequence joins the propose rotation
        immediately.  A running request with no output yet is NECESSARILY
        a resume (fresh admission emits its first token inside
        ``_admit``) — including the fully-covered case where
        ``pending_prefix`` is empty and only the final prompt token needs
        feeding, which the vanilla decode loop would pick up from
        ``_last_tokens`` but the session must extend explicitly.  All
        resumes admitted this step share ONE extend (each slot's suffix
        in its own row, bucket sized to the longest) — per-request
        forwards would pay full depth per resume."""
        todo = [req for req in eng.running.values()
                if not req.output and req.slot is not None]
        if not todo:
            return
        chunks = {req.slot: list(req.prompt[req.cached_prefix:])
                  for req in todo}
        bucket = _bucket(max(len(c) for c in chunks.values()), eng.buckets)
        tokens = np.zeros((eng.max_num_seqs, bucket), np.int32)
        for slot, chunk in chunks.items():
            tokens[slot, :len(chunk)] = chunk
        ext = self._extend_for(eng)
        eng.pool.cache, logits = ext(eng.params, eng.pool.cache,
                                     jnp.asarray(tokens))
        gtok = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        extra = {}
        for req in todo:
            T0 = len(chunks[req.slot])
            tok = int(gtok[req.slot, T0 - 1])
            req.pending_prefix = []
            req.output.append(tok)
            req.last_token = tok
            req.first_token_at = time.perf_counter()
            eng.stats.prefill_tokens += T0 - 1
            extra[req.slot] = req.cached_prefix + T0
            if eng is self.target:
                eng._last_tokens = eng._last_tokens.at[req.slot].set(tok)
                eng._check_done(req)
        self._rewind_slots(eng, extra=extra)

    def _rewind_slots(self, eng: InferenceEngine, extra=None):
        """Batch-reset slot lengths after an extend/decode advanced EVERY
        slot: each running sequence returns to its true coverage (stale KV
        past it is never attended and is overwritten by later writes —
        the same argument the prefix-resume rewind makes).  Untracked
        requests (admitted but not yet paired) are covered too: their
        lens were bumped just the same."""
        updates = dict(extra or {})
        cov_by_req = {}
        for s in self._seqs.values():
            if not s.ready or s.treq.done:
                continue
            if eng is self.target:
                cov_by_req[id(s.treq)] = s.t_cov
            elif s.dreq is not None:
                cov_by_req[id(s.dreq)] = s.d_cov
        for slot, req in eng.running.items():
            if slot in updates or req.done:
                continue
            cov = cov_by_req.get(id(req))
            if cov is None:
                if not req.output:  # resume whose catch-up extend has
                    cov = req.cached_prefix  # not run yet (first-token
                    #                          feed still pending)
                else:  # freshly prefilled, waiting to pair
                    n = min(req.n_prompt, eng.max_len - 1)
                    cov = min(n, _bucket(n, eng.buckets))
            updates[slot] = cov
        eng.pool.set_lens(updates)
        for slot, n in updates.items():  # the decode counter reads pos
            if slot in eng.running:
                eng.running[slot].pos = n

    # ------------------------------------------------------------------
    # The propose / verify / rewind round
    # ------------------------------------------------------------------
    def _spec_round(self, active) -> list:
        k = self.k
        props = {id(s): [] for s in active}
        pend = {id(s): list(s.draft_pending) for s in active}
        steps = max(len(s.draft_pending) for s in active) - 1 + k
        # -- propose: k batched greedy draft decodes (catch-up feeds
        #    first); a sequence whose proposals are complete re-feeds its
        #    last token — the rewind below discards that garbage anyway
        for j in range(steps):
            feed = []
            for s in active:
                fl = pend[id(s)]
                feed.append(fl[j] if j < len(fl) else fl[-1])
            toks = self._draft_step(active, feed)
            for i, s in enumerate(active):
                fl = pend[id(s)]
                if len(fl) - 1 <= j and len(props[id(s)]) < k:
                    t = int(toks[i])
                    props[id(s)].append(t)
                    fl.append(t)
        # -- verify: ONE extend forward over [last_tok, d_1..d_k]
        chunks = np.zeros((len(active), k + 1), np.int32)
        for i, s in enumerate(active):
            chunks[i, 0] = s.last_tok
            chunks[i, 1:] = props[id(s)]
        g = self._verify(active, chunks)  # target greedy picks [B, k+1]
        # -- accept + emit + rewind
        events = []
        t_slot_updates = {}
        d_slot_updates = {}
        for i, s in enumerate(active):
            prop = props[id(s)]
            row = g[i]
            a = 0
            while a < k and prop[a] == int(row[a]):
                a += 1
            self.proposed += k
            self.accepted += a
            treq = s.treq
            n = s.t_cov + 1  # emitted sequence length before this round
            for j in range(a + 1):
                if treq.done:
                    break
                tok = int(row[j])
                treq.output.append(tok)
                events.append((treq.uid, tok))
                self.target.stats.decode_tokens += 1
                self.target._check_done(treq)
            seq_len = treq.n_prompt + len(treq.output)
            # valid coverage: the verified feeds matching the true
            # sequence (capped by what was actually emitted)
            t_new = min(n + a, seq_len - 1)
            d_new = min(n + a if a < k else n + k - 1, seq_len - 1)
            if treq.done:
                continue  # retirement keeps the written KV; no rewind
            s.t_cov = t_new
            s.d_cov = d_new
            s.last_tok = treq.output[-1]
            transcript = list(treq.prompt) + list(treq.output)
            s.draft_pending = transcript[d_new:]
            if self.target.paged:
                self._rewind_paged(self.target, treq, t_new)
                treq.last_token = s.last_tok
            else:
                t_slot_updates[treq.slot] = t_new
            if self.draft.paged:
                self._rewind_paged(self.draft, s.dreq, d_new)
            else:
                d_slot_updates[s.dreq.slot] = d_new
        if not self.target.paged:
            self._rewind_slots(self.target, extra=t_slot_updates)
        if not self.draft.paged:
            self._rewind_slots(self.draft, extra=d_slot_updates)
        self.rounds += 1
        return events

    def _draft_step(self, active, feed):
        """One batched greedy decode on the draft engine; returns the
        proposal token per active sequence."""
        eng = self.draft
        eng.stats.steps += 1
        if not eng.paged:
            feeds = np.zeros((eng.max_num_seqs,), np.int32)
            for s, f in zip(active, feed):
                feeds[s.dreq.slot] = f
            eng.pool.cache, logits = eng._decode(
                eng.params, eng.pool.cache, jnp.asarray(feeds))
            eng._count_decode([s.dreq.pos for s in active])
            for s in active:
                s.dreq.pos += 1
            gtok = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
            return [int(gtok[s.dreq.slot]) for s in active]
        B = 1
        while B < len(active):
            B *= 2
        mb, bs = eng.pool.max_blocks, eng.block_size
        bt = np.zeros((B, mb), np.int32)
        lens = np.zeros((B,), np.int32)
        tokens = np.zeros((B,), np.int32)
        wphys = np.zeros((B,), np.int32)
        woff = np.zeros((B,), np.int32)
        for i, s in enumerate(active):
            r = s.dreq
            eng._ensure_writable(r, r.pos, 1)
            bt[i, :len(r.table)] = r.table
            lens[i] = r.pos
            tokens[i] = feed[i]
            p = min(r.pos, mb * bs - 1)
            wphys[i] = r.table[p // bs]
            woff[i] = p % bs
        eng.pool.cache, logits = eng._paged_decode(
            eng.params, eng.pool.cache, jnp.asarray(bt), jnp.asarray(lens),
            jnp.asarray(tokens), jnp.asarray(wphys), jnp.asarray(woff))
        eng._count_decode(lens[:len(active)])
        for s in active:
            s.dreq.pos += 1
        gtok = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        return [int(gtok[i]) for i in range(len(active))]

    def _verify(self, active, chunks):
        """ONE extend forward verifying all k+1 positions per sequence;
        returns the target's greedy pick at each position [B, k+1]."""
        eng = self.target
        T = chunks.shape[1]
        if not eng.paged:
            tokens = np.zeros((eng.max_num_seqs, T), np.int32)
            for i, s in enumerate(active):
                tokens[s.treq.slot] = chunks[i]
            ext = self._extend_for(eng)
            eng.pool.cache, logits = ext(eng.params, eng.pool.cache,
                                         jnp.asarray(tokens))
            gtok = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
            return gtok[[s.treq.slot for s in active]]
        B = 1
        while B < len(active):
            B *= 2
        mb, bs = eng.pool.max_blocks, eng.block_size
        bt = np.zeros((B, mb), np.int32)
        lens = np.zeros((B,), np.int32)
        tokens = np.zeros((B, T), np.int32)
        wphys = np.zeros((B, T), np.int32)
        woff = np.zeros((B, T), np.int32)
        for i, s in enumerate(active):
            r = s.treq
            eng._ensure_writable(r, s.t_cov, T)
            bt[i, :len(r.table)] = r.table
            lens[i] = s.t_cov
            tokens[i] = chunks[i]
            for t in range(T):
                p = min(s.t_cov + t, mb * bs - 1)
                wphys[i, t] = r.table[p // bs]
                woff[i, t] = p % bs
        eng.pool.cache, logits = eng._paged_extend(
            eng.params, eng.pool.cache, jnp.asarray(bt), jnp.asarray(lens),
            jnp.asarray(tokens), jnp.asarray(wphys), jnp.asarray(woff))
        gtok = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        return gtok[:len(active)]

    def _rewind_paged(self, eng: InferenceEngine, req: Request,
                      new_pos: int):
        """Truncate the block table past the accepted prefix: tail blocks
        holding only rejected K/V free back to the pool AND to the
        request's admission reserve (symmetric with ``_alloc_block``), so
        chunk-budget accounting stays exact across rounds."""
        bs = eng.block_size
        keep = max(1, -(-new_pos // bs))
        while len(req.table) > keep:
            b = req.table.pop()
            eng.pool.alloc.free(b)
            req.reserve_left += 1
            eng._reserved += 1
        req.pos = new_pos


def make_engine_from_scratch(cfg: ModelConfig, *, seed=0, **kw):
    """Init params and build an engine (used by services/examples)."""
    from repro.models import nn

    api = get_model(cfg)
    params, _ = nn.split(api.init(jax.random.PRNGKey(seed), cfg))
    return InferenceEngine(cfg, params, **kw)
