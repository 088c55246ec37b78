"""Continuous-batching serving engine — iteration-level scheduling over
``InferenceEngine`` (Orca, Yu et al. OSDI'22; slot/paged KV management in
the spirit of vLLM's PagedAttention, Kwon et al. SOSP'23 — here with the
TPU constraint that every program keeps FIXED shapes).

The scheduler loop per iteration (:meth:`ServingEngine.step`):

1. **Admission** — while a KV slot is free, the page pool can back the
   request and the queue is non-empty, pop a request (``fcfs`` or
   ``shortest_first``) and stream its prompt through the donated
   per-chunk prefill executable straight into the slot's pages, spending
   at most ``prefill_token_budget x num_slots / live lanes`` prompt tokens
   per iteration (``_prefill_limit``: the budget is the stall a FULL
   batch tolerates, so empty slots fill fast and a full server keeps the
   bound) so a long prompt cannot starve decoding.  A finished prefill
   dispatches ONE admit program (first-token sample + in-program
   slot-state write).
2. **Decode** — ONE call of the single reusable decode-step program
   advances every live slot ``decode_block`` tokens (pool + slot state
   donated).  Rows that emit their ``eos`` (or exhaust ``max_new_tokens``)
   retire IN-PROGRAM; the host mirrors the retirement bookkeeping from the
   emitted tokens, frees their slots and pages mid-flight, and hands them
   to the admission queue — no request ever waits for a batch to finish.

**Latency-hiding:** the slot state lives ON DEVICE and every program
chains through it by data dependency, so the host never synchronizes
inside the dispatch path.  Token reads lag ONE event behind: the host
dispatches the next decode block first and only then materializes the
previous block's tokens, so the device stays busy while
the host does its scheduling bookkeeping.  The price is that a slot freed
in block N is re-admittable only from block N+2 — at most one block of
idle per retirement.

Because slot occupancy and the page tables ride traced arguments, the
whole server lifetime compiles exactly ONE decode-step executable per
(num_slots, num_pages, page_size, block, sampling) configuration.  The
serving programs compile once per PROCESS and deliberately bypass the
persistent cache layers — reloaded serving executables corrupt the
donated slot workspace (see the ``_persist_opt_out`` note in
``__init__``).

**KV cache** (``docs/serving.md`` "KV cache"): one shared page pool
``[L, num_pages, page_size, KVH*D]`` plus per-slot page tables the host
allocates (``paging.SlotPages`` — the one cache manager; the scheduler
here holds no page arithmetic) and ships as TRACED arguments on every
dispatch — HBM cost is ``num_pages × page_size``, admission prefill
writes straight into the slot's pages (no staging lane, no admit-time
insert), hash-matched prompt prefixes map to the same refcounted
physical pages (prefilled once, copy-on-write at page granularity via
recompute-on-divergence), and pool pressure degrades into admission
backpressure handled by the bounded queue instead of an allocation
cliff.  The int8 KV path (``kv_cache_quant``) quantizes pool pages,
roughly doubling page capacity.  Page churn only changes table
CONTENTS, never a program shape.

**Speculative decoding** (``serving.speculative``, ``docs/serving.md``
"Speculative decoding"): a small DRAFT model proposes ``spec_k`` greedy
tokens per live slot from its own (always monolithic) KV workspace, and
the target model verifies the whole window in ONE batched forward —
accept mask, per-slot accepted length, eos/budget truncation and the
state update all computed IN-PROGRAM, the draft tokens flowing
propose → verify as a device array.  Up to ``spec_k + 1`` tokens commit
per target dispatch; every committed token is the target's own
``build_sample_fn`` output over exactly the committed history, so
greedy speculative serving is BITWISE-identical to the plain decode
path.  Fixed ``spec_k`` keeps the one-executable discipline: exactly
one draft-propose and one verify-and-commit executable per server
lifetime.  Admission streams each prompt chunk through BOTH models
(the draft's prefill lane rides the admit event one-behind);
preemption snapshots committed tokens only, and restore
re-derives all draft state through the ordinary re-prefill path.
A model with a multi-token-prediction module of its own
(``spec_draft_model="mtp"``, the contract's ``drafts_itself``) drafts for
ITSELF: no draft model, cache or programs — ``decode_block`` verify windows of
two rows a lane ride ONE dispatch (``slots.make_spec_block_fn``), the module's
rows are one more layer of the model's own pools, and the mirror commits
one or two tokens a lane a window from the program's ``accepted``.

**Robustness / SLO layer** (``docs/serving.md`` "Robustness & SLOs"):
every request ends in a typed terminal status (``COMPLETED`` |
``SHED_DEADLINE`` | ``CANCELLED`` | ``ABORTED``); per-request wall-clock
deadlines shed queued work before it ever occupies a slot and retire
in-slot work at the next scheduling point; the queue is bounded
(``max_queue_depth`` + reject-or-block); a circuit breaker trips after N
consecutive failed dispatches and rejects-with-reason instead of
hammering a sick device; and graceful preemption (:meth:`preempt`)
drains in-flight slots under a budget then snapshots the remainder
through the crash-atomic checkpoint protocol, so a restarted server
(:meth:`restore`) resumes them with greedy outputs bitwise-identical to
an uninterrupted run.  All of it is host bookkeeping riding the existing
traced slot arguments — no new program shapes, the one-decode-executable
invariant holds through overload, drain and resume.

**Observability layer** (``docs/observability.md``): with
``serving.tracing`` on, every request carries a span tree (submit →
queue wait → admission prefill chunks → admit dispatch → decode /
spec-propose / spec-verify dispatches with tokens-committed counts →
terminal), recorded host-side at the existing scheduler seams,
exportable as Chrome trace-event JSON (:meth:`ServingEngine.dump_trace`,
Perfetto-loadable, one track per slot plus scheduler/queue/handler
tracks) and summarized as a queue/prefill/decode/host latency breakdown
on every :class:`~.slo.RequestResult`; TTFT, time-between-tokens,
queue-wait, per-program dispatch-duration and lock-wait histograms feed
``/metrics``.  With ``serving.flight_recorder`` on, a bounded
self-locked ring of recent structured events (dispatch begin/end,
scheduler decisions, breaker transitions, shed/cancel/abort reasons,
lock-wait samples, fault-injection hits) auto-dumps to JSON on
breaker-open, ``DrainTimeout``, ``ConcurrencyViolation`` and
scheduler-thread death, and on demand via ``GET /debug/flightrec``,
SIGUSR2 or :meth:`ServingEngine.dump_flightrec`.  Both are default-off
= seed behavior, host-side only (zero new jitted programs — the
zero-new-executables proof covers the tracing-on path), and the hot
path never contends a reader: the ring and the histograms carry their
own locks.
"""

import math
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving.concurrency import (
    InstrumentedRLock, checks_enabled, install_concurrency_checks)
from deepspeed_tpu.inference.serving.config import ServingConfig
from deepspeed_tpu.inference.serving.flightrec import FlightRecorder
from deepspeed_tpu.monitor import trace as span_trace
from deepspeed_tpu.monitor.trace import ServingHistograms, span
from deepspeed_tpu.inference.serving.paging import SlotPages
from deepspeed_tpu.inference.serving.slo import (CircuitBreaker,
                                                 DrainTimeout, QueueFull,
                                                 RequestResult,
                                                 RequestStatus,
                                                 TERMINAL_STATUSES,
                                                 TokenStream)
from deepspeed_tpu.inference.serving.slots import (admission_chunk,
                                                   chunk_rows,
                                                   chunk_write_form,
                                                   init_slot_state,
                                                   make_admit_fn,
                                                   make_chunk_fn,
                                                   make_decode_block_fn,
                                                   make_draft_admit_fn,
                                                   make_draft_chunk_fn,
                                                   make_draft_propose_fn,
                                                   make_spec_block_fn,
                                                   make_spec_verify_fn)
from deepspeed_tpu.models import contract as slot_contract
from deepspeed_tpu.runtime.fault import inject
from deepspeed_tpu.utils.logging import log_dist, logger


@dataclass
class ServeRequest:
    """One queued/running generation request (host bookkeeping only).

    ``prefix`` holds tokens ALREADY generated in a previous server
    incarnation (graceful-preemption resume): admission prefills
    ``ids + prefix`` and the device decodes only the remaining budget —
    the greedy continuation is bitwise what the uninterrupted run would
    have produced.  ``deadline`` is an absolute ``time.monotonic()``
    instant (``None`` = no deadline).  ``priority`` is the admission
    lane (0 = most urgent; only meaningful with
    ``serving.priority_lanes > 1``); ``streamed`` counts the tokens
    already published to :meth:`ServingEngine.token_events`
    subscribers."""
    rid: int
    ids: np.ndarray                  # [P] int32 prompt
    max_new: int
    eos: int                         # -1 = never stop early
    submitted_it: int = 0
    tokens: list = field(default_factory=list)
    slot: Optional[int] = None
    finished_it: Optional[int] = None
    status: str = RequestStatus.QUEUED
    deadline: Optional[float] = None
    client_id: Any = None
    prefix: list = field(default_factory=list)
    submit_t: float = 0.0
    first_tok_t: Optional[float] = None
    priority: int = 0
    streamed: int = 0
    resumed: bool = False            # restored from a preempt snapshot
    # observability stamps (serving.tracing only; the tracer's clock, so
    # tests can inject a deterministic one) — the request's span-tree
    # boundaries: submit -> admission start -> admit dispatched ->
    # first token processed -> terminal; t_last_tok drives the
    # time-between-tokens histogram and is stamped ONCE per token at
    # the host-mirror drain (a TokenStream late-attach replay never
    # re-stamps it)
    t_trace: Optional[float] = None
    t_admit_start: Optional[float] = None
    t_prefill_done: Optional[float] = None
    t_first_tok: Optional[float] = None
    t_last_tok: Optional[float] = None

    @property
    def fill_ids(self):
        """What admission prefills: the prompt plus any resumed tokens."""
        if not self.prefix:
            return self.ids
        return np.concatenate(
            [self.ids, np.asarray(self.prefix, np.int32)])


class _PendingPrefill:
    """An admission in progress: the slot and its pages are reserved, the
    prompt streams chunk-by-chunk into them across scheduler iterations."""

    def __init__(self, req, slot, fill, start, chunk):
        self.req, self.slot = req, slot
        self.fill = fill                 # prompt + any resumed tokens
        self.fill_len = len(fill)
        # prefill starts at the shared-prefix boundary (chunk-aligned);
        # positions < start are served by shared pages
        self.start = start
        self.n_chunks = -(-(self.fill_len - start) // chunk)
        self.ids_pad = np.zeros((1, self.n_chunks * chunk), np.int32)
        self.ids_pad[0, :self.fill_len - start] = fill[start:]
        self.ci = 0                      # chunks handed to a dispatch
        # the logits [R, 1, V] of the dispatch that held the prompt's last
        # real position, and which of its rows that chunk rode
        self.sel = None
        self.sel_row = 0
        # speculative serving: the DRAFT model's single-lane prefill
        # cache (the prompt's K/V must land in the draft cache too)
        self.draft_lane = None
        # self-drafting: the last chunk's guess after the first sampled
        # token (device-side), the slot's first pending draft
        self.draft0 = None
        # expert models: the device-side load vector (slots._expert_load)
        # of each dispatch whose FIRST row was this prompt's, read when
        # the admit event is processed
        self.expert_loads = []


class _LanePool:
    """The DRAFT model's reusable single-lane prefill caches (speculative
    serving; the target prefills straight into its pages).  Several
    admissions can be in flight at once (the draft admit that consumes a
    lane is processed one event behind), so this is a pool, not a single
    workspace slot — with the same donated-and-dead liveness check
    ``KVCacheWorkspace`` does."""

    def __init__(self, module):
        self._module = module
        self._lanes = []

    def take(self, cache_len, dtype):
        while self._lanes:
            lane = self._lanes.pop()
            if not any(getattr(l, "is_deleted", lambda: False)()
                       for l in jax.tree.leaves(lane)):
                return lane
        return self._module.init_cache(1, cache_len, dtype=dtype)

    def give_back(self, lane):
        self._lanes.append(lane)

    def release(self):
        self._lanes.clear()


class ServingEngine:
    """Slot-based continuous batching over an :class:`InferenceEngine`.

    ``submit()`` enqueues a request and returns its id; ``step()`` runs one
    scheduler iteration; ``drain()`` loops until everything submitted has
    finished and returns ``{rid: np.ndarray}`` where each output follows
    the ``generate()`` contract ``[prompt..., generated...]`` of length
    ``len(prompt) + max_new_tokens`` (eos-padded past early stops — under
    greedy decoding, bitwise what ``engine.generate()`` returns for the
    same request solo)."""

    # The concurrency contract (docs/tpu_lint.md "Concurrency
    # contracts"): every mutable piece of scheduler state is declared
    # lock-guarded in serving/concurrency.py GUARDED_FIELDS — tpu-lint's
    # TL008 checks each source access statically, and
    # DSTPU_CONCURRENCY_CHECKS=1 asserts the lock is held at runtime
    # (__init__ tail below).

    def __init__(self, engine, monitor=None, draft_module=None,
                 draft_params=None, **overrides):
        assert engine.params is not None, \
            "no parameters: set_params/init_params first"
        cfg = getattr(engine._config, "serving", None) or ServingConfig()
        if overrides:
            cfg = ServingConfig(**{**cfg.model_dump(), **overrides})
        for key, val in (cfg.model_extra or {}).items():
            # the removed layout switches (``paged`` and its kernel A/B
            # knob) still arrive from config files, which keep unknown
            # keys: on is what the engine does anyway, off asks for
            # something it no longer has — refuse by name, never
            # silently serve another layout
            if key.startswith("paged") and not val:
                raise ValueError(
                    f"serving.{key}={val!r}: the lane KV layout and the "
                    f"gather A/B switch were removed — the slot engine "
                    f"has ONE KV cache, the page pool behind the Pallas "
                    f"paged kernels (docs/serving.md 'KV cache'); drop "
                    f"the key (true is accepted and ignored)")
        self.engine = engine
        self.module = engine.module
        self.config = cfg
        self.monitor = monitor
        self.num_slots = int(cfg.num_slots)
        if self.num_slots < 1:
            raise ValueError(f"serving.num_slots={cfg.num_slots}: need >= 1")
        # what the model declares (models/contract.py), read ONCE
        self.contract = slot_contract.read(self.module)
        self.chunk = admission_chunk(self.contract, cfg.prefill_chunk)
        self.speculative = bool(cfg.speculative)
        # self-drafting (``spec_draft_model: "mtp"``): the model's own
        # multi-token-prediction module drafts, from the pools' own last
        # layer — no draft model, no draft cache, no draft programs
        self.self_draft = self.speculative and \
            (cfg.spec_draft_model or "").strip() == "mtp"
        if self.self_draft and not self.contract.drafts_itself:
            raise ValueError(
                f"serving.spec_draft_model='mtp': "
                f"{type(self.module).__name__} has no multi-token-"
                f"prediction module to draft with")
        # ... or a separate draft MODEL with a lane cache of its own
        self.separate_draft = self.speculative and not self.self_draft
        # layers the drafting module adds to every dispatch (the pools'
        # last; the load vector's last rows), and a dispatch's in all
        self._draft_layers = self.contract.draft_layers \
            if self.self_draft else 0
        self._layers = self.contract.paged_layers + self._draft_layers
        # scheduler counters (docs/serving.md) — made before the cache
        # manager, which counts prefix hits and evictions into them
        self.stats = {"iterations": 0, "decode_calls": 0,  # guarded-by: _lock
                      "decode_tokens": 0, "prefill_tokens": 0,
                      "prefill_dispatches": 0, "prefill_rows": 0,
                      "completed": 0, "admitted": 0, "wall_secs": 0.0,
                      "sync_secs": 0.0, "shed": 0, "cancelled": 0,
                      "resumed": 0, "prefix_lookups": 0, "prefix_hits": 0,
                      "prefix_tokens_reused": 0, "page_evictions": 0,
                      "admission_stalls": 0, "prefill_budget_widened": 0,
                      "fairness_rejected": 0,
                      "paged_attention_fallback": 0,
                      "stream_bridge_drops": 0,
                      "lock_wait_scheduler_s": 0.0,
                      "lock_wait_handler_s": 0.0}
        # ---- KV cache (docs/serving.md "KV cache"): the page pool and
        # which pages back which slot.  Speculative serving skips prefix
        # sharing: the DRAFT cache has no page pool, so its prefill must
        # run from position 0 anyway — a shared target prefix would
        # leave the draft side unfilled ----
        self._pages = SlotPages(                 # guarded-by: _lock
            self.module, self.contract, self.num_slots, cfg.max_cache_len,
            cfg.page_size, cfg.num_pages, self.chunk,
            share_prefixes=cfg.prefix_cache and not self.speculative,
            stats=self.stats)
        if self.stats.get("prefix_sharing_refused"):
            logger.warning(
                f"serving.prefix_cache: {type(self.module).__name__} keeps "
                f"cache state a slot owns (a window ring, a fixed-size "
                f"state row) — a shared prefix's pages would not carry it, "
                f"so prefix sharing is OFF for this server "
                f"(stats['prefix_sharing_refused'])")
        self.page = self._pages.page
        self.num_pages = self._pages.num_pages
        self.pages_per_slot = self._pages.pages_per_slot
        # the shipped table's width (lane pages, then a window model's ring
        # pages) and the pools' constructor: geometry, fixed for good
        self.table_width = self._pages.table_width
        self._new_pools = self._pages.new_pools
        # ... held against the cache the model builds
        slot_contract.check(self.contract, self.module, self.page,
                            self.chunk, self._layers)
        # a model that counts its own attention work names the span args
        # to sum into ``stats`` (``work_counters``); the names are its own
        self._work_keys = tuple(self.contract.work_counters)
        self.stats.update(dict.fromkeys(self._work_keys, 0))
        # the slot's virtual lane: max_cache_len in whole pages
        self.cache_len = self._pages.cache_len
        max_seq = self.contract.max_seq_len
        if self.cache_len > max_seq:
            logger.warning(
                f"serving.max_cache_len={self.cache_len} exceeds the "
                f"model's max_seq_len={max_seq} — positions past it will "
                f"fault on learned position embeddings")
        if cfg.admission not in ("fcfs", "shortest_first"):
            raise ValueError(f"serving.admission={cfg.admission!r}: "
                             f"one of 'fcfs', 'shortest_first'")
        self.block = max(1, int(cfg.decode_block))
        # ---- network front end: priority lanes + fairness ----
        self.priority_lanes = int(cfg.priority_lanes)
        if self.priority_lanes < 1:
            raise ValueError(f"serving.priority_lanes="
                             f"{cfg.priority_lanes}: need >= 1")
        if float(cfg.priority_aging_s) < 0:
            raise ValueError(f"serving.priority_aging_s="
                             f"{cfg.priority_aging_s}: need >= 0")
        if float(cfg.fairness_tokens_per_s) > 0:
            from deepspeed_tpu.inference.serving.frontend.fairness import \
                FairnessTracker
            self._fairness = FairnessTracker(
                float(cfg.fairness_tokens_per_s),
                float(cfg.fairness_window_s))   # guarded-by: _lock
        else:
            self._fairness = None               # guarded-by: _lock
        # ---- speculative decoding (docs/serving.md "Speculative
        # decoding"): draft model + the fixed verify window ----
        self.spec_k = int(cfg.spec_k)
        if self.speculative:
            if cfg.do_sample:
                raise ValueError(
                    "serving.speculative=True requires greedy decoding "
                    "(do_sample=False): the verify-and-commit program's "
                    "bitwise contract is the target's greedy tokens — "
                    "lossless speculative SAMPLING is not implemented")
            if not 1 <= self.spec_k <= 64:
                raise ValueError(f"serving.spec_k={cfg.spec_k}: need "
                                 f"1 <= spec_k <= 64")
        if self.self_draft:
            if self.spec_k != 1 or draft_module is not None \
                    or draft_params is not None:
                raise ValueError(
                    "serving.spec_draft_model='mtp' drafts ONE token a "
                    "window with the model's own module: spec_k is 1 and "
                    "no draft model is passed")
        elif self.speculative:
            draft_module, draft_params = self._resolve_draft(
                engine, self.contract, cfg, draft_module, draft_params)
            self.draft_module = draft_module
            dvocab = slot_contract.read(draft_module).vocab_size
            tvocab = self.contract.vocab_size
            if dvocab != tvocab:
                raise ValueError(
                    f"draft model vocab_size={dvocab} != target "
                    f"vocab_size={tvocab} — speculative verification "
                    f"compares token ids, the vocabularies must match")

        # ---- expert models (docs/serving.md "Expert models"): the slot
        # programs mask dead lanes and padded chunk tails out of the
        # dropless routing and return the expert load ----
        self.routed = self.contract.routes_experts
        if self.routed:
            if self.speculative and not self.self_draft:
                raise ValueError(
                    "serving.speculative=True with a separate draft model "
                    "is not implemented for a model with expert layers "
                    "(the verify program does not route; a model that "
                    "drafts for itself — spec_draft_model='mtp' — routes "
                    "and counts its window's rows)")
            if getattr(engine, "_quantizer", None) is not None:
                raise ValueError(
                    "weight quantization is not implemented for a model "
                    "with expert layers: the expert kernel reads its "
                    "weights as the program holds them, a dequantized "
                    "copy would be materialized every step")

        from deepspeed_tpu.inference.engine import (KVCacheWorkspace,
                                                    build_sample_fn)
        sample_fn = build_sample_fn(bool(cfg.do_sample),
                                    float(cfg.temperature),
                                    int(cfg.top_k), float(cfg.top_p))
        sampling_key = (bool(cfg.do_sample), float(cfg.temperature),
                        int(cfg.top_k), float(cfg.top_p))
        # which attention-kernel mode each program class dispatches
        # through (ops/transformer/registry.py — the same capability
        # probes the traced programs take, so bench records /
        # prefill_plan reasons attribute the path that actually ran)
        from deepspeed_tpu.ops.transformer.registry import (
            kernel_modes as _registry_modes)
        self.kernel_modes = _registry_modes(
            paged=True, has_bias=self.contract.attention_bias)
        # ... and, of a model whose window layers keep K/V rings beside the
        # K/V pages, those layers' own
        self.ring_kernel_modes = _registry_modes(
            paged=True, has_bias=self.contract.attention_bias,
            has_window=True, ring=True) \
            if self.contract.kv_pages and self._pages.ring_pages else None
        # ... and the form the chunk program's K/V write takes, by the
        # predicate the traced write asks.  A stat, not a third key of
        # kernel_modes: callers compare that dict whole
        form = chunk_write_form(self.contract, self.chunk, self.page)
        if form is not None:
            self.stats["chunk_write"] = form
        # chunk rows a prefill dispatch takes (docs/serving.md "Prefill
        # dispatches"): what the chunk kernel's bound holds of the chunk
        # the user set, 1 where rows would depend on each other through
        # more than the K/V pages — observed, not set
        self.chunk_rows = chunk_rows(self.contract, self.chunk, self.page,
                                     self.speculative)
        self._decode_fn = self._propose_fn = self._verify_fn = None
        self._draft_chunk_fn = self._draft_admit_fn = None
        # Page tables are traced arguments (rebuilt host-side per
        # dispatch), so page churn/sharing never mints a new executable
        # — exactly ONE decode signature per server lifetime.
        self._spec_fn = None
        if self.self_draft:
            self._spec_fn = make_spec_block_fn(
                self.module, self.contract, sample_fn, engine._deq,
                self.block, self.cache_len)
            engine._tags[id(self._spec_fn)] = (
                "serving_spec_block", self.num_slots, self.num_pages,
                self.page, self.block, sampling_key)
        elif self.speculative:
            self._verify_fn = make_spec_verify_fn(
                self.module, sample_fn, engine._deq, self.spec_k,
                self.cache_len)
            engine._tags[id(self._verify_fn)] = (
                "serving_spec_verify", self.num_slots, self.num_pages,
                self.page, self.spec_k, sampling_key)
        else:
            self._decode_fn = make_decode_block_fn(
                self.module, self.contract, sample_fn, engine._deq,
                self.block, self.cache_len)
            engine._tags[id(self._decode_fn)] = (
                "serving_decode", self.num_slots, self.num_pages,
                self.page, self.block, sampling_key)
        self._admit_fn = make_admit_fn(sample_fn, self.chunk_rows,
                                       self.self_draft)
        engine._tags[id(self._admit_fn)] = (
            "serving_admit", self.num_slots, sampling_key)
        if self.separate_draft:
            # the draft side: one propose program, one draft prefill
            # chunk, one draft lane insert — the draft KV cache is
            # monolithic lanes [L_d, num_slots, cache_len, ...], the
            # last user of that layout (ROADMAP D15)
            self._draft_deq = engine._deq \
                if draft_module is self.module else None
            self._propose_fn = make_draft_propose_fn(
                draft_module, self._draft_deq, self.spec_k,
                self.cache_len)
            self._draft_chunk_fn = make_draft_chunk_fn(draft_module,
                                                       self._draft_deq)
            self._draft_admit_fn = make_draft_admit_fn()
            engine._tags[id(self._propose_fn)] = (
                "serving_spec_propose", self.num_slots, self.cache_len,
                self.spec_k)
            engine._tags[id(self._draft_chunk_fn)] = (
                "serving_spec_draft_prefill", self.chunk)
            engine._tags[id(self._draft_admit_fn)] = (
                "serving_spec_draft_admit", self.num_slots,
                self.cache_len)
        # The serving programs must NOT be reloaded from either
        # persistent cache layer (serialized-executable store OR the XLA
        # disk cache): they chain one donated slot workspace across three
        # different programs (prefill chunks -> admit -> decode blocks),
        # and running ANY of them from a cross-process reloaded artifact
        # nondeterministically corrupts the slot cache — wrong tokens,
        # cross-lane mixing, one lane's KV clobbered the moment another
        # lane admits — or segfaults outright (reproduced and bisected
        # with the serving kill-harness driver: cache-less runs are 100%
        # stable, warm runs flake at ~25-50%; the train and whole-batch
        # generate paths show no such failures and keep both layers).
        # Each server process compiles its three serving programs once —
        # the one-decode-executable-per-server-lifetime invariant is
        # untouched, and overload/drain/resume cycles mint no further
        # executables (tests/unit/test_serving_slo.py).
        # Prefill writes straight into the slot's pool pages (the pool
        # chains chunk -> decode by donation).
        self._chunk_fn = make_chunk_fn(self.module, self.contract,
                                       engine._deq, self.self_draft)
        engine._tags[id(self._chunk_fn)] = (
            "serving_prefill", self.chunk, self.page, self.chunk_rows)
        # ... each under the name its dispatch span carries, which its
        # compile span (dstpu.setup.compile) carries as ``program``
        for fn, program in ((self._decode_fn, "decode"),
                            (self._admit_fn, "admit"),
                            (self._chunk_fn, "prefill_chunk"),
                            (self._verify_fn, "spec_verify"),
                            (self._spec_fn, "spec_block"),
                            (self._propose_fn, "spec_propose"),
                            (self._draft_chunk_fn, "draft_prefill_chunk"),
                            (self._draft_admit_fn, "draft_admit")):
            if fn is not None:
                engine._persist_opt_out.add(id(fn))
                engine._programs[id(fn)] = program

        if self.separate_draft:
            self._draft_params = draft_params
            self._draft_ws = KVCacheWorkspace(self.draft_module)
            self._draft_lanes = _LanePool(self.draft_module)  # guarded-by: _lock
            self._draft_cache = None                          # guarded-by: _lock
        self._cache = None               # the pool buffer  # guarded-by: _lock
        self._state = None               # device-resident state  # guarded-by: _lock
        # host mirror of slot occupancy, updated as events are PROCESSED
        # (it lags the device by the in-flight events — by design)
        self._mirror_active = np.zeros((self.num_slots,), bool)  # guarded-by: _lock
        self._slots = [None] * self.num_slots      # guarded-by: _lock
        self._free = deque(range(self.num_slots))  # guarded-by: _lock
        self._queue = deque()                      # guarded-by: _lock
        self._pending = None                       # guarded-by: _lock
        # dispatched-but-unprocessed device work, processed FIFO one
        # event behind the newest dispatch: ("decode", toks_dev, [load]) |
        # ("admit", req, slot, first_dev, draft_lane, [loads])
        self._events = deque()                     # guarded-by: _lock
        self._rng = jax.random.key(int(cfg.seed))  # guarded-by: _lock
        self._next_rid = 0                         # guarded-by: _lock
        self._it = 0                               # guarded-by: _lock
        # ---- robustness / SLO state (docs/serving.md) ----
        if cfg.queue_policy not in ("reject", "block"):
            raise ValueError(f"serving.queue_policy={cfg.queue_policy!r}: "
                             f"one of 'reject', 'block'")
        self._requests = {}              # all known  # guarded-by: _lock
        self._results = {}               # terminal   # guarded-by: _lock
        self._pending_reports = {}       # -> step()  # guarded-by: _lock
        # ---- threading model (docs/serving.md "Network front end") ----
        # ONE lock guards every piece of mutable scheduler state (queue,
        # requests/results maps, slot mirror, stats, streams): submit()/
        # cancel()/status()/result()/token_events() are safe from any
        # thread.  step()/drain()/preempt() additionally enforce a
        # single SCHEDULER OWNER thread (_check_owner): the host mirror,
        # the in-flight event deque and the donated-buffer chain assume
        # exactly one driver, and a second thread racing the mirror
        # would corrupt slot bookkeeping even under the lock (the lag-
        # one protocol is stateful across calls).  _cond lets blocked
        # submit()s (queue_policy="block" from a non-owner thread) wait
        # for the owner's next step instead of stepping themselves.
        # the engine lock also meters wall time spent waiting on it per
        # thread class — Serving/lock_wait_s + /metrics (concurrency.py)
        self._lock = InstrumentedRLock()
        self._cond = threading.Condition(self._lock)
        self._owner_thread = None        # first step()  # guarded-by: _lock
        self._streams = {}               # rid->[stream]  # guarded-by: _lock
        # set by submit()/restore() so an idle scheduler-owner loop
        # (frontend/transport.py) can sleep instead of busy-polling
        self.wake = threading.Event()
        self._breaker = CircuitBreaker(cfg.breaker_threshold,
                                       cfg.breaker_cooldown_s)
        self._closed = False             # guarded-by: _lock
        self._close_report = []          # undrained rids  # guarded-by: _lock
        # what a PREEMPTED request's stream ends with: such a request has no
        # result record, and a stream subscribed after the preemption (a
        # handler that lost the race under load) replays its end from here
        self._preempt_detail = ""
        self._snap_seq = 0               # snapshot lineage  # guarded-by: _lock
        self._slot_last_dispatch = {}    # slot -> mono t  # guarded-by: _lock
        if self.speculative:
            # speculative-decoding observability (docs/serving.md
            # "Speculative decoding"): windows = (dispatch x live slot)
            # verify opportunities, each committing 1..spec_k+1 tokens;
            # accept_rate = accepted draft tokens / proposed draft
            # tokens; draft/verify secs are host dispatch wall time.
            # Every key is exported as a dstpu_serving_spec_* gauge by
            # /metrics (the stats sweep) and as Serving/spec_* monitor
            # events (_emit_metrics).
            self.stats.update({
                "spec_rounds": 0, "spec_windows": 0,
                "spec_committed_tokens": 0, "spec_accept_rate": 0.0,
                "spec_tokens_per_dispatch": 0.0,
                "spec_draft_secs": 0.0, "spec_verify_secs": 0.0,
                "spec_draft_fraction": 0.0})
            if self.self_draft:
                # drafts offered (one a window), drafts the target
                # reproduced AND committed, and verify rows whose token
                # was not committed — the work speculation wasted
                self.stats.update({"spec_proposed": 0, "spec_accepted": 0,
                                   "spec_rows_rejected": 0})
                # what the windows read since the last dispatch came to:
                # the next dispatch span's args (the mirror lags by one)
                self._spec_unreported = dict(
                    windows=0, proposed=0, accepted=0,
                    rows_rejected=0)            # guarded-by: _lock
        if self.routed:
            # expert load (docs/observability.md): (token, expert)
            # assignments the expert layers computed, expert-weight reads
            # (experts with a live token, per layer per call), the busiest
            # expert's tokens summed likewise — and, outside ``stats``
            # (every value there is one /metrics gauge), the assignments
            # by expert layer and expert
            self.stats.update({"moe_assignments": 0,
                               "moe_experts_touched": 0,
                               "moe_max_expert_tokens": 0})
            # a model that holds a share of the experts also reports the
            # choices that fell on the experts other chips hold
            # ... and one whose router has zero-compute outputs, the
            # choices that fell on those (the load vector's columns after
            # the held experts', ``contract.load_columns``)
            self._moe_columns = tuple(
                {"elsewhere": "moe_assignments_elsewhere",
                 "zero": "moe_zero_picks"}[c]
                for c in self.contract.load_columns)
            for key in self._moe_columns:
                self.stats[key] = 0
            # the load vector's rows: the model's expert layers, then —
            # self-drafting — its drafting module's
            self.moe_expert_tokens = np.zeros(
                (self.contract.expert_layers + self._draft_layers,
                 self.contract.experts), np.int64)  # guarded-by: _lock
        # the slot-occupancy trace the correctness test asserts
        # EOS-mid-flight retirement against
        self.occupancy_trace = []        # (it, n_active)  # guarded-by: _lock
        # ---- observability layer (docs/observability.md): span tracer
        # + histograms + flight recorder.  All default-off = seed
        # behavior; all host-side (zero new jitted programs — the
        # zero-new-executables proof covers the tracing-on path too).
        self.tracing = bool(cfg.tracing)
        if self.tracing:
            # the process's ONE tracer (monitor/trace.py): every layer's
            # span() mirrors into its ring, and it stays readable through
            # monitor.trace.tracer() after this engine is closed
            self._tracer = span_trace.enable(int(cfg.trace_max_spans))  # guarded-by: _lock
            # histograms carry their own per-bucket locks (the /metrics
            # scrape renders them WITHOUT the engine lock); the
            # InstrumentedRLock observer feeds per-acquire lock waits
            # straight into the lock-wait family
            self._hist = ServingHistograms()
            self._lock.on_wait = self._hist.lock_wait.observe
        else:
            self._tracer = None          # guarded-by: _lock
            self._hist = None
        self._inject_observer = None
        self._memwatch = None            # guarded-by: _lock
        if cfg.flight_recorder:
            # the ring is guarded by its OWN lock (flightrec.py): the
            # hot path appends without contending readers, and crash
            # paths (/debug/flightrec, SIGUSR2, ConcurrencyViolation)
            # read without the engine lock
            self._flightrec = FlightRecorder(
                int(cfg.flight_recorder_events),
                dump_dir=cfg.flight_recorder_dir or None)
            fr = self._flightrec
            self._inject_observer = inject.add_fire_observer(
                lambda point, action, hit: fr.record(
                    "fault_injection", point=point, action=action,
                    hit=hit))
        else:
            self._flightrec = None
        if cfg.memory_telemetry:
            # live HBM telemetry (docs/observability.md "Device memory
            # & roofline"): host-side sampler over the accelerator's
            # canonical memory reader, owner-reconciled against this
            # engine's known buffers; rides the flight recorder when
            # that is on.  Zero new executables — memory_stats() is a
            # PJRT host call
            from deepspeed_tpu.monitor.memwatch import DeviceMemorySampler
            self._memwatch = DeviceMemorySampler(
                interval_s=float(cfg.memory_sample_interval_s),
                owners_fn=self._device_memory_owners,
                flightrec=self._flightrec)
            self.stats.update({
                "hbm_bytes_in_use": 0, "hbm_peak_bytes": 0,
                "hbm_limit_bytes": 0, "hbm_owned_bytes": 0,
                "hbm_unattributed_bytes": 0, "memory_samples": 0})
        # classify lock waiters as scheduler vs handler; the ref is read
        # AFTER a successful acquire, i.e. lock-held (concurrency.py)
        self._lock._owner_ref = \
            lambda: object.__getattribute__(self, "_owner_thread")
        if checks_enabled():
            # DSTPU_CONCURRENCY_CHECKS=1: every guarded-field access now
            # asserts the lock is held — the runtime half of TL008, the
            # interleaving stress harness drives serving traffic with
            # this armed (tools/lint/interleave_check.py)
            install_concurrency_checks(self)

    @staticmethod
    def _resolve_draft(engine, target, cfg, draft_module, draft_params):
        """The draft model behind ``serving.speculative``: an explicitly
        passed ``(draft_module, draft_params)`` pair wins;
        ``spec_draft_model="self"`` drafts with the target model itself
        (accept rate 1.0 under greedy — the dispatch/batched-verify
        ceiling, at the cost of a second full-size KV cache and a
        doubled decode forward); an OPT preset name builds the
        architecture against the target's vocab (``target``: its
        contract) and uses the given
        ``draft_params`` — or RANDOM weights with a loud warning
        (accept rate ~0; smoke/bench floor only).  Float draft params
        are cast to the engine's compute dtype like ``set_params``
        does."""
        if draft_module is None:
            name = (cfg.spec_draft_model or "").strip()
            if name == "self":
                if draft_params is not None:
                    raise ValueError(
                        "spec_draft_model='self' drafts with the TARGET "
                        "model's own weights, but draft_params was also "
                        "passed — silently ignoring them would run the "
                        "wrong draft; pass draft_module with those "
                        "params, or drop one of the two")
                return engine.module, engine._params
            if not name:
                raise ValueError(
                    "serving.speculative=True needs a draft model: pass "
                    "engine.serve(draft_module=..., draft_params=...) "
                    "or set serving.spec_draft_model ('self' = the "
                    "target drafts for itself; docs/serving.md "
                    "'Speculative decoding')")
            from deepspeed_tpu.models.opt import opt_model
            draft_module = opt_model(
                name, vocab_size=target.vocab_size,
                max_seq_len=max(target.max_seq_len, int(cfg.max_cache_len)),
                dtype=target.dtype)
            if draft_params is None:
                logger.warning(
                    f"serving.spec_draft_model={name!r} with no "
                    f"draft_params — RANDOM draft weights: the accept "
                    f"rate will be ~0 and speculation will SLOW decode; "
                    f"pass trained weights via "
                    f"engine.serve(draft_params=...)")
                draft_params = draft_module.init(
                    jax.random.key(0),
                    {"input_ids": jnp.zeros((1, 8), jnp.int32)})
        elif draft_params is None:
            raise ValueError("draft_module passed without draft_params")
        if draft_params is engine._params:
            return draft_module, draft_params
        # cast AND place replicated on the engine mesh (set_params'
        # discipline): unplaced draft params would compile the whole
        # draft program chain single-device, and its committed outputs
        # would then clash with the mesh-replicated slot state the
        # target programs produce
        from jax.sharding import NamedSharding, PartitionSpec
        cast = engine.compute_dtype
        put = jax.jit(
            lambda t: jax.tree.map(
                lambda p: p.astype(cast)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, t),
            out_shardings=NamedSharding(engine.mesh, PartitionSpec()))
        return draft_module, put(draft_params)

    # ------------------------------------------------------------------ #
    # Observability: span tracing, flight recorder, histograms
    # (docs/observability.md) — host bookkeeping only, all default-off
    # ------------------------------------------------------------------ #
    def _count_work(self, args):  # lock-held: _lock
        """Sum a dispatch's attention-work counters into ``stats`` and hand
        the span's args back."""
        for key in self._work_keys:
            self.stats[key] += args.get(key, 0)
        return args

    @contextmanager
    def _observe_dispatch(self, program, **args):  # lock-held: _lock
        """Record one device dispatch at its scheduler seam: the span
        ``dstpu.sched.dispatch.<program>`` (always a profiler
        annotation; on the ring's scheduler track under tracing), a
        dispatch-duration histogram sample (tracing) and a
        ``dispatch_begin``/``dispatch_end`` (or ``dispatch_error``)
        event pair (flight recorder) — ONE timing, the span's, for all
        three.  The measured duration is the HOST dispatch call — the
        async-dispatch cost the latency-hiding protocol is built around
        — never a device sync."""
        fr = self._flightrec
        if fr is not None:
            fr.record("dispatch_begin", program=program, **args)
        try:
            with span("dstpu.sched.dispatch." + program, track="scheduler",
                      cat="dispatch", program=program, **args) as sp:
                yield
        except BaseException as e:
            if fr is not None:
                fr.record("dispatch_error", program=program,
                          error=f"{type(e).__name__}: {e}"[:200], **args)
            raise
        if self._hist is not None:
            self._hist.dispatch.observe(program, sp.dur_s)
        if fr is not None:
            fr.record("dispatch_end", program=program,
                      dur_s=round(sp.dur_s, 6), **args)

    def _trace_done(self, req, status):  # lock-held: _lock
        """Terminal-time tracing: compute the request's latency
        breakdown (the :class:`~.slo.RequestResult` fields — segments
        between the stamped span boundaries, the LAST reached phase
        absorbing the remainder, so the parts always sum to
        ``latency_s`` exactly) and emit its span tree onto its slot
        track (requests that never reached a slot land on the ``queue``
        track).  With tracing off only ``queue_s`` is known — submit to
        admission start (or to the end, for a request that never left
        the queue), two ``time.monotonic()`` stamps."""
        tr = self._tracer
        if tr is None or req.t_trace is None:
            t_adm = req.t_admit_start if req.t_admit_start is not None \
                else time.monotonic()
            return {"queue_s": max(t_adm - req.submit_t, 0.0)}
        t_end = tr.now()
        t_sub = req.t_trace
        bd = {"latency_s": max(t_end - t_sub, 0.0)}
        prev = t_sub
        for name, nxt in (("queue_s", req.t_admit_start),
                          ("prefill_s", req.t_prefill_done),
                          ("host_s", req.t_first_tok),
                          ("decode_s", t_end)):
            if nxt is None:              # ended mid-phase: absorb rest
                bd[name] = max(t_end - prev, 0.0)
                break
            bd[name] = max(nxt - prev, 0.0)
            prev = nxt
        track = req.slot if req.slot is not None else "queue"
        cid = None if req.client_id is None else str(req.client_id)
        tr.add("request", "request", t_sub, t_end, track=track,
               rid=req.rid, client_id=cid, slot=req.slot,
               priority=req.priority, status=status,
               tokens=len(req.tokens))
        tr.add("queue", "phase", t_sub,
               t_end if req.t_admit_start is None else req.t_admit_start,
               track=track, rid=req.rid, phase="queue")
        if req.t_admit_start is not None:
            tr.add("prefill", "phase", req.t_admit_start,
                   t_end if req.t_prefill_done is None
                   else req.t_prefill_done,
                   track=track, rid=req.rid, phase="prefill")
        if req.t_prefill_done is not None:
            # admit dispatched -> first token PROCESSED at the drain
            # point: the lag-one window RequestResult books as host_s
            tr.add("first_token_lag", "phase", req.t_prefill_done,
                   t_end if req.t_first_tok is None else req.t_first_tok,
                   track=track, rid=req.rid, phase="first_token_lag")
        if req.t_first_tok is not None:
            tr.add("decode", "phase", req.t_first_tok, t_end,
                   track=track, rid=req.rid, phase="decode",
                   tokens=len(req.tokens))
        return bd

    def _flight_dump(self, reason):
        """Best-effort auto-dump: a failing dump must never mask the
        distress being recorded.  Returns the dump path or ``None``."""
        fr = self._flightrec
        if fr is None:
            return None
        try:
            path = fr.dump(reason)
            logger.warning(f"serving flight recorder dumped to {path} "
                           f"({reason})")
            return path
        except Exception as e:           # noqa: BLE001
            logger.warning(f"serving flight-recorder dump failed "
                           f"({reason}): {type(e).__name__}: {e}")
            return None

    def _detach_observability(self):  # lock-held: _lock
        """Engine retirement (close/preempt): unhook the process-global
        fault-injection observer and flush the monitor so short-lived
        serving processes never drop tail events."""
        if self._inject_observer is not None:
            inject.remove_fire_observer(self._inject_observer)
            self._inject_observer = None
        mon = self.monitor
        flush = getattr(mon, "flush", None)
        if callable(flush):
            try:
                flush()
            except Exception as e:       # noqa: BLE001
                logger.warning(f"serving monitor flush on retirement "
                               f"failed: {type(e).__name__}: {e}")

    def dump_trace(self, path):
        """Write the span ring as Chrome trace-event JSON to ``path``
        (Perfetto / ``chrome://tracing`` loadable: one track per slot
        plus scheduler/queue/handler tracks; ``docs/observability.md``).
        Raises with ``serving.tracing`` off.  Thread-safe — only the
        ring COPY happens under the engine lock; rendering and writing
        (tens of MB on a full ring) run outside it, so a live
        scheduler is never stalled for the serialization."""
        with self._lock:
            if self._tracer is None:
                raise RuntimeError(
                    "dump_trace(): serving.tracing is off — enable it "
                    "to record spans (docs/observability.md)")
            tracer = self._tracer
            snap = tracer.span_snapshot()    # (spans, added), lock-held
        return tracer.dump(path, spans=snap)

    def last_lock_wait_s(self):
        """The calling thread's newest engine-lock wait, in seconds —
        right after ``submit()`` returns, that submit's own wait (the
        front end books it on its ``dstpu.frontend.submit`` span).
        Thread-local; takes no lock."""
        return self._lock.last_wait_s

    def histograms(self):
        """The :class:`~deepspeed_tpu.monitor.trace.ServingHistograms`
        set (``None`` with ``serving.tracing`` off).  Internally locked
        — ``/metrics`` renders it without the engine lock."""
        return self._hist

    # ------------------------------------------------------------------ #
    # Device-memory telemetry (docs/observability.md "Device memory &
    # roofline") — host-side, serving.memory_telemetry, default off
    # ------------------------------------------------------------------ #
    def _device_memory_owners(self):  # lock-held: _lock
        """Bytes of every device buffer this engine can NAME — what the
        sampler reconciles against the accelerator-reported device
        total; the gap is the unattributed-bytes gauge.  Owner figures
        are ``nbytes`` sums (no device sync)."""
        from deepspeed_tpu.monitor.memwatch import tree_device_bytes
        owners = {"params": tree_device_bytes(self.engine._params),
                  "page_pool": tree_device_bytes(self._cache),
                  "slot_state": tree_device_bytes(self._state)}
        if self.separate_draft:
            owners["draft_kv"] = tree_device_bytes(self._draft_cache) \
                + tree_device_bytes(self._draft_lanes._lanes)
            if self._draft_params is not self.engine._params:
                owners["draft_params"] = \
                    tree_device_bytes(self._draft_params)
        return owners

    def _sample_memory(self):  # lock-held: _lock
        """The scheduler-seam sampling hook: interval-gated; folds the
        newest sample into ``stats`` (peak is monotone — the serving
        run's HBM watermark)."""
        if self._memwatch is None:
            return
        sample = self._memwatch.maybe_sample()
        if sample is not None:
            self._sample_memory_into_stats(sample)

    def memory_snapshot(self):
        """One locked on-demand device-memory sample (owner-reconciled)
        — ``None`` with ``serving.memory_telemetry`` off.  Thread-safe;
        ``/metrics`` renders the gauges from this."""
        with self._lock:
            if self._memwatch is None:
                return None
            sample = self._memwatch.sample()
            self._sample_memory_into_stats(sample)
            return sample

    def _sample_memory_into_stats(self, sample):  # lock-held: _lock
        st = self.stats
        st["hbm_bytes_in_use"] = sample["bytes_in_use"]
        st["hbm_peak_bytes"] = max(st["hbm_peak_bytes"],
                                   sample["peak_bytes_in_use"],
                                   sample["bytes_in_use"])
        st["hbm_limit_bytes"] = sample["bytes_limit"]
        st["hbm_owned_bytes"] = sample["owned_bytes"]
        st["hbm_unattributed_bytes"] = sample["unattributed_bytes"]
        st["memory_samples"] = self._memwatch.samples

    @property
    def flightrec_enabled(self):
        """Cheap enabled predicate — use this for gating, not
        :meth:`flightrec_snapshot` (which copies the whole ring)."""
        return self._flightrec is not None

    def flightrec_snapshot(self):
        """Point-in-time copy of the flight-recorder ring (``None``
        when ``serving.flight_recorder`` is off).  Never takes the
        engine lock."""
        fr = self._flightrec
        return None if fr is None else fr.snapshot()

    def dump_flightrec(self, reason="manual", path=None):
        """Dump the flight-recorder ring to a JSON file (default: under
        ``serving.flight_recorder_dir``); returns the path.  Raises
        with ``serving.flight_recorder`` off.  Never takes the engine
        lock — callable from signal handlers and crash paths."""
        fr = self._flightrec
        if fr is None:
            raise RuntimeError(
                "dump_flightrec(): serving.flight_recorder is off — "
                "enable it to record events (docs/observability.md)")
        return fr.dump(reason, path=path)

    # ------------------------------------------------------------------ #
    # Public API
    # ------------------------------------------------------------------ #
    def submit(self, input_ids, max_new_tokens=32, eos_token_id=-1,
               deadline_s=None, client_id=None, priority=0):
        """Enqueue one prompt; returns the request id.  The request must
        fit a slot's virtual lane: ``ceil(P/chunk)*chunk <= max_cache_len``
        (chunked prefill writes the padded tail) and ``P + max_new_tokens
        <= max_cache_len`` — and the page pool must be able to hold it.

        ``deadline_s`` (seconds from now; ``None`` = the config's
        ``default_deadline_s``, ``0`` = already expired): past it the
        request is SHED from the queue before ever occupying a slot, or
        retired at the next scheduling point once in a slot — terminal
        status ``SHED_DEADLINE``.  ``client_id`` is an opaque correlation
        value round-tripped through results and preemption snapshots
        (snapshots store it as JSON: non-serializable values are
        stringified, tuples come back as lists); with fairness enabled
        (``serving.fairness_tokens_per_s > 0``) it is also the accounting
        key — an over-budget client's submit raises
        :class:`~.slo.QueueFull` (HTTP 429) until its window decays.
        ``priority`` is the admission lane, ``0 <= priority <
        serving.priority_lanes`` with 0 the most urgent; queued requests
        age one lane per ``serving.priority_aging_s`` seconds so low
        priority cannot starve.

        Thread-safe: any thread may submit (the engine lock serializes it
        against the scheduler owner's ``step()``).

        Raises :class:`~.slo.QueueFull` when the bounded queue is at
        ``max_queue_depth`` under the ``reject`` policy (``block`` runs
        scheduler iterations inline when called from the scheduler-owner
        thread, and waits for the owner to free a spot otherwise), and
        :class:`~.slo.CircuitOpen` while the dispatch breaker is open."""
        inject.fire("serving.pre_submit_lock")
        with self._lock:
            rid = self._submit_locked(input_ids, max_new_tokens,
                                      eos_token_id, deadline_s, client_id,
                                      priority)
        self.wake.set()                  # rouse an idle scheduler thread
        return rid

    def _submit_locked(self, input_ids, max_new_tokens, eos_token_id,  # lock-held: _lock
                       deadline_s, client_id, priority):
        if self._closed:
            raise RuntimeError(
                "submit() on a closed ServingEngine — close() retired it; "
                "create a fresh server with engine.serve()")
        ids = np.asarray(input_ids, np.int32).reshape(-1)
        P = int(ids.shape[0])
        max_new = int(max_new_tokens)
        if P < 1:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new_tokens={max_new}: need >= 1")
        priority = int(priority)
        if not 0 <= priority < self.priority_lanes:
            raise ValueError(
                f"priority={priority}: need 0 <= priority < "
                f"serving.priority_lanes={self.priority_lanes} "
                f"(0 = most urgent)")
        padded = -(-P // self.chunk) * self.chunk
        # speculative serving reserves spec_k-1 tail positions per lane:
        # a live lane's last verify window writes K/V for up to spec_k
        # draft tokens past its final committed position (overwritten or
        # never attended — but they must land INSIDE the lane)
        spec_tail = (self.spec_k - 1) if self.speculative else 0
        need = max(P + max_new + spec_tail, padded)
        if need > self.cache_len:
            raise ValueError(
                f"request needs {need} cache positions (prompt {P} + new "
                f"{max_new}"
                + (f" + speculative window reserve {spec_tail}"
                   if spec_tail else "")
                + f", chunk-padded {padded}) but slot lanes hold "
                f"{self.cache_len} — raise serving.max_cache_len or split "
                f"the request")
        too_big = self._pages.cannot_hold(need)
        if too_big:
            # (the per-request check above only bounds it against the
            # virtual lane)
            raise ValueError(f"request needs {too_big} — raise "
                             f"serving.num_pages or split the request")
        self._breaker.check_submit()         # reject-with-reason when open
        if self._fairness is not None and not self._fairness.allow(client_id):
            self.stats["fairness_rejected"] += 1
            if self._flightrec is not None:
                self._flightrec.record(
                    "fairness_reject",
                    client_id=None if client_id is None
                    else str(client_id))
            raise QueueFull(
                f"client {client_id!r} is over its fairness budget "
                f"({self._fairness.usage(client_id):.0f} window tokens "
                f">= {self._fairness.budget:.0f}) — retry after the "
                f"window decays (HTTP 429; docs/serving.md 'Network "
                f"front end')")
        self._apply_backpressure()
        if deadline_s is None and self.config.default_deadline_s > 0:
            deadline_s = self.config.default_deadline_s
        deadline = None if deadline_s is None \
            else time.monotonic() + float(deadline_s)
        req = ServeRequest(self._next_rid, ids, max_new, int(eos_token_id),
                           submitted_it=self._it, deadline=deadline,
                           client_id=client_id, submit_t=time.monotonic(),
                           priority=priority)
        self._next_rid += 1
        self._queue.append(req)
        self._requests[req.rid] = req
        if self._tracer is not None:
            # the span-tree root's start; submissions arrive on client
            # threads, so the instant marker lands on the handler track
            req.t_trace = self._tracer.now()
            self._tracer.add("submit", "request", req.t_trace,
                             track="handler", rid=req.rid,
                             priority=priority,
                             client_id=None if client_id is None
                             else str(client_id))
        if self._flightrec is not None:
            self._flightrec.record(
                "submit", rid=req.rid, prompt_len=P, max_new=max_new,
                priority=priority,
                client_id=None if client_id is None else str(client_id))
        return req.rid

    def _apply_backpressure(self):  # lock-held: _lock
        depth = int(self.config.max_queue_depth)
        if not depth or len(self._queue) < depth:
            return
        if self.config.queue_policy == "reject":
            raise QueueFull(
                f"serving queue at max_queue_depth={depth} "
                f"(policy=reject) — retry later or raise the bound")
        if self._owner_thread is not None \
                and self._owner_thread is not threading.current_thread():
            # block, from a NON-owner thread (an HTTP handler): wait for
            # the owner's step() to free a spot — stepping here would
            # race the host mirror.  _cond releases the engine lock while
            # waiting, so the owner keeps scheduling.
            while len(self._queue) >= depth:
                if self._closed:
                    raise RuntimeError(
                        "submit() on a closed ServingEngine — close() "
                        "retired it while this submit was blocked")
                if self._no_block_progress():
                    raise QueueFull(
                        f"serving queue at max_queue_depth={depth} and "
                        f"the blocked submit cannot make progress: "
                        f"{self._breaker.last_error or 'circuit open'}")
                self._cond.wait(timeout=0.05)
            return
        # block, from the owner (or a not-yet-owned engine): run the
        # scheduler inline until a spot frees.  Progress is guaranteed
        # while anything can retire or admit; an open breaker with an
        # idle scheduler cannot make progress — reject then.
        while len(self._queue) >= depth:
            if self._no_block_progress():
                raise QueueFull(
                    f"serving queue at max_queue_depth={depth} and the "
                    f"blocked submit cannot make progress: "
                    f"{self._breaker.last_error or 'circuit open'}")
            self.step()

    def _no_block_progress(self):  # lock-held: _lock
        return self._breaker.open and not self._breaker.allow_dispatch() \
            and not (self._events or self._mirror_active.any()
                     or self._pending is not None)

    def _known(self, rid, what):  # lock-held: _lock
        """The :class:`ServeRequest` for ``rid``, or a CLEAR ``KeyError``
        for ids this server never issued — a typo'd/stale rid must fail
        loudly, not look like a still-running request."""
        req = self._requests.get(rid)
        if req is None:
            raise KeyError(
                f"unknown request id {rid!r} — {what} on a request this "
                f"server never issued (submit() returned the valid ids)")
        return req

    def cancel(self, rid):
        """Client cancellation.  A queued request is retired immediately
        (never occupies a slot); an in-slot request is retired at this
        scheduling point — its slot returns to the free list and any
        tokens still in flight for it are discarded.  Terminal status
        ``CANCELLED``.  Returns ``False`` for already-terminal (or
        preempted) requests; raises ``KeyError`` for ids this server
        never issued.  Thread-safe."""
        inject.fire("serving.pre_cancel_lock")
        with self._lock:
            req = self._known(rid, "cancel()")
            if req.status in TERMINAL_STATUSES \
                    or req.status == RequestStatus.PREEMPTED:
                return False
            self.stats["cancelled"] += 1
            if req in self._queue:
                self._queue.remove(req)
                self._record_terminal(req, RequestStatus.CANCELLED,
                                      "cancelled while queued")
                self._cond.notify_all()      # a queue spot freed
                return True
            if self._pending is not None and self._pending.req is req:
                self._drop_pending()
                self._record_terminal(req, RequestStatus.CANCELLED,
                                      "cancelled during admission prefill")
                return True
            self._record_terminal(req, RequestStatus.CANCELLED,
                                  f"cancelled in slot {req.slot}")
            self._retire_slot_host_side(req)
            return True

    def status(self, rid):
        """The request's :class:`~.slo.RequestStatus` string; ``KeyError``
        for ids this server never issued.  Thread-safe."""
        with self._lock:
            return self._known(rid, "status()").status

    def result(self, rid):
        """The terminal :class:`~.slo.RequestResult`, or ``None`` while
        the request is still queued/running; ``KeyError`` for ids this
        server never issued.  Thread-safe."""
        with self._lock:
            self._known(rid, "result()")
            return self._results.get(rid)

    def token_events(self, rid, on_event=None):
        """Subscribe to the request's per-token event stream — a
        :class:`~.slo.TokenStream` fed from the host-mirror drain point
        (one event behind the device, flushed as each ``decode_block``'s
        tokens are processed), so TTFT and time-between-tokens are
        observable per request without synchronizing the dispatch path.

        Subscribing replays everything already generated (and, for a
        terminal request, the typed ``end`` event), so the stream is
        lossless no matter when the consumer attaches; resumed requests
        replay their prior-incarnation tokens first.  ``on_event``
        bridges each push synchronously into another world (the HTTP
        transport passes ``loop.call_soon_threadsafe``); it must never
        block.  ``KeyError`` for ids this server never issued.
        Thread-safe."""
        inject.fire("serving.pre_subscribe_lock")
        with self._lock:
            req = self._known(rid, "token_events()")
            stream = TokenStream(rid, on_event=on_event,
                                 on_drop=self._count_stream_drop)
            for i, t in enumerate(req.tokens):
                stream.push({"event": "token", "rid": rid,
                             "index": i, "token": int(t)})
            if req.status in TERMINAL_STATUSES \
                    or req.status == RequestStatus.PREEMPTED:
                res = self._results.get(rid)
                stream.push({"event": "end", "rid": rid,
                             "status": req.status,
                             "detail": res.detail if res is not None
                             else self._preempt_detail})
            else:
                self._streams.setdefault(rid, []).append(stream)
            return stream

    def _count_stream_drop(self, rid, exc):  # lock-held: _lock
        """Dropped subscriber-bridge accounting — pushes only ever run
        under the engine lock, so the counter mutation inherits it (the
        ``TokenStream.push`` contract; slo.py logs the warning_once)."""
        self.stats["stream_bridge_drops"] += 1

    def _publish_progress(self, req):  # lock-held: _lock
        """Push the request's not-yet-streamed tokens to every subscriber
        (called under the lock at the host-mirror drain points — the
        per-token stream is exactly the retirement bookkeeping's view,
        one event behind the device)."""
        n = len(req.tokens)
        streams = self._streams.get(req.rid)
        if streams:
            for i in range(req.streamed, n):
                ev = {"event": "token", "rid": req.rid, "index": i,
                      "token": int(req.tokens[i])}
                for s in streams:
                    s.push(ev)
        req.streamed = n

    def _publish_end(self, req, status, detail=""):  # lock-held: _lock
        """The typed terminal event — exactly once, last; subscribers
        are dropped (late ``token_events()`` calls replay from the
        request record instead)."""
        self._publish_progress(req)
        streams = self._streams.pop(req.rid, None)
        if streams:
            ev = {"event": "end", "rid": req.rid, "status": status,
                  "detail": detail}
            for s in streams:
                s.push(ev)

    def _release_workspaces(self):  # lock-held: _lock
        """Free every device buffer but the weights (close/preempt
        teardown)."""
        self._cache = self._state = None
        self._pages.drop_buffer()
        if self.separate_draft:
            self._draft_cache = None
            self._draft_ws.release()
            self._draft_lanes.release()

    def _give_back_draft_lane(self, p):  # lock-held: _lock
        """Return a dropped admission's draft prefill lane to its pool
        (speculative serving only)."""
        if p.draft_lane is not None:
            self._draft_lanes.give_back(p.draft_lane)
            p.draft_lane = None

    def _drop_pending(self):  # lock-held: _lock
        """Drop the admission in progress alone: its slot and pages go
        back (partial prefill writes are overwritten by the next
        occupant before any of its queries attend them)."""
        p, self._pending = self._pending, None
        self._give_back_draft_lane(p)
        self._free.append(int(p.slot))
        self._pages.release(p.slot)

    def _free_slot(self, s):  # lock-held: _lock
        """The host half of a retirement: the slot and its pages return
        to their free lists."""
        self._slots[s] = None
        self._free.append(int(s))
        self._pages.release(s)

    def _retire_slot_host_side(self, req):  # lock-held: _lock
        """Free a retired request's slot in the HOST MIRROR only — the
        device lane keeps masked-no-op decoding until the slot's next
        occupant's admit program overwrites its state wholesale (the same
        overwrite every admission performs), so retirement never needs a
        device round trip or a new program.  When the request's admit
        event is still in flight (mirror not yet active), the slot is
        freed by ``_process_admit`` when the event arrives."""
        s = req.slot
        if s is not None and self._mirror_active[s]:
            self._mirror_active[s] = False
            self._free_slot(s)

    def _record_terminal(self, req, status, detail):  # lock-held: _lock
        """Mark a non-COMPLETED terminal outcome and queue it for the
        next ``step()`` return (output ``None``)."""
        req.status = status
        req.finished_it = self._it
        ttft = (req.first_tok_t - req.submit_t) \
            if req.first_tok_t is not None else None
        self._results[req.rid] = RequestResult(
            rid=req.rid, status=status, output=None, detail=detail,
            client_id=req.client_id, submitted_it=req.submitted_it,
            finished_it=self._it, ttft_s=ttft,
            **self._trace_done(req, status))
        if self._flightrec is not None:
            self._flightrec.record("terminal", rid=req.rid,
                                   status=status, detail=detail[:200])
        self._pending_reports[req.rid] = None
        # result is recorded BEFORE the end event: a subscriber woken by
        # "end" can immediately read result(rid)
        self._publish_end(req, status, detail)

    def _shed_expired(self):  # lock-held: _lock
        """Deadline enforcement at the scheduling point: expired QUEUED
        requests are shed before admission (they never occupy a slot);
        expired pending-prefill / in-slot requests are retired host-side
        (see :meth:`_retire_slot_host_side`)."""
        now = time.monotonic()
        expired = [r for r in self._queue
                   if r.deadline is not None and now >= r.deadline]
        for req in expired:
            self._queue.remove(req)
            self.stats["shed"] += 1
            self._record_terminal(
                req, RequestStatus.SHED_DEADLINE,
                f"deadline expired {now - req.deadline:.3f}s ago while "
                f"queued (never occupied a slot)")
        p = self._pending
        if p is not None and p.req.deadline is not None \
                and now >= p.req.deadline:
            self._drop_pending()
            self.stats["shed"] += 1
            self._record_terminal(p.req, RequestStatus.SHED_DEADLINE,
                                  "deadline expired during admission "
                                  "prefill")
        for req in list(self._slots):
            if req is None or req.deadline is None or now < req.deadline \
                    or req.status in TERMINAL_STATUSES:
                continue
            self.stats["shed"] += 1
            self._record_terminal(req, RequestStatus.SHED_DEADLINE,
                                  f"deadline expired in slot {req.slot} "
                                  f"after {len(req.tokens)} token(s)")
            self._retire_slot_host_side(req)

    def _check_owner(self, what):
        """Bind the SCHEDULER OWNER to the first thread that drives the
        engine and refuse every other thread afterwards: the host mirror,
        the in-flight event deque and the donated-buffer chain are
        stateful ACROSS calls (the lag-one protocol), so two drivers
        corrupt slot bookkeeping even with every individual call locked.
        submit()/cancel()/status()/result()/token_events() stay callable
        from any thread — only the driving methods are owner-bound.

        A dedicated scheduler thread (frontend/transport.py) calls
        :meth:`bind_owner` BEFORE any request can arrive: without the
        eager claim, a blocked ``queue_policy="block"`` submit racing
        the owner's first ``step()`` could bind ITSELF as owner and
        wedge the real scheduler thread forever."""
        me = threading.current_thread()
        with self._lock:
            if self._owner_thread is None:
                self._owner_thread = me
                return
            if self._owner_thread is not me:
                raise RuntimeError(
                    f"{what} from thread {me.name!r} but this "
                    f"ServingEngine's scheduler owner is "
                    f"{self._owner_thread.name!r} — exactly one thread "
                    f"may drive step()/drain()/preempt() (the host "
                    f"mirror is stateful across calls); other threads "
                    f"use submit()/result()/cancel()/token_events() "
                    f"(docs/serving.md 'Network front end')")

    def bind_owner(self):
        """Eagerly claim the scheduler-owner role for the CURRENT thread
        (idempotent for the owner; raises for any other thread once
        bound).  A dedicated scheduler thread calls this before work can
        arrive, closing the race where a blocked ``block``-policy submit
        binds itself as owner ahead of the real driver's first
        ``step()``."""
        self._check_owner("bind_owner()")

    def release_owner(self):
        """Release the scheduler-owner binding — called by an EXITING
        owner thread (frontend/transport.py's scheduler loop on its way
        out) so a successor driver can claim the engine afterwards.
        Sequential handoff is safe: the mirror's cross-call state lives
        in the engine, the binding only exists to forbid CONCURRENT
        drivers.  No-op when unowned; raises from any non-owner thread
        (stealing the role while the owner lives is the bug the binding
        prevents)."""
        me = threading.current_thread()
        with self._lock:
            if self._owner_thread is None:
                return
            if self._owner_thread is not me:
                raise RuntimeError(
                    f"release_owner() from thread {me.name!r} but the "
                    f"scheduler owner is {self._owner_thread.name!r} — "
                    f"only the owner thread may release its binding")
            self._owner_thread = None

    def step(self):
        """One scheduler iteration: deadline shedding, admission prefill
        under the token budget, one decode-block dispatch, then process
        device results one event behind (latency-hiding).  Returns
        ``{rid: output}`` for every request that reached a terminal
        status this iteration — ``np.ndarray`` for ``COMPLETED``,
        ``None`` for shed/cancelled/aborted (typed detail via
        :meth:`result`).

        Owner-bound: the first thread to call a driving method
        (``step``/``drain``/``preempt``) becomes the scheduler owner and
        every other thread's call raises — see ``_check_owner``."""
        self._check_owner("step()")
        inject.fire("serving.pre_step_lock")
        with self._lock:
            return self._step_locked()

    def _step_locked(self):  # lock-held: _lock
        if self._closed:
            raise RuntimeError("step() on a closed ServingEngine")
        with span("dstpu.sched.step", track="scheduler", cat="scheduler",
                  it=self._it, live_slots=int(self._mirror_active.sum()),
                  queue_depth=len(self._queue)) as step_span:
            inject.fire("serving.sigterm_at_iter")
            self._ensure_workspace()
            finished = {}
            with span("dstpu.sched.shed", track="scheduler", cat="scheduler"):
                self._shed_expired()
            if self._breaker.enabled:
                # breaker mode: dispatch failures are ABSORBED (the except
                # blocks below already restored the bookkeeping and recorded
                # ABORTED results) and counted; `threshold` consecutive ones
                # open the breaker — no dispatches until the cooldown's
                # half-open probe, and submit() rejects with the reason
                was_open = self._breaker.open
                dispatched = False
                try:
                    if self._breaker.allow_dispatch():
                        self._admit()
                        dispatched = self._dispatch_decode()
                except Exception as e:
                    self._breaker.record_failure(e)
                    if self._flightrec is not None:
                        self._flightrec.record(
                            "breaker_failure",
                            consecutive=self._breaker.consecutive_failures,
                            threshold=self._breaker.threshold,
                            error=f"{type(e).__name__}: {e}"[:200])
                        if self._breaker.open and not was_open:
                            # the moment the server stops trusting its own
                            # device: capture what led here
                            self._flightrec.record(
                                "breaker_open", trips=self._breaker.trips,
                                last_error=self._breaker.last_error[:200])
                            self._flight_dump("breaker_open")
                    logger.warning(
                        f"serving dispatch failure absorbed by the circuit "
                        f"breaker ({self._breaker.consecutive_failures}"
                        f"/{self._breaker.threshold} consecutive"
                        f"{'; OPEN' if self._breaker.open else ''}): "
                        f"{type(e).__name__}: {e}")
                if was_open and not self._breaker.open \
                        and self._flightrec is not None:
                    self._flightrec.record("breaker_close",
                                           trips=self._breaker.trips)
            else:
                self._admit()
                dispatched = self._dispatch_decode()
            # lag-one processing: with fresh work in flight, leave the newest
            # event unread so the device keeps running while the host
            # does bookkeeping; once nothing new was dispatched, flush fully
            self._process_events(finished, keep=1 if dispatched else 0)
            # lock-contention observability: cumulative wall time threads
            # spent WAITING on the engine lock, scheduler vs handlers
            # (InstrumentedRLock; exported via /metrics and Serving/ events)
            self.stats["lock_wait_scheduler_s"] = self._lock.wait_s["scheduler"]
            self.stats["lock_wait_handler_s"] = self._lock.wait_s["handler"]
            if self._flightrec is not None \
                    and self.stats["iterations"] % 32 == 0:
                # periodic lock-wait sample: cheap cumulative snapshot so a
                # dump shows whether contention grew before the distress
                self._flightrec.record(
                    "lock_wait",
                    scheduler_s=round(self.stats["lock_wait_scheduler_s"], 6),
                    handler_s=round(self.stats["lock_wait_handler_s"], 6))
            # interval-gated device-memory sample (serving.memory_telemetry;
            # a clock compare between samples)
            with span("dstpu.sched.emit", track="scheduler", cat="scheduler"):
                self._sample_memory()
                self._emit_metrics()
            self.stats["iterations"] += 1
        self.stats["wall_secs"] += step_span.dur_s
        self._it += 1
        if self._pending_reports:
            finished.update(self._pending_reports)
            self._pending_reports.clear()
        # retirements/admissions may have freed queue spots: rouse
        # blocked non-owner submit()s (queue_policy="block")
        self._cond.notify_all()
        return finished

    def drain(self, timeout_s=None):
        """Run the scheduler until every submitted request has reached a
        terminal status; returns ``{rid: output}`` for everything that
        finished during the call (``None`` outputs for non-COMPLETED
        terminals).  ``timeout_s`` (default: the config's
        ``drain_timeout_s``; 0/None = no limit) bounds the wall clock —
        past it :class:`~.slo.DrainTimeout` is raised with per-slot
        diagnostics (slot id, request id, last dispatch age) instead of
        spinning forever on a wedged scheduler."""
        self._check_owner("drain()")
        if timeout_s is None:
            timeout = self.config.drain_timeout_s or None
        else:
            timeout = timeout_s or None      # explicit 0 = no limit
        t0 = time.monotonic()
        results = {}
        while self._work_outstanding():
            if timeout is not None and time.monotonic() - t0 > timeout:
                with self._lock:
                    diag = self._drain_diagnostics(timeout,
                                                   time.monotonic() - t0)
                if self._flightrec is not None:
                    # the dump's tail is the dispatch sequence that led
                    # into the wedge — what the diagnostics (a
                    # point-in-time view) cannot show
                    self._flightrec.record("drain_timeout",
                                           diag=diag[:400])
                    self._flight_dump("drain_timeout")
                raise DrainTimeout(diag)
            if self._breaker.open and not self._breaker.allow_dispatch() \
                    and not self._anything_in_flight():
                # open breaker, nothing in flight: don't busy-spin the
                # queue scan while waiting out the cooldown
                time.sleep(min(
                    0.01, self._breaker.seconds_until_half_open()))
            results.update(self.step())
        with self._lock:
            if self._pending_reports:
                results.update(self._pending_reports)
                self._pending_reports.clear()
        return results

    def _work_outstanding(self):
        """True while anything submitted has not reached a terminal
        status (queued, mid-prefill, in flight or mirror-active) — the
        locked point-in-time view ``drain()`` loops on (its old unlocked
        reads raced ``submit()``/``cancel()`` from other threads)."""
        with self._lock:
            return bool(self._queue or self._pending is not None
                        or self._events or self._mirror_active.any())

    def work_pending(self):
        """Public combined scheduler predicate: anything queued,
        mid-prefill, dispatched or mirror-live — ONE lock round-trip,
        for driving loops (``frontend/transport.py``, ``resilient.py``)
        that would otherwise take the lock three times per iteration
        through the individual monitoring properties.  Thread-safe."""
        return self._work_outstanding()

    def _anything_in_flight(self):
        """Locked: dispatched events unprocessed or mirror-live slots."""
        with self._lock:
            return bool(self._events or self._mirror_active.any())

    def _drain_diagnostics(self, timeout, elapsed):  # lock-held: _lock
        now = time.monotonic()
        lines = [f"drain() exceeded its {timeout:.1f}s wall-clock budget "
                 f"({elapsed:.1f}s elapsed) with work outstanding: "
                 f"queue={len(self._queue)}, "
                 f"in_flight_events={len(self._events)}"]
        for s, req in enumerate(self._slots):
            if req is None:
                continue
            last = self._slot_last_dispatch.get(s)
            age = f"{now - last:.1f}s ago" if last is not None else "never"
            lines.append(f"  slot {s}: request {req.rid} "
                         f"(status {req.status}, {len(req.tokens)} "
                         f"token(s), last dispatch {age})")
        if self._pending is not None:
            lines.append(f"  pending prefill: request "
                         f"{self._pending.req.rid} on slot "
                         f"{self._pending.slot} "
                         f"({self._pending.ci}/{self._pending.n_chunks} "
                         f"chunks)")
        if self._breaker.open:
            lines.append(f"  circuit breaker OPEN "
                         f"({self._breaker.consecutive_failures} "
                         f"consecutive failures; last: "
                         f"{self._breaker.last_error})")
        lines.append(f"  {self._pages.describe()}, "
                     f"{self.stats['admission_stalls']} admission stall(s), "
                     f"{self.stats['prefill_rows']} chunk row(s) in "
                     f"{self.stats['prefill_dispatches']} prefill "
                     f"dispatch(es) of {self.chunk_rows}")
        return "\n".join(lines)

    def close(self):
        """Retire the server: abort everything undrained (queued,
        prefilling and in-slot requests all end ``ABORTED``), release the
        KV workspaces, and mark the engine closed — ``submit()``/
        ``step()`` afterwards raise.  Idempotent: every call returns the
        same sorted list of the request ids that were undrained at the
        first close."""
        with self._lock:
            return self._close_locked()

    def _close_locked(self):  # lock-held: _lock
        if self._closed:
            return list(self._close_report)
        finished = {}
        try:
            self._process_events(finished, keep=0)
        except Exception as e:               # dead buffers from a failure
            logger.warning(f"serving close(): discarding unreadable "
                           f"in-flight events ({type(e).__name__}: {e})")
        if finished:
            logger.warning(f"serving close(): {len(finished)} finished "
                           f"request(s) discarded unread")
        undrained = sorted(
            [r.rid for r in self._slots if r is not None]
            + ([self._pending.req.rid] if self._pending is not None else [])
            + [r.rid for r in self._queue])
        for req in list(self._queue):
            self._record_terminal(req, RequestStatus.ABORTED,
                                  "engine closed with the request still "
                                  "queued")
        self._queue.clear()
        self._abort_in_flight("close()")
        self._release_workspaces()
        self._detach_observability()
        self._closed = True
        self._close_report = undrained
        # blocked submit()s must observe _closed and raise, idle
        # scheduler loops must notice the shutdown
        self._cond.notify_all()
        self.wake.set()
        if undrained:
            logger.warning(f"serving close(): {len(undrained)} undrained "
                           f"request(s) {undrained} aborted")
        return list(self._close_report)

    def _abort_in_flight(self, why):  # lock-held: _lock
        """Drop every request past admission (its KV rows live in buffers
        that are dead or about to be re-initialized) and restore the slot
        bookkeeping to all-free — queued requests survive and the next
        ``step()`` runs on a fresh workspace.  Without this, a failed
        decode dispatch would leak the occupied slots forever (drain()
        then spins: nothing free to admit, nothing active to decode) and
        stale events would replay against the fresh all-inactive state."""
        lost = []
        for req in self._slots:
            if req is None:
                continue
            lost.append(req.rid)
            if req.status not in TERMINAL_STATUSES:
                self._record_terminal(req, RequestStatus.ABORTED,
                                      f"in-flight request aborted: {why}")
        if self._pending is not None:
            req = self._pending.req
            lost.append(req.rid)
            if req.status not in TERMINAL_STATUSES:
                self._record_terminal(req, RequestStatus.ABORTED,
                                      f"admission aborted: {why}")
            self._give_back_draft_lane(self._pending)
            self._pending = None
        self._events.clear()
        self._slots = [None] * self.num_slots
        self._free = deque(range(self.num_slots))
        self._mirror_active[:] = False
        self._state = None
        if self.separate_draft:
            # the draft cache's contents mirror the aborted in-flight
            # requests (and may be donated-dead after a failed propose)
            # — drop it so the next step reallocates a fresh one
            self._draft_ws.give_back(self._draft_cache)
            self._draft_cache = None
        self._pages.reset()
        if lost:
            self.stats["aborted"] = self.stats.get("aborted", 0) + len(lost)
            if self._flightrec is not None:
                self._flightrec.record("abort_in_flight", why=why[:200],
                                       rids=lost)
            logger.warning(f"serving {why}: aborted {len(lost)} in-flight "
                           f"request(s) {lost} — queued requests survive")

    # Monitoring properties take the engine lock (re-entrant, so locked
    # callers like _emit_metrics/_metrics_body compose): an unlocked
    # read would race the scheduler mutating the same state — the
    # "/metrics iterating fairness state while the scheduler compacted
    # it" bug class TL008 exists to kill.
    @property
    def queue_depth(self):
        with self._lock:
            return len(self._queue) + (1 if self._pending is not None
                                       else 0)

    @property
    def active_slots(self):
        """Live slots as of the last PROCESSED event (the host mirror)."""
        with self._lock:
            return int(np.sum(self._mirror_active))

    @property
    def in_flight(self):
        """Dispatched device events not yet processed."""
        with self._lock:
            return len(self._events)

    @property
    def page_pool_utilization(self):
        """Allocated fraction of the page pool."""
        with self._lock:
            return self._pages.utilization

    @property
    def prefix_hit_rate(self):
        """Fraction of prefix-cache lookups that matched >= 1 page."""
        with self._lock:
            n = self.stats["prefix_lookups"]
            return self.stats["prefix_hits"] / n if n else 0.0

    def health_snapshot(self):
        """One locked point-in-time view of the scheduler for health
        endpoints (``/healthz``): queue depth, mirror occupancy,
        in-flight events, breaker state, closed flag.  Thread-safe —
        the HTTP front end calls it through ``run_in_executor`` so the
        loop thread never blocks on the engine lock itself."""
        with self._lock:
            # the properties re-enter the already-held lock (re-entrant
            # acquires are excluded from the wait samples), so /healthz
            # and the property/metrics view share ONE implementation
            snap = {
                "closed": self._closed,
                "queue_depth": self.queue_depth,
                "active_slots": self.active_slots,
                "num_slots": self.num_slots,
                "in_flight_events": self.in_flight,
                "breaker": {
                    "open": self._breaker.open,
                    "consecutive_failures":
                        self._breaker.consecutive_failures,
                    "trips": self._breaker.trips,
                    "last_error": self._breaker.last_error,
                },
            }
            snap["slot_occupancy"] = snap["active_slots"] / self.num_slots
            snap["page_pool_utilization"] = self.page_pool_utilization
            return snap

    # ------------------------------------------------------------------ #
    # Warmup — compile (or reload) the expensive programs up front
    # ------------------------------------------------------------------ #
    def warmup(self, monitor=None):
        """AOT-compile the expensive serving programs (the decode block
        and the admission prefill chunk) against abstract arguments, once
        per process, up front — so the first requests do not pay the
        compile.  Returns ``{program: compile_seconds}`` (0.0 = this
        process already compiled it).  The serving programs deliberately
        bypass the persistent cache layers (see ``__init__``:
        cross-process reloaded serving executables corrupt the slot
        workspace), so a restarted server recompiles here rather than
        reloading.

        The fused admit program deliberately compiles on first use
        instead: it takes no ``params``, so an abstract-args compile would
        pin it to single-device input shardings while its runtime inputs
        (chunk-program outputs) carry the mesh's replicated sharding —
        first-use compilation sees the real shardings."""
        with span("dstpu.setup.warmup", cat="setup") as sp:
            eng = self.engine
            N, S, C = self.num_slots, self.cache_len, self.chunk
            dtype = eng.compute_dtype
            cache = jax.eval_shape(lambda: self._new_pools(dtype))
            state = {
                "token": jax.ShapeDtypeStruct((N,), jnp.int32),
                "pos": jax.ShapeDtypeStruct((N,), jnp.int32),
                "active": jax.ShapeDtypeStruct((N,), jnp.bool_),
                "remaining": jax.ShapeDtypeStruct((N,), jnp.int32),
                "eos": jax.ShapeDtypeStruct((N,), jnp.int32),
            }
            if self.self_draft:
                state["draft"] = jax.ShapeDtypeStruct((N,), jnp.int32)
            rng = jax.eval_shape(lambda: jax.random.key(0))
            report = {}

            def warm(fn, args, name):
                from deepspeed_tpu.runtime import compile_cache as cc
                sig = (id(fn),) + cc.abstract_signature(args)
                if sig in eng._aot:
                    return {name: 0.0}
                compiled, dt, hit = eng._aot_compile(fn, args)
                if compiled is None:
                    logger.warning(f"serving warmup: {name} failed to "
                                   f"AOT-compile — it compiles on first use")
                    return {}
                eng._aot[sig] = compiled
                return {name: 0.0 if hit else dt}

            R = self.chunk_rows
            rows = jax.ShapeDtypeStruct((R, self.table_width), jnp.int32)
            tables = jax.ShapeDtypeStruct((N, self.table_width), jnp.int32)
            cargs = (eng._params, cache, rows,
                     jax.ShapeDtypeStruct((R, C), jnp.int32),
                     jax.ShapeDtypeStruct((R,) if R > 1 else (), jnp.int32),
                     jax.ShapeDtypeStruct((R,), jnp.int32)) \
                + ((jax.ShapeDtypeStruct((R,), jnp.int32),)
                   if self.self_draft else ())
            report.update(warm(self._chunk_fn, cargs,
                               f"serving_prefill:c{C}p{self.page}"
                               + (f"r{R}" if R > 1 else "")))
            if self.self_draft:
                report.update(warm(
                    self._spec_fn,
                    (eng._params, cache, state, tables, rng),
                    f"serving_spec_block:n{N}s{S}b{self.block}p{self.page}"))
            elif self.speculative:
                draft = jax.ShapeDtypeStruct((N, self.spec_k), jnp.int32)
                report.update(warm(
                    self._verify_fn,
                    (eng._params, cache, state, tables, draft, rng),
                    f"serving_spec_verify:n{N}s{S}k{self.spec_k}"
                    f"p{self.page}"))
            else:
                report.update(warm(
                    self._decode_fn,
                    (eng._params, cache, state, tables, rng),
                    f"serving_decode:n{N}s{S}b{self.block}p{self.page}"))
            if self.separate_draft:
                dcache = jax.eval_shape(
                    lambda: self.draft_module.init_cache(N, S, dtype=dtype))
                dlane = jax.eval_shape(
                    lambda: self.draft_module.init_cache(1, S, dtype=dtype))
                report.update(warm(
                    self._propose_fn, (self._draft_params, dcache, state),
                    f"serving_spec_propose:n{N}s{S}k{self.spec_k}"))
                report.update(warm(
                    self._draft_chunk_fn,
                    (self._draft_params, dlane,
                     jax.ShapeDtypeStruct((1, C), jnp.int32),
                     jax.ShapeDtypeStruct((), jnp.int32),
                     jax.ShapeDtypeStruct((1,), jnp.int32)),
                    f"serving_spec_draft_prefill:c{C}"))
            sp.set(programs=len(report))
        for name, dt in report.items():
            log_dist(f"serving warmup[{name}]: "
                     + ("cached" if dt == 0.0 else f"{dt:.1f}s"), ranks=[0])
        mon = monitor or self.monitor
        if mon is not None and getattr(mon, "enabled", True):
            mon.write_events([(f"Compile/{name}_secs", dt, 0)
                              for name, dt in report.items()])
        log_dist(span_trace.ready_line("serving"), ranks=[0])
        return report

    # ------------------------------------------------------------------ #
    # Admission: queue -> prefill chunks -> fused admit dispatch
    # ------------------------------------------------------------------ #
    def _pop_request(self):  # lock-held: _lock
        if self.priority_lanes > 1:
            return self._pop_request_priority()
        if self.config.admission == "shortest_first":
            req = min(self._queue, key=lambda r: (len(r.ids), r.rid))
            self._queue.remove(req)
            return req
        return self._queue.popleft()

    def _pop_request_priority(self):  # lock-held: _lock
        """Priority lanes over the base admission order: pop the lowest
        EFFECTIVE lane, breaking ties with the configured policy (queue
        position for fcfs, prompt length for shortest_first).  Effective
        lane = ``priority - floor(waited / priority_aging_s)`` clamped at
        0, so a lane-``k`` request reaches lane 0 after at most
        ``k * priority_aging_s`` seconds queued — the aging bound that
        keeps sustained high-priority load from starving low priority
        (``priority_aging_s = 0`` disables aging: strict lanes)."""
        now = time.monotonic()
        aging = float(self.config.priority_aging_s)

        def lane(r):
            if aging <= 0:
                return r.priority
            return max(0, r.priority - int((now - r.submit_t) / aging))

        if self.config.admission == "shortest_first":
            req = min(self._queue,
                      key=lambda r: (lane(r), len(r.ids), r.rid))
        else:
            req = min(enumerate(self._queue),
                      key=lambda ir: (lane(ir[1]), ir[0]))[1]
        self._queue.remove(req)
        return req

    def _admit(self):  # lock-held: _lock
        """Admission under the prefill token budget, as one
        ``dstpu.sched.admit`` span: queue pop, page allocation, prefix
        lookup, the chunk dispatches and the fused admit dispatch."""
        admitted0 = self.stats["admitted"]
        tokens0 = self.stats["prefill_tokens"]
        limit, live, widened = self._prefill_limit()
        self.stats["prefill_budget_widened"] += widened
        with span("dstpu.sched.admit", track="scheduler",
                  cat="scheduler", live_slots=live,
                  budget_tokens=limit) as sp:
            try:
                self._admit_under_budget(limit or math.inf)
            finally:
                sp.set(admitted=self.stats["admitted"] - admitted0,
                       prefill_tokens=self.stats["prefill_tokens"]
                       - tokens0)

    def _prefill_limit(self):  # lock-held: _lock
        """``(prompt tokens this iteration's admission may prefill — 0 =
        unbounded, like the option —, live lanes, whether the limit is
        more than the configured budget alone buys)``.
        ``prefill_token_budget`` is the stall a FULL batch tolerates
        between two of its decode blocks — ``budget x num_slots``
        lane-tokens of waiting — so the limit is the budget scaled by
        ``num_slots / live``: the budget itself with every lane live,
        ``num_slots`` budgets with none (nobody waits).  ``live`` is
        what the coming decode block will serve, read BEFORE this
        iteration's admissions (what ``_dispatch_decode`` tests):
        mirror-live slots plus unread admit events.  Whole chunks,
        rounded down, never fewer than ``ceil(budget / chunk)`` — and
        where that is more than one dispatch's ``chunk_rows``, whole
        dispatches, so a queue that lasts leaves no row dead."""
        live = int(self._mirror_active.sum()) \
            + sum(e[0] == "admit" for e in self._events)
        budget = self.config.prefill_token_budget
        if not budget:
            return 0, live, False
        base = -(-budget // self.chunk)
        chunks = budget * self.num_slots // (self.chunk * max(live, 1))
        if chunks > self.chunk_rows:
            chunks -= chunks % self.chunk_rows
        chunks = max(base, chunks)
        return chunks * self.chunk, live, chunks > base

    def _admit_under_budget(self, limit):  # lock-held: _lock
        """Prefill dispatches until ``limit`` tokens are spent (live rows
        x chunk) or nothing is left to prefill: each takes up to
        ``chunk_rows`` granted chunks, in order."""
        spent = 0
        while spent < limit:
            cap = int(min(self.chunk_rows, (limit - spent) / self.chunk))
            rows = self._fill_rows(cap)
            if rows:
                self._run_prefill_dispatch(rows)
                spent += len(rows) * self.chunk
            if len(rows) < cap:
                return      # the queue, the slots or the pages ran out

    def _fill_rows(self, cap):  # lock-held: _lock
        """The next prefill dispatch's live rows, ``[(admission, chunk
        index)]``, at most ``cap``: the pending prompt's next chunks, then
        — while rows, a free slot, pages and the queue last — the next
        admission's.  Rows fill in order, so every admission but the
        last-started one rides to its end: ``_pending`` is that one, or
        ``None``.  A prompt whose last chunk is placed publishes its
        prefix pages here, BEFORE the next admission looks prefixes up:
        whoever maps them attends them no sooner than this dispatch, whose
        every layer writes all rows before any row attends."""
        rows = []
        try:
            while len(rows) < cap:
                if self._pending is None and not self._begin_admission():
                    break
                p = self._pending
                take = min(p.n_chunks - p.ci, cap - len(rows))
                rows += [(p, p.ci + i) for i in range(take)]
                p.ci += take
                if p.ci == p.n_chunks:
                    self._pages.share(p.slot, p.fill)
                    self._pending = None
        except BaseException as e:
            if rows:
                # rows placed and never dispatched: a published prefix
                # would promise K/V nobody wrote — lose them like a
                # failed dispatch does
                self._lose_rows(rows, f"admission failed before its "
                                      f"prefill dispatch: "
                                      f"{type(e).__name__}: {e}")
            raise
        return rows

    def _lose_rows(self, rows, why):  # lock-held: _lock
        """Every admission that rode a lost dispatch ends ``ABORTED``, and
        with it everything in flight (the pool they wrote is gone)."""
        riders = list(dict.fromkeys(p for p, _ in rows))
        for p in riders:
            self._give_back_draft_lane(p)
            if p.req.status not in TERMINAL_STATUSES:
                self._record_terminal(p.req, RequestStatus.ABORTED, why)
        # _abort_in_flight counts the slots' requests and the pending one
        self.stats["aborted"] = self.stats.get("aborted", 0) \
            + sum(p is not self._pending for p in riders)
        self._abort_in_flight(
            f"prefill dispatch lost (request(s) "
            f"{', '.join(str(p.req.rid) for p in riders)} lost)")

    def _begin_admission(self):  # lock-held: _lock
        """Pop the next request and reserve its slot and pages as
        ``_pending``; False — nothing popped — when the queue or the free
        slots ran out, or the pool cannot back the head request yet."""
        if not self._queue or not self._free:
            return False
        req = self._pop_request()
        pend = self._start_prefill(req)
        if pend is None:
            # pool pressure: not enough free pages even after
            # evicting unreferenced prefix pages — the request
            # waits at the queue head until retirements free
            # pages (backpressure, never a partial grab)
            self._queue.appendleft(req)
            self.stats["admission_stalls"] += 1
            if self._flightrec is not None:
                self._flightrec.record(
                    "admission_stall", rid=req.rid,
                    pool_in_use=self._pages.in_use)
            return False
        if self._tracer is not None and req.t_trace is not None:
            # queue phase ends here: admission decided, the slot
            # is reserved and prefill chunks start streaming
            req.t_admit_start = self._tracer.now()
            self._hist.queue_wait.observe(
                req.t_admit_start - req.t_trace)
        else:
            # tracing off: the one stamp RequestResult.queue_s
            # needs, on submit_t's clock
            req.t_admit_start = time.monotonic()
        if self._flightrec is not None:
            self._flightrec.record(
                "admit_start", rid=req.rid, slot=req.slot,
                fill_len=pend.fill_len, chunks=pend.n_chunks)
        if self._fairness is not None and not req.resumed:
            # charge admitted prefill work once, when admission
            # actually starts (a stall above retries the
            # same request without double-charging).  Resumed
            # requests charge NOTHING here: their prompt and
            # generated-so-far tokens were billed in the prior
            # incarnation and ride the snapshot balance — the
            # re-prefill is the server's preemption cost, not
            # the client's
            self._fairness.charge(req.client_id,
                                  len(req.fill_ids))
        self._pending = pend
        return True

    def _start_prefill(self, req):  # lock-held: _lock
        """Reserve the head free slot and its pages (``paging.SlotPages.
        reserve``) for ``req``.  Returns ``None`` — nothing popped,
        nothing allocated — when the pool cannot back the request yet."""
        fill = req.fill_ids              # prompt + any resumed tokens
        slot = self._free[0]
        got = self._pages.reserve(slot, fill,
                                  req.max_new - len(req.prefix))
        if got is None:
            return None
        _, start = got
        self._free.popleft()
        req.slot = slot
        req.status = RequestStatus.PREFILLING
        pend = _PendingPrefill(req, slot, fill, start, self.chunk)
        if self.separate_draft:
            # start == 0 under speculation (prefix sharing disabled), so
            # the draft lane prefills the same chunk spans as the pool
            pend.draft_lane = self._draft_lanes.take(
                self.cache_len, self.engine.compute_dtype)
        return pend

    def _run_prefill_dispatch(self, rows):  # lock-held: _lock
        """ONE dispatch of the chunk program over ``rows`` (``[(admission,
        chunk index)]``, row order; fewer than ``chunk_rows`` leaves the
        rest dead: an all-trash table row, start 0, last real position -1
        — no token of it is routed to an expert —, logits never read),
        then the fused admit of every prompt whose last chunk rode it."""
        C, R, n = self.chunk, self.chunk_rows, len(rows)
        tables = np.zeros((R, self.table_width), np.int32)   # trash rows
        ids = np.zeros((R, C), np.int32)
        starts = np.zeros((R,), np.int32)
        last = np.full((R,), -1, np.int32)
        work = {}
        for r, (p, ci) in enumerate(rows):
            # chunk ci covers absolute positions [start + ci*C, start +
            # (ci+1)*C); start > 0 only for shared-prefix admissions
            tables[r] = self._pages.row(p.slot)[0]
            ids[r] = p.ids_pad[0, ci * C:(ci + 1) * C]
            starts[r] = p.start + ci * C
            last[r] = min(max(p.fill_len - 1 - starts[r], 0), C - 1)
            for key, val in self._pages.chunk_reach(
                    self._layers, int(starts[r]) + C,
                    live_end=p.fill_len).items():
                work[key] = work.get(key, 0) + val
        p0, ci0 = rows[0]
        try:
            with self._observe_dispatch(
                    "prefill_chunk", rid=p0.req.rid, slot=p0.slot,
                    chunk=ci0, phase="prefill", rows=n, rows_cap=R,
                    **self._count_work(work)):
                # the chunks write straight into the slots' pool pages
                # — the POOL is the donated buffer, chained with decode
                more = ()
                if self.self_draft:
                    # the token after the chunk (R = 1): the next chunk's
                    # first, or -1 — the first sampled one, taken
                    # in-program — after the prompt's last chunk
                    after = (ci0 + 1) * C
                    more = (jnp.asarray(
                        [p0.ids_pad[0, after] if ci0 < p0.n_chunks - 1
                         else -1], jnp.int32),)
                logits, self._cache, *load = self.engine._run_guarded(
                    self._chunk_fn,
                    (self.engine._params, self._cache,
                     jnp.asarray(tables), jnp.asarray(ids),
                     jnp.asarray(starts if R > 1 else starts[0]),
                     jnp.asarray(last)) + more)
                if self.self_draft:
                    # the module's guess after the chunk's last real token:
                    # the slot's first pending draft, once this was the
                    # prompt's last chunk
                    p0.draft0 = load.pop()
                # expert models only: the dispatch's ONE load vector, summed
                # into the statistics when the first row's prompt is admitted
                p0.expert_loads += load
        except BaseException as e:
            # the donated POOL may be dead — this is a decode-grade
            # failure: every in-flight request's KV lived in it
            self._pages.give_back(self._cache)
            self._cache = None
            self._lose_rows(rows, f"admission prefill dispatch failed: "
                                  f"{type(e).__name__}: {e}")
            raise
        if self.separate_draft:
            # mirror the chunk into the DRAFT lane: speculation proposes
            # from the draft model's own cache, so it needs the prompt's
            # K/V too (same spans — prefix sharing is disabled under
            # speculation, p.start is always 0; R = 1)
            t0s = time.perf_counter()
            try:
                with self._observe_dispatch("draft_prefill_chunk",
                                            rid=p0.req.rid, slot=p0.slot,
                                            chunk=ci0, phase="prefill"):
                    _, p0.draft_lane = self.engine._run_guarded(
                        self._draft_chunk_fn,
                        (self._draft_params, p0.draft_lane,
                         jnp.asarray(ids), jnp.asarray(starts[0]),
                         jnp.asarray(last)))
            except BaseException as e:
                # the donated draft lane may be dead — drop only THIS
                # admission
                self._pending = p0
                self._drop_pending()
                if p0.req.status not in TERMINAL_STATUSES:
                    self._record_terminal(
                        p0.req, RequestStatus.ABORTED,
                        f"draft prefill dispatch failed: "
                        f"{type(e).__name__}: {e}")
                    self.stats["aborted"] = \
                        self.stats.get("aborted", 0) + 1
                logger.warning(f"serving draft prefill failed — request "
                               f"{p0.req.rid} dropped")
                raise
            self.stats["spec_draft_secs"] += time.perf_counter() - t0s
        self._breaker.record_success()
        self.stats["prefill_tokens"] += n * C
        self.stats["prefill_dispatches"] += 1
        self.stats["prefill_rows"] += n
        done = []
        for r, (p, ci) in enumerate(rows):
            if ci == p.n_chunks - 1:
                # this row held the prompt's last real position — its
                # selected logits seed the first sampled token
                # (device-side; never synchronized here)
                p.sel, p.sel_row = logits, r
                done.append(p)
        for i, p in enumerate(done):
            try:
                self._dispatch_admit(p)
            except BaseException:
                # the state died under the admissions behind this one too
                for q in done[i + 1:]:
                    self._give_back_draft_lane(q)
                    self._record_terminal(
                        q.req, RequestStatus.ABORTED,
                        f"admission aborted: admit dispatch of request "
                        f"{p.req.rid} failed")
                raise

    def _dispatch_admit(self, p):  # lock-held: _lock
        """Prefill complete: ONE dispatch samples the first token and
        writes the slot state in-program.  The first token is read
        lazily when the event is processed.  A resumed
        request (non-empty ``prefix``) admits with the REMAINING token
        budget — its prefix already counts against ``max_new``."""
        req = p.req
        dev_new = req.max_new - len(req.prefix)
        self._rng, sub = jax.random.split(self._rng)
        try:
            inject.fire("serving.pre_admit")
            with self._observe_dispatch("admit", rid=req.rid,
                                        slot=int(p.slot),
                                        phase="admit"):
                # the prompt's K/V already sits in the slot's pages —
                # admission is just the first-token sample + the
                # in-program slot-state write (state donated)
                self._state, first = self.engine._run_guarded(
                    self._admit_fn,
                    (self._state, p.sel, sub,
                     jnp.asarray(p.slot, jnp.int32),
                     jnp.asarray(p.fill_len, jnp.int32),
                     jnp.asarray(dev_new, jnp.int32),
                     jnp.asarray(req.eos, jnp.int32))
                    + ((jnp.asarray(p.sel_row, jnp.int32),)
                       if self.chunk_rows > 1 else ())
                    + ((p.draft0,) if self.self_draft else ()))
        except BaseException as e:
            # the state was donated — same recovery as a decode failure
            # (this admission's request is lost with it).  Only the
            # STATE died (the pool is not an admit argument);
            # _abort_in_flight still resets all page bookkeeping, so
            # stale KV is never attended.
            self._give_back_draft_lane(p)
            if req.status not in TERMINAL_STATUSES:
                self._record_terminal(req, RequestStatus.ABORTED,
                                      f"admit dispatch failed: "
                                      f"{type(e).__name__}: {e}")
            self._abort_in_flight(f"admit dispatch failed "
                                  f"(request {req.rid} lost)")
            raise
        self._breaker.record_success()
        if self.separate_draft:
            # insert the prefilled draft lane into the draft cache
            t0s = time.perf_counter()
            try:
                with self._observe_dispatch("draft_admit", rid=req.rid,
                                            slot=int(p.slot),
                                            phase="admit"):
                    self._draft_cache = self.engine._run_guarded(
                        self._draft_admit_fn,
                        (self._draft_cache, p.draft_lane,
                         jnp.asarray(p.slot, jnp.int32)))
            except BaseException as e:
                # the donated draft cache may be dead — decode-grade
                # failure: every live slot's draft K/V lived in it
                self._give_back_draft_lane(p)
                if req.status not in TERMINAL_STATUSES:
                    self._record_terminal(
                        req, RequestStatus.ABORTED,
                        f"draft admit dispatch failed: "
                        f"{type(e).__name__}: {e}")
                self._abort_in_flight(f"draft admit dispatch failed "
                                      f"(request {req.rid} lost)")
                raise
            self.stats["spec_draft_secs"] += time.perf_counter() - t0s
        self._slot_last_dispatch[int(p.slot)] = time.monotonic()
        req.status = RequestStatus.RUNNING
        self._slots[p.slot] = req
        self._events.append(("admit", req, p.slot, first, p.draft_lane,
                             p.expert_loads))
        self.stats["admitted"] += 1
        if self._tracer is not None and req.t_trace is not None:
            # prefill phase ends: the fused admit is dispatched; what
            # follows until the first token is PROCESSED is the lag-one
            # host window the breakdown books as host_s
            req.t_prefill_done = self._tracer.now()

    # ------------------------------------------------------------------ #
    # Decode: one block of the single reusable decode-step program
    # ------------------------------------------------------------------ #
    def _dispatch_decode(self):  # lock-held: _lock
        # dispatch when anything can be live on device: a slot active as
        # of the mirror, or an unprocessed admit that (probably) went live
        if not (self._mirror_active.any()
                or any(e[0] == "admit" for e in self._events)):
            return False
        self._rng, sub = jax.random.split(self._rng)
        try:
            inject.fire("serving.pre_decode_dispatch")
            if self.self_draft:
                ev = self._dispatch_spec_block(sub)
            elif self.speculative:
                ev = self._dispatch_spec(sub)
            else:
                with self._observe_dispatch(
                        "decode", phase="decode",
                        live_slots=int(self._mirror_active.sum()),
                        **self._count_work(self._block_kv_work())):
                    toks, self._cache, self._state, *load = \
                        self.engine._run_guarded(
                            self._decode_fn,
                            (self.engine._params, self._cache,
                             self._state,
                             jnp.asarray(self._pages.table()), sub))
                ev = ("decode", toks, load)
        except BaseException:
            # the donated cache/state may be dead — drop them so the next
            # step's workspace take() reallocates, and abort everything
            # past admission (its KV rows died with the buffers; stale
            # events/slot bookkeeping must not survive into the fresh
            # state).  Queued requests are untouched.
            self._pages.give_back(self._cache)
            self._cache = None
            self._abort_in_flight("decode dispatch failed")
            raise
        self._breaker.record_success()
        now = time.monotonic()
        for s, r in enumerate(self._slots):
            if r is not None:
                self._slot_last_dispatch[s] = now
        self._events.append(ev)
        self.stats["decode_calls"] += 1
        if "reference_fallback" in (
                self.kernel_modes["decode"],
                (self.ring_kernel_modes or {}).get("decode")):
            # this decode dispatch took the take_along_axis gather path
            # (no Pallas, or alibi) — the BENCH_r04 bs128 cliff,
            # surfaced instead of silent
            self.stats["paged_attention_fallback"] += 1
        return True

    def _block_kv_work(self):  # lock-held: _lock
        """What the decode block about to be dispatched attends, as the
        dispatch span's args.  ``kv_positions``: positions, summed over
        its steps and the slots live on the DEVICE — from the host
        mirror plus what is still in flight: a mirror-live slot is ahead
        of ``req.tokens`` by the unprocessed decode block, and a slot
        whose admit event is unread is live with one token.  The step
        that produces a request's token ``i`` attends ``prompt + i``
        positions.  Exact unless a request stops early on eos inside
        the unread block (then over by less than one block for that
        slot): the bytes the paged-decode kernel must read are this
        times the K/V bytes of one position, every layer.  With them,
        the pages those steps walk beside the whole table's
        (``paging.SlotPages.block_reach``).

        A self-drafting block (``_dispatch_spec_block``) is ``block``
        WINDOWS of two rows a lane, at the lane's position and the next: a
        window moves its lane one or two positions and the host learns
        which an event later, so every unread or coming window is reckoned
        at ONE — under by the drafts accepted meanwhile, a few positions
        of a context of thousands."""
        block = self.block
        unread = block * sum(e[0] in ("decode", "spec")
                             for e in self._events)
        live = [(r, len(r.tokens) + unread)
                for s, r in enumerate(self._slots)
                if r is not None and self._mirror_active[s]]
        live += [(e[1], len(e[1].prefix) + 1) for e in self._events
                 if e[0] == "admit" and e[1].status not in TERMINAL_STATUSES]
        # (context the first step attends, steps) a live slot
        work = [(len(req.ids) + have, min(block, req.max_new - have))
                for req, have in live if req.max_new > have]
        if self.self_draft:
            work = [(first + i, 2) for first, windows in work
                    for i in range(windows)]
            block *= 2
        return {"kv_positions": sum(steps * first + steps * (steps - 1) // 2
                                    for first, steps in work),
                **self._pages.block_reach(self._layers, work, block)}

    def _dispatch_spec_block(self, sub):  # lock-held: _lock
        """``decode_block`` self-drafted verify windows in ONE dispatch
        (``slots.make_spec_block_fn``): pool and slot state donated, the
        pending drafts ride the state.  The span carries the attention
        work of two rows a lane a window (``_block_kv_work``) and, under
        ``windows`` / ``proposed`` / ``accepted`` / ``rows_rejected``, what
        the windows READ since the last dispatch came to — the mirror lags
        the device by one event, so a dispatch reports its predecessors'
        commits."""
        seen, self._spec_unreported = self._spec_unreported, dict.fromkeys(
            self._spec_unreported, 0)
        with self._observe_dispatch(
                "spec_block", phase="decode",
                live_slots=int(self._mirror_active.sum()), **seen,
                **self._count_work(self._block_kv_work())):
            toks, accepted, self._cache, self._state, *load = \
                self.engine._run_guarded(
                    self._spec_fn,
                    (self.engine._params, self._cache, self._state,
                     jnp.asarray(self._pages.table()), sub))
        return ("spec", toks, accepted, load)

    def _dispatch_spec(self, sub):  # lock-held: _lock
        """One speculative round, two device-chained dispatches and zero
        host syncs: the draft proposes ``spec_k`` greedy tokens per slot
        from its OWN cache (draft cache donated through), then the
        target verifies the whole window in ONE batched forward and
        commits the accepted prefix in-program (cache + slot state
        donated).  The draft tokens never touch the host — they flow
        propose → verify as a device array.  A failure in either
        dispatch is handled by the caller's decode-failure recovery
        (``_abort_in_flight`` drops the draft cache too)."""
        live = int(self._mirror_active.sum())
        t0 = time.perf_counter()
        with self._observe_dispatch("spec_propose", phase="decode",
                                    live_slots=live):
            draft, self._draft_cache = self.engine._run_guarded(
                self._propose_fn,
                (self._draft_params, self._draft_cache, self._state))
        t1 = time.perf_counter()
        self.stats["spec_draft_secs"] += t1 - t0
        with self._observe_dispatch("spec_verify", phase="decode",
                                    live_slots=live):
            toks, accepted, self._cache, self._state = \
                self.engine._run_guarded(
                    self._verify_fn,
                    (self.engine._params, self._cache, self._state,
                     jnp.asarray(self._pages.table()), draft, sub))
        self.stats["spec_verify_secs"] += time.perf_counter() - t1
        return ("spec", toks[None], accepted[None], [])

    # ------------------------------------------------------------------ #
    # Event processing (the host's lagging mirror of the device)
    # ------------------------------------------------------------------ #
    def _process_events(self, finished, keep=0):  # lock-held: _lock
        while len(self._events) > keep:
            inject.fire("serving.mirror_drain")
            ev = self._events.popleft()
            if ev[0] == "admit":
                self._process_admit(ev, finished)
            elif ev[0] == "spec":
                self._process_spec(ev, finished)
            else:
                self._process_decode(ev, finished)

    def _account_expert_load(self, loads, sp, steps=1):  # lock-held: _lock
        """Fold the load vectors (``slots._expert_load``) of programs
        whose results the scheduler has just waited for — each
        ``steps`` forward passes — into ``stats`` /
        ``moe_expert_tokens``, and put their sums on the span ``sp``
        with ``moe_calls``, the expert-layer calls they cover."""
        if not loads:
            return
        assigned = touched = busiest = 0
        held = self.moe_expert_tokens.size
        beside = dict.fromkeys(self._moe_columns, 0)
        for vec in map(np.asarray, loads):
            self.moe_expert_tokens += vec[:held].reshape(
                self.moe_expert_tokens.shape)
            assigned += int(vec[:held].sum())
            touched += int(vec[-2])
            busiest += int(vec[-1])
            for i, key in enumerate(self._moe_columns):
                beside[key] += int(vec[held + i])
        for key, n in beside.items():
            self.stats[key] += n
        sp.set(**beside)
        self.stats["moe_assignments"] += assigned
        self.stats["moe_experts_touched"] += touched
        self.stats["moe_max_expert_tokens"] += busiest
        sp.set(moe_assignments=assigned, moe_experts_touched=touched,
               moe_max_expert_tokens=busiest,
               moe_calls=len(loads) * steps * len(self.moe_expert_tokens))

    def _process_admit(self, ev, finished):  # lock-held: _lock
        _, req, slot, first_dev, draft_lane, expert_loads = ev
        with span("dstpu.sched.wait_device", track="scheduler",
                  cat="scheduler", event="admit", rid=req.rid) as sp:
            first = int(np.asarray(first_dev))
            # the prompt's chunks ran before the admit that sampled
            # ``first``: their loads are on the host's side of the wait
            self._account_expert_load(expert_loads, sp)
        self.stats["sync_secs"] += sp.dur_s
        if draft_lane is not None:
            self._draft_lanes.give_back(draft_lane)
        if req.status in TERMINAL_STATUSES:
            # shed/cancelled while the admit event was in flight: free
            # the slot now (the shed path left it to us), discard the
            # token — the device lane stays a masked no-op until its
            # next occupant's admit overwrites it
            self._free_slot(slot)
            return
        if req.first_tok_t is None:
            req.first_tok_t = time.monotonic()
        if self._tracer is not None and req.t_first_tok is None \
                and req.t_trace is not None:
            # the first token is PROCESSED here (the host-mirror drain
            # point, one event behind the device) — TTFT is stamped
            # exactly once, on the tracer's clock; TokenStream replays
            # re-read req.tokens, they never come back through here
            req.t_first_tok = req.t_last_tok = self._tracer.now()
            self._hist.ttft.observe(req.t_first_tok - req.t_trace)
        req.tokens = list(req.prefix) + [first]
        if self._fairness is not None:
            # the sampled first token; prefill tokens (incl. any resumed
            # prefix) were charged when admission started
            self._fairness.charge(req.client_id, 1)
        # mirror the admit program's activation rule (the device saw the
        # REMAINING budget max_new - len(prefix))
        dev_new = req.max_new - len(req.prefix)
        if (req.eos >= 0 and first == req.eos) or dev_new == 1:
            self._free_slot(slot)
            finished[req.rid] = self._finalize(req)
        else:
            self._mirror_active[slot] = True
            self._publish_progress(req)

    def _mirror_commit_token(self, s, req, tok, finished):  # lock-held: _lock
        """The ONE per-token mirror rule both decode paths (plain block
        and speculative window) share: append the committed token,
        account it, and either retire the slot (eos or budget exhausted
        — mirroring the in-program rule) or flush the per-token stream
        at this drain point (the stream's tick — one event behind the
        device, TTFT/time-between-tokens observable here).  Returns
        True when the slot retired."""
        req.tokens.append(tok)
        self.stats["decode_tokens"] += 1
        if self._tracer is not None and req.t_trace is not None:
            # time-between-tokens at the drain point, stamped once per
            # token — late-attached stream replays never re-stamp
            now = self._tracer.now()
            if req.t_last_tok is not None:
                self._hist.tbt.observe(now - req.t_last_tok)
            req.t_last_tok = now
        if self._fairness is not None:
            self._fairness.charge(req.client_id, 1)
        if (req.eos >= 0 and tok == req.eos) \
                or len(req.tokens) >= req.max_new:
            self._mirror_active[s] = False
            self._free_slot(s)
            finished[req.rid] = self._finalize(req)
            return True
        self._publish_progress(req)
        return False

    def _process_decode(self, ev, finished):  # lock-held: _lock
        n0 = self.stats["decode_tokens"]
        with span("dstpu.sched.wait_device", track="scheduler",
                  cat="scheduler", event="decode") as sp:
            toks = np.asarray(ev[1])                     # [block, N]
            loads = list(map(np.asarray, ev[2]))         # expert models
        self.stats["sync_secs"] += sp.dur_s
        # mirror the in-program retirement rule step by step: an emitted
        # eos (or max_new reached) ends the request and frees its slot
        with span("dstpu.sched.commit", track="scheduler", cat="mirror",
                  kind="decode") as sp:
            for t in range(toks.shape[0]):
                row = toks[t]
                for s in np.nonzero(self._mirror_active)[0]:
                    req = self._slots[s]
                    self._mirror_commit_token(s, req, int(row[s]),
                                              finished)
            committed = self.stats["decode_tokens"] - n0
            sp.set(tokens=committed)
            self._account_expert_load(loads, sp, steps=self.block)
        if self._flightrec is not None:
            self._flightrec.record("commit", kind="decode",
                                   tokens=committed)
        self.occupancy_trace.append(
            (self._it, int(self._mirror_active.sum())))

    def _process_spec(self, ev, finished):  # lock-held: _lock
        """Mirror one speculative round: per live slot, append EXACTLY
        the ``accepted[s]`` committed tokens (the device's in-program
        accept count — rows beyond it are window padding, never real
        tokens) and apply the same per-token eos/max_new retirement rule
        the plain decode mirror applies.  Each committed token is pushed
        to the request's stream subscribers individually at this drain
        point, so a dispatch that commits m tokens emits m ORDERED
        per-token events with monotonic indices — never one blob per
        dispatch — and mid-window retirement cuts the stream exactly at
        the terminal token."""
        _, toks_dev, acc_dev, loads = ev
        n0, w0 = (self.stats["spec_committed_tokens"],
                  self.stats["spec_windows"])
        with span("dstpu.sched.wait_device", track="scheduler",
                  cat="scheduler", event="spec") as sp:
            # the dispatch's windows, in order: one (the draft model's
            # verify) or ``decode_block`` (a self-drafting block)
            toks = np.asarray(toks_dev)           # [windows, spec_k+1, N]
            acc = np.asarray(acc_dev)             # [windows, N]
            loads = list(map(np.asarray, loads))  # expert models
        self.stats["sync_secs"] += sp.dur_s
        self.stats["spec_rounds"] += 1
        with span("dstpu.sched.commit", track="scheduler", cat="mirror",
                  kind="spec") as commit_span:
            for w in range(toks.shape[0]):
                for s in np.nonzero(self._mirror_active)[0]:
                    req = self._slots[s]
                    m = int(acc[w, s])
                    self.stats["spec_windows"] += 1
                    self.stats["spec_committed_tokens"] += m
                    for i in range(m):
                        # by the in-program commit rule the device stopped
                        # committing at exactly the token that retires here
                        if self._mirror_commit_token(
                                s, req, int(toks[w, i, s]), finished):
                            break
            committed = self.stats["spec_committed_tokens"] - n0
            commit_span.set(tokens=committed)
            self._account_expert_load(loads, commit_span,
                                      steps=toks.shape[0])
        if self.self_draft:
            # spec_k is 1: a window offers one draft, verifies two rows
            # and commits one or two tokens
            windows = self.stats["spec_windows"] - w0
            for key, n in (("windows", windows), ("proposed", windows),
                           ("accepted", committed - windows),
                           ("rows_rejected", 2 * windows - committed)):
                self._spec_unreported[key] += n
                if key != "windows":        # spec_windows: counted above
                    self.stats["spec_" + key] += n
        # derived rates for /metrics + Serving/spec_* monitor events
        w = self.stats["spec_windows"]
        if w:
            committed = self.stats["spec_committed_tokens"]
            self.stats["spec_accept_rate"] = \
                (committed - w) / (w * self.spec_k)
            self.stats["spec_tokens_per_dispatch"] = \
                committed / self.stats["spec_rounds"]
        d, v = self.stats["spec_draft_secs"], self.stats["spec_verify_secs"]
        if d + v > 0:
            self.stats["spec_draft_fraction"] = d / (d + v)
        if self._flightrec is not None:
            self._flightrec.record(
                "commit", kind="spec",
                tokens=self.stats["spec_committed_tokens"] - n0)
        self.occupancy_trace.append(
            (self._it, int(self._mirror_active.sum())))

    def _finalize(self, req):  # lock-held: _lock
        """The ``generate()`` output contract: ``[prompt..., tokens...]``
        of length ``P + max_new_tokens``, eos-padded past an early stop.
        For resumed requests ``tokens`` already includes the prefix, so
        the stitched output is exactly the uninterrupted run's."""
        req.finished_it = self._it
        req.status = RequestStatus.COMPLETED
        self.stats["completed"] += 1
        P = len(req.ids)
        pad = req.eos if req.eos >= 0 else 0
        out = np.full((P + req.max_new,), pad, np.int32)
        out[:P] = req.ids
        out[P:P + len(req.tokens)] = np.asarray(req.tokens, np.int32)
        ttft = (req.first_tok_t - req.submit_t) \
            if req.first_tok_t is not None else None
        self._results[req.rid] = RequestResult(
            rid=req.rid, status=RequestStatus.COMPLETED, output=out,
            client_id=req.client_id, submitted_it=req.submitted_it,
            finished_it=self._it, ttft_s=ttft,
            **self._trace_done(req, RequestStatus.COMPLETED))
        if self._flightrec is not None:
            self._flightrec.record("terminal", rid=req.rid,
                                   status=RequestStatus.COMPLETED,
                                   tokens=len(req.tokens))
        self._publish_end(req, RequestStatus.COMPLETED)
        return out

    # ------------------------------------------------------------------ #
    # Graceful preemption: drain -> crash-atomic snapshot -> resume
    # ------------------------------------------------------------------ #
    def _undrained_requests(self):  # lock-held: _lock
        """Every request that would be lost if the process died now:
        in-slot (non-terminal), mid-admission, and queued — in a stable
        order (slots, pending, queue)."""
        reqs = [r for r in self._slots
                if r is not None and r.status not in TERMINAL_STATUSES]
        if self._pending is not None \
                and self._pending.req.status not in TERMINAL_STATUSES:
            reqs.append(self._pending.req)
        reqs.extend(r for r in self._queue
                    if r.status not in TERMINAL_STATUSES)
        return reqs

    def preempt(self, checkpoint_dir, drain_budget_s=None, tag=None):
        """The SIGTERM path (``DSElasticAgent`` preemption): stop
        admission, keep decoding the in-flight slots for up to
        ``drain_budget_s`` seconds (default: the config's
        ``drain_budget_s``; 0 = snapshot immediately), then snapshot
        every undrained request — prompt, tokens generated so far,
        remaining deadline and the scheduler RNG lane state — through the
        crash-atomic checkpoint protocol, and retire the engine (it is
        closed afterwards).  Returns ``(tag, snapshotted_rids,
        finished)`` where ``finished`` holds the requests that completed
        during the drain.  A restarted server picks the snapshot up with
        :meth:`restore`; greedy resumed outputs are bitwise-identical to
        an uninterrupted run."""
        self._check_owner("preempt()")
        with self._lock:
            return self._preempt_locked(checkpoint_dir, drain_budget_s,
                                        tag)

    def _preempt_locked(self, checkpoint_dir, drain_budget_s, tag):  # lock-held: _lock
        if self._closed:
            raise RuntimeError("preempt() on a closed ServingEngine")
        budget = self.config.drain_budget_s if drain_budget_s is None \
            else float(drain_budget_s)
        t0 = time.monotonic()
        finished = {}
        self._shed_expired()
        # drain: decode-only iterations (no admissions) under the budget
        while (self._mirror_active.any()
               or any(e[0] == "admit" for e in self._events)) \
                and time.monotonic() - t0 < budget:
            inject.fire("serving.mid_drain")
            try:
                dispatched = self._dispatch_decode()
            except Exception as e:
                # a sick device must not block the snapshot: the failed
                # dispatch aborted the in-flight slots (their requests
                # are ABORTED with the reason); snapshot what remains
                logger.error(f"serving preempt: drain dispatch failed "
                             f"({type(e).__name__}: {e}) — snapshotting "
                             f"the queue")
                break
            self._process_events(finished, keep=1 if dispatched else 0)
        try:
            self._process_events(finished, keep=0)
        except Exception as e:
            logger.warning(f"serving preempt: discarding unreadable "
                           f"in-flight events ({type(e).__name__}: {e})")
            self._abort_in_flight("preempt event flush failed")
        drain_secs = time.monotonic() - t0
        self._shed_expired()                 # don't snapshot expired work
        undrained = self._undrained_requests()
        tag = self.snapshot(checkpoint_dir, tag=tag)
        self._preempt_detail = (f"preempted — snapshotted for resume "
                                f"(tag {tag!r})")
        for req in undrained:
            req.status = RequestStatus.PREEMPTED
            # active HTTP/token streams end with the TYPED event — the
            # client knows its request resumes on a restarted server
            # (reconnect and re-subscribe) instead of seeing a dead
            # socket with no verdict
            self._publish_end(req, RequestStatus.PREEMPTED,
                              self._preempt_detail)
        snapped = [r.rid for r in undrained]
        # retire the engine without ABORTED accounting: the snapshotted
        # requests are not lost, they resume elsewhere
        self._pending = None
        self._queue.clear()
        self._events.clear()
        self._slots = [None] * self.num_slots
        self._free = deque(range(self.num_slots))
        self._mirror_active[:] = False
        self._release_workspaces()
        self._pages.reset()
        self._detach_observability()
        self._closed = True
        self._close_report = sorted(snapped)
        self._cond.notify_all()
        self.wake.set()
        self.stats["drain_secs"] = \
            self.stats.get("drain_secs", 0.0) + drain_secs
        self.stats["preempt_snapshotted"] = len(snapped)
        if self._pending_reports:
            finished.update(self._pending_reports)
            self._pending_reports.clear()
        logger.warning(f"serving preempt: drained {drain_secs:.2f}s, "
                       f"{len(finished)} request(s) finished in drain, "
                       f"{len(snapped)} snapshotted to {tag!r}")
        return tag, snapped, finished

    def snapshot(self, checkpoint_dir, tag=None):
        """Crash-atomically publish the undrained requests (and the
        scheduler RNG lane state) under ``checkpoint_dir`` — the
        serving analog of a training checkpoint (staging dir, manifest
        with checksums, fsync, atomic rename, ``latest`` swap; see
        ``inference/serving/snapshot.py``).  Pure write: the engine's
        bookkeeping is untouched.  Returns the tag.  Thread-safe (the
        state walk runs under the engine lock; ``preempt()`` re-enters
        it lock-held)."""
        with self._lock:
            return self._snapshot_locked(checkpoint_dir, tag)

    def _snapshot_locked(self, checkpoint_dir, tag):  # lock-held: _lock
        from deepspeed_tpu.inference.serving.snapshot import save_snapshot
        self._snap_seq += 1
        tag = tag or f"serving_{self._snap_seq}"
        import json
        now = time.monotonic()
        reqs = []
        for r in self._undrained_requests():
            cid = r.client_id
            try:
                json.dumps(cid)
            except (TypeError, ValueError):
                # a non-JSON client_id must never cost the snapshot (and
                # with it every undrained request) on the SIGTERM path
                logger.warning(
                    f"serving snapshot: request {r.rid} client_id "
                    f"{type(cid).__name__} is not JSON-serializable — "
                    f"stored as str()")
                cid = str(cid)
            entry = {
                "rid": int(r.rid),
                "client_id": cid,
                "prompt": [int(t) for t in r.ids],
                # tokens generated so far (a queued resumed request has
                # produced none this incarnation — carry its prefix)
                "tokens": [int(t) for t in (r.tokens or r.prefix)],
                "max_new": int(r.max_new),
                "eos": int(r.eos),
                "deadline_remaining_s":
                    None if r.deadline is None else r.deadline - now,
                "submitted_it": int(r.submitted_it),
                "priority": int(r.priority),
            }
            pages = None if r.slot is None \
                else self._pages.slot_pages_str(r.slot)
            if pages is not None:
                # diagnostics only (restore re-prefills; physical pages
                # are meaningless in another process) — range-compressed,
                # never one JSON int per table entry
                entry["pages"] = pages
            reqs.append(entry)
        fcfg = getattr(self.engine._config, "fault", None)
        state = {
            "seq": int(self._snap_seq),
            "iteration": int(self._it),
            "next_rid": int(self._next_rid),
            "rng": np.asarray(
                jax.random.key_data(self._rng)).ravel().tolist(),
            "requests": reqs,
        }
        if self._fairness is not None:
            # quota balances survive preemption: a restarted server keeps
            # enforcing the same per-client budgets (conservative — decay
            # during the downtime is not credited; frontend/fairness.py)
            state["fairness"] = self._fairness.state_dict()
        return save_snapshot(
            checkpoint_dir, tag, state,
            checksum=getattr(fcfg, "checksum", None) or "sha256")

    def restore(self, checkpoint_dir):
        """Resume the newest valid snapshot's requests into this server's
        queue, keeping their original request ids, client ids and
        remaining deadlines; the RNG lane state is restored too.  Each
        resumed request re-prefills ``prompt + generated-so-far`` through
        the ordinary admission path and decodes only its remaining budget
        — under greedy decoding the stitched output is bitwise what the
        uninterrupted run would have produced.  Returns the restored
        request ids (empty when there is nothing to resume)."""
        from deepspeed_tpu.inference.serving.snapshot import \
            load_newest_snapshot
        tag, state = load_newest_snapshot(checkpoint_dir)
        if state is None:
            return []
        with self._lock:
            rids = self._restore_locked(tag, state)
        self.wake.set()                  # rouse an idle scheduler thread
        return rids

    def _restore_locked(self, tag, state):  # lock-held: _lock
        self._snap_seq = max(self._snap_seq, int(state.get("seq", 0)))
        if self._fairness is not None and state.get("fairness"):
            self._fairness.load_state(state["fairness"])
        if state.get("rng"):
            self._rng = jax.random.wrap_key_data(
                jnp.asarray(state["rng"], jnp.uint32))
        now = time.monotonic()
        rids = []
        for r in state.get("requests", []):
            if int(r["rid"]) in self._requests:
                raise ValueError(
                    f"restore(): request id {r['rid']} already exists on "
                    f"this server — call restore() before submitting new "
                    f"work (snapshotted ids are preserved verbatim)")
            ids = np.asarray(r["prompt"], np.int32)
            prefix = [int(t) for t in r.get("tokens", [])]
            max_new, eos = int(r["max_new"]), int(r["eos"])
            if len(prefix) >= max_new \
                    or (eos >= 0 and eos in prefix):
                # defensive: a finished request has nothing to resume
                continue
            deadline = None
            if r.get("deadline_remaining_s") is not None:
                deadline = now + float(r["deadline_remaining_s"])
            req = ServeRequest(
                int(r["rid"]), ids, max_new, eos, submitted_it=self._it,
                deadline=deadline, client_id=r.get("client_id"),
                prefix=prefix, submit_t=now, resumed=True,
                # clamp to THIS server's lane count (the snapshot may
                # come from a config with more lanes); aging restarts
                # from restore time — conservative, never a starvation
                priority=min(int(r.get("priority", 0)),
                             self.priority_lanes - 1))
            # every restored request must pass submit()'s capacity check
            # against THIS server's lane config (the snapshot may come
            # from a server with a larger max_cache_len / smaller chunk
            # — admitting an oversized request would stream prefill
            # chunks past the lane's end)
            P = len(ids)
            spec_tail = (self.spec_k - 1) if self.speculative else 0
            need = max(P + max_new + spec_tail,
                       -(-P // self.chunk) * self.chunk)
            if need > self.cache_len:
                self._requests[req.rid] = req
                self._record_terminal(
                    req, RequestStatus.ABORTED,
                    f"restored request needs more than the "
                    f"{self.cache_len} cache positions this server's "
                    f"lanes hold (prompt {P} + new {max_new}) — raise "
                    f"serving.max_cache_len to resume it")
                logger.warning(f"serving restore: request {req.rid} does "
                               f"not fit this server's lanes — ABORTED")
                self._next_rid = max(self._next_rid, req.rid + 1)
                continue
            too_big = self._pages.cannot_hold(need)
            if too_big:
                # the snapshot may come from a server with a bigger page
                # pool — mirror submit()'s pool-capacity check instead
                # of stalling admission forever on an unfittable request
                self._requests[req.rid] = req
                self._record_terminal(
                    req, RequestStatus.ABORTED,
                    f"restored request needs {too_big} — raise "
                    f"serving.num_pages to resume it")
                logger.warning(f"serving restore: request {req.rid} does "
                               f"not fit this server's page pool — "
                               f"ABORTED")
                self._next_rid = max(self._next_rid, req.rid + 1)
                continue
            # the resumed fill (prompt + prefix) must still fit a lane;
            # when the chunk-padded tail would overflow, drop the prefix
            # and re-decode from scratch — still bitwise-correct, just
            # wasteful
            fill = P + len(prefix)
            padded = -(-fill // self.chunk) * self.chunk
            if prefix and max(fill + (max_new - len(prefix)) + spec_tail,
                              padded) > self.cache_len:
                logger.warning(
                    f"serving restore: request {req.rid} prefix "
                    f"({len(prefix)} tokens) does not fit its lane "
                    f"chunk-padded — re-decoding from the prompt")
                req.prefix = []
            if self._tracer is not None:
                # the resumed incarnation's span tree starts at restore
                req.t_trace = self._tracer.now()
            self._queue.append(req)
            self._requests[req.rid] = req
            self._next_rid = max(self._next_rid, req.rid + 1)
            rids.append(req.rid)
        self._next_rid = max(self._next_rid,
                             int(state.get("next_rid", 0)))
        self.stats["resumed"] += len(rids)
        if rids:
            log_dist(f"serving restore[{tag}]: resumed {len(rids)} "
                     f"request(s) {rids}", ranks=[0])
        return rids

    # ------------------------------------------------------------------ #
    # Plumbing
    # ------------------------------------------------------------------ #
    def _ensure_workspace(self):  # lock-held: _lock
        if self._cache is None:
            # the pools' allocation: once a server (again only after a
            # failed dispatch left the donated buffers dead)
            with span("dstpu.setup.pools", cat="setup") as sp:
                self._cache = self._pages.take(self.engine.compute_dtype)
                sp.set(**self._pages.pool_bytes(self._cache))
        if self.separate_draft and self._draft_cache is None:
            self._draft_cache = self._draft_ws.take(
                self.num_slots, self.cache_len, self.engine.compute_dtype)
        if self._state is None:
            self._state = {k: jnp.asarray(v) for k, v in init_slot_state(
                self.num_slots, draft=self.self_draft).items()}
            self._mirror_active[:] = False

    def _emit_metrics(self):  # lock-held: _lock
        mon = self.monitor
        if mon is None or not getattr(mon, "enabled", True):
            return
        wall = self.stats["wall_secs"]
        mon.write_events([
            ("Serving/queue_depth", self.queue_depth, self._it),
            ("Serving/slot_occupancy",
             self.active_slots / self.num_slots, self._it),
            ("Serving/decode_tok_s",
             self.stats["decode_tokens"] / wall if wall > 0 else 0.0,
             self._it),
            ("Serving/prefill_decode_ratio",
             self.stats["prefill_tokens"]
             / max(self.stats["decode_tokens"], 1), self._it),
            ("Serving/completed", self.stats["completed"], self._it),
            ("Serving/shed", self.stats["shed"], self._it),
            ("Serving/cancelled", self.stats["cancelled"], self._it),
            ("Serving/aborted", self.stats.get("aborted", 0), self._it),
            ("Serving/breaker_open",
             1.0 if self._breaker.open else 0.0, self._it),
            ("Serving/lock_wait_scheduler_s",
             self.stats["lock_wait_scheduler_s"], self._it),
            ("Serving/lock_wait_handler_s",
             self.stats["lock_wait_handler_s"], self._it),
        ] + ([
            ("Serving/fairness_rejected",
             self.stats["fairness_rejected"], self._it),
        ] if self._fairness is not None else []) + [
            ("Serving/page_pool_util", self.page_pool_utilization,
             self._it),
            ("Serving/prefix_hit_rate", self.prefix_hit_rate, self._it),
        ] + ([
            ("Serving/hbm_bytes_in_use",
             self.stats["hbm_bytes_in_use"], self._it),
            ("Serving/hbm_peak_bytes",
             self.stats["hbm_peak_bytes"], self._it),
            ("Serving/hbm_unattributed_bytes",
             self.stats["hbm_unattributed_bytes"], self._it),
        ] if self._memwatch is not None else []) + ([
            ("Serving/spec_accept_rate",
             self.stats["spec_accept_rate"], self._it),
            ("Serving/spec_tokens_per_dispatch",
             self.stats["spec_tokens_per_dispatch"], self._it),
            ("Serving/spec_draft_fraction",
             self.stats["spec_draft_fraction"], self._it),
        ] if self.speculative else []))
