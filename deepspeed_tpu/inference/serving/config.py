"""``serving`` config block — continuous-batching serving engine knobs
(``docs/serving.md``).  Kept import-light: ``inference/config.py`` embeds
this model, and the serving engine itself is imported lazily."""

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


class ServingConfig(DeepSpeedConfigModel):
    """Knobs for :class:`deepspeed_tpu.inference.serving.ServingEngine`
    (``engine.serve()``).  Default off = current behavior: nothing in the
    whole-batch ``generate()`` path changes unless ``serve()`` is called
    (the explicit opt-in); ``enabled`` documents the deployment intent in
    ops configs.  ``ServingEngine.warmup()`` precompiles the serving
    programs."""
    enabled: bool = False
    # fixed-shape KV slots: the ONE decode-step program is compiled for
    # exactly this many rows; requests map onto freed slots
    num_slots: int = 8
    # per-slot cache positions, the slot's VIRTUAL lane (rounded up to a
    # whole number of pages); every request must satisfy
    # ceil(prompt/chunk)*chunk <= max_cache_len and
    # prompt + max_new_tokens <= max_cache_len
    max_cache_len: int = 2048
    # admission-prefill chunk: prompts stream through the engine's donated
    # per-chunk executable in blocks of this many tokens (aligned to a
    # multiple of 8, floor 8, cap 512 like prefill_chunk_size)
    prefill_chunk: int = 128
    # the prefill stall a FULL batch tolerates between two of its decode
    # blocks (the Sarathi/Orca-style interleave bound): an iteration
    # prefills at most budget * num_slots / live lanes prompt tokens (whole
    # chunks, never fewer than the budget alone buys) before decode
    # resumes — the budget itself with every lane live, more the fewer
    # streams are waiting on it, num_slots budgets with none; 0 = unbounded
    # (finish every admission's prefill in one iteration)
    prefill_token_budget: int = 512
    # decode steps per host round trip: one compiled program advances all
    # slots `decode_block` tokens between scheduling points.  Larger blocks
    # amortize dispatch latency; retired slots idle for at most
    # decode_block-1 steps before the scheduler reclaims them
    decode_block: int = 4
    # admission order: "fcfs" (arrival) | "shortest_first" (shortest
    # prompt first — lowers mean time-to-first-token under backlog)
    admission: str = "fcfs"
    # ---- KV cache (docs/serving.md "KV cache"): one shared page pool +
    # per-slot block tables (traced args — still ONE decode executable
    # per server).  HBM cost is num_pages * page_size, shared prefixes
    # are stored once, and capacity pressure degrades into admission
    # backpressure instead of an allocation cliff.  (The ``paged``
    # switch and its kernel A/B knob are gone with the lane layout they
    # selected against: ``true`` is accepted and ignored, ``false`` is
    # refused by name — ServingEngine.__init__.) ----
    # positions per page (rounded up to a multiple of 8 — sublane
    # alignment — floor 8).  Smaller pages waste less per-request tail
    # but cost a bigger table and finer gathers
    page_size: int = 64
    # physical pages in the pool, INCLUDING the reserved trash page 0;
    # 0 = auto: num_slots * ceil(max_cache_len/page_size) + 1 (full
    # worst-case capacity — no savings, no pressure).  Size it below
    # auto to actual demand for the HBM win; admission then waits for
    # free pages under pressure (queue backpressure, never corruption)
    num_pages: int = 0
    # copy-on-write prefix sharing: page-aligned leading
    # blocks of a prompt that hash-match an earlier prompt map to the
    # SAME physical pages, prefilled once; divergence re-prefills at
    # most one page.  Unreferenced prefix pages evict LRU under pool
    # pressure
    prefix_cache: bool = True
    # ---- speculative decoding (docs/serving.md "Speculative
    # decoding") ----
    # speculative=True: a small DRAFT model proposes spec_k tokens per
    # live slot per dispatch and the target model verifies all of them
    # in ONE batched forward — up to spec_k+1 tokens committed per
    # target forward, greedy outputs bitwise-identical to
    # non-speculative serving.  Requires a draft model
    # (engine.serve(draft_module=..., draft_params=...) or
    # spec_draft_model="self") and greedy decoding (do_sample=False).
    # Supersedes decode_block (the verify window is the block).  Default
    # off = seed behavior.
    speculative: bool = False
    # draft tokens proposed per verify window; each window commits
    # between 1 and spec_k+1 tokens.  Each slot lane reserves spec_k-1
    # extra tail positions for the window's writes, so requests must
    # satisfy prompt + max_new_tokens + spec_k - 1 <= max_cache_len
    spec_k: int = 4
    # draft model source when serve() is not handed one explicitly:
    # "self" = the target model drafts for itself (accept rate 1.0 under
    # greedy — the dispatch/batched-verify ceiling; doubles KV + decode
    # compute), or an OPT preset name ("opt-125m") built against the
    # target's vocab — pass its trained weights via
    # serve(draft_params=...), else they are RANDOMLY initialized
    # (accept rate ~0; smoke/bench floor only, warned loudly).  "mtp" =
    # the model's OWN multi-token-prediction module drafts (a model with a
    # ``draft`` method, models/glm5.py): spec_k is 1, decode_block windows
    # ride one dispatch, the module's rows live in the model's page pool
    spec_draft_model: str = ""
    # sampling applied to every request (greedy when do_sample=False);
    # per-request eos_token_id/max_new_tokens ride the slot state instead
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    # ---- robustness / SLO knobs (docs/serving.md "Robustness & SLOs",
    # inference/serving/slo.py) — every default = seed behavior ----
    # bounded-queue admission control: submit() beyond this depth either
    # rejects (QueueFull) or blocks running scheduler iterations inline
    # until a spot frees; 0 = unbounded (seed behavior)
    max_queue_depth: int = 0
    queue_policy: str = "reject"          # "reject" | "block"
    # default per-request wall-clock deadline (seconds from submit);
    # submit(deadline_s=...) overrides per request; 0 = no deadline.
    # Expired-while-queued requests are SHED before ever occupying a
    # slot; in-slot expiry retires at the next scheduling point
    default_deadline_s: float = 0.0
    # dispatch circuit breaker: this many CONSECUTIVE failed
    # decode/admit/prefill dispatches trip it open — failures are
    # absorbed (requests -> ABORTED), admission stops and submit()
    # rejects with reason until the cooldown's half-open probe succeeds.
    # 0 = off (seed behavior: dispatch failures propagate to the caller)
    breaker_threshold: int = 0
    breaker_cooldown_s: float = 30.0
    # drain() wall-clock timeout: raise DrainTimeout with per-slot
    # diagnostics instead of spinning forever on a wedged scheduler;
    # 0 = off (seed behavior)
    drain_timeout_s: float = 0.0
    # ---- network front end (docs/serving.md "Network front end") ----
    # admission priority lanes layered on the fcfs/shortest_first queue:
    # submit(priority=p) with 0 <= p < priority_lanes, 0 = most urgent.
    # 1 (default) = no lanes, seed admission order
    priority_lanes: int = 1
    # starvation bound for the lanes: a queued request's effective
    # priority improves one lane per this many seconds waited, so the
    # lowest lane reaches lane 0 after (priority_lanes-1)*aging seconds
    # and fcfs/shortest_first order takes over; 0 = no aging (strict
    # lanes — low priority CAN starve under sustained high-priority load)
    priority_aging_s: float = 30.0
    # multi-tenant fairness: per-client_id token-rate accounting
    # (admitted prefill + generated tokens, exponentially decaying
    # window) feeding admission control — submit() from a client whose
    # window usage exceeds fairness_tokens_per_s * fairness_window_s
    # raises QueueFull (HTTP 429) while other clients keep flowing.
    # 0 = off (seed behavior)
    fairness_tokens_per_s: float = 0.0
    # decay time constant (seconds) of the fairness window: usage decays
    # by 1/e per window, budget = fairness_tokens_per_s * window
    fairness_window_s: float = 10.0
    # graceful-preemption drain budget (preempt()): keep decoding
    # in-flight slots for up to this many seconds before snapshotting
    # the remainder; 0 = snapshot immediately, no drain
    drain_budget_s: float = 30.0
    # ---- observability (docs/observability.md) — every default = seed
    # behavior: zero spans, zero histograms, zero ring events ----
    # per-request span tracing: record a span tree per request (submit ->
    # queue wait -> prefill chunks -> admit -> decode/spec dispatches ->
    # terminal) at the existing scheduler seams, export Chrome
    # trace-event JSON via srv.dump_trace(path) (Perfetto: one track per
    # slot + scheduler/queue tracks), attach a queue/prefill/decode/host
    # latency breakdown to every RequestResult, and feed the
    # TTFT/TBT/queue-wait/dispatch/lock-wait histograms /metrics
    # exposes.  Host-side only: no new jitted programs, greedy outputs
    # bitwise-identical either way
    tracing: bool = False
    # span-ring bound (oldest spans fall off; the dump records how many
    # were dropped)
    trace_max_spans: int = 100000
    # flight recorder: a bounded ring of recent structured scheduler
    # events (dispatch begin/end, admit/shed/cancel/abort decisions,
    # breaker transitions, lock-wait samples, fault-injection hits)
    # that auto-dumps to JSON on breaker-open, DrainTimeout,
    # ConcurrencyViolation and scheduler-thread death, and on demand via
    # GET /debug/flightrec, SIGUSR2 or srv.dump_flightrec().  The ring
    # has its OWN lock — readers never contend the engine lock
    flight_recorder: bool = False
    # ring capacity in events (memory is bounded; ~300 bytes/event)
    flight_recorder_events: int = 2048
    # auto-dump directory; "" = <tmpdir>/dstpu_flightrec
    flight_recorder_dir: str = ""
    # on-demand device-level profiling: POST /debug/profile?secs=N runs
    # jax.profiler for N seconds and returns the trace directory
    # (Perfetto/TensorBoard-loadable).  Off by default: profiling is a
    # debug affordance, not a production endpoint
    profile_endpoint: bool = False
    # live device-memory telemetry (docs/observability.md, "Device
    # memory & roofline"): a host-side sampler reads per-device
    # bytes_in_use/peak/limit through the accelerator's canonical
    # memory reader at scheduler seams, reconciles the engine's known
    # owners (page pool, KV/draft workspaces, params, lanes, slot
    # state) against the device total into an unattributed-bytes gap,
    # exports dstpu_device_memory_* gauges on /metrics, records
    # memory_sample events in the flight-recorder ring (when that is
    # on), and stamps a peak-HBM watermark into stats.  Host-side only
    # — zero new executables, outputs bitwise-identical either way.
    # Default off = seed behavior
    memory_telemetry: bool = False
    # seconds between memory samples (a clock compare between samples;
    # each sample is one PJRT memory_stats() host call per device)
    memory_sample_interval_s: float = 10.0
