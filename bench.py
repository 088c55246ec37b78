"""Benchmark suite: the reference's headline workloads on the local chip(s).

Mirrors DeepSpeed-Chat's numbers (``BASELINE.json`` / ``BASELINE.md``):

1. **North star** — step-1 SFT of OPT-1.3B with ZeRO-3, target >=35% MFU.
   A single v5e chip (16 GB) cannot hold fp32 master+moments for 1.3B
   params (12 bytes/param = 15.8 GB), so the 1.3B run uses
   the documented memory-lean mode (bf16 master weights + bf16 Adam
   moments, fp32 optimizer arithmetic — ``bf16.master_weights_in_bf16`` +
   optimizer ``state_dtype``).  Headline metric.
2. **Regression guard** — OPT-350M SFT with full fp32 master/moments
   (reference-exact semantics), the round-1 38%-MFU config.
3. **Generation** — the DS-Chat generation phase (prompt 256 + gen 256,
   ``blogs/deepspeed-chat/README.md:57``) through ``InferenceEngine``'s
   jitted prefill+decode program, at bf16 / int8 / int8+int8-KV and at
   throughput (bs64/bs128) and long-cache (4k) serving points.
4. **Hybrid RLHF** — DS-Chat step-3 loop (train steps + shared-weight
   rollouts) with a full-pytree weight-identity check.
5. **Long context** — seq-8k SFT through the Pallas flash path.
Plus a **calibration** phase that measures the chip's achievable HBM
bandwidth and MXU flops so every roofline/MFU claim is anchored to an
in-run measurement, not just a datasheet constant.

Crash containment (the round-3 lesson: one late-phase OOM erased the whole
record; the round-5 lesson: one 40-min cold compile starved everything
behind it): each phase runs in its OWN subprocess, like the reference runs
each workload under its launcher (``launcher/runner.py:377``).  The parent
never imports jax, so a dead phase cannot pin device memory anywhere.
Phases run CHEAP-FIRST under per-phase wall-clock budgets
(``BENCH_PHASE_TIMEOUT`` × ``PHASE_TIMEOUT_SCALE``); an overrun is
skipped-and-recorded (no fallback retry — a safe config fixes an OOM, not
slowness; ``BENCH_RETRY_ON_TIMEOUT=1`` re-enables it), and an optional
``BENCH_SUITE_BUDGET`` skips whatever the total budget can no longer
afford.  Under a suite budget, phase ORDER rotates round-robin across
rounds by staleness (``_phase_order``, reading the ``BENCH_r*.json``
trail): whatever starved last round runs first this round, so every
phase is measured every few rounds instead of the same leading k forever
(the round-5 blackout: 3/10 phases, five rounds running).  A crashed phase is retried ONCE with a safe config (remat on /
smaller batch, recorded as ``"fallback": true``) and a double failure
records an ``error`` field instead of killing the run.  Results accumulate
TWO ways as phases complete: the raw phase map in ``.bench_partial.json``
and the full driver-contract record in ``BENCH_partial.json`` (env
``BENCH_RESULTS_JSON``), so an interrupt / kill / crash after phase k
still leaves a complete record of all k finished phases — Ctrl-C and
SIGTERM additionally flush that record to stdout and exit 0.  Engines run
with the persistent compile/executable cache
(``runtime/compile_cache.py``), so every
program — including sft_2.7b's — is cold exactly once per machine; each
phase's record carries a ``compile_cache`` block showing what it compiled
vs reloaded.  The final line on stdout is ONE JSON object and the exit
code is 0 whenever the harness itself survived — missing numbers are
visible as ``error`` fields, never as a stack trace in place of the
record.

``BENCH_MODEL``/``BENCH_*`` env vars run a single custom training bench
in-process instead (old behavior).
"""

import datetime
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import numpy as np


def _setup_compile_cache():
    """Persistent compile/executable cache (runtime/compile_cache.py): the
    suite is compile-dominated (sft_2.7b's four 2.7B backward programs
    alone approach 40 min cold — the rc=124 that erased the round-5
    record); the framework cache makes every program cold exactly once per
    machine.  Shared by all phase subprocesses."""
    from deepspeed_tpu.runtime.compile_cache import configure_persistent_cache
    configure_persistent_cache(min_compile_time_secs=2.0)


def _cc_block():
    """``compile_cache`` config block handed to every engine a phase
    builds: persistent XLA cache + serialized AOT executables, shared
    across phase subprocesses and across runs."""
    return {"enabled": True, "min_compile_time_secs": 2.0}


def _cache_report(before):
    """Delta of the compile-cache counters across one phase body — makes
    compile cost (and the warm-run savings) visible in the record."""
    from deepspeed_tpu.runtime.compile_cache import stats
    now = stats().snapshot()
    rep = {k: now[k] - before.get(k, 0)
           for k in ("persistent_requests", "persistent_hits",
                     "executable_hits", "executable_misses",
                     "executable_saves")}
    rep["compile_seconds"] = {
        k: round(v, 1) for k, v in now["compile_seconds"].items()
        if k not in before.get("compile_seconds", {})}
    return rep


def _sync_scalar(x):
    """Fence: wait for ``x`` on the device."""
    import jax
    return jax.block_until_ready(x)


def _measured_peaks():
    """(tflops, gbps) from the calibration phase, handed to later phases
    via env; (None, None) when calibration hasn't run."""
    t = os.environ.get("BENCH_MEASURED_TFLOPS")
    g = os.environ.get("BENCH_MEASURED_GBPS")
    return (float(t) if t else None, float(g) if g else None)


# --------------------------------------------------------------------- #
# Phase bodies (run inside a phase subprocess)
# --------------------------------------------------------------------- #

def calibrate_bench():
    """Measure what this chip actually achieves, next to the datasheet
    constants the profiler uses — anchors every ``mfu`` /
    ``hbm_utilization`` in the suite (a wrong peak constant would silently
    inflate them all).

    - HBM bandwidth: time ``y = x * 1.0001`` over a 1 GiB bf16 array
      (reads + writes 2 GiB; pure streaming, no reuse).
    - MXU flops: time a 8192^3 bf16 matmul (2*M*N*K flops, fully
      MXU-resident).
    """
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.profiling.flops_profiler.profiler import (
        device_peak_tflops, device_peak_hbm_gbps)

    on_cpu = jax.devices()[0].platform == "cpu"

    # Measurement hygiene: (1) every rep lives INSIDE one compiled
    # program, so per-execution dispatch overhead is paid once; (2) timing
    # two rep counts and differencing cancels the remaining per-execution
    # overhead (same trick the decode bench uses for prefill); (3) the
    # loop body must not be constant-foldable — a scale below 1 + 2^-7
    # rounds to bf16 1.0 and compiles to identity, and multiplying by the
    # SAME scalar every iteration folds to one multiply, so the scalar
    # rides the loop carry and changes per step.
    def timed_loop(build, warm_arg, reps):
        fn = jax.jit(build, static_argnums=(1,))
        _sync_scalar(fn(warm_arg, reps))           # compile + warm
        _sync_scalar(fn(warm_arg, 2 * reps))
        # one differenced pair only cancels the MEAN dispatch overhead.
        # MEDIAN of several pairs:
        # min-of-diffs is biased FAST (a contended t1 shrinks the diff and
        # inflates the rate — an early round recorded 3.8x the datasheet
        # bandwidth that way), while the median rejects both tails.
        # sample until 5 positive pairs land (cap 12 attempts): on a
        # loaded 1-core CI box a burst of scheduler noise can flip several
        # consecutive diffs negative, and giving up after 5 straight
        # attempts made the whole phase flaky — the estimator is unchanged
        # (median of positive diffs), only the patience grew
        diffs = []
        for _ in range(12):
            t0 = time.perf_counter()
            _sync_scalar(fn(warm_arg, reps))
            t1 = time.perf_counter()
            _sync_scalar(fn(warm_arg, 2 * reps))
            t2 = time.perf_counter()
            d = (t2 - t1) - (t1 - t0)
            if d > 0:
                diffs.append(d)
            if len(diffs) >= 5:
                break
        if not diffs:
            raise RuntimeError(
                "calibration: dispatch jitter swamped the measurement "
                "(all differenced pairs were non-positive)")
        return float(np.median(diffs)) / reps      # per-rep, overhead-free

    # --- streaming bandwidth: v = v * s with a per-iteration scalar ---
    n = ((1 << 26) if on_cpu else (1 << 30)) // 2   # 1 GiB bf16 (64 MiB cpu)
    x = jnp.ones((n,), jnp.bfloat16)
    assert float(jnp.bfloat16(1.0078125)) != 1.0    # really a multiply

    def bw(v, reps):
        def body(_, carry):
            v, s = carry
            return v * s, s + jnp.bfloat16(0.0078125)
        out, _ = jax.lax.fori_loop(0, reps, body,
                                   (v, jnp.bfloat16(1.0078125)))
        return out[0]

    dt = timed_loop(bw, x, 16)
    measured_gbps = 2 * x.nbytes / dt / 1e9  # read + write per element

    # --- MXU matmul: out = out @ a, data-dependent, unfoldable ---
    m = 1024 if on_cpu else 8192
    a = jnp.full((m, m), 1.0 / m, jnp.bfloat16)   # fixed point of p @ a

    def mm(p, reps):
        return jax.lax.fori_loop(0, reps, lambda _, o: o @ p, p)[0, 0]

    dt = timed_loop(mm, a, 4 if on_cpu else 8)
    measured_tflops = 2 * m ** 3 / dt / 1e12

    # --- host<->device link (the offload tier's speed limit) ---
    h = np.ones((1 << 27,), np.uint8)              # 128 MB
    x = jax.device_put(h); x.block_until_ready()   # warm path + alloc
    up, down = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        x = jax.device_put(h); x.block_until_ready()
        up.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _ = np.asarray(jax.device_get(x))
        down.append(time.perf_counter() - t0)
    link_up = h.nbytes / min(up) / 1e9
    link_down = h.nbytes / min(down) / 1e9

    const_tflops, const_gbps = device_peak_tflops(), device_peak_hbm_gbps()
    return {
        "platform": jax.devices()[0].platform,
        "n_devices": jax.device_count(),
        # host link: what ZeRO-Offload's per-boundary grad-down/param-up
        # round trip can at best achieve on THIS host path (the honest
        # denominator for the offload phase's overhead)
        "host_to_device_gbps": round(link_up, 2),
        "device_to_host_gbps": round(link_down, 2),
        "measured_hbm_gbps": round(measured_gbps, 1),
        "measured_mxu_tflops": round(measured_tflops, 1),
        "datasheet_hbm_gbps": const_gbps,
        "datasheet_mxu_tflops": const_tflops,
        # >1.0 would mean the datasheet constant understates the chip and
        # every "percent of roofline" in this suite is conservative
        "hbm_fraction_of_datasheet": round(measured_gbps / const_gbps, 3),
        "mxu_fraction_of_datasheet": round(measured_tflops / const_tflops, 3),
    }


def memory_snapshot_bench(fallback=False):
    """Per-program memory & roofline micro-phase (the r05-blackout
    lesson applied to the MEMORY record: cheap, pinned right behind
    calibration, so per-program HBM numbers commit even in rounds whose
    budget dies before the heavy phases).

    For every contract-locked hot-path program (the tier-1 entry-point
    builders — toy shapes, exact compiler budgets): compile, extract
    ``compiled.memory_analysis()`` + ``cost_analysis()`` through the
    same shared cost model ``PROGRAMS.lock`` format 3 locks, time a few
    executions, and derive the roofline block — achieved FLOP/s,
    achieved GB/s, arithmetic intensity, memory-bound/compute-bound —
    against the calibration phase's measured peaks (datasheet when
    calibration hasn't run or was implausible).  Wall times at toy
    shapes include host dispatch, so the achieved fractions are floors;
    the intensity and bound classification are timing-independent."""
    import jax
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.profiling.roofline import (device_peaks,
                                                  roofline_block)
    from deepspeed_tpu.tools.lint import mem_contract

    meas_t, meas_g = _measured_peaks()
    peak_t, peak_g, peak_src = device_peaks(meas_t, meas_g)

    def _copy(x):
        try:
            return x.copy()
        except Exception:
            return x

    want = os.environ.get("BENCH_MEMSNAP_PROGRAMS")
    want = {w.strip() for w in want.split(",") if w.strip()} if want \
        else None
    fallback_keep = {"inference_decode", "serving_decode_step",
                     "serving_admit"}
    programs, errors = {}, {}
    matched = set()
    # the name filter + builder->program map discipline is shared with
    # ds_lint --mem (mem_contract.filtered_builders): subset runs skip
    # the engine builds of filtered-out programs, and the map is
    # cross-checked against what each builder actually constructs
    for build, mapped in mem_contract.filtered_builders(want):
        if fallback and build.__name__ not in fallback_keep:
            # safe-config retry: the three cheapest engine builds
            # still commit a usable memory record
            continue
        reset_topology()
        try:
            ep = build()
            drift = mem_contract.map_drift_problem(build.__name__,
                                                   mapped, ep.name)
            if drift:
                errors[build.__name__] = drift
            if want and ep.name not in want:
                continue
            # matched BEFORE compiling: a matched program whose compile
            # fails is a program_errors entry, not a "misspelled name"
            matched.add(ep.name)
            # cache-bypassed: a persistent-cache reload (bench runs with
            # the compile cache on) reports degenerate alias bytes
            with mem_contract.fresh_compile_env():
                compiled = ep.fn.lower(*ep.args).compile()
            rec = mem_contract.memory_cost_of(compiled)
            # timed execution: donated buffers die per call, so every
            # rep runs on fresh copies; median rejects dispatch jitter
            times = []
            for _ in range(3):
                args = jax.tree.map(_copy, ep.args)
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(*args))
                times.append(time.perf_counter() - t0)
            wall = float(np.median(times))
            programs[ep.name] = {
                "memory": rec["memory"],
                "cost": rec["cost"],
                "roofline": roofline_block(
                    rec["cost"]["flops"], rec["cost"]["bytes_accessed"],
                    wall, peak_t, peak_g, peak_src),
            }
        except Exception as e:               # one sick program must not
            errors[build.__name__] = f"{type(e).__name__}: {e}"[:300]
        finally:                             # erase the others' numbers
            reset_topology()
    result = {
        "programs": programs,
        "n_programs": len(programs),
        "peaks": {"tflops": peak_t, "gbps": peak_g, "source": peak_src},
        "shapes": "tier-1 contract entry points (toy): budgets exact, "
                  "wall times include host dispatch",
        # the per-phase hbm_watermark is stamped centrally by run_phase
        # (device_memory_record) like every other phase
    }
    if want:
        # a misspelled subset name must fail LOUDLY, not thin the
        # record silently (ds_lint --mem enforces the same rule)
        unmatched = want - matched
        if unmatched:
            errors["unmatched_names"] = (
                f"BENCH_MEMSNAP_PROGRAMS name(s) {sorted(unmatched)} "
                f"matched no program — nothing was recorded for them")
    if errors:
        result["program_errors"] = errors
    if not programs:
        result["error"] = f"no program produced a memory record: {errors}"
    return result


def train_bench(model_name, *, micro_bs, zero_stage, steps, seq=2048,
                lean=False, remat=False, remat_policy="dots_and_attn_saveable",
                scan_layers=False, fused_qkv=False, loss_chunks=8,
                gas=1, offload=None, grad_accum_dtype=None, grad_groups=1):
    """``offload``: None (in-HBM optimizer) | "cpu" (ZeRO-Offload: bf16
    working params on device, fp32 masters+moments in host RAM, the C++
    SIMD Adam steps them) | "nvme" (moments/masters in swap files through
    ``csrc/aio``, pipelined reads).  ``gas`` amortizes the per-optimizer-
    step host round-trip over gradient-accumulation micro-steps —
    large-model single-chip training exactly as the reference stages it
    (stage_1_and_2.py:1037 offload path; blogs/deepspeed-chat README
    OPT-13B-on-one-A100 story)."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer
    from deepspeed_tpu.profiling.flops_profiler.profiler import device_peak_tflops

    cfg = opt_config(model_name, max_seq_len=seq, dtype="bfloat16",
                     remat=remat, remat_policy=remat_policy,
                     scan_layers=scan_layers, fused_qkv=fused_qkv,
                     loss_seq_chunks=loss_chunks)
    model = Transformer(cfg)
    opt_params = {"lr": 9.65e-6, "weight_decay": 0.0}
    if lean:
        opt_params["state_dtype"] = "bfloat16"
    config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": opt_params},
        "bf16": {"enabled": True, "master_weights_in_bf16": bool(lean)},
        "zero_optimization": {"stage": zero_stage},
        "gradient_clipping": 1.0,
        "compile_cache": _cc_block(),
    }
    if offload:
        config["zero_optimization"]["offload_optimizer"] = {
            "device": offload, "pipeline_read": offload == "nvme",
            **({"nvme_path": "/tmp/dstpu_bench_nvme"}
               if offload == "nvme" else {})}
    if grad_groups > 1:
        config["zero_optimization"]["grad_partition_groups"] = grad_groups
    if grad_accum_dtype:
        config["data_types"] = {"grad_accum_dtype": grad_accum_dtype}
    engine, *_ = deepspeed_tpu.initialize(model=model, config=config)

    rng = np.random.default_rng(0)
    n_dev = jax.device_count()
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size,
        (gas, micro_bs * engine.topology.dp, seq)).astype(np.int32)}

    loss = engine.train_batch(batch=batch)
    loss = engine.train_batch(batch=batch)
    _sync_scalar(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
    final_loss = float(_sync_scalar(loss))
    dt = (time.perf_counter() - t0) / steps

    tokens_per_step = micro_bs * engine.topology.dp * seq * gas
    n_params = cfg.num_params()
    peak = device_peak_tflops() * 1e12 * n_dev
    mfu = 6.0 * n_params * tokens_per_step / dt / peak if peak else 0.0
    result = {
        "model": model_name,
        "tokens_per_sec_chip": round(tokens_per_step / dt / n_dev, 1),
        "mfu": round(mfu, 4),
        "step_time_s": round(dt, 4),
        "loss": round(final_loss, 4),
        "seq": seq,
        "micro_bs": micro_bs,
        "zero_stage": zero_stage,
        "lean_optimizer_states": bool(lean),
        "remat": bool(remat),
        "platform": jax.devices()[0].platform,
    }
    if gas != 1:
        result["gradient_accumulation_steps"] = gas
    if offload:
        result["offload_optimizer"] = offload
    if grad_accum_dtype:
        result["grad_accum_dtype"] = grad_accum_dtype
    meas_tflops, _ = _measured_peaks()
    if meas_tflops:
        result["mfu_vs_measured_mxu"] = round(
            6.0 * n_params * tokens_per_step / dt
            / (meas_tflops * 1e12 * n_dev), 4)
    return result


def decode_bench(model_name="opt-1.3b", *, batch_size=16, prompt=256,
                 gen=256, int8=False, kv_int8=False, mxu_int8=False):
    """DS-Chat generation-phase workload (prompt 256 + gen 256) through the
    jitted prefill+decode program (reference Hybrid Engine `generate`,
    ``blogs/deepspeed-chat/README.md:265``).  ``int8=True`` runs the
    per-channel INT8-at-rest weight path (reference
    ``runtime/weight_quantizer.py``); layers are unrolled
    (``scan_layers=False``) — scanning the trunk dynamic-slices a relayout
    copy of each layer's qkv weights per token.

    ``hbm_utilization`` is estimated traffic / peak bandwidth: weight bytes
    once per decode step plus the KV blocks the Pallas decode kernel
    actually DMAs (live blocks only, at its block_k granularity)."""
    import jax
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.ops.transformer.decode_attention import \
        DEFAULT_BLOCK_K_DECODE
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        device_peak_hbm_gbps

    cfg = opt_config(model_name, max_seq_len=prompt + gen, dtype="bfloat16",
                     scan_layers=False, kv_cache_quant=kv_int8,
                     decode_int8_matmuls=mxu_int8)
    model = Transformer(cfg)
    quant = {"enabled": True, "bits": 8, "per_channel": True} if int8 else {}
    # Long prompts must run the REAL chunked-prefill pipeline.  The r04
    # 4k phase's "fallback": true was the "auto" chunk policy silently
    # declining chunking (the Pallas chunk kernel is gated off on some
    # backends), which dropped the 3968-token prompt onto the one-pass
    # path — its dense-attention fallback materializes [B, H, S, S] fp32
    # scores (~32 GB at bs16 x 4k) and OOMs, and only the bs8 retry fit.
    # Pinning the chunk size forces the split per-chunk pipeline (dense
    # per-chunk transient is only [B, H, C, S]); prefill_plan records
    # which pipeline ran and why, either way.
    chunk_cfg = 512 if prompt >= 1024 else "auto"
    eng = InferenceEngine(model, DeepSpeedInferenceConfig(
        dtype="bfloat16", quant=quant, compile_cache=_cc_block(),
        prefill_chunk_size=chunk_cfg))
    eng.init_params()
    plan_mode, plan_chunk, plan_why = eng.prefill_plan(batch_size, prompt)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch_size, prompt)).astype(np.int32)

    def timed(n_new):
        out = eng.generate(ids, max_new_tokens=n_new)   # compile + warm
        _sync_scalar(out[:, -1])
        t0 = time.perf_counter()
        out = eng.generate(ids, max_new_tokens=n_new)
        _sync_scalar(out[:, -1])
        return time.perf_counter() - t0

    # two run lengths isolate the pure-decode rate from the shared prefill
    dt_full, dt_half = timed(gen), timed(gen // 2)
    if dt_full <= dt_half:
        # timing inversion (a host scheduling hiccup) —
        # re-measure once before declaring the run invalid
        dt_full, dt_half = timed(gen), timed(gen // 2)
    error = None
    if dt_full > dt_half:
        decode_rate = round(batch_size * (gen - gen // 2)
                            / (dt_full - dt_half) / jax.device_count(), 1)
        # estimated HBM traffic per decode step: all params once + the live
        # KV blocks (the kernel skips blocks past the cache's live region)
        param_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                          for l in jax.tree.leaves(eng.params))
        bk = min(DEFAULT_BLOCK_K_DECODE, prompt + gen)
        steps = np.arange(gen // 2, gen)        # the measured decode steps
        live_blocks = np.ceil((prompt + steps + 1) / bk)
        # bytes per cached position: bf16 payload, or int8 + f32 scale/head
        kv_row = cfg.kv_heads * cfg.head_dim * (1 if kv_int8 else 2) \
            + (cfg.kv_heads * 4 if kv_int8 else 0)
        cache_bytes = 2 * cfg.num_layers * batch_size * kv_row * bk \
            * float(np.mean(live_blocks))
        step_t = (dt_full - dt_half) / (gen - gen // 2)
        # per-chip traffic: params are replicated at tp=1, so EVERY chip
        # streams the full param_bytes per step; only the batch's KV cache
        # spreads across chips (dp-sharded)
        traffic = param_bytes + cache_bytes / jax.device_count()
        hbm_util = traffic / step_t / (device_peak_hbm_gbps() * 1e9)
        _, meas_gbps = _measured_peaks()
        hbm_util_meas = traffic / step_t / (meas_gbps * 1e9) \
            if meas_gbps else None
        # roofline attribution (docs/observability.md "Device memory &
        # roofline"): per-chip decode-step flops ~ 2 x params x the
        # chip's batch shard (matmul-dominated), bytes = the same
        # traffic estimate hbm_utilization uses — the classification
        # says WHY a cliff happened (a decode step left of the ridge is
        # bandwidth-ceilinged: HBM traffic regressions cut throughput
        # linearly no matter how idle the MXU is)
        from deepspeed_tpu.profiling.roofline import (device_peaks,
                                                      roofline_block)
        param_count = sum(int(np.prod(l.shape))
                          for l in jax.tree.leaves(eng.params))
        flops_step = 2.0 * param_count * batch_size / jax.device_count()
        peak_t, peak_g, peak_src = device_peaks(*_measured_peaks())
        roofline = roofline_block(flops_step, traffic, step_t,
                                  peak_t, peak_g, peak_src)
    else:
        decode_rate, hbm_util, hbm_util_meas, roofline = (None,) * 4
        error = (f"timing inversion persisted across re-measure "
                 f"(gen={gen}: {dt_full:.3f}s <= gen={gen // 2}: "
                 f"{dt_half:.3f}s) — decode rate not measurable")
    result = {
        "model": model_name,
        "weights": "int8-per-channel" if int8 else "bf16",
        "kv_cache": "int8" if kv_int8 else "bf16",
        "decode_tokens_per_sec_chip": decode_rate,
        "e2e_tokens_per_sec_chip": round(batch_size * gen / dt_full
                                         / jax.device_count(), 1),
        "hbm_utilization": round(hbm_util, 3) if hbm_util else None,
        "batch_size": batch_size,
        "prompt_len": prompt,
        "gen_len": gen,
        "e2e_time_s": round(dt_full, 3),
        # which prefill pipeline generate() took and why — the condition
        # behind the old 4k "fallback": true is visible in every record
        "prefill_plan": {"mode": plan_mode, "chunk": plan_chunk,
                         "reason": plan_why},
    }
    if hbm_util_meas:
        result["hbm_utilization_vs_measured"] = round(hbm_util_meas, 3)
    if roofline:
        result["roofline"] = roofline
    if error:
        result["error"] = error
    return result


def serving_bench(model_name="opt-1.3b", *, num_slots=8, n_requests=24,
                  decode_block=8, prefill_chunk=128,
                  prefill_token_budget=256):
    """Continuous-batching serving (``inference/serving/``,
    ``docs/serving.md``) on a MIXED-LENGTH workload — varied prompt and
    completion lengths, more requests than slots — against the sequential
    bucketed ``generate()`` baseline a naive server runs: requests grouped
    into arrival-order batches of ``num_slots``, prompts right-padded to
    the batch max, every row decoding to the batch's max completion
    length.  Continuous batching recovers exactly that padding +
    lockstep waste: slots retire on completion and the queue backfills
    them mid-decode through ONE reusable decode-step program.

    ``speedup_vs_sequential`` is aggregate useful tokens/s over the same
    requests — the headline serving metric.

    Since PR 29 this phase runs the slot engine's ONE KV layout, the page
    pool (it used to run the lane layout, now removed): its numbers are
    not comparable with ``BENCH_r*.json`` records from before."""
    import jax
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    cache_len = 384                         # prompts <= 256, new <= 128
    cfg = opt_config(model_name, max_seq_len=cache_len, dtype="bfloat16",
                     scan_layers=False)
    model = Transformer(cfg)
    eng = InferenceEngine(model, DeepSpeedInferenceConfig(
        dtype="bfloat16", compile_cache=_cc_block(),
        serving={"enabled": True, "num_slots": num_slots,
                 "max_cache_len": cache_len,
                 "prefill_chunk": prefill_chunk,
                 "prefill_token_budget": prefill_token_budget,
                 "decode_block": decode_block}))
    eng.init_params()
    rng = np.random.default_rng(0)
    prompt_lens = rng.choice([64, 96, 128, 192, 256], n_requests)
    new_lens = rng.choice([16, 32, 64, 128], n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, (int(p),)).astype(np.int32)
               for p in prompt_lens]
    useful_tokens = int(np.sum(new_lens))

    def run_sequential():
        t0 = time.perf_counter()
        for i in range(0, n_requests, num_slots):
            bp = prompts[i:i + num_slots]
            bn = new_lens[i:i + num_slots]
            P = max(len(p) for p in bp)
            ids = np.zeros((len(bp), P), np.int32)
            mask = np.zeros((len(bp), P), np.int32)
            for j, p in enumerate(bp):
                ids[j, :len(p)] = p
                mask[j, :len(p)] = 1
            out = eng.generate(ids, max_new_tokens=int(max(bn)),
                               attention_mask=mask)
            _sync_scalar(out[:, -1])
        return time.perf_counter() - t0

    srv = eng.serve()
    srv.warmup()

    def run_serving():
        t0 = time.perf_counter()
        for p, n in zip(prompts, new_lens):
            srv.submit(p, max_new_tokens=int(n))
        srv.drain()
        return time.perf_counter() - t0

    run_sequential()                        # compile + warm both paths
    run_serving()
    t_seq = run_sequential()
    occ0 = len(srv.occupancy_trace)
    t_srv = run_serving()
    occ = [o for _, o in srv.occupancy_trace[occ0:]]
    return {
        "model": model_name,
        "num_slots": num_slots,
        "n_requests": n_requests,
        "decode_block": decode_block,
        "prefill_chunk": prefill_chunk,
        "prefill_token_budget": prefill_token_budget,
        "prompt_lens": sorted(int(p) for p in prompt_lens),
        "new_lens": sorted(int(n) for n in new_lens),
        "serving_tokens_per_sec": round(useful_tokens / t_srv, 1),
        "sequential_tokens_per_sec": round(useful_tokens / t_seq, 1),
        "speedup_vs_sequential": round(t_seq / t_srv, 3),
        "serving_time_s": round(t_srv, 3),
        "sequential_time_s": round(t_seq, 3),
        "mean_slot_occupancy": round(float(np.mean(occ)) / num_slots, 3)
        if occ else None,
        "decode_calls": srv.stats["decode_calls"],
        "decode_tokens": srv.stats["decode_tokens"],
        "prefill_tokens": srv.stats["prefill_tokens"],
        "platform": jax.devices()[0].platform,
    }


def serving_overload_bench(model_name="opt-1.3b", *, num_slots=8,
                           burst_factor=4, decode_block=8,
                           prefill_chunk=128):
    """Serving SLO micro-phase (``docs/serving.md`` "Robustness & SLOs"):
    a burst of ``burst_factor``x slot capacity submits with mixed
    deadlines — a quarter of the burst arrives already expired and must
    SHED before occupying a slot — then a graceful preemption mid-burst
    (drain in-flight slots, crash-atomic snapshot) and a second server
    resuming the snapshot to finish the backlog.  Records the shed rate,
    p50/p99 time-to-first-token of the completed requests, the
    preemption drain+snapshot latency, and the per-server decode-
    executable count (the one-decode-executable invariant under
    overload + drain + resume)."""
    import shutil
    import tempfile
    import jax
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    cache_len = 384                         # prompts <= 256, new <= 128
    n_requests = num_slots * burst_factor
    cfg = opt_config(model_name, max_seq_len=cache_len, dtype="bfloat16",
                     scan_layers=False)
    model = Transformer(cfg)
    eng = InferenceEngine(model, DeepSpeedInferenceConfig(
        dtype="bfloat16", compile_cache=_cc_block(),
        serving={"enabled": True, "num_slots": num_slots,
                 "max_cache_len": cache_len,
                 "prefill_chunk": prefill_chunk,
                 "prefill_token_budget": 256,
                 "decode_block": decode_block,
                 "drain_budget_s": 60.0}))
    eng.init_params()
    rng = np.random.default_rng(0)
    prompt_lens = rng.choice([64, 96, 128, 192, 256], n_requests)
    new_lens = rng.choice([16, 32, 64, 128], n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, (int(p),)).astype(np.int32)
               for p in prompt_lens]
    # mixed deadlines: every 4th request arrives already expired — the
    # deterministic shed-rate floor; the rest are deadline-free
    deadlines = [0.0 if i % 4 == 3 else None for i in range(n_requests)]

    srv = eng.serve()
    srv.warmup()
    t0 = time.perf_counter()
    rids = [srv.submit(p, max_new_tokens=int(n), deadline_s=dl)
            for p, n, dl in zip(prompts, new_lens, deadlines)]
    live = [r for r, dl in zip(rids, deadlines) if dl is None]
    done = {}
    # run the burst until half the live requests completed, then preempt
    # mid-flight (in-flight slots drain under the budget, the queued
    # backlog snapshots)
    it = 0
    while sum(1 for r in live if r in done) < len(live) // 2:
        done.update(srv.step())
        it += 1
        if it > 100000:                     # parent timeout is the real
            break                           # guard; this bounds the loop
    snap_dir = tempfile.mkdtemp(prefix="bench_serving_snap_")
    try:
        t_pre = time.perf_counter()
        tag, snapped, fin = srv.preempt(snap_dir)
        drain_latency = time.perf_counter() - t_pre
        done.update(fin)
        srv2 = eng.serve()
        restored = srv2.restore(snap_dir)
        done.update(srv2.drain())
        t_total = time.perf_counter() - t0
    finally:
        shutil.rmtree(snap_dir, ignore_errors=True)
    results = {**srv._results, **srv2._results}
    ttfts = sorted(r.ttft_s for r in results.values()
                   if r.status == "COMPLETED" and r.ttft_s is not None)
    shed = srv.stats["shed"] + srv2.stats["shed"]
    completed = srv.stats["completed"] + srv2.stats["completed"]
    useful = sum(int(n) for r, n in zip(rids, new_lens)
                 if results[r].status == "COMPLETED")
    return {
        "model": model_name,
        "num_slots": num_slots,
        "burst_requests": n_requests,
        "burst_factor": burst_factor,
        "shed": shed,
        "shed_rate": round(shed / n_requests, 3),
        "completed": completed,
        "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 3)
        if ttfts else None,
        "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 3)
        if ttfts else None,
        "drain_snapshot_latency_s": round(drain_latency, 3),
        "snapshotted_requests": len(snapped),
        "resumed_requests": len(restored),
        "useful_tokens_per_sec": round(useful / t_total, 1),
        "total_time_s": round(t_total, 3),
        # the one-decode-executable invariant under overload+drain+resume
        "decode_executables_per_server": [
            sum(1 for sig in eng._aot if sig and sig[0] == id(s._decode_fn))
            for s in (srv, srv2)],
        "platform": jax.devices()[0].platform,
    }


def serving_http_bench(model_name="opt-1.3b", *, num_slots=8,
                       n_requests=24, decode_block=8, prefill_chunk=128):
    """Network front end micro-phase (``docs/serving.md`` "Network front
    end"): the SAME mixed workload served twice — direct ``submit()`` /
    ``drain()`` vs concurrent HTTP clients (2 tenants x 2 priorities,
    half streaming, half blocking) — recording the transport overhead:
    req/s and p50/p99 TTFT for both paths, p50/p99 time-between-tokens
    on the streamed responses, and the decode-executable count proving
    the HTTP path minted nothing new."""
    import http.client
    import json
    import threading
    import jax
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.serving.frontend import \
        ServingHTTPFrontend

    cache_len = 384                         # prompts <= 256, new <= 64
    cfg = opt_config(model_name, max_seq_len=cache_len, dtype="bfloat16",
                     scan_layers=False)
    model = Transformer(cfg)
    eng = InferenceEngine(model, DeepSpeedInferenceConfig(
        dtype="bfloat16", compile_cache=_cc_block(),
        serving={"enabled": True, "num_slots": num_slots,
                 "max_cache_len": cache_len,
                 "prefill_chunk": prefill_chunk,
                 "prefill_token_budget": 256,
                 "decode_block": decode_block,
                 "priority_lanes": 2}))
    eng.init_params()
    rng = np.random.default_rng(0)
    prompt_lens = rng.choice([64, 96, 128, 192, 256], n_requests)
    new_lens = rng.choice([16, 32, 64], n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, (int(p),)).astype(np.int32)
               for p in prompt_lens]

    def pct(xs, q):
        return round(float(np.percentile(xs, q)), 4) if len(xs) else None

    # ---- direct path: submit() + drain() on the scheduler thread ----
    srv = eng.serve()
    srv.warmup()
    t0 = time.perf_counter()
    rids = [srv.submit(p, max_new_tokens=int(n),
                       client_id=f"tenant-{i % 2}", priority=(i // 2) % 2)
            for i, (p, n) in enumerate(zip(prompts, new_lens))]
    srv.drain()
    t_direct = time.perf_counter() - t0
    direct_ttfts = sorted(srv._results[r].ttft_s for r in rids
                          if srv._results[r].ttft_s is not None)
    # record the decode-executable count, then retire the direct-path
    # server BEFORE the HTTP server exists — two live servers would
    # double the phase's KV-workspace footprint for nothing
    decode_execs = [
        sum(1 for sig in eng._aot if sig and sig[0] == id(srv._decode_fn))]
    srv.close()

    # ---- HTTP path: same workload through concurrent clients ----
    # wire TTFT (streaming clients: submit -> first token ON THE WIRE,
    # includes transport + queueing) and engine TTFT (blocking clients:
    # the engine's internal admission->first-token clock) are DIFFERENT
    # quantities — recorded separately, never mixed in one percentile
    srv2 = eng.serve()
    wire_ttfts, engine_ttfts, tbt_gaps, errors = [], [], [], []

    def client(k, port):
        try:
            stream = bool(k % 2)
            t_sub = time.perf_counter()
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=600)
            conn.request("POST", "/v1/generate", json.dumps(
                {"input_ids": [int(t) for t in prompts[k]],
                 "max_new_tokens": int(new_lens[k]),
                 "client_id": f"tenant-{k % 2}",
                 "priority": (k // 2) % 2, "stream": stream}))
            resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {resp.read()!r}")
            if stream:
                arrivals = []
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    ev = json.loads(line)
                    if ev["event"] == "token":
                        arrivals.append(time.perf_counter())
                    else:
                        break
                if arrivals:
                    wire_ttfts.append(arrivals[0] - t_sub)
                    tbt_gaps.extend(np.diff(arrivals).tolist())
            else:
                body = json.loads(resp.read())
                if body.get("ttft_s") is not None:
                    engine_ttfts.append(body["ttft_s"])
            conn.close()
        except Exception as e:              # recorded, fails the phase
            errors.append(f"client {k}: {type(e).__name__}: {e}")

    t1 = time.perf_counter()
    with ServingHTTPFrontend(srv2) as fe:
        threads = [threading.Thread(target=client, args=(k, fe.port))
                   for k in range(n_requests)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    t_http = time.perf_counter() - t1
    decode_execs.append(
        sum(1 for sig in eng._aot if sig and sig[0] == id(srv2._decode_fn)))
    # engine-lock contention under concurrent HTTP handlers: per-acquire
    # wait percentiles from the InstrumentedRLock sample window — the
    # baseline future threading changes regress against (the PR 7
    # threshold machinery classifies *_s as lower-is-better)
    lock_waits = {cls: sorted(srv2._lock.samples[cls])
                  for cls in ("scheduler", "handler")}
    lock_wait_total = dict(srv2._lock.wait_s)
    srv2.close()
    if errors:
        raise RuntimeError("serving_http bench clients failed: "
                           + "; ".join(errors[:5]))
    wire_ttfts.sort()
    engine_ttfts.sort()
    return {
        "model": model_name,
        "num_slots": num_slots,
        "n_requests": n_requests,
        "tenants": 2,
        "priorities": 2,
        "direct_reqs_per_sec": round(n_requests / t_direct, 2),
        "direct_ttft_p50_s": pct(direct_ttfts, 50),
        "direct_ttft_p99_s": pct(direct_ttfts, 99),
        "http_reqs_per_sec": round(n_requests / t_http, 2),
        # engine TTFT is directly comparable to direct_ttft_* (same
        # clock); wire TTFT additionally includes the transport
        "http_engine_ttft_p50_s": pct(engine_ttfts, 50),
        "http_engine_ttft_p99_s": pct(engine_ttfts, 99),
        "http_wire_ttft_p50_s": pct(wire_ttfts, 50),
        "http_wire_ttft_p99_s": pct(wire_ttfts, 99),
        "http_time_between_tokens_p50_s": pct(tbt_gaps, 50),
        "http_time_between_tokens_p99_s": pct(tbt_gaps, 99),
        "lock_wait_scheduler_p50_s": pct(lock_waits["scheduler"], 50),
        "lock_wait_scheduler_p99_s": pct(lock_waits["scheduler"], 99),
        "lock_wait_handler_p50_s": pct(lock_waits["handler"], 50),
        "lock_wait_handler_p99_s": pct(lock_waits["handler"], 99),
        "lock_wait_scheduler_total_s": round(
            lock_wait_total["scheduler"], 4),
        "lock_wait_handler_total_s": round(
            lock_wait_total["handler"], 4),
        # < 1.0 = the transport costs throughput; the decode_block
        # flush cadence bounds per-token latency, not aggregate rate
        "http_vs_direct_reqs_ratio": round(
            (n_requests / t_http) / (n_requests / t_direct), 3),
        # the one-decode-executable invariant through the HTTP path
        "decode_executables_per_server": decode_execs,
        "platform": jax.devices()[0].platform,
    }


def serving_paged_bench(model_name="opt-1.3b", *, slots_list=(96, 128, 192),
                        page_size=64, pool_fraction=0.75, decode_block=8,
                        prefill_chunk=128, prefix_requests=24,
                        prefix_len=512):
    """Paged-KV serving (``inference/serving/paging.py``, ``docs/serving.md``
    "KV cache") at the throughput serving points where the
    monolithic per-slot lanes collapsed (r04: int8-KV decode fell 8,673 →
    1,193 tok/s/chip between bs96 and bs128 as ``num_slots × cache_len``
    HBM crossed the chip).  Per concurrency level: ``num_slots`` paged
    int8-KV slots over a pool sized at ``pool_fraction`` of worst case
    (pages back ACTUAL request lengths; pressure degrades into admission
    stalls, never an allocation cliff), recording useful tok/s/chip,
    page-pool utilization, and admission stalls.  Plus a shared-prefix
    workload: ``prefix_requests`` prompts behind one ``prefix_len``-token
    system prompt — the prefix prefills ONCE (copy-on-write page sharing),
    every later admission hits the prefix index."""
    import jax
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    cache_len = 384                         # prompts <= 256, new <= 128
    cfg = opt_config(model_name, max_seq_len=max(cache_len, prefix_len + 256),
                     dtype="bfloat16", scan_layers=False, kv_cache_quant=True)
    model = Transformer(cfg)
    quant = {"enabled": True, "bits": 8, "per_channel": True}
    eng = InferenceEngine(model, DeepSpeedInferenceConfig(
        dtype="bfloat16", quant=quant, compile_cache=_cc_block(),
        serving={"enabled": True, "page_size": page_size,
                 "max_cache_len": cache_len, "prefill_chunk": prefill_chunk,
                 "prefill_token_budget": 256, "decode_block": decode_block}))
    eng.init_params()
    rng = np.random.default_rng(0)
    n_dev = jax.device_count()
    # roofline numerators (constant across concurrency levels): int8
    # weights stream once per decode step; KV bytes come from the live
    # page-pool occupancy sampled at the decode window (the paged kernel
    # pins dead-tail page indices to the last live page, so repeated-index
    # DMAs are elided and only live pages cost traffic)
    param_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                      for l in jax.tree.leaves(eng.params))
    param_count = sum(int(np.prod(l.shape))
                      for l in jax.tree.leaves(eng.params))
    # bytes per cached position, k + v: int8 payload + f32 per-head scale
    kv_row = 2 * (cfg.kv_heads * cfg.head_dim + cfg.kv_heads * 4)
    plan_mode, plan_chunk, plan_why = eng.prefill_plan(
        max(slots_list), 256, paged=True)
    per_bs = {}
    for bs in slots_list:
        n_requests = 2 * bs                 # slots churn at least once
        prompt_lens = rng.choice([64, 96, 128, 192, 256], n_requests)
        new_lens = rng.choice([16, 32, 64, 128], n_requests)
        prompts = [rng.integers(0, cfg.vocab_size, (int(p),))
                   .astype(np.int32) for p in prompt_lens]
        worst = bs * (-(-cache_len // page_size))
        num_pages = max(2, int(pool_fraction * worst)) + 1
        srv = eng.serve(num_slots=bs, num_pages=num_pages)
        srv.warmup()
        srv_modes = srv.kernel_modes
        util_peak = 0.0

        def run(srv):
            nonlocal util_peak
            t0 = time.perf_counter()
            for p, n in zip(prompts, new_lens):
                srv.submit(p, max_new_tokens=int(n))
            while srv.queue_depth or srv.in_flight or srv.active_slots:
                srv.step()
                util_peak = max(util_peak, srv.page_pool_utilization)
            return time.perf_counter() - t0

        run(srv)                            # compile + warm
        stalls0 = srv.stats["admission_stalls"]
        fb0 = srv.stats["paged_attention_fallback"]
        util_peak = 0.0
        dt = run(srv)
        useful = int(np.sum(new_lens))
        # decode-only roofline window (docs/observability.md "Device
        # memory & roofline"): park one short request per slot in steady
        # decode, then time pure decode dispatches — no admissions or
        # prefill chunks interleaved — so the step time attributes the
        # paged decode kernel itself, not the mixed scheduler loop
        for _ in range(bs):
            srv.submit(rng.integers(0, cfg.vocab_size, (64,))
                       .astype(np.int32), max_new_tokens=160)
        pf = -1
        while srv.queue_depth or srv.stats["prefill_tokens"] != pf:
            pf = srv.stats["prefill_tokens"]
            srv.step()
        live_pos = srv.page_pool_utilization * (num_pages - 1) * page_size
        n0, t0 = srv.stats["decode_calls"], time.perf_counter()
        while srv.stats["decode_calls"] - n0 < 8:
            srv.step()
        dt_win = time.perf_counter() - t0
        steps_win = (srv.stats["decode_calls"] - n0) * decode_block
        step_t = dt_win / max(steps_win, 1)
        from deepspeed_tpu.profiling.roofline import (device_peaks,
                                                      roofline_block)
        # per-chip traffic per decode step: replicated int8 params once,
        # live KV pages dp-sharded across chips
        traffic = param_bytes + cfg.num_layers * live_pos * kv_row / n_dev
        flops_step = 2.0 * param_count * bs / n_dev
        peak_t, peak_g, peak_src = device_peaks(*_measured_peaks())
        per_bs[str(bs)] = {
            "num_slots": bs,
            "n_requests": n_requests,
            "num_pages": num_pages,
            "pool_fraction_of_worst_case": pool_fraction,
            "tokens_per_sec_chip": round(useful / dt / n_dev, 1),
            "page_pool_util_peak": round(util_peak, 3),
            "admission_stalls": srv.stats["admission_stalls"] - stalls0,
            "paged_attention_fallback":
                srv.stats["paged_attention_fallback"] - fb0,
            "decode_step_ms": round(step_t * 1e3, 3),
            "roofline": roofline_block(flops_step, traffic, step_t,
                                       peak_t, peak_g, peak_src),
            "time_s": round(dt, 3),
        }
        srv.drain()
        srv.close()

    # shared-prefix workload: one system prompt, divergent user tails —
    # the prefix prefills exactly once; hit rate counts the rest
    pre = rng.integers(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
             for _ in range(prefix_requests)]
    # lanes must hold the CHUNK-PADDED prompt (submit's capacity check):
    # ceil(528 / 128) * 128 = 640 positions
    pc_len = prefix_len + 2 * prefill_chunk
    srv = eng.serve(num_slots=8, max_cache_len=pc_len)
    t0 = time.perf_counter()
    for t in tails:
        srv.submit(np.concatenate([pre, t]), max_new_tokens=32)
    srv.drain()
    dt_prefix = time.perf_counter() - t0
    prefix = {
        "requests": prefix_requests,
        "prefix_len": prefix_len,
        "prefix_hits": srv.stats["prefix_hits"],
        "prefix_hit_rate": round(srv.prefix_hit_rate, 3),
        "prefix_tokens_reused": srv.stats["prefix_tokens_reused"],
        "prefill_tokens": srv.stats["prefill_tokens"],
        # what the same workload costs with no sharing: every request
        # prefills its full chunk-padded prompt
        "prefill_tokens_without_sharing":
            prefix_requests * (-(-(prefix_len + 16) // prefill_chunk))
            * prefill_chunk,
        "time_s": round(dt_prefix, 3),
    }
    srv.close()
    r128 = per_bs.get("128", {})
    return {
        "model": model_name,
        "weights": "int8-per-channel",
        "kv_cache": "int8",
        "page_size": page_size,
        "decode_block": decode_block,
        # which attention-registry kernels the serving programs dispatch
        # through (ops/transformer/registry.py) — pallas_paged_decode /
        # pallas_chunked_prefill on kernel-capable backends,
        # reference_fallback otherwise (then per_bs
        # paged_attention_fallback counts every slow-path decode)
        "kernel_modes": dict(srv_modes),
        "prefill_plan": {"mode": plan_mode, "chunk": plan_chunk,
                         "reason": plan_why},
        "per_bs": per_bs,
        "prefix_sharing": prefix,
        # the acceptance anchor: r04's bs128 monolithic int8-KV decode
        # collapsed to 1,193 tok/s/chip (HBM util 0.58 -> 0.075)
        "vs_r04_bs128_decode": round(
            r128["tokens_per_sec_chip"] / 1193.0, 2)
        if r128.get("tokens_per_sec_chip") else None,
        "platform": jax.devices()[0].platform,
    }


def serving_spec_bench(model_name="opt-1.3b", *, slots_list=(4, 8, 16),
                       k_list=(2, 4, 8), decode_block=8,
                       prefill_chunk=128):
    """Speculative multi-token serving (``docs/serving.md`` "Speculative
    decoding") at the latency-sensitive bs<=16 points where BENCH_r02/r04
    show decode stuck near ~1.2k tok/s/chip: per (num_slots, spec_k)
    point, a SELF-draft speculative server (the target model drafts for
    itself — accept rate ~1.0 under greedy, so the measurement isolates
    the dispatch-amortization/batched-verify ceiling; a trained small
    draft trades accept rate against draft cost) against the
    non-speculative serving baseline at the same concurrency.  Records
    the accept rate, committed tokens per dispatch, decode tok/s/chip
    and speedup vs non-spec, time-between-tokens p50/p99 from the
    per-token event streams, and the executables-per-server proof
    (exactly one draft-propose + one verify-and-commit signature)."""
    import jax
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    cache_len = 384                         # prompts <= 256, new <= 128
    cfg = opt_config(model_name, max_seq_len=cache_len, dtype="bfloat16",
                     scan_layers=False)
    model = Transformer(cfg)
    eng = InferenceEngine(model, DeepSpeedInferenceConfig(
        dtype="bfloat16", compile_cache=_cc_block(),
        serving={"enabled": True, "max_cache_len": cache_len,
                 "prefill_chunk": prefill_chunk,
                 "prefill_token_budget": 256,
                 "decode_block": decode_block}))
    eng.init_params()
    rng = np.random.default_rng(0)
    n_dev = jax.device_count()
    max_k = max(k_list)

    def workload(bs):
        n_requests = max(2 * bs, 12)        # slots churn at least once
        prompt_lens = rng.choice([64, 96, 128, 192], n_requests)
        new_lens = rng.choice([64, 96, 128], n_requests)
        prompts = [rng.integers(0, cfg.vocab_size, (int(p),))
                   .astype(np.int32)
                   # leave room for the spec window reserve at every k
                   if p + 128 + max_k - 1 <= cache_len else
                   rng.integers(0, cfg.vocab_size, (64,)).astype(np.int32)
                   for p in prompt_lens]
        return prompts, [int(n) for n in new_lens]

    def run(srv, prompts, new_lens):
        """Drain the workload; returns (dt, tbt_ms list) — time between
        consecutive token events per request, wall clock at the
        host-mirror drain point (the stream's tick)."""
        stamps = {}

        def on_event_for(rid):
            def on_event(ev, _rid=rid):
                if ev.get("event") == "token":
                    stamps.setdefault(_rid, []).append(time.perf_counter())
            return on_event

        t0 = time.perf_counter()
        rids = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, new_lens)]
        for rid in rids:
            srv.token_events(rid, on_event=on_event_for(rid))
        srv.drain()
        dt = time.perf_counter() - t0
        tbt = []
        for ts in stamps.values():
            tbt.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
        return dt, tbt

    def pct(xs, q):
        return round(float(np.percentile(xs, q)), 2) if xs else None

    points, baselines = [], []
    for bs in slots_list:
        prompts, new_lens = workload(bs)
        useful = int(np.sum(new_lens))
        base = eng.serve(num_slots=bs)
        base.warmup()
        run(base, prompts, new_lens)        # compile + warm
        dt_base, tbt_base = run(base, prompts, new_lens)
        base.close()
        base_tps = useful / dt_base / n_dev
        baselines.append({
            "num_slots": bs, "n_requests": len(prompts),
            "tokens_per_sec_chip": round(base_tps, 1),
            "time_between_tokens_p50_ms": pct(tbt_base, 50),
            "time_between_tokens_p99_ms": pct(tbt_base, 99),
            "time_s": round(dt_base, 3),
        })
        for k in k_list:
            srv = eng.serve(num_slots=bs, speculative=True, spec_k=k,
                            spec_draft_model="self")
            srv.warmup()
            run(srv, prompts, new_lens)     # compile + warm
            dt, tbt = run(srv, prompts, new_lens)
            tps = useful / dt / n_dev
            points.append({
                "num_slots": bs, "spec_k": k,
                "accept_rate": round(srv.stats["spec_accept_rate"], 3),
                "tokens_per_dispatch":
                    round(srv.stats["spec_tokens_per_dispatch"], 2),
                "draft_time_fraction":
                    round(srv.stats["spec_draft_fraction"], 3),
                "tokens_per_sec_chip": round(tps, 1),
                "speedup_vs_nonspec": round(tps / base_tps, 3),
                "time_between_tokens_p50_ms": pct(tbt, 50),
                "time_between_tokens_p99_ms": pct(tbt, 99),
                "time_s": round(dt, 3),
                # the one-executable-per-program proof, per server
                "propose_executables": sum(
                    1 for sig in eng._aot
                    if sig and sig[0] == id(srv._propose_fn)),
                "verify_executables": sum(
                    1 for sig in eng._aot
                    if sig and sig[0] == id(srv._verify_fn)),
            })
            srv.close()
    best = max(points, key=lambda p: p.get("speedup_vs_nonspec") or 0.0) \
        if points else None
    return {
        "model": model_name,
        "draft": "self (accept-rate ceiling; trained small drafts trade "
                 "accept rate against draft cost)",
        "decode_block_baseline": decode_block,
        "points": points,
        "baselines": baselines,
        "best_speedup_vs_nonspec":
            best["speedup_vs_nonspec"] if best else None,
        "best_point": {"num_slots": best["num_slots"],
                       "spec_k": best["spec_k"]} if best else None,
        "platform": jax.devices()[0].platform,
    }


def long_context_bench(model_name="opt-1.3b", *, seq=8192, micro_bs=1,
                       steps=4):
    """Long-context SFT through the Pallas flash-attention path (the
    reference's long-sequence story rides its sparse/flash attention kernels,
    ``csrc/sparse_attention`` + ``ops/sparse_attention/``, SURVEY §5) — at
    the flagship OPT-1.3B scale.  ``flash_only_saveable`` remat keeps only
    the O(S) attention residuals (r3 sweep: 29.7% MFU vs 25.9% full
    recompute; dots-saveable OOMs at this length).  Reports tokens/s and an
    attention-aware MFU: at seq 8k the causal attention FLOPs (~6·L·S·H per
    token) rival the 6·N·tokens parameter FLOPs that the standard MFU
    formula counts."""
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        device_peak_tflops
    r = train_bench(model_name, micro_bs=micro_bs, zero_stage=3, steps=steps,
                    seq=seq, lean=True, remat=True,
                    remat_policy="flash_only_saveable", loss_chunks=32)
    cfg = opt_config(model_name, max_seq_len=seq)
    attn_flops_per_tok = 6.0 * cfg.num_layers * seq * cfg.hidden_size
    total_per_tok = 6.0 * cfg.num_params() + attn_flops_per_tok
    peak = device_peak_tflops() * 1e12
    r["mfu_attn_aware"] = round(
        r["tokens_per_sec_chip"] * total_per_tok / peak, 4)
    return r


def hybrid_bench(model_name="opt-1.3b", *, train_bs=2, rollout_bs=(8, 32, 64),
                 prompt=256, gen=128, seq=2048, cycles=2, train_steps=4,
                 remat=True, quantize_rollouts=True):
    """DS-Chat step-3 RLHF loop at OPT-1.3B scale through the Hybrid Engine
    (reference ``runtime/hybrid_engine.py:32``; headline rows in
    ``blogs/deepspeed-chat/README.md:38,52``): N ZeRO-3 train steps → rollout
    ``generate`` through the shared-weight inference view → training resumes
    on the same engine.  Reports rollout throughput, train step time before
    and after a rollout (the engine-flip cost the reference's blog headlines)
    and TWO weight checks:

    - full-pytree identity between the masters and the inference view
      (every leaf; the view must BE the cast masters — the Hybrid Engine's
      whole premise, reference ``runtime/hybrid_engine.py:84-130``);
    - the int8 quantized-rollout path's round-trip error on the LARGEST
      matmul weight (the per-channel quantizer used by
      ``hybrid_engine.quantize_rollouts``).
    """
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer

    # remat ON by default here: the int8 rollout view + its KV cache are
    # resident during training's activation peak at the larger rollout
    # batches (the no-remat + int8-view combination OOMs at 1.3B —
    # r3 probe); the fallback drops to the bf16 view at bs8
    cfg = opt_config(model_name, max_seq_len=seq, dtype="bfloat16",
                     remat=remat, scan_layers=False, loss_seq_chunks=8,
                     kv_cache_quant=quantize_rollouts)
    model = Transformer(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": train_bs,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 9.65e-6, "weight_decay": 0.0,
                                     "state_dtype": "bfloat16"}},
            "bf16": {"enabled": True, "master_weights_in_bf16": True},
            "zero_optimization": {"stage": 3},
            "gradient_clipping": 1.0,
            # int8-at-rest rollout view + int8 KV cache: rollouts are the
            # Hybrid Engine's whole point (reference blog: "up to 9x vs
            # HF") and decode is HBM-bound — serve them like the
            # inference engine serves (reference runtime/hybrid_engine.py
            # :178 generate; quantized view is this framework's extension)
            "hybrid_engine": {"enabled": True,
                              "quantize_rollouts": bool(quantize_rollouts)},
            "compile_cache": _cc_block(),
        })
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size,
        (1, train_bs * engine.topology.dp, seq)).astype(np.int32)}
    if isinstance(rollout_bs, int):
        rollout_bs = (rollout_bs,)
    prompt_sets = {bs: rng.integers(0, cfg.vocab_size,
                                    (bs, prompt)).astype(np.int32)
                   for bs in rollout_bs}

    # warm both compiled programs (train step + rollout decode)
    _sync_scalar(engine.train_batch(batch=batch))
    for bs in rollout_bs:
        out = engine.generate(prompt_sets[bs], max_new_tokens=gen)
        _sync_scalar(out[:, -1])

    def timed_train(n):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = engine.train_batch(batch=batch)
        _sync_scalar(loss)
        return (time.perf_counter() - t0) / n

    train_before = timed_train(train_steps)
    rollout_times = {bs: [] for bs in rollout_bs}
    train_after = None
    for _ in range(cycles):
        for bs in rollout_bs:
            t0 = time.perf_counter()
            out = engine.generate(prompt_sets[bs], max_new_tokens=gen,
                                  do_sample=True, temperature=1.0, top_p=0.9)
            _sync_scalar(out[:, -1])
            rollout_times[bs].append(time.perf_counter() - t0)
        train_after = timed_train(train_steps)

    # weight identity over the FULL pytree, reduced on device to one
    # scalar: each view leaf must equal the master cast to the view dtype
    # (the view is exactly a cast/reshard — any wrong transform on any
    # tensor fails this).  Per-leaf equality avoids fp32 upcast
    # temporaries with HBM near-full.
    import jax.numpy as jnp

    def _tree_identical(masters, views):
        checks = [jnp.all(m.astype(v.dtype) == v)
                  for m, v in zip(jax.tree.leaves(masters),
                                  jax.tree.leaves(views))]
        return jnp.all(jnp.stack(checks))

    masters = engine._params
    # the identity contract is about the UNQUANTIZED shared-weight view
    # (the reference Hybrid Engine premise); flip quantization off for the
    # check, back on after
    if quantize_rollouts:
        engine.set_rollout_quantization(bits=0)
    views = engine._inference_view()
    n_leaves = len(jax.tree.leaves(masters))
    assert n_leaves == len(jax.tree.leaves(views))
    identical = bool(jax.device_get(
        jax.jit(_tree_identical)(masters, views)))
    if quantize_rollouts:
        engine.set_rollout_quantization(bits=8)

    # int8 rollout-view spot check: round-trip the LARGEST matmul weight
    # through the same per-channel quantizer quantize_rollouts uses
    from deepspeed_tpu.runtime.weight_quantizer import WeightQuantization
    leaves = [l for l in jax.tree.leaves(masters) if l.ndim >= 2]
    big = leaves[int(np.argmax([int(np.prod(l.shape)) for l in leaves]))]
    q = WeightQuantization(bits=8, per_channel=True)
    deq = q.dequantize_tree(q.quantize_tree({"w": big}),
                            jnp.bfloat16)["w"]
    scale = float(jax.device_get(jnp.max(jnp.abs(big)).astype(jnp.float32)))
    err = float(jax.device_get(
        jnp.max(jnp.abs(deq.astype(jnp.float32)
                        - big.astype(jnp.float32)))))
    # symmetric per-channel int8: error bound is one quant step of the
    # channel max; channel maxes <= global max, so global-max/127 bounds it
    int8_roundtrip_ok = err <= scale / 127.0 + 1e-6

    per_bs = {bs: min(ts) for bs, ts in rollout_times.items()}
    best_bs = max(per_bs, key=lambda bs: bs * gen / per_bs[bs])
    result = {
        "model": model_name,
        "zero_stage": 3,
        "train_step_s_before_rollout": round(train_before, 4),
        "train_step_s_after_rollout": round(train_after, 4),
        "rollout_quant": "int8+int8kv" if quantize_rollouts else "bf16",
        "rollout_tokens_per_sec_chip": round(
            best_bs * gen / per_bs[best_bs] / jax.device_count(), 1),
        "rollout_bs": best_bs,
        "rollout_sweep_tokens_per_sec_chip": {
            str(bs): round(bs * gen / t / jax.device_count(), 1)
            for bs, t in per_bs.items()},
        "prompt_len": prompt,
        "gen_len": gen,
        "rollout_time_s": round(per_bs[best_bs], 3),
        "weights_shared_identical": identical,
        "weights_checked_leaves": n_leaves,
        "int8_view_roundtrip_ok": bool(int8_roundtrip_ok),
        "int8_view_max_abs_err": round(err, 6),
        "remat": bool(remat),
        "cycles": cycles,
    }
    return result


def offload_bench(model_name="opt-350m", *, micro_bs=4, steps=3, gas=4):
    """Measured ZeRO-Offload tier (reference ``stage_1_and_2.py:1037``
    CPU-offload + ``swap_tensor/`` NVMe, perf harness
    ``csrc/aio/py_test/``): the SAME workload in-HBM, host-offloaded
    (C++ SIMD Adam over host-resident fp32 masters/moments), and
    NVMe-swapped (pipelined ``csrc/aio`` reads behind the Adam compute).
    Reports step times and the offload overhead factor against the
    calibration phase's measured host-link numbers."""
    base = train_bench(model_name, micro_bs=micro_bs, zero_stage=2,
                       steps=steps, gas=gas)
    cpu = train_bench(model_name, micro_bs=micro_bs, zero_stage=2,
                      steps=steps, gas=gas, offload="cpu")
    nvme = train_bench(model_name, micro_bs=micro_bs, zero_stage=2,
                       steps=steps, gas=gas, offload="nvme")
    return {
        "model": model_name,
        "gradient_accumulation_steps": gas,
        "in_hbm_step_s": base["step_time_s"],
        "cpu_offload_step_s": cpu["step_time_s"],
        "nvme_offload_step_s": nvme["step_time_s"],
        "cpu_offload_overhead_x": round(
            cpu["step_time_s"] / base["step_time_s"], 2),
        "nvme_offload_overhead_x": round(
            nvme["step_time_s"] / base["step_time_s"], 2),
        # the NVMe leg's own cost on top of host offload = the swap
        # read/write not hidden behind the pipelined Adam
        "nvme_vs_cpu_x": round(
            nvme["step_time_s"] / cpu["step_time_s"], 2),
        "in_hbm_tokens_per_sec_chip": base["tokens_per_sec_chip"],
        "cpu_offload_tokens_per_sec_chip": cpu["tokens_per_sec_chip"],
        "nvme_offload_tokens_per_sec_chip": nvme["tokens_per_sec_chip"],
        "loss_in_hbm": base["loss"],
        "loss_cpu_offload": cpu["loss"],
    }


def custom_single_bench():
    """Env-driven single training bench (BENCH_MODEL etc.) — the round-1
    interface, kept for sweeps."""
    result = train_bench(
        os.environ.get("BENCH_MODEL", "opt-350m"),
        micro_bs=int(os.environ.get("BENCH_BS", "4")),
        zero_stage=int(os.environ.get("BENCH_ZERO", "1")),
        steps=int(os.environ.get("BENCH_STEPS", "10")),
        seq=int(os.environ.get("BENCH_SEQ", "2048")),
        lean=os.environ.get("BENCH_LEAN", "0") == "1",
        remat=os.environ.get("BENCH_REMAT", "0") == "1",
        remat_policy=os.environ.get("BENCH_REMAT_POLICY",
                                    "dots_and_attn_saveable"),
        scan_layers=os.environ.get("BENCH_SCAN", "0") == "1",
        fused_qkv=os.environ.get("BENCH_FQ", "0") == "1",
        loss_chunks=int(os.environ.get("BENCH_LOSS_CHUNKS", "8")))
    import jax
    print(json.dumps({
        "metric": f"{result['model']}-sft-tokens/sec/chip"
                  f"(seq{result['seq']},bs{result['micro_bs']},"
                  f"zero{result['zero_stage']},{jax.devices()[0].platform})",
        "value": result["tokens_per_sec_chip"],
        "unit": "tokens/s/chip",
        "vs_baseline": round(result["mfu"] / 0.35, 4),
        **result,
    }))


# --------------------------------------------------------------------- #
# Phase registry: name -> (primary kwargs, fallback kwargs)
# The fallback is the memory-safe variant recorded with "fallback": true.
# --------------------------------------------------------------------- #

def _north(fallback):
    steps = int(os.environ.get("BENCH_STEPS", "8"))
    # remat OFF for ~2 MFU points (r3 sweep: 48.8% vs 46.9% with remat);
    # the fallback flips it back on, which is the config that always fits
    return train_bench("opt-1.3b", micro_bs=2, zero_stage=3, steps=steps,
                       lean=True, remat=bool(fallback))


def _guard(fallback):
    steps = int(os.environ.get("BENCH_STEPS", "8"))
    return train_bench("opt-350m", micro_bs=4, zero_stage=1, steps=steps,
                       remat=bool(fallback))


def _sft27(fallback):
    """OPT-2.7B on ONE 16 GB chip: bf16 working params + bf16 grad
    accumulation on device (~10.8 GB), fp32 masters + Adam moments in
    host RAM stepped by the C++ SIMD Adam, with gradient accumulation
    amortizing the per-boundary host round trip — the reference's
    single-GPU large-model recipe (blogs/deepspeed-chat README:64-66,
    OPT-13B on one A100-80G via offload)."""
    # flash_only remat + 4-way partitioned backward: bf16 params + bf16
    # accumulator are 10.6 GB, and a one-pass backward's gradient
    # temporaries (~4 GB measured by memory_analysis) push the boundary
    # over this chip's budget — grad_partition_groups trades (N-1) extra
    # backward sweeps (free: the step is host-link-bound) for 1/N grad
    # temps
    r = train_bench("opt-2.7b", micro_bs=1, zero_stage=2,
                    steps=2,
                    gas=4 if fallback else 8,
                    remat=True, remat_policy="flash_only_saveable",
                    offload="cpu", grad_accum_dtype="bf16",
                    grad_groups=4, loss_chunks=8)
    r["bottleneck"] = (
        "host link: the per-boundary grad-down/param-up round trip "
        "(~11 GB at 2.7B) runs at the calibration phase's measured "
        "host_to_device_gbps; gradient accumulation amortizes it")
    return r


PHASES = [
    # (key in result, phase name, runner(fallback) -> dict).  Ordered
    # cheap-first (the round-5 lesson: the most expensive phase ran 4th
    # and its 40-min cold compile starved the ten phases behind it): a
    # budget overrun late in the suite can only cost the phases BEHIND
    # it, and the record already holds everything cheap.  sft_2.7b — the
    # compile-dominated single-chip 2.7B story — runs dead last, and with
    # the persistent compile cache its cold compile happens exactly once
    # per machine.
    ("calibration", "calibrate", lambda fb: calibrate_bench()),
    # per-program memory & roofline record — pinned cheap-first right
    # behind calibration (whose measured peaks anchor its rooflines):
    # the memory record commits even in rounds that die before the
    # heavy phases (the r05-blackout lesson on the memory axis)
    ("memory_snapshot", "memory_snapshot",
     lambda fb: memory_snapshot_bench(fallback=fb)),
    ("sft_350m_guard", "guard", _guard),
    ("__headline__", "north", _north),
    # the offload/NVMe tier, measured against the same in-HBM workload
    ("optimizer_offload", "offload",
     lambda fb: offload_bench(gas=2 if fb else 4,
                              steps=2 if fb else 3)),
    ("generation", "decode",
     lambda fb: decode_bench("opt-1.3b", batch_size=8 if fb else 16)),
    # continuous-batching serving vs sequential bucketed generate() on a
    # mixed-length workload — cheap-first: one extra decode-step program
    # and a lane-width prefill chunk on top of the generation phase's cost
    ("serving_continuous_batching", "serving",
     lambda fb: serving_bench("opt-1.3b", num_slots=4 if fb else 8,
                              n_requests=12 if fb else 24)),
    # serving SLO micro-phase: 4x-capacity burst with mixed deadlines →
    # shed rate, p50/p99 TTFT, graceful-preemption drain latency and the
    # one-decode-executable invariant — cheap-first, right behind the
    # serving phase whose programs it shares
    ("serving_overload", "serving_overload",
     lambda fb: serving_overload_bench("opt-1.3b",
                                       num_slots=4 if fb else 8,
                                       burst_factor=3 if fb else 4)),
    # network-front-end micro-phase: the same mixed workload via direct
    # submit() vs concurrent HTTP clients (2 tenants x 2 priorities,
    # half streaming) — transport overhead on req/s, p50/p99 TTFT and
    # time-between-tokens; cheap-first, it shares the serving phases'
    # program shapes
    ("serving_http", "serving_http",
     lambda fb: serving_http_bench("opt-1.3b",
                                   num_slots=4 if fb else 8,
                                   n_requests=12 if fb else 24)),
    # paged-KV serving at the bs96/128/192 points where the monolithic
    # lanes collapsed (r04), plus the shared-prefix prefill-once story —
    # after the cheap serving phases (it compiles one paged decode
    # program per concurrency level; see PHASE_TIMEOUT_SCALE)
    ("serving_paged", "serving_paged",
     lambda fb: serving_paged_bench("opt-1.3b",
                                    slots_list=(48, 64) if fb
                                    else (96, 128, 192),
                                    prefix_requests=12 if fb else 24)),
    # speculative decoding at the latency-sensitive bs<=16 end (ROADMAP
    # item 3): self-draft accept-rate ceiling per (bs, k) point vs the
    # non-spec serving baseline — accept rate, tok/s/chip, TBT p50/p99,
    # and the one-propose/one-verify executables-per-server proof.
    # After serving_paged: each (bs, k) point compiles a fresh
    # propose+verify pair (serving programs bypass the persistent
    # caches), so the grid is the compile cost (see PHASE_TIMEOUT_SCALE)
    ("serving_speculative", "serving_spec",
     lambda fb: serving_spec_bench("opt-1.3b",
                                   slots_list=(4,) if fb else (4, 8, 16),
                                   k_list=(2, 4) if fb else (2, 4, 8))),
    ("generation_int8", "decode_int8",
     lambda fb: decode_bench("opt-1.3b", int8=True,
                             batch_size=8 if fb else 16)),
    ("generation_int8_kv", "decode_int8_kv",
     lambda fb: decode_bench("opt-1.3b", int8=True, kv_int8=True,
                             batch_size=8 if fb else 16)),
    # throughput serving points: at bs>=64 the KV stream dominates decode
    # traffic — where the int8 cache and the S-major kernel's dead-block
    # DMA skip pay off (reference generation-phase scaling story,
    # blogs/deepspeed-chat/README.md:265)
    ("generation_int8_kv_bs64", "decode_int8_kv_bs64",
     lambda fb: decode_bench("opt-1.3b", int8=True, kv_int8=True,
                             batch_size=32 if fb else 64, gen=128)),
    ("generation_int8_kv_bs96", "decode_int8_kv_bs96",
     lambda fb: decode_bench("opt-1.3b", int8=True, kv_int8=True,
                             batch_size=48 if fb else 96, gen=128)),
    # bs128 collapsed 8x in rounds <=4 (the decode loop's out-of-kernel
    # cache writes made XLA copy the cache per step); the fused in-kernel
    # write (decode_attention new_k/new_v) runs it at full speed
    ("generation_int8_kv_bs128", "decode_int8_kv_bs128",
     lambda fb: decode_bench("opt-1.3b", int8=True, kv_int8=True,
                             batch_size=64 if fb else 128, gen=128)),
    # long-cache point: 4k-position KV cache (prompt 3968 + gen 128).
    # r04 only completed as "fallback": true (bs8) because the "auto"
    # chunk policy dropped the 4k prompt onto the one-pass dense path
    # (~32 GB of fp32 scores at bs16); decode_bench now pins the chunk
    # size for prompts >= 1024 so the primary bs16 attempt runs the real
    # chunked-prefill pipeline, and records prefill_plan either way
    ("generation_int8_kv_4k", "decode_int8_kv_4k",
     lambda fb: decode_bench("opt-1.3b", int8=True, kv_int8=True,
                             batch_size=8 if fb else 16,
                             prompt=3968, gen=128)),
    ("hybrid_rlhf", "hybrid",
     lambda fb: hybrid_bench("opt-1.3b",
                             rollout_bs=(8,) if fb else (8, 32, 64),
                             quantize_rollouts=not fb)),
    ("long_context", "long_context",
     lambda fb: long_context_bench("opt-1.3b", seq=4096 if fb else 8192)),
    # single-chip large-model story: 2.7B via ZeRO-Offload (see _sft27) —
    # LAST: the most compile- and wall-clock-expensive phase must never
    # again starve the record (round-5 rc=124)
    ("sft_2.7b", "sft_2.7b", _sft27),
]

# per-phase wall-clock budget, as a multiple of BENCH_PHASE_TIMEOUT: the
# compile-heavy tails get more rope without inflating every phase's
# budget.  Rebalanced after the round-5 rc=124 (three phases recorded,
# everything behind the 4th starved): the BASE timeout dropped 3000→900 s
# — r5 showed the cheap phases finishing in 62-73 s each, so 900 bounds
# a wedged cheap phase at ~1/3 the old damage — while the slow tier
# (offload's three training runs, hybrid's train+rollout cycles,
# long-context's 8k compiles, and above all sft_2.7b's four 2.7B
# backward compiles, ~40 min cold) keeps its old headroom via scale.
PHASE_TIMEOUT_SCALE = {
    "sft_2.7b": 4.0,
    "long_context": 2.0,
    "hybrid": 2.0,
    # three paged decode programs (one per concurrency level) + the
    # prefix server's — all opted out of the persistent caches (the PR 5
    # reload-corruption class), so every run compiles them cold
    "serving_paged": 2.0,
    # one propose + one verify program per (bs, k) grid point, all
    # persistent-cache-opted-out like every serving program: the 3x3
    # grid compiles 18 programs cold plus 3 non-spec baselines
    "serving_spec": 3.0,
    "offload": 1.5,
}


# --------------------------------------------------------------------- #
# Round-robin phase fairness across bench ROUNDS (the r05 blackout:
# under BENCH_SUITE_BUDGET a FIXED cheap-first order measured the same
# leading phases every round and starved the other 7 forever — rc=124
# with 3/10 phases, five rounds running).
# --------------------------------------------------------------------- #

def _normalize_record(rec):
    """A usable final-format record from whatever shape a ``BENCH_r*.json``
    arrived in, or None.

    The driver may publish either the final record itself or a wrapper
    ``{n, cmd, rc, tail, parsed}`` around the run — in the wrapper the
    record is ``parsed`` (when the driver decoded it) or the LAST stdout
    line captured in ``tail`` (``main()`` prints the final record as one
    JSON line).  A tail truncated mid-record is unrecoverable: return
    None and let callers walk to an older round."""
    if not isinstance(rec, dict):
        return None
    if not ("rc" in rec and ("tail" in rec or "cmd" in rec)):
        return rec                               # already final-format
    parsed = rec.get("parsed")
    if isinstance(parsed, dict):
        return parsed
    tail = rec.get("tail") or ""
    for line in reversed(tail.rstrip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                return None                      # clipped mid-record
    return None


def _round_trail():
    """Previous rounds' final records (``BENCH_r*.json`` next to this
    file / in ``BENCH_OUT_DIR``), oldest first — the driver publishes one
    per round.  Unreadable/unrecoverable files are skipped (a partial
    record must never wedge scheduling)."""
    import glob
    recs = []
    for p in sorted(glob.glob(os.path.join(_out_dir(), "BENCH_r*.json"))):
        try:
            with open(p) as f:
                rec = _normalize_record(json.load(f))
        except (OSError, ValueError):
            continue
        if rec is not None:
            recs.append(rec)
    return recs


def _REC_KEY(key):
    """Phase key -> final-record key (the headline phase is published
    under ``north_star``)."""
    return "north_star" if key == "__headline__" else key


def _phase_measured(rec, key):
    """True when ``rec`` holds a COMPLETED measurement for the phase —
    skipped / timed-out / errored entries don't count (that phase is
    still starving)."""
    ph = rec.get(_REC_KEY(key))
    return isinstance(ph, dict) and ph \
        and not any(t in ph for t in ("skipped", "timeout", "error"))


def _phase_order(phases):
    """Order phases by STALENESS — how many rounds ago the BENCH_r* trail
    last holds a completed measurement (never measured = older than the
    whole trail) — most starved first, ties in registry (cheap-first)
    order.  With a suite budget that fits k of the n phases, every phase
    is measured at least every ceil(n/k) rounds instead of the same k
    forever, and because the incremental record is rewritten after every
    phase, each round's partial record stays a valid final-format record
    of whatever its budget afforded.  Calibration is pinned first (later
    phases anchor their roofline math to its measured peaks), the
    memory_snapshot micro-phase right behind it (the per-program memory
    record must commit before any heavy phase can starve it), and
    serving_paged third: it carries the paged-attention-kernel acceptance
    story (bs128 decode vs the r04 cliff, per-bs rooflines) and must land
    in the NEXT record (BENCH_r06) rather than wait out a starvation
    rotation."""
    trail = _round_trail()

    def staleness(key):
        for age, rec in enumerate(reversed(trail), 1):
            if _phase_measured(rec, key):
                return age
        return len(trail) + 1

    pinned = ("calibrate", "memory_snapshot", "serving_paged")
    index = {p[0]: i for i, p in enumerate(phases)}
    rest = sorted((p for p in phases if p[1] not in pinned),
                  key=lambda p: (-staleness(p[0]), index[p[0]]))
    head = sorted((p for p in phases if p[1] in pinned),
                  key=lambda p: pinned.index(p[1]))
    return head + rest


# --------------------------------------------------------------------- #
# Per-phase regression thresholds against the previous round's record
# (warn-and-annotate — ROADMAP item 5: the perf trajectory must flag its
# own cliffs, not wait for a human to diff BENCH_r* files by eye)
# --------------------------------------------------------------------- #

def _regression_direction(key):
    """+1 = higher is better, -1 = lower is better, 0 = not a perf metric."""
    if "tokens_per_sec" in key or "tok_s" in key or key == "mfu" \
            or key.startswith("speedup") or key.endswith("_efficiency") \
            or "accept_rate" in key or key == "tokens_per_dispatch" \
            or key in ("achieved_gbps", "achieved_tflops") \
            or key.startswith("hbm_utilization") \
            or key.endswith("_fraction_of_peak"):
        return 1
    if key in ("step_time_s", "e2e_time_s") or "ttft_" in key \
            or "time_between_tokens" in key or key.startswith("lock_wait_") \
            or key in ("temp_size_in_bytes", "total_bytes",
                       "hbm_unattributed_bytes"):
        # roofline regressions: a program's achieved bandwidth/compute
        # falling, or its temp/live HBM budget growing, is exactly the
        # bs128-cliff class the memory record exists to flag
        return -1
    return 0


def _walk_metrics(d, path=""):
    for k, v in d.items():
        p = f"{path}.{k}" if path else k
        if isinstance(v, dict):
            yield from _walk_metrics(v, p)
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            yield p, k, v


def _annotate_regressions(key, phase, trail=None, threshold=None):
    """Compare this phase's perf metrics against the newest previous
    ``BENCH_r*`` record that measured it; annotate drops beyond the
    threshold in the phase record (``phase["regressions"]``) and warn.
    Never fails the run — the record is the alarm, the bench keeps
    measuring (a regressed phase is exactly the one worth re-measuring
    next round)."""
    if not isinstance(phase, dict) or \
            any(t in phase for t in ("skipped", "timeout", "error")):
        return
    if threshold is None:
        threshold = float(os.environ.get("BENCH_REGRESSION_THRESHOLD",
                                         "0.15"))
    if threshold <= 0:
        return
    trail = _round_trail() if trail is None else trail
    prev = next((rec[_REC_KEY(key)] for rec in reversed(trail)
                 if _phase_measured(rec, key)), None)
    if not isinstance(prev, dict):
        return
    prev_flat = {p: v for p, _, v in _walk_metrics(prev)}
    regs = []
    for path, leaf, now in _walk_metrics(phase):
        d = _regression_direction(leaf)
        old = prev_flat.get(path)
        if not d or not isinstance(old, (int, float)) or old <= 0 or now <= 0:
            continue
        ratio = now / old if d > 0 else old / now
        if ratio < 1.0 - threshold:
            regs.append({"metric": path, "prev": old, "now": now,
                         "drop_pct": round((1.0 - ratio) * 100, 1)})
    if regs:
        regs.sort(key=lambda r: -r["drop_pct"])
        phase["regressions"] = regs
        worst = regs[0]
        print(f"bench: REGRESSION in phase {key}: {len(regs)} metric(s) "
              f"beyond the {threshold:.0%} threshold vs the previous "
              f"record (worst: {worst['metric']} {worst['prev']} -> "
              f"{worst['now']}, -{worst['drop_pct']}%)", file=sys.stderr)


def run_phase(name, fallback, out_path):
    """Entry point inside a phase subprocess: run one phase, write its JSON
    to ``out_path``."""
    # crash-containment test knobs (tests/unit/test_bench_harness.py): die
    # on the primary attempt (the fallback retry must recover), die on
    # every attempt (the parent must record the error and keep going), or
    # hang (the parent's per-phase budget must skip-and-record)
    if os.environ.get("BENCH_TEST_FAIL_PRIMARY") == name and not fallback:
        raise RuntimeError("injected primary-attempt failure")
    if os.environ.get("BENCH_TEST_FAIL_ALWAYS") == name:
        raise RuntimeError("injected unconditional failure")
    if os.environ.get("BENCH_TEST_HANG") == name:
        time.sleep(10 ** 6)
    _setup_compile_cache()
    runner = next((r for _, n, r in PHASES if n == name), None)
    if runner is None:
        raise SystemExit(f"unknown phase {name!r}; valid: "
                         f"{', '.join(n for _, n, _ in PHASES)}")
    from deepspeed_tpu.runtime.compile_cache import stats
    before = stats().snapshot()
    result = runner(fallback)
    if fallback:
        result["fallback"] = True
    # compile cost observability: how much this phase compiled vs reloaded
    result["compile_cache"] = _cache_report(before)
    # per-phase peak-HBM watermark (docs/observability.md "Device memory
    # & roofline"): each phase owns its subprocess, so the accelerator's
    # process-lifetime peak IS the phase watermark.  Best-effort — a
    # backend with no live stats still records the (zero) shape
    try:
        from deepspeed_tpu.monitor.memwatch import device_memory_record
        result.setdefault("hbm_watermark", device_memory_record())
    except Exception as e:
        result.setdefault("hbm_watermark", {"error": str(e)[:200]})
    with open(out_path, "w") as f:
        json.dump(result, f)


# --------------------------------------------------------------------- #
# Parent orchestrator (never imports jax — a dead phase cannot pin HBM
# here, and the device is free for the next phase subprocess)
# --------------------------------------------------------------------- #

def _out_dir():
    """Scratch/record directory — overridable so concurrent runs (a test
    harness next to a live TPU suite) never clobber each other's partial
    results."""
    d = os.environ.get("BENCH_OUT_DIR", REPO)
    os.makedirs(d, exist_ok=True)
    return d


def _utc_now():
    """ISO-8601 UTC timestamp for per-phase forensics (the r05 blackout
    could not even be ORDERED from the record)."""
    return datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")


def _spawn_phase(name, fallback, timeout_s, extra_env):
    # pid-suffixed: two bench parents must not share phase scratch files
    out_path = os.path.join(_out_dir(),
                            f".bench_phase_{name}.{os.getpid()}.json")
    log_path = os.path.join(_out_dir(),
                            f".bench_phase_{name}.{os.getpid()}.log")
    if os.path.exists(out_path):
        os.unlink(out_path)
    cmd = [sys.executable, os.path.abspath(__file__),
           "--phase", name, "--out", out_path]
    if fallback:
        cmd.append("--fallback")
    env = dict(os.environ)
    env.update(extra_env)
    t0 = time.perf_counter()
    timed_out = False
    rc = None
    try:
        with open(log_path, "w") as log:
            proc = subprocess.run(cmd, stdout=log, stderr=subprocess.STDOUT,
                                  env=env, timeout=timeout_s)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        # distinct from any child returncode (a SIGHUP death is rc=-1 and
        # must not be mislabeled a timeout)
        timed_out = True
    wall = time.perf_counter() - t0
    if rc == 0 and os.path.exists(out_path):
        with open(out_path) as f:
            result = json.load(f)
        os.unlink(out_path)
        return result, None, wall
    tail = ""
    if os.path.exists(log_path):
        with open(log_path, errors="replace") as f:
            tail = f.read()[-2000:]
    reason = f"timeout after {timeout_s}s" if timed_out else f"rc={rc}"
    return None, f"{reason}; log tail: {tail}", wall


def _assemble_final(result, errors):
    """The final driver-contract record, from whatever phases are done —
    callable after EVERY phase (incremental record) and at exit."""
    result = dict(result)
    north = result.pop("__headline__", {})
    calib = result.get("calibration", {})
    platform = calib.get("platform", "unknown")
    final = {
        "metric": "opt-1.3b-sft-tokens/sec/chip(seq2048,bs2,zero3,"
                  "bf16-lean-opt-states," + platform + ")",
        "value": north.get("tokens_per_sec_chip"),
        "unit": "tokens/s/chip",
        # north star: >=35% MFU on the OPT-1.3B ZeRO-3 SFT workload
        "vs_baseline": round(north["mfu"] / 0.35, 4)
        if north.get("mfu") else None,
        "mfu": north.get("mfu"),
        "step_time_s": north.get("step_time_s"),
        "loss": north.get("loss"),
        "n_devices": calib.get("n_devices"),
        # honesty: on one chip the zero/dp mesh axes are size-1, so the
        # zero3 label shards nothing here — real ZeRO-3 collectives are
        # exercised on the virtual multi-device mesh (tests + driver dryrun)
        "sharding_note": ("single-chip: zero/dp axes size-1 (nominal); "
                          "multi-device sharding covered by dryrun_multichip"
                          if calib.get("n_devices") == 1 else None),
        "north_star": north,
        **result,
    }
    if errors:
        final["phase_errors"] = errors
    return final


def _write_record(path, record):
    """Atomic write: a reader (or a crash) never sees a half-written
    record."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1)
    os.replace(tmp, path)


def main():
    if os.environ.get("BENCH_MODEL"):
        _setup_compile_cache()
        custom_single_bench()
        return

    # 900s base (was 3000: the round-5 rebalance — see PHASE_TIMEOUT_SCALE):
    # cheap phases measured 62-73s each, so 900 bounds a wedged one, while
    # the compile-heavy tail (sft_2.7b's four 2.7B backward programs, ~40
    # min cold) keeps its headroom through its 4.0x scale; the persistent
    # compile cache makes warm reruns fit easily
    timeout_s = int(os.environ.get("BENCH_PHASE_TIMEOUT", "900"))
    # total-suite budget (seconds; 0 = off): once exhausted, remaining
    # phases are recorded as skipped instead of starving whatever driver
    # is wrapping this run in ITS OWN timeout (the round-5 rc=124)
    suite_budget = float(os.environ.get("BENCH_SUITE_BUDGET", "0"))
    partial_path = os.path.join(_out_dir(), ".bench_partial.json")
    # final-format record, rewritten after EVERY phase: an interrupt, a
    # crash, or an external kill after phase k still leaves a complete
    # record of all k finished phases on disk
    results_path = os.environ.get("BENCH_RESULTS_JSON") \
        or os.path.join(_out_dir(), "BENCH_partial.json")
    result = {}
    errors = {}
    extra_env = {}
    suite_t0 = time.perf_counter()
    # previous rounds' records, read once: the per-phase regression
    # thresholds (warn-and-annotate) compare against the newest record
    # that measured each phase
    trail = _round_trail()

    phases = PHASES
    if suite_budget:
        # a bounded round cannot fit every phase — rotate by staleness so
        # whatever starved last round runs first this round (the r05
        # blackout fix; without a budget the registry's cheap-first order
        # is strictly better crash containment)
        phases = _phase_order(phases)
    if os.environ.get("BENCH_PHASES"):      # subset, for debugging/tests
        want = set(os.environ["BENCH_PHASES"].split(","))
        phases = [p for p in phases if p[1] in want]

    # SIGTERM (a wrapping driver's kill) lands like Ctrl-C: emit the
    # partial record instead of dying with whatever was buffered
    import signal

    def _sigterm(signum, frame):
        raise KeyboardInterrupt
    try:
        signal.signal(signal.SIGTERM, _sigterm)
    except ValueError:
        pass                               # non-main thread (tests)

    interrupted = None
    name = "startup"
    try:
        for key, name, _ in phases:
            budget = uncapped = int(timeout_s
                                    * PHASE_TIMEOUT_SCALE.get(name, 1.0))
            if suite_budget:
                # the round-5 lesson, part two: the budget was only
                # checked BETWEEN phases, so one phase could blow straight
                # through it and starve the wrapping driver into rc=124 —
                # cap every phase's timeout at what the suite can still
                # afford (30s reserved for record flushing), and skip
                # outright when the remainder is not worth a phase
                remaining = suite_budget - (time.perf_counter() - suite_t0)
                if remaining - 30 < 60:
                    # r05-blackout forensics: the record must say WHY a
                    # phase is missing (budget math at the decision
                    # point), not just that it is
                    result[key] = {
                        "skipped": f"suite budget "
                                   f"({suite_budget:.0f}s) exhausted",
                        "skipped_reason":
                            f"suite budget {suite_budget:.0f}s exhausted "
                            f"with {remaining:.0f}s remaining (< 90s "
                            f"floor incl. the 30s record-flush reserve)",
                        "started_at": _utc_now(),
                        "elapsed_s": 0.0,
                        "timeout_budget_s": 0,
                    }
                    print(f"bench: suite budget exhausted — skipping {name}",
                          file=sys.stderr)
                    _write_record(partial_path, result)
                    _write_record(results_path,
                                  _assemble_final(result, errors))
                    continue
                budget = min(budget, int(remaining - 30))
            started_at = _utc_now()
            phase, err, wall = _spawn_phase(name, False, budget, extra_env)
            timed_out = phase is None and err and err.startswith("timeout")
            if phase is None and timed_out \
                    and os.environ.get("BENCH_RETRY_ON_TIMEOUT") != "1":
                # budget overrun: SKIP AND RECORD — a fallback retry after
                # a timeout doubles the damage to every phase behind it
                # (crashes still get the fallback retry below: a safe
                # config fixes an OOM, it does not fix slowness)
                errors[name] = err
                phase = {"error": err, "timeout": True,
                         "skipped_reason": f"timed out after {budget}s "
                                           f"(BENCH_PHASE_TIMEOUT "
                                           f"x {PHASE_TIMEOUT_SCALE.get(name, 1.0)}"
                                           f"{', capped by suite budget' if budget < uncapped else ''})"}
                print(f"bench: phase {name} exceeded its {budget}s budget — "
                      f"recording the overrun and continuing",
                      file=sys.stderr)
            elif phase is None:
                print(f"bench: phase {name} failed "
                      f"({err.splitlines()[0] if err else '?'}); "
                      f"retrying with safe config", file=sys.stderr)
                phase, err2, wall = _spawn_phase(name, True, budget,
                                                 extra_env)
                # both attempts' errors matter: the fallback can fail for a
                # DIFFERENT reason than the primary (config bug, timeout)
                err = None if phase is not None else \
                    f"primary attempt: {err}\nfallback attempt: {err2}"
                if phase is None:
                    errors[name] = err
                    phase = {"error": err}
                    print(f"bench: phase {name} failed twice — recording "
                          f"the error and continuing", file=sys.stderr)
            # per-phase forensics in EVERY record (the r05 lesson: a
            # missing phase with no started_at/budget context is
            # undiagnosable from the record alone)
            phase["phase_wall_s"] = round(wall, 1)
            phase["started_at"] = started_at
            phase["elapsed_s"] = round(wall, 1)
            phase["timeout_budget_s"] = budget
            _annotate_regressions(key, phase, trail=trail)
            if key == "calibration" and "measured_mxu_tflops" in phase:
                # anchor later phases' roofline math to the measured peaks —
                # but ONLY when they are physically plausible: host jitter
                # can corrupt the differenced timing (a >datasheet "measured
                # peak" would silently deflate every *_vs_measured below it)
                plausible = (0.3 <= phase.get("mxu_fraction_of_datasheet", 0)
                             <= 1.1
                             and 0.3 <= phase.get("hbm_fraction_of_datasheet",
                                                  0) <= 1.1)
                if plausible:
                    extra_env["BENCH_MEASURED_TFLOPS"] = \
                        str(phase["measured_mxu_tflops"])
                    extra_env["BENCH_MEASURED_GBPS"] = \
                        str(phase["measured_hbm_gbps"])
                else:
                    phase["calibration_unreliable"] = True
                    print("bench: calibration outside plausible range — "
                          "later phases use datasheet peaks only",
                          file=sys.stderr)
            result[key] = phase
            _write_record(partial_path, result)       # raw phase map
            _write_record(results_path,
                          _assemble_final(result, errors))
            print(f"bench: phase {name} done in {wall:.0f}s", file=sys.stderr)
    except KeyboardInterrupt:
        interrupted = name
        errors["__interrupted__"] = f"interrupted during phase {name}"
        print(f"bench: interrupted during {name} — emitting the record of "
              f"all completed phases", file=sys.stderr)

    final = _assemble_final(result, errors)
    if interrupted is not None:
        final["interrupted_during"] = interrupted
    _write_record(results_path, final)
    print(json.dumps(final))


if __name__ == "__main__":
    if "--phase" in sys.argv:
        i = sys.argv.index("--phase")
        name = sys.argv[i + 1]
        out = sys.argv[sys.argv.index("--out") + 1]
        run_phase(name, "--fallback" in sys.argv, out)
    else:
        main()
