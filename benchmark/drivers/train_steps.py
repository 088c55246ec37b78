"""Traffic kind ``train_steps``: a fine-tuning job.  ``deepspeed_tpu.
initialize`` with the cell's engine settings, then fused train steps
(``train_batch``) on packed synthetic batches from the seed — a fresh batch
every step, produced by a host thread while the step runs.  A step counts
when its loss has been fenced by ``block_until_ready``; step ``i + 1`` is
dispatched before step ``i`` is fenced, as a training loop does.

Mix parameters: ``seq_len``, ``support`` (symbols the tokens are drawn
from), ``check_calls`` and ``check_positions`` (how much of the first batch
the forward comparison labels), ``step_check_rows`` (the rows of the first
batch that the checked step trains on, see ``step_check_batch``).
"""

import queue
import threading
import time

import numpy as np

from benchmark import harness, opsbytes, stats, trafficgen


ADAM_BETAS = (0.9, 0.999)
CLIP = 1.0


def _engine_config(ctx):
    t = ctx.cell["system"]["training"]
    lean = t["master_weights_in_bf16"]
    opt = {"lr": t["lr"], "weight_decay": 0.0, "betas": list(ADAM_BETAS)}
    if lean:
        opt["state_dtype"] = "bfloat16"
    return {
        "train_micro_batch_size_per_gpu": t["micro_batch_per_chip"],
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": opt},
        "bf16": {"enabled": True, "master_weights_in_bf16": lean},
        "zero_optimization": {"stage": t["zero_stage"]},
        "gradient_clipping": CLIP,
        "compile_cache": harness.compile_cache_block(ctx.cache_dir),
    }


def _prefetch(source, depth=3):
    """Batches made by a host thread while the device steps."""
    q, stop = queue.Queue(maxsize=depth), threading.Event()

    def work():
        for batch in source:
            while not stop.is_set():
                try:
                    q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if stop.is_set():
                return

    t = threading.Thread(target=work, name="bench-batches", daemon=True)
    t.start()
    return q, stop, t


def check_positions(ctx, rows, seq):
    """``[calls, rows, per]``: the positions each eval call labels."""
    mix = ctx.cell["traffic"]
    rng = np.random.default_rng([ctx.seed, 7])
    return np.stack([np.stack([
        rng.choice(seq - 1, size=mix["check_positions"], replace=False)
        for _ in range(rows)]) for _ in range(mix["check_calls"])])


def reference_call_losses(ctx, tokens, positions, precision="float32"):
    """What each eval call should return, by the plain reference (on device
    0 alone: no mesh in the reference)."""
    import jax
    z = ctx.family.sizes_of(ctx.cell["config"])
    calls, rows, per = positions.shape
    flat = positions.transpose(1, 0, 2).reshape(rows, calls * per)
    group = min(rows, 4)      # rows at a time: float32 activations are large
    with jax.default_device(jax.devices()[0]):
        nll = np.concatenate([np.asarray(ctx.family.nll_at(
            z, ctx.seed, tokens[r:r + group], flat[r:r + group], precision))
            for r in range(0, rows, group)])
    return nll.reshape(rows, calls, per).mean(axis=(0, 2))


def first_loss_check(ctx, engine, tokens, reference):
    """``correct`` for a training cell, part one — the forward: the
    program's loss on the first batch against the plain float32 reference,
    before any step.  The engine returns one scalar per call, so
    ``check_calls`` calls each label ``check_positions`` positions per row
    (all else ignored), and the number compared is the root mean square over
    calls of (program - reference).  ``reference``: what
    ``reference_call_losses`` gave for these tokens."""
    limits = ctx.cell["system"]["correct"]
    rows, seq = tokens.shape
    positions = check_positions(ctx, rows, seq)
    program = []
    for call in positions:
        labels = np.full((rows, seq), -100, np.int32)
        for r in range(rows):
            # Transformer.__call__ scores labels[:, p] against position p
            labels[r, call[r]] = tokens[r, call[r] + 1]
        program.append(float(engine.eval_batch(
            {"input_ids": tokens, "labels": labels})))
    diff = stats.rms(np.asarray(program) - reference)
    return {"check": "first_batch_loss_vs_reference",
            "calls": len(positions), "positions_per_call": rows * positions.shape[2],
            "program_mean": float(np.mean(program)),
            "reference_mean": float(reference.mean()),
            "rms_difference": float(diff), "limit": limits["loss_rms"],
            "ok": bool(diff <= limits["loss_rms"])}


def step_check_batch(ctx, first):
    """The batch the checked step trains on, and the rows of it that differ:
    the first ``step_check_rows`` rows of the first batch, each repeated so
    that the batch keeps its shape and every chip of a data-parallel mesh
    holds a row of its own.  The mean over repeated rows is the mean over
    the distinct ones, so the reference differentiates those only."""
    rows = len(first)
    distinct = min(rows, ctx.cell["traffic"]["step_check_rows"])
    if rows % distinct:
        raise ValueError(f"{rows} rows do not divide into {distinct}")
    unique = first[:distinct]
    return np.repeat(unique, rows // distinct, axis=0), unique


def reference_step(ctx, unique, precision="float32"):
    """Loss, gradient norm and sampled gradients of one step on the checked
    batch, by the plain reference on device 0 alone."""
    import jax
    z = ctx.family.sizes_of(ctx.cell["config"])
    with jax.default_device(jax.devices()[0]):
        return ctx.family.loss_and_gradients(z, ctx.seed, unique, precision,
                                             group=min(len(unique), 4))


def implied_gradients(moment1, moment2, grad_norm):
    """The gradient that Adam's two moments hold after ONE step from zero
    moments, and its size: ``m = (1 - b1) c g`` and ``v = (1 - b2) (c g)^2``,
    where ``c`` is the clipping factor the step applied."""
    import jax.numpy as jnp
    c = jnp.minimum(1.0, CLIP / (grad_norm + 1e-6))
    b1, b2 = ADAM_BETAS
    return (moment1.astype(jnp.float32) / ((1 - b1) * c),
            jnp.sqrt(moment2.astype(jnp.float32) / (1 - b2)) / c)


def gradient_errors(moment1_of, moment2_of, want, grad_norm, chunk=1 << 26):
    """Relative error in L2, over all the tensors of ``want`` together, of
    the gradients the first moments hold against ``want``, and of the sizes
    the second moments hold against ``|want|``.  ``moment*_of(name)`` gives
    the program's array.  The arithmetic runs on the device, a chunk of a
    tensor at a time (a sum over 10^8 elements on the host takes seconds)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(m, v, w, norm):
        g1, g2 = implied_gradients(m, v, norm)
        return jnp.stack([jnp.sum(jnp.square(g1 - w)),
                          jnp.sum(jnp.square(g2 - jnp.abs(w))),
                          jnp.sum(jnp.square(w))])

    total = np.zeros(3)
    for name, w in want.items():
        m, v, w = (np.ravel(x) for x in (moment1_of(name), moment2_of(name), w))
        for i in range(0, w.size, chunk):
            total += np.asarray(sums(m[i:i + chunk], v[i:i + chunk],
                                     w[i:i + chunk], np.float32(grad_norm)),
                                np.float64)
    return float(np.sqrt(total[0] / total[2])), \
        float(np.sqrt(total[1] / total[2]))


def first_step_check(ctx, engine, loss, reference):
    """``correct`` for a training cell, part two — the backward and the
    optimizer: after ONE fused ``train_batch`` from zero moments, the
    gradients that the optimizer's moments hold (read through the program's
    ``safe_get_full_optimizer_state``, for the tensors of the family's
    ``gradient_sample``) against the plain float32 reference's, one element
    at a time.  The number compared is the relative error in L2 over all
    sampled elements, the larger of the first moment's (signed gradients)
    and the second's (their sizes).  It reaches the attention kernel's
    backward, remat, the chunked loss, the reduction over chips (a sum for a
    mean is an error of 3) and the moments as the optimizer stores them."""
    from deepspeed_tpu.utils import tensor_fragment
    limits = ctx.cell["system"]["correct"]
    z = ctx.family.sizes_of(ctx.cell["config"])
    grad_norm = float(engine.get_global_grad_norm())
    want = reference["gradients"]
    slot = lambda key: lambda name: ctx.family.program_tensor(
        lambda path: tensor_fragment.safe_get_full_optimizer_state(
            engine, path, key), name, z)
    e1, e2 = gradient_errors(slot("exp_avg"), slot("exp_avg_sq"), want,
                             grad_norm)
    worst = max(e1, e2)
    return {"check": "first_step_gradients_vs_reference",
            "elements": int(sum(g.size for g in want.values())),
            "program_loss": float(loss), "reference_loss": reference["loss"],
            "program_grad_norm": grad_norm,
            "reference_grad_norm": reference["grad_norm"],
            "moment1_relative_error": e1, "moment2_relative_error": e2,
            "relative_error": worst, "limit": limits["gradient_rel_error"],
            "ok": bool(np.isfinite(worst)
                       and worst <= limits["gradient_rel_error"])}


def rows_per_step(ctx):
    return ctx.cell["system"]["training"]["micro_batch_per_chip"] \
        * ctx.cell["chips"]


def build_engine(ctx):
    """``deepspeed_tpu.initialize`` at the cell's settings with weights from
    the seed, on a mesh of the cell's chips."""
    import deepspeed_tpu
    sys_, model = ctx.cell["system"], ctx.cell["config"]
    module = ctx.family.program_model(
        model, loss_seq_chunks=sys_["training"]["loss_seq_chunks"])
    engine, *_ = deepspeed_tpu.initialize(
        model=module, config=_engine_config(ctx),
        model_parameters=ctx.family.program_params(module, model, ctx.seed))
    if engine.topology.dp != ctx.cell["chips"]:
        raise RuntimeError(f"the engine's mesh has dp={engine.topology.dp}, "
                           f"the cell has {ctx.cell['chips']} chips")
    return engine


def run(ctx):
    import jax
    mix, sys_ = ctx.cell["traffic"], ctx.cell["system"]
    z, rows = ctx.family.sizes_of(ctx.cell["config"]), rows_per_step(ctx)
    batches = trafficgen.train_batches(mix, z["vocab"], rows, ctx.seed)
    first = next(batches)
    step_batch, unique = step_check_batch(ctx, first)
    # the reference first, while the device holds nothing else
    t_ref = time.monotonic()
    ref_losses = reference_call_losses(
        ctx, first, check_positions(ctx, *first.shape))
    ref_step = reference_step(ctx, unique)
    t_ref = time.monotonic() - t_ref
    engine = build_engine(ctx)
    chips = jax.device_count()
    check = first_loss_check(ctx, engine, first, ref_losses)
    warm = engine.warmup(batch={"input_ids": first[None]})
    loss0 = float(engine.train_batch(batch={"input_ids": step_batch[None]}))
    step_check = first_step_check(ctx, engine, loss0, ref_step)
    del ref_step
    harness.say(phase="engine", dp=engine.topology.dp, rows_per_step=rows,
                warmup_compile_s=warm, first_step_loss=loss0,
                reference_s=t_ref)

    q, stop, thread = _prefetch(batches)
    tokens_per_step = rows * mix["seq_len"]
    prof, losses, fenced = ctx.profiler, [], 0
    try:
        t0 = time.monotonic()
        ctx.window_started(t0)
        pending = engine.train_batch(batch={"input_ids": q.get()[None]})
        while True:
            now = time.monotonic() - t0
            prof.poll(now)
            # host spans on the profiler's own clock (free with no trace on)
            with jax.profiler.TraceAnnotation("bench:next_batch"):
                batch = q.get()
            nxt = engine.train_batch(batch={"input_ids": batch[None]})
            with jax.profiler.TraceAnnotation("bench:fence"):
                pending.block_until_ready()
            fenced += 1
            losses.append(pending)
            pending = nxt
            if time.monotonic() - t0 >= ctx.seconds:
                break
        pending.block_until_ready()
        fenced += 1
        losses.append(pending)
        window = time.monotonic() - t0
        prof.finish()
    finally:
        stop.set()
        thread.join(timeout=10)
    losses = [float(l) for l in losses]
    limits = sys_["correct"]
    finite = bool(np.all(np.isfinite(losses)))
    band = {"check": "loss_after_window", "steps": fenced,
            "loss_first": loss0, "loss_last": losses[-1],
            "floor_ln_support": float(np.log(mix["support"])),
            "limit_low": limits["loss_last_low"],
            "limit_high": limits["loss_last_high"],
            "ok": bool(finite and limits["loss_last_low"] <= losses[-1]
                       <= limits["loss_last_high"])}
    rate = stats.rate(fenced * tokens_per_step, window) / chips
    flops = opsbytes.model_flops_per_token(z, mix["seq_len"])
    harness.say(phase="window", window_s=window, steps=fenced,
                tokens_per_step=tokens_per_step, chips=chips,
                step_s=window / fenced, model_flops_per_token=flops,
                mfu_pct=100.0 * rate * flops
                / ctx.peaks["bf16_flops_per_s"])
    engine.destroy()
    return {
        "attempted": fenced, "failed": 0 if finite else fenced,
        "checks": [check, step_check, band],
        "end_to_end": {"train_tokens_per_s_per_chip": rate},
        "observed": {"rows": rows, "seq_len": mix["seq_len"], "sizes": z,
                     "chips": chips},
    }
