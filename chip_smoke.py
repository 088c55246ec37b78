#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path through the public entry points at the full
published width and depth of OPT-1.3B (bf16, weights from ``--seed``):

* **serve**: ``init_inference`` → ``engine.serve()`` (paged slot engine) →
  ``serve_http`` on a loopback port; blocking and streaming
  ``POST /v1/generate`` requests of differing prompt lengths, ``GET /healthz``,
  and one request's greedy tokens checked against ``engine.generate()``;
* **train**: ``initialize`` with ZeRO-3 + the memory-lean bf16 mode; steps
  through ``engine(batch)/backward/step`` and through ``train_batch`` on a
  fixed batch (loss finite and falling), then a checkpoint round trip.

``--chips 4`` runs ONLY the sharded phase instead: ZeRO-3 over a 4-device mesh
with fp32 master weights and moments (16 B/param — does not fit one chip),
compared with a plain single-device bf16 forward of the same parameters.

Every stdout line is one JSON object.  The LAST line, and nothing after it, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase raises: the exit code is non-zero and no result line is
printed.  Without a TPU the script exits 2 with an empty stdout.

``--rehearse`` runs every phase at toy size on the CPU (interpreted kernels)
to find wrong paths before chip time is spent.  It prints "rehearsal passed"
on an earlier line, ends with ``{"ok": false, ...}`` naming the platform it
really ran on, and exits 1 — no run without a TPU can be read as a pass.
"""

import argparse
import gc
import http.client
import json
import os
import shutil
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# width and depth are OPT-1.3B's published ones (models/opt.py); ``toy``
# exists only for --rehearse
REAL = dict(
    model="opt-1.3b", overrides={}, seq=2048, micro_bs=2,
    slots=8, cache_len=512, chunk=128, page=64, decode_block=8,
    # (prompt_len, max_new_tokens, stream); the first is the one compared
    # with generate() — one full prefill chunk
    requests=[(128, 16, False), (24, 16, True), (57, 24, False),
              (200, 8, True), (256, 32, False), (100, 16, False)],
    mesh_micro_bs=1, fence=(4096, 256))
TOY = dict(
    model="opt-125m",
    overrides=dict(hidden_size=64, num_layers=2, num_heads=4,
                   ffn_hidden_size=128, vocab_size=512, max_seq_len=128),
    seq=64, micro_bs=2,
    slots=2, cache_len=64, chunk=16, page=16, decode_block=2,
    requests=[(16, 4, False), (5, 4, True), (23, 3, False)],
    mesh_micro_bs=1, fence=(256, 16))

LR = 2e-4                 # large enough to survive bf16 master-weight rounding
LOSS0_TOL = 0.05          # |sharded step-0 loss - single-device bf16 forward|
NEAR_TIE_TOL = 0.25       # logit margin that bf16 batch-shape noise may flip
SHARD_BAND = (0.8, 1.6)   # per-device bytes_in_use / (sharded state / 4)


def emit(**record):
    print(json.dumps(record), flush=True)


def mem(device_index=0):
    from deepspeed_tpu.accelerator import get_accelerator
    return get_accelerator().memory_snapshot(device_index)


def release(what):
    """Drop freed device buffers and report what the next phase starts on."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    emit(phase="release", after=what, bytes_in_use=mem()["bytes_in_use"])


def cache_delta(before):
    """What the phase compiled; an AOT compile that raised and fell back to
    plain jit is a compiler refusal the smoke exists to find — fatal."""
    from deepspeed_tpu.runtime import compile_cache
    now = compile_cache.stats().snapshot()
    out = {k: now[k] - before[k] for k in (
        "persistent_requests", "persistent_hits", "aot_fallbacks")}
    out["compile_seconds"] = {
        k: round(v, 1) for k, v in now["compile_seconds"].items()
        if before["compile_seconds"].get(k) != v}
    if out["aot_fallbacks"]:
        raise RuntimeError(f"{out['aot_fallbacks']} AOT compile(s) raised "
                           f"and fell back to plain jit")
    return out


def probe(engine):
    """One scalar of the engine's parameters, to compare across a restore."""
    import jax
    return float(jax.tree.leaves(engine.params)[0].astype("float32").sum())


def structured_tokens(rng, vocab, shape):
    """Tokens from a 64-symbol support: a few optimizer steps visibly lower
    the loss on them (uniform tokens over 50k symbols only memorize)."""
    support = rng.choice(vocab, size=64, replace=False)
    return support[rng.integers(0, 64, shape)].astype("int32")


# --------------------------------------------------------------------- #
def env_phase():
    import flax
    import jax
    import jaxlib
    from deepspeed_tpu.ops.adam import cpu_adam
    from deepspeed_tpu.runtime import compile_cache
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    snap = mem()
    emit(phase="env", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, flax=flax.__version__,
         python=sys.version.split()[0],
         bytes_limit=snap["bytes_limit"], limit_source=snap["limit_source"],
         compile_cache_dir=compile_cache.default_cache_dir(),
         compile_cache_env=compile_cache.env_cache_dir(),
         cpu_adam_native=cpu_adam.is_available())


def fence_phase(size):
    """Does ``block_until_ready`` fence?  If it does, the wait is the work's
    own time and a scalar that depends on the result then costs next to
    nothing; if it returned early, the dependent fetch would pay instead.
    Every timer in the repo leans on it, so a miss is fatal."""
    import jax
    import jax.numpy as jnp

    n, iters = size["fence"]

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(
            0, iters, lambda _, a: (a @ a) * (1.0 / n) + 1.0, x)

    x = jnp.ones((n, n), jnp.bfloat16)
    float(work(x)[0, 0])                       # compile work AND the fetch
    t0 = time.perf_counter()
    y = work(x)
    t_dispatch = time.perf_counter() - t0
    y.block_until_ready()
    t_ready = time.perf_counter() - t0
    float(y[0, 0])                             # dependent fetch
    t_fetch = time.perf_counter() - t0 - t_ready
    fences = t_fetch < t_ready
    emit(phase="fence", matmul=n, iters=iters, dispatch_s=round(t_dispatch, 5),
         block_until_ready_s=round(t_ready, 5),
         dependent_fetch_after_s=round(t_fetch, 5),
         block_until_ready_fences=fences)
    if not fences:
        raise RuntimeError("block_until_ready returned before the device "
                           "finished: every timing in this repo is suspect")


# --------------------------------------------------------------------- #
def _post(port, body, out, k):
    """One HTTP client; its result (or exception) lands in ``out[k]``."""
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
        conn.request("POST", "/v1/generate", json.dumps(body))
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {resp.read()!r}")
        if body["stream"]:
            tokens, end = [], None
            while True:
                line = resp.readline()
                if not line.strip():
                    if not line:
                        break
                    continue
                ev = json.loads(line)
                if ev["event"] == "token":
                    tokens.append(ev["token"])
                else:
                    end = ev
                    break
            out[k] = {"status": end and end["status"], "new": tokens,
                      "rid": end and end["rid"]}
        else:
            payload = json.loads(resp.read())
            out[k] = {"status": payload["status"], "rid": payload["rid"],
                      "new": payload["output"][len(body["input_ids"]):]}
        conn.close()
    except Exception as e:                      # re-raised by the caller
        out[k] = e


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def serve_phase(size, seed):
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving.frontend import serve_http
    from deepspeed_tpu.models.opt import opt_model
    from deepspeed_tpu.runtime import compile_cache

    t_phase = time.perf_counter()
    cc0 = compile_cache.stats().snapshot()
    model = opt_model(size["model"], dtype="bfloat16", scan_layers=False,
                      **size["overrides"])
    engine = deepspeed_tpu.init_inference(model, config={
        "dtype": "bfloat16",
        "prefill_chunk_size": None,          # generate(): one-pass prefill
        "compile_cache": {"enabled": True, "executables": False},
        "serving": {"enabled": True,
                    "page_size": size["page"], "num_slots": size["slots"],
                    "max_cache_len": size["cache_len"],
                    "prefill_chunk": size["chunk"],
                    "decode_block": size["decode_block"]}})
    engine.init_params(seed=seed)
    vocab = model.config.vocab_size
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, (p,)).astype(np.int32)
               for p, _, _ in size["requests"]]

    srv = engine.serve()
    modes = dict(srv.kernel_modes)
    if modes != {"decode": "pallas_paged_decode",
                 "prefill_chunk": "pallas_chunked_prefill"}:
        raise RuntimeError(f"serving resolved non-Pallas kernels: {modes}")
    warm = srv.warmup()
    fe = serve_http(srv)
    try:
        status, body = _get(fe.port, "/healthz")
        if status != 200 or not json.loads(body).get("ok"):
            raise RuntimeError(f"/healthz answered {status}: {body!r}")
        t0 = time.perf_counter()
        results = [None] * len(prompts)
        clients = [threading.Thread(target=_post, args=(fe.port, {
            "input_ids": [int(t) for t in prompt], "max_new_tokens": new,
            "stream": stream}, results, k))
            for k, (prompt, (_, new, stream)) in enumerate(
                zip(prompts, size["requests"]))]
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=900)
            if c.is_alive():
                raise RuntimeError("an HTTP client did not finish in 900 s")
        t_requests = time.perf_counter() - t0
        for k, (res, (p, new, stream)) in enumerate(
                zip(results, size["requests"])):
            if isinstance(res, Exception):
                raise res
            if res["status"] != "COMPLETED" or len(res["new"]) != new:
                raise RuntimeError(
                    f"request {k} (prompt {p}, stream={stream}): status "
                    f"{res['status']}, {len(res['new'])}/{new} tokens")
            if stream:      # the streamed tokens ARE the terminal record
                status, body = _get(fe.port, f"/v1/requests/{res['rid']}")
                record = json.loads(body)["output"][p:]
                if status != 200 or record != res["new"]:
                    raise RuntimeError(f"request {k}: streamed tokens differ "
                                       f"from the terminal record")
        stats = dict(srv.stats)
    finally:
        fe.shutdown(close_engine=True)
    for name in ("_sched_thread", "_loop_thread"):
        if getattr(fe, name).is_alive():
            raise RuntimeError(f"front end thread {name} outlived shutdown")
    if stats["paged_attention_fallback"] != 0:
        raise RuntimeError(f"paged attention fell back to the reference "
                           f"{stats['paged_attention_fallback']} time(s)")

    # greedy tokens of request 0 against the whole-batch generate() path
    p0, new0, _ = size["requests"][0]
    got = np.asarray(results[0]["new"])
    ref = np.asarray(engine.generate(prompts[0][None], max_new_tokens=new0))
    ref = ref[0, p0:p0 + new0]
    agree = int(np.argmin(np.append(got == ref, False)))   # common prefix
    match = "exact"
    margin = None
    if agree < new0:
        # bf16: the 8-slot paged decode and the B=1 monolithic decode round
        # differently, and a random-weight model has near-ties.  The first
        # divergence must BE one under a teacher-forced full forward.
        seq = np.concatenate([prompts[0], got[:agree]])
        pad = -len(seq) % size["chunk"]
        logits = np.asarray(engine.forward(
            np.pad(seq, (0, pad))[None]))[0, len(seq) - 1].astype(np.float32)
        margin = float(abs(logits[got[agree]] - logits[ref[agree]]))
        best = float(logits.max() - min(logits[got[agree]],
                                        logits[ref[agree]]))
        if best > NEAR_TIE_TOL:
            raise RuntimeError(
                f"serving and generate() diverge at token {agree} and it is "
                f"no near-tie: candidates {int(got[agree])}/{int(ref[agree])}"
                f" sit {best:.3f} below the reference argmax")
        match = "near_tie"
    cc = cache_delta(cc0)
    emit(phase="serve", model=size["model"], layers=model.config.num_layers,
         hidden=model.config.hidden_size,
         requests=len(prompts), streamed=sum(s for _, _, s in size["requests"]),
         completed=len(prompts), requests_wall_s=round(t_requests, 2),
         warmup_compile_s={k: round(v, 1) for k, v in warm.items()},
         kernel_modes=modes,
         paged_attention_fallback=stats["paged_attention_fallback"],
         generate_match=match, generate_agree_tokens=agree,
         generate_compared_tokens=new0, near_tie_margin=margin,
         compile_cache=cc, peak_bytes_in_use=mem()["peak_bytes_in_use"],
         wall_s=round(time.perf_counter() - t_phase, 1))
    engine.release_workspace()
    return [res["new"] for res in results]


# --------------------------------------------------------------------- #
def _train_config(micro_bs, lean):
    opt = {"lr": LR, "weight_decay": 0.0}
    if lean:
        opt["state_dtype"] = "bfloat16"
    return {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": opt},
        "bf16": {"enabled": True, "master_weights_in_bf16": lean},
        "zero_optimization": {"stage": 3},
        "gradient_clipping": 1.0,
        "compile_cache": {"enabled": True, "executables": False},
    }


def _train_model(size):
    from deepspeed_tpu.models.opt import opt_model
    return opt_model(size["model"], dtype="bfloat16", loss_seq_chunks=8,
                     **{"max_seq_len": size["seq"], **size["overrides"]})


def train_phase(size, seed):
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.runtime import compile_cache

    t_phase = time.perf_counter()
    cc0 = compile_cache.stats().snapshot()
    model = _train_model(size)
    config = _train_config(size["micro_bs"], lean=True)
    engine, *_ = deepspeed_tpu.initialize(model=model, config=config)
    rng = np.random.default_rng(seed)
    tokens = structured_tokens(rng, model.config.vocab_size,
                               (size["micro_bs"] * engine.topology.dp,
                                size["seq"]))
    losses = []
    for _ in range(2):                       # the three-call API
        loss = engine({"input_ids": tokens})
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    for _ in range(3):                       # the fused step
        losses.append(float(engine.train_batch(
            batch={"input_ids": tokens[None]})))
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"training loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"training loss did not fall: {losses}")
    peak = mem()["peak_bytes_in_use"]

    # checkpoint round trip into a FRESH engine: an in-place restore would
    # hold two copies of the 7.9 GB train state on one 16 GB chip
    steps = engine.global_steps
    before = probe(engine)
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        t0 = time.perf_counter()
        if not engine.save_checkpoint(ckpt):
            raise RuntimeError("save_checkpoint reported failure")
        t_save = time.perf_counter() - t0
        engine.destroy()
        del engine, loss
        release("train engine")
        engine, *_ = deepspeed_tpu.initialize(model=model, config=config)
        t0 = time.perf_counter()
        engine.load_checkpoint(ckpt)
        t_load = time.perf_counter() - t0
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    if engine.global_steps != steps or probe(engine) != before:
        raise RuntimeError(
            f"checkpoint round trip: global_steps {steps} -> "
            f"{engine.global_steps}, probe {before} -> {probe(engine)}")
    cc = cache_delta(cc0)
    emit(phase="train", model=size["model"], layers=model.config.num_layers,
         hidden=model.config.hidden_size, seq=size["seq"],
         micro_bs=size["micro_bs"], zero_stage=3, lean_bf16=True,
         losses=[round(l, 4) for l in losses], global_steps=steps,
         checkpoint_roundtrip=True, save_s=round(t_save, 1),
         load_s=round(t_load, 1), compile_cache=cc, peak_bytes_in_use=peak,
         wall_s=round(time.perf_counter() - t_phase, 1))
    engine.destroy()


# --------------------------------------------------------------------- #
def mesh_phase(size, seed):
    """ZeRO-3 across 4 devices with reference-exact fp32 master weights and
    moments, against a plain single-device bf16 forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.runtime import compile_cache

    t_phase = time.perf_counter()
    cc0 = compile_cache.stats().snapshot()
    n = jax.device_count()
    if n != 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {n}")
    model = _train_model(size)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=_train_config(size["mesh_micro_bs"], lean=False))
    topo = engine.topology
    rng = np.random.default_rng(seed)
    tokens = structured_tokens(rng, model.config.vocab_size,
                               (size["mesh_micro_bs"] * topo.dp, size["seq"]))
    batch = {"input_ids": tokens}

    loss0 = float(engine.eval_batch(batch))          # inits the sharded state
    state_bytes = sum(
        leaf.nbytes for leaf in jax.tree.leaves(
            (engine.params, engine._opt_state)) if hasattr(leaf, "nbytes"))
    placed = [mem(i)["bytes_in_use"] for i in range(n)]

    # the same parameters and batch, bf16, on device 0 alone, through the
    # plain XLA attention: no kernel and no mesh in the reference
    import dataclasses
    plain = model.clone(config=dataclasses.replace(
        model.config, use_flash_attention=False))
    dev0 = jax.devices()[0]
    cast = jax.jit(lambda t: jax.tree.map(        # stays sharded
        lambda p: p.astype(jnp.bfloat16)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, t))
    solo = jax.device_put(cast(engine.params),
                          jax.sharding.SingleDeviceSharding(dev0))
    ref0 = float(jax.jit(lambda p, b: plain.apply(p, b))(
        solo, jax.device_put(batch, dev0)))
    del solo
    if not abs(loss0 - ref0) <= LOSS0_TOL:
        raise RuntimeError(f"step-0 loss {loss0} on the mesh vs {ref0} on one "
                           f"device: beyond {LOSS0_TOL}")

    losses = [float(engine.train_batch(batch={"input_ids": tokens[None]}))
              for _ in range(3)]
    loss_end = float(engine.eval_batch(batch))
    if not all(np.isfinite(losses + [loss_end])) or not loss_end < loss0:
        raise RuntimeError(f"mesh training loss did not fall: {loss0} -> "
                           f"{losses} -> {loss_end}")
    gc.collect()
    in_use = [mem(i)["bytes_in_use"] for i in range(n)]
    share = state_bytes / n
    live = jax.devices()[0].platform == "tpu"       # the CPU reports no stats
    if live:
        for i, b in enumerate(in_use):
            if not SHARD_BAND[0] * share <= b <= SHARD_BAND[1] * share:
                raise RuntimeError(
                    f"device {i} holds {b} bytes, outside {SHARD_BAND} x the "
                    f"quarter share {share:.0f}: {in_use}")
    cc = cache_delta(cc0)
    emit(phase="mesh", model=size["model"], layers=model.config.num_layers,
         hidden=model.config.hidden_size, seq=size["seq"], devices=n,
         mesh_shape=dict(topo.mesh.shape), mesh_built_by=topo.mesh_built_by,
         zero_stage=3, master="fp32", state_bytes=state_bytes,
         quarter_share_bytes=int(share), shard_band=SHARD_BAND,
         bytes_in_use_after_init=placed, bytes_in_use_after_steps=in_use,
         per_device_checked=live,
         loss0_mesh=round(loss0, 4), loss0_single_device=round(ref0, 4),
         loss0_tolerance=LOSS0_TOL, losses=[round(l, 4) for l in losses],
         loss_end=round(loss_end, 4), compile_cache=cc,
         peak_bytes_in_use=[mem(i)["peak_bytes_in_use"] for i in range(n)],
         wall_s=round(time.perf_counter() - t_phase, 1))
    engine.destroy()


# --------------------------------------------------------------------- #
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training phase on a "
                         "4-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU; never reports ok")
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run.  --rehearse runs the toy-size CPU rehearsal.",
              file=sys.stderr)
        return 2
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{device['count']} device(s)", file=sys.stderr)
        return 2

    size = TOY if args.rehearse else REAL
    t0 = time.perf_counter()
    env_phase()
    if args.chips == 4:
        mesh_phase(size, args.seed)
        release("mesh phase")
    else:
        fence_phase(size)
        serve_phase(size, args.seed)
        release("serve phase")
        train_phase(size, args.seed)
        release("train phase")
    emit(phase="total", wall_s=round(time.perf_counter() - t0, 1))

    stray = [t.name for t in threading.enumerate()
             if t is not threading.main_thread() and not t.daemon]
    if stray:
        raise RuntimeError(f"threads still running at exit: {stray}")
    if args.rehearse:
        emit(rehearsal="rehearsal passed", note="toy sizes on the CPU: not a "
             "chip run, so the result below is not ok")
    sys.stderr.flush()
    # the contract's last line: these keys and no others, nothing after it
    print(json.dumps({"ok": not args.rehearse, "device": device}), flush=True)
    return 1 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
