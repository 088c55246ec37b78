"""What the two serving drivers share: the server under test, built through
the program's normal entry points, and the comparison with the reference."""

import numpy as np

from benchmark import harness


def build_server(ctx, tracing):
    """``init_inference`` -> ``engine.serve()`` at the cell's settings, with
    weights from the seed, and the two expensive programs compiled.
    ``generate()`` is never compiled: no request of a cell goes through it."""
    import deepspeed_tpu
    model = ctx.cell["config"]
    module = ctx.family.program_model(model, scan_layers=False)
    settings = dict(ctx.cell["system"]["serving"])
    engine = deepspeed_tpu.init_inference(module, config={
        "dtype": "bfloat16", "prefill_chunk_size": None,
        "compile_cache": harness.compile_cache_block(ctx.cache_dir),
        "serving": {"enabled": True, "tracing": tracing, **settings}})
    engine.set_params(ctx.family.program_params(module, model, ctx.seed))
    srv = engine.serve()
    modes = dict(srv.kernel_modes)
    want = {"decode": "pallas_paged_decode",
            "prefill_chunk": "pallas_chunked_prefill"}
    if settings.get("paged") and modes != want:
        raise RuntimeError(f"serving resolved non-Pallas kernels: {modes}")
    warm = srv.warmup()
    harness.say(phase="server", kernel_modes=modes, num_pages=srv.num_pages,
                cache_len=srv.cache_len,
                warmup_compile_s={k: round(v, 2) for k, v in warm.items()})
    return engine, srv


def check_outputs(ctx, completed, chooser=None):
    """``correct`` for a serving cell: for a seeded sample of completed
    requests, the plain float32 reference's full forward over prompt +
    generated tokens.  Each generated token's reference logit lies some gap
    below that position's largest (0 where the program picked the
    reference's own choice); the number compared is the MEAN gap over the
    sample's tokens — steady from seed to seed, where the largest single gap
    is not, and one token from a broken cache row (a gap of units) moves it
    past the limit all the same.  (Tokens are not compared: with weights
    from a seed the largest logit changes on rounding.)

    ``completed``: ``[(prompt_ids, generated_ids)]``.  ``chooser``: see
    ``families/opt.py::chosen_gaps`` — the control.  Returns the check
    record with the number compared beside its limit."""
    limits = ctx.cell["system"]["correct"]
    z = ctx.family.sizes_of(ctx.cell["config"])
    pad_to = ctx.cell["system"]["serving"]["max_cache_len"]
    rng = np.random.default_rng([ctx.seed, 6])
    pick = rng.permutation(len(completed))[:limits["sample_requests"]]
    gaps = [np.zeros(0)]
    for i in pick:
        prompt, new = completed[i]
        gaps.append(ctx.family.chosen_gaps(
            z, ctx.seed, np.concatenate([prompt, new]), len(prompt),
            len(new), pad_to, chooser))
    gaps = np.concatenate(gaps)
    mean = float(gaps.mean()) if len(gaps) else float("inf")
    return {"check": "reference_logit_gap", "requests": int(len(pick)),
            "tokens": int(len(gaps)),
            "tokens_not_the_reference_argmax": int((gaps > 0).sum()),
            "max_logit_gap": float(gaps.max()) if len(gaps) else None,
            "mean_logit_gap": mean, "limit": limits["mean_logit_gap"],
            "ok": bool(mean <= limits["mean_logit_gap"])}
