"""Operations and bytes the longcat family's double layer NEEDS, and where
its per-layer readers find the program's counters and scopes — kept with the
benchmark so that no PR that claims a gain can change the count.  The PR
that brought the family wrote no kernel: the dense latent layer runs the
dots3 / glm5 families' kernels under a mask that keeps every visible key,
so its two rooflines are read off the program's SCOPES (``attn.mla_dense_
chunk``, ``attn.mla_dense_decode``: whatever runs under them, kernels and
XLA) and a later kernel PR is judged by the same count.

What the spans carry (``docs/observability.md``):
``dstpu.sched.dispatch.prefill_chunk`` and ``.decode`` — ``causal_pairs``
((query, key) pairs under the causal mask) and ``latent_rows_read`` (a
lane's live rows once a row), each summed over the eight pool layers (and,
of a decode dispatch, its steps); ``dstpu.sched.commit`` and
``dstpu.sched.wait_device`` — the expert load: ``moe_assignments`` (rows the
HELD experts computed), ``moe_experts_touched``, ``moe_assignments_elsewhere``
(real choices of absent experts) and ``moe_zero_picks`` (choices that fell
on zero experts).  A parent commit from before them, or another model's
cell, has none: every function here then returns None and the metric is
left out."""

from benchmark import opsbytes, opsbytes_dots3, scopes, spans

DENSE_CHUNK, DENSE_DECODE = "attn.mla_dense_chunk", "attn.mla_dense_decode"
BRANCH = "scmoe.experts"
PICKS = ("moe_zero_picks", "moe_assignments", "moe_assignments_elsewhere",
         "moe_experts_touched")


def scope_seconds(run, *frames):
    """Own device seconds of the slot programs' instructions whose
    ``op_name`` lies under one of the scopes ``frames``; None without the
    join, or where nothing ran under them."""
    joined = scopes.by_part(run, scopes.SERVE) if run.trace else None
    if joined is None:
        return None
    seconds = sum(s for op_name, s in joined["by_op_name"].items()
                  if any(f in frames for f in op_name.split("/")))
    return seconds or None


def scope_share_pct(run, *frames):
    seconds = scope_seconds(run, *frames)
    return None if seconds is None else 100.0 * seconds / run.trace.window_s


def picks():
    """The router's choices over the slice's spans: ``{key: sum}`` of
    :data:`PICKS`, None where no span carries ``moe_zero_picks``."""
    total, found = dict.fromkeys(PICKS, 0), False
    for e in spans.host_spans():
        if PICKS[0] in e["stats"]:
            found = True
            for k in PICKS:
                total[k] += int(e["stats"].get(k, 0))
    return total if found else None


def dispatch_roofline_pct(run, frame, program, span, flops_of, bytes_of):
    """A scope's share of its roofline, both sides PER DISPATCH (host spans
    and device events are cut by the slice at different dispatches): what
    a dispatch needs — ``flops_of`` / ``bytes_of`` of the ``span``s' summed
    counters over their number — against the scope's device seconds over
    the executions of ``program``."""
    seconds = scope_seconds(run, frame)
    runs = len(run.trace.module_durations(program)) if seconds else 0
    work = opsbytes_dots3.span_sums(span, ("causal_pairs",
                                           "latent_rows_read"))
    if not runs or not work:
        return None
    a = dict(run.family.sizes_of(run.cell["config"])["full"])
    pct, _bound = opsbytes.roofline_pct(
        flops_of(work["causal_pairs"], a) / work["spans"],
        bytes_of(work["latent_rows_read"], a) / work["spans"],
        seconds / runs, run.peaks)
    return pct


def latent_bytes(rows, a):
    """Latent rows ``[c_kv | k_r]`` read once."""
    return opsbytes_dots3.latent_bytes(rows, a["kv_rank"] + a["rope"])


def absorbed_flops(pairs, a):
    """A decode row's absorbed softmax: the latent row is key (with the
    rope part) and value at once."""
    return opsbytes_dots3.attention_flops(
        pairs, a["heads"], a["kv_rank"] + a["rope"], a["kv_rank"])


def decompressed_flops(pairs, a):
    """A chunk's non-absorbed softmax over decompressed keys and values."""
    return opsbytes_dots3.attention_flops(
        pairs, a["heads"], a["nope"] + a["rope"], a["v"])
