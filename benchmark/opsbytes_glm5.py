"""The counters the self-drafting programs' spans carry, and where the
glm5 family's per-layer readers find them — kept with the benchmark so
that no PR that claims a gain can change the count.  The PR that brought
the family wrote no kernel, so there are no operations or bytes to count
here for the chunk: its attention, indexer and expert kernels are the dots3
family's (``opsbytes_dots3.py``), read through that family's metrics.  The
WINDOW's two kernels are this family's — ``attn.dsa_lane_index`` and
``attn.mla_lane_decode``, a lane's index keys and latent rows read through
its table once for all its rows and heads —, and what a call of each needs
is :func:`lane_work`'s, counted from the same spans.

``dstpu.sched.dispatch.spec_block`` (``docs/observability.md``): one span a
dispatch of ``decode_block`` verify windows, with ``windows`` (live lane x
window), ``proposed`` (drafts offered: one a window), ``accepted`` (drafts
the target reproduced AND committed) and ``rows_rejected`` (verify rows
whose token was not committed) — of the windows whose results the
scheduler READ since its last dispatch: the host's mirror lags the device
by one event, so a span reports its predecessors' commits — beside the
model's attention work for two rows a lane a window (``dsa_keys_scored``,
``dsa_keys_kept``, ``latent_rows_read``).  A parent commit from before
them has none: every function here then returns None and the metric is
left out."""

from benchmark import opsbytes_dots3

SPEC = "dstpu.sched.dispatch.spec_block"
# the programs that run the multi-token-prediction module: the window
# block, and the prefill chunk that fills the module's rows
PROGRAMS = ("jit_spec_block", "jit_chunk_step")
KEYS = ("windows", "proposed", "accepted", "rows_rejected")


def window_sums():
    """``{key: sum over the slice's spec_block spans, "spans": n}``, None
    where no span carries them or none has read a window yet."""
    sums = opsbytes_dots3.span_sums(SPEC, KEYS)
    return sums if sums and sums["windows"] else None


LANE_KEYS = ("kv_pages", "dsa_keys_scored", "dsa_keys_kept")


def lane_work(run):
    """What ONE call of a window kernel needs — one layer, one window,
    every live lane — as the slice's ``spec_block`` spans count it:
    ``rows``, the lanes' live rows once a LANE (``kv_pages`` counts a
    lane's pages once for each of its two rows: halved, times the page);
    ``scored`` and ``kept``, the (query, key) pairs the indexer scores
    and the softmax runs over (the spans' sums are over the pools' layers,
    main and module).  A span covers ``decode_block`` windows of every
    layer; kernel events and spans are cut by the slice at different
    dispatches, so both sides of a roofline are taken per call.  None
    where no span carries the counters."""
    work = opsbytes_dots3.span_sums(SPEC, LANE_KEYS)
    if not work:
        return None
    serving = run.cell["system"]["serving"]
    layers = len(run.family.sizes_of(run.cell["config"])["kinds"])
    windows = work["spans"] * serving["decode_block"]
    return {"rows": work["kv_pages"] * serving["page_size"] / 2 / windows,
            "scored": work["dsa_keys_scored"] / layers / windows,
            "kept": work["dsa_keys_kept"] / layers / windows}
