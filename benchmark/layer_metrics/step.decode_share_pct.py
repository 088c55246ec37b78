"""Share of the traced slice that the device spends in decode blocks: the
summed device durations of the ``decode_block`` program's executions over
the slice.  In a cell of long prompts and short answers the rest is
prefill chunks; less ``kernel.moe_gmm_share_pct`` it bounds what a decode
step's attention (index scores, ``lax.top_k``, the gather of the kept rows
and ``attn.mla_sparse_decode`` — XLA's, with no kernel name to find them
by) and its projections take."""


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    d = run.trace.module_durations("decode_block")
    return 100.0 * sum(d) / run.trace.window_s if d else None
