"""Share of the traced slice that the device spends in the latent-attention
kernels, found by name (``attn.mla*``: ``attn.mla_chunk_prefill``, the
chunk's flash attention under its kept-set mask over decompressed keys, and
``attn.mla_window``, the window layers').  A decode step's attention over
its kept rows is XLA's (scope ``attn.mla_sparse_decode``) and is not
counted: ``breakdown.device_ops`` shows it."""
from benchmark import spans


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.mla")
    return 100.0 * seconds / run.trace.window_s if calls else None
