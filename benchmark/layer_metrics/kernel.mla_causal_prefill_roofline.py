"""A dense latent layer's chunk form's share of its roofline (scope
``attn.mla_dense_chunk``, whatever kernel runs under it).

Needed, a dispatch: softmax attention over the CAUSAL (query, key) pairs in
the non-absorbed form (``opsbytes_longcat.decompressed_flops`` of the chunk
spans' ``causal_pairs``, 64 heads of 192 + 128) against 197 TFLOP/s; the
latent rows' bytes are counted too and never bind.  Time: the scope's
device seconds over the chunk program's executions.  The up-projection of
the live key blocks (``attn.mla_decompress``) runs under the scope and is
not in the count, and the flash kernel computes whole tiles along the
diagonal: both are the distance to 100 it starts with."""
from benchmark import opsbytes_dots3, opsbytes_longcat as ob


def read(run):
    return ob.dispatch_roofline_pct(
        run, ob.DENSE_CHUNK, "chunk_step", opsbytes_dots3.CHUNK,
        ob.decompressed_flops, ob.latent_bytes)
