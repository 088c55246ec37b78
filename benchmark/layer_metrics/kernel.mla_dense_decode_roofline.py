"""A dense latent layer's decode form's share of its roofline (scope
``attn.mla_dense_decode``, whatever kernel runs under it).

Needed, a dispatch: every live lane's live latent rows read ONCE a layer
and step (``opsbytes_longcat.latent_bytes`` of the decode spans'
``latent_rows_read``, 576 values a row) against 819 GB/s, or the absorbed
softmax over the causal pairs (64 heads of 576 + 512) against 197 TFLOP/s,
whichever binds — memory.  Time: the scope's device seconds over the
decode-block program's executions.  The kernel fetches rows padded to 640
lanes in whole 512-key blocks, which is the distance to 100 it starts
with."""
from benchmark import opsbytes_dots3, opsbytes_longcat as ob


def read(run):
    return ob.dispatch_roofline_pct(
        run, ob.DENSE_DECODE, "decode_block", opsbytes_dots3.DECODE,
        ob.absorbed_flops, ob.latent_bytes)
