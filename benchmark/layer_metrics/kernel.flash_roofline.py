"""The flash-attention kernels' share of their roofline in training.

Operations (``opsbytes.flash_fwd_bwd_flops``): causal forward + backward of
every layer of every train step in the traced slice, as the algorithm
requires them — what remat recomputes is in the time and not in the count.
Time: the summed device time of the Mosaic kernels' events (forward,
recomputed forward, dq, dkv) inside ``train_step`` executions on device 0,
which under ZeRO-3 holds one chip's rows.  The train step has no other
Mosaic kernel, and the instruction is named after whatever encloses the
``pallas_call`` (``attn`` on one chip, ``shard_map`` on a mesh), so every
``tpu_custom_call`` inside a train step counts.  The bound is compute
(197 TFLOP/s): at seq 2048 the kernel does hundreds of operations per byte."""
from benchmark import opsbytes, trace


def read(run):
    if not run.trace:
        return None
    seconds, calls = run.trace.op_seconds(trace.is_pallas,
                                          module="train_step")
    steps = len(run.trace.module_intervals("train_step"))
    if not calls or not steps:
        return None
    z, obs = run.observed["sizes"], run.observed
    rows_here = obs["rows"] // obs["chips"]
    flops = steps * z["layers"] * opsbytes.flash_fwd_bwd_flops(
        rows_here, z["heads"], obs["seq_len"], z["d"])
    pct, _bound = opsbytes.roofline_pct(flops, 0, seconds, run.peaks)
    return pct
