#!/usr/bin/env python3
"""Records ``small_trace.xplane.pb``: four executions of one small jitted
program on one TPU chip, 20 ms of host sleep between them, traced with the
Python tracer off so that the file stays small.  Run on the chip:

    python3 benchmark/testdata/record_small_trace.py chiprun_out/small

``tests/benchmark/test_benchmark_trace.py`` holds ``trace.py``'s reader and
reductions to what this file is known to contain."""

import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark import trace  # noqa: E402


@jax.jit
def small_step(x):
    for _ in range(8):
        x = jnp.tanh(x @ x) * 0.5
    return x


def main(out_dir):
    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU")
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    small_step(x).block_until_ready()
    tmp = os.path.join(out_dir, "raw")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(4):
        small_step(x).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    dst = os.path.join(out_dir, "small_trace.xplane.pb")
    shutil.copy(trace.find_xplane(tmp), dst)
    shutil.rmtree(tmp)
    print(os.path.getsize(dst), "bytes")
    trace.summarize(dst)


if __name__ == "__main__":
    main(sys.argv[1])
