"""Op-level micro-benchmarks — the analog of reference ``tests/perf/``
(``adam_test.py`` op-speed measurement) plus kernel throughput for the Pallas
hot paths.  Run as a CLI; prints one JSON line per op.

Timing protocol: every measurement closes with ``block_until_ready``, and
the op is iterated *inside* one compiled ``lax.fori_loop`` with a data
dependence between iterations — one dispatch for all iters, and XLA cannot
elide or overlap the chain.
"""

import argparse
import json
import time

import numpy as np


def _sync_scalar(x):
    import jax
    return jax.block_until_ready(x)


def _timeit(fn, args, iters):
    """Wall-clock per call with warm-up + sync (multi-dispatch — includes
    per-call dispatch latency; used where chaining is impossible)."""
    out = fn(*args)          # compile
    _sync_scalar(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync_scalar(out)
    return (time.perf_counter() - t0) / iters


def _timeit_chained(step, init, iters):
    """Time ``step`` (a pytree→same-shape-pytree function) applied ``iters``
    times inside one jitted ``fori_loop`` — one dispatch total."""
    import jax
    from jax import lax

    @jax.jit
    def loop(x0):
        return lax.fori_loop(0, iters, lambda i, x: step(x), x0)

    out = loop(init)         # compile + warm
    _sync_scalar(out)
    t0 = time.perf_counter()
    out = loop(init)
    _sync_scalar(out)
    return (time.perf_counter() - t0) / iters


def bench_adam(numel=50_000_000, iters=20):
    """Fused Adam update throughput (reference tests/perf/adam_test.py).
    The (params, state) chain is the natural data dependence."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdamW

    opt = FusedAdamW(lr=1e-4)
    params = {"w": jnp.ones((numel,), jnp.float32)}
    grads = {"w": jnp.full((numel,), 1e-3, jnp.float32)}
    state = opt.init(params)

    def step(carry):
        p, s = carry
        new_p, new_s = opt.update(grads, s, p, step=1)
        return (new_p, new_s)

    dt = _timeit_chained(step, (params, state), iters)
    # adam reads p,g,m,v and writes p,m,v: 7 fp32 streams
    gbps = 7 * numel * 4 / dt / 1e9
    return {"op": "fused_adamw", "numel": numel, "ms": round(dt * 1e3, 3),
            "effective_GB/s": round(gbps, 1)}


def bench_flash_attention(b=2, s=2048, h=32, d=64, iters=20, bwd=False):
    """Causal flash attention at the ``opt13b-sft-1chip`` cell's shape
    (micro-batch 2, 32 heads of 64, sequence 2048).  Beside the time: the
    (query, key) pairs the kernels EXECUTE over the pairs causal attention
    needs, from the tile plan the kernels walk — a kernel's rate is only
    as good as the work it is credited with."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer.flash_attention import (
        backward_plans, flash_attention, tile_plan)

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
               for _ in range(3))
    if bwd:
        grad_fn = jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2))

        def step(carry):
            qq, kk, vv = carry
            dq, dk, dv = grad_fn(qq, kk, vv)
            # feed grads back in as next inputs: full data dependence
            return (dq.astype(jnp.bfloat16), dk.astype(jnp.bfloat16),
                    dv.astype(jnp.bfloat16))
    else:
        def step(carry):
            qq, kk, vv = carry
            out = flash_attention(qq, kk, vv, causal=True)
            return (out, kk, vv)

    dt = _timeit_chained(step, (q, k, v), iters)
    needed = s * (s + 1) / 2
    executed = {}
    plans = [tile_plan("fwd", s, s, d, q.dtype, True)]
    if bwd:
        plans += backward_plans(s, s, d, q.dtype, True)
    for plan in plans:
        c = plan.counts()
        executed[plan.kernel] = round(
            c["tiles_run"] * c["tile_q"] * c["tile_k"] / needed, 3)
    # causal attention flops: 2 gemms, half the square
    flops = (2 * 2 * b * h * s * s * d) / 2 * (3.5 if bwd else 1)
    return {"op": f"flash_attention_{'bwd' if bwd else 'fwd'}",
            "shape": [b, s, h, d], "ms": round(dt * 1e3, 3),
            "TFLOP/s": round(flops / dt / 1e12, 2),
            "pairs_executed_over_needed": executed}


def bench_quantizer(numel=64 * 1024 * 1024, bits=8, iters=20):
    import jax.numpy as jnp
    from deepspeed_tpu.ops.quantizer.kernels import quantize, dequantize

    x = jnp.ones((numel,), jnp.bfloat16)
    groups = numel // 2048

    def step(t):
        return dequantize(*quantize(t, groups, num_bits=bits),
                          num_bits=bits).reshape(t.shape).astype(t.dtype)

    dt = _timeit_chained(step, x, iters)
    return {"op": f"quant_dequant_int{bits}", "numel": numel,
            "ms": round(dt * 1e3, 3),
            "GB/s": round(numel * 2 / dt / 1e9, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default="adam,flash_fwd,flash_bwd,quant")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--flash-shape", default="2,2048,32,64",
                    help="b,s,h,d of the flash ops (default: the "
                         "opt13b-sft-1chip cell's)")
    args = ap.parse_args()
    b, s, h, d = (int(x) for x in args.flash_shape.split(","))
    flash = dict(b=b, s=s, h=h, d=d, iters=args.iters)
    runners = {
        "adam": lambda: bench_adam(iters=args.iters),
        "flash_fwd": lambda: bench_flash_attention(**flash),
        "flash_bwd": lambda: bench_flash_attention(**flash, bwd=True),
        "quant": lambda: bench_quantizer(iters=args.iters),
    }
    for name in args.ops.split(","):
        try:
            print(json.dumps(runners[name.strip()]()))
        except Exception as e:          # keep sweeping (parity: ds_bench)
            print(json.dumps({"op": name, "error": str(e)[:200]}))


if __name__ == "__main__":
    main()
