"""1-bit optimizer + compressed-collective tests (analog of reference
``tests/unit/runtime/half_precision/onebit/test_onebit.py``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime.comm.compressed import (CompressedBackend,
                                                   compressed_allreduce,
                                                   pack_signs, unpack_signs)

from simple_model import SimpleModel, random_batch


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(100), jnp.float32)
    signs = unpack_signs(pack_signs(x), 100)
    np.testing.assert_array_equal(np.asarray(signs),
                                  np.where(np.asarray(x) >= 0, 1.0, -1.0))
    # wire volume really is 1 bit/elem (+padding to bytes)
    assert pack_signs(x).nbytes == 13


@pytest.mark.parametrize("opt_type", ["OneBitAdam", "ZeroOneAdam", "OneBitLamb"])
def test_onebit_optimizers_train(opt_type):
    """Every 1-bit family member must train SimpleModel to a lower loss,
    both in warmup and in the compressed regime (freeze_step=3)."""
    params = {"lr": 1e-2}
    if opt_type in ("OneBitAdam", "OneBitLamb"):
        params["freeze_step"] = 3
    else:
        params["var_freeze_step"] = 3
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": opt_type, "params": params}})
    losses = []
    for i in range(12):
        # fixed batch: the compressed-regime assertion needs a deterministic
        # decreasing trajectory, not fresh noise per step
        loss = engine(random_batch(batch_size=16, seed=0))
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert losses[-1] < losses[0], (opt_type, losses)


def test_zero_one_adam_refresh_schedule():
    """Variance refreshes must be geometrically spaced and keep firing
    forever (a naive per-step interval formula stops refreshing after a few
    multiples of var_update_scaler)."""
    from deepspeed_tpu.ops.adam.onebit_adam import ZeroOneAdam
    opt = ZeroOneAdam(var_update_scaler=4, var_freeze_step=10**6)
    steps = np.arange(1, 2000)
    hits = [int(s) for s in steps if bool(opt._is_refresh_step(jnp.float32(s)))]
    # fires in every segment: intervals 1,2,4,8,... with 4 refreshes each
    assert hits[:8] == [1, 2, 3, 4, 6, 8, 10, 12], hits[:10]
    # still refreshing late (the buggy formula goes silent after step ~64)
    assert any(h > 1000 for h in hits), hits[-5:]
    # spacing grows geometrically
    gaps = np.diff(hits)
    assert gaps[-1] > gaps[0]
    assert all(g in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024) for g in gaps)


def test_onebit_lamb_freezes_trust_ratio():
    from deepspeed_tpu.ops.lamb.onebit_lamb import OnebitLamb
    opt = OnebitLamb(lr=1e-2, freeze_step=2)
    params = {"w": jnp.ones((8, 8))}
    state = opt.init(params)
    # non-uniform gradient: a constant tensor compresses losslessly (sign ×
    # mean|.| is exact), which would leave no error feedback to observe
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)),
                          jnp.float32) * 0.1}
    for step in range(1, 6):
        params, state = opt.update(g, state, params, step=step)
        if step == 2:
            frozen = float(state.frozen_lamb_coeff["w"])
    # post-freeze the cached coefficient must not change
    assert float(state.frozen_lamb_coeff["w"]) == frozen
    # error feedback active post-freeze
    assert float(jnp.abs(state.error_feedback["w"]).sum()) > 0


def test_compressed_allreduce_approximates_mean(eight_devices):
    """Compressed allreduce must approximate the exact mean and the error
    feedback must tighten it over repeated rounds of the same signal."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(eight_devices), ("dp",))
    be = CompressedBackend(mesh, "dp")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    exact = np.asarray(x)  # every worker holds the same tensor → mean == x
    # error feedback guarantees the CUMULATIVE transmitted signal telescopes
    # to the cumulative true signal (Σ out = Σ x + e_0 − e_T): per-round
    # outputs may wobble, the running sum must track
    cum = np.zeros_like(exact)
    cum_errs = []
    for i in range(1, 7):
        out = be.allreduce("g", x)
        cum += np.asarray(out)
        cum_errs.append(float(np.linalg.norm(cum - i * exact)
                              / np.linalg.norm(i * exact)))
    assert cum_errs[-1] < cum_errs[0], cum_errs
    assert cum_errs[-1] < 0.5, cum_errs
    # buffers persist + update
    assert float(jnp.abs(be.worker_errors["g"]).sum()) > 0


def test_compressed_allreduce_padded_tail(eight_devices):
    """n not divisible by world×8: pad bits must not bias the last chunk
    (pads decode as +1 sign with no error feedback unless masked)."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(eight_devices), ("dp",))
    be = CompressedBackend(mesh, "dp")
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(1001), jnp.float32)
    exact = np.asarray(x)
    cum = np.zeros_like(exact)
    for i in range(1, 7):
        cum += np.asarray(be.allreduce("t", x))
    np.testing.assert_array_less(
        np.linalg.norm(cum - 6 * exact) / np.linalg.norm(6 * exact), 0.5)
    # tail elements specifically must track (they share the padded chunk)
    tail_err = np.abs(cum[-60:] / 6 - exact[-60:]).mean()
    head_err = np.abs(cum[:60] / 6 - exact[:60]).mean()
    assert tail_err < 3 * head_err + 0.2, (tail_err, head_err)
    # name reuse at a different size resets feedback instead of crashing
    out = be.allreduce("t", jnp.asarray(rng.standard_normal(257), jnp.float32))
    assert out.shape == (257,)


def test_compressed_allreduce_unbiased_over_workers(eight_devices):
    """With different per-worker tensors (sharded batch axis), the decoded
    mean must correlate strongly with the true mean."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map
    import functools
    mesh = Mesh(np.array(eight_devices), ("dp",))
    rng = np.random.default_rng(1)
    per_worker = rng.standard_normal((8, 512)).astype(np.float32)
    true_mean = per_worker.mean(0)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("dp"),),
                       out_specs=P(), check_vma=False)
    def run(xs):
        x = xs[0]
        out, _, _ = compressed_allreduce(
            x, jnp.zeros_like(x), jnp.zeros((512 // 8,), jnp.float32), "dp")
        return out

    out = np.asarray(run(jnp.asarray(per_worker)))
    corr = np.corrcoef(out, true_mean)[0, 1]
    assert corr > 0.5, corr
