"""Tests: FP16_Optimizer wrappers, MoE mappings/utils, runtime utils, nvtx,
mpu interop (analogs of reference tests/unit/runtime/half_precision/
test_fp16.py, moe utils coverage, utils)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from simple_model import SimpleModel, random_batch


# ------------------------------------------------------------------ #
# FP16_Optimizer
# ------------------------------------------------------------------ #
def _quadratic_setup(optimizer_cls):
    from deepspeed_tpu.runtime.optimizers import build_optimizer
    from deepspeed_tpu.runtime.config import OptimizerConfig
    inner = build_optimizer(OptimizerConfig(type="Adam",
                                            params={"lr": 1e-1}))
    params = {"w": jnp.asarray([2.0, -3.0, 1.0])}
    opt = optimizer_cls(inner, params=params, clip_grad=1.0)
    return opt, params


@pytest.mark.parametrize("cls_name", ["FP16_Optimizer", "FP16_UnfusedOptimizer"])
def test_fp16_optimizer_converges(cls_name):
    from deepspeed_tpu.runtime.fp16.fused_optimizer import FP16_Optimizer
    from deepspeed_tpu.runtime.fp16.unfused_optimizer import FP16_UnfusedOptimizer
    cls = {"FP16_Optimizer": FP16_Optimizer,
           "FP16_UnfusedOptimizer": FP16_UnfusedOptimizer}[cls_name]
    opt, params = _quadratic_setup(cls)
    loss_fn = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(50):
        masters = opt.fp32_groups_flat
        scaled_grads = jax.grad(lambda p: opt.scale_loss(loss_fn(p)))(masters)
        opt.backward(scaled_grads)
        overflow = opt.step()
        assert not overflow
    assert float(loss_fn(opt.fp32_groups_flat)) < 0.1


def test_fp16_optimizer_overflow_skips_and_rescales():
    from deepspeed_tpu.runtime.fp16.fused_optimizer import FP16_Optimizer
    opt, params = _quadratic_setup(FP16_Optimizer)
    before = np.asarray(jax.device_get(opt.fp32_groups_flat["w"]))
    scale0 = opt.cur_scale
    opt.backward({"w": jnp.asarray([jnp.inf, 0.0, 0.0])})
    overflow = opt.step()
    assert overflow
    # params untouched, scale not raised (hysteresis may defer the drop)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(opt.fp32_groups_flat["w"])), before)
    assert opt.cur_scale <= scale0
    # state dict round-trip
    sd = opt.state_dict()
    opt.load_state_dict(sd)
    assert opt.step_count == sd["step"]


# ------------------------------------------------------------------ #
# MoE mappings / utils
# ------------------------------------------------------------------ #
def test_moe_gather_drop_tokens_roundtrip(eight_devices):
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from deepspeed_tpu.moe.mappings import drop_tokens, gather_tokens
    mesh = Mesh(np.asarray(eight_devices).reshape(8), ("tp",))
    x = jnp.arange(32.0).reshape(8, 4)  # [tokens, dim] split over tp

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("tp"),),
                       out_specs=P("tp"), check_vma=False)
    def gd(xs):
        full = gather_tokens(xs, "tp", 0)       # every rank: all 32 rows
        return drop_tokens(full, "tp", 0)       # back to this rank's rows

    np.testing.assert_array_equal(np.asarray(gd(x)), np.asarray(x))

    # gradient flows: d/dx of sum(gather(x)) == ones (drop is gather's vjp)
    @functools.partial(shard_map, mesh=mesh, in_specs=(P("tp"),),
                       out_specs=P("tp"), check_vma=False)
    def g(xs):
        return jax.grad(lambda y: gather_tokens(y, "tp", 0).sum())(xs)

    np.testing.assert_array_equal(np.asarray(g(x)), np.ones((8, 4)))


def test_moe_param_split():
    from deepspeed_tpu.moe.utils import (
        has_moe_layers, is_moe_param,
        split_params_grads_into_shared_and_expert_params,
        split_params_into_different_moe_groups_for_optimizer)
    params = {"dense": {"kernel": jnp.ones((2, 2))},
              "experts": {"0": {"kernel": jnp.ones((2, 2)) * 2}}}
    assert has_moe_layers(params)
    assert is_moe_param("experts/0/kernel")
    assert not is_moe_param("dense/kernel")
    dense_mask, expert_mask = \
        split_params_into_different_moe_groups_for_optimizer(params)
    assert dense_mask["dense"]["kernel"] is True
    assert expert_mask["experts"]["0"]["kernel"] is True
    shared, expert = split_params_grads_into_shared_and_expert_params(params)
    assert float(shared["experts"]["0"]["kernel"].sum()) == 0.0
    assert float(expert["dense"]["kernel"].sum()) == 0.0
    assert float(expert["experts"]["0"]["kernel"].sum()) == 8.0


# ------------------------------------------------------------------ #
# runtime utils
# ------------------------------------------------------------------ #
def test_grad_norm_and_clip():
    from deepspeed_tpu.runtime.utils import (CheckOverflow, clip_grad_norm_,
                                             get_global_norm, get_grad_norm)
    grads = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.zeros(2)}
    assert float(get_grad_norm(grads)) == pytest.approx(5.0)
    clipped, norm = clip_grad_norm_(grads, max_norm=1.0)
    assert float(norm) == pytest.approx(5.0)
    assert float(get_grad_norm(clipped)) == pytest.approx(1.0, rel=1e-3)
    assert float(get_global_norm([3.0, 4.0])) == pytest.approx(5.0)
    assert not bool(CheckOverflow.has_overflow(grads))
    assert bool(CheckOverflow.has_overflow({"a": jnp.asarray([jnp.nan])}))


def test_partition_helpers():
    from deepspeed_tpu.runtime.utils import (PartitionedTensor,
                                             partition_balanced,
                                             partition_uniform)
    assert partition_uniform(10, 3) == [0, 4, 7, 10]
    bounds = partition_balanced([1, 1, 1, 10, 1, 1], 2)
    assert bounds[0] == 0 and bounds[-1] == 6
    assert bounds[1] in (3, 4)  # heavy item isolates
    t = jnp.arange(10.0).reshape(2, 5)
    pt = PartitionedTensor(t, num_parts=4)
    assert len(pt.parts) == 4
    np.testing.assert_array_equal(np.asarray(pt.full()), np.asarray(t))


def test_nvtx_and_memory():
    from deepspeed_tpu.runtime.utils import see_memory_usage
    from deepspeed_tpu.utils.nvtx import instrument_w_nvtx, range_pop, range_push

    @instrument_w_nvtx
    def f(x):
        return x + 1

    assert f(1) == 2
    range_push("region")
    range_pop()
    see_memory_usage("test", force=True)  # must not raise


def test_mpu_interop():
    class FakeMPU:
        def get_model_parallel_world_size(self):
            return 2

        def get_pipe_parallel_world_size(self):
            return 1

    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), mpu=FakeMPU(),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    assert engine.topology.get_model_parallel_world_size() == 2
    loss = engine(random_batch(batch_size=8))
    assert np.isfinite(float(jax.device_get(loss)))

# ------------------------------------------------------------------ #
# BF16_Optimizer
# ------------------------------------------------------------------ #
def test_bf16_optimizer_converges_and_shards():
    """BF16_Optimizer (reference ``runtime/bf16_optimizer.py:30``): unit
    scale, fp32 grad accumulation, masters sharded ZeRO-1-style over dp."""
    from deepspeed_tpu.runtime.bf16_optimizer import BF16_Optimizer
    from deepspeed_tpu.parallel.topology import (initialize_topology,
                                                 reset_topology)
    reset_topology()
    topo = initialize_topology(dp=8)
    try:
        opt, params = _quadratic_setup(BF16_Optimizer)
        loss_fn = lambda p: jnp.sum(p["w"].astype(jnp.float32) ** 2)
        for _ in range(50):
            grads = jax.grad(loss_fn)(opt.fp32_groups_flat)
            opt.backward(grads)
            assert opt.step() is False
        assert float(loss_fn(opt.fp32_groups_flat)) < 0.1
        assert opt.cur_scale == 1.0

        # masters sharded over dp when divisible (ZeRO-1 partitioning)
        big = {"w": jnp.zeros((16, 4))}
        opt2 = BF16_Optimizer(opt.optimizer, params=big)
        sh = opt2.fp32_groups_flat["w"].sharding
        assert not sh.is_fully_replicated, sh

        # GAS: two backward() calls accumulate
        opt3, _ = _quadratic_setup(BF16_Optimizer)
        g = {"w": jnp.asarray([1.0, 1.0, 1.0])}
        opt3.backward(g)
        opt3.backward(g)
        acc = np.asarray(opt3._accum_grads["w"])
        np.testing.assert_allclose(acc, [2.0, 2.0, 2.0])

        # state-dict round trip
        sd = opt3.state_dict()
        opt4, _ = _quadratic_setup(BF16_Optimizer)
        opt4.load_state_dict(sd)
        assert opt4.step_count == opt3.step_count
    finally:
        reset_topology()
