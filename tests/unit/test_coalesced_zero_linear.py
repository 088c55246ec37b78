"""Tests: coalesced collectives + ZeRO-3 linear parity shims."""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

_SM = lambda f, mesh, i, o: shard_map(f, mesh=mesh, in_specs=i,
                                      out_specs=o, check_vma=False)


def test_reduce_scatter_coalesced(eight_devices):
    from deepspeed_tpu.runtime.comm.coalesced_collectives import (
        reduce_scatter_coalesced)
    mesh = Mesh(np.asarray(eight_devices), ("dp",))
    t1 = jnp.arange(16.0)
    t2 = jnp.ones((3, 5))  # 15 elems → padded to 16

    def run(a, b):
        outs = reduce_scatter_coalesced([a, b], "dp")
        return outs[0], outs[1]

    f = _SM(run, mesh, (P(), P()), (P("dp"), P("dp")))
    s1, s2 = f(t1, t2)
    # every device held identical copies → psum_scatter yields 8× the shard
    np.testing.assert_allclose(np.asarray(s1).ravel()[:16],
                               8 * np.arange(16.0))
    got2 = np.asarray(s2).ravel()
    np.testing.assert_allclose(got2[:15], 8 * np.ones(15))
    np.testing.assert_allclose(got2[15:], 0)  # padding


def test_all_gather_coalesced(eight_devices):
    from deepspeed_tpu.runtime.comm.coalesced_collectives import (
        all_gather_coalesced)
    mesh = Mesh(np.asarray(eight_devices), ("dp",))
    shards = jnp.arange(8.0).reshape(8, 1)  # each rank holds one scalar shard

    def run(s):
        (full,) = all_gather_coalesced([s[0]], "dp")
        return full

    f = _SM(run, mesh, (P("dp"),), P())
    np.testing.assert_allclose(np.asarray(f(shards)), np.arange(8.0))


def test_zero3_linear_matches_torch_layout():
    from deepspeed_tpu.runtime.zero.linear import (LinearModuleForZeroStage3,
                                                   zero3_linear_wrap)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
    m = LinearModuleForZeroStage3(in_features=8, out_features=3)
    params = m.init(jax.random.key(0), x)
    y = m.apply(params, x)
    W = np.asarray(params["params"]["weight"])     # [out, in] torch layout
    b = np.asarray(params["params"]["bias"])
    np.testing.assert_allclose(np.asarray(y), x @ W.T + b, rtol=1e-5)
    y2 = zero3_linear_wrap(x, jnp.asarray(W), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=1e-6)
