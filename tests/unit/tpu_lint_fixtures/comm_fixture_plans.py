"""Synthetic-break plans module for the ``ds_lint --comm`` prover tests.

Loaded via ``DSTPU_COMM_PLANS_MODULE`` (a .py path): one deliberately
broken plan whose batch enters the mesh program fully replicated while the
global batch scales with the mesh (weak scaling) — the per-chip all-reduce
volume therefore GROWS with mesh size, the exact replicated-tensor smell
the scaling prover must fail on, readably, with no ``allowed_growth``
escape hatch declared."""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.parallel.plans import PlanProgram
from jax import shard_map

MESH_POINTS = (1, 2, 4)


def replicated_batch_plan(world=4):
    mesh = Mesh(np.array(jax.devices()[:world]), ("tp",))

    def body(batch, w):   # tpu-lint: disable=TL010 -- fixture: the replication IS the synthetic break
        return jax.lax.psum(batch * w.sum(), "tp")

    fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=(P(), P(None, "tp")),
                           out_specs=P()))
    batch = jnp.ones((4 * world, 16), jnp.float32)   # weak scaling
    w = jnp.ones((16, 8), jnp.float32)
    return PlanProgram("fixture.replicated_batch", fn, (batch, w),
                       mesh={"tp": world}, reduction=False, world=world)


PLAN_BUILDERS = (replicated_batch_plan,)
