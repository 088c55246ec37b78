"""Canonical tiny sharding-plan builders for the static collective-schedule
gate (``tools/lint/contract.py``) and the mesh-scaling prover
(``tools/lint/comm_contract.py``).

Each builder constructs the SAME plan family the MULTICHIP dry-run exercises
(``__graft_entry__._run_dryrun_phases``: ZeRO-3 + tp + sp, MoE expert
parallelism, 1F1B pipeline x tp, MiCS hierarchical ZeRO) at toy sizes on the
8-virtual-device CPU mesh, and returns the jitted fused train step plus
concrete args — so the contract analyzer can compile it once and COUNT the
collective ops XLA actually scheduled.  Locking those counts in
``PROGRAMS.lock`` turns the dry-run's re-measured collective totals into a
static, diffable artifact: a sharding-plan change that silently adds an
all-gather (or drops the Ulysses all-to-all) fails the tier-1 gate with a
per-plan diff instead of surfacing as a multichip perf cliff.

Every builder takes ``world`` (default 8, the full tier-1 mesh) and scales
its plan DOWN through a fixed per-plan axis allocation (``MESH_POINTS`` =
{1, 2, 4, 8}) so the comm-cost analyzer can compile the same plan family at
every mesh size and lock a bytes-per-chip scaling table: a collective whose
per-chip volume GROWS with mesh size is the classic replicated-tensor smell
and fails the prover.  The ``world=8`` allocation is bit-identical to the
pre-scaling builders (no explicit topology is passed), so the locked
schedules never move.  Deliberately replicated traffic that must grow is
declared per-plan in ``allowed_growth`` with a reviewable reason.

Builders are self-contained and deterministic (fixed seeds, fixed shapes);
``world=8`` requires ``jax.device_count() >= 8`` (the tier-1 harness forces
8 virtual CPU devices; the ``ds_lint --contracts`` / ``--comm`` CLIs do the
same).
"""

import dataclasses
from typing import Any, Callable, Dict, Tuple

import numpy as np

# Mesh sizes the scaling prover compiles every plan at.  The top point is
# the canonical full-mesh plan whose schedule is locked in PROGRAMS.lock.
MESH_POINTS = (1, 2, 4, 8)


@dataclasses.dataclass
class PlanProgram:
    """One sharding plan's fused step, ready to lower/compile.

    ``expect`` names the collectives the plan MUST schedule (sanity
    invariants, checked on top of the exact locked counts): e.g. ZeRO-3
    must all-gather params, a pipeline must collective-permute at stage
    boundaries.  ``reduction`` plans additionally require at least one of
    all-reduce / reduce-scatter (XLA picks per shape).  ``world`` is the
    number of mesh devices the plan was built for; ``allowed_growth``
    maps a collective op to the REASON its per-chip byte volume may grow
    with mesh size (anything not listed fails the scaling prover when it
    grows — the replicated-tensor smell)."""
    name: str
    fn: Callable
    args: Tuple[Any, ...]
    mesh: Dict[str, int]
    expect: Tuple[str, ...] = ()
    reduction: bool = True
    world: int = 8
    allowed_growth: Dict[str, str] = dataclasses.field(default_factory=dict)


def _tiny_cfg(**over):
    from deepspeed_tpu.models.transformer import TransformerConfig
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                max_seq_len=32, dtype="float32", use_flash_attention=False,
                remat=False)
    base.update(over)
    return TransformerConfig(**base)


def _fused_step_args(engine, batch):
    """(fused_step, args) for a lazily-initialized DeepSpeedEngine —
    the exact per-step program ``train_batch`` dispatches."""
    import jax
    import jax.numpy as jnp
    fused = engine._get_fused_step()
    args = (engine._params, engine._opt_state, engine._scaler_state,
            jnp.asarray(1e-3, jnp.float32), jnp.asarray(1, jnp.int32),
            engine._rng, jax.tree.map(jnp.asarray, batch))
    return fused, args


def _scaled_topology(world, **axes):
    """Explicit topology over the first ``world`` devices — only for the
    scaled-down mesh points; ``world=8`` builders pass ``topology=None``
    so the canonical locked plans keep the exact pre-scaling build path."""
    import jax
    from deepspeed_tpu.parallel.topology import ParallelTopology
    if world >= 8:
        return None
    return ParallelTopology(devices=jax.devices()[:world], **axes)


def _check_world(world):
    if world not in MESH_POINTS:
        raise ValueError(f"world={world} not a mesh point {MESH_POINTS}")


def zero3_tp_sp(world=8):
    """ZeRO-3 param sharding + Megatron tp=2 + Ulysses sp=2 over dp=2:
    param all-gathers, grad reduction, and the sp head/seq all-to-all.

    Scaling allocation (axis added per doubling, innermost first):
    1 -> dp=1; 2 -> dp=2 (pure ZeRO-3); 4 -> dp=2 x tp=2;
    8 -> dp=2 x tp=2 x sp=2 (the canonical locked plan)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer
    _check_world(world)
    dp, tp, sp = {1: (1, 1, 1), 2: (2, 1, 1),
                  4: (2, 2, 1), 8: (2, 2, 2)}[world]
    rng = np.random.default_rng(0)
    engine, *_ = deepspeed_tpu.initialize(
        model=Transformer(_tiny_cfg(max_seq_len=64)),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3},
                "gradient_clipping": 1.0,
                "tensor_parallel": {"tp_size": tp},
                "sequence_parallel": {"sp_size": sp}},
        topology=_scaled_topology(world, dp=dp, tp=tp, sp=sp))
    batch = {"input_ids": rng.integers(0, 64, (2, dp, 64)).astype(np.int32)}
    micro = {"input_ids": batch["input_ids"][0]}
    engine._lazy_init((micro,), {})
    fn, args = _fused_step_args(engine, batch)
    return PlanProgram(
        "parallel.zero3_tp_sp", fn, args,
        mesh=dict(engine.mesh.shape),
        expect=("all-gather", "all-to-all") if world == 8 else (),
        reduction=world > 1, world=world,
        allowed_growth={
            "all-gather": "the Ulysses sp axis exists only at mesh 8: "
                          "sequence-parallel activation regathers are "
                          "added traffic from the new axis, not lost "
                          "param sharding (per-chip param gathers fall "
                          "2->4)",
            "all-to-all": "the Ulysses head<->seq exchange is batch-"
                          "proportional and the toy global batch grows "
                          "with dp",
            "collective-permute": "axis-boundary reshard permutes track "
                                  "the tp/sp axes added at meshes 4 and "
                                  "8",
        })


def moe_ep(world=8):
    """Expert parallelism: experts sharded over ep=2, GShard
    dispatch/combine einsums, expert-data-parallel gradient semantics
    (ZeRO-2).  The dispatch is the einsum formulation
    (``moe/sharded_moe.py``), so GSPMD picks the collective: at this toy
    config XLA lowers it through all-gathers rather than an explicit
    all-to-all — the locked counts pin whichever schedule it chose, which
    is exactly what the gate is for (a strategy flip on a jax/XLA bump
    shows up as a readable diff, not a multichip surprise).

    Scaling allocation: 1 -> ep=1, dp=1; 2 -> ep=2, dp=2;
    4 -> ep=2, dp=4; 8 -> ep=2, dp=8 (canonical)."""
    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    import deepspeed_tpu
    from deepspeed_tpu.moe.layer import MoE
    _check_world(world)
    ep, dp = {1: (1, 1), 2: (2, 2), 4: (2, 4), 8: (2, 8)}[world]

    class MoELM(nn.Module):
        @nn.compact
        def __call__(self, batch):
            ids = batch["input_ids"]
            h = nn.Embed(64, 32, param_dtype=jnp.float32)(ids)
            y, aux, _ = MoE(hidden_size=32, num_experts=4, ep_size=ep,
                            k=1, capacity_factor=2.0, dtype=jnp.float32,
                            name="moe")(h)
            h = h + y
            logits = nn.Dense(64)(h)
            tgt = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
            ce = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits)
                                   * jax.nn.one_hot(tgt, 64), -1))
            return ce + 0.01 * aux

    rng = np.random.default_rng(1)
    engine, *_ = deepspeed_tpu.initialize(
        model=MoELM(),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "moe": {"ep_size": ep},
                "zero_optimization": {"stage": 2}},
        topology=_scaled_topology(world, dp=dp, ep=ep))
    batch = {"input_ids": rng.integers(0, 64, (1, dp, 16)).astype(np.int32)}
    micro = {"input_ids": batch["input_ids"][0]}
    engine._lazy_init((micro,), {})
    fn, args = _fused_step_args(engine, batch)
    return PlanProgram(
        "parallel.moe_ep", fn, args,
        mesh=dict(engine.mesh.shape),
        reduction=world > 1, world=world,
        allowed_growth={
            "all-reduce": "the toy global batch grows with dp, so batch-"
                          "proportional activation/aux-loss reductions "
                          "grow with it; per-chip dense-grad reduction "
                          "is flat",
            "all-gather": "the GShard dispatch gathers tokens over the "
                          "edp group and the toy token count grows with "
                          "dp",
        })


def pipeline_1f1b(world=8):
    """pp=2 x tp=2 interleaved 1F1B: stage-boundary activations ride
    collective-permute; tp adds Megatron all-reduces.

    Scaling allocation: 1 -> pp=1 (degenerate single-stage pipe);
    2 -> pp=2; 4 -> pp=2 x tp=2; 8 -> pp=2 x tp=2 x dp=2 (canonical)."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.pipeline_transformer import transformer_pipe
    _check_world(world)
    pp, tp, dp = {1: (1, 1, 1), 2: (2, 1, 1),
                  4: (2, 2, 1), 8: (2, 2, 2)}[world]
    rng = np.random.default_rng(2)
    pipe_module = transformer_pipe(_tiny_cfg(
        num_layers=4, scan_layers=False, pre_layer_norm=False,
        embed_proj_dim=32, tie_word_embeddings=True))
    engine, *_ = deepspeed_tpu.initialize(
        model=pipe_module,
        config={"train_micro_batch_size_per_gpu": 2,
                # M=4 > P=2 so the interleaved schedule's steady state
                # genuinely executes (same contract as the dry-run)
                "gradient_accumulation_steps": 4,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "tensor_parallel": {"tp_size": tp},
                "pipeline": {"stages": pp, "schedule": "1f1b"}},
        topology=_scaled_topology(world, dp=dp, tp=tp, pp=pp))
    batch = jax.tree.map(
        jnp.asarray,
        {"input_ids": rng.integers(0, 64, (4, 2, 32)).astype(np.int32)})
    engine._lazy_init_pipe(batch)
    fused = engine._get_fused_step()
    args = (engine._params, engine._opt_state, engine._scaler_state,
            jnp.asarray(1e-4, jnp.float32), jnp.asarray(1, jnp.int32),
            engine._rng, batch)
    return PlanProgram(
        "parallel.pipeline_1f1b", fused, args,
        mesh=dict(engine.mesh.shape),
        expect=("collective-permute",) if world == 8 else (),
        reduction=world > 1, world=world,
        allowed_growth={
            "all-gather": "Megatron tp=2 param/activation gathers "
                          "appear with the tp axis at mesh 4; the "
                          "per-chip trajectory is flat from there "
                          "(4 -> 8 unchanged)",
            # the three below appeared with the jaxlib 0.9.0 SPMD
            # partitioner (the 0.4.37 lock had a flat trajectory); the
            # parent tree schedules the same ops under the installed JAX
            "all-reduce": "Megatron tp=2 activation reductions appear "
                          "with the tp axis at mesh 4 (jaxlib 0.9.0 "
                          "partitioner; flat 4 -> 8)",
            "all-to-all": "one tp-axis reshard the jaxlib 0.9.0 "
                          "partitioner emits at mesh 4 (2KB/chip, flat "
                          "4 -> 8)",
            "collective-permute": "UNEXPLAINED: with the dp axis at mesh "
                                  "8 the jaxlib 0.9.0 partitioner adds "
                                  "reshard permutes (8KB -> 144KB/chip) "
                                  "the 0.4.37 one did not; CPU toy plan, "
                                  "never run on chips — PERF.md open "
                                  "question",
        })


def mics(world=8):
    """MiCS hierarchical ZeRO-3 + tp=2: params shard within edp=2 groups
    (ICI-local all-gather) and grads reduce across mdp x edp.

    Scaling allocation: 1 -> dp=1 (plain ZeRO-3, no groups);
    2 -> dp=2, shard group 2; 4 -> dp=4, two groups of 2;
    8 -> dp=4 x tp=2, two groups of 2 (canonical)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer
    _check_world(world)
    dp, tp, mics_size = {1: (1, 1, 0), 2: (2, 1, 2),
                         4: (4, 1, 2), 8: (4, 2, 2)}[world]
    mdp = (dp // mics_size) if mics_size else 1
    rng = np.random.default_rng(3)
    zero_cfg = {"stage": 3}
    if mics_size:
        zero_cfg["mics_shard_size"] = mics_size
    engine, *_ = deepspeed_tpu.initialize(
        model=Transformer(_tiny_cfg()),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "tensor_parallel": {"tp_size": tp},
                "zero_optimization": zero_cfg},
        topology=_scaled_topology(world, dp=dp, tp=tp, mdp=mdp))
    dp_world = engine.topology.mdp * engine.topology.edp
    batch = {"input_ids": rng.integers(0, 64, (1, dp_world, 32))
             .astype(np.int32)}
    micro = {"input_ids": batch["input_ids"][0]}
    engine._lazy_init((micro,), {})
    fn, args = _fused_step_args(engine, batch)
    return PlanProgram(
        "parallel.mics", fn, args,
        mesh=dict(engine.mesh.shape),
        expect=("all-gather",) if world == 8 else (),
        reduction=world > 1, world=world,
        allowed_growth={
            "all-reduce": "cross-group (mdp) grad reduction appears at "
                          "mesh 4 on top of the batch-proportional toy "
                          "reductions",
            "all-gather": "the mdp hierarchy at mesh 4 adds cross-group "
                          "param propagation to the ICI-local gathers",
            "collective-permute": "group-boundary reshards track the "
                                  "mdp/tp axes added at meshes 4 and 8",
            "all-to-all": "the tp axis exists only at mesh 8: XLA "
                          "lowers its boundary reshards through "
                          "all-to-alls (new-axis traffic, same ops as "
                          "zero3_tp_sp at tp introduction)",
        })


PLAN_BUILDERS = (zero3_tp_sp, moe_ep, pipeline_1f1b, mics)
