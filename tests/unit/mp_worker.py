"""Worker for the real multi-process bootstrap test (launched through
``launcher/runner.py``; see ``test_multiprocess_bootstrap.py``).

Each OS process brings ``WORKER_LOCAL_DEVICES`` virtual CPU devices; with a
``DSTPU_COORDINATOR_ADDRESS`` in the environment (injected per-host by the
launcher), ``deepspeed_tpu.init_distributed`` rendezvouses the processes via
``jax.distributed.initialize`` into one global mesh — the analog of the
reference's multi-process test harness (``tests/unit/common.py:89-186``)
and its RANK/MASTER_ADDR bootstrap (``launcher/launch.py:216``).
"""

import os
import sys

n_local = int(os.environ.get("WORKER_LOCAL_DEVICES", "4"))
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + f" --xla_force_host_platform_device_count={n_local}").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.environ["DSTPU_REPO_ROOT"])

import numpy as np
import jax

import deepspeed_tpu

deepspeed_tpu.init_distributed()

import jax.numpy as jnp  # noqa: E402  (after distributed init)
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

rank, world = jax.process_index(), jax.process_count()
print(f"[worker] process {rank}/{world}, local devices "
      f"{jax.local_device_count()}, global {jax.device_count()}", flush=True)

variant = os.environ.get("WORKER_VARIANT", "zero2")
rng = np.random.default_rng(0)
if variant == "pp":
    # pipeline over the OUTERMOST mesh axis: with 2 processes the pp
    # ppermutes cross the process boundary — the DCN-tier exchange of a
    # real multi-host pipeline (reference 3D topology maps pp to the
    # inter-node axis, runtime/pipe/topology.py)
    from deepspeed_tpu.models.pipeline_transformer import transformer_pipe
    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=4,
                            num_heads=4, max_seq_len=32,
                            use_flash_attention=False, dtype="float32",
                            scan_layers=False, remat=False)
    engine, *_ = deepspeed_tpu.initialize(
        model=transformer_pipe(cfg),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 4,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "tensor_parallel": {"tp_size": 2},
            "pipeline": {"stages": 2, "schedule": "1f1b"},
            "seed": 0,
        })
    # microbatch dim covers micro_bs(2) x dp replicas, like the zero2 path
    batch = {"input_ids": rng.integers(
        0, 64, (4, 2 * engine.topology.edp, 16)).astype(np.int32)}
else:
    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, max_seq_len=32,
                            use_flash_attention=False, dtype="float32",
                            scan_layers=False, remat=False)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "seed": 0,
    }
    if variant in ("sp", "ulysses"):
        # sequence parallelism over the FULL device set (sp=8, dp=1): edp
        # is outer to sp in the mesh axis order, so only a full-width sp
        # axis actually spans both processes' devices.  "sp" = ring
        # attention (KV-rotation ppermutes cross the process boundary);
        # "ulysses" = all-to-all head scatter/gather crossing it (the
        # DeepSpeed-Ulysses exchange at DCN tier)
        import dataclasses
        impl = "ring" if variant == "sp" else "ulysses"
        # ulysses scatters heads over sp: needs num_heads % sp == 0
        heads = 4 if variant == "sp" else 8
        cfg = dataclasses.replace(cfg, sequence_parallel_impl=impl,
                                  num_heads=heads)
        config["sequence_parallel"] = {"sp_size": 8}
    elif variant == "moe":
        # expert parallelism over the FULL device set (ep=8, edp=1): the
        # MoE dispatch/combine all_to_alls cross the process boundary —
        # the reference's multi-node expert placement
        # (moe/sharded_moe.py all_to_all over the expert group)
        import dataclasses
        cfg = dataclasses.replace(cfg, scan_layers=False,
                                  moe_num_experts=8, moe_ep_size=8,
                                  moe_every=2, moe_capacity_factor=2.0)
        config["moe"] = {"ep_size": 8}
    engine, *_ = deepspeed_tpu.initialize(
        model=Transformer(cfg),
        config=config)
    # every process supplies the same global batch (single-controller-per-
    # host: the engine shards it over the global mesh)
    batch = {"input_ids": rng.integers(
        0, 64, (1, 2 * engine.topology.dp, 16)).astype(np.int32)}

# cross-world-size checkpoint flow (the reference's DistributedFixture
# pattern, tests/unit/common.py:215: produce at one world size, consume at
# another): WORKER_LOAD_DIR resumes before stepping, WORKER_SAVE_DIR
# checkpoints after the first two steps
load_dir = os.environ.get("WORKER_LOAD_DIR")
if load_dir:
    engine.load_checkpoint(load_dir)
    print(f"[worker] resumed at global_steps={engine.global_steps}",
          flush=True)

losses = []
for _ in range(2):
    loss = engine.train_batch(batch=batch)
    losses.append(float(jax.device_get(loss)))

save_dir = os.environ.get("WORKER_SAVE_DIR")
if save_dir:
    engine.save_checkpoint(save_dir)
    loss = engine.train_batch(batch=batch)   # one post-save step
    losses.append(float(jax.device_get(loss)))
print(f"[worker] rank {rank} losses: {losses}", flush=True)

out = os.environ.get("WORKER_OUT")
if out:
    with open(f"{out}.rank{rank}", "w") as f:
        f.write(" ".join(repr(l) for l in losses))
