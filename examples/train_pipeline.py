"""Pipeline-parallel training — the reference pipeline tutorial's workflow
(``docs/_tutorials/pipeline.md``: PipelineModule + train_batch) on the SPMD
pipeline, composed 3D (pp × tp × dp) with the interleaved 1F1B schedule.

Run on a CPU dev mesh (pp=2 × tp=2 × dp=2):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/train_pipeline.py
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--micro_batches", type=int, default=4,
                    help="gradient_accumulation_steps = microbatches in flight")
    ap.add_argument("--schedule", default="1f1b",
                    choices=["fill_drain", "1f1b"],
                    help="fill_drain: O(M) stash; 1f1b: O(P) stash at the "
                         "same (P-1)/(M+P-1) bubble")
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.pipeline_transformer import transformer_pipe
    from deepspeed_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=512, hidden_size=64, num_layers=4,
                            num_heads=4, max_seq_len=64, dtype="float32",
                            use_flash_attention=False, scan_layers=False,
                            remat=False)
    # transformer_pipe splits the model into LayerSpecs: embedding (pre),
    # the uniform block trunk (stacked over pp), final norm + head (post)
    engine, *_ = deepspeed_tpu.initialize(
        model=transformer_pipe(cfg),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": args.micro_batches,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "tensor_parallel": {"tp_size": args.tp},
            "pipeline": {"stages": args.stages, "schedule": args.schedule},
        })
    print(f"mesh: pp={engine.topology.pp} tp={engine.topology.tp} "
          f"dp={engine.topology.dp}, schedule={args.schedule}")

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, 512, (args.micro_batches, 2 * engine.topology.dp, 64))
        .astype(np.int32)}
    for step in range(args.steps):
        # train_batch is the unit of work — forward/backward/step are
        # forbidden on the pipeline engine, exactly like the reference
        loss = engine.train_batch(batch=batch)
        print(f"step {step}: loss {float(jax.device_get(loss)):.4f}")


if __name__ == "__main__":
    main()
