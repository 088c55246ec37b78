"""MoE-GPT training — Megatron-DeepSpeed MoE layout on the TPU trunk:
every 2nd block's MLP is a top-1-gated expert layer sharded over the ``ep``
mesh axis; expert-data-parallel gradient semantics come from the sharding
plan (reference ``deepspeed/moe`` + ``utils/groups.py``).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/train_moe_gpt.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

    ep = min(4, jax.device_count())
    cfg = TransformerConfig(
        vocab_size=512, hidden_size=64, num_layers=4, num_heads=4,
        max_seq_len=128, dtype="float32", use_flash_attention=False,
        scan_layers=False, moe_num_experts=2 * ep, moe_every=2,
        moe_top_k=1, moe_ep_size=ep, moe_capacity_factor=1.25)
    engine, *_ = deepspeed_tpu.initialize(
        model=Transformer(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
                "moe": {"ep_size": ep},
                "zero_optimization": {"stage": 1}})

    rng = np.random.default_rng(0)
    for step in range(10):
        batch = {"input_ids": rng.integers(
            0, 512, (2 * max(engine.topology.dp, 1), 128)).astype(np.int32)}
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        print(f"step {step}: loss {float(jax.device_get(loss)):.4f} "
              f"(incl. aux)")


if __name__ == "__main__":
    main()
