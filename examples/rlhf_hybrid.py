"""RLHF actor loop sketch with the Hybrid Engine (reference
``runtime/hybrid_engine.py:32`` — DeepSpeed-Chat step 3): the SAME weights
serve fast batched generation (rollout) and ZeRO-sharded training (update),
with no reallocation between the two.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/rlhf_hybrid.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=64, dtype="float32",
                            use_flash_attention=False)
    engine, *_ = deepspeed_tpu.initialize(
        model=Transformer(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-5}},
                "zero_optimization": {"stage": 3},
                "hybrid_engine": {"enabled": True}})

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 256, (2, 8)).astype(np.int32)
    for it in range(3):
        # rollout: batched KV-cache generation from the live training weights
        seqs = np.asarray(engine.generate(prompts, max_new_tokens=8))
        # reward + PPO loss stand-in: SFT loss on the sampled continuations
        loss = engine({"input_ids": seqs.astype(np.int32)})
        engine.backward(loss)
        engine.step()
        print(f"iter {it}: rollout {seqs.shape} loss "
              f"{float(jax.device_get(loss)):.4f}")


if __name__ == "__main__":
    main()
