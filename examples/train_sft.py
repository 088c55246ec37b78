"""Step-1 SFT — the framework's headline workload (DeepSpeed-Chat step 1,
reference ``BASELINE.json``): supervised fine-tuning of an OPT-family model
with ZeRO sharding, bf16, and the fused train step.

Run on one chip:        python examples/train_sft.py
Run on a CPU dev mesh:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                        JAX_PLATFORMS=cpu \
                        python examples/train_sft.py --model tiny
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="opt-125m")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--micro_bs", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--zero", type=int, default=3)
    ap.add_argument("--ckpt_dir", default=None)
    args = ap.parse_args()

    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

    if args.model == "tiny":
        cfg = TransformerConfig(vocab_size=512, hidden_size=64, num_layers=2,
                                num_heads=4, max_seq_len=args.seq,
                                dtype="float32", use_flash_attention=False)
    else:
        cfg = opt_config(args.model, max_seq_len=args.seq, dtype="bfloat16")

    engine, optimizer, _, scheduler = deepspeed_tpu.initialize(
        model=Transformer(cfg),
        config={
            "train_micro_batch_size_per_gpu": args.micro_bs,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 9.65e-6, "weight_decay": 0.0}},
            "scheduler": {"type": "WarmupDecayLR",
                          "params": {"warmup_num_steps": 10,
                                     "total_num_steps": args.steps}},
            "bf16": {"enabled": args.model != "tiny"},
            "zero_optimization": {"stage": args.zero},
            "gradient_clipping": 1.0,
        })

    # stand-in for a tokenized SFT dataset: {"input_ids": [B, S]}
    rng = np.random.default_rng(0)
    for step in range(args.steps):
        batch = {"input_ids": rng.integers(
            0, cfg.vocab_size, (args.micro_bs * max(engine.topology.dp, 1),
                                args.seq)).astype(np.int32)}
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        if step % 5 == 0:
            print(f"step {step}: loss {float(jax.device_get(loss)):.4f}")

    if args.ckpt_dir:
        engine.save_checkpoint(args.ckpt_dir)
        print("checkpoint saved to", args.ckpt_dir)


if __name__ == "__main__":
    main()
