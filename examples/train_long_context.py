"""Long-context training — the reference's sparse-attention/long-sequence
story (``docs/_tutorials/sparse-attention.md``; SURVEY §5 long-context)
rendered three ways on TPU:

* ``--attn flash``  — exact Pallas flash attention (O(S) memory);
* ``--attn bigbird`` (or fixed/longformer) — block-sparse attention via the
  sparsity-config zoo, dead blocks' DMAs skipped;
* ``--sp N``        — sequence parallelism: the sequence axis shards over
  the ``sp`` mesh axis (``ring`` KV rotation or ``ulysses`` all-to-all).

Run on a CPU dev mesh (ring attention over sp=8 at seq 2048):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu \
    python examples/train_long_context.py --sp 8 --seq 2048 --attn none
On the real chip (flash at seq 8192):
    python examples/train_long_context.py --seq 8192
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--attn", default="flash",
                    choices=["flash", "fixed", "bigbird", "longformer",
                             "none"])
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--sp_impl", default="ring",
                    choices=["ring", "ulysses"])
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

    sparse = None
    if args.attn in ("fixed", "bigbird", "longformer"):
        from deepspeed_tpu.ops.sparse_attention import (
            BigBirdSparsityConfig, BSLongformerSparsityConfig,
            FixedSparsityConfig)
        sparse = {"fixed": FixedSparsityConfig,
                  "bigbird": BigBirdSparsityConfig,
                  "longformer": BSLongformerSparsityConfig}[args.attn](
            num_heads=args.heads)

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=256, num_layers=4, num_heads=args.heads,
        max_seq_len=args.seq, dtype="bfloat16",
        use_flash_attention=args.attn == "flash",
        sparse_attention=sparse,
        sequence_parallel_impl=args.sp_impl,
        # long sequences: rematerialize blocks, chunk the vocab loss
        remat=True, remat_policy="dots_and_attn_saveable",
        loss_seq_chunks=16)
    engine, *_ = deepspeed_tpu.initialize(
        model=Transformer(cfg),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 1},
            "sequence_parallel": {"sp_size": args.sp},
        })
    print(f"attn={args.attn} seq={args.seq} sp={args.sp}({args.sp_impl}) "
          f"dp={engine.topology.dp}")

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, 512, (1, engine.topology.dp, args.seq)).astype(np.int32)}
    import time
    for step in range(args.steps):
        t0 = time.perf_counter()
        loss = engine.train_batch(batch=batch)
        loss = float(jax.device_get(loss))
        dt = time.perf_counter() - t0
        toks = engine.topology.dp * args.seq
        print(f"step {step}: loss {loss:.4f}  {toks/dt:,.0f} tok/s")


if __name__ == "__main__":
    main()
