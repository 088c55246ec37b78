"""Kernel-injected inference + greedy/sampled generation from an HF
checkpoint (reference ``deepspeed.init_inference`` + DS-kernel generate).

    python examples/generate.py --model facebook/opt-125m --tp 1
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="facebook/opt-125m")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--prompt", default="DeepSpeed on TPU is")
    ap.add_argument("--max_new_tokens", type=int, default=32)
    args = ap.parse_args()

    import numpy as np
    import deepspeed_tpu
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(args.model)
    engine = deepspeed_tpu.init_inference(
        args.model,
        config={"dtype": "bfloat16",
                "tensor_parallel": {"tp_size": args.tp},
                "replace_with_kernel_inject": True})
    ids = np.asarray(tok(args.prompt, return_tensors="np")["input_ids"],
                     dtype=np.int32)
    out = engine.generate(ids, max_new_tokens=args.max_new_tokens)
    print(tok.decode(np.asarray(out)[0]))


if __name__ == "__main__":
    main()
