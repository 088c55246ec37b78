"""Subprocess driver for the kill-and-resume proof
(``test_fault_tolerance.py``).

Trains SimpleModel under ``run_resilient`` with data derived from
``engine.global_steps`` (the determinism contract), appending
``step,repr(loss)`` lines to ``--losses`` after every completed step.  The
test harness arms ``DSTPU_FAULT_INJECT`` (e.g.
``point=ckpt.before_latest_swap,action=exit,at=2``) so this process dies
mid-save with ``os._exit`` — no cleanup, the honest SIGKILL simulation —
then relaunches it clean and compares the merged loss trajectory bitwise
against an uninterrupted run.

Exit codes: 0 done, 3 preempted, 4 failed (and the injected ``exit_code``
— default 17 — when a kill fires).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"
sys.path.insert(0, os.environ["DSTPU_REPO_ROOT"])
sys.path.insert(0, os.path.join(os.environ["DSTPU_REPO_ROOT"], "tests",
                                "unit"))

import jax  # noqa: E402

# The launching test hands this process its own compile cache through
# $JAX_COMPILATION_CACHE_DIR, NEVER the suite's: this process is killed
# with os._exit at arbitrary seams, and a truncated cache write makes
# every LATER process that loads the entry abort natively deep in XLA
# (observed: deterministic SIGABRT in engine.step until the poisoned
# entry was pruned).  Isolation bounds the blast radius to this test's
# own tmp dir.

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.runtime.fault.supervisor import run_resilient  # noqa: E402
from simple_model import SimpleModel, random_batch  # noqa: E402


def main():
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument("--ckpt-dir", required=True)
    parser.add_argument("--max-steps", type=int, default=6)
    parser.add_argument("--save-interval", type=int, default=2)
    parser.add_argument("--losses", required=True)
    args = parser.parse_args()

    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2},
        "seed": 7,
        "fault": {"enabled": True, "checksum": "crc32",
                  "backoff_base_secs": 0.01, "backoff_max_secs": 0.05},
    }
    engine, *_ = deepspeed_tpu.initialize(model=SimpleModel(hidden_dim=16),
                                          config=config)

    def step_fn(engine):
        # data is a pure function of the resumable step counter — the
        # resumed trajectory replays exactly the batches the uninterrupted
        # run would have seen
        batch = random_batch(batch_size=16, seed=engine.global_steps)
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        with open(args.losses, "a") as f:
            f.write(f"{engine.global_steps},"
                    f"{float(jax.device_get(loss))!r}\n")

    status, info = run_resilient(engine, step_fn,
                                 checkpoint_dir=args.ckpt_dir,
                                 max_steps=args.max_steps,
                                 save_interval=args.save_interval)
    print(f"[driver] {status} {info}", flush=True)
    return {"done": 0, "preempted": 3, "failed": 4}[status]


if __name__ == "__main__":
    sys.exit(main())
