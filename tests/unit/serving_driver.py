"""Subprocess driver for the serving kill-at-seam proof
(``test_serving_slo.py``).

Serves a fixed, seeded workload (5 greedy requests + 1 already-expired
deadline request) through ``serve_resilient`` on a tiny Transformer.
The test harness arms ``DSTPU_FAULT_INJECT`` at the serving seams
(``serving.sigterm_at_iter`` / ``serving.pre_admit`` /
``serving.pre_decode_dispatch`` / ``serving.mid_drain``) so this process
dies mid-serving — gracefully (SIGTERM → drain → crash-atomic snapshot)
or hard (``os._exit``) — then relaunches it clean.  A relaunch restores
the snapshot (original rids / client ids / partial tokens), re-submits
only the workload requests that are neither completed (results file) nor
restored, and finishes.  The merged per-request outputs must be
BITWISE-identical to an uninterrupted run, and the deadline request must
report ``SHED_DEADLINE`` without ever occupying a slot.

Results file: one ``<client_idx>,<status>,<tok tok ...>`` line per
terminal request, appended after the serve loop returns (last write
wins).  Exit codes: 0 done, 3 preempted (snapshot written), plus the
injected ``exit_code`` (default 17) when a hard kill fires.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.environ["DSTPU_REPO_ROOT"])

import jax  # noqa: E402

# the launching test hands this process its own compile cache through
# $JAX_COMPILATION_CACHE_DIR, NEVER the suite's (see fault_driver.py: an
# os._exit mid-cache-write once poisoned the shared cache for every
# later process)

import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.inference.serving.resilient import serve_resilient  # noqa: E402
from deepspeed_tpu.models.transformer import (Transformer,  # noqa: E402
                                              TransformerConfig)


def workload():
    """Deterministic request mix: 5 greedy requests and one whose
    deadline is already expired at submit (it must SHED, never admit).
    Entries: (prompt, max_new_tokens, deadline_s)."""
    rng = np.random.default_rng(42)
    reqs = []
    for _ in range(5):
        p = rng.integers(1, 97, (int(rng.integers(9, 21)),)).astype(np.int32)
        reqs.append((p, int(rng.integers(4, 11)), None))
    reqs.append((rng.integers(1, 97, (10,)).astype(np.int32), 6, 0.0))
    return reqs


def read_done(path):
    """client_idx -> (status, tokens) from the results file (last write
    wins — a resumed run may legitimately re-record nothing, but merging
    is what the test does too)."""
    done = {}
    if not os.path.exists(path):
        return done
    with open(path) as f:
        for line in f:
            parts = line.strip().split(",", 2)
            if len(parts) == 3:
                done[int(parts[0])] = (parts[1], parts[2])
    return done


def main():
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument("--ckpt-dir", required=True)
    parser.add_argument("--results", required=True)
    parser.add_argument("--drain-budget", type=float, default=0.0)
    # speculative serving (docs/serving.md "Speculative decoding"):
    # self-draft, k=2 — greedy outputs must stay BITWISE-identical to
    # the non-speculative reference run, and a SIGTERM mid-speculation
    # must snapshot committed tokens only
    parser.add_argument("--spec", action="store_true")
    args = parser.parse_args()

    cfg = TransformerConfig(vocab_size=97, hidden_size=32, num_layers=2,
                            num_heads=4, max_seq_len=64,
                            use_flash_attention=False, dtype="float32")
    model = Transformer(cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})
    config = {
        "dtype": "float32", "prefill_chunk_size": 8,
        "serving": {"enabled": True, "num_slots": 2, "max_cache_len": 64,
                    "prefill_chunk": 8, "prefill_token_budget": 16,
                    "decode_block": 2,
                    "drain_budget_s": args.drain_budget,
                    **({"speculative": True, "spec_k": 2,
                        "spec_draft_model": "self"} if args.spec else {})},
    }
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        config["compile_cache"] = {"enabled": True,
                                   "min_compile_time_secs": 0.0}
    eng = deepspeed_tpu.init_inference(model, config=config)
    eng.set_params(params)
    srv = eng.serve()

    restored = srv.restore(args.ckpt_dir)
    done = read_done(args.results)
    have = set(done) | {srv._requests[rid].client_id for rid in restored}
    for rid in restored:
        print(f"[driver] restored idx={srv._requests[rid].client_id} "
              f"rid={rid} prefix={len(srv._requests[rid].prefix)}",
              flush=True)
    rids = list(restored)
    for i, (p, n, dl) in enumerate(workload()):
        if i in have:
            continue
        rids.append(srv.submit(p, max_new_tokens=n, deadline_s=dl,
                               client_id=i))

    status, _results = serve_resilient(srv, args.ckpt_dir, resume=False)

    with open(args.results, "a") as f:
        for rid in rids:
            res = srv.result(rid)
            if res is None:               # preempted (snapshotted) — the
                continue                  # restarted run finishes it
            toks = " ".join(str(t) for t in res.output) \
                if res.output is not None else ""
            f.write(f"{res.client_id},{res.status},{toks}\n")
        f.flush()
        os.fsync(f.fileno())
    print(f"[driver] {status}", flush=True)
    return {"done": 0, "preempted": 3}[status]


if __name__ == "__main__":
    sys.exit(main())
