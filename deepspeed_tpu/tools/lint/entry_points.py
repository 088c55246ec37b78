"""Registered jaxpr-check entry points: the REAL hot paths, tiny-sized.

Each builder returns an :class:`EntryPoint` wrapping the jit-wrapped
callable the engine itself dispatches per step (the fused train step, the
generation/decode loop, the split-prefill chunk program) plus concrete CPU
args to trace it with, and whether the program is expected to declare buffer
donation.  Runs entirely on CPU (``JAX_PLATFORMS=cpu``) at toy shapes —
tracing and lowering exercise everything the checks need.
"""

import dataclasses
from typing import Any, Callable, Tuple

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class EntryPoint:
    name: str
    fn: Callable            # jit-wrapped callable
    args: Tuple[Any, ...]
    expect_donation: bool   # program must declare (and use) buffer donation
    # minimum number of donated inputs that must actually alias an output.
    # When set, the "donated buffers were not usable" warning is tolerated —
    # for programs that deliberately donate CONSUMED inputs (e.g. grads,
    # freed for scratch reuse) the warning is expected; the count is what
    # guards the state buffers' aliasing.
    min_aliased: int = 0


def _tiny_train_engine():
    import flax.linen as nn
    import deepspeed_tpu

    class TinyModel(nn.Module):
        @nn.compact
        def __call__(self, batch):
            x, y = batch["x"], batch["y"]
            h = nn.relu(nn.Dense(16, name="l0")(x))
            logits = nn.Dense(16, name="head")(h)
            one_hot = jax.nn.one_hot(y, 16)
            return -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * one_hot, axis=-1))

    engine, *_ = deepspeed_tpu.initialize(
        model=TinyModel(),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}})
    return engine


def runtime_train_step():
    """The fused train step ``runtime/engine.py`` dispatches per
    ``train_batch`` (params/opt_state/scaler donated)."""
    engine = _tiny_train_engine()
    rng = np.random.default_rng(0)
    micro = {"x": jnp.asarray(rng.standard_normal((2, 16)), jnp.float32),
             "y": jnp.asarray(rng.integers(0, 16, (2,)), jnp.int32)}
    batch = jax.tree.map(lambda x: x[None], micro)     # [gas=1, ...]
    engine._lazy_init((micro,), {})
    fused = engine._get_fused_step()
    args = (engine._params, engine._opt_state, engine._scaler_state,
            jnp.asarray(1e-3, jnp.float32), jnp.asarray(1, jnp.int32),
            engine._rng, batch)
    return EntryPoint("runtime.train_step", fused, args, expect_donation=True)


def runtime_apply_update():
    """The 3-call path's optimizer step (params/opt_state/scaler/grads all
    donated; grads are CONSUMED — their donation never aliases, so the check
    demands the params+opt_state aliasing count instead of a clean warning
    log)."""
    engine = _tiny_train_engine()
    rng = np.random.default_rng(0)
    micro = {"x": jnp.asarray(rng.standard_normal((2, 16)), jnp.float32),
             "y": jnp.asarray(rng.integers(0, 16, (2,)), jnp.int32)}
    engine._lazy_init((micro,), {})
    apply = engine._get_apply()
    grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                         engine._params)
    args = (engine._params, engine._opt_state, engine._scaler_state, grads,
            jnp.asarray(False), jnp.asarray(1e-3, jnp.float32),
            jnp.asarray(1, jnp.int32))
    n_state = len(jax.tree.leaves((engine._params, engine._opt_state)))
    return EntryPoint("runtime.apply_update", apply, args,
                      expect_donation=True, min_aliased=n_state)


def _tiny_inference_engine(prefill_chunk=None):
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import (Transformer,
                                                  TransformerConfig)
    cfg = TransformerConfig(vocab_size=97, hidden_size=32, num_layers=2,
                            num_heads=4, max_seq_len=64,
                            use_flash_attention=False, dtype="float32")
    model = Transformer(cfg)
    config = {"dtype": "float32"}
    if prefill_chunk is not None:
        config["prefill_chunk_size"] = prefill_chunk
    engine = deepspeed_tpu.init_inference(model, config=config)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 97, (1, 8)),
                      jnp.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})
    engine.set_params(params)
    return engine


def inference_decode():
    """The generation program (prefill + decode scan) ``inference/engine.py``
    dispatches per ``generate`` — the KV cache is donated through it."""
    from deepspeed_tpu.inference.engine import required_cache_len
    engine = _tiny_inference_engine()
    B, P, T = 1, 8, 4
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 97, (B, P)),
                      jnp.int32)
    fn = engine._get_generate(P, T, False, 1.0, 0, 1.0, with_mask=False,
                              prefill_chunk=None)
    cache = engine._workspace.take(B, required_cache_len(P, T, None),
                                   engine.compute_dtype)
    args = (engine._params, cache, ids, jax.random.key(0),
            jnp.asarray(-1))
    return EntryPoint("inference.decode", fn, args, expect_donation=True)


def inference_prefill_chunk():
    """The split-prefill per-chunk program (donated-cache; the round-5 OOM
    fix) — built by driving a real chunked ``generate`` and re-tracing the
    compiled chunk function."""
    engine = _tiny_inference_engine(prefill_chunk=8)
    B, P, C, T = 1, 24, 8, 2
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 97, (B, P)),
                      jnp.int32)
    engine.generate(ids, max_new_tokens=T, seed=0)
    key = next(k for k in engine._compiled
               if isinstance(k, tuple) and k and k[0] == "chunkfill")
    chunk_fn = engine._compiled[key]
    cache = engine._workspace.take(B, 64, engine.compute_dtype)
    args = (engine._params, cache, ids[:, :C],
            jnp.asarray(0, jnp.int32), jnp.zeros((B,), jnp.int32))
    return EntryPoint("inference.prefill_chunk", chunk_fn, args,
                      expect_donation=True)


def _slot_state(N):
    return {"token": jnp.zeros((N,), jnp.int32),
            "pos": jnp.asarray([8, 3], jnp.int32),
            "active": jnp.asarray([True, False]),
            "remaining": jnp.asarray([4, 0], jnp.int32),
            "eos": jnp.full((N,), -1, jnp.int32)}


def serving_decode_step():
    """The serving loop's single reusable decode-step program
    (``inference/serving/slots.py``): page pool + slot state donated, the
    per-slot page tables a plain traced input — the pool/state donations
    must alias (the whole continuous-batching design rests on this one
    executable updating the pool in place) and the program must stay
    callback-free even though every cache touch routes through the page
    table."""
    from deepspeed_tpu.inference.engine import build_sample_fn
    from deepspeed_tpu.inference.serving.slots import make_decode_block_fn
    engine = _tiny_inference_engine()
    N, NP, PG = 2, 9, 8                 # 9 pages of 8 (page 0 = trash)
    fn = make_decode_block_fn(engine.module, engine.module.slot_contract(),
                              build_sample_fn(False, 1.0, 0, 1.0),
                              None, 2, 4 * PG)
    pool = engine.module.init_paged_cache(NP, PG,
                                          dtype=engine.compute_dtype)
    pages = jnp.asarray([[3, 5, 2, 7], [1, 4, 0, 0]], jnp.int32)
    args = (engine._params, pool, _slot_state(N), pages,
            jax.random.key(0))
    return EntryPoint("serving.decode_step", fn, args, expect_donation=True)


def serving_prefill_chunk():
    """The admission-prefill chunk program as a dense model's server
    dispatches it — several chunk rows a pass of the weights, each with
    its own [table_width] table row, start and last real position (two
    chunks of one prompt, one of another, a dead row): the pool is the
    donated buffer (chunk writes land in the slots' pages directly — no
    staging lane), the table rows a separate traced input so the pool
    donation aliases cleanly."""
    from deepspeed_tpu.inference.serving.slots import make_chunk_fn
    engine = _tiny_inference_engine()
    C, NP, PG = 8, 9, 8
    chunk_fn = make_chunk_fn(engine.module, engine.module.slot_contract(),
                             None)
    pool = engine.module.init_paged_cache(NP, PG,
                                          dtype=engine.compute_dtype)
    pages = jnp.asarray([[3, 5, 2, 7], [3, 5, 2, 7], [1, 4, 6, 8],
                         [0, 0, 0, 0]], jnp.int32)
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 97, (4, C)),
                      jnp.int32)
    args = (engine._params, pool, pages, ids,
            jnp.asarray([0, 8, 0, 0], jnp.int32),
            jnp.asarray([7, 3, 7, 0], jnp.int32))
    return EntryPoint("serving.prefill_chunk", chunk_fn, args,
                      expect_donation=True)


def serving_admit():
    """The admission program (first-token sample + in-program slot-state
    write, slot index traced, slot state donated; no cache argument at
    all — prefill already wrote the pages).  The one-row form; a server
    whose prefill dispatches take rows adds a traced row index."""
    from deepspeed_tpu.inference.engine import build_sample_fn
    from deepspeed_tpu.inference.serving.slots import make_admit_fn
    fn = make_admit_fn(build_sample_fn(False, 1.0, 0, 1.0))
    logits = jnp.zeros((1, 1, 97), jnp.float32)
    args = (_slot_state(2), logits, jax.random.key(0),
            jnp.asarray(1, jnp.int32), jnp.asarray(8, jnp.int32),
            jnp.asarray(4, jnp.int32), jnp.asarray(-1, jnp.int32))
    return EntryPoint("serving.admit", fn, args, expect_donation=True)


def serving_spec_propose():
    """The speculative draft-propose program: k+1 greedy draft steps in
    one in-program scan (the extra step is the write-only cache
    catch-up), ONLY the draft KV workspace donated — the slot state is
    read-only here (the verify program owns its donation)."""
    from deepspeed_tpu.inference.serving.slots import make_draft_propose_fn
    engine = _tiny_inference_engine()
    N, S, K = 2, 32, 2
    fn = make_draft_propose_fn(engine.module, None, K, S)
    dcache = engine.module.init_cache(N, S, dtype=engine.compute_dtype)
    args = (engine._params, dcache, _slot_state(N))
    return EntryPoint("serving.spec_propose", fn, args,
                      expect_donation=True)


def serving_spec_verify():
    """The speculative verify-and-commit program: ONE batched target
    forward over [token, drafts], in-program accept mask + per-slot
    accepted length — pool AND slot state donated, page tables traced,
    no host callbacks (the whole point is committing up to k+1 tokens
    per dispatch without a sync); inactive lanes' window writes redirect
    to the trash page in-program, live lanes' per-row multi-token
    scatter routes through the table."""
    from deepspeed_tpu.inference.engine import build_sample_fn
    from deepspeed_tpu.inference.serving.slots import make_spec_verify_fn
    engine = _tiny_inference_engine()
    N, NP, PG, K = 2, 9, 8, 2
    fn = make_spec_verify_fn(engine.module,
                             build_sample_fn(False, 1.0, 0, 1.0),
                             None, K, 4 * PG)
    pool = engine.module.init_paged_cache(NP, PG,
                                          dtype=engine.compute_dtype)
    pages = jnp.asarray([[3, 5, 2, 7], [1, 4, 0, 0]], jnp.int32)
    draft = jnp.asarray(np.random.default_rng(7).integers(0, 97, (N, K)),
                        jnp.int32)
    args = (engine._params, pool, _slot_state(N), pages, draft,
            jax.random.key(0))
    return EntryPoint("serving.spec_verify", fn, args,
                      expect_donation=True)


def serving_spec_block():
    """The self-drafting decode program (``models/glm5.py``'s multi-token-
    prediction module drafting for its own model): ``block`` verify windows
    of two rows a lane in one scan — main forward, in-program commit of one
    or two tokens, the module's two rows — pool AND slot state (with its
    pending-draft leaf) donated, page tables traced, the expert load of
    main layers and module as one vector, no host callbacks."""
    from deepspeed_tpu.inference.engine import build_sample_fn
    from deepspeed_tpu.inference.serving.slots import make_spec_block_fn
    from deepspeed_tpu.models.glm5 import Glm5Config, Glm5Model
    from deepspeed_tpu.models.latent_attention import LatentSpec
    attn = LatentSpec(hidden=32, heads=2, q_rank=16, kv_rank=16, nope=8,
                      rope=8, v=8, theta=1e6, index_heads=2, index_dim=16,
                      index_topk=8, rescale=False, gated=False,
                      interleaved=True)
    model = Glm5Model(Glm5Config(
        vocab_size=97, hidden_size=32, num_layers=2, first_k_dense=1,
        intermediate_size=48, moe_intermediate_size=16, n_routed_experts=8,
        n_shared_experts=1, moe_top_k=2, norm_topk_prob=True,
        routed_scaling_factor=2.5, attn=attn, mtp_layers=1, max_seq_len=64,
        held_experts=(0, 4), dtype="float32"))
    params = model.init(jax.random.key(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    N, NP, PG = 2, 9, 8
    fn = make_spec_block_fn(model, model.slot_contract(),
                            build_sample_fn(False, 1.0, 0, 1.0), None, 2,
                            4 * PG)
    pool = model.init_paged_cache(NP, PG, dtype=jnp.float32)
    pages = jnp.asarray([[3, 5, 2, 7], [1, 4, 0, 0]], jnp.int32)
    state = dict(_slot_state(N), draft=jnp.asarray([5, 0], jnp.int32))
    args = (params, pool, state, pages, jax.random.key(0))
    return EntryPoint("serving.spec_block", fn, args, expect_donation=True)


def serving_spec_draft_prefill():
    """The draft-side admission-prefill chunk program (the draft cache
    needs the prompt's K/V too): same body as the engine chunk program
    bound to the draft module, draft lane donated."""
    from deepspeed_tpu.inference.serving.slots import make_draft_chunk_fn
    engine = _tiny_inference_engine()
    C = 8
    chunk_fn = make_draft_chunk_fn(engine.module, None)
    lane = engine.module.init_cache(1, 32, dtype=engine.compute_dtype)
    ids = jnp.asarray(np.random.default_rng(8).integers(0, 97, (1, C)),
                      jnp.int32)
    args = (engine._params, lane, ids, jnp.asarray(0, jnp.int32),
            jnp.zeros((1,), jnp.int32))
    return EntryPoint("serving.spec_draft_prefill", chunk_fn, args,
                      expect_donation=True)


def serving_spec_draft_admit():
    """The draft-side admission insert: prefilled draft lane into the
    draft cache over the traced slot index (draft cache donated); no
    sampling, no state write — the target admit owns both."""
    from deepspeed_tpu.inference.serving.slots import make_draft_admit_fn
    engine = _tiny_inference_engine()
    N, S = 2, 32
    fn = make_draft_admit_fn()
    dcache = engine.module.init_cache(N, S, dtype=engine.compute_dtype)
    lane = engine.module.init_cache(1, S, dtype=engine.compute_dtype)
    args = (dcache, lane, jnp.asarray(1, jnp.int32))
    return EntryPoint("serving.spec_draft_admit", fn, args,
                      expect_donation=True)


def hybrid_rollout():
    """The hybrid engine's rollout generation program (RLHF: decode over
    the live training weights' inference view) — same jitted body as
    ``inference.decode`` (``make_generate_fn``) but built through
    ``DeepSpeedHybridEngine._get_rollout_fn`` with the rollout view as
    params; the KV cache is donated through it."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.engine import (KVCacheWorkspace,
                                                required_cache_len)
    from deepspeed_tpu.models.transformer import (Transformer,
                                                  TransformerConfig)
    cfg = TransformerConfig(vocab_size=97, hidden_size=32, num_layers=2,
                            num_heads=4, max_seq_len=64,
                            use_flash_attention=False, dtype="float32")
    engine, *_ = deepspeed_tpu.initialize(
        model=Transformer(cfg),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0},
                "hybrid_engine": {"enabled": True}})
    B, P, T = 1, 8, 4
    ids = jnp.asarray(np.random.default_rng(5).integers(0, 97, (B, P)),
                      jnp.int32)
    key = (P, T, False, 1.0, 0, 1.0, False, None,
           engine._rollout_early_exit)
    fn = engine._get_rollout_fn(key)
    params = engine._inference_view()
    cache = KVCacheWorkspace(engine.module).take(
        B, required_cache_len(P, T, None), engine.compute_dtype)
    args = (params, cache, ids, jax.random.key(0), jnp.asarray(-1))
    return EntryPoint("hybrid.rollout", fn, args, expect_donation=True)


BUILDERS = (runtime_train_step, runtime_apply_update, inference_decode,
            inference_prefill_chunk, serving_decode_step,
            serving_prefill_chunk, serving_admit, serving_spec_propose,
            serving_spec_verify, serving_spec_block,
            serving_spec_draft_prefill, serving_spec_draft_admit,
            hybrid_rollout)

# builder function name -> the EntryPoint name it constructs.  Lets
# name-filtered sweeps (``ds_lint --mem <program>``, the bench
# memory_snapshot subset) skip the engine builds of filtered-out
# programs instead of paying all 13 just to learn their names.  Kept
# honest mechanically: every consumer cross-checks ``ep.name`` against
# this map after building, so drift fails loudly instead of silently
# skipping the wrong program.
BUILDER_PROGRAMS = {
    "runtime_train_step": "runtime.train_step",
    "runtime_apply_update": "runtime.apply_update",
    "inference_decode": "inference.decode",
    "inference_prefill_chunk": "inference.prefill_chunk",
    "serving_decode_step": "serving.decode_step",
    "serving_prefill_chunk": "serving.prefill_chunk",
    "serving_admit": "serving.admit",
    "serving_spec_propose": "serving.spec_propose",
    "serving_spec_verify": "serving.spec_verify",
    "serving_spec_block": "serving.spec_block",
    "serving_spec_draft_prefill": "serving.spec_draft_prefill",
    "serving_spec_draft_admit": "serving.spec_draft_admit",
    "hybrid_rollout": "hybrid.rollout",
}


def iter_entry_points():
    for build in BUILDERS:
        yield build()
