"""The seam between a page-and-state-row family and its skeleton
(``models/hybrid.py``), from both sides:

* a FOURTH family written inside this file — a config, a state mixer of a
  dozen lines (a running sum behind the slot's state row) and a declaration
  — is served through ``InferenceEngine.serve()``, chunked prefill and
  decode blocks both, and every token it streams is its own cache-less
  forward's greedy choice;
* the parameter tree of each family the skeleton (or its attention module)
  was cut out of — path, shape and dtype of every leaf at a toy config — is
  the listing taken from the tree BEFORE the refactor (PR 57's): what
  ``benchmark/families/*`` map BY PATH and the profiler's by-part table
  reads as ``op_name`` frames.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import flax.linen as nn

import deepspeed_tpu
from benchmark import spec
from deepspeed_tpu.models import contract as contract_mod
from deepspeed_tpu.models.hybrid import (Attention, Hybrid, HybridModel,
                                         StateKind)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHUNK, PAGE, BLOCK, TOL = 8, 8, 4, 2e-4


# ---- a fourth family: a config, a mixer, a declaration -------------------- #
@dataclasses.dataclass(frozen=True)
class ToyConfig:
    vocab_size: int = 96
    hidden_size: int = 32
    num_layers: int = 4
    max_seq_len: int = 128
    dtype: str = "float32"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


class RunningSum(nn.Module):
    """``tanh`` of the running sum of ``u W_in`` over the sequence so far,
    through ``W_out``: the sum is the state a slot keeps (float32)."""
    config: ToyConfig

    @nn.compact
    def __call__(self, u, state=None, start=None, last=None, live=None):
        z = nn.Dense(u.shape[-1], use_bias=False, name="in_proj")(u)
        if state is None:                            # a whole sequence
            total, pools = jnp.cumsum(z, 0), (None,)
        else:
            pool, at, rows = state
            held = pool[at, rows]
            if start is None:                        # a step: a row a lane
                total = held + z
                kept = total if live is None \
                    else jnp.where(live[:, None], total, held)
            else:                                    # a chunk of one slot
                total = jnp.where(start == 0, 0.0, held) + jnp.cumsum(z, 0)
                kept = total[z.shape[0] - 1 if last is None else last]
            pools = (pool.at[at, rows].set(kept),)
        return nn.Dense(u.shape[-1], use_bias=False, name="out_proj")(
            jnp.tanh(total)), pools


class ToyModel(HybridModel):

    @staticmethod
    def declare(cfg):
        return Hybrid(
            norms=("mixer_norm", "mlp_norm"), norm_eps=1e-5,
            attention_layers=(1,),
            attention=Attention(cfg.hidden_size, 4, 2, 8, cfg.jnp_dtype,
                                rope_theta=1e4),
            mixer=("summed", RunningSum),
            state=(StateKind("sum", (cfg.hidden_size,), jnp.float32),),
            dense=("mlp", 48, 1), work="sum",
            moe=dict(num_experts=4, k=2, norm_topk_prob=True,
                     ffn_hidden_size=16, scoring="sigmoid"))


@pytest.fixture(scope="module")
def toy():
    module = ToyModel(ToyConfig())
    forward = jax.jit(module.apply)
    params = module.init(jax.random.key(3),
                         {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    # a layer that weighs something against the stream it is added to
    params = jax.tree.map(lambda x: x * 3.0 if x.ndim > 1 else x, params)
    return module, params, forward


@pytest.fixture(scope="module")
def served(toy):
    """Five requests on two slots: prompts of several chunks with padded
    tails, slot churn, lanes that retire inside decode blocks."""
    module, params, _ = toy
    eng = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None,
        "serving": {"enabled": True, "num_slots": 2, "max_cache_len": 64,
                    "prefill_chunk": CHUNK, "decode_block": BLOCK,
                    "page_size": PAGE}})
    eng.set_params(params)
    srv = eng.serve()
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, 96, int(n)).astype(np.int32), int(k))
            for n, k in zip(rng.integers(1, 30, 5), rng.integers(3, 12, 5))]
    rids = [srv.submit(p, max_new_tokens=k) for p, k in reqs]
    outs = srv.drain()
    return srv, reqs, [np.asarray(outs[r]) for r in rids]


def test_a_fourth_family_declares_what_the_slot_engine_reads(toy):
    module, _, _ = toy
    declared = contract_mod.read(module)
    contract_mod.check(declared, module, PAGE, CHUNK, declared.paged_layers)
    assert (declared.state_kinds, declared.lane_layers,
            declared.expert_layers, declared.experts,
            declared.work_counters) == (
        ("sum",), 1, 3, 4, ("sum_scan_rows", "sum_state_rows", "full_keys"))
    pools = jax.eval_shape(lambda: module.init_paged_cache(
        5, PAGE, jnp.bfloat16, state_rows=3))
    assert {k: (v.shape, str(v.dtype)) for k, v in pools.items()} == {
        "k": ((1, 5, PAGE, 16), "bfloat16"),
        "v": ((1, 5, PAGE, 16), "bfloat16"),
        "sum": ((3, 3, 32), "float32")}


def test_a_fourth_family_serves_its_own_greedy_tokens(toy, served):
    """Every token a request streamed, after chunks and decode blocks over
    the pools, is the cache-less forward's largest logit at its position
    (to the float32 tolerance: the two sum in different orders)."""
    module, params, forward = toy
    srv, reqs, outs = served
    assert srv.stats["paged_attention_fallback"] == 0
    for (prompt, n_new), out in zip(reqs, outs):
        assert len(out) == len(prompt) + n_new
        assert (out[:len(prompt)] == prompt).all()
        ids = np.zeros((1, 48), np.int32)
        ids[0, :len(out)] = out
        logits = np.asarray(forward(params, {"input_ids": ids}))[0]
        for t in range(len(prompt), len(out)):
            assert logits[t - 1].max() - logits[t - 1, out[t]] <= TOL, t


def test_a_fourth_family_counts_its_work(served):
    srv, reqs, _ = served
    live = sum(len(p) + k - 1 for p, k in reqs)
    assert srv.stats["sum_scan_rows"] == 3 * live
    assert srv.stats["full_keys"] == sum(
        n * (n + 1) // 2 for n in (len(p) + k - 1 for p, k in reqs))


# ---- the parameter trees, as they were before the refactor ---------------- #
TOYS = {
    "lfm2": dict(
        model_type="lfm2_moe", conv_L_cache=3, conv_bias=False,
        hidden_size=64, intermediate_size=160,
        layer_types=["conv", "conv", "full_attention", "conv", "conv",
                     "full_attention"],
        max_position_embeddings=512, moe_intermediate_size=48, norm_eps=1e-5,
        norm_topk_prob=True, num_attention_heads=4, num_dense_layers=2,
        num_experts=8, num_experts_per_tok=2, num_hidden_layers=6,
        num_key_value_heads=2,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        routed_scaling_factor=1, use_expert_bias=True, vocab_size=128),
    "solar_open2": dict(
        model_type="solar_open2",
        linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                num_heads=4, num_kv_heads=None),
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
        head_dim=16, num_key_value_heads=2, vocab_size=128,
        intermediate_size=160, moe_intermediate_size=32, rms_norm_eps=1e-5,
        rope_theta=10000, partial_rotary_factor=1,
        tie_word_embeddings=False, max_position_embeddings=512,
        first_k_dense_replace=0, use_rope=False, gqa_interval=3,
        gqa_layers=[0, 4, 8], use_gqa_gate=True, kda_use_full_proj=False,
        kda_allow_neg_eigval=True, n_routed_experts=4,
        n_routed_experts_published=16, held_experts=[4, 4],
        n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1,
        num_experts_per_tok=2),
    "granite_hybrid": dict(
        model_type="granitemoehybrid", hidden_size=128, num_hidden_layers=4,
        layer_types=["mamba", "attention", "mamba", "mamba"],
        num_attention_heads=4, num_key_value_heads=2, attention_bias=False,
        attention_multiplier=0.03125, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=16, hidden_act="silu",
        normalization_function="rmsnorm", position_embedding_type="nope",
        mamba_n_heads=4, mamba_d_head=64, mamba_d_state=32, mamba_expand=2,
        mamba_n_groups=1, mamba_d_conv=4, mamba_conv_bias=True,
        mamba_proj_bias=False, mamba_chunk_size=256, intermediate_size=32,
        shared_intermediate_size=64, num_local_experts=4,
        num_local_experts_published=16, held_experts=[4, 4],
        num_experts_per_tok=3, vocab_size=128, rms_norm_eps=1e-5,
        rope_scaling=None, rope_theta=10000, tie_word_embeddings=True,
        max_position_embeddings=512),
    "trinity": dict(
        vocab_size=128, hidden_size=64, num_hidden_layers=5,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, num_shared_experts=1, num_dense_layers=1,
        route_norm=True, route_scale=2.826, score_func="sigmoid", n_group=1,
        topk_group=1, sliding_window=16, rope_theta=10000, rope_scaling=None,
        max_position_embeddings=512, mup_enabled=True, rms_norm_eps=1e-5,
        hidden_act="silu", tie_word_embeddings=False),
}

# path, shape and dtype a leaf at the toys above, from PR 57's tree; the
# layers that hold the same leaf are folded into one line
LEAVES = {
    "lfm2": """
embed_tokens/embedding (128, 64) float32
embedding_norm/scale (64,) float32
layers_[0 1 3 4]/conv/conv_kernel (3, 64) float32
layers_[0 1 3 4]/conv/in_proj/kernel (64, 192) float32
layers_[0 1 3 4]/conv/out_proj/kernel (64, 64) float32
layers_[0 1]/feed_forward/down_proj/kernel (160, 64) float32
layers_[0 1]/feed_forward/gate_proj/kernel (64, 160) float32
layers_[0 1]/feed_forward/up_proj/kernel (64, 160) float32
layers_[0 1 2 3 4 5]/ffn_norm/scale (64,) float32
layers_[0 1 2 3 4 5]/operator_norm/scale (64,) float32
layers_[2 3 4 5]/moe_mlp/ExpertsMLP_0/experts_wg (8, 64, 48) float32
layers_[2 3 4 5]/moe_mlp/ExpertsMLP_0/experts_wi (8, 64, 48) float32
layers_[2 3 4 5]/moe_mlp/ExpertsMLP_0/experts_wo (8, 48, 64) float32
layers_[2 3 4 5]/moe_mlp/gate_kernel (64, 8) float32
layers_[2 3 4 5]/moe_mlp/select_bias (8,) float32
layers_[2 5]/self_attn/k_norm (16,) float32
layers_[2 5]/self_attn/k_proj/kernel (64, 2, 16) float32
layers_[2 5]/self_attn/out_proj/kernel (4, 16, 64) float32
layers_[2 5]/self_attn/q_norm (16,) float32
layers_[2 5]/self_attn/q_proj/kernel (64, 4, 16) float32
layers_[2 5]/self_attn/v_proj/kernel (64, 2, 16) float32""",
    "solar_open2": """
embed_tokens/embedding (128, 64) float32
layers_[0 1 2]/input_layernorm/scale (64,) float32
layers_[0 1 2]/moe_mlp/ExpertsMLP_0/experts_wg (4, 64, 32) float32
layers_[0 1 2]/moe_mlp/ExpertsMLP_0/experts_wi (4, 64, 32) float32
layers_[0 1 2]/moe_mlp/ExpertsMLP_0/experts_wo (4, 32, 64) float32
layers_[0 1 2]/moe_mlp/gate_kernel (64, 16) float32
layers_[0 1 2]/moe_mlp/select_bias (16,) float32
layers_[0 1 2]/moe_mlp/shared_down/kernel (32, 64) float32
layers_[0 1 2]/moe_mlp/shared_gate/kernel (64, 32) float32
layers_[0 1 2]/moe_mlp/shared_up/kernel (64, 32) float32
layers_[0 1 2]/post_attention_layernorm/scale (64,) float32
layers_[0]/self_attn/gate_proj/kernel (64, 64) float32
layers_[0]/self_attn/k_proj/kernel (64, 2, 16) float32
layers_[0]/self_attn/o_proj/kernel (64, 64) float32
layers_[0]/self_attn/q_proj/kernel (64, 4, 16) float32
layers_[0]/self_attn/v_proj/kernel (64, 2, 16) float32
layers_[1 2]/linear_attn/A_log (4,) float32
layers_[1 2]/linear_attn/b_proj/kernel (64, 4) float32
layers_[1 2]/linear_attn/dt_bias (64,) float32
layers_[1 2]/linear_attn/f_a_proj/kernel (64, 16) float32
layers_[1 2]/linear_attn/f_b_proj/kernel (16, 64) float32
layers_[1 2]/linear_attn/g_a_proj/kernel (64, 16) float32
layers_[1 2]/linear_attn/g_b_proj/kernel (16, 64) float32
layers_[1 2]/linear_attn/k_conv1d (4, 64) float32
layers_[1 2]/linear_attn/k_proj/kernel (64, 64) float32
layers_[1 2]/linear_attn/o_norm (16,) float32
layers_[1 2]/linear_attn/o_proj/kernel (64, 64) float32
layers_[1 2]/linear_attn/q_conv1d (4, 64) float32
layers_[1 2]/linear_attn/q_proj/kernel (64, 64) float32
layers_[1 2]/linear_attn/v_conv1d (4, 64) float32
layers_[1 2]/linear_attn/v_proj/kernel (64, 64) float32
lm_head/kernel (64, 128) float32
norm/scale (64,) float32""",
    "granite_hybrid": """
embed_tokens/embedding (128, 128) float32
layers_[0 1 2 3]/input_layernorm/scale (128,) float32
layers_[0 2 3]/mamba/A_log (4,) float32
layers_[0 2 3]/mamba/D (4,) float32
layers_[0 2 3]/mamba/conv1d (4, 320) float32
layers_[0 2 3]/mamba/conv1d_bias (320,) float32
layers_[0 2 3]/mamba/dt_bias (4,) float32
layers_[0 2 3]/mamba/in_proj/kernel (128, 580) float32
layers_[0 2 3]/mamba/norm (256,) float32
layers_[0 2 3]/mamba/out_proj/kernel (256, 128) float32
layers_[0 1 2 3]/moe_mlp/ExpertsMLP_0/experts_wg (4, 128, 32) float32
layers_[0 1 2 3]/moe_mlp/ExpertsMLP_0/experts_wi (4, 128, 32) float32
layers_[0 1 2 3]/moe_mlp/ExpertsMLP_0/experts_wo (4, 32, 128) float32
layers_[0 1 2 3]/moe_mlp/gate_kernel (128, 16) float32
layers_[0 1 2 3]/moe_mlp/select_bias (16,) float32
layers_[0 1 2 3]/moe_mlp/shared_down/kernel (64, 128) float32
layers_[0 1 2 3]/moe_mlp/shared_gate/kernel (128, 64) float32
layers_[0 1 2 3]/moe_mlp/shared_up/kernel (128, 64) float32
layers_[0 1 2 3]/post_attention_layernorm/scale (128,) float32
layers_[1]/self_attn/k_proj/kernel (128, 2, 32) float32
layers_[1]/self_attn/o_proj/kernel (128, 128) float32
layers_[1]/self_attn/q_proj/kernel (128, 4, 32) float32
layers_[1]/self_attn/v_proj/kernel (128, 2, 32) float32
norm/scale (128,) float32""",
    "trinity": """
embed_tokens/embedding (128, 64) float32
layers_[0 1 2 3 4]/input_layernorm/scale (64,) float32
layers_[0]/mlp/down_proj/kernel (96, 64) float32
layers_[0]/mlp/gate_proj/kernel (64, 96) float32
layers_[0]/mlp/up_proj/kernel (64, 96) float32
layers_[0 1 2 3 4]/post_attention_layernorm/scale (64,) float32
layers_[0 1 2 3 4]/post_mlp_layernorm/scale (64,) float32
layers_[0 1 2 3 4]/pre_mlp_layernorm/scale (64,) float32
layers_[0 1 2 3 4]/self_attn/gate_proj/kernel (64, 64) float32
layers_[0 1 2 3 4]/self_attn/k_norm (16,) float32
layers_[0 1 2 3 4]/self_attn/k_proj/kernel (64, 2, 16) float32
layers_[0 1 2 3 4]/self_attn/o_proj/kernel (64, 64) float32
layers_[0 1 2 3 4]/self_attn/q_norm (16,) float32
layers_[0 1 2 3 4]/self_attn/q_proj/kernel (64, 4, 16) float32
layers_[0 1 2 3 4]/self_attn/v_proj/kernel (64, 2, 16) float32
layers_[1 2 3 4]/moe_mlp/ExpertsMLP_0/experts_wg (8, 64, 32) float32
layers_[1 2 3 4]/moe_mlp/ExpertsMLP_0/experts_wi (8, 64, 32) float32
layers_[1 2 3 4]/moe_mlp/ExpertsMLP_0/experts_wo (8, 32, 64) float32
layers_[1 2 3 4]/moe_mlp/gate_kernel (64, 8) float32
layers_[1 2 3 4]/moe_mlp/select_bias (8,) float32
layers_[1 2 3 4]/moe_mlp/shared_down/kernel (32, 64) float32
layers_[1 2 3 4]/moe_mlp/shared_gate/kernel (64, 32) float32
layers_[1 2 3 4]/moe_mlp/shared_up/kernel (64, 32) float32
lm_head/kernel (64, 128) float32
norm/scale (64,) float32""",
}


def _listing(tree):
    rows = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(k.key for k in path)
        layer = re.match(r"layers_(\d+)/", name)
        key = (re.sub(r"^layers_\d+", "layers_[]", name), leaf.shape,
               str(leaf.dtype))
        rows.setdefault(key, []).append(layer.group(1) if layer else None)
    return "\n".join(
        (name.replace("[]", "[" + " ".join(at) + "]") if at[0] else name)
        + f" {shape} {dtype}" for (name, shape, dtype), at in rows.items())


@pytest.mark.parametrize("family", sorted(TOYS))
def test_a_familys_parameter_tree_is_what_it_was(family):
    module = spec.Benchmark(ROOT).family(family).program_model(
        TOYS[family], dtype="float32")
    tree = jax.eval_shape(lambda: module.init(
        jax.random.key(0), {"input_ids": jnp.zeros((1, 8), jnp.int32)}))
    assert _listing(tree["params"]).split("\n") \
        == LEAVES[family].strip().split("\n")
