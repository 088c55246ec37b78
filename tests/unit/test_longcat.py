"""LongCat-Flash-Chat's double layer at a toy size on the CPU (2 double
layers, 16 real + 8 zero router outputs, top-4, 8 of the 16 held): the
program through the paged pool and through ``ServingEngine`` against the
plain float32 reference (``benchmark/families/longcat.py``), every control
of the family against the tolerance, the expert shares and the zero part
against the uncut layer, the parameter count of the configuration's file.

Tolerance: program and reference are both float32 here and differ by the
order of their sums alone (``tests/unit/test_dots3.py`` has the sizes):
2e-4 absolute on logits of ~1.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmark import spec
from deepspeed_tpu.inference.serving.paging import SlotPages
from deepspeed_tpu.models import longcat
from deepspeed_tpu.models.latent_attention import (LatentAttention,
                                                   LatentSpec)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.moe.layer import MoE

TOL = 2e-4
TOY = dict(
    attention_bias=False, vocab_size=128, hidden_size=64, ffn_hidden_size=96,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8, v_head_dim=16,
    qk_nope_head_dim=16, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6, n_routed_experts=8,
    n_routed_experts_published=16, held_experts=[8, 8],
    max_position_embeddings=512, rms_norm_eps=1e-5, rope_theta=10000000,
    attention_method="MLA", zero_expert_num=8, zero_expert_type="identity",
    moe_topk=4)
SEED = 7
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a family instance of this file's own, drawn at a toy's scales (see
# test_dots3.py): at hidden 64 the real stds give every layer nothing to add
fam = spec.Benchmark(ROOT).family("longcat")
fam._W, fam._OUT, fam._ATTN, fam._DOWN, fam._EMBED = \
    0.12, 0.12, 0.15, 0.6, 1.0
# ... and balanced on a toy's sample: ONE 1,024-id sequence hands each of
# the toy's 24 router outputs 170 choices (the real 768 get 512 from the
# family's 32 sequences); the controls' margins below were read on it
fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH = 1, 1024
Z = fam.sizes_of(TOY)
TOKENS = np.random.default_rng(3).integers(0, 128, 86).astype(np.int32)


@pytest.fixture(scope="module")
def program():
    module = fam.program_model(TOY, dtype="float32")
    params = fam.program_params(module, TOY, SEED)
    return module, jax.tree.map(lambda x: x.astype(jnp.float32), params)


@pytest.fixture(scope="module")
def reference():
    return np.asarray(fam.logits(Z, SEED, TOKENS))


# ---- (a) the program against the reference ------------------------------ #
def test_the_uncached_forward_is_the_reference(program, reference):
    module, params = program
    got = jax.jit(lambda p, ids: module.apply(p, {"input_ids": ids}))(
        params, jnp.asarray(TOKENS[None]))
    assert np.abs(reference).mean() > 0.3     # the toy's layers are visible
    assert np.abs(np.asarray(got[0]) - reference).max() < TOL


def _serve_logits(module, params, tokens, prompt_len, chunk, page=8,
                  slots=3, slot=1, cache_len=160):
    """Logits at every position of ``tokens``: the prompt through prefill
    chunks of ``chunk`` (the last one padded), the rest a token a decode
    step, teacher-forced, in lane ``slot`` of ``slots`` — the other lanes
    dead, as a retired slot is (table row on the trash page).  Returns them
    with the ``moe_stats`` the last step sowed."""
    mgr = SlotPages(module, module.slot_contract(), slots, cache_len, page,
                    0, chunk, False, {})
    pools = mgr.new_pools(jnp.float32)
    mgr.reserve(slot, tokens[:prompt_len], len(tokens) - prompt_len)

    @jax.jit
    def decode(pools, ids, pages, start, live):
        (lg, pools), sown = module.apply(
            params, ids, {**pools, "pages": pages}, start, live=live,
            method=type(module).decode, mutable=["moe_stats"])
        return lg, pools, sown["moe_stats"]

    out = []
    for s0 in range(0, prompt_len, chunk):
        ids = np.zeros(chunk, np.int32)
        n = min(chunk, prompt_len - s0)
        ids[:n] = tokens[s0:s0 + n]
        lg, pools, _ = decode(pools, jnp.asarray(ids[None]),
                              jnp.asarray(mgr.row(slot)), jnp.int32(s0),
                              jnp.asarray((np.arange(chunk) < n)[None]))
        out.append(np.asarray(lg[0, :n]))
    active = np.arange(slots) == slot
    table = np.where(active[:, None], mgr.table(), 0)
    for p in range(prompt_len, len(tokens)):
        ids = np.where(active, tokens[p], 0).astype(np.int32)
        pos = np.where(active, p, cache_len - 1).astype(np.int32)
        lg, pools, sown = decode(pools, jnp.asarray(ids[:, None]),
                                 jnp.asarray(table), jnp.asarray(pos),
                                 jnp.asarray(active[:, None]))
        out.append(np.asarray(lg[slot]))
    return np.concatenate(out), sown


@pytest.mark.parametrize("chunk,prompt_len", [(16, 50), (32, 50), (64, 70),
                                              (32, 64)])
def test_prefill_then_decode_match_the_reference(program, reference, chunk,
                                                 prompt_len):
    """Chunk boundaries inside the prompt, padded last chunks, decode rows
    that straddle pages of 8, both pool layers of both double layers;
    logits, not tokens."""
    module, params = program
    got, sown = _serve_logits(module, params, TOKENS, prompt_len, chunk)
    assert np.abs(got - reference).max() < TOL
    # a decode step of one live lane: its four choices are real and held,
    # real and elsewhere, or zero — in each expert layer
    for layer in ("layers_0", "layers_1"):
        stats = sown[layer]["moe_mlp"]
        assert int(stats["expert_tokens"].sum()) + int(stats["elsewhere"]) \
            + int(stats["zero"]) == TOY["moe_topk"]


@pytest.mark.parametrize("control", fam.CONTROLS)
def test_a_control_fails_the_tolerance(reference, control):
    """Each control is the reference in bfloat16 but for one thing, and
    lies further from float32 than bfloat16 alone does — and far outside
    the tolerance the program is held to."""
    sound = np.abs(np.asarray(fam.logits(Z, SEED, TOKENS, "bfloat16"))
                   - reference).mean()
    off = np.abs(np.asarray(fam.logits(Z, SEED, TOKENS, control))
                 - reference)
    assert off.max() > 100 * TOL and off.mean() > 2 * sound


def test_chooser_control_reads_the_generated_positions():
    gaps = fam.gaps_under(Z, SEED, TOKENS[:64], 40, 24, 64,
                          [None, "float32", "zero_dropped"])
    assert all(g.shape == (24,) and (g >= 0).all() for g in gaps.values())
    assert gaps["float32"].max() == 0.0     # the reference picks its own
    assert gaps[None].max() > 0.0           # random tokens are not its picks
    assert gaps["zero_dropped"].mean() > 0.0


# ---- (b) served through the slot engine --------------------------------- #
def test_served_through_the_slot_engine(program):
    """``init_inference`` -> ``serve()`` -> ``submit`` / ``drain``: five
    requests over three slots, prompts of several chunks, decode blocks of
    three steps.  Every generated token is the float32 reference's own
    greedy choice along the request's tokens, and the router's choices are
    all accounted for: held, elsewhere, or zero."""
    module, params = program
    engine = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None,
        "serving": {"enabled": True, "num_slots": 3, "max_cache_len": 96,
                    "page_size": 8, "prefill_chunk": 16,
                    "prefill_token_budget": 64, "decode_block": 3,
                    "tracing": True}})
    engine.set_params(params)
    srv = engine.serve()
    assert srv.contract.num_layers == 4 and srv.contract.expert_layers == 2
    assert srv.contract.load_columns == ("elsewhere", "zero")
    assert srv.chunk_rows == 1 and not srv.contract.kv_pages
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, n).astype(np.int32)
               for n in (37, 50, 21, 64, 33)]
    news = [20, 13, 31, 8, 17]
    rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    done = srv.drain()
    stats = dict(srv.stats)
    srv.close()
    for rid, prompt, new in zip(rids, prompts, news):
        out = np.asarray(done[rid])
        assert len(out) == len(prompt) + new
        want = np.asarray(fam.logits(Z, SEED, out))
        at = np.arange(len(prompt) - 1, len(out) - 1)
        margin = np.sort(want[at], axis=-1)
        assert (margin[:, -1] - margin[:, -2]).min() > 10 * TOL
        assert (np.argmax(want[at], -1) == out[len(prompt):]).all()
    # every live row makes top_k choices in each of the 2 expert layers: a
    # prompt's tokens, and every generated token but the last (the first
    # comes with the admission, the last is never fed back)
    routed = sum(map(len, prompts)) + sum(news) - len(news)
    picks = stats["moe_assignments"] + stats["moe_assignments_elsewhere"] \
        + stats["moe_zero_picks"]
    assert picks == routed * TOY["moe_topk"] * 2
    # balanced over 24 outputs: a third zero, a third held (8 of 16 real)
    assert 0.25 < stats["moe_zero_picks"] / picks < 0.42
    assert 0.25 < stats["moe_assignments"] / picks < 0.42
    assert stats["causal_pairs"] > 0 and stats["latent_rows_read"] > 0


# ---- (c) the shares add up to the uncut layer --------------------------- #
def _expert_inputs(rows=64, layer=1):
    key = fam.seed_key(SEED)
    h = jax.random.normal(jax.random.fold_in(key, 5), (rows, Z["h"]))
    return key, h, fam.router_weights(Z, key, layer)


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Four shares of 4 of the toy's 16 real experts: their real parts,
    plus the zero part — computed where the token lives, so counted ONCE —
    are the uncut reference's layer."""
    key, h, w = _expert_inputs()
    uncut = fam.expert_layer(Z, key, 1, h, w, "float32", held=(0, 16))
    parts = [fam.expert_layer(Z, key, 1, h, w, "float32", held=(f, 4),
                              zero=False) for f in range(0, 16, 4)]
    zero = fam.expert_layer(Z, key, 1, h, w, "float32", held=(0, 0))
    assert all(np.abs(np.asarray(p)).mean() > 1e-3 for p in parts)
    assert np.abs(np.asarray(zero)).mean() > 1e-3
    assert np.abs(np.asarray(sum(parts) + zero - uncut)).max() < 1e-5
    # counting the zero part with every share would not
    assert np.abs(np.asarray(sum(parts) + 4 * zero - uncut)).max() > 1e-2


@pytest.mark.parametrize("first", [0, 8, 12])
@pytest.mark.parametrize("rows", [16, dropless.GROUPED_MIN_ROWS],
                         ids=["dense", "grouped"])
def test_the_programs_share_is_the_references(first, rows):
    """The program's expert layer, told it holds experts ``first .. first
    + 3`` of 16 beside 8 zero experts, against the reference's same share
    — in both of its forms: a decode step's few rows and a chunk's many."""
    key, h, w = _expert_inputs(rows)
    want = fam.expert_layer(Z, key, 1, h, w, "float32", held=(first, 4))
    experts = [fam.expert_weights(Z, key, 1, first + e) for e in range(4)]
    stack = lambda n: jnp.stack([e[n] for e in experts]).astype(jnp.float32)
    layer = MoE(hidden_size=Z["h"], num_experts=16, k=4,
                capacity_factor=None, norm_topk_prob=False,
                ffn_hidden_size=Z["ef"], dtype=jnp.float32, gated=True,
                activation=jax.nn.silu, scoring="softmax", noaux_tc=True,
                routed_scaling=6.0, zero_experts=8, held_experts=(first, 4))
    params = {"params": {
        "gate_kernel": w["router"].astype(jnp.float32),
        "select_bias": w["select_bias"].astype(jnp.float32),
        "ExpertsMLP_0": {"experts_wg": stack("wg"), "experts_wi": stack("wu"),
                         "experts_wo": stack("wd")}}}
    (got, _, _), sown = layer.apply(params, h, train=False,
                                    mutable=["moe_stats"])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    stats = sown["moe_stats"]
    assert int(stats["expert_tokens"].sum()) + int(stats["elsewhere"]) \
        + int(stats["zero"]) == rows * 4
    assert int(stats["zero"]) > 0 and int(stats["elsewhere"]) > 0


# ---- (d) the router, the dense latent layer, the contract ---------------- #
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_route_scored_is_a_plain_router(scoring):
    key = jax.random.key(1)
    x = jax.random.normal(key, (40, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 24)) * 0.3
    bias = jax.random.normal(jax.random.fold_in(key, 2), (24,)) * 0.01
    live = jnp.arange(40) % 5 != 0
    choice, gate = dropless.route_scored(
        x, w, bias, 4, renormalize=False, scaling=6.0, live=live,
        scoring=scoring)
    logits = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, -1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    order = np.argsort(-np.asarray(scores + bias), axis=1,
                       kind="stable")[:, :4]
    want_gate = 6.0 * np.take_along_axis(np.asarray(scores), order, 1)
    assert (np.asarray(choice)[live] == order[live]).all()
    assert np.abs(np.asarray(gate)[live] - want_gate[live]).max() < 1e-6
    assert (np.asarray(choice)[~live] == -1).all()
    assert (np.asarray(gate)[~live] == 0).all()
    if scoring == "softmax":
        assert np.abs(np.asarray(scores).sum(1) - 1).max() < 1e-5


def test_held_load_tells_zero_choices_from_absent_ones():
    choice = jnp.asarray([[0, 5, 16, 23], [9, 8, 17, -1]], jnp.int32)
    gate = jnp.asarray([[.1, .2, .3, .4], [.5, .6, .7, 0.]], jnp.float32)
    local, counts, elsewhere = dropless.held_load(choice, 8, 4, real=16)
    assert np.asarray(local).tolist() == [[4, 4, 4, 4], [1, 0, 4, 4]]
    assert np.asarray(counts).tolist() == [1, 1, 0, 0]
    assert int(elsewhere) == 2          # experts 0 and 5; 16, 17, 23: zero
    kept, picks = dropless.zero_gate(choice, gate, 16)
    assert np.allclose(np.asarray(kept), [0.7, 0.7]) and int(picks) == 3
    # without zero experts every live choice off the share is elsewhere
    assert int(dropless.held_load(choice, 8, 4)[2]) == 5


def test_a_dense_latent_layer_has_no_indexer():
    spec_ = LatentSpec(hidden=64, heads=4, q_rank=48, kv_rank=32, nope=16,
                       rope=8, v=16, theta=1e7, gated=False,
                       interleaved=True)
    assert spec_.window == 0 and spec_.index_topk == 0
    attn = LatentAttention(spec_, jnp.float32)
    x = jnp.ones((16, 64), jnp.float32)
    params = attn.init(jax.random.key(0), x, jnp.int32(0),
                       method=LatentAttention.chunk)
    assert sorted(params["params"]) == [
        "kv_a", "kv_a_norm", "kv_b", "o_proj", "q_a", "q_a_norm", "q_b"]
    module = fam.program_model(TOY, dtype="float32")
    pools = jax.eval_shape(lambda: module.init_paged_cache(5, 8))
    assert sorted(pools) == ["latent"]
    assert pools["latent"].shape == (4, 5, 8, 128)    # 40 -> one lane tile


def test_the_contract_and_its_work_counters():
    module = fam.program_model(TOY, dtype="float32")
    c = module.slot_contract()
    assert (c.num_layers, c.expert_layers, c.experts) == (4, 2, 8)
    assert c.own_chunk_path and not c.kv_pages and c.routes_experts
    assert c.holds_share and c.zero_experts and c.chunk_cap == 2048
    assert c.work_counters == ("latent_rows_read", "causal_pairs",
                               "flash_tiles_live", "flash_tiles_whole")
    # a chunk over positions 32 .. 47 of a slot, pages of 8, 4 pool layers
    assert c.chunk_work(32, 48, 8, 0, 4) == {
        "causal_pairs": 4 * (16 * 32 + 16 * 17 // 2),
        "latent_rows_read": 4 * 48,
        "flash_tiles_live": 4, "flash_tiles_whole": 0}
    # the cell's chunk of 512 at 512 .. 1023, eight pool layers: the tile
    # under the diagonal is kept whole, the diagonal's is not
    tiles = c.chunk_work(512, 1024, 64, 0, 8)
    assert (tiles["flash_tiles_live"], tiles["flash_tiles_whole"]) == (16, 8)
    # two live slots, 3 and 2 rows: contexts 10, 11, 12 and 20, 21
    assert c.block_work([(10, 3), (20, 2)], 0, 4) == {
        "causal_pairs": 4 * 74, "latent_rows_read": 4 * 74}
    assert fam.program_model(dict(TOY, zero_expert_num=0), dtype="float32") \
        .slot_contract().load_columns == ("elsewhere",)


@pytest.mark.parametrize("key,value,named", [
    ("zero_expert_type", "copy", "zero_expert_type"),
    ("zero_expert_type", None, "zero_expert_type"),
    ("rope_scaling", {"rope_type": "deepseek_yarn", "factor": 40},
     "rope_scaling")])
def test_what_is_not_implemented_is_refused_by_name(key, value, named):
    for refuse in (longcat.longcat_config, fam.sizes_of):
        with pytest.raises(ValueError, match=named):
            refuse(dict(TOY, **{key: value}))


# ---- (e) the configuration's count, from the module's own shapes -------- #
def test_parameter_count_is_the_configuration_files():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "longcat-flash-chat-l4-e16.json")) as f:
        cfg = json.load(f)
    module = fam.program_model(cfg)
    shapes = jax.eval_shape(module.init, jax.random.key(0),
                            {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat = jax.tree_util.tree_flatten_with_path(shapes["params"])[0]
    size = lambda keep: sum(int(np.prod(x.shape)) for path, x in flat
                            if keep([p.key for p in path]))
    parts = cfg["parameters_by_part"]
    one_layer = lambda names: names[0] == "layers_0"
    assert size(lambda n: one_layer(n) and n[1] == "attn_0"
                and not n[-1].endswith("_norm")) \
        == parts["latent_attention_each_of_8"] == 90570752
    assert size(lambda n: one_layer(n) and n[1] == "mlp_1") \
        == parts["dense_ffn_each_of_8"] == 226492416
    assert size(lambda n: one_layer(n) and n[-1] == "gate_kernel") \
        == parts["router_each_of_4"] == 4718592
    assert size(lambda n: one_layer(n) and n[-1] == "experts_wi") * 3 \
        == 16 * parts["one_expert_3x6144x2048"] \
        == parts["held_experts_16_each_of_4"]
    assert 2 * parts["latent_attention_each_of_8"] \
        + 2 * parts["dense_ffn_each_of_8"] + parts["router_each_of_4"] \
        == parts["double_layer_outside_its_experts"] == 638844928
    assert size(lambda n: n[0] == "embed_tokens") == parts["embedding"]
    assert size(lambda n: n[0] == "lm_head") == parts["head"]
    matrices = size(lambda n: len(shapes["params"]) and n[-1] not in (
        "scale", "q_a_norm", "kv_a_norm", "select_bias"))
    assert matrices == parts["matrices"]
    assert size(lambda n: True) == cfg["parameters"] \
        == parts["matrices"] + parts["norm_gains_and_biases"]
    assert round(cfg["parameters"] / 1e9, 2) == 5.17
