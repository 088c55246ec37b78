"""OLMoE through the program (``models/olmoe.py``, ``moe/dropless.py``, the
paged serving programs) against the benchmark's plain float32 reference
(``benchmark/families/olmoe.py``), at a toy size that keeps every
mechanism: hidden 64, 4 heads of 16, QK-norm, rope, 8 SwiGLU experts of
width 32, top-2 un-renormalised, 2 layers, vocab 128.  Weights are the
family's own — normal draws from a seed, bf16-exact — so program and
reference share nothing but the seed."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark import spec
from deepspeed_tpu.inference.serving import slots
from deepspeed_tpu.models.olmoe import olmoe_config
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.sharded_moe import topkgating

TOY = dict(hidden_size=64, intermediate_size=32, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=4, num_experts=8,
           num_experts_per_tok=2, norm_topk_prob=False, vocab_size=128,
           max_position_embeddings=128, hidden_act="silu",
           rms_norm_eps=1e-5, rope_theta=10000.0, attention_bias=False,
           clip_qkv=None, tie_word_embeddings=False)
LAYERS, TOP_K, EXPERTS = 2, 2, 8
PAGE, PAGES_A_SLOT, SEED = 16, 4, 5


@pytest.fixture(scope="module")
def fam():
    return spec.Benchmark().family("olmoe")


@functools.lru_cache(maxsize=None)
def _program(dtype):
    fam = spec.Benchmark().family("olmoe")
    module = fam.program_model(TOY, dtype=dtype)
    params = fam.program_params(module, TOY, SEED)
    if dtype == "float32":
        params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    return module, params


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], shape).astype(np.int32)


# --------------------------------------------------------------------- #
# 1. whole forward, program against reference
# --------------------------------------------------------------------- #
def test_float32_program_logits_equal_the_reference(fam):
    """In float32 program and reference compute the same mathematics on
    the same weights, so the routing coincides and what is left is the
    order of float32 sums (kernel tiles against one matmul, the expert
    accumulation): 1e-4 on logits of size ~0.6 is a hundred times that
    and a thirtieth of what bfloat16 arithmetic leaves (3e-3)."""
    module, params = _program("float32")
    toks = _tokens((2, 24))
    got = module.apply(params, jnp.asarray(toks), method=type(module).logits)
    want = fam.logits(fam.sizes_of(TOY), SEED, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=0)


def test_bfloat16_program_within_a_bound_the_float8_control_fails(fam):
    """The program in the configuration's bfloat16 against the float32
    reference: the largest logit error stays under 1e-2 (measured 3e-3:
    bfloat16's 2^-9 relative steps through 2 layers, and the rare top-2
    flip where two gates are within rounding).  The control — the
    reference computed in float8, the precision below — reads 3.6e-2 and
    must fail the same bound, or the bound holds nothing."""
    module, params = _program("bfloat16")
    z, toks = fam.sizes_of(TOY), _tokens((2, 24))
    want = np.asarray(fam.logits(z, SEED, toks))
    got = np.asarray(module.apply(
        params, jnp.asarray(toks), method=type(module).logits), np.float32)
    control = np.asarray(fam.logits(z, SEED, toks, "float8"))
    assert np.abs(got - want).max() < 1e-2
    assert np.abs(control - want).max() > 2e-2


# --------------------------------------------------------------------- #
# 2. the paged serving path: chunks, then decode steps
# --------------------------------------------------------------------- #
def _pool_and_tables(module, lanes):
    pool = module.init_paged_cache(lanes * PAGES_A_SLOT + 1, PAGE,
                                   dtype=jnp.float32)
    tables = 1 + np.arange(lanes * PAGES_A_SLOT, dtype=np.int32).reshape(
        lanes, PAGES_A_SLOT)
    return pool, tables


def test_paged_chunks_then_decode_match_the_reference_logits(fam):
    """Chunked prefill (chunks of 8, the last one padded) and then
    single-token decode steps through the page pool, as the serving
    programs run them, against the reference's ONE full forward over
    prompt + generated tokens — logits, position by position.  float32
    program: the tolerance of the whole-forward test."""
    module, params = _program("float32")
    z = fam.sizes_of(TOY)
    prompt, n_new, C = _tokens((21,), seed=3), 6, 8
    pool, tables = _pool_and_tables(module, 1)
    row = jnp.asarray(tables[:1])
    chunk_fn = slots.make_chunk_fn(module, module.slot_contract(), None)
    padded = np.zeros((1, 24), np.int32)
    padded[0, :21] = prompt
    chunk_logits = []
    for ci in range(3):
        last = min(21 - 1 - ci * C, C - 1)
        logits, pool, load = chunk_fn(
            params, pool, row, jnp.asarray(padded[:, ci * C:(ci + 1) * C]),
            jnp.asarray(ci * C, jnp.int32), jnp.asarray([last], jnp.int32))
        chunk_logits.append((ci * C + last, np.asarray(logits)[0, 0]))
        assert int(np.asarray(load)[:-2].sum()) == (last + 1) * TOP_K * LAYERS
    toks, step_logits = list(prompt), []
    nxt = int(np.argmax(chunk_logits[-1][1]))
    step = jax.jit(lambda ids, pool, pos: slots._decode(    # traced once
        module, params, ids, {**pool, "pages": row}, pos,
        live=jnp.ones((1, 1), bool)))
    for i in range(n_new):
        toks.append(nxt)
        logits, cache, counts = step(jnp.asarray([[nxt]], jnp.int32), pool,
                                     jnp.asarray([21 + i], jnp.int32))
        pool = {k: cache[k] for k in pool}
        step_logits.append(np.asarray(logits)[0, 0])
        nxt = int(np.argmax(step_logits[-1]))
    want = np.asarray(fam.logits(z, SEED, np.asarray([toks], np.int32)))[0]
    for pos, got in chunk_logits:
        np.testing.assert_allclose(got, want[pos], atol=1e-4, rtol=0)
    for i, got in enumerate(step_logits):
        np.testing.assert_allclose(got, want[21 + i], atol=1e-4, rtol=0)


SERVING = {"enabled": True, "num_slots": 3, "max_cache_len": 64,
           "prefill_chunk": 8, "decode_block": 2, "paged": True,
           "page_size": PAGE}


@pytest.fixture(scope="module")
def served():
    """Six requests through ``init_inference`` -> ``serve()`` -> ``submit``
    / ``drain`` on three slots: slot churn, padded chunk tails, dead lanes
    inside blocks."""
    module, params = _program("float32")
    eng = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None, "serving": SERVING})
    eng.set_params(params)
    srv = eng.serve()
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, 128, int(n)).astype(np.int32), int(k))
            for n, k in zip(rng.integers(5, 30, 6), rng.integers(3, 12, 6))]
    rids = [srv.submit(p, max_new_tokens=k) for p, k in reqs]
    outs = srv.drain()
    return srv, reqs, [np.asarray(outs[r]) for r in rids]


def test_slot_engine_serves_the_reference_choice(fam, served):
    """Through the scheduler: every generated token's REFERENCE logit is
    the reference's largest at its position, to the float32 tolerance —
    the benchmark's ``correct`` statistic, at toy size."""
    srv, reqs, outs = served
    assert srv.kernel_modes == {"decode": "pallas_paged_decode",
                                "prefill_chunk": "pallas_chunked_prefill"}
    z = fam.sizes_of(TOY)
    for (prompt, n_new), out in zip(reqs, outs):
        assert len(out) == len(prompt) + n_new
        gaps = fam.chosen_gaps(z, SEED, out, len(prompt), n_new, 64)
        assert gaps.max() <= 1e-4


def test_slot_engine_counts_live_assignments_only(served):
    """``srv.stats`` / ``srv.moe_expert_tokens``: every token that went
    through the model — prompt tokens in their chunks, each generated token
    but a request's last — chose ``top_k`` experts in each layer; dead
    lanes and padded tails chose none."""
    srv, reqs, _ = served
    live_tokens = sum(len(p) + k - 1 for p, k in reqs)
    assert srv.stats["moe_assignments"] == live_tokens * TOP_K * LAYERS
    assert srv.moe_expert_tokens.shape == (LAYERS, EXPERTS)
    assert srv.moe_expert_tokens.sum() == srv.stats["moe_assignments"]
    assert (srv.moe_expert_tokens.sum(axis=1)
            == live_tokens * TOP_K).all()
    assert 0 < srv.stats["moe_experts_touched"] \
        <= srv.stats["moe_assignments"]
    assert srv.stats["moe_max_expert_tokens"] >= \
        srv.stats["moe_assignments"] / EXPERTS


@pytest.mark.parametrize("refused", [{"speculative": True, "spec_k": 2}])
def test_slot_engine_refuses_what_it_cannot_route(refused):
    module, params = _program("float32")
    eng = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None,
        "serving": {**SERVING, **refused}})
    eng.set_params(params)
    with pytest.raises(ValueError, match="expert layers"):
        eng.serve(**({"draft_module": module, "draft_params": params}
                     if "speculative" in refused else {}))


# --------------------------------------------------------------------- #
# 3. dropless
# --------------------------------------------------------------------- #
def _same_expert_inputs():
    """32 tokens that ALL choose experts 3 and 5: positive inputs, a router
    whose columns 3 and 5 are large and positive."""
    ks = jax.random.split(jax.random.key(7), 5)
    x = jnp.abs(jax.random.normal(ks[0], (1, 32, 64))) + 0.5
    gate = 0.01 * jax.random.normal(ks[1], (64, EXPERTS))
    gate = gate.at[:, 3].set(0.05).at[:, 5].set(0.03)
    w = {"router": gate,
         "wg": 0.1 * jax.random.normal(ks[2], (EXPERTS, 64, 32)),
         "wu": 0.1 * jax.random.normal(ks[3], (EXPERTS, 64, 32)),
         "wd": 0.1 * jax.random.normal(ks[4], (EXPERTS, 32, 64))}
    params = {"params": {"gate_kernel": w["router"], "ExpertsMLP_0": {
        "experts_wg": w["wg"], "experts_wi": w["wu"],
        "experts_wo": w["wd"]}}}
    return x, w, params


def _layer(capacity_factor):
    return MoE(hidden_size=64, num_experts=EXPERTS, k=TOP_K,
               capacity_factor=capacity_factor,
               eval_capacity_factor=capacity_factor or 1.0,
               norm_topk_prob=False, ffn_hidden_size=32, gated=True,
               activation=jax.nn.silu, dtype=jnp.float32)


def test_dropless_computes_every_pair_when_all_tokens_pick_one_expert(fam):
    """The worst imbalance: the dropless layer still equals the reference
    (an expert's queue has no end), where the GShard gate at capacity
    factor 1 keeps 8 of an expert's 32 tokens and zeroes the rest."""
    x, w, params = _same_expert_inputs()
    z = fam.sizes_of(TOY)
    want = np.asarray(fam._experts(z, x, w, "float32"))
    got, _, counts = _layer(None).apply(params, x, train=False)
    np.testing.assert_array_equal(np.asarray(counts),
                                  [0, 0, 0, 32, 0, 32, 0, 0])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=0)
    dropped, _, _ = _layer(1.0).apply(params, x, train=False)
    lost = np.abs(np.asarray(dropped) - want).max(axis=-1)[0]
    assert (lost > 1e-3).sum() >= 16      # most tokens lost an expert


# --------------------------------------------------------------------- #
# 4. dead lanes and padded tails
# --------------------------------------------------------------------- #
def _decode_logits(module, params, pool, tables, toks, pos, live):
    logits, _, counts = jax.jit(functools.partial(slots._decode, module))(
        params, jnp.asarray(toks), {**pool, "pages": jnp.asarray(tables)},
        jnp.asarray(pos, jnp.int32), live=jnp.asarray(live))
    return np.asarray(logits), np.asarray(counts)


def test_dead_lanes_change_no_live_lane_and_are_not_counted():
    """A decode step over four lanes, two of them dead (their table rows on
    the trash page, as the decode block presents them): the live lanes'
    logits are BITWISE the same whether the dead lanes hold zeros or
    garbage, and the load counts the live lanes only."""
    module, params = _program("float32")
    pool, tables = _pool_and_tables(module, 4)
    pool = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(1), a.shape, a.dtype),
        pool)
    live = np.array([True, False, True, False])
    tables = np.where(live[:, None], tables, 0)
    runs = []
    for dead_tok, dead_pos in ((0, 0), (97, 63)):
        toks = np.where(live, [11, dead_tok, 45, dead_tok], dead_tok)
        pos = np.where(live, [9, 0, 30, 0], dead_pos)
        runs.append(_decode_logits(module, params, pool, tables,
                                   toks[:, None], pos, live[:, None]))
    (a, counts_a), (b, counts_b) = runs
    np.testing.assert_array_equal(a[live], b[live])
    np.testing.assert_array_equal(counts_a, counts_b)
    assert counts_a.shape == (LAYERS, EXPERTS)
    assert (counts_a.sum(axis=1) == 2 * TOP_K).all()


def test_a_chunks_padded_tail_changes_no_real_row_and_is_not_counted():
    """One 8-token chunk with 5 real tokens: the real rows' logits are
    bitwise the same under two different paddings, and 5 x top_k
    assignments a layer are counted."""
    module, params = _program("float32")
    pool, tables = _pool_and_tables(module, 1)
    live = (np.arange(8) < 5)[None]
    runs = []
    for pad in (0, 101):
        toks = np.where(live, _tokens((1, 8), seed=4), pad)
        runs.append(_decode_logits(module, params, pool, tables[:1], toks,
                                   0, live))
    (a, counts_a), (b, counts_b) = runs
    np.testing.assert_array_equal(a[0, :5], b[0, :5])
    np.testing.assert_array_equal(counts_a, counts_b)
    assert (counts_a.sum(axis=1) == 5 * TOP_K).all()


# --------------------------------------------------------------------- #
# 5. sizes; the kernels against plain einsums; the small repairs
# --------------------------------------------------------------------- #
def test_num_params_of_the_published_config():
    assert olmoe_config().num_params() == 6_919_161_856
    assert olmoe_config({"num_hidden_layers": 8}).num_params() \
        == 3_562_604_544


def test_num_params_counts_what_init_builds():
    module, params = _program("float32")
    built = sum(x.size for x in jax.tree.leaves(params))
    assert module.config.num_params() == built


def _oracle(x, gate_w, k, renormalize, live, wg, wu, wd):
    gates = jax.nn.softmax(x.astype(jnp.float32)
                           @ gate_w.astype(jnp.float32), -1)
    top_w, top_i = jax.lax.top_k(gates, k)
    if renormalize:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    onehot = (top_i[:, :, None] == jnp.arange(gate_w.shape[1])) \
        & live[:, None, None]
    combine = jnp.sum(jnp.where(onehot, top_w[:, :, None], 0.0), 1)
    up = jnp.einsum("tm,emf->etf", x, wu)
    hid = jax.nn.silu(jnp.einsum("tm,emf->etf", x, wg)) * up \
        if wg is not None else jax.nn.silu(up)
    out = jnp.einsum("etf,efm->tm", hid * combine.T[:, :, None], wd)
    return combine, onehot.sum((0, 1)), out


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("renormalize", [False, True])
@pytest.mark.parametrize("tokens,width", [(5, 32), (32, 256), (48, 384)])
def test_kernels_match_plain_einsums(tokens, width, renormalize, gated):
    """``moe.route`` + ``moe.experts_gmm`` (interpreted) against softmax /
    ``lax.top_k`` / three einsums over all experts: odd token counts (row
    padding), several width tiles, dead tokens, untouched experts."""
    ks = jax.random.split(jax.random.key(tokens), 5)
    x = jax.random.normal(ks[0], (tokens, 64))
    gate_w = 0.3 * jax.random.normal(ks[1], (64, EXPERTS))
    gate_w = gate_w.at[:, 6].set(-5.0 * jnp.sign(x.sum(0)))  # never chosen
    wg = 0.1 * jax.random.normal(ks[2], (EXPERTS, 64, width)) \
        if gated else None
    wu = 0.1 * jax.random.normal(ks[3], (EXPERTS, 64, width))
    wd = 0.1 * jax.random.normal(ks[4], (EXPERTS, width, 64))
    live = jnp.arange(tokens) % 3 != 1
    combine, counts = dropless.route(x, gate_w, TOP_K, renormalize, live)
    out = dropless.experts(x, combine, counts, wg, wu, wd, jax.nn.silu)
    want_c, want_n, want_out = _oracle(x, gate_w, TOP_K, renormalize, live,
                                       wg, wu, wd)
    np.testing.assert_allclose(np.asarray(combine)[:tokens], want_c,
                               atol=1e-6)
    assert not np.asarray(combine)[tokens:].any()
    np.testing.assert_array_equal(np.asarray(counts), want_n)
    np.testing.assert_allclose(np.asarray(out), want_out, atol=1e-5)


def _dispatch_layer(rows, dtype=jnp.float32):
    """The softmax layer over ``rows`` chunk rows of 128 tokens — what a
    chunk dispatch hands it — with a padded tail in row 1 and the last row
    DEAD: ``(apply(x, live) -> (y, counts), x, live, params)``."""
    layer = _layer(None).clone(dtype=dtype)
    ks = jax.random.split(jax.random.key(rows), 2)
    x = jax.random.normal(ks[0], (rows, 128, 64), dtype)
    last = np.full((rows,), 127)
    last[1], last[-1] = 40, -1
    live = jnp.arange(128)[None, :] <= jnp.asarray(last)[:, None]
    params = layer.init(ks[1], x[:1, :8], train=False)
    apply = lambda x, live: layer.apply(params, x, train=False,
                                        live=live)[::2]
    return apply, x, live, params["params"]


def _expert_kernels(fn, *args):
    return set(re.findall(r"name=(moe\.experts[a-z_]*)",
                          str(jax.make_jaxpr(fn)(*args))))


def test_a_dispatch_of_512_rows_takes_the_sorted_form():
    """Four rows of 128 are one 512-token call of the softmax layer:
    ``moe.experts_grouped`` (interpreted) over the router's own picks —
    against the float32 oracle, and against the gmm form the same tokens
    take three rows at a time; dead tokens (a padded tail, a whole dead
    row) get zeros from both and are counted by neither."""
    apply, x, live, p = _dispatch_layer(4)
    assert _expert_kernels(apply, x, live) == {"moe.experts_grouped"}
    assert _expert_kernels(apply, x[:3], live[:3]) == {"moe.experts_gmm"}
    y, counts = apply(x, live)
    w = p["ExpertsMLP_0"]
    _, want_n, want = _oracle(
        x.reshape(-1, 64), p["gate_kernel"], TOP_K, False,
        live.reshape(-1), w["experts_wg"], w["experts_wi"], w["experts_wo"])
    np.testing.assert_array_equal(np.asarray(counts), want_n)
    assert int(counts.sum()) == (128 + 41 + 128) * TOP_K
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 64), want,
                               atol=1e-5)
    assert not np.asarray(y)[~np.asarray(live)].any()
    gmm = jnp.concatenate([apply(x[:3], live[:3])[0],
                           apply(x[3:], live[3:])[0]])
    np.testing.assert_allclose(np.asarray(y), np.asarray(gmm), atol=1e-5)


def test_the_sorted_form_in_bfloat16_is_as_close_as_the_gmm_form():
    """The configuration's precision: both forms of the 512-token call
    against the float32 oracle on the same bfloat16-exact inputs — the
    sorted form rounds each pair's output before the gates weigh it, the
    gmm form each gated hidden row before the down projection; neither is
    the closer by more than a factor of two."""
    apply, x, live, p = _dispatch_layer(4, jnp.bfloat16)
    w = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                     p["ExpertsMLP_0"])
    _, _, want = _oracle(
        x.reshape(-1, 64).astype(jnp.float32), p["gate_kernel"], TOP_K,
        False, live.reshape(-1), w["experts_wg"], w["experts_wi"],
        w["experts_wo"])
    err = lambda y: float(jnp.abs(
        y.reshape(-1, 64).astype(jnp.float32) - want).mean())
    grouped = err(apply(x, live)[0])
    gmm = err(jnp.concatenate([apply(x[:2], live[:2])[0],
                               apply(x[2:], live[2:])[0]]))
    assert 0 < grouped < 2 * gmm and gmm < 2 * grouped


def test_picks_of_names_no_expert_for_a_dead_token():
    combine = jnp.zeros((4, EXPERTS)).at[0, 5].set(0.7).at[0, 2].set(0.1) \
        .at[2, 7].set(0.4).at[2, 0].set(0.4)
    local, gate = dropless.picks_of(combine, TOP_K)
    np.testing.assert_array_equal(
        np.asarray(local), [[5, 2], [EXPERTS] * 2, [0, 7], [EXPERTS] * 2])
    np.testing.assert_allclose(
        np.asarray(gate), [[0.7, 0.1], [0, 0], [0.4, 0.4], [0, 0]])


def test_the_sorted_layout_gathers_from_no_expert_sized_table():
    """What the layout needs of an expert (its first row, its first place
    in the sorted order) is looked up as a one-hot sum: a ``gather`` of
    T x k indices into a ``[experts]`` table cost XLA's TPU compiler ~0.4 s
    apiece, two a layer — 9 s of ``setup_s`` in an eight-layer chunk
    program (PERF.md section 6, PR 59)."""
    jaxpr = jax.make_jaxpr(lambda local: dropless.grouped_layout(
        local, 64, 128))(jnp.zeros((512, TOP_K), jnp.int32))
    tables = [eqn.invars[0].aval.shape for eqn in jaxpr.jaxpr.eqns
              if eqn.primitive.name == "gather"]
    assert tables and all(shape[0] >= 512 * TOP_K for shape in tables), \
        tables


def test_no_live_token_gives_zeros():
    x = jax.random.normal(jax.random.key(0), (16, 64))
    w = 0.1 * jax.random.normal(jax.random.key(1), (EXPERTS, 64, 32))
    combine, counts = dropless.route(x, w[:, :, 0].T, TOP_K,
                                     live=jnp.zeros((16,), bool))
    out = dropless.experts(x, combine, counts, w, w,
                           w.transpose(0, 2, 1), jax.nn.silu)
    assert not np.asarray(counts).any() and not np.asarray(out).any()


def test_topkgating_renormalises_only_when_asked():
    logits = jax.random.normal(jax.random.key(2), (16, EXPERTS))
    gates = np.asarray(jax.nn.softmax(logits, -1))
    top2 = np.sort(gates, axis=-1)[:, -2:].sum(-1)
    kw = dict(k=2, capacity_factor=8.0, drop_tokens=False)
    _, combine, _, _ = topkgating(logits, **kw)
    np.testing.assert_allclose(np.asarray(combine).sum((1, 2)), 1.0,
                               atol=1e-6)
    _, combine, _, _ = topkgating(logits, norm_topk_prob=False, **kw)
    np.testing.assert_allclose(np.asarray(combine).sum((1, 2)), top2,
                               atol=1e-6)


def test_gated_experts_take_the_capacity_path_too():
    """A model that TRAINS through gated experts gives its gate a capacity:
    the GShard batch form over the same three matrices.  With room for
    every token it equals the dropless layer."""
    x, _, params = _same_expert_inputs()
    roomy, _, _ = _layer(8.0).apply(params, x, train=False)
    free, _, _ = _layer(None).apply(params, x, train=False)
    np.testing.assert_allclose(np.asarray(roomy), np.asarray(free),
                               atol=1e-5)
