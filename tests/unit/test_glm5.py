"""GLM-5 with its multi-token-prediction module at a toy size on the CPU:
the program through the paged pools against the plain float32 reference
(``benchmark/families/glm5.py``), self-drafted serving against plain
serving token for token, the module's drafts and their count against the
reference's, the expert share, the parameter count.

Tolerances: program and reference are both float32 here and differ by the
order of their sums alone (``tests/unit/test_dots3.py`` has the sizes):
2e-4 absolute on logits of ~1.
"""

import functools
import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmark import spec
from deepspeed_tpu.inference.serving import slots
from deepspeed_tpu.inference.serving.paging import SlotPages

TOL = 2e-4
TOY = dict(
    attention_bias=False, first_k_dense_replace=1, hidden_act="silu",
    hidden_size=64, index_head_dim=16, index_n_heads=4, index_topk=24,
    indexer_rope_interleave=True, intermediate_size=96, kv_lora_rank=32,
    max_position_embeddings=512, moe_intermediate_size=32, moe_layer_freq=1,
    model_type="glm_moe_dsa", n_group=1, n_routed_experts=8,
    n_routed_experts_published=32, held_experts=[8, 8], n_shared_experts=1,
    norm_topk_prob=True, num_attention_heads=4, num_experts_per_tok=4,
    num_hidden_layers=2, num_key_value_heads=4, num_nextn_predict_layers=1,
    q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
    rms_norm_eps=1e-5, rope_interleave=True,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
    v_head_dim=16, vocab_size=128)
SEED = 7
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a family instance of this file's own, drawn at a toy's scales (see
# test_dots3.py): at hidden 64 the real stds give every layer nothing to add
fam = spec.Benchmark(ROOT).family("glm5")
fam._W, fam._OUT, fam._ATTN, fam._DOWN, fam._SHARED, fam._EMBED, fam._EH = \
    0.12, 0.12, 0.15, 0.3, 0.3, 1.0, 0.02
fam._SUCC = 5.0           # the toy's own, whatever the chip run set
Z = fam.sizes_of(TOY)


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def program():
    module = fam.program_model(TOY, dtype="float32")
    return module, _f32(fam.program_params(module, TOY, SEED))


def _scrambled(params):
    """The module's block and ``eh_proj`` reversed along their first axis:
    a module that has nothing to do with the model it drafts for."""
    mtp = jax.tree.map(lambda x: x[::-1], params["params"]["mtp"])
    return {"params": {**params["params"], "mtp": mtp}}


TOKENS = np.random.default_rng(3).integers(0, 128, 86).astype(np.int32)


@pytest.fixture(scope="module")
def reference():
    """The reference's full forward over ``TOKENS``, main model and
    module, computed once."""
    want, want_guess = fam.logits(Z, SEED, TOKENS, drafts=True)
    return np.asarray(want), np.asarray(want_guess)


# ---- (a) the uncached forward, main model and module ------------------ #
def test_the_uncached_forward_is_the_reference(program, reference):
    module, params = program
    got, guess = jax.jit(lambda p, ids: module.apply(
        p, {"input_ids": ids}, drafts=True))(params, jnp.asarray(TOKENS[None]))
    want, want_guess = reference
    assert np.abs(np.asarray(want)).mean() > 0.3
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < TOL
    # the last row has no next token: every other row is the reference's
    assert np.abs(np.asarray(guess[0, :-1])
                  - np.asarray(want_guess)[:-1]).max() < TOL


# ---- (b) chunked prefill, then self-drafted windows, by logits --------- #
@functools.lru_cache(maxsize=None)
def _both(module):
    """The main forward and the module's over the same rows, ONE jitted
    function a model: a case that repeats a call form (a chunk length, a
    table shape) replays the program an earlier case compiled."""
    model = type(module)

    @jax.jit
    def both(params, ids, nxt, pools, pages, start, live):
        (lg, h, pools), _ = module.apply(
            params, ids, {**pools, "pages": pages}, start, live=live,
            hidden=True, method=model.decode, mutable=["moe_stats"])
        (dl, pools), _ = module.apply(
            params, nxt, {**pools, "pages": pages}, start, hidden=h,
            live=live, method=model.draft, mutable=["moe_stats"])
        return lg, dl, pools

    return both


def _windowed_logits(module, params, tokens, prompt_len, chunk, accepts,
                     page=8, slots_n=3, slot=1, cache_len=192):
    """Main and module logits at every position of ``tokens``: the prompt
    through prefill chunks (the last padded), then verify windows of two
    rows in lane ``slot``, teacher-forced — a window whose turn in
    ``accepts`` is True holds the next token as its draft and moves two
    positions, one whose turn is False holds a WRONG draft, moves one, and
    leaves a rejected row behind for the next window to overwrite."""
    mgr = SlotPages(module, module.slot_contract(), slots_n, cache_len, page,
                    0, chunk, False, {})
    pools = mgr.new_pools(jnp.float32)
    mgr.reserve(slot, tokens[:prompt_len], len(tokens) - prompt_len)
    main, guess = {}, {}
    both = _both(module)

    def run(*args):
        lg, dl, pools = both(params, *map(jnp.asarray, args[:2]), args[2],
                             *map(jnp.asarray, args[3:]))
        return np.asarray(lg), np.asarray(dl), pools

    padded = np.concatenate([tokens, np.zeros(chunk + 2, np.int32)])
    for s0 in range(0, prompt_len, chunk):
        n = min(chunk, prompt_len - s0)
        ids = np.where(np.arange(chunk) < n, padded[s0:s0 + chunk], 0)
        lg, dl, pools = run(ids[None], padded[s0 + 1:s0 + chunk + 1][None],
                            pools, mgr.row(slot), jnp.int32(s0),
                            (np.arange(chunk) < n)[None])
        for i in range(n):
            main[s0 + i], guess[s0 + i] = lg[0, i], dl[0, i]
    active = np.arange(slots_n) == slot
    table = np.where(active[:, None], mgr.table(), 0)
    p, turn = prompt_len, 0
    while p < len(tokens):
        ok = accepts[turn % len(accepts)] and p + 1 < len(tokens)
        turn += 1
        draft = padded[p + 1] if ok else (padded[p + 1] + 1) % 128
        ids = np.where(active[:, None], [[padded[p], draft]], 0)
        nxt = np.where(active[:, None], [[padded[p + 1], padded[p + 2]]], 0)
        pos = np.where(active, p, cache_len - 2).astype(np.int32)
        lg, dl, pools = run(ids.astype(np.int32), nxt.astype(np.int32),
                            pools, table, jnp.asarray(pos),
                            np.repeat(active[:, None], 2, axis=1))
        main[p], guess[p] = lg[slot, 0], dl[slot, 0]
        if ok:
            main[p + 1], guess[p + 1] = lg[slot, 1], dl[slot, 1]
        p += 2 if ok else 1
    order = range(len(tokens))
    return (np.stack([main[i] for i in order]),
            np.stack([guess[i] for i in order]))


@pytest.mark.parametrize("chunk,prompt_len,accepts,cache_len", [
    (16, 50, [True], 96), (32, 50, [False], 192),
    (16, 41, [True, False, False], 96), (64, 70, [False, True], 192)])
def test_prefill_then_self_drafted_windows_match_the_reference(
        program, reference, chunk, prompt_len, accepts, cache_len):
    """A context (86) longer than the toy ``index_topk`` (24); windows that
    straddle page boundaries (pages of 8); every window accepted, every
    window rejected, and mixes: the main model's logits AND the module's
    at every position are the reference's full forward — in both forms of
    the window's attention (a table of 96 positions is 4 kept sets, the
    lane form; one of 192 takes the per-row form)."""
    module, params = program
    got, got_guess = _windowed_logits(module, params, TOKENS, prompt_len,
                                      chunk, accepts, cache_len=cache_len)
    want, want_guess = reference
    assert np.abs(got - want).max() < TOL
    assert np.abs(got_guess[:-1] - want_guess[:-1]).max() < TOL
    assert (np.argmax(got_guess[:-1], -1)
            == np.argmax(want_guess[:-1], -1)).all()


# ---- (c) self-drafted serving is plain serving, token for token -------- #
def _server(program, speculative, **over):
    module, params = program
    # 96 = 4 x the toy index_topk: the lane form, the cell's
    serving = {"enabled": True, "num_slots": 3, "max_cache_len": 96,
               "page_size": 8, "prefill_chunk": 16,
               "prefill_token_budget": 64, "decode_block": 3}
    if speculative:
        serving.update(speculative=True, spec_draft_model="mtp", spec_k=1)
    engine = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None,
        "serving": {**serving, **over}})
    engine.set_params(params)
    return engine, engine.serve()


def _run(server, prompts, news, eos=None):
    """One batch of requests through a (reused) server: ``(outputs, what
    its counters moved by)``."""
    _, srv = server
    before = dict(srv.stats)
    rids = [srv.submit(p, max_new_tokens=n, eos_token_id=e)
            for p, n, e in zip(prompts, news, eos or [-1] * len(prompts))]
    done = srv.drain()
    moved = {k: v - before[k] for k, v in srv.stats.items()
             if isinstance(v, int)}
    return [np.asarray(done[r]) for r in rids], moved


RNG = np.random.default_rng(0)
PROMPTS = [RNG.integers(0, 128, int(n)).astype(np.int32)
           for n in (37, 50, 21, 64, 33, 47, 30)]
# budgets of every parity (a budget that ends inside a window and one that
# ends on its edge), seven requests over three lanes (lanes retire and are
# taken again while the others run), 8-row pages (windows straddle them)
NEWS = [20, 13, 31, 8, 17, 1, 2]


@pytest.fixture(scope="module")
def servers(program):
    """One plain and one self-drafting server for the whole file: a
    server's programs compile once, whatever is submitted."""
    plain, drafted = _server(program, False), _server(program, True)
    yield plain, drafted
    plain[1].close()
    drafted[1].close()


@pytest.fixture(scope="module")
def plain(servers):
    return _run(servers[0], PROMPTS, NEWS)


def _reference_counts(outs, prompts, news, eos=None):
    """``(windows, accepted)`` summed over finished requests, by the
    reference's own module along each request's committed tokens."""
    windows = accepted = 0
    for i, (out, prompt) in enumerate(zip(outs, prompts)):
        n = len(prompt) + news[i]
        if eos and eos[i] >= 0:           # cut after the first eos
            hit = np.nonzero(out[len(prompt):] == eos[i])[0]
            n = len(prompt) + int(hit[0]) + 1 if len(hit) else n
        w, a, _ = fam.accepted_along(out[:n], fam.drafts(Z, SEED, out[:n]),
                                     len(prompt))
        windows, accepted = windows + w, accepted + a
    return windows, accepted


def test_self_drafted_serving_is_plain_serving(servers, plain):
    outs, moved = _run(servers[1], PROMPTS, NEWS)
    assert all(np.array_equal(a, b) for a, b in zip(outs, plain[0]))
    assert moved["decode_tokens"] == plain[1]["decode_tokens"]
    # the toy module agrees with its model often enough to matter ...
    assert 0.2 < moved["spec_accepted"] / moved["spec_proposed"] < 0.95
    # ... and exactly as often as the REFERENCE's module does along the
    # same tokens: the program's drafts are the reference's
    assert (moved["spec_windows"], moved["spec_accepted"]) \
        == _reference_counts(outs, PROMPTS, NEWS)
    assert moved["spec_rows_rejected"] \
        == 2 * moved["spec_windows"] - moved["spec_committed_tokens"]
    # a window routes both rows of a live lane, main layers and module
    assert moved["moe_assignments"] + moved["moe_assignments_elsewhere"] \
        > plain[1]["moe_assignments"] \
        + plain[1]["moe_assignments_elsewhere"]


@pytest.mark.parametrize("offset", [3, 4, 5, 6])
def test_an_eos_inside_a_window_ends_the_request_there(servers, plain,
                                                       offset):
    """Four consecutive generated positions as the eos: whichever windows
    the module's acceptances make, some of these sit on a window's second
    row."""
    eos = [int(out[len(p) + offset]) if n > offset + 1 else -1
           for out, p, n in zip(plain[0], PROMPTS, NEWS)]
    want, _ = _run(servers[0], PROMPTS, NEWS, eos)
    got, moved = _run(servers[1], PROMPTS, NEWS, eos)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
    assert any(len(set(w[len(p):])) > 1 and w[-1] == e
               for w, p, e in zip(want, PROMPTS, eos) if e >= 0)
    assert (moved["spec_windows"], moved["spec_accepted"]) \
        == _reference_counts(got, PROMPTS, NEWS, eos)


def test_a_scrambled_module_rejects_and_overwrites_every_window(
        program, servers, plain):
    """Acceptance ~0: every window leaves a rejected row — the main
    model's and the module's — and the next window overwrites both; the
    committed tokens do not move."""
    engine, _ = servers[1]
    engine.set_params(_scrambled(program[1]))
    try:
        outs, moved = _run(servers[1], PROMPTS, NEWS)
    finally:
        engine.set_params(program[1])
    assert all(np.array_equal(a, b) for a, b in zip(outs, plain[0]))
    assert moved["spec_accepted"] <= 0.1 * moved["spec_proposed"]
    assert moved["spec_windows"] >= 0.9 * moved["decode_tokens"]


def test_what_self_drafting_refuses(program):
    module, params = program
    with pytest.raises(ValueError, match="spec_k is 1"):
        _server(program, True, spec_k=2)
    from deepspeed_tpu.models.transformer import (Transformer,
                                                  TransformerConfig)
    dense = Transformer(TransformerConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=64, use_flash_attention=False, dtype="float32"))
    engine = deepspeed_tpu.init_inference(dense, config={
        "dtype": "float32", "serving": {
            "enabled": True, "num_slots": 2, "max_cache_len": 64,
            "speculative": True, "spec_draft_model": "mtp", "spec_k": 1}})
    engine.set_params(dense.init(
        jax.random.key(0), {"input_ids": jnp.zeros((1, 8), jnp.int32)}))
    with pytest.raises(ValueError, match="no multi-token-prediction"):
        engine.serve()
    # a separate draft model over expert layers is still refused
    with pytest.raises(ValueError, match="separate draft model"):
        _server(program, True, spec_draft_model="self", spec_k=2)


# ---- (d) the module's lane after a prompt cut into uneven chunks ------- #
@pytest.mark.parametrize("chunks", [(16, 64), (32, 64)])
def test_the_modules_rows_do_not_depend_on_the_chunking(program, chunks):
    """The chunk program fills the module's rows: row ``t`` from ``h_t``
    and token ``t + 1`` — the row of a chunk's LAST position from the next
    chunk's first token (``next_id``), the prompt's last from the first
    sampled token.  A prompt of 53 through chunks of 16 / 32 / 64 leaves
    the same rows in the pools' last layer, and the same first draft."""
    module, params = program
    prompt = np.random.default_rng(9).integers(0, 128, 53).astype(np.int32)

    def prefill(chunk):
        declared = module.slot_contract()
        mgr = SlotPages(module, declared, 2, 128, 8, 0, chunk, False, {})
        pools = mgr.new_pools(jnp.float32)
        mgr.reserve(1, prompt, 8)
        fn = slots.make_chunk_fn(module, declared, None, self_draft=True)
        n_chunks = -(-len(prompt) // chunk)
        padded = np.zeros(n_chunks * chunk, np.int32)
        padded[:len(prompt)] = prompt
        for ci in range(n_chunks):
            last = ci == n_chunks - 1
            logits, pools, load, draft = fn(
                params, pools, jnp.asarray(mgr.row(1)),
                jnp.asarray(padded[None, ci * chunk:(ci + 1) * chunk]),
                jnp.int32(ci * chunk),
                jnp.asarray([len(prompt) - 1 - ci * chunk if last
                             else chunk - 1], jnp.int32),
                jnp.asarray([-1 if last else padded[(ci + 1) * chunk]],
                            jnp.int32))
        rows = np.asarray(pools["latent"])[-1][mgr.row(1)[0]]
        return (rows.reshape(-1, rows.shape[-1])[:len(prompt)],
                int(np.argmax(np.asarray(logits[0, 0]))), int(draft[0]))

    (a, first_a, draft_a), (b, first_b, draft_b) = map(prefill, chunks)
    assert np.abs(a).mean() > 0.05
    assert np.abs(a - b).max() < TOL
    assert (first_a, draft_a) == (first_b, draft_b)
    # the first draft is the reference's guess after the first sampled token
    tokens = np.concatenate([prompt, [first_a]]).astype(np.int32)
    assert draft_a == fam.drafts(Z, SEED, tokens)[len(prompt) - 1]


# ---- (e) the shares add up to the uncut layer --------------------------- #
@pytest.mark.parametrize("layer", [1, "mtp"])
def test_the_shares_add_up_to_the_uncut_expert_layer(layer):
    """Four shares of 8 of the toy's 32 experts: their routed parts, plus
    the shared expert counted ONCE, are the uncut reference's layer — the
    main model's and the module's."""
    key = fam.seed_key(SEED)
    a = jax.random.normal(jax.random.fold_in(key, 5), (64, Z["h"]))
    w = fam.mtp_weights(Z, key) if layer == "mtp" \
        else fam.layer_weights(Z, key, layer)
    uncut = fam.expert_layer(Z, key, layer, a, w, "float32", held=(0, 32))
    parts = [fam.expert_layer(Z, key, layer, a, w, "float32", held=(f, 8),
                              shared=False) for f in range(0, 32, 8)]
    shared = fam.expert_layer(Z, key, layer, a, w, "float32", held=(0, 0))
    assert all(np.abs(np.asarray(p)).mean() > 1e-3 for p in parts)
    assert np.abs(np.asarray(sum(parts) + shared - uncut)).max() < 1e-5
    assert np.abs(np.asarray(sum(parts) + 4 * shared - uncut)).max() > 1e-2


def test_the_selection_bias_evens_the_loads(program):
    """The distilled selection bias (``balanced_biases``): on ids it was
    NOT balanced on, every published expert of the expert layer is chosen
    within 2x of its share, where the drawn bias leaves some 3x over and
    others 3x under; the program's tree carries the reference's rows."""
    from benchmark.families.dots3 import _tensor_alone
    key = fam.seed_key(SEED)
    rows = fam.balanced_biases(Z, key)
    assert rows.shape == (Z["layers"] - Z["dense_layers"] + Z["mtp"],
                          Z["experts"])
    tree = program[1]["params"]
    for row, block in ((rows[0], tree["layers_1"]),
                       (rows[-1], tree["mtp"]["block"])):
        np.testing.assert_array_equal(
            np.asarray(block["moe_mlp"]["select_bias"]),
            np.asarray(row, np.float32))
    kw = dict(sizes=fam._static(Z), precision="float32")
    g = fam.global_weights(Z, key, _tensor_alone)
    ids = jax.random.randint(jax.random.key(11), (1024,), 0, Z["vocab"])
    x = fam._embed_jit(g, ids, precision="float32")
    x = fam._ffn_jit(key, fam._attention_jit(
        x, fam.layer_weights(Z, key, 0), **kw),
        fam.layer_weights(Z, key, 0), layer=0, **kw)
    drawn = fam.layer_weights(Z, key, 1)
    x = fam._attention_jit(x, drawn, **kw)
    scores = fam._scores(fam._rms_norm(x, drawn["ln2_g"], Z["eps"]), drawn,
                         "float32")

    def loads(bias):
        _, top = jax.lax.top_k(scores + bias.astype(jnp.float32), Z["top_k"])
        return np.bincount(np.asarray(top).ravel(), minlength=Z["experts"])

    share = 1024 * Z["top_k"] / Z["experts"]
    even, uneven = loads(rows[0]) / share, loads(drawn["select_bias"]) / share
    assert even.max() < 2.0 and even.min() > 0.5
    assert uneven.max() > 2.0 and uneven.min() < 0.5


# ---- (f) the configuration's parameters, recounted from the shapes ------ #
def test_parameters_counted_from_the_shapes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm5-l5-e16.json")) as f:
        config = json.load(f)
    module = fam.program_model(config)
    shapes = jax.eval_shape(module.init, jax.random.key(0),
                            {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    size = lambda keep: sum(int(np.prod(x.shape)) for path, x in flat
                            if keep("/".join(p.key for p in path), x))
    matrices = size(lambda name, x: x.ndim > 1)
    parts = config["parameters_by_part"]
    assert matrices == parts["matrices"] == 4_802_740_224
    assert size(lambda name, x: x.ndim == 1) \
        == parts["norm_gains_and_biases"]
    assert size(lambda name, x: True) == config["parameters"]
    of = lambda *frames: size(lambda name, x: x.ndim > 1 and all(
        f in name for f in frames))
    attention = of("layers_0/attn/") - of("layers_0/attn/index_")
    assert attention == parts["attention_each_of_6"]
    assert of("layers_0/attn/index_") == parts["indexer_each_of_6"]
    assert of("layers_0/") == parts["dense_layer_0"]
    assert of("layers_3/") == parts["expert_layer_16_held_each_of_4"]
    assert of("mtp/") == parts["mtp_module_eh_proj_plus_one_expert_layer"]
    assert of("embed_tokens") == of("lm_head") == parts["embedding"]
    # the cache: six layers of 640 + 128 stored features a token, bfloat16
    pools = jax.eval_shape(lambda: module.init_paged_cache(2, 64))
    assert sum(x.shape[0] * x.shape[-1] * 2
               for x in jax.tree.leaves(pools)) == 6 * 1536


# ---- (g) dots3 did not move when its block was factored out ------------- #
def test_dots3s_parameter_tree_is_what_it_was():
    """``models/dots3.py``'s layers are ``models/latent_block.py``'s now:
    the same 80 leaves under the same names and shapes as before the
    factoring (the digest is the parent commit's; the toy logits against
    the reference are ``test_dots3.py``'s)."""
    import test_dots3
    module = test_dots3.fam.program_model(test_dots3.TOY, dtype="float32")
    shapes = jax.eval_shape(module.init, jax.random.key(0),
                            {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    tree = sorted(("/".join(p.key for p in path), tuple(x.shape))
                  for path, x in flat)
    assert len(tree) == 80
    assert any(name.endswith("layers_0/attn/gate") for name, _ in tree)
    assert hashlib.sha256(repr(tree).encode()).hexdigest()[:16] \
        == "feb253ac5e79eaf5"


# ---- (g2) what a chunk's span carries ------------------------------------ #
@pytest.mark.parametrize("layers", [None, "with_the_module"])
def test_chunk_work_counts_the_live_blocks(program, layers):
    """A chunk's attention work over the main model's layers, or those and
    the module's (what a self-drafting server's dispatches run): the rows it
    decompresses are the live 512-key blocks, whole — not the slot's lane."""
    declared = program[0].slot_contract()
    assert declared.drafts_itself and declared.draft_layers == 1
    n = declared.num_layers + (declared.draft_layers if layers else 0)
    work = declared.chunk_work(1024, 2500, 64, 0, n)
    assert work["latent_rows_read"] == n * 40 * 64
    assert work["latent_rows_decompressed"] == n * 5 * 512
    assert work["dsa_keys_kept"] <= work["dsa_keys_scored"] \
        == n * sum(range(1025, 2501))
    # query tiles at 1024, 1536 and 2048 over 3, 4 and 5 key tiles; past
    # the toy ``index_topk`` (24) no tile is known to be kept whole
    assert (work["flash_tiles_live"], work["flash_tiles_whole"]) \
        == (n * 12, 0)


# ---- (h) the lane kernels against plain math ---------------------------- #
# page 8, blocks of 4 pages (32 keys), 8 pages a lane; context 0: a dead lane
_LANE_CASES = {
    "inside_a_page_a_block_and_on_the_tables_edge": (1, (37, 64, 5)),
    "a_dead_lane_between_two_live": (2, (38, 0, 64)),
    "two_rows_a_lane": (2, (17, 33, 49)),
    "contexts_of_a_few_rows": (1, (1, 2, 3)),
    "a_dead_lane_first": (1, (0, 41, 20)),
    "a_dead_lane_last": (2, (41, 20, 0)),
    "two_dead_lanes_in_a_row": (1, (9, 0, 0, 50, 0, 0, 33)),
    "every_lane_dead": (2, (0, 0, 0)),
    "a_lane_of_one_page": (1, (8, 7, 2)),
    "on_a_page_edge_and_on_a_block_edge": (2, (24, 32, 40, 64)),
    # one block a lane, then two: the ring's parity is carried
    "odd_blocks_then_even": (1, (30, 64, 64, 12, 40, 0, 33, 64)),
}


@pytest.mark.parametrize("poison", [False, True],
                         ids=["finite_pool", "nan_off_the_live_pages"])
@pytest.mark.parametrize("case", sorted(_LANE_CASES))
def test_the_lane_kernels_are_the_plain_math(case, poison):
    """``attn.dsa_lane_index`` and ``attn.mla_lane_decode`` over a pool
    read THROUGH a shuffled table — contexts that end inside a page, on a
    page's edge, on a block's and on the table's, dead lanes (context 0:
    the table at the trash page) wherever the hand-over from lane to lane
    can meet them, block counts that flip the ring's parity — against the
    gathered lane in plain ``jnp``.  A lane's pages past its last live one
    are not fetched: with NaN in every such page, and in the trash page,
    the results are the same and finite, and the scores past the last
    live PAGE read ``NEG``."""
    from deepspeed_tpu.ops.transformer import latent_attention as ops
    rows, contexts = _LANE_CASES[case]
    rng = np.random.default_rng(rows + sum(contexts))
    N, page, n, H, J, D, rank, rope = len(contexts), 8, 8, 4, 2, 16, 32, 8
    width, layers, pages = 128, 2, 1 + N * n
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    latent, index = f(layers, pages, page, width), f(layers, pages, page, D)
    table = rng.permutation(np.arange(1, pages)).reshape(N, n)
    ctx = np.asarray(contexts, np.int32)
    table = np.where(ctx[:, None] > 0, table, 0).astype(np.int32)
    lane, bp = ops.lane_pages(jnp.asarray(table), page, block_keys=32)
    L = lane.shape[1] * page
    live_pages = -(-ctx // page)
    # the reference reads the clean pools; the kernels, where ``poison``,
    # pools that hold NaN wherever they have no business reading
    clean_latent, clean_index = latent, index
    if poison:
        dead = np.ones((pages,), bool)
        for row, k in zip(table, live_pages):
            dead[row[:k]] = False
        dead[0] = True
        latent = latent.at[:, dead].set(jnp.nan)
        index = index.at[:, dead].set(jnp.nan)
    q, w = f(N, rows, J, D), f(N, rows, J)
    got, same = ops.lane_index_scores(q, w, index, 1, lane, bp,
                                      jnp.asarray(ctx))
    assert np.array_equal(same, index, equal_nan=True)
    keys = clean_index[1][lane].reshape(N, L, D)
    want = jnp.einsum("nwjl,nwj->nwl", jnp.maximum(
        jnp.einsum("nwjd,nld->nwjl", q, keys), 0.0), w)
    scored = (np.arange(L)[None] < live_pages[:, None] * page)[:, None]
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.where(scored, got - want, 0.0)).max() < 1e-4
    assert (np.where(scored, ops.NEG, got) <= ops.NEG / 2).all()
    # each row keeps a random half of the positions it may see
    pos = ctx[:, None] - rows + np.arange(rows)[None]
    kept = (np.arange(L)[None, None] <= pos[..., None]) \
        & (rng.random((N, rows, L)) < 0.5)
    kept[1, :, :] &= np.arange(L) != 3          # and one row keeps nothing
    kept[0, 0, :] = False
    qq = jnp.pad(f(N, rows, H, rank + rope),
                 ((0, 0), (0, 0), (0, 0), (0, width - rank - rope)))
    got, same = ops.lane_decode(qq, jnp.asarray(kept, jnp.int8), latent, 0,
                                lane, bp, jnp.asarray(ctx), rank, 0.25)
    assert np.array_equal(same, latent, equal_nan=True)
    lat = clean_latent[0][lane].reshape(N, L, width)
    s = jnp.einsum("nwhd,nld->nwhl", qq, lat) * 0.25
    on = jnp.asarray(kept)[:, :, None, :]
    p = jnp.where(on, jnp.exp(s - jnp.max(jnp.where(on, s, -1e30), -1,
                                          keepdims=True)), 0.0)
    want = jnp.einsum("nwhl,nlr->nwhr", p, lat[..., :rank]) \
        / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    assert got.shape == (N, rows, H, rank)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert np.abs(np.asarray(got[0, 0])).max() == 0.0
    assert np.abs(np.asarray(got)[ctx == 0]).max(initial=0.0) == 0.0
