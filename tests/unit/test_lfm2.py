"""LFM2 (``lfm2_moe``) at a toy size on the CPU: the program through the
SLOT ENGINE — the chunk step, the admit and the decode block that
``serving/slots.py`` builds for every model, over ``paging.SlotPages``'
pools — against the plain float32 reference (``benchmark/families/
lfm2.py``); the conv state's hand-overs; the router; the cache manager's
state kind.

Tolerances: program and reference are both float32 here, so they differ by
the order of their sums alone (a paged kernel's online softmax, the
expert kernel's accumulation, the taps summed with or without the state's
two).  Logits are ~1 in size; ``TOL`` 1e-4 absolute is twenty times what
those reorderings give at these sizes (5e-6) and a thousand times under
what one stale state row, one wrong page or a missing expert moves them by
(``test_a_stale_state_is_visible`` reads 0.1 and more).
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmark import spec
from deepspeed_tpu.inference.serving import slots
from deepspeed_tpu.inference.serving.paging import SlotPages
from deepspeed_tpu.models.lfm2 import lfm2_config
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.moe.layer import MoE

TOL = 1e-4
TOY = dict(
    model_type="lfm2_moe", conv_L_cache=3, conv_bias=False, hidden_size=64,
    intermediate_size=160,
    layer_types=["conv", "conv", "full_attention", "conv", "conv",
                 "full_attention"],
    max_position_embeddings=512, moe_intermediate_size=48, norm_eps=1e-5,
    norm_topk_prob=True, num_attention_heads=4, num_dense_layers=2,
    num_experts=8, num_experts_per_tok=2, num_hidden_layers=6,
    num_key_value_heads=2,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=128)
SEED, CHUNK, PAGE, BLOCK = 7, 8, 8, 4
MOE_LAYERS, TOP_K = 4, 2
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a family instance of this file's own, drawn at a toy's scales: at hidden
# 64 the real stds give every layer nothing to add, so they are scaled
# until a toy layer weighs what a real one does (sqrt(hidden) x std ~ 1)
fam = spec.Benchmark(ROOT).family("lfm2")
fam._W, fam._OUT, fam._DOWN, fam._EMBED, fam._OWN = 0.12, 0.2, 0.3, 0.15, 0.5
Z = fam.sizes_of(TOY)


@pytest.fixture(scope="module")
def program():
    module = fam.program_model(TOY, dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          fam.program_params(module, TOY, SEED))
    return module, params


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, 128, n) \
        .astype(np.int32)


# the rows of logits the programs' sample function saw since an Engine last
# cleared it, and the programs by (model, lane): every Engine of a module is
# the same three programs, traced and compiled ONCE a test session — an
# Engine owns its pools, state and pages, not its executables
_SEEN, _PROGRAMS = [], {}


def _programs(module, contract, cache_len):
    key = (type(module), module.config, cache_len)
    if key not in _PROGRAMS:
        def sample(logits, rng):
            jax.debug.callback(lambda l: _SEEN.append(np.asarray(l)),
                               logits, ordered=True)
            return jnp.argmax(logits, axis=-1)

        _PROGRAMS[key] = (
            slots.make_chunk_fn(module, contract, None),
            slots.make_admit_fn(sample),
            slots.make_decode_block_fn(module, contract, sample, None, BLOCK,
                                       cache_len))
    return _PROGRAMS[key]


class Engine:
    """The slot programs as ``ServingEngine`` builds and calls them, with a
    scheduler a test can read: ``admit`` runs a request's chunks and the
    admit program, ``block`` one decode block.  The sample function is
    greedy and hands every row of logits it sees to the host, so
    ``logits[rid]`` is what the ENGINE computed for each token it
    generated: the last prompt position's (the admit), then a decode
    step's a token."""

    def __init__(self, module, params, num_slots=2, cache_len=64):
        self.module, self.params = module, params
        self.stats = {}
        contract = module.slot_contract()
        self.pages = SlotPages(module, contract, num_slots, cache_len, PAGE,
                               0, CHUNK, False, self.stats)
        self.pools = self.pages.new_pools(jnp.float32)
        self.state = {k: jnp.asarray(v) for k, v in
                      slots.init_slot_state(num_slots).items()}
        self._seen = _SEEN
        self.chunk_fn, self.admit_fn, self.decode_fn = _programs(
            module, contract, self.pages.cache_len)
        self.rng = jax.random.key(0)
        self.lanes = {}                  # slot -> [rid, tokens left]
        self.tokens, self.logits = {}, {}

    def admit(self, rid, slot, prompt, n_new):
        P = len(prompt)
        assert self.pages.reserve(slot, prompt, n_new) is not None
        ids = np.zeros(-(-P // CHUNK) * CHUNK, np.int32)
        ids[:P] = prompt
        for ci in range(len(ids) // CHUNK):
            last = int(min(max(P - 1 - ci * CHUNK, 0), CHUNK - 1))
            logits, self.pools, _ = self.chunk_fn(
                self.params, self.pools, jnp.asarray(self.pages.row(slot)),
                jnp.asarray(ids[None, ci * CHUNK:(ci + 1) * CHUNK]),
                jnp.asarray(ci * CHUNK, jnp.int32),
                jnp.asarray([last], jnp.int32))
        self._seen.clear()
        self.state, first = self.admit_fn(self.state, logits, self.rng,
                                          slot, P, n_new, -1)
        self.tokens[rid] = [int(first)]
        self.logits[rid] = [self._seen[0][0]]
        self.lanes[slot] = [rid, n_new - 1]

    def block(self):
        self._seen.clear()
        toks, self.pools, self.state, _ = self.decode_fn(
            self.params, self.pools, self.state,
            jnp.asarray(self.pages.table()), self.rng)
        toks = np.asarray(toks)
        for slot, lane in list(self.lanes.items()):
            for i in range(BLOCK):
                if lane[1] > 0:
                    self.tokens[lane[0]].append(int(toks[i, slot]))
                    self.logits[lane[0]].append(self._seen[i][slot])
                    lane[1] -= 1

    def retire(self, slot):
        assert self.lanes.pop(slot)[1] == 0
        self.pages.release(slot)

    def run(self, rid):
        while self.lanes and any(left for _, left in self.lanes.values()):
            self.block()
        return np.asarray(self.tokens[rid]), np.stack(self.logits[rid])


def _reference_rows(prompt, generated):
    """The reference's ONE full forward over prompt + generated: the rows
    that predict each generated token."""
    full = np.concatenate([prompt, generated]).astype(np.int32)
    lg = np.asarray(fam.logits(Z, SEED, full))
    return lg[len(prompt) - 1:len(full) - 1]


# ---- the forward, uncached and through the slot engine ------------------- #
def test_the_uncached_forward_is_the_reference(program):
    module, params = program
    tokens = _prompt(40)
    got = np.asarray(module.apply(params,
                                  {"input_ids": jnp.asarray(tokens[None])}))
    want = np.asarray(fam.logits(Z, SEED, tokens))
    assert np.abs(want).mean() > 0.3          # the toy's layers are visible
    assert np.abs(got[0] - want).max() < TOL


@pytest.mark.parametrize("prompt_len", [1, 2, CHUNK, CHUNK + 1,
                                        3 * CHUNK - 1])
def test_chunks_then_decode_blocks_match_the_full_forward(program,
                                                          prompt_len):
    """Prompts shorter than the kernel (1, 2: the state's rows before the
    sequence are zeros), of a whole chunk, one over, and three chunks less
    one (a padded last chunk); then ten tokens through three decode blocks,
    the last one cut short.  Logits, not tokens."""
    eng = Engine(*program)
    prompt = _prompt(prompt_len)
    eng.admit("a", 1, prompt, 10)
    tokens, logits = eng.run("a")
    want = _reference_rows(prompt, tokens)
    assert logits.shape == want.shape == (10, 128)
    assert np.abs(logits - want).max() < TOL
    assert (want.argmax(-1) == tokens).all()


def test_a_slots_second_occupant_starts_from_zeros(program):
    """Three requests on two slots: the third takes the first's slot and
    state row, which still holds what the first left there."""
    eng = Engine(*program)
    reqs = {"a": (_prompt(5, 1), 3), "b": (_prompt(11, 2), 14),
            "c": (_prompt(9, 3), 6)}
    eng.admit("a", 0, *reqs["a"])
    eng.admit("b", 1, *reqs["b"])
    eng.block()                               # a retires inside this block
    left = np.asarray(eng.pools["conv"][:, 1])
    assert np.abs(left).max() > 0
    eng.retire(0)
    eng.admit("c", 0, *reqs["c"])
    assert eng.pages.table()[0, -1] == 1      # the same state row
    for rid, (prompt, n_new) in reqs.items():
        tokens, logits = eng.run(rid)
        assert len(tokens) == n_new
        assert np.abs(logits - _reference_rows(prompt, tokens)).max() < TOL


def test_a_lane_that_retires_mid_block_stops_writing_its_row(program):
    """Lane 0 has two tokens left when a block of four starts; lane 1
    decodes on.  Lane 0's state row holds the state after its LAST LIVE
    step — the dead steps wrote the trash row — and its neighbour's logits
    are the reference's."""
    eng = Engine(*program)
    pa, pb = _prompt(6, 4), _prompt(13, 5)
    eng.admit("a", 0, pa, 3)                  # the admit samples one
    eng.admit("b", 1, pb, 9)
    eng.block()
    assert not bool(eng.state["active"][0]) and bool(eng.state["active"][1])
    ta, la = eng.run("a")
    assert np.abs(la - _reference_rows(pa, ta)).max() < TOL
    # the last live step fed a's second token: the state is that of the
    # prompt and two generated tokens
    want = np.asarray(fam.conv_states(Z, SEED, np.concatenate([pa, ta[:2]])))
    got = np.asarray(eng.pools["conv"][:, 1]).reshape(want.shape)
    assert np.abs(got - want).max() < TOL
    tb, lb = eng.run("b")
    assert np.abs(lb - _reference_rows(pb, tb)).max() < TOL


@pytest.mark.parametrize("prompt_len", [1, 2, CHUNK + 1, 3 * CHUNK - 1])
def test_state_rows_after_a_padded_last_chunk(program, prompt_len):
    """After the prompt's chunks the slot's row holds ``(z_{P-2}, z_{P-1})``
    of every conv layer — of the last REAL row, not of the padding — and
    zeros where the sequence had not begun."""
    eng = Engine(*program)
    prompt = _prompt(prompt_len, 6)
    eng.admit("a", 1, prompt, 2)
    want = np.asarray(fam.conv_states(Z, SEED, prompt))
    got = np.asarray(eng.pools["conv"][:, 2]).reshape(want.shape)
    assert np.abs(got - want).max() < TOL
    if prompt_len == 1:
        assert (got[:, 0] == 0).all()
    assert (np.asarray(eng.pools["conv"][:, 1]) == 0).all()   # slot 0's row


def test_rows_in_tiles_through_the_slot_programs(monkeypatch):
    """At a width 128 divides a slot's row is whole tiles under the row's
    index — the cells' form; the 64-wide toy's rows above are flat — and a
    decode step reads and writes it through ``conv.rows_read`` /
    ``conv.rows_write`` (``ops/transformer/short_conv.py``, interpreted
    here).  Three requests on two slots: one retires mid-block (its dead
    steps land on the trash row), the third takes its slot and the stale
    row; logits and the rows against the reference."""
    toy = {**TOY, "hidden_size": 128}
    for name in ("_W", "_OUT", "_DOWN"):      # sqrt(hidden) x std, as at 64
        monkeypatch.setattr(fam, name, getattr(fam, name) / 2 ** 0.5)
    sizes = fam.sizes_of(toy)
    module = fam.program_model(toy, dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          fam.program_params(module, toy, SEED))
    eng = Engine(module, params)
    # 2 x 128 values a row on one float32 tile of 8 sublanes
    assert eng.pools["conv"].shape == (4, 3, 8, 128)

    def rows(slot, like):
        got = np.asarray(eng.pools["conv"][:, 1 + slot]).reshape(4, -1)
        assert (got[:, like[0].size:] == 0).all()         # the pad
        return got[:, :like[0].size].reshape(like.shape)

    def reference(prompt, generated):
        full = np.concatenate([prompt, generated]).astype(np.int32)
        lg = np.asarray(fam.logits(sizes, SEED, full))
        return lg[len(prompt) - 1:len(full) - 1]

    reqs = {"a": (_prompt(6, 4), 3), "b": (_prompt(13, 5), 9),
            "c": (_prompt(2 * CHUNK + 3, 3), 6)}
    eng.admit("a", 0, *reqs["a"])
    want = np.asarray(fam.conv_states(sizes, SEED, reqs["a"][0]))
    assert np.abs(rows(0, want) - want).max() < TOL       # a padded chunk
    eng.admit("b", 1, *reqs["b"])
    eng.block()                               # a retires inside this block
    assert not bool(eng.state["active"][0]) and bool(eng.state["active"][1])
    ta = np.asarray(eng.tokens["a"])
    want = np.asarray(fam.conv_states(
        sizes, SEED, np.concatenate([reqs["a"][0], ta[:2]])))
    assert np.abs(rows(0, want) - want).max() < TOL       # its LAST LIVE step
    eng.retire(0)
    eng.admit("c", 0, *reqs["c"])             # over a's stale row
    for rid, (prompt, n_new) in reqs.items():
        tokens, logits = eng.run(rid)
        assert len(tokens) == n_new
        assert np.abs(logits - reference(prompt, tokens)).max() < TOL


def test_a_stale_state_is_visible(program):
    """What the tolerance stands against: the same request with its state
    row zeroed between prefill and decode leaves the reference by a
    thousand tolerances, and is what the ``stale_conv_state`` control
    computes for the first decoded token."""
    eng = Engine(*program)
    prompt = _prompt(12, 7)
    eng.admit("a", 1, prompt, 4)
    eng.pools = {**eng.pools, "conv": jnp.zeros_like(eng.pools["conv"])}
    tokens, logits = eng.run("a")
    want = _reference_rows(prompt, tokens)
    assert np.abs(logits[0] - want[0]).max() < TOL      # the admit's row
    assert np.abs(logits[1] - want[1]).max() > 1000 * TOL
    stale = np.asarray(fam.logits(Z, SEED, np.concatenate([prompt, tokens]),
                                  "stale_conv_state", stale_from=12))
    # the control is bfloat16 but for the state: nearer by far all the same
    assert np.abs(logits[1] - stale[12]).max() \
        < 0.3 * np.abs(logits[1] - want[1]).max()


def test_dead_lanes_write_the_trash_row_only(program):
    """A block over a table whose rows are all trash (every lane dead)
    leaves every slot's state row as it was."""
    eng = Engine(*program)
    eng.admit("a", 1, _prompt(10, 8), 2)
    before = np.asarray(eng.pools["conv"])
    eng.state = {**eng.state, "active": jnp.zeros((2,), bool)}
    eng.block()
    after = np.asarray(eng.pools["conv"])
    assert (after[:, 1:] == before[:, 1:]).all()


# ---- through init_inference -> serve() -> submit / drain ------------------ #
SERVING = {"enabled": True, "num_slots": 2, "max_cache_len": 64,
           "prefill_chunk": CHUNK, "decode_block": BLOCK, "page_size": PAGE,
           "prefix_cache": True}


@pytest.fixture(scope="module")
def served(program):
    """Six requests on two slots: slot churn (a slot's later occupants),
    padded chunk tails, lanes that retire inside blocks."""
    module, params = program
    eng = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None, "serving": SERVING})
    eng.set_params(params)
    srv = eng.serve()
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, 128, int(n)).astype(np.int32), int(k))
            for n, k in zip(rng.integers(1, 30, 6), rng.integers(3, 12, 6))]
    rids = [srv.submit(p, max_new_tokens=k) for p, k in reqs]
    outs = srv.drain()
    return srv, reqs, [np.asarray(outs[r]) for r in rids]


def test_serve_takes_the_engines_own_programs(served):
    srv, _, _ = served
    assert srv.kernel_modes == {"decode": "pallas_paged_decode",
                                "prefill_chunk": "pallas_chunked_prefill"}
    assert srv.stats["paged_attention_fallback"] == 0
    assert srv.table_width == srv.pages_per_slot + 1


def test_serve_gives_the_reference_choice(served):
    """Every generated token's REFERENCE logit is the reference's largest
    at its position, to the float32 tolerance — the benchmark's ``correct``
    statistic, at toy size."""
    _, reqs, outs = served
    for (prompt, n_new), out in zip(reqs, outs):
        assert len(out) == len(prompt) + n_new
        gaps = fam.chosen_gaps(Z, SEED, out, len(prompt), n_new, 64)
        assert gaps.max() <= TOL


def test_serve_counts_the_routed_layers_only(served):
    """``_decode`` finds the load under ``moe_stats/layers_<i>/moe_mlp``:
    the two dense layers sow nothing, so the counts are the four routed
    layers'."""
    srv, reqs, _ = served
    live = sum(len(p) + k - 1 for p, k in reqs)
    assert srv.moe_expert_tokens.shape == (MOE_LAYERS, 8)
    assert srv.stats["moe_assignments"] == live * TOP_K * MOE_LAYERS
    assert (srv.moe_expert_tokens.sum(axis=1) == live * TOP_K).all()


def test_serve_refuses_prefix_sharing_and_counts_the_state(served):
    srv, _, _ = served
    assert srv.stats["prefix_sharing_refused"] == 1
    assert srv.stats["prefix_lookups"] == 0
    # everything drained: no row held
    assert srv.stats["state_rows_live"] == 0 and srv.stats["state_bytes"] == 0
    with srv._lock:
        text = srv._pages.describe()
    assert "state (conv): state_rows_live 0/2" in text


@pytest.mark.parametrize("refused", [{"speculative": True, "spec_k": 2}])
def test_serve_refuses_what_an_expert_model_cannot(program, refused):
    module, params = program
    eng = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None,
        "serving": {**SERVING, **refused}})
    eng.set_params(params)
    with pytest.raises(ValueError, match="expert layers"):
        eng.serve(draft_module=module, draft_params=params)


# ---- the router ----------------------------------------------------------- #
def _router_inputs(tokens=24, experts=8):
    ks = jax.random.split(jax.random.key(3), 3)
    return (jax.random.normal(ks[0], (tokens, 64)),
            0.3 * jax.random.normal(ks[1], (64, experts)),
            0.4 * jax.random.normal(ks[2], (experts,)))


def test_router_selects_by_score_plus_bias_and_gates_from_scores():
    x, w, bias = _router_inputs()
    choice, gate = dropless.route_scored(x, w, bias, 2, sum_eps=1e-6)
    top_i, top_w = fam.route(Z, x, {"router": w, "expert_bias": bias},
                             "float32")
    assert (np.asarray(choice) == np.asarray(top_i)).all()
    assert np.abs(np.asarray(gate) - np.asarray(top_w)).max() < 1e-6
    scores = np.asarray(jax.nn.sigmoid(x @ w))
    picked = np.take_along_axis(scores, np.asarray(choice), axis=1)
    want = picked / (picked.sum(axis=1, keepdims=True) + 1e-6)
    assert np.abs(np.asarray(gate) - want).max() < 1e-6
    # selection is by score + bias: it differs from the top scores
    # somewhere, and the gates are the unbiased scores' all the same
    by_score = np.argsort(-scores, axis=1)[:, :2]
    assert (np.sort(by_score, 1) != np.sort(np.asarray(choice), 1)).any()


def test_a_bias_flips_a_choice():
    x, w, bias = _router_inputs()
    scores = np.asarray(jax.nn.sigmoid(x @ w))
    loser = int(np.argmin(scores[0]))
    flipped = jnp.zeros_like(bias).at[loser].set(1.0)
    plain, _ = dropless.route_scored(x, w, jnp.zeros_like(bias), 2)
    choice, gate = dropless.route_scored(x, w, flipped, 2, sum_eps=1e-6)
    assert loser not in np.asarray(plain[0])
    assert np.asarray(choice[0])[0] == loser
    assert abs(float(gate[0].sum()) - 1.0) < 1e-5     # gates: the scores'


def test_the_gate_denominator_carries_the_families_guard():
    x, w, bias = _router_inputs()
    _, plain = dropless.route_scored(x, w, bias, 2)
    _, guarded = dropless.route_scored(x, w, bias, 2, sum_eps=1e-6)
    assert np.abs(np.asarray(plain).sum(axis=1) - 1.0).max() < 1e-6
    ratio = np.asarray(guarded) / np.asarray(plain)
    assert (ratio <= 1.0).all() and (ratio < 1.0).any() \
        and (ratio > 1.0 - 1e-4).all()
    # the guard is IN the denominator: a large one shows to the digit
    scores = np.asarray(jax.nn.sigmoid(x @ w))
    choice, wide = dropless.route_scored(x, w, bias, 2, sum_eps=0.5)
    picked = np.take_along_axis(scores, np.asarray(choice), axis=1)
    want = picked / (picked.sum(axis=1, keepdims=True) + 0.5)
    assert np.abs(np.asarray(wide) - want).max() < 1e-6


@pytest.mark.parametrize("family", ["dots3", "olmoe"])
def test_the_other_families_routed_layers_keep_their_bits(family):
    """``sum_eps`` / ``gate_sum_eps`` default to 0, which leaves the
    division as it was: dots3's gates are bit-for-bit the chosen scores
    over their sum, times the scaling; OLMoE's softmax router is another
    function and takes no such argument."""
    x, w, bias = _router_inputs(experts=16)
    if family == "olmoe":
        combine, counts = dropless.route(x.astype(jnp.float32), w, 4)
        assert "sum_eps" not in dropless.route.__code__.co_varnames
        assert int(counts.sum()) == 24 * 4 and combine.shape == (32, 16)
        return
    choice, gate = dropless.route_scored(x, w, bias, 4, scaling=2.5)
    scores = jax.nn.sigmoid(jnp.matmul(x, w,
                                       precision=jax.lax.Precision.HIGHEST))
    picked = jnp.take_along_axis(scores, choice, axis=1)
    old = picked / jnp.sum(picked, axis=1, keepdims=True) * 2.5
    assert (np.asarray(gate) == np.asarray(old)).all()
    layer = lambda **kw: MoE(
        hidden_size=64, num_experts=16, k=4, capacity_factor=None,
        ffn_hidden_size=32, gated=True, activation=jax.nn.silu,
        dtype=jnp.float32, scoring="sigmoid", held_experts=(4, 8),
        shared_ffn_hidden_size=32, **kw)
    params = layer().init(jax.random.key(0), x[None])
    y0 = layer().apply(params, x[None], train=False)[0]
    y1 = layer(gate_sum_eps=0.0).apply(params, x[None], train=False)[0]
    assert (np.asarray(y0) == np.asarray(y1)).all()


def test_the_grouped_form_is_the_dense_form(monkeypatch):
    """Every expert held, no shared expert: a chunk's rows sorted by
    expert (``moe.experts_grouped``, what a 512-token chunk takes) give
    what every touched expert over every row gives (``moe.experts_gmm``,
    what 256 decode lanes take)."""
    x, _, _ = _router_inputs(tokens=40)
    layer = MoE(hidden_size=64, num_experts=8, k=2, capacity_factor=None,
                ffn_hidden_size=48, gated=True, activation=jax.nn.silu,
                dtype=jnp.float32, scoring="sigmoid", gate_sum_eps=1e-6)
    params = layer.init(jax.random.key(1), x[None])
    live = jnp.arange(40) < 33
    dense = layer.apply(params, x[None], train=False, live=live)[0]
    monkeypatch.setattr(dropless, "GROUPED_MIN_ROWS", 8)
    grouped = layer.apply(params, x[None], train=False, live=live)[0]
    assert np.abs(np.asarray(dense)).max() > 0.01
    assert np.abs(np.asarray(dense - grouped)).max() < 1e-5
    assert (np.asarray(grouped)[0, 33:] == 0).all()


# ---- the cache manager's state kind --------------------------------------- #
def _manager(program, share=False, slots_=3, stats=None):
    stats = {"prefix_lookups": 0} if stats is None else stats
    return SlotPages(program[0], program[0].slot_contract(), slots_, 64,
                     PAGE, 0, CHUNK, share, stats), stats


def test_slot_pages_ship_the_state_row_in_the_table(program):
    mgr, stats = _manager(program)
    assert mgr.state_kinds == ("conv",) and mgr.state_rows == 4
    assert mgr.table_width == mgr.pages_per_slot + 1 == 9
    assert mgr.table().shape == (3, 9) and (mgr.table() == 0).all()
    pools = jax.eval_shape(lambda: mgr.new_pools(jnp.float32))
    # 4 conv layers; 128 does not divide the toy's 64: a flat row, where the
    # cell's 2 x 2,048 are whole tiles (``short_conv.rows_shape``)
    assert pools["conv"].shape == (4, 4, 2 * 64)
    assert pools["k"].shape == pools["v"].shape == (2, 25, 8, 2 * 16)
    assert mgr.state_row_bytes == 4 * 2 * 64 * 4
    assert mgr.page_bytes == 2 * 2 * 8 * 32 * 4
    row, start = mgr.reserve(2, _prompt(20), 10)
    assert start == 0 and mgr.table()[2, -1] == 3     # row 1 + slot
    assert list(mgr.table()[2, :len(row)]) == row
    assert (mgr.row(2) == mgr.table()[2:3]).all()
    assert stats["state_rows_live"] == 1
    assert stats["state_bytes"] == mgr.state_row_bytes


def test_slot_pages_release_and_reset_send_the_row_to_trash(program):
    mgr, stats = _manager(program)
    mgr.new_pools(jnp.float32)
    mgr.reserve(0, _prompt(9), 4)
    mgr.reserve(1, _prompt(30), 8)
    assert list(mgr.table()[:, -1]) == [1, 2, 0]
    assert "state (conv): state_rows_live 2/3" in mgr.describe()
    assert f"state_bytes {2 * mgr.state_row_bytes}" in mgr.describe()
    mgr.release(0)
    assert list(mgr.table()[:, -1]) == [0, 2, 0]
    assert stats["state_rows_live"] == 1
    mgr.reset()
    assert (mgr.table() == 0).all() and stats["state_rows_live"] == 0
    assert stats["state_bytes"] == 0 and mgr.in_use == 0


def test_slot_pages_refuse_prefix_sharing_for_a_state_kind(program):
    mgr, stats = _manager(program, share=True)
    assert mgr.share_prefixes is False
    assert stats["prefix_sharing_refused"] == 1
    prompt = _prompt(40)
    mgr.reserve(0, prompt, 4)
    mgr.share(0, prompt)
    _, start = mgr.reserve(1, prompt, 4)      # the same prompt again
    assert start == 0 and stats["prefix_lookups"] == 0


def test_slot_pages_without_a_state_kind_are_as_they_were():
    stats = {"prefix_lookups": 0, "prefix_hits": 0,
             "prefix_tokens_reused": 0, "page_evictions": 0}
    plain = dataclasses.replace(fam.program_model(TOY).slot_contract(),
                                state_kinds=())
    mgr = SlotPages(None, plain, 3, 64, PAGE, 0, CHUNK, True, stats)
    assert mgr.state_kinds == () and mgr.state_rows == 0
    assert mgr.table_width == mgr.pages_per_slot
    mgr.reserve(0, _prompt(20), 4)
    assert "state_rows_live" not in stats and "state" not in mgr.describe()
    assert "state_rows" not in mgr.chunk_reach(2, 16)
    assert "state_rows" not in mgr.block_reach(2, [(20, 4)], 4)


def test_dispatch_spans_carry_the_state_rows(program):
    mgr, _ = _manager(program)
    mgr.new_pools(jnp.float32)
    mgr.reserve(0, _prompt(20), 10)
    mgr.reserve(2, _prompt(5), 3)
    assert mgr.chunk_reach(6, 16)["state_rows"] == 1
    reach = mgr.block_reach(6, [(21, 4), (6, 2)], 4)
    assert reach["state_rows"] == 6
    assert reach["state_bytes"] == 2 * mgr.state_row_bytes
    assert reach["kv_bytes_mapped"] == mgr.in_use * mgr.page_bytes > 0


# ---- the config ----------------------------------------------------------- #
def test_config_reads_the_hf_keys():
    cfg = lfm2_config(TOY)
    assert cfg.layers_of("conv") == [0, 1, 3, 4]
    assert cfg.layers_of("full_attention") == [2, 5]
    assert (cfg.head_dim, cfg.num_kv_heads, cfg.rope_theta) == (16, 2, 1e6)
    # what the slot engine reads of it: dropless experts after the two
    # dense layers, eight a layer
    declared = fam.program_model(TOY).slot_contract()
    assert declared.routes_experts and not declared.holds_share
    assert (declared.expert_layers, declared.experts) == (4, 8)


@pytest.mark.parametrize("key,value", [
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("conv_bias", True), ("layer_types", ["conv"] * 5 + ["mamba"]),
    ("num_key_value_heads", 3)])
def test_config_refuses_what_the_model_lacks(key, value):
    with pytest.raises(ValueError):
        lfm2_config({**TOY, key: value})
