"""Nemotron-H (``nemotron_h``) at a toy size on the CPU: the Mamba-2 scan's
two state kernels (``ops/transformer/ssd.py``) with ``B`` and ``C`` in GROUPS,
interpreted and in plain XLA, against the recurrence — and one group bit for
bit what it was; the group-wise gate-norm; the un-gated (two-matrix) experts
through both dropless kernels at a width 128 does not divide; the program
through the SLOT ENGINE — ONE-sublayer blocks in the pattern ``M E * M E M``,
the chunk step, the admit and the decode block over ``paging.SlotPages``'
pools — against the plain float32 reference (``benchmark/families/
nemotron_h.py``); a slot's second occupant after a long one; the contract's
three counts where they differ; the config's refusals.

Tolerances as ``tests/unit/test_granite_hybrid.py``'s: program and reference
are both float32 here and differ by the order of their sums alone.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmark import spec
from deepspeed_tpu.inference.serving import slots
from deepspeed_tpu.inference.serving.paging import SlotPages
from deepspeed_tpu.models import contract as contract_mod
from deepspeed_tpu.models.nemotron_h import (NemotronHModel,
                                             nemotron_h_config, relu2)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.transformer import ssd

TOL = 2e-4
TOY = dict(
    model_type="nemotron_h", hidden_size=128, num_hidden_layers=6,
    hybrid_override_pattern="ME*MEM", num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, attention_bias=False,
    mamba_num_heads=16, mamba_head_dim=16, ssm_state_size=16, n_groups=2,
    conv_kernel=4, use_conv_bias=True, mamba_proj_bias=False, chunk_size=128,
    expand=2, n_routed_experts=4, n_routed_experts_published=8,
    held_experts=[4, 4], num_experts_per_tok=3, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, n_shared_experts=1,
    mlp_hidden_act="relu2", mamba_hidden_act="silu", norm_topk_prob=True,
    routed_scaling_factor=2.5, n_group=1, topk_group=1,
    layer_norm_epsilon=1e-5, vocab_size=128, tie_word_embeddings=False,
    max_position_embeddings=512, rope_theta=10000, mlp_bias=False,
    use_bias=False)
SEED, CHUNK, PAGE, BLOCK = 7, 8, 8, 4
BLOCKS, MAMBA, EXPERT, ATTEND = 6, 3, 2, 1      # three counts that differ
HEADS, P, N, GROUPS = 16, 16, 16, 2
CONV = HEADS * P + 2 * GROUPS * N               # x, B and C: one stream
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a family instance of this file's own, drawn at a toy's scales (at hidden
# 128 the real stds give the projections nothing to say), its selection
# biases balanced on ONE sequence of the length the tests' own forwards are
# padded to (its blocks compile once for both)
fam = spec.Benchmark(ROOT).family("nemotron_h")
fam._W, fam._QK = 0.09, 0.15
fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH = 1, 64
Z = fam.sizes_of(TOY)


# ---- the two kernels in groups against the recurrence --------------------- #
def _draw(T, heads, p, n, groups, seed=0):
    """``x``, ``B [T, G, N]``, ``C`` bfloat16, a step size a head over 0.001
    .. 0.1 through ``A`` over 1 .. 16."""
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (T, heads, p))
    b, c = (jax.random.normal(k, (T, groups, n)) for k in ks[1:3])
    dt = jnp.exp(jax.random.uniform(ks[3], (T, heads), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    a = -dt * jax.random.uniform(ks[4], (heads,), minval=1.0, maxval=16.0)
    bf = lambda t: t.astype(jnp.bfloat16)
    return bf(x), dt, a, bf(b), bf(c)


def _recurrence(state, x, dt, a, b, c):
    """The recurrence by hand in numpy float64, one position after the
    other, head ``h`` reading group ``h // (H / G)``: ``(S C [T, H, P],
    state)``."""
    x, dt, a, b, c = (np.asarray(t, np.float64) for t in (x, dt, a, b, c))
    of = np.arange(x.shape[1]) // (x.shape[1] // b.shape[1])
    S, out = np.array(state, np.float64), []
    for t in range(x.shape[0]):
        S = np.exp(a[t])[:, None, None] * S \
            + (dt[t][:, None] * x[t])[:, :, None] * b[t][of][:, None, :]
        out.append(np.einsum("hpn,hn->hp", S, c[t][of]))
    return np.stack(out), S


def _pool(heads, p, n):
    return jax.random.normal(jax.random.key(9),
                             (2, 3) + ssd.state_shape(heads, p, n))


def _heads(rows, p=P):
    return np.asarray(ssd.heads_of(rows, p))


# heads x head size x state, groups: eight heads a grid step of the chunk
# kernel are one group's or half of one; a decode step's are 1, 2, 8 or 4
# whole groups (the last the published 64 heads in 8 groups, half a row)
SHAPES = [("16x16x16", 1), ("16x16x16", 2), ("64x16x16", 8), ("64x64x32", 8)]


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["interpreted", "xla"])
@pytest.mark.parametrize("shape,groups", SHAPES)
@pytest.mark.parametrize("T,real,fresh", [(200, 200, False), (200, 70, True)])
def test_chunk_scan_in_groups_is_the_recurrence(pallas, shape, groups, T,
                                                real, fresh):
    heads, p, n = map(int, shape.split("x"))
    assert ssd.chunk_heads(heads, p, groups) == 8
    pool = _pool(heads, p, n)
    x, dt, a, b, c = _draw(T, heads, p, n, groups)
    out, new = ssd.chunk_scan(x, dt, a, b, c, pool, 1, 2, fresh=fresh,
                              real=real, pallas=pallas)
    start = np.zeros((heads, p, n)) if fresh else _heads(pool[1, 2], p)
    want_o, want_s = _recurrence(start, *(t[:real] for t in (x, dt, a, b,
                                                             c)))
    size = max(np.abs(want_s).max(), 1.0)
    assert np.abs(_heads(new[1, 2], p) - want_s).max() < 2e-5 * size
    assert np.abs(np.asarray(out[:real], np.float64) - want_o).max() \
        < 2e-5 * max(np.abs(want_o).max(), 1.0)
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, 2] = False
    assert (np.asarray(new)[untouched] == np.asarray(pool)[untouched]).all()


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["interpreted", "xla"])
@pytest.mark.parametrize("shape,groups", SHAPES)
def test_decode_step_in_groups_is_one_step(pallas, shape, groups):
    """Five lanes, two live on rows of their own and three dead on the
    trash row, each live lane's heads reading their own group's ``B`` and
    ``C``."""
    heads, p, n = map(int, shape.split("x"))
    assert ssd.step_groups(heads, p, groups) == {
        ("16x16x16", 1): 1, ("16x16x16", 2): 2, ("64x16x16", 8): 8,
        ("64x64x32", 8): 4}[shape, groups]
    pool = _pool(heads, p, n)
    x, dt, a, b, c = _draw(5, heads, p, n, groups, seed=3)
    rows = jnp.asarray([1, 2, 0, 0, 0])
    live = jnp.asarray([True, True, False, False, False])
    out, new = ssd.decode_step(x, dt, a, b, c, pool, 0, rows, live,
                               pallas=pallas)
    for lane in (0, 1):
        pick = lambda t: t[lane:lane + 1]
        want_o, want_s = _recurrence(_heads(pool[0, lane + 1], p),
                                     *map(pick, (x, dt, a, b, c)))
        assert np.abs(_heads(new[0, lane + 1], p) - want_s).max() < 2e-6
        assert np.abs(np.asarray(out[lane], np.float64)
                      - want_o[0]).max() < 2e-4
    assert (np.asarray(new[0, 0]) == np.asarray(pool[0, 0])).all()
    assert (np.asarray(new[1]) == np.asarray(pool[1])).all()
    assert (np.asarray(out[2:]) == 0).all()


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["interpreted", "xla"])
def test_one_group_is_bit_for_bit_what_it_was(pallas):
    """``b`` / ``c [T, 1, N]`` — the grouped call at one group — gives the
    bits of ``[T, N]``, the call Granite 4.0-H makes, in both kernels and
    both plain-XLA forms."""
    heads, p, n = 16, 64, 32
    pool = _pool(heads, p, n)
    x, dt, a, b, c = _draw(200, heads, p, n, 1)
    flat = lambda t: t[:, 0]
    for one, two in (
            (ssd.chunk_scan(x, dt, a, flat(b), flat(c), pool, 0, 1,
                            fresh=False, real=150, pallas=pallas),
             ssd.chunk_scan(x, dt, a, b, c, pool, 0, 1, fresh=False,
                            real=150, pallas=pallas)),
            (ssd.decode_step(x[:3], dt[:3], a[:3], flat(b)[:3], flat(c)[:3],
                             pool, 1, jnp.asarray([1, 2, 0]), pallas=pallas),
             ssd.decode_step(x[:3], dt[:3], a[:3], b[:3], c[:3], pool, 1,
                             jnp.asarray([1, 2, 0]), pallas=pallas))):
        for got, want in zip(two, one):
            assert (np.asarray(got) == np.asarray(want)).all()


def test_heads_that_fit_no_group_take_the_plain_path():
    """Eight heads in four groups of two: a chunk step's eight heads would
    straddle groups, a tile's eight heads too — no kernel form, and the
    plain-XLA recurrence is what runs."""
    assert ssd.chunk_heads(8, 16, 4) is None
    assert ssd.step_groups(8, 16, 4) is None
    pool = _pool(8, 16, 16)
    x, dt, a, b, c = _draw(20, 8, 16, 16, 4)
    out, new = ssd.chunk_scan(x, dt, a, b, c, pool, 0, 1, fresh=True,
                              real=20)
    want_o, want_s = _recurrence(np.zeros((8, 16, 16)), x, dt, a, b, c)
    assert np.abs(_heads(new[0, 1]) - want_s).max() < 2e-5
    step, _ = ssd.decode_step(x[:2], dt[:2], a[:2], b[:2], c[:2], pool, 0,
                              jnp.asarray([1, 2]))
    assert step.shape == (2, 8, 16)


# ---- the gate-norm a group ------------------------------------------------- #
def test_the_gate_norm_is_a_group_at_a_time():
    """``Mamba2Mixer`` at two groups norms each group's 128 channels on
    their own mean of squares.  Read through an ``out_proj`` that passes ONE
    group's channels through: over its gain each group of each row has unit
    mean of squares — under one norm over the whole width only their mean
    would."""
    cfg = nemotron_h_config(TOY, dtype="float32")
    mixer = NemotronHModel.declare(cfg).mixer[1](cfg)
    u = jax.random.normal(jax.random.key(0), (12, 128))
    params = mixer.init(jax.random.key(1), u, start=0)["params"]
    gain = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (256,))
    eye, zero = jnp.eye(128), jnp.zeros((128, 128))
    squares = []
    for g, kernel in enumerate((jnp.concatenate([eye, zero]),
                                jnp.concatenate([zero, eye]))):
        out, _ = mixer.apply({"params": dict(
            params, norm=gain, out_proj={"kernel": kernel})}, u, start=0)
        squares.append(np.mean(np.square(
            np.asarray(out) / np.asarray(gain[128 * g:128 * (g + 1)])), -1))
    assert np.abs(np.stack(squares) - 1.0).max() < 5e-3


# ---- un-gated experts through both kernels --------------------------------- #
@pytest.mark.parametrize("F", [48, 200], ids=["f48", "f200"])
def test_both_expert_kernels_ungated_are_the_einsum(F):
    """``relu(x U)^2 D`` a chosen expert — two matrices, no gate — through
    ``moe.experts_gmm`` and ``moe.experts_grouped`` at a width 128 does not
    divide (the whole width is one tile), against an einsum over every
    expert."""
    M, E, T, k = 64, 6, 40, 2
    ks = jax.random.split(jax.random.key(F), 4)
    x = jax.random.normal(ks[0], (T, M))
    wu = 0.1 * jax.random.normal(ks[1], (E, M, F))
    wd = 0.1 * jax.random.normal(ks[2], (E, F, M))
    choice = jax.random.randint(ks[3], (T, k), 0, E + 2)     # some elsewhere
    gate = jax.random.uniform(ks[3], (T, k), minval=0.2, maxval=1.0)
    local, counts, elsewhere = dropless.held_load(choice, 0, E)
    assert int(elsewhere) > 0 and dropless._width_tile(F) == F
    combine = dropless.combine_of(local, gate, E)
    every = jnp.einsum("tef,efm->tem", relu2(jnp.einsum(
        "tm,emf->tef", x, wu, precision="highest")), wd, precision="highest")
    want = np.asarray(jnp.einsum("tem,te->tm", every, combine))
    got = dropless.experts(x, combine, counts, None, wu, wd, relu2)
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()
    got = dropless.experts_grouped(x, local, gate, None, wu, wd, relu2,
                                   tile=16)
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()


def test_the_scored_layer_takes_ungated_experts_and_an_ungated_shared():
    """``MoE(scoring="sigmoid", gated=False, activation=relu2, ...)``: two
    matrices an expert and a shared expert of two, the sorted kernel from
    ``GROUPED_MIN_ROWS`` rows on — by hand."""
    from deepspeed_tpu.moe.layer import MoE
    M, F, E = 64, 40, 8
    layer = MoE(hidden_size=M, num_experts=E, k=3, capacity_factor=None,
                ffn_hidden_size=F, dtype=jnp.float32, gated=False,
                activation=relu2, scoring="sigmoid", routed_scaling=2.5,
                gate_sum_eps=1e-20, shared_ffn_hidden_size=2 * F,
                held_experts=(2, 4))
    x = jax.random.normal(jax.random.key(0), (dropless.GROUPED_MIN_ROWS, M))
    params = layer.init(jax.random.key(1), x[:8], train=False)
    p = params["params"]
    assert set(p) == {"gate_kernel", "select_bias", "ExpertsMLP_0",
                      "shared_up", "shared_down"}
    assert set(p["ExpertsMLP_0"]) == {"experts_wi", "experts_wo"}
    p = dict(p, select_bias=0.1 * jax.random.normal(jax.random.key(2), (E,)))
    scores = jax.nn.sigmoid(jnp.matmul(x, p["gate_kernel"],
                                       precision="highest"))
    _, top = jax.lax.top_k(scores + p["select_bias"], 3)
    w = jnp.take_along_axis(scores, top, axis=1)
    w = 2.5 * w / w.sum(-1, keepdims=True)
    two = lambda a, u, d: relu2(a @ u) @ d
    want = two(x, p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    for e in range(4):
        weight = jnp.sum(jnp.where(top == 2 + e, w, 0.0), axis=-1)
        want = want + weight[:, None] * two(
            x, p["ExpertsMLP_0"]["experts_wi"][e],
            p["ExpertsMLP_0"]["experts_wo"][e])
    for rows in (24, dropless.GROUPED_MIN_ROWS):       # gmm, then sorted
        got = layer.apply({"params": p}, x[:rows], train=False)[0]
        assert np.abs(np.asarray(got - want[:rows])).max() \
            < 1e-4 * np.abs(np.asarray(want)).max()


# ---- the program and its engine ------------------------------------------- #
@pytest.fixture(scope="module")
def program():
    module = fam.program_model(TOY, dtype="float32")
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          fam.program_params(module, TOY, SEED))
    return module, params


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, 128, n) \
        .astype(np.int32)


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
    scheduler a test can read (``tests/unit/test_granite_hybrid.py::
    Engine``)."""

    def __init__(self, module, params, num_slots=2, cache_len=64):
        self.module, self.params = module, params
        self.stats = {}
        contract = module.slot_contract()
        self.pages = SlotPages(module, contract, num_slots, cache_len, PAGE,
                               0, CHUNK, False, self.stats)
        self.pools = self.pages.new_pools(jnp.float32)
        self.state = {k: jnp.asarray(v) for k, v in
                      slots.init_slot_state(num_slots).items()}
        self.chunk_fn, self.admit_fn, self.decode_fn = _programs(
            module, contract, self.pages.cache_len)
        self.rng = jax.random.key(0)
        self.lanes = {}                  # slot -> [rid, tokens left]
        self.tokens, self.logits = {}, {}

    def admit(self, rid, slot, prompt, n_new):
        n = len(prompt)
        assert self.pages.reserve(slot, prompt, n_new) is not None
        ids = np.zeros(-(-n // CHUNK) * CHUNK, np.int32)
        ids[:n] = prompt
        for ci in range(len(ids) // CHUNK):
            last = int(min(max(n - 1 - ci * CHUNK, 0), CHUNK - 1))
            logits, self.pools, _ = self.chunk_fn(
                self.params, self.pools, jnp.asarray(self.pages.row(slot)),
                jnp.asarray(ids[None, ci * CHUNK:(ci + 1) * CHUNK]),
                jnp.asarray(ci * CHUNK, jnp.int32),
                jnp.asarray([last], jnp.int32))
        _SEEN.clear()
        self.state, first = self.admit_fn(self.state, logits, self.rng,
                                          slot, n, n_new, -1)
        self.tokens[rid] = [int(first)]
        self.logits[rid] = [_SEEN[0][0]]
        self.lanes[slot] = [rid, n_new - 1]

    def block(self):
        _SEEN.clear()
        toks, self.pools, self.state, _ = self.decode_fn(
            self.params, self.pools, self.state,
            jnp.asarray(self.pages.table()), self.rng)
        toks = np.asarray(toks)
        for slot, lane in list(self.lanes.items()):
            for i in range(BLOCK):
                if lane[1] > 0:
                    self.tokens[lane[0]].append(int(toks[i, slot]))
                    self.logits[lane[0]].append(_SEEN[i][slot])
                    lane[1] -= 1

    def retire(self, slot):
        assert self.lanes.pop(slot)[1] == 0
        self.pages.release(slot)

    def run(self, rid):
        while self.lanes and any(left for _, left in self.lanes.values()):
            self.block()
        return np.asarray(self.tokens[rid]), np.stack(self.logits[rid])


def _reference_rows(prompt, generated):
    full = np.concatenate([prompt, generated]).astype(np.int32)
    lg = np.asarray(fam.logits(Z, SEED, full))
    return lg[len(prompt) - 1:len(full) - 1]


def test_the_uncached_forward_is_the_reference(program):
    module, params = program
    tokens = _prompt(40)
    got = np.asarray(module.apply(params,
                                  {"input_ids": jnp.asarray(tokens[None])}))
    want = np.asarray(fam.logits(Z, SEED, tokens))
    assert np.abs(want).mean() > 0.1          # the toy's blocks are visible
    assert np.abs(got[0] - want).max() < TOL


def test_a_block_is_one_sublayer_under_its_kinds_name(program):
    """The parameter tree: a block holds ``norm`` and ONE of ``mamba`` /
    ``moe_mlp`` / ``self_attn`` by its pattern's character; the final norm
    is ``norm_f`` and the head untied."""
    params = program[1]["params"]
    kinds = {"M": "mamba", "E": "moe_mlp", "*": "self_attn"}
    for i, c in enumerate(TOY["hybrid_override_pattern"]):
        assert set(params[f"layers_{i}"]) == {"norm", kinds[c]}
    assert {"embed_tokens", "norm_f", "lm_head"} <= set(params)
    assert params["layers_0"]["mamba"]["conv1d"].shape == (4, CONV)


@pytest.mark.parametrize("prompt_len", [1, 2, CHUNK, CHUNK + 1,
                                        3 * CHUNK - 1])
def test_chunks_then_decode_blocks_match_the_full_forward(program,
                                                          prompt_len):
    """Prompts shorter than the taps, of a whole chunk, one over, and three
    chunks less one (a padded last chunk); then ten tokens through three
    decode blocks, the last cut short.  Logits, not tokens — and the slot's
    state rows hold what the reference's recurrence holds after the last
    position the program has run, in the THREE Mamba blocks' pool layers."""
    eng = Engine(*program)
    assert eng.pools["ssm"].shape[0] == eng.pools["conv"].shape[0] == MAMBA
    assert eng.pools["k"].shape[0] == ATTEND
    prompt = _prompt(prompt_len)
    eng.admit("a", 1, prompt, 10)
    want = np.asarray(fam.ssm_states(Z, SEED, prompt))
    assert np.abs(_heads(eng.pools["ssm"][:, 2]) - want).max() < TOL
    assert (np.asarray(eng.pools["ssm"][:, 1]) == 0).all()    # slot 0's row
    tokens, logits = eng.run("a")
    rows = _reference_rows(prompt, tokens)
    assert logits.shape == rows.shape == (10, 128)
    assert np.abs(logits - rows).max() < TOL
    assert (rows.argmax(-1) == tokens).all()
    after = np.asarray(fam.ssm_states(
        Z, SEED, np.concatenate([prompt, tokens[:9]])))
    assert np.abs(_heads(eng.pools["ssm"][:, 2]) - after).max() < TOL


def test_a_short_prompt_after_a_long_occupant_starts_from_zeros(program):
    """Three requests on two slots: the third — a prompt of FIVE — takes
    the slot of a request that ran 43 positions, and both its state rows."""
    eng = Engine(*program)
    reqs = {"a": (_prompt(40, 1), 3), "b": (_prompt(11, 2), 14),
            "c": (_prompt(5, 3), 6)}
    eng.admit("a", 0, *reqs["a"])
    eng.admit("b", 1, *reqs["b"])
    eng.block()                               # a retires inside this block
    assert np.abs(np.asarray(eng.pools["ssm"][:, 1])).max() > 0
    assert np.abs(np.asarray(eng.pools["conv"][:, 1])).max() > 0
    eng.retire(0)
    eng.admit("c", 0, *reqs["c"])
    assert eng.pages.table()[0, -1] == 1      # the same state row
    for rid, (prompt, n_new) in reqs.items():
        tokens, logits = eng.run(rid)
        assert len(tokens) == n_new
        assert np.abs(logits - _reference_rows(prompt, tokens)).max() < TOL


def test_a_stale_state_is_visible(program):
    eng = Engine(*program)
    prompt = _prompt(20, 7)
    eng.admit("a", 1, prompt, 4)
    eng.pools = {**eng.pools, "ssm": jnp.zeros_like(eng.pools["ssm"])}
    tokens, logits = eng.run("a")
    want = _reference_rows(prompt, tokens)
    assert np.abs(logits[0] - want[0]).max() < TOL      # the admit's row
    assert np.abs(logits[1] - want[1]).max() > 100 * TOL


# ---- through init_inference -> serve() -> submit / drain ------------------ #
SERVING = {"enabled": True, "num_slots": 2, "max_cache_len": 64,
           "prefill_chunk": CHUNK, "decode_block": BLOCK, "page_size": PAGE}


@pytest.fixture(scope="module")
def served(program):
    """Five requests on two slots: slot churn, padded chunk tails, lanes
    that retire inside blocks."""
    module, params = program
    eng = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None, "serving": SERVING})
    eng.set_params(params)
    srv = eng.serve()
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, 128, int(n)).astype(np.int32), int(k))
            for n, k in zip(rng.integers(1, 30, 5), rng.integers(3, 12, 5))]
    rids = [srv.submit(p, max_new_tokens=k) for p, k in reqs]
    outs = srv.drain()
    return srv, reqs, [np.asarray(outs[r]) for r in rids]


def test_serve_gives_the_reference_choice(served):
    srv, reqs, outs = served
    assert srv.kernel_modes == {"decode": "pallas_paged_decode",
                                "prefill_chunk": "pallas_chunked_prefill"}
    for (prompt, n_new), out in zip(reqs, outs):
        assert len(out) == len(prompt) + n_new
        gaps = fam.chosen_gaps(Z, SEED, out, len(prompt), n_new, 64)
        assert gaps.max() <= TOL


def test_serve_counts_the_work_over_each_kind_of_block(served):
    """The contract's counters: every position of every request is scanned
    once a MAMBA block (three of six), attended once an ATTENTION block
    (one), routed once an EXPERT block (two) — three different counts."""
    srv, reqs, _ = served
    live = sum(len(p) + k - 1 for p, k in reqs)
    assert srv.stats["ssd_scan_rows"] == MAMBA * live
    chunks = sum(-(-len(p) // CHUNK) for p, _ in reqs)
    steps = sum(k - 1 for _, k in reqs)
    assert srv.stats["ssd_state_rows"] == MAMBA * (chunks + steps)
    assert srv.stats["full_keys"] == ATTEND * sum(
        n * (n + 1) // 2 for n in (len(p) + k - 1 for p, k in reqs))
    assert srv.moe_expert_tokens.shape == (EXPERT, 4)
    assert srv.stats["moe_assignments"] \
        + srv.stats["moe_assignments_elsewhere"] == live * 3 * EXPERT
    assert srv.stats["moe_assignments_elsewhere"] > 0


def test_the_contracts_three_counts_differ(program):
    module = program[0]
    declared = contract_mod.read(module)
    assert declared.state_kinds == ("conv", "ssm") and declared.own_chunk_path
    assert declared.routes_experts and declared.holds_share
    assert (declared.num_layers, declared.expert_layers, declared.lane_layers,
            declared.experts) == (BLOCKS, EXPERT, ATTEND, 4)
    contract_mod.check(declared, module, PAGE, CHUNK, BLOCKS)
    d = NemotronHModel.declare(module.config)
    assert d.state_layers(BLOCKS) == (0, 3, 5)
    assert [d.sublayers(i) for i in range(BLOCKS)] == [
        ("mamba",), ("moe_mlp",), ("self_attn",), ("mamba",), ("moe_mlp",),
        ("mamba",)]
    work = module.slot_contract().block_work([(10, 2)], 0, BLOCKS)
    assert work == {"ssd_scan_rows": MAMBA * 2, "ssd_state_rows": MAMBA * 2,
                    "full_keys": ATTEND * (10 + 11)}


# ---- the config ----------------------------------------------------------- #
def test_config_reads_the_hf_keys():
    cfg = nemotron_h_config(TOY, held_experts=(4, 4))
    assert cfg.blocks_of("M") == (0, 3, 5) and cfg.blocks_of("E") == (1, 4)
    assert cfg.blocks_of("*") == (2,)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
            cfg.mamba_groups, cfg.conv_size, cfg.conv_width,
            cfg.mamba_width) == (16, 16, 16, 2, 4, CONV, 256)
    assert cfg.num_experts == 8 and cfg.held_experts == (4, 4)
    assert (cfg.head_dim, cfg.routed_scaling, cfg.moe_intermediate_size,
            cfg.shared_intermediate_size) == (32, 2.5, 48, 96)


@pytest.mark.parametrize("key,value,said", [
    ("hybrid_override_pattern", "ME-MEM", "dense MLP block"),
    ("hybrid_override_pattern", "MEXMEM", "hybrid_override_pattern"),
    ("n_group", 2, "n_group"), ("topk_group", 2, "topk_group"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("attention_bias", True, "attention_bias"),
    ("mlp_bias", True, "mlp_bias"),
    ("use_conv_bias", False, "use_conv_bias"),
    ("tie_word_embeddings", True, "untied head"),
    ("mlp_hidden_act", "silu", "relu2"),
    ("n_groups", 3, "n_groups"),
    ("moe_latent_size", 64, "moe_latent_size"),
    ("num_key_value_heads", 3, "KV heads")])
def test_config_refuses_by_name_what_the_model_lacks(key, value, said):
    with pytest.raises(ValueError, match=said):
        nemotron_h_config({**TOY, key: value})
