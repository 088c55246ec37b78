"""Solar Open 2 (``solar_open2``) at a toy size on the CPU: the two state
kernels of the gated delta rule (``ops/transformer/delta_attention.py``) in
interpret mode and their plain-XLA form against the RECURRENCE; the program
through the SLOT ENGINE — the chunk step, the admit and the decode block that
``serving/slots.py`` builds for every model, over ``paging.SlotPages``'
pools — against the plain float32 reference (``benchmark/families/
solar_open2.py``); the two state kinds' hand-overs (a slot's second occupant,
a lane that retires inside a block, a request preempted in its prefill and
run again);
the cache manager's accounting by kind.

Tolerances: program and reference are both float32 here, so they differ by
the order of their sums alone (the chunked form's blocks against the
recurrence's positions, a paged kernel's online softmax, the expert kernel's
accumulation).  Logits are ~1 in size; ``TOL`` 2e-4 absolute is twenty times
what those reorderings give at these sizes (1e-5) and hundreds of times
under what one stale state row or a scanned padded tail moves them by
(``test_a_stale_state_is_visible`` reads 0.05 and more).  The kernels alone
are held to 2e-5 of a state ~1 in size.
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
from deepspeed_tpu.models import contract as contract_mod
from deepspeed_tpu.models.solar_open2 import solar_open2_config
from deepspeed_tpu.ops.transformer import delta_attention as delta
from deepspeed_tpu.ops.transformer import registry

TOL = 2e-4
TOY = dict(
    model_type="solar_open2",
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None),
    hidden_size=64, num_hidden_layers=3, num_attention_heads=4, head_dim=16,
    num_key_value_heads=2, vocab_size=128, intermediate_size=160,
    moe_intermediate_size=32, rms_norm_eps=1e-5, rope_theta=10000,
    partial_rotary_factor=1, tie_word_embeddings=False,
    max_position_embeddings=512, first_k_dense_replace=0, use_rope=False,
    gqa_interval=3, gqa_layers=[0, 4, 8], use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True,
    n_routed_experts=4, n_routed_experts_published=16, held_experts=[4, 4],
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1,
    num_experts_per_tok=2)
SEED, CHUNK, PAGE, BLOCK = 7, 8, 8, 4
LAYERS, KDA_LAYERS, HEADS, D = 3, 2, 4, 16
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a family instance of this file's own, drawn at a toy's scales: at hidden
# 64 the real stds give every layer nothing to add, so they are scaled
# until a toy layer weighs what a real one does (sqrt(hidden) x std ~ 1),
# and the balance is run on ONE sequence of the length the tests' own
# forwards are padded to (its sublayers compile once for both; what a
# balanced bias is for is tests/benchmark/test_benchmark_solar_open2.py's)
fam = spec.Benchmark(ROOT).family("solar_open2")
fam._W, fam._QK, fam._OUT, fam._DOWN, fam._EMBED = 0.12, 0.15, 0.2, 0.3, 0.5
fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH = 1, 64
Z = fam.sizes_of(TOY)


# ---- the two kernels against the recurrence ------------------------------- #
def _draw(T, seed=0, strong=False, heads=HEADS, d=D):
    """``q, k, v`` bfloat16 (``q`` and ``k`` unit a head, ``q`` times
    ``d^-1/2``), a log-decay a channel — per-token decays 0.87 .. 0.999, or
    ``strong``: down to e^-12, where ``exp(-G)`` alone overflows inside a
    block — and ``beta`` over (0, 2) with a third of the steps past 1."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (T, heads, d)))
    v = jax.random.normal(ks[2], (T, heads, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (T, heads, d), minval=-7.0,
                                    maxval=2.5 if strong else -2.0))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (T, heads)))
    bf = lambda x: x.astype(jnp.bfloat16)
    return bf(q), bf(k), bf(v), g, beta


def _recurrence(state, q, k, v, g, beta):
    """The recurrence by hand in numpy float64, one position after the
    other: ``(o [T, H, d], state)``."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    S, out = np.array(state, np.float64), []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[:, :, None] * S
        seen = np.einsum("hkv,hk->hv", S, k[t])
        S = S + k[t][:, :, None] * (beta[t][:, None]
                                    * (v[t] - seen))[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


def _pool(heads=HEADS, d=D):
    return jax.random.normal(jax.random.key(9), (2, 3, heads, d, d))


@pytest.fixture(scope="module")
def pool():
    return _pool()


# heads x width: the heads of it a grid step of the chunk kernel takes — the
# toy's go one a step, 128-wide heads four, or two where four do not divide
# them
GROUPS = {"4x16": 1, "8x128": 4, "6x128": 2}


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["interpreted", "xla"])
@pytest.mark.parametrize("T,real,fresh,strong,shape", [
    (150, 150, False, False, "4x16"),  # three blocks, the last a padded one
    (150, 150, True, False, "4x16"),   # the row's old contents are not read
    (150, 70, False, False, "4x16"),   # a padded tail past the second block
    (150, 3, False, False, "4x16"),    # a tail inside the first sub-block
    (150, 150, False, True, "4x16"),   # decays no exp(-G) survives
    (150, 150, False, False, "8x128"),   # four heads a grid step, two steps
    (150, 70, True, True, "8x128"),
    (150, 150, False, True, "6x128"),    # two a step: four do not divide six
])
def test_chunk_scan_is_the_recurrence(pallas, T, real, fresh, strong, shape):
    """A non-zero incoming state (or a fresh one over a dirty row), ``beta``
    past 1, blocks and sub-blocks crossed, a padded tail that leaves the
    state alone; only the call's own row of its own layer is written."""
    heads, d = map(int, shape.split("x"))
    assert delta._chunk_heads(heads, d) == GROUPS[shape]
    pool = _pool(heads, d)
    q, k, v, g, beta = _draw(T, strong=strong, heads=heads, d=d)
    out, new = delta.chunk_scan(q, k, v, g, beta, pool, 1, 2, fresh=fresh,
                                real=real, pallas=pallas)
    start = np.zeros((heads, d, d)) if fresh else np.asarray(pool[1, 2])
    want_o, want_s = _recurrence(start, *(x[:real] for x in (q, k, v, g,
                                                             beta)))
    assert np.abs(np.asarray(new[1, 2]) - want_s).max() < 2e-5
    # the output is bfloat16: half a unit in its last place at ~1
    assert np.abs(np.asarray(out[:real], np.float64) - want_o).max() < 8e-3
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, 2] = False
    assert (np.asarray(new)[untouched] == np.asarray(pool)[untouched]).all()
    assert out.shape == (T, heads, d) and out.dtype == jnp.bfloat16


def test_a_head_is_the_same_wherever_its_group_puts_it():
    """Eight heads of 128 go four a grid step.  Shuffled — every head in
    another group or another place of its group — and shuffled back, each
    head's output rows and state are BITWISE what they were: the heads of
    a step share its masks and nothing else."""
    heads, d = 8, 128
    assert delta._chunk_heads(heads, d) == 4
    pool = _pool(heads, d)
    q, k, v, g, beta = _draw(100, seed=5, heads=heads, d=d)
    order = np.asarray([5, 2, 7, 0, 3, 6, 1, 4])    # head order[i] in place i
    assert all(i // 4 != j // 4 or i % 4 != j % 4
               for i, j in enumerate(order))
    out, new = delta.chunk_scan(q, k, v, g, beta, pool, 0, 1, fresh=False,
                                real=90)
    shuffled = lambda x, axis: jnp.take(x, order, axis=axis)
    out_s, new_s = delta.chunk_scan(
        *(shuffled(x, 1) for x in (q, k, v, g, beta)), shuffled(pool, 2),
        0, 1, fresh=False, real=90)
    assert (np.asarray(out_s, np.float32)
            == np.asarray(shuffled(out, 1), np.float32)).all()
    assert (np.asarray(new_s[0, 1]) == np.asarray(new[0, 1])[order]).all()


def test_unequal_chunks_hand_the_state_on(pool):
    """One sequence as chunks of 60, 64 and 26 rows is the sequence in
    one: the state out of a chunk is the state into the next."""
    q, k, v, g, beta = _draw(150, seed=2)
    whole, want = delta.chunk_scan(q, k, v, g, beta, pool, 0, 1, fresh=True,
                                   real=150)
    state, outs, at = pool, [], 0
    for n in (60, 64, 26):
        part = slice(at, at + n)
        o, state = delta.chunk_scan(q[part], k[part], v[part], g[part],
                                    beta[part], state, 0, 1, fresh=at == 0,
                                    real=n)
        outs.append(o)
        at += n
    assert np.abs(np.asarray(state[0, 1] - want[0, 1])).max() < 2e-5
    assert np.abs(np.asarray(jnp.concatenate(outs) - whole,
                             np.float32)).max() < 8e-3


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["interpreted", "xla"])
def test_decode_step_is_one_step_and_dead_lanes_write_nothing(pool, pallas):
    """Five lanes: two live on rows of their own, three dead on the trash
    row.  A live lane's row is one step of the recurrence on; the trash row
    and every other row are as they were, a dead lane's output is zero."""
    q, k, v, g, beta = _draw(5, seed=3)
    rows = jnp.asarray([1, 2, 0, 0, 0])
    live = jnp.asarray([True, True, False, False, False])
    out, new = delta.decode_step(q, k, v, g, beta, pool, 0, rows, live,
                                 pallas=pallas)
    for n in (0, 1):
        pick = lambda x: x[n:n + 1]
        want_o, want_s = _recurrence(np.asarray(pool[0, n + 1]),
                                     *map(pick, (q, k, v, g, beta)))
        assert np.abs(np.asarray(new[0, n + 1]) - want_s).max() < 2e-6
        assert np.abs(np.asarray(out[n], np.float64) - want_o[0]).max() < 8e-3
    assert (np.asarray(new[0, 0]) == np.asarray(pool[0, 0])).all()
    assert (np.asarray(new[1]) == np.asarray(pool[1])).all()
    assert (np.asarray(out[2:], np.float32) == 0).all()


def test_the_registry_picks_the_state_kernels(pool, monkeypatch):
    """``registry.delta_state_update`` takes the Pallas kernels, and the
    plain-XLA recurrence under the switch the attention kernels' parity
    tests use; both forms of both calls agree."""
    q, k, v, g, beta = _draw(16, seed=4)
    taken = []
    for name in ("chunk_scan", "decode_step"):
        real = getattr(delta, name)
        monkeypatch.setattr(delta, name, lambda *a, _f=real, _n=name, **kw: (
            taken.append((_n, kw["pallas"])), _f(*a, **kw))[1])
    outs = []
    for off in ("0", "1"):
        monkeypatch.setenv("DSTPU_DISABLE_FLASH", off)
        chunk, p1 = registry.delta_state_update(
            q, k, v, g, beta, (pool, 0, 1), start=jnp.asarray(0), real=11)
        step, p2 = registry.delta_state_update(
            q, k, v, g, beta, (pool, 1, jnp.arange(16) % 3),
            live=jnp.arange(16) < 3)
        outs.append((chunk[:11], p1[0, 1], step[:3], p2[1]))
    assert taken == [("chunk_scan", True), ("decode_step", True),
                     ("chunk_scan", False), ("decode_step", False)]
    for a, b in zip(*outs):
        assert np.abs(np.asarray(a, np.float32)
                      - np.asarray(b, np.float32)).max() < 8e-3


# ---- the program and its engine ------------------------------------------- #
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
# the same three programs, traced and compiled ONCE a test session
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
    scheduler a test can read (``tests/unit/test_lfm2.py::Engine``):
    ``admit`` runs a request's chunks and the admit program, ``block`` one
    decode block; ``logits[rid]`` is what the ENGINE computed for each token
    it generated."""

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

    def admit(self, rid, slot, prompt, n_new, chunks=None):
        """``chunks``: stop after that many chunks (a request preempted in
        its prefill)."""
        P = len(prompt)
        assert self.pages.reserve(slot, prompt, n_new) is not None
        ids = np.zeros(-(-P // CHUNK) * CHUNK, np.int32)
        ids[:P] = prompt
        for ci in range(len(ids) // CHUNK)[:chunks]:
            last = int(min(max(P - 1 - ci * CHUNK, 0), CHUNK - 1))
            logits, self.pools, _ = self.chunk_fn(
                self.params, self.pools, jnp.asarray(self.pages.row(slot)),
                jnp.asarray(ids[None, ci * CHUNK:(ci + 1) * CHUNK]),
                jnp.asarray(ci * CHUNK, jnp.int32),
                jnp.asarray([last], jnp.int32))
        if chunks is not None:
            return
        _SEEN.clear()
        self.state, first = self.admit_fn(self.state, logits, self.rng,
                                          slot, P, n_new, -1)
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
    """The reference's ONE full forward over prompt + generated: the rows
    that predict each generated token."""
    full = np.concatenate([prompt, generated]).astype(np.int32)
    lg = np.asarray(fam.logits(Z, SEED, full))
    return lg[len(prompt) - 1:len(full) - 1]


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
    """Prompts shorter than the taps (1, 2), of a whole chunk, one over, and
    three chunks less one (a padded last chunk); then ten tokens through
    three decode blocks, the last one cut short.  Logits, not tokens — and
    the slot's state rows hold what the reference's recurrence holds after
    the last position the program has run."""
    eng = Engine(*program)
    prompt = _prompt(prompt_len)
    eng.admit("a", 1, prompt, 10)
    want = np.asarray(fam.kda_states(Z, SEED, prompt))
    assert np.abs(np.asarray(eng.pools["kda"][:, 2]) - want).max() < TOL
    assert (np.asarray(eng.pools["kda"][:, 1]) == 0).all()    # slot 0's row
    tokens, logits = eng.run("a")
    rows = _reference_rows(prompt, tokens)
    assert logits.shape == rows.shape == (10, 128)
    assert np.abs(logits - rows).max() < TOL
    assert (rows.argmax(-1) == tokens).all()
    # the last live step fed the ninth generated token
    after = np.asarray(fam.kda_states(
        Z, SEED, np.concatenate([prompt, tokens[:9]])))
    assert np.abs(np.asarray(eng.pools["kda"][:, 2]) - after).max() < TOL


def test_a_slots_second_occupant_starts_from_zeros(program):
    """Three requests on two slots: the third takes the first's slot and
    BOTH its state rows, which still hold what the first left there."""
    eng = Engine(*program)
    reqs = {"a": (_prompt(5, 1), 3), "b": (_prompt(11, 2), 14),
            "c": (_prompt(9, 3), 6)}
    eng.admit("a", 0, *reqs["a"])
    eng.admit("b", 1, *reqs["b"])
    eng.block()                               # a retires inside this block
    assert np.abs(np.asarray(eng.pools["kda"][:, 1])).max() > 0
    assert np.abs(np.asarray(eng.pools["conv"][:, 1])).max() > 0
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
    step — the dead steps wrote the trash row."""
    eng = Engine(*program)
    pa, pb = _prompt(6, 4), _prompt(13, 5)
    eng.admit("a", 0, pa, 3)                  # the admit samples one
    eng.admit("b", 1, pb, 9)
    eng.block()
    assert not bool(eng.state["active"][0]) and bool(eng.state["active"][1])
    ta, la = eng.run("a")
    assert np.abs(la - _reference_rows(pa, ta)).max() < TOL
    want = np.asarray(fam.kda_states(Z, SEED, np.concatenate([pa, ta[:2]])))
    assert np.abs(np.asarray(eng.pools["kda"][:, 1]) - want).max() < TOL
    tb, lb = eng.run("b")
    assert np.abs(lb - _reference_rows(pb, tb)).max() < TOL


def test_a_stale_state_is_visible(program):
    """What the tolerance stands against: the same request with its
    delta-rule state zeroed between prefill and decode leaves the reference
    by hundreds of tolerances."""
    eng = Engine(*program)
    prompt = _prompt(20, 7)
    eng.admit("a", 1, prompt, 4)
    eng.pools = {**eng.pools, "kda": jnp.zeros_like(eng.pools["kda"])}
    tokens, logits = eng.run("a")
    want = _reference_rows(prompt, tokens)
    assert np.abs(logits[0] - want[0]).max() < TOL      # the admit's row
    assert np.abs(logits[1] - want[1]).max() > 200 * TOL


def test_dead_lanes_write_the_trash_row_only(program):
    """A block over a table whose rows are all trash (every lane dead)
    leaves every slot's state rows as they were, in both kinds."""
    eng = Engine(*program)
    eng.admit("a", 1, _prompt(10, 8), 2)
    before = {k: np.asarray(eng.pools[k]) for k in ("conv", "kda")}
    eng.state = {**eng.state, "active": jnp.zeros((2,), bool)}
    eng.block()
    for kind, was in before.items():
        assert (np.asarray(eng.pools[kind])[:, 1:] == was[:, 1:]).all(), kind


def test_preempted_in_prefill_and_resumed_gives_the_same_logits(program):
    """A request stopped after two of its three chunks, its slot released
    and taken by another, then run again from its first position on another
    slot (restore is re-prefill: no state survives a preemption): the
    logits are an uninterrupted run's."""
    module, params = program
    prompt, other = _prompt(3 * CHUNK - 2, 9), _prompt(12, 10)
    eng = Engine(module, params)
    eng.admit("a", 0, prompt, 6, chunks=2)
    eng.pages.release(0)
    eng.admit("b", 0, other, 5)               # takes the slot and its rows
    eng.admit("a", 1, prompt, 6)
    tokens, logits = eng.run("a")
    assert np.abs(logits - _reference_rows(prompt, tokens)).max() < TOL
    tb, lb = eng.run("b")
    assert np.abs(lb - _reference_rows(other, tb)).max() < TOL


# ---- through init_inference -> serve() -> submit / drain ------------------ #
SERVING = {"enabled": True, "num_slots": 2, "max_cache_len": 64,
           "prefill_chunk": CHUNK, "decode_block": BLOCK, "page_size": PAGE,
           "prefix_cache": True}


@pytest.fixture(scope="module")
def served(program):
    """Five requests on two slots: slot churn (a slot's later occupants),
    padded chunk tails, lanes that retire inside blocks."""
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


def test_serve_counts_the_work_and_the_held_share(served):
    """The contract's counters summed into ``srv.stats``: every position of
    every request is scanned once a linear layer, and the held quarter of a
    16-wide router takes about a quarter of the choices."""
    srv, reqs, _ = served
    live = sum(len(p) + k - 1 for p, k in reqs)
    assert srv.stats["kda_scan_rows"] == KDA_LAYERS * live
    chunks = sum(-(-len(p) // CHUNK) for p, _ in reqs)
    steps = sum(k - 1 for _, k in reqs)
    assert srv.stats["kda_state_rows"] == KDA_LAYERS * (chunks + steps)
    assert srv.stats["full_keys"] == sum(
        n * (n + 1) // 2 for n in (len(p) + k - 1 for p, k in reqs))
    assert srv.moe_expert_tokens.shape == (LAYERS, 4)
    assert srv.stats["moe_assignments"] \
        + srv.stats["moe_assignments_elsewhere"] == live * 2 * LAYERS


def test_serve_refuses_prefix_sharing_by_name_and_counts_both_kinds(served):
    srv, _, _ = served
    assert srv.stats["prefix_sharing_refused"] == 1
    assert srv.stats["prefix_lookups"] == 0
    # everything drained: no row held
    assert srv.stats["state_rows_live"] == 0 and srv.stats["state_bytes"] == 0
    with srv._lock:
        text = srv._pages.describe()
    assert "state (conv, kda): state_rows_live 0/2" in text
    assert "(conv 0, kda 0)" in text


# ---- the cache manager's two state kinds ---------------------------------- #
def _manager(program, share=False, slots_=3, stats=None):
    stats = {"prefix_lookups": 0} if stats is None else stats
    return SlotPages(program[0], program[0].slot_contract(), slots_, 64,
                     PAGE, 0, CHUNK, share, stats), stats


CONV_ROW = KDA_LAYERS * 3 * 3 * HEADS * D          # values a slot
KDA_ROW = KDA_LAYERS * HEADS * D * D


@pytest.mark.parametrize("dtype,conv_bytes", [(jnp.float32, 4),
                                              (jnp.bfloat16, 2)])
def test_slot_pages_size_a_row_kind_by_kind(program, dtype, conv_bytes):
    """``conv`` follows the server's dtype, ``kda`` is float32 whatever it
    is: a row's bytes are summed over pools of different dtypes and
    shapes."""
    mgr, stats = _manager(program)
    assert mgr.state_kinds == ("conv", "kda") and mgr.state_rows == 4
    assert mgr.table_width == mgr.pages_per_slot + 1 == 9
    pools = mgr.new_pools(dtype)
    # 128 does not divide the toy's 192-wide q | k | v: a flat row, where
    # the cell's 3 x 24,576 are whole tiles under the row's index
    # (``short_conv.rows_shape``; test_tpu_compile.py holds the cell's)
    assert pools["conv"].shape == (KDA_LAYERS, 4, 3 * 3 * HEADS * D)
    assert pools["kda"].shape == (KDA_LAYERS, 4, HEADS, D, D)
    assert (pools["conv"].dtype, pools["kda"].dtype) == (dtype, jnp.float32)
    assert pools["k"].shape == (1, 25, 8, 2 * 16) and pools["k"].dtype == dtype
    assert mgr.state_kind_bytes == {"conv": CONV_ROW * conv_bytes,
                                    "kda": KDA_ROW * 4}
    assert mgr.state_row_bytes == CONV_ROW * conv_bytes + KDA_ROW * 4
    assert mgr.page_bytes == 2 * 8 * 32 * conv_bytes
    sized = mgr.pool_bytes(pools)
    assert sized["bytes_state"] == 4 * mgr.state_row_bytes
    assert sized["bytes_pages"] == 25 * mgr.page_bytes
    mgr.reserve(2, _prompt(20), 10)
    assert mgr.table()[2, -1] == 3 and stats["state_rows_live"] == 1
    assert stats["state_bytes"] == mgr.state_row_bytes
    text = mgr.describe()
    assert f"state_bytes {mgr.state_row_bytes} (conv " \
        f"{CONV_ROW * conv_bytes}, kda {KDA_ROW * 4})" in text


def test_dispatch_spans_carry_the_state_by_kind_and_the_scan(program):
    mgr, _ = _manager(program)
    mgr.new_pools(jnp.float32)
    mgr.reserve(0, _prompt(20), 10)
    mgr.reserve(2, _prompt(5), 3)
    # a chunk over positions 8 .. 15 of which 8 .. 12 are real
    chunk = mgr.chunk_reach(LAYERS, 16, live_end=13)
    assert chunk["state_rows"] == 1
    assert chunk["kda_scan_rows"] == KDA_LAYERS * 5
    assert chunk["kda_state_rows"] == KDA_LAYERS
    assert chunk["full_keys"] == sum(range(9, 14))
    reach = mgr.block_reach(LAYERS, [(21, 4), (6, 2)], 4)
    assert reach["state_rows"] == 6
    assert reach["kda_scan_rows"] == reach["kda_state_rows"] == KDA_LAYERS * 6
    assert reach["full_keys"] == 21 + 22 + 23 + 24 + 6 + 7
    assert reach["state_bytes"] == 2 * mgr.state_row_bytes
    assert reach["state_bytes_conv"] == 2 * CONV_ROW * 4
    assert reach["state_bytes_kda"] == 2 * KDA_ROW * 4
    assert reach["kv_bytes_mapped"] == mgr.in_use * mgr.page_bytes > 0


def test_contract_check_holds_both_state_kinds(program):
    module = program[0]
    declared = contract_mod.read(module)
    assert declared.state_kinds == ("conv", "kda") and declared.own_chunk_path
    assert declared.routes_experts and declared.holds_share
    assert (declared.expert_layers, declared.experts, declared.lane_layers) \
        == (LAYERS, 4, 1)
    contract_mod.check(declared, module, PAGE, CHUNK, LAYERS)
    with pytest.raises(ValueError, match="state_kinds names .'delta'."):
        contract_mod.check(dataclasses.replace(
            declared, state_kinds=("conv", "delta")), module, PAGE, CHUNK,
            LAYERS)

    class Flat(type(module)):
        def init_paged_cache(self, *args, **kw):
            pools = super().init_paged_cache(*args, **kw)
            return {**pools, "kda": pools["kda"].reshape(-1, HEADS, D, D)}

    with pytest.raises(ValueError, match="state kind 'kda' is a pool of "
                                         "shape"):
        contract_mod.check(declared, Flat(module.config), PAGE, CHUNK, LAYERS)


# ---- the config ----------------------------------------------------------- #
def test_config_reads_the_hf_keys():
    cfg = solar_open2_config(TOY, held_experts=(4, 4))
    assert cfg.gqa_layers == (0,) and cfg.kda_layers == (1, 2)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_size, cfg.kda_rank) \
        == (4, 16, 4, 16)
    assert cfg.n_routed_experts == 16 and cfg.held_experts == (4, 4)
    assert cfg.allow_neg_eigval and cfg.gqa_gate


@pytest.mark.parametrize("key,value,said", [
    ("rope_scaling", {"type": "yarn"}, "rope scaling"),
    ("use_rope", True, "use_rope"),
    ("kda_use_full_proj", True, "kda_use_full_proj"),
    ("linear_attn_config", dict(TOY["linear_attn_config"], num_kv_heads=2),
     "num_kv_heads"),
    ("n_group", 8, "grouped router"),
    ("first_k_dense_replace", 1, "every"),
    ("num_key_value_heads", 3, "KV heads")])
def test_config_refuses_by_name_what_the_model_lacks(key, value, said):
    with pytest.raises(ValueError, match=said):
        solar_open2_config({**TOY, key: value})
