"""Granite 4.0-H (``granitemoehybrid``) at a toy size on the CPU: the two
state kernels of the Mamba-2 scan (``ops/transformer/ssd.py``) in interpret
mode and their plain-XLA form against the RECURRENCE; the program through
the SLOT ENGINE — the chunk step, the admit and the decode block that
``serving/slots.py`` builds for every model, over ``paging.SlotPages``'
pools — against the plain float32 reference (``benchmark/families/
granite_hybrid.py``); the two state kinds' hand-overs (a slot's second
occupant after a LONG one, a lane that retires inside a block, a request
preempted in its prefill and run again); the three multipliers and the
attention scale, each visible when dropped; the cache manager's accounting
by kind.

Tolerances: program and reference are both float32 here, so they differ by
the order of their sums alone (the chunked form's blocks against the
recurrence's positions, a paged kernel's online softmax, the expert kernel's
accumulation).  Logits are ~0.5 in size; ``TOL`` 2e-4 absolute is some
twenty times what those reorderings give at these sizes and hundreds of
times under what one stale state row or a dropped multiplier moves them by
(``test_a_stale_state_is_visible`` reads 0.04 and more).  The kernels alone
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
from deepspeed_tpu.models.granite_hybrid import (GraniteHybridModel,
                                                 granite_hybrid_config)
from deepspeed_tpu.ops.transformer import registry, ssd

TOL = 2e-4
TOY = dict(
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
    max_position_embeddings=512)
SEED, CHUNK, PAGE, BLOCK = 7, 8, 8, 4
LAYERS, SSM_LAYERS, HEADS, P, N = 4, 3, 4, 64, 32
CONV = HEADS * P + 2 * N                          # x, B and C: one stream
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a family instance of this file's own, drawn at a toy's scales: at hidden
# 128 the real stds give the projections nothing to say, so they are scaled
# until a toy layer weighs what a real one does (sqrt(hidden) x std ~ 1),
# and the router's means are read on ONE sequence of the length the tests'
# own forwards are padded to (its sublayers compile once for both)
fam = spec.Benchmark(ROOT).family("granite_hybrid")
fam._W, fam._QK, fam._EMBED, fam._ROUTER = 0.09, 0.3, 0.35, 0.15
fam._SSM_OUT, fam._ATT_OUT, fam._SHARED_DOWN, fam._DOWN = 6.0, 3.0, 3.0, 6.0
fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH = 1, 64
Z = fam.sizes_of(TOY)


# ---- the two kernels against the recurrence ------------------------------- #
def _draw(T, seed=0, decay=None, heads=HEADS, p=P, n=N):
    """``x``, ``B``, ``C`` bfloat16, a step size a head over 0.001 .. 0.1
    through ``A`` over 1 .. 16 — per-token decays 0.2 .. 0.999 — or
    ``decay``: that log-decay a position for every head (the strongest the
    weight draw allows is -1.6: over a 128-row block the cumulative sum
    spans 200, where ``exp(-L)`` alone overflows; the weakest -0.001)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (T, heads, p))
    b, c = (jax.random.normal(k, (T, n)) for k in ks[1:3])
    dt = jnp.exp(jax.random.uniform(ks[3], (T, heads), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    a = -dt * jax.random.uniform(ks[4], (heads,), minval=1.0, maxval=16.0)
    if decay is not None:
        dt, a = jnp.full((T, heads), 0.1), jnp.full((T, heads), decay)
    bf = lambda t: t.astype(jnp.bfloat16)
    return bf(x), dt, a, bf(b), bf(c)


def _recurrence(state, x, dt, a, b, c):
    """The recurrence by hand in numpy float64, one position after the
    other: ``(S C [T, H, P], state)``."""
    x, dt, a, b, c = (np.asarray(t, np.float64) for t in (x, dt, a, b, c))
    S, out = np.array(state, np.float64), []
    for t in range(x.shape[0]):
        S = np.exp(a[t])[:, None, None] * S \
            + (dt[t][:, None] * x[t])[:, :, None] * b[t][None, None, :]
        out.append(S @ c[t])
    return np.stack(out), S


def _pool(heads=HEADS, p=P, n=N):
    return jax.random.normal(jax.random.key(9),
                             (2, 3) + ssd.state_shape(heads, p, n))


def _heads(rows, p=P):
    """Pool rows as ``[..., H, P, N]`` numpy."""
    return np.asarray(ssd.heads_of(rows, p))


@pytest.fixture(scope="module")
def pool():
    return _pool()


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["interpreted", "xla"])
@pytest.mark.parametrize("T,real,fresh,decay,shape", [
    (300, 300, False, None, "4x64x32"),   # three blocks, the last padded
    (300, 300, True, None, "4x64x32"),    # the row's old contents unread
    (300, 140, False, None, "4x64x32"),   # a padded tail past block two
    (300, 3, False, None, "4x64x32"),     # a tail inside the first block
    (300, 300, False, -1.6, "4x64x32"),   # the strongest decay: L spans 200
    (300, 300, False, -1e-3, "4x64x32"),  # the weakest: a memory of 1,000
    (200, 200, False, None, "16x64x128"),  # eight heads a step, two steps
    (200, 70, True, -1.6, "16x64x128"),
])
def test_chunk_scan_is_the_recurrence(pallas, T, real, fresh, decay, shape):
    """A non-zero incoming state (or a fresh one over a dirty row), blocks
    crossed, a padded tail that leaves the state alone, decays no
    ``exp(-L)`` survives: finite, and right; only the call's own row of its
    own layer is written."""
    heads, p, n = map(int, shape.split("x"))
    assert ssd.chunk_heads(heads, p) == min(heads, 8)
    pool = _pool(heads, p, n)
    x, dt, a, b, c = _draw(T, decay=decay, heads=heads, p=p, n=n)
    out, new = ssd.chunk_scan(x, dt, a, b, c, pool, 1, 2, fresh=fresh,
                              real=real, pallas=pallas)
    start = np.zeros((heads, p, n)) if fresh else _heads(pool[1, 2], p)
    want_o, want_s = _recurrence(start, *(t[:real] for t in (x, dt, a, b,
                                                             c)))
    assert np.isfinite(np.asarray(out)).all()
    size = max(np.abs(want_s).max(), 1.0)
    assert np.abs(_heads(new[1, 2], p) - want_s).max() < 2e-5 * size
    assert np.abs(np.asarray(out[:real], np.float64) - want_o).max() \
        < 2e-5 * max(np.abs(want_o).max(), 1.0)
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, 2] = False
    assert (np.asarray(new)[untouched] == np.asarray(pool)[untouched]).all()
    assert out.shape == (T, heads, p) and out.dtype == jnp.float32


def test_unequal_chunks_hand_the_state_on(pool):
    """One sequence as chunks of 130, 128 and 42 rows is the sequence in
    one: the state out of a chunk is the state into the next."""
    x, dt, a, b, c = _draw(300, seed=2)
    whole, want = ssd.chunk_scan(x, dt, a, b, c, pool, 0, 1, fresh=True,
                                 real=300)
    state, outs, at = pool, [], 0
    for n in (130, 128, 42):
        part = slice(at, at + n)
        o, state = ssd.chunk_scan(x[part], dt[part], a[part], b[part],
                                  c[part], state, 0, 1, fresh=at == 0,
                                  real=n)
        outs.append(o)
        at += n
    assert np.abs(np.asarray(state[0, 1] - want[0, 1])).max() < 2e-5
    assert np.abs(np.asarray(jnp.concatenate(outs) - whole)).max() < 2e-4


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["interpreted", "xla"])
def test_decode_step_is_one_step_and_dead_lanes_write_nothing(pool, pallas):
    """Five lanes: two live on rows of their own, three dead on the trash
    row.  A live lane's row is one step of the recurrence on; the trash row
    and every other row are as they were, a dead lane's output is zero."""
    assert ssd.step_tiles(HEADS // 2) == 2 and ssd.step_tiles(64) == 16
    assert ssd.state_shape(128, 64, 128) == (64, 128, 128)
    x, dt, a, b, c = _draw(5, seed=3)
    rows = jnp.asarray([1, 2, 0, 0, 0])
    live = jnp.asarray([True, True, False, False, False])
    out, new = ssd.decode_step(x, dt, a, b, c, pool, 0, rows, live,
                               pallas=pallas)
    for n in (0, 1):
        pick = lambda t: t[n:n + 1]
        want_o, want_s = _recurrence(_heads(pool[0, n + 1]),
                                     *map(pick, (x, dt, a, b, c)))
        assert np.abs(_heads(new[0, n + 1]) - want_s).max() < 2e-6
        assert np.abs(np.asarray(out[n], np.float64) - want_o[0]).max() < 2e-4
    assert (np.asarray(new[0, 0]) == np.asarray(pool[0, 0])).all()
    assert (np.asarray(new[1]) == np.asarray(pool[1])).all()
    assert (np.asarray(out[2:]) == 0).all()


def test_the_registry_picks_the_state_kernels(pool, monkeypatch):
    """``registry.ssm_state_update`` takes the Pallas kernels, and the
    plain-XLA recurrence under the switch the attention kernels' parity
    tests use; both forms of both calls agree."""
    x, dt, a, b, c = _draw(16, seed=4)
    taken = []
    for name in ("chunk_scan", "decode_step"):
        real = getattr(ssd, name)
        monkeypatch.setattr(ssd, name, lambda *args, _f=real, _n=name, **kw: (
            taken.append((_n, kw["pallas"])), _f(*args, **kw))[1])
    outs = []
    for off in ("0", "1"):
        monkeypatch.setenv("DSTPU_DISABLE_FLASH", off)
        chunk, p1 = registry.ssm_state_update(
            x, dt, a, b, c, (pool, 0, 1), start=jnp.asarray(0), real=11)
        step, p2 = registry.ssm_state_update(
            x, dt, a, b, c, (pool, 1, jnp.arange(16) % 3),
            live=jnp.arange(16) < 3)
        outs.append((chunk[:11], p1[0, 1], step[:3], p2[1]))
    assert taken == [("chunk_scan", True), ("decode_step", True),
                     ("chunk_scan", False), ("decode_step", False)]
    for got, want in zip(*outs):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-4


# ---- a stated attention scale ---------------------------------------------- #
@pytest.mark.parametrize("rows", [1, 16], ids=["decode", "chunk"])
def test_a_stated_attention_scale_reaches_kernels_and_plain_paths(
        rows, monkeypatch):
    """A config that STATES ``attention_scale`` (1/32 at the toy's heads of
    32, where the kernels' default is 1/sqrt(32)) gets it from the paged
    kernels (their ``scale=``) and, with the kernels switched off, from the
    gather path (``q`` handed over times what is left): both are softmax
    attention at that scale by hand, and neither is the default's."""
    # what the model hands the registry: its attention layers' declaration
    cfg = GraniteHybridModel.declare(granite_hybrid_config(
        TOY, held_experts=(4, 4), dtype="float32")).attention
    rng = np.random.default_rng(rows)
    shape = (1, 9, PAGE, 2 * 32)
    draw = lambda *dims: jnp.asarray(rng.normal(size=dims), jnp.float32)
    cache = {"k": draw(*shape), "v": draw(*shape),
             "pages": jnp.asarray([[4, 2, 7, 1, 3, 8]], jnp.int32),
             "layer": jnp.asarray(0, jnp.int32)}
    start = 20
    cache.update({"page_runs": jnp.zeros((), jnp.int32)} if rows > 1
                 else {"per_row": jnp.zeros((), jnp.int32)})
    q, k, v = draw(1, rows, 4, 32), draw(1, rows, 2, 32), draw(1, rows, 2, 32)
    positions = (start + jnp.arange(rows))[None]
    outs = {}
    for off in ("0", "1"):
        monkeypatch.setenv("DSTPU_DISABLE_FLASH", off)
        outs[off], new = registry.write_and_attend(cfg, q, k, v, positions,
                                                   cache)
    # by hand: the slot's rows in position order, this call's written
    table = np.asarray(cache["pages"][0])
    held = lambda pool: np.asarray(pool[0])[table].reshape(-1, 2, 32)
    keys, values = held(new["k"]), held(new["v"])
    want = np.zeros((rows, 4, 32))
    for i in range(rows):
        for h in range(4):
            s = keys[:start + i + 1, h // 2] @ np.asarray(q[0, i, h]) \
                * cfg.attention_scale
            p = np.exp(s - s.max())
            want[i, h] = (p / p.sum()) @ values[:start + i + 1, h // 2]
    for off, out in outs.items():
        assert np.abs(np.asarray(out[0]) - want).max() < 2e-5, off
    plain = dataclasses.replace(cfg, attention_scale=None)
    other, _ = registry.write_and_attend(plain, q, k, v, positions, cache)
    assert np.abs(np.asarray(other[0]) - want).max() > 1e-2


# ---- the router without a stored bias ------------------------------------- #
@pytest.mark.parametrize("experts,k,held", [(72, 10, (0, 18)),
                                            (72, 10, (54, 18)),
                                            (16, 3, (4, 4))])
def test_softmax_over_the_chosen_logits_is_the_scored_form_at_zero_bias(
        experts, k, held):
    """Granite's router — the ``k`` largest RAW logits, gates a softmax over
    those ``k`` — is ``moe/layer.py``'s scored form (a softmax over all the
    outputs, the top-k of score + bias, renormalised) at a bias of zeros:
    the same choices, the same gates, and through ``MoE(scoring="softmax",
    noaux_tc=True, held_experts=...)`` the same output as the held experts
    gated by hand plus the shared MLP.  ``moe/layer.py`` is not touched: the
    other families' programs (``PROGRAMS.lock``) are what they were."""
    import flax.linen as nn
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.moe.layer import MoE
    M, F, T = 64, 32, 40
    ks = jax.random.split(jax.random.key(experts + k), 3)
    x = jax.random.normal(ks[0], (T, M))
    gate_w = 0.3 * jax.random.normal(ks[1], (M, experts))
    choice, gate = dropless.route_scored(
        x, gate_w, jnp.zeros((experts,)), k, renormalize=True,
        scoring="softmax")
    top_l, top_i = jax.lax.top_k(jnp.matmul(
        x, gate_w, precision=jax.lax.Precision.HIGHEST), k)
    assert (np.asarray(choice) == np.asarray(top_i)).all()
    want_gate = np.asarray(jax.nn.softmax(top_l, axis=-1))
    assert np.abs(np.asarray(gate) - want_gate).max() < 1e-6
    layer = MoE(hidden_size=M, num_experts=experts, k=k, capacity_factor=None,
                norm_topk_prob=True, ffn_hidden_size=F, dtype=jnp.float32,
                gated=True, activation=nn.silu, scoring="softmax",
                noaux_tc=True, shared_ffn_hidden_size=2 * F,
                held_experts=held)
    params = layer.init(ks[2], x, train=False)
    p = params["params"]
    assert (np.asarray(p["select_bias"]) == 0).all()
    got = np.asarray(layer.apply(params, x, train=False)[0])
    logits = jnp.matmul(x, p["gate_kernel"],
                        precision=jax.lax.Precision.HIGHEST)
    top_l, top_i = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_l, axis=-1)
    experts_p = p["ExpertsMLP_0"]
    swiglu = lambda a, g, u, d: (nn.silu(a @ g) * (a @ u)) @ d
    want = swiglu(x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                  p["shared_down"]["kernel"])
    first, count = held
    for e in range(count):
        weight = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), axis=-1)
        want = want + weight[:, None] * swiglu(
            x, experts_p["experts_wg"][e], experts_p["experts_wi"][e],
            experts_p["experts_wo"][e])
    assert np.abs(got).mean() > 0.01
    assert np.abs(got - np.asarray(want)).max() < 1e-4 * np.abs(got).max()


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
        n = len(prompt)
        assert self.pages.reserve(slot, prompt, n_new) is not None
        ids = np.zeros(-(-n // CHUNK) * CHUNK, np.int32)
        ids[:n] = prompt
        for ci in range(len(ids) // CHUNK)[:chunks]:
            last = int(min(max(n - 1 - ci * CHUNK, 0), CHUNK - 1))
            logits, self.pools, _ = self.chunk_fn(
                self.params, self.pools, jnp.asarray(self.pages.row(slot)),
                jnp.asarray(ids[None, ci * CHUNK:(ci + 1) * CHUNK]),
                jnp.asarray(ci * CHUNK, jnp.int32),
                jnp.asarray([last], jnp.int32))
        if chunks is not None:
            return
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
    assert np.abs(want).mean() > 0.1          # the toy's layers are visible
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
    want = np.asarray(fam.ssm_states(Z, SEED, prompt))
    assert np.abs(_heads(eng.pools["ssm"][:, 2]) - want).max() < TOL
    assert (np.asarray(eng.pools["ssm"][:, 1]) == 0).all()    # slot 0's row
    tokens, logits = eng.run("a")
    rows = _reference_rows(prompt, tokens)
    assert logits.shape == rows.shape == (10, 128)
    assert np.abs(logits - rows).max() < TOL
    assert (rows.argmax(-1) == tokens).all()
    # the last live step fed the ninth generated token
    after = np.asarray(fam.ssm_states(
        Z, SEED, np.concatenate([prompt, tokens[:9]])))
    assert np.abs(_heads(eng.pools["ssm"][:, 2]) - after).max() < TOL


def test_a_short_prompt_after_a_long_occupant_starts_from_zeros(program):
    """Three requests on two slots: the third — a prompt of FIVE — takes
    the slot of a request that ran 43 positions, and BOTH its state rows,
    which still hold what that one left there."""
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
    want = np.asarray(fam.ssm_states(Z, SEED, np.concatenate([pa, ta[:2]])))
    assert np.abs(_heads(eng.pools["ssm"][:, 1]) - want).max() < TOL
    tb, lb = eng.run("b")
    assert np.abs(lb - _reference_rows(pb, tb)).max() < TOL


def test_a_stale_state_is_visible(program):
    """What the tolerance stands against: the same request with its scan
    state zeroed between prefill and decode leaves the reference by hundreds
    of tolerances."""
    eng = Engine(*program)
    prompt = _prompt(20, 7)
    eng.admit("a", 1, prompt, 4)
    eng.pools = {**eng.pools, "ssm": jnp.zeros_like(eng.pools["ssm"])}
    tokens, logits = eng.run("a")
    want = _reference_rows(prompt, tokens)
    assert np.abs(logits[0] - want[0]).max() < TOL      # the admit's row
    assert np.abs(logits[1] - want[1]).max() > 200 * TOL


def test_dead_lanes_write_the_trash_row_only(program):
    """A block over a table whose rows are all trash (every lane dead)
    leaves every slot's state rows as they were, in both kinds."""
    eng = Engine(*program)
    eng.admit("a", 1, _prompt(10, 8), 2)
    before = {k: np.asarray(eng.pools[k]) for k in ("conv", "ssm")}
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


@pytest.mark.parametrize("field,other", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("attention_scale", 32 ** -0.5)])
def test_each_multiplier_decides_the_logits(program, field, other):
    """The three multipliers and the attention scale (1/32 at the toy's
    heads of 32, not 1/sqrt(32)): the same weights under a config that
    drops one leave the reference by hundreds of tolerances."""
    module, params = program
    tokens = _prompt(24, 11)
    want = np.asarray(fam.logits(Z, SEED, tokens))
    dropped = type(module)(dataclasses.replace(module.config,
                                               **{field: other}))
    got = np.asarray(dropped.apply(
        params, {"input_ids": jnp.asarray(tokens[None])}))[0]
    assert np.abs(got - want).max() > 200 * TOL


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
    every request is scanned once a state-space layer, and the held quarter
    of a 16-wide router takes about a quarter of the choices."""
    srv, reqs, _ = served
    live = sum(len(p) + k - 1 for p, k in reqs)
    assert srv.stats["ssd_scan_rows"] == SSM_LAYERS * live
    chunks = sum(-(-len(p) // CHUNK) for p, _ in reqs)
    steps = sum(k - 1 for _, k in reqs)
    assert srv.stats["ssd_state_rows"] == SSM_LAYERS * (chunks + steps)
    assert srv.stats["full_keys"] == sum(
        n * (n + 1) // 2 for n in (len(p) + k - 1 for p, k in reqs))
    assert srv.moe_expert_tokens.shape == (LAYERS, 4)
    assert srv.stats["moe_assignments"] \
        + srv.stats["moe_assignments_elsewhere"] == live * 3 * LAYERS


def test_serve_refuses_prefix_sharing_by_name_and_counts_both_kinds(served):
    srv, _, _ = served
    assert srv.stats["prefix_sharing_refused"] == 1
    assert srv.stats["prefix_lookups"] == 0
    # everything drained: no row held
    assert srv.stats["state_rows_live"] == 0 and srv.stats["state_bytes"] == 0
    with srv._lock:
        text = srv._pages.describe()
    assert "state (conv, ssm): state_rows_live 0/2" in text
    assert "(conv 0, ssm 0)" in text


# ---- the cache manager's two state kinds ---------------------------------- #
def _manager(program, share=False, slots_=3, stats=None):
    stats = {"prefix_lookups": 0} if stats is None else stats
    return SlotPages(program[0], program[0].slot_contract(), slots_, 64,
                     PAGE, 0, CHUNK, share, stats), stats


CONV_ROW = SSM_LAYERS * 3 * CONV                   # values a slot
SSM_ROW = SSM_LAYERS * HEADS * P * N


@pytest.mark.parametrize("dtype,conv_bytes", [(jnp.float32, 4),
                                              (jnp.bfloat16, 2)])
def test_slot_pages_size_a_row_kind_by_kind(program, dtype, conv_bytes):
    """``conv`` follows the server's dtype, ``ssm`` is float32 whatever it
    is: a row's bytes are summed over pools of different dtypes and
    shapes — and the state a slot outweighs its pages."""
    mgr, stats = _manager(program)
    assert mgr.state_kinds == ("conv", "ssm") and mgr.state_rows == 4
    assert mgr.table_width == mgr.pages_per_slot + 1 == 9
    pools = mgr.new_pools(dtype)
    # 128 does not divide the toy's 320-wide stream: a flat row, where the
    # cell's 3 x 8,448 are whole tiles under the row's index
    # (``short_conv.rows_shape``; test_tpu_compile.py holds the cell's)
    assert pools["conv"].shape == (SSM_LAYERS, 4, 3 * CONV)
    assert pools["ssm"].shape == (SSM_LAYERS, 4, HEADS // 2, N, 2 * P)
    assert (pools["conv"].dtype, pools["ssm"].dtype) == (dtype, jnp.float32)
    assert pools["k"].shape == (1, 25, 8, 2 * 32) and pools["k"].dtype == dtype
    assert mgr.state_kind_bytes == {"conv": CONV_ROW * conv_bytes,
                                    "ssm": SSM_ROW * 4}
    assert mgr.state_row_bytes == CONV_ROW * conv_bytes + SSM_ROW * 4
    assert mgr.page_bytes == 2 * 8 * 64 * conv_bytes
    assert mgr.state_row_bytes > mgr.pages_per_slot * mgr.page_bytes
    sized = mgr.pool_bytes(pools)
    assert sized["bytes_state"] == 4 * mgr.state_row_bytes
    assert sized["bytes_pages"] == 25 * mgr.page_bytes
    mgr.reserve(2, _prompt(20), 10)
    assert mgr.table()[2, -1] == 3 and stats["state_rows_live"] == 1
    assert stats["state_bytes"] == mgr.state_row_bytes
    text = mgr.describe()
    assert f"state_bytes {mgr.state_row_bytes} (conv " \
        f"{CONV_ROW * conv_bytes}, ssm {SSM_ROW * 4})" in text


def test_dispatch_spans_carry_the_state_by_kind_and_the_scan(program):
    mgr, _ = _manager(program)
    mgr.new_pools(jnp.float32)
    mgr.reserve(0, _prompt(20), 10)
    mgr.reserve(2, _prompt(5), 3)
    # a chunk over positions 8 .. 15 of which 8 .. 12 are real
    chunk = mgr.chunk_reach(LAYERS, 16, live_end=13)
    assert chunk["state_rows"] == 1
    assert chunk["ssd_scan_rows"] == SSM_LAYERS * 5
    assert chunk["ssd_state_rows"] == SSM_LAYERS
    assert chunk["full_keys"] == sum(range(9, 14))
    reach = mgr.block_reach(LAYERS, [(21, 4), (6, 2)], 4)
    assert reach["state_rows"] == 6
    assert reach["ssd_scan_rows"] == reach["ssd_state_rows"] == SSM_LAYERS * 6
    assert reach["full_keys"] == 21 + 22 + 23 + 24 + 6 + 7
    assert reach["state_bytes"] == 2 * mgr.state_row_bytes
    assert reach["state_bytes_conv"] == 2 * CONV_ROW * 4
    assert reach["state_bytes_ssm"] == 2 * SSM_ROW * 4
    assert reach["kv_bytes_mapped"] == mgr.in_use * mgr.page_bytes > 0


def test_contract_check_holds_both_state_kinds(program):
    module = program[0]
    declared = contract_mod.read(module)
    assert declared.state_kinds == ("conv", "ssm") and declared.own_chunk_path
    assert declared.routes_experts and declared.holds_share
    assert (declared.expert_layers, declared.experts, declared.lane_layers) \
        == (LAYERS, 4, 1)
    contract_mod.check(declared, module, PAGE, CHUNK, LAYERS)
    with pytest.raises(ValueError, match="state_kinds names .'scan'."):
        contract_mod.check(dataclasses.replace(
            declared, state_kinds=("conv", "scan")), module, PAGE, CHUNK,
            LAYERS)

    class Flat(type(module)):
        def init_paged_cache(self, *args, **kw):
            pools = super().init_paged_cache(*args, **kw)
            return {**pools, "ssm": pools["ssm"].reshape(-1, N, 2 * P)}

    with pytest.raises(ValueError, match="state kind 'ssm' is a pool of "
                                         "shape"):
        contract_mod.check(declared, Flat(module.config), PAGE, CHUNK, LAYERS)


# ---- the config ----------------------------------------------------------- #
def test_config_reads_the_hf_keys():
    cfg = granite_hybrid_config(TOY, held_experts=(4, 4))
    assert cfg.layers_of("attention") == (1,)
    assert cfg.layers_of("mamba") == (0, 2, 3)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
            cfg.conv_size, cfg.conv_width) == (4, 64, 32, 4, CONV)
    assert cfg.num_experts == 16 and cfg.held_experts == (4, 4)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_scale, cfg.head_dim) \
        == (12.0, 0.22, 16.0, 0.03125, 32)


@pytest.mark.parametrize("key,value,said", [
    ("rope_scaling", {"type": "yarn"}, "rope scaling"),
    ("position_embedding_type", "rope", "position_embedding_type"),
    ("mamba_n_groups", 8, "mamba_n_groups"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("mamba_conv_bias", False, "mamba_conv_bias"),
    ("tie_word_embeddings", False, "tied head"),
    ("mamba_d_head", 32, "mamba_expand"),
    ("layer_types", ["mamba", "full_attention", "mamba", "mamba"],
     "layer_types"),
    ("num_key_value_heads", 3, "KV heads")])
def test_config_refuses_by_name_what_the_model_lacks(key, value, said):
    with pytest.raises(ValueError, match=said):
        granite_hybrid_config({**TOY, key: value})
