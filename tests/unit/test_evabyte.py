"""EvaByte (``evabyte``, EVA attention) at a toy size on the CPU: the program
through the SLOT ENGINE — the chunk step, the admit and the decode block
that ``serving/slots.py`` builds for every model, over ``paging.SlotPages``'
pools — against the plain float32 reference (``benchmark/families/
evabyte.py``); the ring's and the summary lane's hand-overs; the cache
manager's strided lane.

Toy: window 32, chunk 4, 2 layers, 4 heads of 8, page 8, prefill chunk 16,
decode block 8 — so a window is 4 pages of ring and 8 summary rows (one
lane page), a prefill chunk is half a window, and a decode block can hold a
window's end.

Tolerances: program and reference are both float32 here, so they differ by
the order of their sums alone (the kernels' online softmax over pages
against the reference's one softmax a window; the pooling over a gathered
ring page).  Logits are ~1.3 in size; ``TOL`` 2e-4 absolute is some twenty
times what those reorderings give at these sizes (~1e-5) and hundreds of
times under what a dropped summary, a ring row of the window before or a
uniform pooling moves them by (``test_each_control_fails`` reads 0.05 and
more for each).
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

TOL = 2e-4
TOY = dict(
    model_type="evabyte", attention_class="eva", attention_bias=False,
    chunk_size=4, window_size=32, hidden_size=32, intermediate_size=64,
    num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=2,
    num_pred_heads=8, vocab_size=64, max_position_embeddings=512,
    rms_norm_eps=1e-5, rope_theta=100000, rope_scaling=None,
    tie_word_embeddings=False, norm_add_unit_offset=True, fp32_skip_add=True,
    fp32_logits=True, hidden_act="silu")
SEED, CHUNK, PAGE, BLOCK, W, C = 7, 16, 8, 8, 32, 4
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
fam = spec.Benchmark(ROOT).family("evabyte")
Z = fam.sizes_of(TOY)
# a second instance of the family whose CONTROLS compute in float32: each
# differs from the reference by its one mechanism alone
ctl = spec.Benchmark(ROOT).family("evabyte")
_parts = ctl._parts
ctl._parts = lambda precision: ("float32",) + _parts(precision)[1:]
CONTROLS = ("summaries_dropped", "stale_ring", "mean_pooled")


@pytest.fixture(scope="module")
def program():
    module = fam.program_model(TOY, dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          fam.program_params(module, TOY, SEED))
    return module, params


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, 64, n) \
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
    scheduler a test can read (``tests/unit/test_lfm2.py`` has the long
    form): ``logits[rid]`` is what the ENGINE computed for each token it
    generated."""

    def __init__(self, module, params, num_slots=2, cache_len=128):
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
            logits, self.pools = self.chunk_fn(
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
        toks, self.pools, self.state = self.decode_fn(
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

    def run(self):
        while any(left for _, left in self.lanes.values()):
            self.block()


def _worst(eng, rid, prompt, family=fam, precision="float32"):
    """Largest |engine logit - reference logit| over ``rid``'s generated
    tokens, the reference teacher-forced over the engine's own tokens."""
    toks = np.concatenate([prompt, eng.tokens[rid]]).astype(np.int32)
    ref = np.asarray(family.logits(Z, SEED, toks[:-1], precision, heads=1))
    got = np.stack(eng.logits[rid])
    return np.abs(got - ref[len(prompt) - 1:]).max()


# ---- the uncached forward ------------------------------------------------ #
def test_uncached_forward_gives_every_head_of_the_reference(program):
    module, params = program
    toks = _prompt(77)                       # 2 windows and a partial chunk
    got = module.apply(params, {"input_ids": jnp.asarray(toks[None])})[0]
    ref = fam.logits(Z, SEED, toks)
    assert got.shape == ref.shape == (77, 8 * 64) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() <= TOL


# ---- prefill in chunks, then decode, through the slot programs ------------ #
@pytest.mark.parametrize("P,n_new,why", [
    (16, 6, "a whole prefill chunk; decode completes a 4-block"),
    (21, 14, "a padded tail and a partial 4-block, completed by decode; "
             "the window's end (32) inside the second decode block"),
    (32, 5, "the prompt ends exactly on a window boundary"),
    (45, 28, "mid-window, mid-block; a window's end (64) inside a block"),
    (70, 4, "three windows of prompt: two of them summaries"),
])
def test_prefill_then_decode_gives_the_reference_logits(program, P, n_new,
                                                        why):
    eng = Engine(*program)
    prompt = _prompt(P)
    eng.admit("r", 0, prompt, n_new)
    eng.run()
    assert len(eng.tokens["r"]) == n_new
    assert _worst(eng, "r", prompt) <= TOL, why


def test_a_padded_tail_writes_no_summary_and_decode_completes_it(program):
    """Hand-overs (i) and (ii): P = 21 leaves positions 20..23 a partial
    4-block, 24..31 the chunk's padded tail."""
    eng = Engine(*program)
    prompt = _prompt(21)
    eng.admit("r", 1, prompt, 8)
    lane = eng.pages.row(1)[0, 0]     # 32 positions = 8 rows = ONE lane page
    assert lane != 0 and (eng.pages.row(1)[0, 1:4] == 0).all()
    rows = lambda name: np.asarray(eng.pools[name])[:, lane]
    ksum, vsum = fam.summary_rows(Z, SEED, prompt)
    assert np.abs(rows("ksum")[:, :5] - np.asarray(ksum)).max() <= TOL
    assert np.abs(rows("vsum")[:, :5] - np.asarray(vsum)).max() <= TOL
    assert (rows("ksum")[:, 5:] == 0).all() and (rows("vsum")[:, 5:] == 0).all()
    eng.run()              # positions 21..27 fed: blocks 5 and 6 complete
    toks = np.concatenate([prompt, eng.tokens["r"]])[:28]
    ksum, _ = fam.summary_rows(Z, SEED, toks)
    assert np.abs(rows("ksum")[:, :7] - np.asarray(ksum)).max() <= TOL
    assert (rows("ksum")[:, 7:] == 0).all()


def test_a_reused_slot_sees_nothing_of_its_last_request(program):
    eng = Engine(*program)
    first = _prompt(75, 1)
    eng.admit("a", 0, first, 20)           # fills ring and three lane pages
    eng.run()
    eng.retire(0)
    second = _prompt(37, 2)                # one window back, other rows
    eng.admit("b", 0, second, 30)
    eng.run()
    assert _worst(eng, "a", first) <= TOL
    assert _worst(eng, "b", second) <= TOL


def test_two_lanes_at_different_phases_of_their_windows(program):
    """One decode step serves a lane 3 rows into its second window and a
    lane at the last rows of its first; the second admitted while the first
    decodes."""
    eng = Engine(*program)
    a, b = _prompt(35, 3), _prompt(27, 4)
    eng.admit("a", 0, a, 26)
    eng.block()
    eng.admit("b", 1, b, 18)
    eng.run()
    assert _worst(eng, "a", a) <= TOL
    assert _worst(eng, "b", b) <= TOL


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_fails(program, control):
    """The reference with ONE mechanism wrong, all float32, against the
    engine's logits past the first window: what a lost summary row, a ring
    masked by "what was written" or a uniform pooling would read."""
    eng = Engine(*program)
    prompt = _prompt(45)
    eng.admit("r", 0, prompt, 28)
    eng.run()
    assert _worst(eng, "r", prompt, ctl, "float32") <= TOL
    assert _worst(eng, "r", prompt, ctl, control) > 100 * TOL


# ---- the cache manager's strided lane ------------------------------------- #
def test_slot_pages_reckons_a_strided_lane_in_rows(program):
    module, _ = program
    stats = {}
    sp = SlotPages(module, module.slot_contract(), 3, 120, PAGE, 0, CHUNK,
                   True, stats)
    # 120 positions = 30 rows = 4 lane pages; the lane in positions
    assert (sp.stride, sp.pages_per_slot, sp.cache_len) == (4, 4, 128)
    assert sp.ring_pages == W // PAGE and sp.window_pages == 1 + 3 * 4
    assert sp.table_width == 4 + 4 and sp.num_pages == 3 * 4 + 1
    assert stats["prefix_sharing_refused"] == 1
    pools = jax.eval_shape(lambda: sp.new_pools(jnp.float32))
    assert pools["k"].shape == (2, 13, PAGE, 32) == pools["v"].shape
    assert pools["ksum"].shape == (2, 13, PAGE, 32) == pools["vsum"].shape
    # 32 positions are one lane page, 33 two: the boundary is in ROWS
    for slot, (P, new, pages) in enumerate([(20, 12, 1), (20, 13, 2),
                                            (100, 28, 4)]):
        row, start = sp.reserve(slot, _prompt(P), new)
        assert (len(row), start) == (pages, 0)
        assert (sp.table()[slot, :pages] == row).all()
        assert (sp.table()[slot, pages:4] == 0).all()
        assert (sp.table()[slot, 4:] == 1 + slot * 4 + np.arange(4)).all()
    assert sp.in_use == 7
    assert "summary rows 7 pages, ring rows 12/12 pages (4 a slot, a ring); " \
        "a lane row a 4 positions" in sp.describe()
    # padded chunks count: 17 tokens prefill two chunks = 32 positions
    sp.release(1)
    assert sp.in_use == 5 and (sp.table()[1] == 0).all()
    assert len(sp.reserve(1, _prompt(17), 1)[0]) == 1
    sp.release(1)
    assert len(sp.reserve(1, _prompt(33), 1)[0]) == 2
    assert sp.cannot_hold(12 * PAGE * 4) is None
    assert "a row a 4 positions" in sp.cannot_hold(12 * PAGE * 4 + 1)
    # what the dispatch spans carry: lane pages in rows, the module's counts
    reach = sp.chunk_reach(2, 48, live_end=45)
    assert reach["kv_pages"] == 2 * 2 and reach["kv_pages_table"] == 2 * 4
    assert reach["eva_summaries_written"] == 2 * 3   # 32..43; 44 is partial
    assert reach["eva_remote_pairs"] == 2 * 13 * 8
    assert reach["eva_local_pairs"] == 2 * sum(range(1, 14))
    work = sp.block_reach(2, [(46, 8), (31, 3)], BLOCK)
    assert work["kv_pages"] == sum(-(-(-(-(46 + i) // 4)) // 8)
                                   for i in range(8)) + 1 + 1 + 2
    # lane one feeds 45..52: ring rows 14..21, 8 summaries each; lane two
    # 30, 31 (no summary yet) and 32 (ring row 0, 8 summaries)
    assert work["eva_ring_rows"] == work["eva_local_pairs"] \
        == 2 * (sum(range(14, 22)) + 31 + 32 + 1)
    assert work["eva_remote_pairs"] == 2 * (8 * 8 + 8)
    assert work["eva_summaries_written"] == 2 * (2 + 1)
    assert work["ring_bytes_held"] == 2 * 2 * 4 * (2 * PAGE * 32 * 4)
    assert work["summary_bytes_mapped"] == 2 * (2 + 2) * (2 * PAGE * 32 * 4)


def test_a_prefill_chunk_may_not_straddle_a_window(program):
    declared = program[0].slot_contract()
    assert slots.admission_chunk(declared, 16) == 16
    assert slots.admission_chunk(declared, 32) == 32
    assert slots.admission_chunk(declared, 4096) == 32  # the cap: a window
    with pytest.raises(ValueError, match="divides window_size=32"):
        slots.admission_chunk(declared, 24)
    assert slots.chunk_write_form(declared, 16, PAGE) == "page_runs"


# ---- through init_inference -> serve() -> submit / drain ------------------ #
SERVING = {"enabled": True, "num_slots": 2, "max_cache_len": 128,
           "prefill_chunk": CHUNK, "decode_block": BLOCK, "page_size": PAGE,
           "prefix_cache": True}


@pytest.fixture(scope="module")
def served(program):
    """Five requests on two slots: slot churn, padded tails, windows' ends
    inside decode blocks, lanes that retire inside blocks."""
    module, params = program
    eng = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None, "serving": SERVING})
    eng.set_params(params)
    srv = eng.serve()
    reqs = [(_prompt(n, 5), k) for n, k in
            [(45, 28), (21, 14), (70, 9), (32, 40), (9, 30)]]
    rids = [srv.submit(p, max_new_tokens=k) for p, k in reqs]
    outs = srv.drain()
    return srv, reqs, [np.asarray(outs[r]) for r in rids]


def test_serve_takes_the_engines_own_programs(served):
    srv, _, _ = served
    assert srv.kernel_modes == {"decode": "pallas_paged_decode",
                                "prefill_chunk": "pallas_chunked_prefill"}
    assert srv.stats["paged_attention_fallback"] == 0
    assert srv.stats["prefix_sharing_refused"] == 1
    assert srv.stats["chunk_write"] == "page_runs"
    assert srv.table_width == srv.pages_per_slot + W // PAGE
    assert srv.cache_len == 128 and srv.pages_per_slot == 4


def test_serve_gives_the_reference_choice(served):
    """Every generated token's REFERENCE logit is the reference's largest
    at its position, to the float32 tolerance — the benchmark's ``correct``
    statistic, at toy size."""
    _, reqs, outs = served
    for (prompt, n_new), out in zip(reqs, outs):
        assert len(out) == len(prompt) + n_new
        gaps = fam.chosen_gaps(Z, SEED, out, len(prompt), n_new, 128)
        assert gaps.max() <= TOL


def test_serve_sums_the_modules_counters(served, program):
    srv, reqs, _ = served
    module, _ = program
    local = remote = 0
    for prompt, n_new in reqs:
        a, b = module._pairs(0, len(prompt) + n_new - 1)
        local, remote = local + a, remote + b
    assert srv.stats["eva_local_pairs"] == 2 * local
    assert srv.stats["eva_remote_pairs"] == 2 * remote
    assert srv.stats["eva_summaries_written"] == 2 * sum(
        (len(p) + k - 1) // C for p, k in reqs)
    with srv._lock:
        assert "summary rows 0 pages, ring rows 0/8" in srv._pages.describe()


def test_the_config_refuses_what_is_not_evabyte():
    from deepspeed_tpu.models.evabyte import evabyte_config
    for key, value in (("attention_class", "softmax"),
                       ("tie_word_embeddings", True),
                       ("norm_add_unit_offset", False),
                       ("num_key_value_heads", 2), ("window_size", 30)):
        with pytest.raises(ValueError):
            evabyte_config({**TOY, key: value})
        if key != "window_size":
            with pytest.raises(ValueError):
                fam.sizes_of({**TOY, key: value})


# ---- device time by part --------------------------------------------------- #
@pytest.mark.parametrize("frames,part", [
    ("attn.step/attn._eva_attend_step/attn.eva_decode/pallas_call",
     "attn.eva"),
    ("attn.chunk/attn._eva_attend_chunk/attn.eva_chunk/pallas_call",
     "attn.eva"),
    ("attn.chunk/attn._eva_attend_chunk/reshape", "attn.eva"),
    ("attn.step/eva.summarise/reduce_sum", "eva.summarise"),
    ("attn.step/attn._write_summaries/cache.write/scatter", "cache.write"),
    ("attn.chunk/cache.write/dynamic_update_slice", "cache.write"),
    ("attn.chunk/attn._project/attn.rope/concatenate", "attn.proj"),
    ("attn.chunk/attn._project/q_proj/dot_general", "attn.proj"),
    ("attn.chunk/o_proj/dot_general", "attn.proj"),
    ("attn.step/gather", "attn.core"),
    ("mlp/down_proj/dot_general", "mlp"), ("post_attn_norm/mul", "norm"),
    ("add", "residual"),
])
def test_the_programs_op_names_file_under_their_parts(frames, part):
    """``op_name``s as the compiled slot programs carry them (read off the
    described-chip compile), through the profiler's ONE table: the two
    kernels and the pooling have parts of their own."""
    from deepspeed_tpu.profiling.flops_profiler import profiler
    name = "jit(decode_block)/while/body/closed_call/EvaByteModel.decode/" \
        f"layers_3/{frames}"
    assert profiler.part_of(name)[0] == part
    assert {"attn.eva", "eva.summarise"} <= set(profiler.PARTS)
