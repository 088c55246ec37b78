"""Trinity's block at a toy size on the CPU (hidden 64, 4 query / 2 KV heads
of 16, 8 experts top-2 + a shared one, window 16, pages of 8; layers
sliding x 4 then full, the first dense): the program through the two kinds
of K/V cache and through ``ServingEngine`` against the plain float32
reference (``benchmark/families/trinity.py``), the window chunk kernel
against a masked dense band, ring decode against the gathered ring, every
control of the family against the tolerance, the contract with a ring
beside K/V pages, the parameter count of the configuration's file.

Tolerance: program and reference are both float32 here and differ by the
order of their sums alone: 2e-4 absolute on logits of ~1.
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
from deepspeed_tpu.models import contract as slot_contract
from deepspeed_tpu.models import trinity
from deepspeed_tpu.ops.transformer import paged_attention, registry
from deepspeed_tpu.ops.transformer.decode_attention import decode_attention

TOL = 2e-4
W, PAGE = 16, 8
TOY = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=5,
    layer_types=["sliding_attention"] * 4 + ["full_attention"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, num_dense_layers=1,
    route_norm=True, route_scale=2.826, score_func="sigmoid", n_group=1,
    topk_group=1, sliding_window=W, rope_theta=10000, rope_scaling=None,
    max_position_embeddings=512, mup_enabled=True, rms_norm_eps=1e-5,
    hidden_act="silu", tie_word_embeddings=False)
SEED = 7
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a family instance of this file's own, drawn at a toy's scales (see
# test_dots3.py): at hidden 64 the real stds leave the head nothing to say
fam = spec.Benchmark(ROOT).family("trinity")
fam._W = 0.12
fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH = 2, 256
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


def _serve_logits(module, params, tokens, prompt_len, chunk, slots=3, slot=1,
                  cache_len=160):
    """Logits at every position of ``tokens``: the prompt through prefill
    chunks of ``chunk`` (the last one padded), the rest a token a decode
    step, teacher-forced, in lane ``slot`` of ``slots`` — the other lanes
    dead, as a retired slot is (table row on the trash page).  Returns them
    with the pools and the manager."""
    mgr = SlotPages(module, module.slot_contract(), slots, cache_len, PAGE,
                    0, chunk, False, {})
    pools = mgr.new_pools(jnp.float32)
    mgr.reserve(slot, tokens[:prompt_len], len(tokens) - prompt_len)

    @jax.jit
    def decode(pools, ids, pages, start, live):
        (lg, pools), _ = module.apply(
            params, ids, {**pools, "pages": pages,
                          **({"page_runs": jnp.zeros((), jnp.int32)}
                             if ids.shape[0] == 1 else {})},
            start, live=live, method=type(module).decode,
            mutable=["moe_stats"])
        return lg, pools

    out = []
    for s0 in range(0, prompt_len, chunk):
        ids = np.zeros(chunk, np.int32)
        n = min(chunk, prompt_len - s0)
        ids[:n] = tokens[s0:s0 + n]
        lg, pools = decode(pools, jnp.asarray(ids[None]),
                           jnp.asarray(mgr.row(slot)), jnp.int32(s0),
                           jnp.asarray((np.arange(chunk) < n)[None]))
        out.append(np.asarray(lg[0, :n]))
    active = np.arange(slots) == slot
    table = np.where(active[:, None], mgr.table(), 0)
    for p in range(prompt_len, len(tokens)):
        ids = np.where(active, tokens[p], 0).astype(np.int32)
        pos = np.where(active, p, cache_len - 1).astype(np.int32)
        lg, pools = decode(pools, jnp.asarray(ids[:, None]),
                           jnp.asarray(table), jnp.asarray(pos),
                           jnp.asarray(active[:, None]))
        out.append(np.asarray(lg[slot]))
    return np.concatenate(out), pools, mgr


@pytest.mark.parametrize("chunk,prompt_len,queries", [
    (8, 10, 512),       # a prompt under the window, a padded last chunk
    (8, 27, 512),       # over it: the ring wraps inside the prefill
    (32, 70, 8)])       # a chunk of two windows in 8-query blocks: blocks
                        # outside the band skipped, a padded tail kept out
def test_prefill_then_decode_match_the_reference(program, reference, chunk,
                                                 prompt_len, queries,
                                                 monkeypatch):
    """Chunk boundaries inside the prompt, padded last chunks, a ring that
    wraps more than once in prefill and again in decode (86 positions over
    a window of 16), decode rows that straddle pages of 8, both kinds of
    cache; logits, not tokens."""
    monkeypatch.setattr(paged_attention, "_WINDOW_CHUNK_QUERIES", queries)
    module, params = program
    got, _, _ = _serve_logits(module, params, TOKENS, prompt_len, chunk)
    assert np.abs(got - reference).max() < TOL


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


def test_a_stale_ring_row_in_the_program_fails_the_comparison(program,
                                                              reference):
    """The program with one ring row a lane never written again (its ring
    write dropped at row ``STALE_ROW``) is what the family's control
    computes in float32: outside the tolerance against the reference."""
    module, params = program
    from deepspeed_tpu.models import latent_attention
    write = latent_attention.write_rows

    def stale(pool, layer, table, positions, rows, keep=None):
        fresh = (positions < W) | (positions % W != fam.STALE_ROW)
        return write(pool, layer, table, positions, rows,
                     fresh if keep is None else keep & fresh)

    latent_attention.write_rows = stale
    try:
        got, _, _ = _serve_logits(module, params, TOKENS, 27, 8)
    finally:
        latent_attention.write_rows = write
    assert np.abs(got - reference).max() > 100 * TOL


# ---- (b) the window chunk kernel and the ring's decode ------------------- #
def _ring_fixture(start, chunk, heads=4, kvh=2, d=16, seed=0):
    """A ring of W rows in 2 layers x 5 pages (the slot's pages 3 and 1)
    that holds positions ``start - W .. start - 1`` where they exist, NaN
    elsewhere, and a chunk's q / k / v."""
    rng = np.random.default_rng(seed)
    ring = np.asarray([3, 1], np.int32)
    pools = np.full((2, 2, 5, PAGE, kvh * d), np.nan, np.float32)
    pools[:, 1, ring] = 0.0                 # the slot's own pages: finite
    history = rng.normal(size=(2, max(start, 1), kvh * d)).astype(np.float32)
    for t in range(max(start - W, 0), start):
        pools[:, 1, ring[(t // PAGE) % 2], t % PAGE] = history[:, t]
    q = rng.normal(size=(chunk, heads, d)).astype(np.float32)
    new = rng.normal(size=(2, chunk, kvh * d)).astype(np.float32)
    return pools, ring, history[:, :start], q, new


@pytest.mark.parametrize("start,chunk,queries", [
    (0, 8, 512), (8, 8, 512), (12, 8, 512), (40, 16, 512), (44, 16, 8),
    (64, 32, 8), (5, 24, 8)])
def test_window_chunk_kernel_is_a_masked_dense_band(start, chunk, queries,
                                                    monkeypatch):
    """``attn.gqa_window_chunk`` (interpreted) against plain attention over
    the sequence's whole history under the band mask: chunk starts on and
    off page boundaries, rings that have and have not wrapped, chunks past
    the window in several query blocks.  Pages the slot does not own hold
    NaN: nothing outside its ring is read."""
    monkeypatch.setattr(paged_attention, "_WINDOW_CHUNK_QUERIES", queries)
    pools, ring, history, q, new = _ring_fixture(start, chunk)
    got = paged_attention.window_chunk_attention(
        jnp.asarray(q), jnp.asarray(new[0]), jnp.asarray(new[1]),
        jnp.asarray(pools[0]), jnp.asarray(pools[1]), start,
        jnp.asarray(ring), window=W, layer=1)
    keys = np.concatenate([history, new], axis=1).reshape(2, -1, 2, 16)
    at = start + np.arange(chunk)[:, None]
    seen = np.arange(start + chunk)[None, :]
    band = (seen <= at) & (seen > at - W)
    want = registry._band_attention(
        jnp.asarray(q[None]), jnp.asarray(keys[0][None]),
        jnp.asarray(keys[1][None]), jnp.asarray(band))[0]
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def _ring_decode_case(seed=0):
    """Three lanes over rings of W rows in pools ``[2 layers, 7 pages, PAGE,
    32]``: one before its ring is full, one after it has wrapped, one
    dead."""
    rng = np.random.default_rng(seed)
    shape = (2, 7, PAGE, 32)
    cache = {"k": jnp.asarray(rng.normal(size=shape), jnp.float32),
             "v": jnp.asarray(rng.normal(size=shape), jnp.float32),
             "pages": jnp.asarray([[5, 2], [3, 6], [0, 0]], jnp.int32),
             "layer": jnp.asarray(1, jnp.int32),
             "ring": jnp.zeros((), jnp.int32)}
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, 1, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, 1, 2, 16)), jnp.float32)
    return q, k, v, jnp.asarray([[9], [37], [11]], jnp.int32), cache


def test_ring_decode_is_the_gather_path_bitwise(monkeypatch):
    """A decode step over the ring through ``attn.paged_decode`` (mode
    ``pallas_ring_decode``) equals the monolithic decode kernel over the
    gathered ring at the paged kernel's block, BITWISE — the paged kernels'
    own reference — and the gather path (``DSTPU_DISABLE_FLASH``) to
    rounding; both write the same rows."""
    cfg = trinity.trinity_config(TOY)
    q, k, v, positions, cache = _ring_decode_case()
    assert registry.select_kernel(s=1, paged=True, has_window=True,
                                  ring=True) == "pallas_ring_decode"
    out, new = registry.write_and_attend(cfg, q, k, v, positions, cache,
                                         window=W)
    # position 37 went to ring row 37 % 16 = 5: page 0 of the lane's ring
    np.testing.assert_array_equal(np.asarray(new["k"][1, 3, 5]),
                                  np.asarray(k[1, 0]).reshape(-1))
    np.testing.assert_array_equal(np.asarray(new["k"][1, 5, 1]),
                                  np.asarray(cache["k"][1, 5, 1]))
    from deepspeed_tpu.models.transformer import _paged_gather
    held = _paged_gather(new)
    lengths = jnp.minimum(positions[:, 0] + 1, W)
    want = decode_attention(
        q[:, 0], held["k"], held["v"], lengths,
        block_k=PAGE * paged_attention._decode_block_pages(PAGE, 2, 4))
    np.testing.assert_array_equal(np.asarray(out[:2, 0]),
                                  np.asarray(want[:2]))
    assert not np.asarray(out[2]).any()      # the dead lane: zeros
    monkeypatch.setenv("DSTPU_DISABLE_FLASH", "1")
    assert registry.select_kernel(s=1, paged=True, has_window=True,
                                  ring=True) == "reference_fallback"
    ref, ref_new = registry.write_and_attend(cfg, q, k, v, positions, cache,
                                             window=W)
    np.testing.assert_allclose(np.asarray(out[:2]), np.asarray(ref[:2]),
                               atol=2e-6)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new[name][:, 1:]),
                                      np.asarray(ref_new[name][:, 1:]))


def test_the_gather_path_serves_the_same_logits(program, reference,
                                                monkeypatch):
    """Without Pallas the ring's chunk is a gathered dense band and its
    decode the gathered ring: the same logits."""
    monkeypatch.setenv("DSTPU_DISABLE_FLASH", "1")
    module, params = program
    got, _, _ = _serve_logits(module, params, TOKENS[:44], 27, 8)
    assert np.abs(got - reference[:44]).max() < TOL


def test_registry_probes_the_ring_modes():
    modes = registry.kernel_modes(paged=True, has_window=True, ring=True)
    assert modes == {"decode": "pallas_ring_decode",
                     "prefill_chunk": "pallas_window_chunk"}
    # a window over LANE pages has no paged kernel; a full layer keeps its
    assert registry.kernel_modes(paged=True, has_window=True) == {
        "decode": "reference_fallback", "prefill_chunk": "reference_fallback"}
    assert registry.kernel_modes(paged=True) == {
        "decode": "pallas_paged_decode",
        "prefill_chunk": "pallas_chunked_prefill"}
    # a full layer's chunk past the kernel's bound: whole multiples of it
    assert registry.select_kernel(s=2048, paged=True) \
        == "pallas_chunked_prefill"
    assert registry.select_kernel(s=2048) == "reference_fallback"
    assert registry.select_kernel(s=768, paged=True) == "reference_fallback"
    # a ring's chunk that is no whole number of query blocks
    assert registry.select_kernel(s=768, paged=True, has_window=True,
                                  ring=True) == "reference_fallback"


def test_a_long_chunk_over_lane_pages_is_rows_of_the_kernels_bound(
        monkeypatch):
    """A full layer's chunk of two ``MAX_CHUNK_S`` (shrunk to 16 here) runs
    as two rows over the one table row: the same numbers as the chunk in
    one call."""
    # what a full layer hands the registry: its attention's declaration
    cfg = trinity.attention_of(trinity.trinity_config(TOY), sliding=False)
    rng = np.random.default_rng(1)
    shape = (1, 9, PAGE, 32)
    cache = {"k": jnp.zeros(shape, jnp.float32),
             "v": jnp.zeros(shape, jnp.float32),
             "pages": jnp.asarray([[4, 2, 7, 1, 3, 8]], jnp.int32),
             "layer": jnp.asarray(0, jnp.int32),
             "page_runs": jnp.zeros((), jnp.int32)}
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32)
    positions = (8 + jnp.arange(32))[None]
    whole, _ = registry.write_and_attend(cfg, q, k, v, positions, cache)
    monkeypatch.setattr(registry, "MAX_CHUNK_S", 16)
    split, _ = registry.write_and_attend(cfg, q, k, v, positions, cache)
    np.testing.assert_allclose(np.asarray(split), np.asarray(whole),
                               atol=2e-6)


# ---- (c) served through the slot engine --------------------------------- #
def test_served_through_the_slot_engine(program):
    """``init_inference`` -> ``serve()`` -> ``submit`` / ``drain``: five
    requests over three slots, prompts under the window, over it and of
    several windows, decode blocks of three steps.  Every generated token
    is the float32 reference's own greedy choice along the request's
    tokens, no dispatch took the gather path, and the two kinds of cache
    are counted apart."""
    module, params = program
    engine = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "prefill_chunk_size": None,
        "serving": {"enabled": True, "num_slots": 3, "max_cache_len": 96,
                    "page_size": PAGE, "prefill_chunk": 16,
                    "prefill_token_budget": 64, "decode_block": 3,
                    "prefix_cache": True, "tracing": True}})
    engine.set_params(params)
    srv = engine.serve()
    assert srv.contract.kv_pages and srv.contract.paged_layers == 1
    assert srv.chunk_rows == 1 and srv.table_width == 12 + 2
    assert srv.stats["prefix_sharing_refused"] == 1     # a ring model
    assert srv.ring_kernel_modes == {"decode": "pallas_ring_decode",
                                     "prefill_chunk": "pallas_window_chunk"}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, n).astype(np.int32)
               for n in (37, 9, 21, 64, 33)]
    news = [20, 13, 31, 8, 17]
    rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    srv.step()
    text = srv._pages.describe()
    assert "K/V rows" in text and "ring rows" in text \
        and "(2 a slot, a ring)" in text and "bytes held" in text
    done = srv.drain()
    stats = dict(srv.stats)
    srv.close()
    for rid, prompt, new in zip(rids, prompts, news):
        out = np.asarray(done[rid])
        assert len(out) == len(prompt) + new
        want = np.asarray(fam.logits(Z, SEED, out))
        at = np.arange(len(prompt) - 1, len(out) - 1)
        margin = np.sort(want[at], axis=-1)
        clear = margin[:, -1] - margin[:, -2] > 10 * TOL   # no near-tie
        assert clear.mean() > 0.9
        assert (np.argmax(want[at], -1) == out[len(prompt):])[clear].all()
    assert stats["paged_attention_fallback"] == 0
    routed = sum(map(len, prompts)) + sum(news) - len(news)
    assert stats["moe_assignments"] == routed * 2 * 4    # top-2, 4 layers
    # four sliding layers stop at the window, the full one does not
    assert 0 < stats["window_keys"] < 4 * stats["full_keys"]


# ---- (d) the contract: a K/V ring beside K/V pages ----------------------- #
def test_the_contract_and_its_work_counters():
    module = fam.program_model(TOY, dtype="float32")
    c = module.slot_contract()
    assert (c.num_layers, c.paged_layers, c.expert_layers, c.experts) \
        == (5, 1, 4, 8)
    assert c.kv_pages and c.own_chunk_path and c.routes_experts
    assert not c.holds_share and c.chunk_cap == 2048
    assert c.ring_pages(PAGE) == 2 and c.ring_kinds == ("k_ring", "v_ring")
    assert c.row_kinds == ("K/V rows", "ring rows")
    assert c.work_counters == ("window_keys", "full_keys")
    slot_contract.check(c, module, PAGE, 16, 1)
    # a chunk over positions 32 .. 47, window 16: every query sees 16 keys
    # in a sliding layer, 33 .. 48 in the full one
    assert c.chunk_work(32, 48, PAGE, 2, 1) == {
        "window_keys": 4 * 16 * 16, "full_keys": 16 * 32 + 16 * 17 // 2,
        "window_pages": 8, "window_ring_rows": 4 * 15,
        "window_chunk_rows": 4 * 16}
    # two live slots, 3 and 2 steps: contexts 10, 11, 12 and 20, 21
    assert c.block_work([(10, 3), (20, 2)], 2, 1) == {
        "window_keys": 4 * (10 + 11 + 12 + 16 + 16), "full_keys": 74,
        "window_pages": 16}
    pools = jax.eval_shape(lambda: module.init_paged_cache(
        7, PAGE, window_pages=5))
    assert pools["k"].shape == (1, 7, PAGE, 32)
    assert pools["k_ring"].shape == (4, 5, PAGE, 32)


def test_the_cache_manager_counts_the_two_kinds(program):
    module, _ = program
    stats = {}
    mgr = SlotPages(module, module.slot_contract(), 3, 96, PAGE, 0, 16, True,
                    stats)
    assert stats["prefix_sharing_refused"] == 1 and not mgr.share_prefixes
    assert (mgr.pages_per_slot, mgr.ring_pages, mgr.window_pages,
            mgr.table_width) == (12, 2, 7, 14)
    pools = mgr.new_pools(jnp.float32)
    assert pools["k"].shape == (1, 37, PAGE, 32)
    assert pools["k_ring"].shape == (4, 7, PAGE, 32)
    # K and V, float32: a lane page of ONE layer, a ring of four
    assert mgr.page_bytes == 2 * PAGE * 32 * 4
    assert mgr.ring_slot_bytes == 2 * 4 * W * 32 * 4
    mgr.reserve(1, np.zeros(20, np.int32), 10)
    row = mgr.row(1)[0]
    assert list(row[12:]) == [3, 4] and (row[:4] > 0).all() \
        and not row[4:12].any()
    reach = mgr.block_reach(1, [(20, 3)], 3)
    assert reach["ring_bytes_held"] == mgr.ring_slot_bytes
    assert reach["kv_bytes_mapped"] == 4 * mgr.page_bytes
    assert reach["kv_pages"] == 3 * 3 and reach["window_pages"] == 8
    assert mgr.chunk_reach(1, 32, live_end=20)["kv_pages"] == 4


@pytest.mark.parametrize("change,named", [
    (dict(lane_layers=5), "lane_layers"),
    (dict(ring_kinds=("k_ring", "rings")), "ring_kinds"),
    (dict(kv_pages=False), "kv_pages")])
def test_contract_check_names_what_the_cache_does_not_hold(change, named):
    import dataclasses
    module = fam.program_model(TOY, dtype="float32")
    c = dataclasses.replace(module.slot_contract(), **change)
    with pytest.raises(ValueError, match=named):
        slot_contract.check(c, module, PAGE, 16, 1)


@pytest.mark.parametrize("key,value,named", [
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}, "rope"),
    ("score_func", "softmax", "sigmoid"),
    ("n_group", 4, "groups")])
def test_what_is_not_implemented_is_refused_by_name(key, value, named):
    for refuse in (trinity.trinity_config, fam.sizes_of):
        with pytest.raises(ValueError, match=named):
            refuse(dict(TOY, **{key: value}))


def test_a_window_that_is_no_whole_pages_and_a_ragged_chunk_are_refused():
    module = fam.program_model(dict(TOY, sliding_window=20), dtype="float32")
    with pytest.raises(ValueError, match="whole number of pages"):
        module.slot_contract().ring_pages(PAGE)
    fault = fam.program_model(TOY).slot_contract().chunk_fault
    assert fault(512) is None and fault(2048) is None and fault(64) is None
    assert "512-query blocks" in fault(768)


# ---- (e) the configuration's count, from the module's own shapes -------- #
def test_parameter_count_is_the_configuration_files():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini-l5.json")) as f:
        cfg = json.load(f)
    module = fam.program_model(cfg)
    shapes = jax.eval_shape(module.init, jax.random.key(0),
                            {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat = jax.tree_util.tree_flatten_with_path(shapes["params"])[0]
    size = lambda keep: sum(int(np.prod(x.shape)) for path, x in flat
                            if keep([p.key for p in path]))
    parts = cfg["parameters_by_part"]
    gains = ("scale", "q_norm", "k_norm", "select_bias")
    attn = lambda layer: size(lambda n: n[0] == layer
                              and n[1] == "self_attn" and n[-1] not in gains)
    assert attn("layers_0") == attn("layers_4") \
        == parts["attention_each_of_5_q_k_v_o_gate"] == 27262976
    assert size(lambda n: n[0] == "layers_0" and n[1] == "mlp") \
        == parts["dense_ffn_layer_0_3x2048x6144"] == 37748736
    assert size(lambda n: n[0] == "layers_1" and n[-1] == "experts_wi") * 3 \
        == 128 * parts["one_expert_3x2048x1024"] \
        == parts["experts_128_each_of_4"] == 805306368
    assert size(lambda n: n[0] == "layers_1" and n[-1] == "gate_kernel") \
        == parts["router_each_of_4"] == 262144
    assert size(lambda n: n[0] == "layers_1" and n[-1] not in gains) \
        == parts["expert_layer_each_of_4"] == 839122944
    assert size(lambda n: n[0] == "layers_0" and n[-1] not in gains) \
        == parts["dense_layer"] == 65011712
    assert size(lambda n: n[0] == "embed_tokens") == parts["embedding"]
    assert size(lambda n: n[0] == "lm_head") == parts["head"]
    assert parts["embedding"] + parts["head"] == 819986432
    assert size(lambda n: n[-1] not in gains) == parts["matrices"] \
        == cfg["parameters"] == 4241489920
    assert size(lambda n: n[-1] in gains) == parts["norm_gains_and_biases"]
    assert [k for k in cfg["source_config"]
            if cfg[k] != cfg["source_config"][k]] == [
        "layer_types", "num_dense_layers", "num_hidden_layers"]
