"""dots3-note-prev at a toy size on the CPU: the program through the paged
pools against the plain float32 reference (``benchmark/families/dots3.py``),
its kernels against ``jax.numpy``, the expert share, the window ring.

Tolerances: program and reference are both float32 here, so they differ by
the order of their sums alone — the chunk form decompresses keys and runs a
blocked online softmax, the decode step attends latent rows in the absorbed
form, the reference does neither.  Logits are ~1 in size; 2e-4 absolute is
fifty times what those reorderings give at these sizes and a hundred times
under what one wrong cache row, one wrong kept key or a missing expert
moves them by.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import spec
from deepspeed_tpu.inference.serving.paging import SlotPages
from deepspeed_tpu.models.dots3 import Dots3Model, dots3_config
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.transformer import latent_attention as ops

TOL = 2e-4
TOY = dict(
    apply_mla_qkv_lora_rescale=True, attention_bias=False,
    attention_gate_type="headwise", first_k_dense_replace=1,
    hidden_act="silu", hidden_size=64, index_head_dim=16, index_n_heads=4,
    index_topk=24, intermediate_size=96, kv_lora_rank=32,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention"],
    max_position_embeddings=512, moe_intermediate_size=32, moe_layer_freq=1,
    n_routed_experts=4, n_routed_experts_published=16, held_experts=[4, 4],
    n_shared_experts=1, norm_topk_prob=True, num_attention_heads=4,
    num_experts_per_tok=4, num_hidden_layers=4, num_key_value_heads=4,
    q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
    rms_norm_eps=1e-5, rope_scaling=None, rope_theta=80000000,
    routed_scaling_factor=1, scoring_func="sigmoid", sliding_window_size=17,
    swa_attention_gate_type="headwise", swa_kv_lora_rank=40,
    swa_num_attention_heads=2, swa_num_key_value_heads=2,
    swa_q_lora_rank=48, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
    swa_rope_theta=50000, swa_v_head_dim=16, tie_word_embeddings=False,
    topk_method="noaux_tc", v_head_dim=16, vocab_size=128)
SEED = 7
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a family instance of this file's own (``Benchmark.family`` loads the file
# anew), drawn at a toy's scales: at hidden 64 the real stds give every
# layer nothing to add, so they are scaled until a toy layer weighs what a
# real one does (sqrt(hidden) x std ~ 1, attention logits of a few units)
fam = spec.Benchmark(ROOT).family("dots3")
fam._W, fam._OUT, fam._ATTN, fam._DOWN, fam._EMBED = 0.12, 0.12, 0.15, 0.3, 1.0


def _program(model, dtype="float32"):
    module = fam.program_model(model, dtype=dtype)
    return module, fam.program_params(module, model, SEED)


def _serve_logits(module, params, tokens, prompt_len, chunk, page=8,
                  slots=3, slot=1, cache_len=192):
    """Logits at every position of ``tokens``: the prompt through prefill
    chunks of ``chunk`` (the last one padded), the rest a token a decode
    step, teacher-forced, in lane ``slot`` of ``slots`` — the other lanes
    dead, as a retired slot is (table row on the trash page)."""
    mgr = SlotPages(module, module.slot_contract(), slots, cache_len, page,
                    0, chunk, True, {"prefix_lookups": 0})
    pools = mgr.new_pools(jnp.float32)
    mgr.reserve(slot, tokens[:prompt_len], len(tokens) - prompt_len)
    # one program a call form (a chunk of one slot, a token a lane), traced
    # once: the loops below replay them
    @jax.jit
    def decode(pools, ids, pages, start, live):
        (lg, pools), _ = module.apply(
            params, ids, {**pools, "pages": pages}, start, live=live,
            method=type(module).decode, mutable=["moe_stats"])
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
    return np.concatenate(out), mgr


# ---- (a) prefill then decode through the pools against the reference ---- #
@pytest.mark.parametrize("chunk,prompt_len", [(16, 50), (32, 50), (64, 70),
                                              (32, 64)])
def test_prefill_then_decode_match_the_reference(chunk, prompt_len):
    """Both layer kinds; a context (86) longer than the toy ``index_topk``
    (24) and the toy window (17); chunk boundaries inside both; padded last
    chunks; logits, not tokens."""
    module, params = _program(TOY)
    tokens = np.random.default_rng(3).integers(0, 128, 86).astype(np.int32)
    got, _ = _serve_logits(module, params, tokens, prompt_len, chunk)
    want = np.asarray(fam.logits(fam.sizes_of(TOY), SEED, tokens))
    assert np.abs(want).mean() > 0.3          # the toy's layers are visible
    assert np.abs(got - want).max() < TOL


def test_the_uncached_forward_is_the_reference():
    module, params = _program(TOY)
    tokens = np.random.default_rng(4).integers(0, 128, 80).astype(np.int32)
    got = np.asarray(module.apply(params,
                                  {"input_ids": jnp.asarray(tokens[None])}))
    want = np.asarray(fam.logits(fam.sizes_of(TOY), SEED, tokens))
    assert np.abs(got[0] - want).max() < TOL


# ---- (b) a kept set that holds every key is dense latent attention ------ #
@pytest.mark.parametrize("topk,same", [(128, True), (24, False)])
def test_index_topk_at_least_the_context_is_dense_attention(topk, same):
    """With ``index_topk >= context`` nothing is dropped, so the indexer's
    weights cannot matter: a model whose indexer is drawn anew gives the
    same logits.  With ``index_topk`` under the context they do matter."""
    model = dict(TOY, index_topk=topk)
    module, params = _program(model)
    tokens = np.random.default_rng(5).integers(0, 128, 64).astype(np.int32)
    forward = jax.jit(lambda p: module.apply(
        p, {"input_ids": jnp.asarray(tokens[None])}))   # traced once
    run = lambda p: np.asarray(forward(p))[0]
    other = jax.tree_util.tree_map_with_path(
        lambda path, x: x[::-1] if "index" in path[-1].key else x, params)
    diff = np.abs(run(params) - run(other)).max()
    assert (diff == 0.0) if same else (diff > 1e-2)
    if same:        # and it is the reference's dense causal softmax
        want = np.asarray(fam.logits(fam.sizes_of(model), SEED, tokens))
        assert np.abs(run(params) - want).max() < TOL


# ---- (c) the shares add up to the uncut layer --------------------------- #
def _expert_layer_inputs(z, layer=1):
    key = fam.seed_key(SEED)
    a = jax.random.normal(jax.random.fold_in(key, 5), (64, z["h"]))
    return key, a, fam.layer_weights(z, key, layer)


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Four shares of 4 of the toy's 16 experts: their routed parts, plus
    the shared expert counted ONCE, are the uncut reference's layer."""
    z = fam.sizes_of(TOY)
    key, a, w = _expert_layer_inputs(z)
    uncut = fam.expert_layer(z, key, 1, a, w, "float32", held=(0, 16))
    parts = [fam.expert_layer(z, key, 1, a, w, "float32", held=(f, 4),
                              shared=False) for f in range(0, 16, 4)]
    shared = fam.expert_layer(z, key, 1, a, w, "float32", held=(0, 0))
    assert all(np.abs(np.asarray(p)).mean() > 1e-3 for p in parts)
    assert np.abs(np.asarray(sum(parts) + shared - uncut)).max() < 1e-5
    # counting the shared expert with every share would not
    assert np.abs(np.asarray(sum(parts) + 4 * shared - uncut)).max() > 1e-2


@pytest.mark.parametrize("first", [0, 4, 8, 12])
@pytest.mark.parametrize("rows", [16, dropless.GROUPED_MIN_ROWS],
                         ids=["dense", "grouped"])
def test_the_programs_share_is_the_references(first, rows):
    """The program's expert layer, told it holds experts ``first .. first
    + 3``, against the reference's same share — in both of its forms: a
    decode step's few rows (every touched expert over every row) and a
    chunk's many (sorted by expert, real rows only)."""
    from deepspeed_tpu.moe.layer import MoE
    z = fam.sizes_of(TOY)
    key = fam.seed_key(SEED)
    a = jax.random.normal(jax.random.fold_in(key, 6), (rows, z["h"]))
    w = fam.layer_weights(z, key, 1)
    want = fam.expert_layer(z, key, 1, a, w, "float32", held=(first, 4))
    experts = [fam.expert_weights(z, key, 1, first + e) for e in range(4)]
    stack = lambda n: jnp.stack([e[n] for e in experts]).astype(jnp.float32)
    layer = MoE(hidden_size=z["h"], num_experts=16, k=4,
                capacity_factor=None, ffn_hidden_size=z["ef"],
                dtype=jnp.float32, gated=True, activation=jax.nn.silu,
                scoring="sigmoid", shared_ffn_hidden_size=z["ef"],
                held_experts=(first, 4))
    f32 = lambda t: t.astype(jnp.float32)
    params = {"params": {
        "gate_kernel": f32(w["router"]), "select_bias": f32(w["select_bias"]),
        "ExpertsMLP_0": {"experts_wg": stack("wg"), "experts_wi": stack("wu"),
                         "experts_wo": stack("wd")},
        **{f"shared_{n}": {"kernel": f32(w[f"shared_{n}"])}
           for n in ("gate", "up", "down")}}}
    live = jnp.arange(rows) % 7 != 3
    (got, _, _), sown = layer.apply(params, a, train=False, live=live,
                                    mutable=["moe_stats"])
    shared = fam.expert_layer(z, key, 1, a, w, "float32", held=(0, 0))
    want = jnp.where(live[:, None], want, shared)   # a dead row: shared only
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    stats = sown["moe_stats"]
    assert int(stats["expert_tokens"].sum()) + int(stats["elsewhere"]) \
        == 4 * int(live.sum())


# ---- (d) the grouped matmul is the dense form --------------------------- #
@pytest.mark.parametrize("tile", [8, 32])
@pytest.mark.parametrize("rows", [40, 256])
def test_grouped_experts_equal_the_dense_form(rows, tile):
    """Sorted by expert and padded to row tiles, against every touched
    expert over every row — with an expert (2) that receives no row, one
    that receives most, and choices that fall on no held expert."""
    rng = np.random.default_rng(rows + tile)
    E, M, F, k = 5, 64, 32, 3
    x = jnp.asarray(rng.normal(size=(rows, M)), jnp.float32)
    local = rng.choice([0, 0, 0, 1, 3, 4, E], size=(rows, k))
    local = jnp.asarray(local, jnp.int32)
    gate = jnp.asarray(rng.uniform(0.1, 1.0, (rows, k)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(E, M, F)) * 0.2, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(E, F, M)) * 0.2, jnp.float32)
    counts = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.int32),
                     axis=(0, 1))[:E]
    assert int(counts[2]) == 0
    dense = dropless.experts(x, dropless.combine_of(local, gate, E), counts,
                             wg, wu, wd, jax.nn.silu)
    grouped = dropless.experts_grouped(x, local, gate, wg, wu, wd,
                                       jax.nn.silu, tile=tile)
    assert np.abs(np.asarray(dense)).mean() > 0.05
    assert np.abs(np.asarray(grouped - dense)).max() < 1e-4


def test_grouped_layout_pads_every_expert_to_whole_tiles():
    local = jnp.asarray([[0, 2], [2, 3], [2, 1], [3, 3]], jnp.int32)
    dest, source, tile_expert, live = dropless.grouped_layout(local, 3, 2)
    # expert 0: 1 row (1 tile), expert 1: 1 row, expert 2: 3 rows (2 tiles)
    assert int(live) == 4
    assert list(np.asarray(tile_expert[:4])) == [0, 1, 2, 2]
    assert np.asarray(dest).tolist() == [[0, 4], [5, 14], [6, 2], [14, 14]]
    assert np.asarray(source)[[0, 2, 4, 5, 6]].tolist() == [0, 2, 0, 1, 2]


# ---- (e) a window layer's ring -------------------------------------------- #
WINDOW_ONLY = dict(TOY, layer_types=["sliding_attention"] * 3,
                   num_hidden_layers=3, first_k_dense_replace=3)


@pytest.mark.parametrize("chunk,prompt_len,page", [
    (8, 61, 8), (16, 61, 8), (24, 50, 8), (40, 77, 8), (64, 65, 8),
    (64, 128, 8), (16, 40, 16)])
def test_the_ring_never_needs_a_row_it_has_given_up(chunk, prompt_len, page):
    """Window layers alone, a window of 17 in a ring of 3–4 pages, prompts
    many rings long: were a chunk's padded tail, a chunk longer than the
    ring or a decode step to overwrite a row that a later query still
    attends, the logits would leave the reference's."""
    module, params = _program(WINDOW_ONLY)
    n = prompt_len + 30
    tokens = np.random.default_rng(chunk).integers(0, 128, n).astype(np.int32)
    got, mgr = _serve_logits(module, params, tokens, prompt_len, chunk,
                             page=page)
    want = np.asarray(fam.logits(fam.sizes_of(WINDOW_ONLY), SEED, tokens))
    assert np.abs(got - want).max() < TOL
    assert mgr.ring_pages == -(-16 // page) + 1


@pytest.mark.parametrize("cache_len", [128, 4096])
def test_window_pools_hold_a_bounded_number_of_pages_a_slot(cache_len):
    """The window layers' pool does not grow with the lane: ring pages a
    slot are the window's, whatever ``max_cache_len``; ``describe()``
    reports the pages by row kind."""
    module = fam.program_model(TOY, dtype="float32")
    stats = {"prefix_lookups": 0}
    mgr = SlotPages(module, module.slot_contract(), 4, cache_len, 8, 0, 32,
                    True, stats)
    assert mgr.ring_pages == 3 and mgr.window_pages == 1 + 4 * 3
    assert mgr.table_width == cache_len // 8 + 3
    pools = jax.eval_shape(lambda: mgr.new_pools(jnp.float32))
    assert pools["window"].shape == (2, 13, 8, 128)
    assert pools["latent"].shape == (2, 4 * cache_len // 8 + 1, 8, 128)
    assert pools["index"].shape[-1] == 16
    mgr.reserve(2, np.arange(100) % 128, 20)
    assert mgr.table()[2, -3:].tolist() == [7, 8, 9]
    text = mgr.describe()
    assert "window rows 3/12 pages (3 a slot, a ring)" in text
    assert "latent + index rows 16 pages" in text   # 4 chunks of 32
    mgr.release(2)
    assert not mgr.table()[2].any() and "window rows 0/12" in mgr.describe()
    # sharing is refused for such a model, and counted
    assert not mgr.share_prefixes and stats["prefix_sharing_refused"] == 1


# ---- the kernels against jax.numpy ---------------------------------------- #
@pytest.mark.parametrize("live", [40, 96])
def test_index_scores_kernel(live):
    rng = np.random.default_rng(live)
    C, J, D, L = 32, 4, 16, 96
    q = jnp.asarray(rng.normal(size=(C, J, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(C, J)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(L, D)), jnp.float32)
    got = np.asarray(ops.index_scores(q, w, k, jnp.int32(live), block_q=8,
                                      block_k=32))
    want = np.einsum("cjl,cj->cl", np.maximum(
        np.einsum("cjd,ld->cjl", q, k), 0), w)
    blocks = -(-live // 32) * 32
    assert np.abs(got[:, :blocks] - want[:, :blocks]).max() < 1e-4
    assert (got[:, blocks:] == ops.NEG).all()
    rows = np.asarray(ops.index_scores_rows(q, w, jnp.broadcast_to(
        k, (C, L, D))))
    assert np.abs(rows - want).max() < 1e-4


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("k", [1, 7, 24, 200])
def test_kept_mask_is_the_exact_top_k(k, ties):
    """The bisection kernel's threshold against a sort, negative and
    positive scores, rows with fewer visible keys than ``k`` — and scores
    that tie at the k-th value (exact zeros, as relu gives), which go to
    the lower positions."""
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(48, 96)) * 3
    if ties:
        scores = np.maximum(scores, 0.0)
    scores = jnp.asarray(scores, jnp.float32)
    positions = jnp.arange(48) + 20
    visible = jnp.arange(96)[None, :] <= positions[:, None]
    mask = np.asarray(ops.kept_mask(scores, positions, k)).astype(bool)
    masked = np.where(np.asarray(visible), np.asarray(scores), -np.inf)
    for t in range(48):
        n = int(np.asarray(visible)[t].sum())
        want = set(np.argsort(-masked[t], kind="stable")[:min(k, n)])
        assert set(np.nonzero(mask[t])[0]) == want
    idx, valid = ops.kept_indices(scores, visible, min(k, 96))
    for t in (0, 47):
        assert set(np.asarray(idx)[t][np.asarray(valid)[t]]) \
            == set(np.nonzero(mask[t])[0])


def _flash_case(case, rng):
    """``(sizes (C, L, Dv), blocks (q, k), mask [C, L])`` of one case of
    :func:`test_masked_flash_kernel`."""
    sizes, blocks = (32, 128, 16), (8, 32)
    if case in ("live64", "live128"):
        live = int(case[4:])
        mask = rng.uniform(size=(32, 128)) < 0.3
        mask[:, live:] = False
        mask[5] = False                # a query that keeps nothing: zeros
        mask[8:16, :64] = False        # tiles that keep nothing: skipped
    elif case == "whole_tiles":
        # a chunk at positions 96 .. 127 under the causal mask: every tile
        # left of the diagonal's is kept whole
        mask = np.arange(128)[None, :] <= 96 + np.arange(32)[:, None]
    elif case == "keeps_late":
        # a row whose first live tiles keep nothing (its neighbours' do):
        # its running max waits at the floor, its weights there are 0
        mask = rng.uniform(size=(32, 128)) < 0.4
        mask[3, :96] = False
        mask[3, 100] = True
    elif case == "keeps_early":
        # ... and one that keeps something early and nothing after
        mask = rng.uniform(size=(32, 128)) < 0.4
        mask[4, 32:] = False
        mask[4, 7] = True
    elif case == "band":
        # a window layer's: 16 predecessors (the first 6 before position 0)
        # and the chunk, each query its 17 last keys
        sizes, blocks = (32, 48, 16), (8, 16)
        positions = 10 + np.arange(32)
        key_pos = np.concatenate([10 - 16 + np.arange(16), positions])
        mask = (key_pos[None, :] >= 0) \
            & (key_pos[None, :] <= positions[:, None]) \
            & (key_pos[None, :] > positions[:, None] - 17)
    else:
        # whole lane tiles of keys and values, as on the chip: the running
        # max and sum are laid side by side across them, not broadcast
        sizes, blocks = (16, 256, 256), (8, 128)
        mask = rng.uniform(size=(16, 256)) < 0.3
        mask[2] = False
    return sizes, blocks, mask


@pytest.mark.parametrize("block_h", [1, 2, 4])
@pytest.mark.parametrize("case", ["live64", "live128", "whole_tiles",
                                  "keeps_late", "keeps_early", "band",
                                  "lane_tiles"])
def test_masked_flash_kernel(case, block_h):
    """The chunk flash kernel against the plain softmax under the same
    mask, a head, a group of two and two groups a grid step."""
    rng = np.random.default_rng(len(case))
    (C, L, Dv), (bq, bk), mask = _flash_case(case, rng)
    H, Dn, Dr = 4, 16, 8
    r = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    qn, qr, kn, kr, v = r(H, C, Dn), r(H, C, Dr), r(H, L, Dn), r(L, Dr), \
        r(H, L, Dv)
    got = np.asarray(ops.masked_flash(
        qn, qr, kn, kr, v, jnp.asarray(mask, jnp.int8), 0.2,
        "attn.mla_chunk_prefill", block_q=bq, block_k=bk, block_h=block_h))
    s = (np.einsum("hcd,hld->hcl", qn, kn)
         + np.einsum("hcd,ld->hcl", qr, kr)) * 0.2
    s = np.where(mask[None], s, -np.inf)
    p = np.exp(s - np.maximum(s.max(-1, keepdims=True), -1e30))
    want = np.einsum("hcl,hld->hcd", p / np.maximum(
        p.sum(-1, keepdims=True), 1e-30), v)
    assert np.abs(got - want).max() < 1e-4
    empty = ~mask.any(axis=1)
    assert not got[:, empty].any()
    assert empty.any() == (case.startswith("live") or case == "lane_tiles")
    tiles, fetch = ops._tile_plan(jnp.asarray(mask, jnp.int8), bq, bk)
    tiles = np.asarray(tiles).reshape(C // bq, L // bk)
    fetch = np.asarray(fetch).reshape(tiles.shape)
    by_tile = mask.reshape(C // bq, bq, L // bk, bk)
    assert np.array_equal(tiles, by_tile.any(axis=(1, 3)))
    if case.startswith("live"):
        live = int(case[4:])
        assert tiles[1].tolist() == [0, 0, live > 64, live > 64]
        assert fetch[1].tolist() == ([2, 2, 2, 3] if live > 64 else [0] * 4)
        assert (tiles[:, live // 32:] == 0).all()
    if case == "whole_tiles":
        assert by_tile.all(axis=(1, 3)).sum() == 4 * 3 and tiles.all()
    if case == "band":
        assert tiles.tolist() == [[1, 1, 0], [1, 1, 0], [0, 1, 1],
                                  [0, 1, 1]]   # two tiles a query tile


@pytest.mark.parametrize("start,end,limit,window", [
    (0, 32, 64, 0), (32, 64, 64, 0), (64, 96, 64, 0), (96, 120, 64, 0),
    (64, 128, None, 0), (0, 64, None, 33), (128, 192, None, 33),
    (128, 170, None, 33), (128, 192, None, 97), (64, 128, None, 65)])
def test_flash_tiles_counts_the_masks_tiles(start, end, limit, window,
                                            monkeypatch):
    """The host's count behind ``flash_tiles_live`` / ``flash_tiles_whole``
    against the tiles of the masks the layers build (tiles of 32 here, the
    chunk two of them): a dense causal lane, a band, and a selecting
    layer's, whose tiles are known whole only under ``limit``."""
    from deepspeed_tpu.models import latent_attention as model
    monkeypatch.setattr(ops, "KEY_BLOCK", 32)
    positions = start + np.arange(64)
    if window:
        key_pos = np.concatenate([start - window + 1 + np.arange(window - 1),
                                  positions])
        mask = (key_pos[None, :] >= 0) \
            & (key_pos[None, :] <= positions[:, None]) \
            & (key_pos[None, :] > positions[:, None] - window)
    else:
        mask = np.arange(start + 64)[None, :] <= positions[:, None]
    real = mask[:end - start]
    real = np.pad(real, ((0, -real.shape[0] % 32), (0, 0)))
    by_tile = real.reshape(-1, 32, mask.shape[1] // 32, 32)
    live = by_tile.any(axis=(1, 3))
    rows = np.minimum(start + 32 * (1 + np.arange(live.shape[0])), end)
    whole = mask[:live.shape[0] * 32].reshape(by_tile.shape).all(
        axis=(1, 3)) & live
    if limit is not None:
        whole &= (rows <= limit)[:, None]
    assert model.flash_tiles(start, end, limit, window) \
        == (live.sum(), whole.sum())


@pytest.mark.parametrize("live", [20, 70, 128],
                         ids=["first_block", "mid_lane", "whole_lane"])
def test_decompress_kernel(live):
    """``attn.mla_decompress`` against ``_attend``'s two einsums over the
    live key blocks, head-major; a dead block is not written (the
    interpreter hands back ``nan`` where a kernel wrote nothing)."""
    rng = np.random.default_rng(live)
    H, nope, dv, rank, L, bk = 4, 16, 8, 32, 128, 32
    rows = rng.normal(size=(L, 48)).astype(np.float32)
    blocks = -(-live // bk) * bk
    w = rng.normal(size=(rank, H * (nope + dv))).astype(np.float32)
    k, v = ops.decompress(jnp.asarray(rows), jnp.asarray(w), H, nope,
                          jnp.int32(live), block_k=bk, block_h=2)
    assert k.shape == (H, L, nope) and v.shape == (H, L, dv)
    by_head = w.reshape(rank, H, nope + dv)
    lat = rows[:blocks, :rank]
    want_k = np.einsum("lr,rhd->hld", lat, by_head[..., :nope])
    want_v = np.einsum("lr,rhd->hld", lat, by_head[..., nope:])
    assert np.abs(np.asarray(k)[:, :blocks] - want_k).max() < 1e-4
    assert np.abs(np.asarray(v)[:, :blocks] - want_v).max() < 1e-4
    assert np.isnan(np.asarray(k)[:, blocks:]).all() \
        and np.isnan(np.asarray(v)[:, blocks:]).all()


@pytest.mark.parametrize("chunks", [1, 5, 9],
                         ids=["first_block", "mid_lane", "whole_lane"])
def test_a_cached_chunk_touches_its_live_blocks_only(chunks):
    """A full layer's cached chunks through a table that is NOT in page
    order, a lane of three 512-key blocks: the last chunk's output is the
    uncached forward's over the same tokens, and it does not change — and
    stays finite — when every page outside its live blocks holds ``inf``:
    the other slots' pages, the slot's own pages past its live blocks and
    the trash page its table is padded with there, in both pools.  Nothing
    dead is read, and nothing the decompression left unwritten reaches the
    softmax."""
    from deepspeed_tpu.models.latent_attention import (LatentAttention,
                                                       live_block_rows)
    z = fam.program_model(TOY).config.full
    attn = LatentAttention(z, dtype=jnp.float32)
    page, C, lane_pages, n_pages = 8, 128, 3 * ops.KEY_BLOCK // 8 - 10, 400
    T = chunks * C
    x = jnp.asarray(np.random.default_rng(chunks).normal(size=(T, z.hidden)),
                    jnp.float32)
    chunk = LatentAttention.chunk
    params = attn.init(jax.random.key(1), x[:C], jnp.int32(0), method=chunk)
    want, _ = attn.apply(params, x, jnp.int32(0), method=chunk)
    table = jnp.asarray(np.random.default_rng(5).permutation(
        np.arange(1, n_pages))[:lane_pages], jnp.int32)
    pools = (jnp.zeros((2, n_pages, page, 128), jnp.float32),
             jnp.zeros((2, n_pages, page, z.index_dim), jnp.float32))
    step = jax.jit(lambda pools, xs, start: attn.apply(
        params, xs, start, cache=(pools, 1, table), method=chunk))
    for c in range(chunks - 1):
        _, pools = step(pools, x[c * C:(c + 1) * C], jnp.int32(c * C))
    last = x[T - C:], jnp.int32(T - C)
    got, _ = step(pools, *last)
    assert np.abs(np.asarray(got) - np.asarray(want[T - C:])).max() < TOL
    # (the lane's padded tail reads the trash page: live in its last block)
    lane = np.pad(np.asarray(table),
                  (0, -lane_pages % (ops.KEY_BLOCK // page)))
    dead = np.setdiff1d(np.arange(n_pages),
                        lane[:live_block_rows(T) // page])
    assert (0 in dead) == (chunks < 9)
    poisoned, _ = step(tuple(p.at[:, dead].set(jnp.inf) for p in pools),
                       *last)
    assert np.isfinite(np.asarray(poisoned)).all()
    assert np.array_equal(np.asarray(poisoned), np.asarray(got))


# ---- what the spans carry -------------------------------------------------- #
@pytest.mark.parametrize("start,end,scored,kept,window", [
    (0, 32, 528, 492, 408),            # 24 x 25 / 2 + 8 x 24;  17-key band
    (32, 64, 1552, 768, 544),
    (64, 80, 1160, 384, 272)])         # a last chunk's 16 real positions
def test_chunk_work_counts_pairs(start, end, scored, kept, window):
    chunk_work = fam.program_model(TOY).slot_contract().chunk_work
    work = chunk_work(start, end, 8, 3, 4)
    assert work == {"dsa_keys_scored": 2 * scored, "dsa_keys_kept": 2 * kept,
                    "latent_rows_read": 2 * end,
                    "latent_rows_decompressed": 2 * 512, "window_pages": 6,
                    "window_keys": 2 * window,
                    # one tile a layer: a toy chunk lies inside a key block
                    "flash_tiles_live": 4, "flash_tiles_whole": 0}
    # the cell's second chunk: two full layers' 3 + 4 tiles — none known
    # whole past the toy ``index_topk`` (24) — and two window layers' band
    # of 2 tiles a query tile
    tiles = chunk_work(1024, 2048, 64, 9, 4)
    assert (tiles["flash_tiles_live"], tiles["flash_tiles_whole"]) \
        == (2 * 7 + 2 * 4, 0)
    # the live 512-key blocks, whole: what ``attn.mla_decompress`` runs
    assert [chunk_work(e - 1024, e, 64, 9, 4)["latent_rows_decompressed"]
            for e in (1024, 1500, 15360)] == [2 * 1024, 2 * 1536, 2 * 15360]


def test_block_work_reads_the_kept_rows_only():
    declared = fam.program_model(TOY).slot_contract()
    work = declared.block_work([(10, 2), (100, 3)], 3, 4)
    # every name the two return is declared: summed into stats, or a level
    assert set(work) <= set(declared.work_counters + declared.work_levels)
    assert work["dsa_keys_scored"] == 2 * (10 + 11 + 100 + 101 + 102)
    assert work["dsa_keys_kept"] == work["latent_rows_read"] \
        == 2 * (10 + 11 + 3 * 24)
    assert work["window_keys"] == 2 * (10 + 11 + 3 * 17)
    assert work["window_pages"] == 3 * 2 * 2


# ---- the mapping refuses what it does not build ----------------------------- #
@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 8),
    ("attention_gate_type", "elementwise"), ("hidden_act", "gelu"),
    ("swa_num_key_value_heads", 1), ("layer_types", ["linear_attention"] * 4)])
def test_the_mapping_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError):
        dots3_config({**TOY, key: value})


def test_the_mapping_reads_both_layer_kinds():
    cfg = dots3_config(TOY, held_experts=(4, 4))
    assert (cfg.full.heads, cfg.full.row, cfg.full.index_topk) == (4, 40, 24)
    assert (cfg.window.heads, cfg.window.row, cfg.window.window) == (2, 48, 17)
    assert cfg.n_routed_experts == 16 and cfg.num_layers == 4
    # what the slot engine reads of it: the load's shape is the experts
    # HELD, in the layers after the dense one
    declared = Dots3Model(cfg).slot_contract()
    assert declared.holds_share and declared.routes_experts
    assert (declared.expert_layers, declared.experts) == (3, 4)
    assert cfg.full.scale == pytest.approx(24 ** -0.5)


# ---- through the engine: stats, spans, what serve() refuses ----------------- #
def test_engine_serves_it_and_the_spans_and_stats_carry_the_work(tmp_path):
    """``init_inference`` → ``serve()`` → ``submit`` at the toy size: the
    served tokens are the reference's own argmax (float32), the dispatch
    spans carry the attention-work counters, the waits the held experts'
    load and the choices that fell elsewhere, and ``srv.stats`` sums them."""
    import json
    import deepspeed_tpu
    from deepspeed_tpu.monitor import trace as span_trace
    module, params = _program(TOY)
    eng = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "serving": {
            "enabled": True, "num_slots": 3, "max_cache_len": 128,
            "page_size": 8, "prefill_chunk": 32, "decode_block": 4}})
    eng.set_params(params)
    srv = eng.serve(tracing=True)
    try:
        assert srv.chunk == 32 and srv.table_width == 16 + 3
        assert srv.stats["prefix_sharing_refused"] == 1
        prompt = np.random.default_rng(8).integers(0, 128, 50)
        rid = srv.submit(prompt.astype(np.int32), max_new_tokens=10)
        out = srv.drain()[rid]
        path = srv.dump_trace(str(tmp_path / "trace.json"))
        stats = dict(srv.stats)
    finally:
        srv.close()
        span_trace.disable()
    gaps = fam.chosen_gaps(fam.sizes_of(TOY), SEED, out, 50, 10, 128)
    assert gaps.max() < 1e-3
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    args = lambda name: [e["args"] for e in evs if e["name"] == name]
    chunks = args("dstpu.sched.dispatch.prefill_chunk")
    decodes = args("dstpu.sched.dispatch.decode")
    assert len(chunks) == 2 and decodes
    # chunk 1 covers positions 32..49 (18 real rows of 32)
    assert chunks[1]["dsa_keys_scored"] == 2 * (18 * 32 + 18 * 19 // 2)
    assert chunks[1]["dsa_keys_kept"] == 2 * 18 * 24
    assert all(a["window_pages"] == 2 * 3 for a in chunks)
    for key in ("dsa_keys_scored", "dsa_keys_kept", "latent_rows_read",
                "window_keys"):
        assert stats[key] == sum(a[key] for a in chunks + decodes) > 0
    # a decode step reads its kept rows only: index_topk a full layer
    assert all(a["latent_rows_read"] == a["dsa_keys_kept"]
               <= a["dsa_keys_scored"] for a in decodes)
    waits = [a for a in args("dstpu.sched.wait_device")
             + args("dstpu.sched.commit") if "moe_assignments" in a]
    assert waits and all("moe_assignments_elsewhere" in a for a in waits)
    assert stats["moe_assignments"] == sum(
        a["moe_assignments"] for a in waits)
    # three expert layers, 4 choices a live token: held + elsewhere
    tokens = 50 + 9
    assert stats["moe_assignments"] + stats["moe_assignments_elsewhere"] \
        == 3 * 4 * tokens
