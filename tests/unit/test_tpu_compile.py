"""The main path's Pallas kernels, compiled to Mosaic for a DESCRIBED v5e
(no chip attached) at OPT-1.3B widths — H=32, D=64, L=24, bf16.

The TPU compiler is installed in the sandbox; ``get_topology_desc`` hands
it a chip that exists only as a description, and it refuses exactly what
the real chip's compiler would refuse (a slice not aligned to the tiling,
too much VMEM, a kernel that cannot be partitioned) — things interpret
mode never sees.  A compile that passes is not a chip run: nothing here
says anything about results or time.

All of these live in ONE file and describe the topology inside a
module-scoped fixture: only one process at a time may load libtpu, so
under xdist exactly one worker — the one handed this file — may do it,
and only after collection.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.moe import dropless as moe_mod
from deepspeed_tpu.ops.sparse_attention import block_sparse
from deepspeed_tpu.ops.transformer import (decode_attention as decode_mod,
                                           delta_attention as delta_mod,
                                           eva_attention as eva_mod,
                                           flash_attention as flash_mod,
                                           latent_attention as latent_mod,
                                           paged_attention as paged_mod,
                                           short_conv as conv_mod,
                                           ssd as ssd_mod)

H, D, L = 32, 64, 24            # OPT-1.3B (models/opt.py)
HD = H * D
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip executable can be written to the persistent cache
    but not read back without a chip — keep it out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as jcc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jcc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    jcc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, no_persistent_cache):
    """``_interpret()`` asks ``jax.default_backend()``, which is the CPU
    here: steer it in the test, not through an option of the program."""
    for mod in (flash_mod, decode_mod, paged_mod, block_sparse, moe_mod,
                latent_mod, eva_mod, delta_mod, ssd_mod, conv_mod):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


@pytest.fixture
def vmem_asks(monkeypatch):
    """The ``vmem_limit_bytes`` of every Pallas call traced while the test
    runs, read off the calls themselves (the chunk kernels' calls are
    jitted: their own caches are dropped so that each is traced anew)."""
    asked, params = [], pltpu.CompilerParams

    def recording(*args, **kw):
        asked.append(kw.get("vmem_limit_bytes"))
        return params(*args, **kw)

    monkeypatch.setattr(pltpu, "CompilerParams", recording)
    for call in (paged_mod._paged_chunk_call, paged_mod._window_chunk_call,
                 eva_mod._eva_chunk_call):
        call.clear_cache()
    return asked


def _flash_fwd_bwd(batch=2, seq=2048, heads=H, head_dim=D):
    def loss(q, k, v):
        out = flash_mod.flash_attention(q, k, v, causal=True)
        return out.astype(F32).sum()
    return (jax.grad(loss, argnums=(0, 1, 2)),
            [((batch, seq, heads, head_dim), BF16)] * 3)


def _paged_decode(quant, page=64, slots=8, cache_len=512, layers=L,
                  kv_heads=H, heads=H, head_dim=D, fused=True):
    """``fused``: the step's K/V row written by the kernel, as a lane pool's
    decode step does; a ring's (``registry``'s ``pallas_ring_decode``) reads
    rows that are written already."""
    pages_per_slot = cache_len // page
    n_pages = slots * pages_per_slot + 1
    pool = ((layers, n_pages, page, kv_heads * head_dim),
            I8 if quant else BF16)
    args = [((slots, heads, head_dim), BF16), pool, pool, ((slots,), I32),
            ((slots, pages_per_slot), I32)]
    if fused:
        args += [((slots, kv_heads, head_dim), BF16)] * 2
    if quant:
        args += [((layers, n_pages, page, kv_heads), F32)] * 2

    def fn(q, k_pool, v_pool, lengths, pages, *rest):
        kw = dict(new_k=rest[0], new_v=rest[1]) if fused else {}
        if quant:
            kw.update(k_scale=rest[-2], v_scale=rest[-1])
        return paged_mod.paged_decode_attention(
            q, k_pool, v_pool, lengths, pages, layer=layers - 1, **kw)
    return fn, args


def _paged_chunk_prefill(page=64, chunk=128, cache_len=512, heads=H,
                         head_dim=D, quant=False, kv_heads=None):
    """One layer-chunk of the paged chunk step (B = 1).  The block loop
    (unquantized pools) holds two K and two V blocks of 8 pages, the
    chunk's q and output and its online-softmax state in VMEM: compiling
    is the proof that they fit the limit the kernel asks for."""
    kv_heads = kv_heads or heads
    pages_per_slot = cache_len // page
    n_pages = 8 * pages_per_slot + 1
    pool = ((4, n_pages, page, kv_heads * head_dim), I8 if quant else BF16)
    args = [((1, chunk, heads, head_dim), BF16), pool, pool, ((1,), I32),
            ((1, pages_per_slot), I32)]
    if quant:
        args += [((4, n_pages, page, kv_heads), F32)] * 2

    def fn(q, k_pool, v_pool, starts, pages, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return paged_mod.paged_chunk_prefill_attention(
            q, k_pool, v_pool, starts, pages, layer=3, **kw)
    return fn, args


def _mono_decode(batch=16, cache_len=1024):
    cache = ((L, batch, cache_len, HD), BF16)

    def fn(q, k_cache, v_cache, lengths, new_k, new_v):
        return decode_mod.decode_attention(
            q, k_cache, v_cache, lengths, layer=3, new_k=new_k, new_v=new_v)
    return fn, [((batch, H, D), BF16), cache, cache, ((batch,), I32),
                ((batch, H, D), BF16), ((batch, H, D), BF16)]


def _moe_experts(tokens, sorted_form=False, hidden=2048, experts=64,
                 width=1024, top_k=8):
    """OLMoE-1B-7B's expert layer at a serving program's token count:
    ``moe.route`` + ``moe.experts_gmm`` (64 decode lanes, or one chunk), or
    — ``sorted_form``, as ``MoE`` runs a chunk DISPATCH's 4 rows of 128 —
    the picks read back off the combine matrix and
    ``moe.experts_grouped``."""
    up = ((experts, hidden, width), BF16)

    def fn(x, gate_w, live, wg, wu, wd):
        combine, counts = moe_mod.route(x, gate_w, top_k, live=live)
        if sorted_form:
            return moe_mod.experts_grouped(
                x, *moe_mod.picks_of(combine[:tokens], top_k), wg, wu, wd,
                jax.nn.silu), counts
        return moe_mod.experts(x, combine, counts, wg, wu, wd,
                               jax.nn.silu), counts
    return fn, [((tokens, hidden), BF16), ((hidden, experts), BF16),
                ((tokens,), jnp.bool_), up, up,
                ((experts, width, hidden), BF16)]


# dots3-note-prev's kernels at the long-document cell's sizes: a chunk of
# 2,048 queries against a 16,448-position lane padded to 512-key blocks
DOTS3_CHUNK, DOTS3_LANE = 2048, 16896


def _dsa_index():
    def fn(q, w, k, live):
        return latent_mod.index_scores(q, w, k, live)
    return fn, [((DOTS3_CHUNK, 64, 128), BF16), ((DOTS3_CHUNK, 64), F32),
                ((DOTS3_LANE, 128), BF16), ((), I32)]


def _dsa_topk():
    def fn(scores, positions):
        return latent_mod.kept_mask(scores, positions, 2048)
    return fn, [((DOTS3_CHUNK, DOTS3_LANE), F32), ((DOTS3_CHUNK,), I32)]


def _mla_flash(name, heads, nope, keys, chunk=DOTS3_CHUNK, v=128):
    """dots3's full layers: 128 heads of 128 + 64 over the lane; its window
    layers: 64 heads of 192 + 64 over the chunk and its 512 predecessors;
    LongCat's dense causal layers: 64 heads of 128 + 64, a chunk of 512 over
    a 2,112-position lane in 512-key blocks; GLM-5's: 64 heads of 192 + 64
    with 256-wide values, the widest blocks the kernel holds."""
    def fn(qn, qr, kn, kr, v, mask):
        return latent_mod.masked_flash(qn, qr, kn, kr, v, mask, 0.07, name)
    return fn, [((heads, chunk, nope), BF16), ((heads, chunk, 64), BF16),
                ((heads, keys, nope), BF16), ((keys, 64), BF16),
                ((heads, keys, v), BF16), ((chunk, keys), I8)]


def _mla_decompress(heads, nope, v, lane):
    """A full layer's ``c_kv W_kvb`` over a lane's live key blocks — dots3:
    128 heads of 128 + 128 over 16,896 rows; GLM-5: 64 heads of 192 + 256
    (a head's 448 columns start 64 lanes off a tile in every odd head) over
    5,120 — from the pool's 640-wide rows, the weight as the parameter
    lies."""
    def fn(rows, w, live):
        return latent_mod.decompress(rows, w, heads, nope, live)
    return fn, [((lane, 640), BF16), ((512, heads * (nope + v)), BF16),
                ((), I32)]


def _moe_grouped(tokens=DOTS3_CHUNK, hidden=5120, held=32, width=1536,
                 top_k=8):
    up = ((held, hidden, width), BF16)

    def fn(x, local, gate, wg, wu, wd):
        return moe_mod.experts_grouped(x, local, gate, wg, wu, wd,
                                       jax.nn.silu)
    return fn, [((tokens, hidden), BF16), ((tokens, top_k), I32),
                ((tokens, top_k), F32), up, up, ((held, width, hidden), BF16)]


# LFM2-24B-A2B's expert layer at the wide-generation cell's sizes: 64
# experts of 2048 x 1536 with 4 a token — the dense form for a decode step's
# 256 rows, the grouped form for a 512-token chunk (its paged kernels, 32
# query heads over 8 KV heads of 64 at 256 lanes of 21 pages, are cases of
# ``_paged_decode`` / ``_paged_chunk_prefill``)
def _moe_scored(tokens, hidden=2048, experts=64, width=1536, top_k=4):
    """The sigmoid-routed layer as ``MoE._scored`` runs it with every
    expert held: ``route_scored``, then the dense or the grouped form by
    ``GROUPED_MIN_ROWS``."""
    up = ((experts, hidden, width), BF16)

    def fn(x, gate_w, bias, live, wg, wu, wd):
        choice, gate = moe_mod.route_scored(x, gate_w, bias, top_k,
                                            live=live, sum_eps=1e-6)
        local, counts, _ = moe_mod.held_load(choice, 0, experts)
        if tokens < moe_mod.GROUPED_MIN_ROWS:
            return moe_mod.experts(
                x, moe_mod.combine_of(local, gate, experts), counts, wg, wu,
                wd, jax.nn.silu), counts
        return moe_mod.experts_grouped(x, local, gate, wg, wu, wd,
                                       jax.nn.silu), counts
    return fn, [((tokens, hidden), BF16), ((hidden, experts), F32),
                ((experts,), F32), ((tokens,), jnp.bool_), up, up,
                ((experts, width, hidden), BF16)]


# evabyte-serve-bytedoc-batch: 32 heads of 128, 8 layers, page 64, window
# 2048 = 32 ring pages a slot, 24 slots, 14 lane pages a slot (one summary
# row a 16-byte chunk: 13,376 positions = 836 rows)
EVA = dict(heads=32, dim=128, layers=8, page=64, window=2048, chunk_size=16,
           slots=24, lane_pages=14)


def _eva_pools():
    e = EVA
    width = e["heads"] * e["dim"]
    ring = ((e["layers"], 1 + e["slots"] * e["window"] // e["page"],
             e["page"], width), BF16)
    lane = ((e["layers"], 1 + e["slots"] * e["lane_pages"], e["page"],
             width), BF16)
    return [ring, ring, lane, lane]


def _eva_decode():
    """One layer-step of the decode block: 24 lanes, each its ring pages
    and its visible summary pages through one VMEM ring of pages, the
    step's K/V row written as an 8-row stripe of the aliased ring pools."""
    e = EVA
    n, vec = e["slots"], ((e["slots"], e["heads"], e["dim"]), BF16)
    args = [vec] + _eva_pools() + [
        ((n,), I32), ((n, e["window"] // e["page"]), I32),
        ((n, e["lane_pages"]), I32), vec, vec]

    def fn(q, k_ring, v_ring, k_sum, v_sum, pos, ring, lane, new_k, new_v):
        return eva_mod.eva_decode_attention(
            q, k_ring, v_ring, k_sum, v_sum, pos, ring, lane,
            layer=e["layers"] - 1, window=e["window"],
            chunk_size=e["chunk_size"], new_k=new_k, new_v=new_v)
    return fn, args


def _eva_chunk(chunk):
    """One layer-chunk of the chunk step: ``chunk`` queries in blocks of
    512 along the grid — a chunk of a whole window is four —, each block's
    [512, 512] score tiles, two K and two V blocks of 8 pages, q, output
    and online-softmax state in VMEM: compiling is the proof they fit."""
    e = EVA
    args = [((1, chunk, e["heads"], e["dim"]), BF16)] + _eva_pools() + [
        ((), I32), ((1, e["window"] // e["page"]), I32),
        ((1, e["lane_pages"]), I32)]

    def fn(q, k_ring, v_ring, k_sum, v_sum, start, ring, lane):
        return eva_mod.eva_chunk_attention(
            q, k_ring, v_ring, k_sum, v_sum, start, ring, lane,
            layer=e["layers"] - 1, window=e["window"],
            chunk_size=e["chunk_size"])
    return fn, args


def _kda_chunk_scan(chunk=2048, heads=64, dim=128, slots=64, layers=3):
    """One layer-chunk of ``solar-serve-longctx-batch``'s chunk step: 2,048
    rows of 64 heads of 128 through ``kda.chunk_scan`` over the cell's
    float32 state pool (65 rows x 3 layers x 4 MiB), a state row in and out."""
    rows = ((chunk, heads, dim), BF16)
    args = [rows] * 3 + [((chunk, heads, dim), F32), ((chunk, heads), F32),
                         ((layers, 1 + slots, heads, dim, dim), F32),
                         ((), I32), ((), I32)]

    def fn(q, k, v, g, beta, pool, row, start):
        return delta_mod.chunk_scan(q, k, v, g, beta, pool, layers - 1, row,
                                    fresh=start == 0, real=chunk - 5)
    return fn, args


def _ssd(chunk, heads=128, dim=64, states=128, slots=176, layers=9,
         groups=None):
    """One Mamba-2 layer of ``granite-serve-chatgen-batch``: a 512-row chunk
    through ``ssd.chunk_scan``, or one token of each of the 176 lanes
    through ``ssd.decode_step``, over the cell's float32 state pool (177
    rows x 9 layers x 4 MiB), the state rows in and out.  ``groups``: ``B``
    and ``C`` a group of heads (``nemotron3-serve-thinkgen-batch``: 8
    groups of 8 heads — a chunk step's eight heads one group's, a decode
    step's 32 heads four —, 193 rows x 6 blocks x 2 MiB)."""
    n = chunk or slots
    pool = ((layers, 1 + slots) + ssd_mod.state_shape(heads, dim, states),
            F32)
    shared = ((n, states) if groups is None else (n, groups, states), BF16)
    args = [((n, heads, dim), BF16), ((n, heads), F32), ((n, heads), F32),
            shared, shared, pool, ((n,), I32), ((), I32)]

    def fn(x, dt, a, b, c, pool, rows, start):
        if chunk:
            return ssd_mod.chunk_scan(x, dt, a, b, c, pool, layers - 1,
                                      rows[0], fresh=start == 0,
                                      real=chunk - 5)
        return ssd_mod.decode_step(x, dt, a, b, c, pool, layers - 1, rows,
                                   rows > 0)
    return fn, args


def _moe_ungated(tokens, hidden=2688, experts=128, held=64, width=1856,
                 top_k=6):
    """Nemotron-H's expert layer as ``MoE._scored`` runs it: sigmoid scores
    over 128 outputs, 64 held experts of TWO matrices (``relu2``, no gate)
    at the published width 1856 = 14.5 lane tiles STORED as 1920 (zero
    columns / rows: ``NemotronHConfig.stored_expert_width``) in width tiles
    of 640 —, the dense form for a decode step's 192 rows, the sorted form
    for a chunk's."""
    assert width == 1856 and moe_mod._width_tile(1920) == 640
    width = 1920
    from deepspeed_tpu.models.nemotron_h import relu2

    def fn(x, gate_w, bias, live, wu, wd):
        choice, gate = moe_mod.route_scored(x, gate_w, bias, top_k,
                                            live=live, scaling=2.5,
                                            sum_eps=1e-20)
        local, counts, _ = moe_mod.held_load(choice, 0, held)
        if tokens < moe_mod.GROUPED_MIN_ROWS:
            return moe_mod.experts(
                x, moe_mod.combine_of(local, gate, held), counts, None, wu,
                wd, relu2), counts
        return moe_mod.experts_grouped(x, local, gate, None, wu, wd,
                                       relu2), counts
    return fn, [((tokens, hidden), BF16), ((hidden, experts), F32),
                ((experts,), F32), ((tokens,), jnp.bool_),
                ((held, hidden, width), BF16), ((held, width, hidden), BF16)]


def _conv_step(slots, taps, width, layers):
    """One short-convolution layer's decode step of a cell — Granite's 176
    lanes of 3 x 8,448 values a row, Solar's 64 of 3 x 24,576, LFM2's 256 of
    2 x 2,048 — over the cell's bfloat16 row pool: ``conv.rows_read``, the
    taps on the rows as they lie, ``conv.rows_write`` with the pool aliased
    in and out."""
    pool = ((layers, 1 + slots) + conv_mod.rows_shape(taps, width, BF16),
            BF16)
    args = [((slots, width), BF16), ((taps, width), F32), pool,
            ((slots,), I32)]

    def fn(z, w, pool, rows):
        return conv_mod.decode_step(z, w, pool, layers - 1, rows)
    return fn, args


CASES = {
    "granite_ssd_chunk_scan_c512": lambda: _ssd(512),
    "granite_ssd_decode_step_176": lambda: _ssd(0),
    "granite_conv_step_176": lambda: _conv_step(176, 4, 8448, 9),
    "nemotron_ssd_chunk_scan_g8_c512": lambda: _ssd(
        512, heads=64, slots=192, layers=6, groups=8),
    "nemotron_ssd_decode_step_g8_192": lambda: _ssd(
        0, heads=64, slots=192, layers=6, groups=8),
    "nemotron_conv_step_192": lambda: _conv_step(192, 4, 6144, 6),
    "nemotron_moe_ungated_gmm_t192": lambda: _moe_ungated(192),
    "nemotron_moe_ungated_grouped_c1024": lambda: _moe_ungated(1024),
    "solar_conv_step_64": lambda: _conv_step(64, 4, 3 * 8192, 3),
    "lfm2_conv_step_256": lambda: _conv_step(256, 3, 2048, 8),
    "solar_kda_chunk_scan_c2048": _kda_chunk_scan,
    "evabyte_eva_decode_24x46": _eva_decode,
    "evabyte_eva_chunk_c512": lambda: _eva_chunk(512),
    "evabyte_eva_chunk_c2048": lambda: _eva_chunk(2048),
    "dots3_dsa_index_c2048": _dsa_index,
    "dots3_dsa_topk_c2048": _dsa_topk,
    "dots3_mla_chunk_prefill_c2048": lambda: _mla_flash(
        "attn.mla_chunk_prefill", 128, 128, DOTS3_LANE),
    "dots3_mla_window_c2048": lambda: _mla_flash(
        "attn.mla_window", 64, 192, DOTS3_CHUNK + 512),
    "longcat_mla_causal_prefill_c512": lambda: _mla_flash(
        "attn.mla_chunk_prefill", 64, 128, 2560, chunk=512),
    "glm5_mla_chunk_prefill_c1024": lambda: _mla_flash(
        "attn.mla_chunk_prefill", 64, 192, 5120, chunk=1024, v=256),
    "dots3_mla_decompress_l16896": lambda: _mla_decompress(
        128, 128, 128, DOTS3_LANE),
    "glm5_mla_decompress_l5120": lambda: _mla_decompress(64, 192, 256, 5120),
    "dots3_moe_grouped_c2048": _moe_grouped,
    "lfm2_moe_scored_gmm_t256": lambda: _moe_scored(256),
    "lfm2_moe_scored_grouped_c512": lambda: _moe_scored(512),
    "lfm2_paged_decode_gqa_256x21": lambda: _paged_decode(
        False, slots=256, cache_len=1344, layers=2, kv_heads=8),
    "lfm2_paged_chunk_prefill_gqa_c512": lambda: _paged_chunk_prefill(
        chunk=512, cache_len=1344, kv_heads=8),
    "moe_experts_olmoe_t64": lambda: _moe_experts(64),
    "moe_experts_olmoe_t128": lambda: _moe_experts(128),
    "moe_experts_olmoe_t512": lambda: _moe_experts(512),
    "moe_sorted_olmoe_r4c128": lambda: _moe_experts(512, sorted_form=True),
    "flash_fwd_bwd_s2048": _flash_fwd_bwd,
    # opt67b-zero3-4chip's shard: micro-batch 4, 32 heads of 128
    "flash_fwd_bwd_s2048_d128": lambda: _flash_fwd_bwd(4, head_dim=128),
    # two major blocks: the walk's state crosses a grid step in scratch
    "flash_fwd_bwd_s4096": lambda: _flash_fwd_bwd(1, seq=4096, heads=4),
    "paged_decode_bf16_fused_write_p64": lambda: _paged_decode(False),
    "paged_decode_int8kv_fused_write_p64": lambda: _paged_decode(True),
    # the two serving cells' tables: 32 slots x 22 pages, 24 x 29 (four
    # layers of pool: this jit donates nothing, so pools count twice)
    "paged_decode_bf16_chat_32x22": lambda: _paged_decode(
        False, slots=32, cache_len=1408, layers=4),
    "paged_decode_bf16_batch_24x29": lambda: _paged_decode(
        False, slots=24, cache_len=1856, layers=4),
    "paged_chunk_prefill_c128_p64": _paged_chunk_prefill,
    "paged_chunk_prefill_int8kv_c128_p64": lambda: _paged_chunk_prefill(
        quant=True),
    "mono_decode_bf16_fused_write": _mono_decode,
}


# the training cells' shapes, and one across two major blocks
_FLASH_TRAINED = ("flash_fwd_bwd_s2048", "flash_fwd_bwd_s2048_d128",
                  "flash_fwd_bwd_s4096")


# the four shapes ``latent_attention.masked_flash`` runs at in the cells
_MLA_FLASH = ("dots3_mla_chunk_prefill_c2048", "dots3_mla_window_c2048",
              "longcat_mla_causal_prefill_c512",
              "glm5_mla_chunk_prefill_c1024")


def _pallas_calls(fn, shapes):
    """The ``pallas_call`` equations of ``fn`` traced at ``shapes``."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)
    return list(walk(jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(s, d) for s, d in shapes)).jaxpr))


def _compile(fn, shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


# the serving cells' chunk steps — OPT-1.3B: 32 heads of 64, 29 pages a
# slot; OLMoE: 16 heads of 128, 17 pages — at the cells' chunk and at the
# largest the registry allows (MAX_CHUNK_S)
@pytest.mark.parametrize("chunk", [128, 512])
@pytest.mark.parametrize("heads,head_dim,pages_per_slot",
                         [(32, 64, 29), (16, 128, 17)],
                         ids=["opt13b_29", "olmoe_17"])
def test_paged_chunk_block_loop_fits_vmem(heads, head_dim, pages_per_slot,
                                          chunk, one_chip, mosaic):
    """The chunk kernel's block loop at both serving shapes: Mosaic
    accepts it under the VMEM limit the kernel asks for (a kernel over
    its limit does not compile), and that limit is one a v5e can grant
    (128 MiB of VMEM)."""
    page = 64
    fn, shapes = _paged_chunk_prefill(
        page=page, chunk=chunk, cache_len=pages_per_slot * page,
        heads=heads, head_dim=head_dim)
    asked = paged_mod._chunk_loop_vmem_bytes(
        chunk, heads, head_dim,
        paged_mod._chunk_block_pages(page, pages_per_slot) * page,
        heads * head_dim, 2, 2)
    assert asked <= 100 * 2 ** 20, f"asks for {asked / 2 ** 20:.0f} MiB"
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


# the chunk fold's kernels at the cells' shapes, and the VMEM each asked
# for BEFORE its running max and sum became whole 128-lane tiles (PR 53):
# the [C, LSE_LANES] tiles they replaced padded to 128 lanes, so the layout
# may not cost a byte.  (Trinity's two are held in its chunk step, below.)
_CHUNK_FOLD_ASKS = {
    # two kv heads a 128-lane group, taken at a dynamic lane offset
    "opt13b_paged_chunk_c128": (lambda: _paged_chunk_prefill(
        cache_len=29 * 64), 34078720),
    "olmoe_paged_chunk_c512": (lambda: _paged_chunk_prefill(
        chunk=512, cache_len=17 * 64, heads=16, head_dim=128), 52428800),
    "evabyte_eva_chunk_c512": (lambda: _eva_chunk(512), 81788928),
}


@pytest.mark.parametrize("case", sorted(_CHUNK_FOLD_ASKS))
def test_chunk_fold_compiles_under_its_old_vmem_ask(case, one_chip, mosaic,
                                                    vmem_asks):
    """Every kernel that folds through ``_chunk_block_update`` lowers
    through Mosaic with the lane-replicated statistics under the SAME
    ``vmem_limit_bytes`` it asked for with the columns (a kernel over its
    ask does not compile)."""
    build, old_ask = _CHUNK_FOLD_ASKS[case]
    fn, shapes = build()
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert vmem_asks == [old_ask], vmem_asks
    assert decode_mod._chunk_scratch_bytes(512, 32, 128) == 25165824


# the cells' calls of ``attn.paged_decode``: slots, query heads, KV heads,
# head size, pages a slot (pages of 64), layers of the pool (two stand for
# OPT's 24, OLMoE's 8 and LFM2's 2 + 8), and whether the call writes the
# step's row — Trinity's four sliding layers read a RING of 32 pages a slot
# through the same kernel, unfused; Solar walks 529 pages a slot
_DECODE_CELLS = {
    "opt13b_chat": (32, 32, 32, 64, 22, 2, True),
    "opt13b_longprompt": (24, 32, 32, 64, 29, 2, True),
    "olmoe_gen": (64, 16, 16, 128, 17, 2, True),
    "lfm2_widegen": (256, 32, 8, 64, 21, 2, True),
    "trinity_ring": (128, 32, 4, 128, 32, 4, False),
    "trinity_lane": (128, 32, 4, 128, 273, 1, True),
    "solar_longctx": (64, 64, 8, 128, 529, 1, True),
    "granite_chatgen": (176, 32, 8, 128, 45, 1, True),
    "nemotron_thinkgen": (192, 32, 2, 128, 88, 2, True),
}


@pytest.mark.parametrize("cell", sorted(_DECODE_CELLS))
def test_paged_decode_block_loop_at_the_cells_shapes(cell, one_chip, mosaic,
                                                     vmem_asks):
    """The decode kernel's block loop — a loop over the live rows, blocks
    of pages double-buffered, the next row's first block handed over,
    the fused write's stripes sent to the pools by hand — lowers through
    Mosaic at each serving cell's shapes: with the fused write the two
    pools stay aliased input -> output (donated, they are updated in
    place: nothing pool-sized among the temporaries), and the call asks
    for the VMEM its buffers need and no floor — what
    ``_decode_vmem_bytes`` counts from the call's shapes, 12.6 to 18.6 MiB
    here where every call asked 96 — and compiles under it (a kernel over
    its limit does not compile)."""
    slots, heads, kv_heads, head_dim, slot_pages, layers, fused = \
        _DECODE_CELLS[cell]
    page = 64
    fn, shapes = _paged_decode(False, page=page, slots=slots,
                               cache_len=slot_pages * page, layers=layers,
                               kv_heads=kv_heads, heads=heads,
                               head_dim=head_dim, fused=fused)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=(1, 2) if fused else ()) \
        .lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if fused:
        mem = compiled.memory_analysis()
        pool_bytes = 2 * layers * (slots * slot_pages + 1) * page \
            * kv_heads * head_dim * 2
        assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
        assert mem.temp_size_in_bytes < pool_bytes // 16, \
            mem.temp_size_in_bytes
    bp = paged_mod._decode_block_pages(page, slot_pages, 2)
    asked = decode_mod._decode_vmem_bytes(
        slots, heads, head_dim, bp * page, kv_heads * head_dim, BF16, BF16)
    assert vmem_asks == [asked], (vmem_asks, asked)
    assert 12 * 2 ** 20 < asked < 20 * 2 ** 20, f"{asked / 2 ** 20:.1f} MiB"


def _lane_kernel(kind, lanes, rows, heads, slot_pages, width=640, rank=512,
                 page=64, layers=2):
    """One layer-step of a latent decode block at a cell's sizes: ``lanes``
    lanes of ``rows`` query rows (GLM-5's verify window has two) over a pool
    of 640-wide latent rows (``mla_lane_decode``) or 128-wide index keys
    (``dsa_lane_index``), the slot's table padded to whole 512-key blocks."""
    bp = 512 // page
    table = -(-slot_pages // bp) * bp
    keys = table * page
    pool = ((layers, lanes * slot_pages + 1, page,
             width if kind == "mla_lane_decode" else 128), BF16)
    tail = [pool, ((lanes, table), I32), ((lanes,), I32)]
    if kind == "mla_lane_decode":
        def fn(q, kept, pool, table, ctx):
            return latent_mod.lane_decode(q, kept, pool, layers - 1, table,
                                          bp, ctx, rank, 0.07)
        return fn, [((lanes, rows, heads, width), BF16),
                    ((lanes, rows, keys), I8)] + tail

    def fn(q, w, pool, table, ctx):
        return latent_mod.lane_index_scores(q, w, pool, layers - 1, table, bp,
                                            ctx)
    return fn, [((lanes, rows, heads, 128), BF16),
                ((lanes, rows, heads), F32)] + tail


# the other decode-side calls at their cells' sizes, and what each asks:
# EvaByte's 24 lanes (a three-deep ring of 64-row pages of 4,096 lanes) and
# the monolithic kernel ``generate()`` runs, what ``_decode_vmem_bytes``
# counts; GLM-5's verify windows (64 lanes x 2 rows x 64 heads over 73
# pages, 32 index heads) and LongCat's steps (128 lanes x 64 heads over 33
# pages), the flat ``VMEM_ASK`` they keep — at the 7 MiB they hold
# LongCat's decode block ran 2% LONGER on the chip (PERF.md, PR 61)
_DECODE_SIDE_ASKS = {
    "evabyte_eva_decode": (_eva_decode, lambda: decode_mod._decode_vmem_bytes(
        1, 32, 128, 64, 4096, BF16, BF16, buffers=3)),
    "mono_decode": (_mono_decode, lambda: decode_mod._decode_vmem_bytes(
        1, H, D, 512, HD, BF16, BF16)),
    "glm5_mla_lane_decode": (
        lambda: _lane_kernel("mla_lane_decode", 64, 2, 64, 73),
        lambda: latent_mod.VMEM_ASK),
    "longcat_mla_lane_decode": (
        lambda: _lane_kernel("mla_lane_decode", 128, 1, 64, 33),
        lambda: latent_mod.VMEM_ASK),
    "glm5_dsa_lane_index": (
        lambda: _lane_kernel("dsa_lane_index", 64, 2, 32, 73),
        lambda: latent_mod.VMEM_ASK),
}


@pytest.mark.parametrize("case", sorted(_DECODE_SIDE_ASKS))
def test_decode_side_kernel_compiles_under_its_ask(case, one_chip, mosaic,
                                                   vmem_asks):
    """``attn.eva_decode`` and ``attn.decode`` ask for what their call's
    shapes count to — 8.5 and 13.1 MiB where they asked 96 —, the two lane
    kernels for their flat 64, and Mosaic accepts each at its cell's sizes
    under its ask."""
    build, ask = _DECODE_SIDE_ASKS[case]
    fn, shapes = build()
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert vmem_asks == [ask()], (vmem_asks, ask())
    assert ask() == latent_mod.VMEM_ASK or ask() < 14 * 2 ** 20, vmem_asks


@pytest.mark.parametrize("rows", [1, 4], ids=["one_row", "four_rows"])
def test_chunk_step_writes_page_runs_in_place(rows, one_chip, mosaic):
    """The WHOLE serving chunk step at OPT-1.3B's widths and the batch
    cell's pool (24 layers x 697 pages of 64 rows: 4.4 GB a buffer), as
    the one-row program (scalar start) and as the cell's own dispatch of
    ``chunk_rows`` = 4 rows (a start a row, 8 page runs a layer and
    buffer): the chunk's K/V goes in as page runs — ``dynamic_update_slice`` under
    ``cache.write``, no ``scatter`` — and XLA keeps the pool in place
    through the chain of 96 updates interleaved with the kernel's reads:
    no pool-sized ``copy``, both pools aliased input -> output, nothing
    pool-sized among the temporaries.  And the chunk kernel asks for
    little enough VMEM that XLA keeps a layer's next weights in flight
    ACROSS it: the MLP's up-projection (33.5 MB a layer) is sliced into
    VMEM asynchronously, started before the kernel and awaited after it
    — under the kernel's old 64 MiB floor it was read from HBM when the
    matmul ran, and only the scatter gave XLA a window to prefetch in."""
    from deepspeed_tpu.inference.serving.slots import (chunk_rows,
                                                       make_chunk_fn)
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer
    pages, page, chunk, slot_pages = 697, 64, 128, 29
    model = Transformer(opt_config(
        "opt-125m", hidden_size=HD, num_layers=L, num_heads=H,
        ffn_hidden_size=4 * HD, vocab_size=50272, max_seq_len=2048,
        dtype="bfloat16", scan_layers=False))
    on_chip = lambda tree, dtype=None: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                       sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), I32))), BF16)
    pool = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(pages, page, BF16)))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)
    declared = model.slot_contract()
    assert chunk_rows(declared, chunk, page) == 4
    compiled = make_chunk_fn(model, declared, None).lower(
        params, pool, ints(rows, slot_pages), ints(rows, chunk),
        ints(rows) if rows > 1 else ints(), ints(rows)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    writes = {line.split("cache.write/")[1].split('"')[0]
              for line in text.splitlines() if "cache.write/" in line}
    # (the rows' writes sit one call deeper: jit(_write_row_runs)/...)
    assert any(w.endswith("dynamic_update_slice") for w in writes) \
        and not any("scatter" in w for w in writes), writes
    pool_shape = f"bf16[{L},{pages},{page},{HD}]"
    copies = [line.strip()[:160] for line in text.splitlines()
              if " copy(" in line and pool_shape in line.split(" copy(")[0]]
    assert not copies, copies
    mem = compiled.memory_analysis()
    pool_bytes = 2 * L * pages * page * HD * 2
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 16, mem.temp_size_in_bytes
    # weights + pools + temporaries inside the chip's 16 GB
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert peak < 16 * 10 ** 9, peak
    asked = paged_mod._chunk_loop_vmem_bytes(
        chunk, H, D, paged_mod._chunk_block_pages(page, slot_pages) * page,
        HD, 2, 2)
    assert asked <= 40 * 2 ** 20, f"asks for {asked / 2 ** 20:.0f} MiB"
    prefetched = {int(m) for m in re.findall(
        r"slice-start\(%params__params____layers_(\d+)____mlp____up_proj",
        text)}
    assert len(prefetched) >= L - 2, sorted(prefetched)


def _slot_programs_of(cell, family, one_chip, **overrides):
    """What a serving cell's slot programs are lowered from, at the cell's
    own settings: the module (``overrides``: the family's
    ``program_model`` keywords, as ``benchmark/serving.py`` passes
    ``scan_layers=False``), the cell's ``serving`` block, its chunk and
    ``SlotPages`` — and ``params`` / ``pool`` as shapes on the described
    chip, with ``on_chip`` and ``ints`` to make more of them."""
    import os
    import types
    from benchmark import spec
    from deepspeed_tpu.inference.serving import slots
    from deepspeed_tpu.inference.serving.paging import SlotPages
    bench = spec.Benchmark(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    cell = bench.cell(cell)
    module = bench.family(family).program_model(cell["config"], **overrides)
    s = cell["system"]["serving"]
    declared = module.slot_contract()
    chunk = slots.admission_chunk(declared, s["prefill_chunk"])
    pages = SlotPages(module, declared, s["num_slots"], s["max_cache_len"],
                      s["page_size"], 0, chunk, False, {})
    on_chip = lambda tree, dtype=None: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                       sharding=one_chip), tree)
    return types.SimpleNamespace(
        module=module, declared=declared, serving=s, chunk=chunk, pages=pages, on_chip=on_chip,
        params=on_chip(jax.eval_shape(lambda: module.init(
            jax.random.key(0), {"input_ids": jnp.zeros((1, 8), I32)})), BF16),
        pool=on_chip(jax.eval_shape(lambda: pages.new_pools(BF16))),
        ints=lambda *shape: jax.ShapeDtypeStruct(shape, I32,
                                                 sharding=one_chip))


_ITEMSIZE = {"bf16": 2, "f32": 4, "s32": 4, "s8": 1}


def _slices_open_across_kernels(text):
    """``[(slices, bytes)]`` a ``tpu_custom_call`` of a compiled program's
    text: the ``slice-start``s whose ``slice-done`` comes after the call —
    operands XLA is copying into VMEM (the second shape of a
    ``slice-start``'s tuple, in memory space ``S(1)``) while the kernel
    runs.  The text of a compiled module lists a computation's instructions
    in the order they are scheduled.  (``docs/performance.md``, "What a
    kernel is granted, XLA cannot use".)"""
    calls, started = [], {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)", line)
        if not m:
            started = {}                    # a computation's first or last line
            continue
        name, rest = m.groups()
        if " slice-start(" in rest:
            dtype, dims = re.findall(r"(\w+)\[([\d,]*)\]", rest)[1]
            started[name] = _ITEMSIZE[dtype] * math.prod(
                map(int, dims.split(",")))
        elif " slice-done(" in rest:
            started.pop(re.search(r"slice-done\(%([^),]+)", rest).group(1),
                        None)
        elif 'custom_call_target="tpu_custom_call"' in rest:
            calls.append((len(started), sum(started.values())))
    return calls


def test_chat_decode_block_prefetches_weights_across_the_kernel(
        one_chip, mosaic, monkeypatch):
    """The mechanism of PR 61, counted without the chip: ``opt13b-serve-chat``'s
    decode block as the cell serves it (``scan_layers=False``; four layers
    of the 24) keeps the next weights' copies into VMEM open ACROSS each
    ``attn.paged_decode`` call — more ``slice-start`` / ``slice-done`` pairs
    than the same program with the call's ask forced back to the 96 MiB
    floor, over 30 MB open across a call where the floor leaves under half
    of that (at 24 layers, PR 53's probe and this PR's: 368 pairs against
    184, 38.1 MB a call against 2.0).  What a kernel is granted XLA cannot
    use: if a later change to the ask, the kernel or the block closes the
    window again, this fails before a chip run has to say so."""
    from deepspeed_tpu.inference.serving import slots

    def block():
        c = _slot_programs_of("opt13b-serve-chat", "opt", one_chip,
                              scan_layers=False, num_layers=4)
        n = c.serving["num_slots"]
        state = c.on_chip({k: jnp.asarray(v) for k, v in
                           slots.init_slot_state(n).items()})
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=one_chip)
        text = slots.make_decode_block_fn(
            c.module, c.declared, lambda logits, rng: jnp.argmax(logits, -1),
            None, c.serving["decode_block"], c.pages.cache_len).lower(
                c.params, c.pool, state, c.ints(n, c.pages.table_width),
                rng).compile().as_text()
        return text.count(" slice-start("), _slices_open_across_kernels(text)

    pairs, across = block()
    monkeypatch.setattr(paged_mod, "_decode_vmem_bytes",
                        lambda *a, **kw: 96 * 2 ** 20)
    floor_pairs, floor_across = block()
    assert len(across) == len(floor_across) == 4
    mean = lambda calls, i: sum(c[i] for c in calls) / len(calls)
    assert pairs > floor_pairs, (pairs, floor_pairs)
    assert mean(across, 0) > mean(floor_across, 0), (across, floor_across)
    assert mean(across, 1) > 30e6 > 2 * mean(floor_across, 1), \
        (across, floor_across)


@pytest.mark.parametrize("program", ["chunk_step", "decode_block"])
def test_evabyte_slot_programs_compile_at_the_cells_sizes(program, one_chip,
                                                          mosaic):
    """The two programs ``evabyte-serve-bytedoc-batch`` runs, whole, as
    ``serving/slots.py`` builds them over ``SlotPages``' pools at the cell's
    own settings: eight Mosaic calls each (a layer's ``attn.eva_chunk`` /
    ``attn.eva_decode``), the pools aliased input -> output, and the 12.5 GB
    the cell's sizing reckons — weights 3.26 GB, 24 rings of 32 pages and
    the summary lane, 8 layers — inside one chip."""
    from deepspeed_tpu.inference.serving import slots
    c = _slot_programs_of("evabyte-serve-bytedoc-batch", "evabyte", one_chip)
    module, s, chunk, pages = c.module, c.serving, c.chunk, c.pages
    params, pool, ints, on_chip = c.params, c.pool, c.ints, c.on_chip
    assert (pages.pages_per_slot, pages.ring_pages) == (14, 32)
    if program == "chunk_step":
        compiled = slots.make_chunk_fn(module, c.declared, None).lower(
            params, pool, ints(1, pages.table_width), ints(1, chunk), ints(),
            ints(1)).compile()
    else:
        n = s["num_slots"]
        state = on_chip({k: jnp.asarray(v) for k, v in
                         slots.init_slot_state(n).items()})
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=one_chip)
        compiled = slots.make_decode_block_fn(
            module, c.declared, lambda logits, rng: jnp.argmax(logits, -1),
            None, s["decode_block"], pages.cache_len).lower(
                params, pool, state, ints(n, pages.table_width),
                rng).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 8
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * 2 for x in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 12.4e9 < total < 14e9, f"{total / 1e9:.2f} GB"


def test_olmoe_chunk_step_compiles_at_four_rows(one_chip, mosaic):
    """``olmoe-serve-gen-batch``'s chunk dispatch as ``serving/slots.py``
    builds it at the cell's own settings — ``chunk_rows`` = 4 rows of 128,
    a start and a last position a row, the load vector beside the logits:
    every layer's 512-token expert call is the sorted form
    (``moe.experts_grouped``; no ``moe.experts_gmm`` in the program), the
    pools are aliased input -> output, and weights, pools and temporaries
    are the 11.7 GB the cell's sizing reckons — the [4, 128] dispatch adds
    its sorted rows (8 x 4,096 pairs padded to tiles: 20 MB a layer) and no
    pool-sized value."""
    from deepspeed_tpu.inference.serving import slots
    c = _slot_programs_of("olmoe-serve-gen-batch", "olmoe", one_chip)
    rows = slots.chunk_rows(c.declared, c.chunk, c.serving["page_size"])
    assert (c.chunk, rows, c.declared.routes_experts) == (128, 4, True)
    compiled = slots.make_chunk_fn(c.module, c.declared, None).lower(
        c.params, c.pool, c.ints(rows, c.pages.table_width),
        c.ints(rows, c.chunk), c.ints(rows), c.ints(rows)).compile()
    text = compiled.as_text()
    layers = c.declared.expert_layers
    assert layers == 8
    calls = lambda name: len(re.findall(
        rf"%{re.escape(name)}[.\d]* = ", text))
    assert (calls("moe.experts_grouped"), calls("moe.route"),
            calls("moe.experts_gmm")) == (layers, layers, 0)
    logits, _, load = compiled.out_info
    assert logits.shape[:2] == (rows, 1)
    assert load.shape == (layers * c.declared.experts + 2,)
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * 2 for x in jax.tree.leaves(c.pool))
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    assert mem.temp_size_in_bytes < 0.3e9, mem.temp_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 11.6e9 < total < 12.1e9, f"{total / 1e9:.2f} GB"


def _no_pool_layer_is_sliced_out(text, pages):
    """A full layer's chunk gathers the slot's lane from the pool by
    ``(layer, page)``: the compiled program holds NO value the size of a
    pool layer (``[pages, 64, 640]`` latent rows, ``[pages, 64, 128]``
    index keys — XLA used to copy the whole layer out, then gather one
    slot's pages from the copy)."""
    layer_sized = re.compile(
        r"= \w+\[(1,)?%d,64,(640|128)\]\S* (?!parameter\()"
        % pages.num_pages)
    found = [line.strip()[:160] for line in text.splitlines()
             if layer_sized.search(line)]
    assert not found, found


def _lane_calls_alias_their_pool(compiled, name, layers, pool):
    """The program's Mosaic calls of lane kernel ``name``: one a pool layer,
    each handing its pool through as an aliased output (the call's last
    operand), and no pool-shaped value anywhere in the program that is a
    COPY — no second pool stands beside the block."""
    text = compiled.as_text()
    calls = re.findall(rf"%{re.escape(name)}[.\d]* = [^\n]*"
                       r'custom_call_target="tpu_custom_call"[^\n]*', text)
    assert len(calls) == layers, (name, len(calls))
    for call in calls:
        operands = call.split("operand_layout_constraints={")[1] \
            .split("}, output_to_operand_aliasing")[0].count("[")
        assert "output_to_operand_aliasing={{1}: (%d, {})}" % (operands - 1) \
            in call, call[:400]
    for leaf in jax.tree.leaves(pool):
        shape = ",".join(str(d) for d in leaf.shape)
        copied = re.findall(rf"= \w+\[{shape}\]\S* copy\([^\n]*", text)
        assert not copied, [line[:160] for line in copied]


def test_dots3_chunk_step_compiles_at_the_cells_sizes(one_chip, mosaic):
    """The chunk program ``dots3-serve-longdoc-batch`` runs, whole: index,
    top-k, decompress and flash a full layer (two), flash a window layer
    (three), the grouped experts a routed layer (four) as Mosaic calls; the
    lane read through the table; the pools aliased input -> output."""
    from deepspeed_tpu.inference.serving import slots
    c = _slot_programs_of("dots3-serve-longdoc-batch", "dots3", one_chip)
    module, s, chunk, pages = c.module, c.serving, c.chunk, c.pages
    params, pool, ints = c.params, c.pool, c.ints
    assert (pages.num_pages, s["page_size"]) == (4113, 64)
    compiled = slots.make_chunk_fn(module, c.declared, None).lower(
        params, pool, ints(1, pages.table_width), ints(1, chunk), ints(),
        ints(1)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 * 4 + 3 + 4
    _no_pool_layer_is_sliced_out(text, pages)
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * 2 for x in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes


@pytest.mark.parametrize("program", ["chunk_step", "spec_block"])
def test_glm5_slot_programs_compile_at_the_cells_sizes(program, one_chip,
                                                       mosaic):
    """The two programs ``glm5-serve-reasongen-batch`` runs, whole, as
    ``serving/slots.py`` builds them for a self-drafting model at the
    cell's own settings: the chunk that fills the multi-token-prediction
    module's rows beside the main model's — each layer's lane read through
    the table —, and the block of verify windows
    of two rows a lane — the pools (six layers: five main, the module's)
    aliased input -> output, 9.61 GB of weights + 2.76 GB of pools and the
    programs' temporaries inside one chip."""
    from deepspeed_tpu.inference.serving import slots
    c = _slot_programs_of("glm5-serve-reasongen-batch", "glm5", one_chip)
    module, s, chunk, pages = c.module, c.serving, c.chunk, c.pages
    params, pool, ints, on_chip = c.params, c.pool, c.ints, c.on_chip
    assert (pages.pages_per_slot, pages.num_pages) == (73, 64 * 73 + 1)
    assert {k: v.shape[0] for k, v in pool.items()} \
        == {"latent": 6, "index": 6}
    if program == "chunk_step":
        compiled = slots.make_chunk_fn(
            module, c.declared, None, self_draft=True).lower(
                params, pool, ints(1, pages.table_width), ints(1, chunk),
                ints(), ints(1), ints(1)).compile()
        calls = 6 * 4         # index, top-k, decompress, flash a layer
        _no_pool_layer_is_sliced_out(compiled.as_text(), pages)
    else:
        n = s["num_slots"]
        state = on_chip({k: jnp.asarray(v) for k, v in
                         slots.init_slot_state(n, draft=True).items()})
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=one_chip)
        compiled = slots.make_spec_block_fn(
            module, c.declared, lambda logits, rng: jnp.argmax(logits, -1),
            None, s["decode_block"], pages.cache_len).lower(
                params, pool, state, ints(n, pages.table_width),
                rng).compile()
        # the expert kernel a routed layer; lane index, top-k, lane
        # decode a pool layer
        calls = 5 + 6 * 3
        for name in ("attn.dsa_lane_index", "attn.mla_lane_decode"):
            _lane_calls_alias_their_pool(compiled, name, 6, pool)
        assert compiled.as_text().count("tpu_custom_call") == calls
    assert compiled.as_text().count("tpu_custom_call") >= calls
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * 2 for x in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 12.3e9 < total < 14.5e9, f"{total / 1e9:.2f} GB"


@pytest.mark.parametrize("program", ["chunk_step", "decode_block"])
def test_longcat_slot_programs_compile_at_the_cells_sizes(program, one_chip,
                                                          mosaic):
    """The two programs ``longcat-serve-agentgen-batch`` runs, whole, as
    ``serving/slots.py`` builds them at the cell's own settings: four
    double layers — eight dense latent attentions over ONE pool of eight
    layers (no index pool), four expert layers of 16 held experts —, the
    pool aliased input -> output, 10.35 GB of weights + 2.77 GB of pool and
    the programs' temporaries inside one chip."""
    from deepspeed_tpu.inference.serving import slots
    c = _slot_programs_of("longcat-serve-agentgen-batch", "longcat",
                          one_chip)
    module, s, chunk, pages = c.module, c.serving, c.chunk, c.pages
    params, pool, ints, on_chip = c.params, c.pool, c.ints, c.on_chip
    assert (pages.pages_per_slot, pages.num_pages) == (33, 128 * 33 + 1)
    assert {k: v.shape for k, v in pool.items()} \
        == {"latent": (8, 128 * 33 + 1, 64, 640)}
    if program == "chunk_step":
        compiled = slots.make_chunk_fn(module, c.declared, None).lower(
            params, pool, ints(1, pages.table_width), ints(1, chunk), ints(),
            ints(1)).compile()
        calls = 8 * 2 + 4     # decompress, flash a sublayer; the experts
        _no_pool_layer_is_sliced_out(compiled.as_text(), pages)
    else:
        n = s["num_slots"]
        state = on_chip({k: jnp.asarray(v) for k, v in
                         slots.init_slot_state(n).items()})
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=one_chip)
        compiled = slots.make_decode_block_fn(
            module, c.declared, lambda logits, rng: jnp.argmax(logits, -1),
            None, s["decode_block"], pages.cache_len).lower(
                params, pool, state, ints(n, pages.table_width),
                rng).compile()
        calls = 8 + 4         # lane decode a sublayer; the experts
        _lane_calls_alias_their_pool(compiled, "attn.mla_lane_decode", 8,
                                     pool)
        assert compiled.as_text().count("tpu_custom_call") == calls
    assert compiled.as_text().count("tpu_custom_call") >= calls
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * 2 for x in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 13.0e9 < total < 15.5e9, f"{total / 1e9:.2f} GB"


@pytest.mark.parametrize("program", ["chunk_step", "decode_block"])
def test_trinity_slot_programs_compile_at_the_cells_sizes(program, one_chip,
                                                          mosaic, vmem_asks):
    """The two programs ``trinity-serve-mixedlen-batch`` runs, whole, as
    ``serving/slots.py`` builds them at the cell's own settings: four
    sliding layers over K/V rings of 32 pages a slot and one full layer over
    K/V lane pages — ``attn.gqa_window_chunk`` / ``attn.paged_decode`` over
    the ring's table and ``attn.paged_chunk_prefill`` / the fused paged
    decode —, four expert layers of 128 experts, a 200,192-wide head; every
    pool aliased input -> output, 8.48 GB of weights + 2.15 GB of lane pool
    + 2.15 GB of rings and the programs' temporaries inside one chip; and
    every instruction under the model's call lies in a part of the
    profiler's table."""
    import re
    from deepspeed_tpu.inference.serving import slots
    from deepspeed_tpu.profiling.flops_profiler import profiler
    c = _slot_programs_of("trinity-serve-mixedlen-batch", "trinity",
                          one_chip)
    module, s, chunk = c.module, c.serving, c.chunk
    params, ints, on_chip = c.params, c.ints, c.on_chip
    from deepspeed_tpu.inference.serving.paging import SlotPages
    pages = SlotPages(module, c.declared, s["num_slots"], s["max_cache_len"],
                      s["page_size"], s["num_pages"], chunk, False, {})
    pool = on_chip(jax.eval_shape(lambda: pages.new_pools(BF16)))
    assert (pages.pages_per_slot, pages.ring_pages, pages.window_pages) \
        == (273, 32, 128 * 32 + 1)
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (1, s["num_pages"], 64, 512), "v": (1, s["num_pages"], 64, 512),
        "k_ring": (4, 4097, 64, 512), "v_ring": (4, 4097, 64, 512)}
    if program == "chunk_step":
        compiled = slots.make_chunk_fn(module, c.declared, None).lower(
            params, pool, ints(1, pages.table_width), ints(1, chunk), ints(),
            ints(1)).compile()
        calls = 4 + 1 + 4     # a window chunk, a paged chunk; the experts
        # the chunk fold's two kernels — 512 queries a grid step, GQA 32 / 4
        # at D = 128 — ask for what they asked before their statistics
        # became whole lane tiles (PR 53)
        assert {75497472, 67108864} <= set(vmem_asks), vmem_asks
    else:
        n = s["num_slots"]
        state = on_chip({k: jnp.asarray(v) for k, v in
                         slots.init_slot_state(n).items()})
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=one_chip)
        compiled = slots.make_decode_block_fn(
            module, c.declared, lambda logits, rng: jnp.argmax(logits, -1),
            None, s["decode_block"], pages.cache_len).lower(
                params, pool, state, ints(n, pages.table_width),
                rng).compile()
        calls = 5 + 4         # paged decode a layer; the experts
        # ``attn.paged_decode`` over the ring's table and over the lane
        # pool's: the same ask, from the same blocks of 8 pages of 512 lanes
        assert vmem_asks.count(decode_mod._decode_vmem_bytes(
            n, 32, 128, 512, 512, BF16, BF16)) == 5, vmem_asks
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= calls
    named = set(re.findall(r'op_name="([^"]*TrinityModel\.decode[^"]*)"',
                           text))
    assert named and not [n for n in named if profiler.part_of(n)[0] is None]
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * 2 for x in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 12.5e9 < total < 15.5e9, f"{total / 1e9:.2f} GB"


@pytest.mark.parametrize("program", ["decode_block"])
def test_solar_slot_programs_compile_at_the_cells_sizes(program, one_chip,
                                                        mosaic):
    """``program``: the decode block only — the chunk step compiles the same
    way (PR 54 ran both, 60 s the chunk step) and its one new kernel is a
    case of ``test_kernel_compiles_to_mosaic`` at the cell's widths.
    The two programs ``solar-serve-longctx-batch`` runs, whole, as
    ``serving/slots.py`` builds them at the cell's own settings: one softmax
    layer over K/V lane pages (529 a slot), three gated delta-rule layers
    over a float32 matrix state and a bfloat16 conv state a slot —
    ``kda.chunk_scan`` / ``kda.decode_step`` with the state pool aliased in
    and out —, four expert layers of 40 held experts under a 320-wide
    router, a 24,576-wide head; every pool aliased input -> output, 6.62 GB
    of weights + 4.3 GB of lane pool + 0.85 GB of state and the programs'
    temporaries inside one chip; and every instruction under the model's
    call lies in a part of the profiler's table."""
    from deepspeed_tpu.inference.serving import slots
    from deepspeed_tpu.inference.serving.paging import SlotPages
    from deepspeed_tpu.profiling.flops_profiler import profiler
    c = _slot_programs_of("solar-serve-longctx-batch", "solar_open2",
                          one_chip)
    module, s, chunk = c.module, c.serving, c.chunk
    params, ints, on_chip = c.params, c.ints, c.on_chip
    pages = SlotPages(module, c.declared, s["num_slots"], s["max_cache_len"],
                      s["page_size"], s["num_pages"], chunk, False, {})
    pool = on_chip(jax.eval_shape(lambda: pages.new_pools(BF16)))
    assert (pages.pages_per_slot, pages.state_rows, pages.table_width) \
        == (529, 65, 530)
    assert {k: (v.shape, str(v.dtype)) for k, v in pool.items()} == {
        "k": ((1, s["num_pages"], 64, 1024), "bfloat16"),
        "v": ((1, s["num_pages"], 64, 1024), "bfloat16"),
        "conv": ((3, 65, 576, 128), "bfloat16"),     # 3 x 24,576 a row
        "kda": ((3, 65, 64, 128, 128), "float32")}
    assert pages.state_kind_bytes == {"conv": 3 * 147456,
                                      "kda": 3 * 4 * 2 ** 20}
    if program == "chunk_step":
        compiled = slots.make_chunk_fn(module, c.declared, None).lower(
            params, pool, ints(1, pages.table_width), ints(1, chunk), ints(),
            ints(1)).compile()
        calls = 1 + 3 + 4     # a paged chunk, a state scan; the experts
    else:
        n = s["num_slots"]
        state = on_chip({k: jnp.asarray(v) for k, v in
                         slots.init_slot_state(n).items()})
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=one_chip)
        compiled = slots.make_decode_block_fn(
            module, c.declared, lambda logits, rng: jnp.argmax(logits, -1),
            None, s["decode_block"], pages.cache_len).lower(
                params, pool, state, ints(n, pages.table_width),
                rng).compile()
        calls = 1 + 3 + 4     # paged decode, a state step; the experts
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= calls
    assert ("kda.chunk_scan" if program == "chunk_step"
            else "kda.decode_step") in text
    named = set(re.findall(r'op_name="([^"]*SolarOpen2Model\.decode[^"]*)"',
                           text))
    assert named and not [n for n in named if profiler.part_of(n)[0] is None]
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 11.5e9 < total < 15.5e9, f"{total / 1e9:.2f} GB"


@pytest.mark.parametrize("program", ["chunk_step", "decode_block"])
def test_granite_slot_programs_compile_at_the_cells_sizes(program, one_chip,
                                                          mosaic):
    """The two programs ``granite-serve-chatgen-batch`` runs, whole, as
    ``serving/slots.py`` builds them at the cell's own settings: nine
    Mamba-2 layers over a float32 matrix state and a bfloat16 conv state a
    slot — ``ssd.chunk_scan`` / ``ssd.decode_step`` with the state pool
    aliased in and out —, one NoPE softmax layer over K/V lane pages at
    scale 1/128, ten expert layers of 18 held experts under a 72-wide
    router, a tied 25,088-wide head.  The state pool (6.8 GB at 176 slots)
    is larger than the weights (5.9 GB): ONE un-aliased copy of it in either
    program and the cell does not fit — every pool aliased input -> output,
    weights + pools + the programs' temporaries inside one chip; and every
    instruction under the model's call lies in a part of the profiler's
    table."""
    from deepspeed_tpu.inference.serving import slots
    from deepspeed_tpu.inference.serving.paging import SlotPages
    from deepspeed_tpu.profiling.flops_profiler import profiler
    c = _slot_programs_of("granite-serve-chatgen-batch", "granite_hybrid",
                          one_chip)
    module, s, chunk = c.module, c.serving, c.chunk
    params, ints, on_chip = c.params, c.ints, c.on_chip
    pages = SlotPages(module, c.declared, s["num_slots"], s["max_cache_len"],
                      s["page_size"], s["num_pages"], chunk, False, {})
    pool = on_chip(jax.eval_shape(lambda: pages.new_pools(BF16)))
    assert (pages.pages_per_slot, pages.state_rows, pages.table_width) \
        == (45, 177, 46)
    assert {k: (v.shape, str(v.dtype)) for k, v in pool.items()} == {
        "k": ((1, s["num_pages"], 64, 1024), "bfloat16"),
        "v": ((1, s["num_pages"], 64, 1024), "bfloat16"),
        # 3 x 8,448 a row: 198 sublanes on 208, whole bfloat16 tiles
        "conv": ((9, 177, 208, 128), "bfloat16"),
        "ssm": ((9, 177, 64, 128, 128), "float32")}
    assert pages.state_kind_bytes == {"conv": 9 * 208 * 128 * 2,
                                      "ssm": 9 * 4 * 2 ** 20}
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    weights = sum(x.size * 2 for x in jax.tree.leaves(params))
    assert pool["ssm"].size * 4 > weights > 5.9e9       # state over weights
    if program == "chunk_step":
        compiled = slots.make_chunk_fn(module, c.declared, None).lower(
            params, pool, ints(1, pages.table_width), ints(1, chunk), ints(),
            ints(1)).compile()
    else:
        n = s["num_slots"]
        state = on_chip({k: jnp.asarray(v) for k, v in
                         slots.init_slot_state(n).items()})
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=one_chip)
        compiled = slots.make_decode_block_fn(
            module, c.declared, lambda logits, rng: jnp.argmax(logits, -1),
            None, s["decode_block"], pages.cache_len).lower(
                params, pool, state, ints(n, pages.table_width),
                rng).compile()
    text = compiled.as_text()
    # a paged chunk / paged decode, a state scan / step a Mamba layer, the
    # experts of ten layers
    assert text.count("tpu_custom_call") >= 1 + 9 + 10
    assert ("ssd.chunk_scan" if program == "chunk_step"
            else "ssd.decode_step") in text
    named = set(re.findall(
        r'op_name="([^"]*GraniteHybridModel\.decode[^"]*)"', text))
    assert named and not [n for n in named if profiler.part_of(n)[0] is None]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 13.4e9 < total < 15.5e9, f"{total / 1e9:.2f} GB"


@pytest.mark.parametrize("program", ["chunk_step", "decode_block"])
def test_nemotron_slot_programs_compile_at_the_cells_sizes(program, one_chip,
                                                           mosaic):
    """The two programs ``nemotron3-serve-thinkgen-batch`` runs, whole, at
    the cell's own settings: 14 ONE-sublayer blocks — six Mamba-2 blocks in
    8 ``B`` / ``C`` groups over a float32 matrix state and a bfloat16 conv
    state a slot, six expert blocks of 64 held two-matrix experts under a
    128-wide sigmoid router, two NoPE softmax blocks of 32 query heads on 2
    KV heads over K/V lane pages —, an untied 65,536-wide head.  Every pool
    aliased input -> output, 9.17 GB of weights + pools + the programs'
    temporaries inside one chip; and every instruction under the model's
    call lies in a part of the profiler's table — none of them a part
    called ``mixer``."""
    from deepspeed_tpu.inference.serving import slots
    from deepspeed_tpu.inference.serving.paging import SlotPages
    from deepspeed_tpu.profiling.flops_profiler import profiler
    c = _slot_programs_of("nemotron3-serve-thinkgen-batch", "nemotron_h",
                          one_chip)
    module, s, chunk = c.module, c.serving, c.chunk
    params, ints, on_chip = c.params, c.ints, c.on_chip
    pages = SlotPages(module, c.declared, s["num_slots"], s["max_cache_len"],
                      s["page_size"], s["num_pages"], chunk, False, {})
    pool = on_chip(jax.eval_shape(lambda: pages.new_pools(BF16)))
    n = s["num_slots"]
    assert (pages.pages_per_slot, pages.state_rows, pages.table_width) \
        == (88, n + 1, 89)
    assert {k: (v.shape, str(v.dtype)) for k, v in pool.items()} == {
        "k": ((2, s["num_pages"], 64, 256), "bfloat16"),
        "v": ((2, s["num_pages"], 64, 256), "bfloat16"),
        # 3 x 6,144 a row: 144 sublanes, whole bfloat16 tiles
        "conv": ((6, n + 1, 144, 128), "bfloat16"),
        "ssm": ((6, n + 1, 32, 128, 128), "float32")}
    assert pages.state_kind_bytes == {"conv": 6 * 144 * 128 * 2,
                                      "ssm": 6 * 2 * 2 ** 20}
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    weights = sum(x.size * 2 for x in jax.tree.leaves(params))
    # the published 4.585 B and the experts' zero padding to 1920
    assert weights == 2 * (4584903936 + 6 * 64 * 2 * 2688 * 64)
    if program == "chunk_step":
        compiled = slots.make_chunk_fn(module, c.declared, None).lower(
            params, pool, ints(1, pages.table_width), ints(1, chunk), ints(),
            ints(1)).compile()
    else:
        state = on_chip({k: jnp.asarray(v) for k, v in
                         slots.init_slot_state(n).items()})
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=one_chip)
        compiled = slots.make_decode_block_fn(
            module, c.declared, lambda logits, rng: jnp.argmax(logits, -1),
            None, s["decode_block"], pages.cache_len).lower(
                params, pool, state, ints(n, pages.table_width),
                rng).compile()
    text = compiled.as_text()
    # a paged chunk / paged decode an attention block, a state scan / step
    # a Mamba block, the experts of six blocks
    assert text.count("tpu_custom_call") >= 2 + 6 + 6
    assert ("ssd.chunk_scan" if program == "chunk_step"
            else "ssd.decode_step") in text
    assert ("moe.experts_grouped" if chunk >= moe_mod.GROUPED_MIN_ROWS
            and program == "chunk_step" else "moe.experts_gmm") in text
    named = set(re.findall(
        r'op_name="([^"]*NemotronHModel\.decode[^"]*)"', text))
    parts = {profiler.part_of(n)[0] for n in named}
    assert named and None not in parts and "mixer" not in parts
    assert {"attn.ssd", "moe.experts", "moe.route", "attn.core", "mlp",
            "norm", "head", "conv.short"} <= parts, parts
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, mem.alias_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(program, "total", total / 1e9, "pool", pool_bytes / 1e9)
    assert 12.0e9 < total < 15.5e9, f"{total / 1e9:.2f} GB"


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_to_mosaic(case, one_chip, mosaic):
    fn, shapes = CASES[case]()
    compiled = _compile(fn, shapes, one_chip)
    text = compiled.as_text()
    assert "tpu_custom_call" in text, \
        f"{case}: no Mosaic kernel in the compiled program"
    if case == "solar_kda_chunk_scan_c2048":
        # what a grid step takes: FOUR of the 64 heads' 64-row blocks, their
        # rows side by side (a quarter of the grid steps a head a step
        # made), in ONE kernel of this name; the pool aliased in -> out
        call, = _pallas_calls(fn, shapes)
        grid, heads = call.params["grid_mapping"], delta_mod._chunk_heads(
            64, 128)
        blocks = [tuple(x.block_size for x in b.block_shape
                        if hasattr(x, "block_size"))
                  for b in grid.block_mappings]
        assert heads == 4 and grid.grid == (64 // heads, 2048 // 64)
        assert blocks == [(64, heads * 128)] * 4 + [
            (64, 64), (heads, 128, 128), (64, heads * 128),
            (heads, 128, 128)], blocks
        assert call.params["name"] == "kda.chunk_scan" in text
        # operand 6 (after the scalars and the five row arrays): the pool
        assert call.params["input_output_aliases"] == ((6, 1),)
        assert "output_to_operand_aliasing={{1}: (6, {})}" in text
    if case.startswith("granite_ssd_"):
        # ONE kernel of the name, the pool (operand 7, after the scalars and
        # the row arrays) aliased in -> out: no copy of its 6.8 GB
        call, = _pallas_calls(fn, shapes)
        name = "ssd.chunk_scan" if "chunk" in case else "ssd.decode_step"
        assert call.params["name"] == name in text
        assert call.params["input_output_aliases"] == ((7, 1),)
        assert "output_to_operand_aliasing={{1}: (7, {})}" in text
        grid = call.params["grid_mapping"].grid
        assert grid == ((128 // 8, 512 // 128) if "chunk" in case
                        else (176, 64 // 16))
    if "_conv_step_" in case:
        # the rows' read and their write-back: one kernel each, the pool
        # whole in HBM (no block of it in VMEM), the write's aliased in ->
        # out (operand 3, after the two scalars and the rows to keep) — no
        # copy of the pool, and no XLA gather or scatter left beside them
        read, write = _pallas_calls(fn, shapes)
        assert (read.params["name"], write.params["name"]) \
            == ("conv.rows_read", "conv.rows_write")
        assert read.params["input_output_aliases"] == ()
        assert write.params["input_output_aliases"] == ((3, 0),)
        assert "output_to_operand_aliasing={{}: (3, {})}" in text
        assert all("any" in str(x.transformed_block_aval)
                   for call in (read, write)
                   for x in call.params["grid_mapping"].block_mappings)
        assert " gather(" not in text and " scatter(" not in text
        rows = shapes[2][0][2:]
        assert rows == {"granite": (208, 128), "solar": (576, 128),
                        "lfm2": (32, 128),
                        "nemotron": (144, 128)}[case.split("_")[0]]
    if case.startswith("nemotron_ssd_"):
        # as Granite's: one kernel of the name, the pool aliased in -> out;
        # a chunk step's eight heads are one of the 8 groups (its C B^T
        # block [None, 128, 128] of the [8, T, 128] scores), a decode step's
        # 16 tiles = 32 heads four (B and C blocks [4, 128])
        call, = _pallas_calls(fn, shapes)
        name = "ssd.chunk_scan" if "chunk" in case else "ssd.decode_step"
        assert call.params["name"] == name in text
        assert call.params["input_output_aliases"] == ((7, 1),)
        grid = call.params["grid_mapping"].grid
        assert grid == ((64 // 8, 512 // 128) if "chunk" in case
                        else (192, 32 // 16))
    if case.startswith("nemotron_moe_ungated_"):
        # ONE expert kernel, TWO weight operands (no gate matrix), each in
        # three width tiles of the stored [64, 2688, 1920] / [64, 1920, 2688]
        call, = [c for c in _pallas_calls(fn, shapes)
                 if c.params["name"].startswith("moe.experts")]
        assert call.params["name"] == (
            "moe.experts_gmm" if "gmm" in case else "moe.experts_grouped")
        blocks = [tuple(x.block_size for x in b.block_shape
                        if hasattr(x, "block_size"))
                  for b in call.params["grid_mapping"].block_mappings]
        assert blocks.count((1, 2688, 640)) == 1 \
            and blocks.count((1, 640, 2688)) == 1, blocks
    if case in _FLASH_TRAINED:
        # the backward of one call is ONE Mosaic kernel beside the
        # forward's: the head's float32 dq sum fits the VMEM a kernel gets
        # by default (the call asks for no more).  A silent fall back to
        # the pair fails here and not in a benchmark
        assert text.count("tpu_custom_call") == 2, case
        assert "attn.flash_dq_dkv" in text and "attn.flash_fwd" in text
    if case in _MLA_FLASH:
        # the flash kernel's blocks at its own block sizes — eight heads a
        # grid step — take under half the flat 64 MiB it asks for (a
        # kernel over its ask does not compile: this says by how much it
        # is under)
        nope, v = shapes[0][0][2], shapes[4][0][2]
        need = latent_mod._flash_vmem_bytes(8, 512, 512, nope, 64, v, 2)
        assert need <= latent_mod.VMEM_ASK // 2, \
            f"{case}: {need / 2 ** 20:.1f} MiB"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9, f"{case}: {total / 1e9:.1f} GB does not fit a v5e"
