"""Dropless routed experts — the inference path of a sparse-expert model
(OLMoE / Mixtral-style: softmax over all experts, top-k, every chosen
(token, expert) pair computed, whatever the imbalance).  An expert is GATED
(three matrices, ``(act(x wg) * (x wu)) wd``: OLMoE, dots3, LFM2, ...) or
UN-GATED (two, ``act(x wu) wd``: Nemotron-H's ``relu2``) — both kernels
below, both routers and the layer's row-count decision take either.

``sharded_moe.py`` is the GShard formulation: ``[tokens, experts,
capacity]`` dispatch tensors and a capacity past which a token is DROPPED.
That is a training-time trade; a served token that was dropped agrees with
no reference.  Here nothing has a capacity.  Two Pallas kernels, each
findable in a device trace by its own name:

* ``moe.route`` (:func:`route`) — the router matmul (float32 logits from
  the residual as the program holds it), a float32 softmax over all
  experts, top-k by repeated max (ties to the lower index, as
  ``lax.top_k``), and the result as a dense ``[T, E]`` COMBINE matrix:
  the chosen experts' gates (as they are, or divided by their sum) and
  zero elsewhere.  A token that is not ``live`` — a dead decode lane, a
  chunk's padded tail — chooses nothing: its row is zero and it is not
  counted.  Beside it, ``counts [E]``: the live tokens that chose each
  expert.
* ``moe.experts_gmm`` (:func:`experts`) — every TOUCHED expert (one with
  a live token) is computed over every token and weighted by its combine
  column; an untouched expert is skipped, weights unread.  Grid (expert,
  width tile), a float32 ``[T, M]`` accumulator in VMEM, the matrices of
  an expert fetched once a call.  An untouched expert's index maps name
  the block that is already there (the last touched expert's), so Mosaic
  elides the DMA, and the body is ``pl.when``-gated off.

At a decode step's token count (one a slot) or a lone chunk's (<= 128)
the layer is bound by streaming the touched experts' weights, not by the
arithmetic — on a v5e 64 rows through a gated expert's three matrices take
about the 15 us their 12.6 MB take to arrive — so the zero-weight rows
ride for free, and no sort, gather or scatter stands around the matmuls:
the weighted combine is the kernel's own accumulation.  Rows are
independent: what a dead lane holds changes no live lane's bits.

Two more pieces serve a model whose router scores sigmoid + selection
bias and whose chip holds a SHARE of the experts (``models/dots3.py``):

* :func:`route_scored` (XLA, under the scope ``moe.route``) — float32
  sigmoid scores, the top-k of ``score + bias`` (``noaux_tc``), gates the
  chosen scores over their sum; the choices come back as indices, since a
  chunk's grouped form sorts by them.
* ``moe.experts_grouped`` (:func:`experts_grouped`) — a chunk's rows
  sorted by the HELD expert they chose, each expert's rows padded to a
  whole row tile, and one kernel over the live tiles: a tile reads its
  expert's (three or two) matrices and computes its own rows only.  At 8 of 256 experts
  a token, a 2,048-token chunk hands each of 32 held experts ~64 rows;
  the dense form above would compute 32 x 2,048.  The softmax layer's
  chunk DISPATCH (4 rows of 128: one 512-token call) takes it too, its
  picks read back off the combine matrix (:func:`picks_of`).

No VJP: a model that trains through expert layers gives its gate a
capacity (``moe_capacity_factor``) and takes the GShard path.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import _interpret

# rows are padded to the bf16 sublane tile
_ROW_TILE = 16
# rows from which a call of the dropless layer — the scored form and the
# softmax form alike, ``MoE._sorted`` — sorts by expert
# (``moe.experts_grouped``) instead of computing every touched expert over
# every row (``moe.experts_gmm``).  On a v5e, 32 held experts of 5120 x
# 1536, 8 of 256 a token (PR 31, ms a call, gmm / grouped): 64 rows 1.82 /
# 2.07, 128 2.00 / 2.32, 256 2.09 / 2.55, 512 4.05 / 2.86, 1024 9.17 /
# 3.57 — the sort and the gathers cost ~0.4 ms, every row past ~256 costs
# the dense form another expert-row product.  OLMoE's sizes (64 of 64,
# 2048 x 1024): 128 rows 1.11 / 1.35, 512 rows 2.18 / 1.53 — and with the
# softmax router before either, as a 4-row chunk dispatch of its cell runs
# them (PR 59): 512 rows 2.20 / 1.55, a row of them dead 2.20 / 1.57
GROUPED_MIN_ROWS = 512


def _pad_rows(*arrays):
    pad = -arrays[0].shape[0] % _ROW_TILE
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays)


def _route_kernel(x_ref, w_ref, live_ref, combine_ref, counts_ref, *, k,
                  renormalize):
    logits = jnp.dot(x_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)      # [T, E]
    gates = jnp.exp(logits - jnp.max(logits, axis=1, keepdims=True))
    gates = gates / jnp.sum(gates, axis=1, keepdims=True)
    E = gates.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, gates.shape, 1)
    chosen = jnp.zeros(gates.shape, jnp.bool_)
    left = gates
    for _ in range(k):
        top = jnp.max(left, axis=1, keepdims=True)
        first = jnp.min(jnp.where(left == top, lane, E), axis=1,
                        keepdims=True)
        pick = lane == first
        chosen = chosen | pick
        left = jnp.where(pick, -1.0, left)
    chosen = chosen & (live_ref[...] > 0)
    combine = jnp.where(chosen, gates, 0.0)
    if renormalize:
        combine = combine / jnp.maximum(
            jnp.sum(combine, axis=1, keepdims=True), 1e-9)
    combine_ref[...] = combine
    counts_ref[...] = jnp.sum(chosen.astype(jnp.int32), axis=0,
                              keepdims=True)


def route(x, gate_w, k, renormalize=False, live=None):
    """Top-``k`` routing of ``x [T, M]`` through ``gate_w [M, E]``.

    Returns ``(combine [T', E] float32, counts [E] int32)`` — ``T'`` is
    ``T`` rounded up to the row tile, the added rows dead.  ``live [T]``
    bool: a token that is not live chooses nothing."""
    T = x.shape[0]
    E = gate_w.shape[1]
    live = jnp.ones((T,), jnp.int32) if live is None \
        else live.astype(jnp.int32)
    if x.dtype != gate_w.dtype:
        x, gate_w = x.astype(jnp.float32), gate_w.astype(jnp.float32)
    x, live = _pad_rows(x, live[:, None])
    Tp = x.shape[0]
    combine, counts = pl.pallas_call(
        functools.partial(_route_kernel, k=k, renormalize=renormalize),
        out_shape=(jax.ShapeDtypeStruct((Tp, E), jnp.float32),
                   jax.ShapeDtypeStruct((1, E), jnp.int32)),
        interpret=_interpret(),
        name="moe.route",
    )(x, gate_w, live)
    return combine, counts[0]


def _fetch_plan(counts, nf):
    """For each expert, what its grid steps fetch: ``(on [E], src [E],
    pin [E])`` int32.  A touched expert fetches its own blocks.  An
    untouched one names the block that is already in VMEM — the last
    width tile of the touched expert before it — or, ahead of the first
    touched expert, that expert's first tile (fetched early, then found
    in place)."""
    E = counts.shape[0]
    touched = counts > 0
    ids = jnp.arange(E, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(touched, ids, -1))
    first = jnp.argmax(touched).astype(jnp.int32)
    src = jnp.where(before < 0, first, before)
    pin = jnp.where(before < 0, 0, nf - 1).astype(jnp.int32)
    return touched.astype(jnp.int32), src, pin


def _width_tile(F):
    """The width tile of an expert's matrices: the largest of 1024, 512 and
    256 that divides ``F``; for an odd number of lane tiles (1920 = 15 x
    128) the most whole lane tiles that divide it under 1024 (640: at 128
    the accumulator's update a grid step costs what the step's DMA does);
    the whole width where 128 does not divide it."""
    for t in (1024, 512, 256):
        if F % t == 0:
            return t
    if F % 128:
        return F
    return max(128 * d for d in range(1, 9) if (F // 128) % d == 0)


def _gmm_kernel(on_ref, src_ref, pin_ref, x_ref, cw_ref, *rest, act, gated):
    if gated:
        wg_ref, wu_ref, wd_ref, o_ref, acc_ref = rest
    else:
        wu_ref, wd_ref, o_ref, acc_ref = rest
    e, f = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (f == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(on_ref[e] > 0)
    def _expert():
        x = x_ref[...]
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        if gated:
            h = act(jnp.dot(x, wg_ref[0],
                            preferred_element_type=jnp.float32)) * up
        else:
            h = act(up)
        # this expert's combine column, picked out of the resident
        # [T, E] matrix by a lane mask (the expert id is a scalar)
        cw = cw_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, cw.shape, 1)
        col = jnp.sum(jnp.where(lane == e, cw, 0.0), axis=1, keepdims=True)
        acc_ref[...] += jnp.dot((h * col).astype(x.dtype), wd_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when((e == pl.num_programs(0) - 1) & (f == pl.num_programs(1) - 1))
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def experts(x, combine, counts, wg, wu, wd, act):
    """The routed expert layer on ``x [T, M]``: ``sum_e combine[:, e] *
    (act(x @ wg[e]) * (x @ wu[e])) @ wd[e]`` over the touched experts
    (``wg`` None: the un-gated ``act(x @ wu[e]) @ wd[e]``).
    ``combine``/``counts`` from :func:`route`; ``wg``/``wu [E, M, F]``,
    ``wd [E, F, M]``, in ``x``'s dtype."""
    T, M = x.shape
    E, _, F = wu.shape
    gated = wg is not None
    (x,) = _pad_rows(x)
    Tp = x.shape[0]
    tf = _width_tile(F)
    on, src, pin = _fetch_plan(counts, F // tf)

    def tile(e, f, on, pin):
        return jnp.where(on[e] > 0, f, pin[e])

    up_spec = pl.BlockSpec((1, M, tf), lambda e, f, on, src, pin: (
        src[e], 0, tile(e, f, on, pin)))
    down_spec = pl.BlockSpec((1, tf, M), lambda e, f, on, src, pin: (
        src[e], tile(e, f, on, pin), 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda e, f, *_: (0, 0))
    itemsize = x.dtype.itemsize
    n_up = 2 if gated else 1
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, act=act, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(E, F // tf),
            in_specs=[whole((Tp, M)), whole((Tp, E))]
            + [up_spec] * n_up + [down_spec],
            out_specs=whole((Tp, M)),
            scratch_shapes=[pltpu.VMEM((Tp, M), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Tp, M), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the weight blocks double-buffered; x, out, the accumulator
            # and the [T, tf] float32 intermediates once
            vmem_limit_bytes=min(
                100 * 1024 * 1024,
                2 * (n_up + 1) * M * tf * itemsize
                + Tp * (4 * M * itemsize + 4 * M + 16 * tf + 8 * E)
                + 16 * 1024 * 1024)),
        interpret=_interpret(),
        name="moe.experts_gmm",
    )(on, src, pin, x, combine, *([wg] if gated else []), wu, wd)
    return out[:T]


# --------------------------------------------------------------------- #
# Sigmoid + bias routing, and the share of the experts a chip holds
# --------------------------------------------------------------------- #
def route_scored(x, gate_w, bias, k, renormalize=True, scaling=1.0,
                 live=None, sum_eps=0.0, scoring="sigmoid"):
    """``noaux_tc`` routing of ``x [T, M]`` through ``gate_w [M, E]``:
    float32 scores ``sigmoid(x @ gate_w)`` (``scoring="softmax"``: a
    softmax over the ``E`` outputs), the ``k`` largest of ``score + bias``
    chosen (ties to the lower index), gates the chosen SCORES (not the
    biased ones) over their sum where ``renormalize`` (plus ``sum_eps``, a
    family's guard in that denominator: LFM2's ``1e-6``), times
    ``scaling``.  Returns ``(choice [T, k] int32, gate [T, k] float32)``;
    a token that is not ``live`` has gates 0 and choice -1."""
    score = {"sigmoid": jax.nn.sigmoid,
             "softmax": lambda t: jax.nn.softmax(t, axis=-1)}[scoring]
    with jax.named_scope("moe.route"):
        scores = score(jnp.matmul(
            x.astype(jnp.float32), gate_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, choice = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        gate = jnp.take_along_axis(scores, choice, axis=1)
        if renormalize:
            total = jnp.sum(gate, axis=1, keepdims=True)
            # no add at the default: the other families' HLO stays as it was
            gate = gate / (total + sum_eps if sum_eps else total)
        gate = gate * scaling
        if live is not None:
            gate = jnp.where(live[:, None], gate, 0.0)
            choice = jnp.where(live[:, None], choice, -1)
        return choice.astype(jnp.int32), gate


def held_load(choice, first, count, real=None):
    """What a chip that holds experts ``first .. first + count - 1`` sees
    of ``choice [T, k]``: ``(local [T, k]`` — the held expert's index or
    ``count`` for a choice that fell elsewhere or on a dead token —,
    ``counts [count]`` int32, ``elsewhere`` — live choices of absent
    experts)``.  ``real``: the router's outputs ``>= real`` are no expert
    (zero-compute choices, :func:`zero_gate`) and are not counted as
    absent ones."""
    held = (choice >= first) & (choice < first + count)
    local = jnp.where(held, choice - first, count)
    counts = jnp.sum(jax.nn.one_hot(local, count + 1, dtype=jnp.int32),
                     axis=(0, 1))
    absent = (choice >= 0) & ~held
    if real is not None:
        absent = absent & (choice < real)
    return local, counts[:count], jnp.sum(absent).astype(jnp.int32)


def zero_gate(choice, gate, real):
    """The zero-compute choices of ``choice [T, k]`` — router outputs
    ``>= real``, identity experts (LongCat-Flash: a chosen one returns its
    input): ``(gates [T] float32`` — a token's chosen zero experts' gates
    summed, what multiplies the layer's input —, ``picks`` int32 — live
    choices that fell on them)``."""
    zero = choice >= real
    return jnp.sum(jnp.where(zero, gate, 0.0), axis=1), \
        jnp.sum(zero).astype(jnp.int32)


def combine_of(local, gate, count):
    """The dense ``[T, count]`` combine matrix of the held choices — what
    :func:`experts` takes (a decode step's few rows)."""
    return jnp.sum(jax.nn.one_hot(local, count + 1, dtype=jnp.float32)
                   * gate[..., None], axis=1)[:, :count]


def picks_of(combine, k):
    """:func:`route`'s dense ``combine [T, E]`` as the sorted form takes a
    call: ``(local [T, k] int32, gate [T, k] float32)`` — a row's ``k``
    largest entries, the chosen experts' gates, and their indices.  An
    entry of 0 is no choice (a dead token's whole row) and names no expert:
    ``E``, as :func:`held_load` writes ``count``."""
    gate, local = jax.lax.top_k(combine, k)
    return jnp.where(gate > 0, local, combine.shape[1]).astype(jnp.int32), \
        gate


def _grouped_kernel(tiles_ref, expert_ref, x_ref, *rest, act, gated):
    if gated:
        wg_ref, wu_ref, wd_ref, o_ref, acc_ref = rest
    else:
        wu_ref, wd_ref, o_ref, acc_ref = rest
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t < tiles_ref[0])
    def _tile():
        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        if gated:
            h = act(jnp.dot(x, wg_ref[0],
                            preferred_element_type=jnp.float32)) \
                * jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        else:
            h = act(jnp.dot(x, wu_ref[0],
                            preferred_element_type=jnp.float32))
        acc_ref[...] += jnp.dot(h.astype(x.dtype), wd_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(f == pl.num_programs(1) - 1)
        def _finish():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_layout(local, count, tile):
    """Where each (token, choice) pair's row sits once the rows are sorted
    by held expert and every expert's rows are padded to whole tiles of
    ``tile``.  From ``local [T, k]`` (:func:`held_load`): ``(dest [T, k]``
    — the pair's row, or ``rows`` for a pair no held expert takes —,
    ``source [rows]`` — the token each row holds (padding rows name token
    0) —, ``tile_expert [rows / tile]``, ``live_tiles)``; ``rows`` is the
    static worst case ``T x k`` rounded up plus one tile an expert."""
    T, k = local.shape
    rows = (-(-T * k // tile) + count) * tile
    flat = local.reshape(-1)
    counts = jnp.sum(jax.nn.one_hot(flat, count + 1, dtype=jnp.int32),
                     axis=0)[:count]
    tiles = -(-counts // tile)
    first_tile = jnp.cumsum(tiles) - tiles
    order = jnp.argsort(flat, stable=True)
    start = jnp.cumsum(counts) - counts                   # in sorted order
    sorted_e = flat[order]
    # an expert's first row less its first place in the sorted order, looked
    # up as a one-hot sum: a gather of T x k indices into a table of
    # ``count`` costs XLA's TPU compiler ~0.4 s apiece (two a layer)
    shift = jnp.sum(jax.nn.one_hot(sorted_e, count, dtype=jnp.int32)
                    * (first_tile * tile - start), axis=1)
    row = jnp.where(sorted_e < count,
                    jnp.arange(T * k, dtype=jnp.int32) + shift, rows)
    dest = jnp.zeros((T * k,), jnp.int32).at[order].set(row)
    source = jnp.zeros((rows,), jnp.int32).at[row].set(
        (order // k).astype(jnp.int32), mode="drop")
    live_tiles = jnp.sum(tiles).astype(jnp.int32)
    tile_expert = jnp.searchsorted(
        jnp.cumsum(tiles), jnp.arange(rows // tile, dtype=jnp.int32),
        side="right").astype(jnp.int32)
    return dest.reshape(T, k), source, \
        jnp.minimum(tile_expert, count - 1), live_tiles


def experts_grouped(x, local, gate, wg, wu, wd, act, tile=128):
    """The held experts' part of the routed layer on a chunk ``x [T, M]``:
    ``sum_j gate[t, j] * E_{local[t, j]}(x[t])`` over the pairs a held
    expert takes — real rows only, each expert's matrices read once a
    row tile.  ``local``/``gate [T, k]`` from :func:`held_load` /
    :func:`route_scored`; ``wg``/``wu [E, M, F]``, ``wd [E, F, M]``
    (``wg`` None: un-gated experts, as :func:`experts`)."""
    T, M = x.shape
    E, _, F = wu.shape
    gated = wg is not None
    n_up = 2 if gated else 1
    dest, source, tile_expert, live_tiles = grouped_layout(local, E, tile)
    rows = source.shape[0]
    xs = x[source]
    tf = _width_tile(F)
    nf = F // tf
    itemsize = x.dtype.itemsize
    # a dead tile names the blocks the last live tile left in place
    last = lambda tiles: jnp.maximum(tiles[0] - 1, 0)
    row_of = lambda t, tiles: jnp.minimum(t, last(tiles))
    f_of = lambda t, f, tiles: jnp.where(t < tiles[0], f, nf - 1)
    up_spec = pl.BlockSpec((1, M, tf), lambda t, f, tiles, ex: (
        ex[row_of(t, tiles)], 0, f_of(t, f, tiles)))
    down_spec = pl.BlockSpec((1, tf, M), lambda t, f, tiles, ex: (
        ex[row_of(t, tiles)], f_of(t, f, tiles), 0))
    row_spec = pl.BlockSpec((tile, M),
                            lambda t, f, tiles, ex: (row_of(t, tiles), 0))
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, act=act, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // tile, nf),
            in_specs=[row_spec] + [up_spec] * n_up + [down_spec],
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((tile, M), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, M), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(
                100 * 1024 * 1024,
                2 * (n_up + 1) * M * tf * itemsize
                + tile * (8 * M + 16 * tf) + 16 * 1024 * 1024)),
        interpret=_interpret(),
        name="moe.experts_grouped",
    )(live_tiles[None], tile_expert, xs, *([wg] if gated else []), wu, wd)
    # each token gathers its pairs' rows back, weighted by their gates
    # (a pair no held expert took reads the zero row appended here)
    out = jnp.concatenate([out, jnp.zeros((1, M), out.dtype)])
    return jnp.einsum("tkm,tk->tm", out[dest], gate,
                      preferred_element_type=jnp.float32).astype(x.dtype)
