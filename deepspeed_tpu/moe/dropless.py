"""Dropless routed experts — the inference path of a sparse-expert model
(OLMoE / Mixtral-style: softmax over all experts, top-k, every chosen
(token, expert) pair computed, whatever the imbalance).

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

At the token counts the serving programs produce (a decode step over the
slots, one prefill chunk of <= 128 tokens) the layer is bound by
streaming the touched experts' weights, not by the arithmetic — on a v5e
64 rows through an expert's three matrices take about the 15 us their
12.6 MB take to arrive — so the zero-weight rows ride for free, and no
sort, gather or scatter stands around the matmuls: the weighted combine
is the kernel's own accumulation.  Rows are independent: what a dead lane
holds changes no live lane's bits.

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
    for t in (1024, 512, 256, 128):
        if F % t == 0:
            return t
    return F


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
