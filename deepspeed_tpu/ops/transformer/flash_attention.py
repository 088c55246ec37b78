"""Pallas flash attention (fwd + bwd) — the centerpiece training kernel.

TPU-native equivalent of the reference's fused transformer attention kernels
(``csrc/transformer/*.cu`` softmax/dropout/gemm stack behind
``DeepSpeedTransformerLayer``, and the inference ``softmax_context`` op,
``csrc/transformer/inference/csrc/pt_binding.cpp:1934-``).  Instead of
separate gemm+softmax kernels stitched by a C++ scheduler, this is one
online-softmax kernel: O(S) memory, no S×S materialization, MXU-tiled.

Layout: inputs [B, S, H, D] (model-native); kernel operates in [B, H, S, D].
GQA is handled in the BlockSpec index maps (kv head = h * KVH // H) — no
jnp.repeat materialization.

Each kernel keeps one block of its own axis resident and walks the other
axis itself: an in-kernel loop over square tiles that stops at the causal
diagonal, masks only the tiles the diagonal crosses (and a ragged tail), and
carries the running statistics as values (:func:`tile_plan` decides the
tiles and is where the loop bounds come from).  The backward pass uses the
saved LSE (log-sum-exp) rows and is ONE kernel, ``attn.flash_dq_dkv``: a key
block resident, the scores, the probabilities and ``ds`` of its slab of
queries computed once, and dq, dk and dv all taken from them — five matmuls
and one pass of the VPU over the score slab, where the flash-attention-2
decomposition (a kernel accumulating dq over key tiles, one accumulating
dk and dv over query tiles) makes seven and two.  dq is summed over the key
blocks in VMEM, so a head's whole query axis has to fit there
(``_DQ_SUM_BYTES``, decided in :func:`tile_plan` from the shape); a longer
head takes that pair, ``attn.flash_dq`` and ``attn.flash_dkv``.
"""

import functools
import os as _os
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.monitor.trace import span


def _env_block(name):
    v = _os.environ.get(name)
    return int(v) if v else None


# The GRID's blocks.  Each kernel keeps one block of its own axis resident
# (a query block in ``attn.flash_fwd``, a key block in the backward's
# ``attn.flash_dq_dkv``; of the pair a long head falls back to,
# ``attn.flash_dq`` a query block and ``attn.flash_dkv`` a key block) and is
# handed the opposite axis in MAJOR blocks, inside which it walks the causal
# triangle itself (:func:`tile_plan`).  Skipping
# the masked half with the GRID does not pay on a v5e: every step along the
# walked axis is another softmax pass (two lane reductions a row, a rescale
# of the accumulator, the statistics' stores), and at 512 x 512 grid blocks
# the forward took 1.74 ms where it took 1.21 at 512 x 2048 with nothing
# skipped (PERF.md §6, PR 32).  So the major block is a whole head's K and V
# at the lengths trained on.  Unset, :func:`tile_plan` picks per kernel.
DEFAULT_BLOCK_Q = _env_block("DSTPU_FLASH_BLOCK_Q")
DEFAULT_BLOCK_K = _env_block("DSTPU_FLASH_BLOCK_K")
DEFAULT_BLOCK_Q_BWD = _env_block("DSTPU_FLASH_BLOCK_Q_BWD")
DEFAULT_BLOCK_K_BWD = _env_block("DSTPU_FLASH_BLOCK_K_BWD")
NEG_INF = -1e30
# LSE/delta row vectors carry a small broadcast trailing dim: Mosaic requires
# the last block dim be 128-divisible OR equal to the full array dim, so an
# 8-lane array keeps blocks legal while costing 16x less HBM than 128 lanes
# (these are saved residuals when attention outputs are remat-saveable).
LSE_LANES = 8


def _interpret():
    return jax.default_backend() == "cpu"


def pallas_supported():
    """False only under ``DSTPU_DISABLE_FLASH=1``, the switch the parity
    tests use to get the XLA reference.  There is no backend to probe:
    TPU compiles the kernels to Mosaic, CPU interprets them, and
    ``accelerator.real_accelerator`` rejects anything else."""
    return _os.environ.get("DSTPU_DISABLE_FLASH") != "1"


# --------------------------------------------------------------------- #
# The tile plan
# --------------------------------------------------------------------- #
# The side of the square score tile the kernels walk, and the most rows of
# the walked axis one grid step holds in VMEM.
_TILE = 512
_MAJOR = 2048
# The fused backward sums a head's whole dq in VMEM, float32, while it goes
# through the key blocks: the most bytes of it.  2 MiB is 8192 x 64 or
# 4096 x 128; a longer head takes the pair of kernels, dq from its own.
_DQ_SUM_BYTES = 2 * 2 ** 20


class TilePlan(NamedTuple):
    """How one kernel covers the ``[q_len, kv_len]`` score square: the
    resident block of its own axis against ``tile_q x tile_k`` tiles of the
    walked axis' major block.  The kernels take their walks' bounds from
    :meth:`segments`; :meth:`counts` sums the same bounds over the grid."""
    kernel: str                 # "fwd" | "dq" | "dkv" | "dq_dkv"
    tile_q: int
    tile_k: int
    block_q: int                # grid blocks: the resident one is its tile
    block_k: int
    q_len: int
    kv_len: int
    causal: bool

    @property
    def walks_q(self):
        return self.kernel in ("dkv", "dq_dkv")

    @property
    def _axes(self):
        """(tile, major block, length) of the walked axis, then the
        resident tile and length."""
        if self.walks_q:
            return (self.tile_q, self.block_q, self.q_len,
                    self.tile_k, self.kv_len)
        return (self.tile_k, self.block_k, self.kv_len,
                self.tile_q, self.q_len)

    @property
    def n_resident(self):
        _, _, _, res_tile, res_len = self._axes
        return pl.cdiv(res_len, res_tile)

    @property
    def n_major(self):
        _, major, length, _, _ = self._axes
        return pl.cdiv(length, major)

    @property
    def ragged(self):
        """The walked axis ends inside a tile: that tile masks its tail."""
        tile, _, length, _, _ = self._axes
        return length % tile != 0

    def segments(self, i_res, i_major, xp=jnp):
        """``[(lo, hi, masked), ...]``: the runs of tiles of major block
        ``i_major`` that resident block ``i_res`` folds in, in order.
        Tiles the causal diagonal crosses and a ragged tail tile are
        ``masked``; tiles past the diagonal are in no run.  Indices may be
        traced (``xp=jnp``, inside a kernel) or plain ints (``xp=np``)."""
        tile, major, length, res_tile, _ = self._axes
        n = major // tile
        base = i_major * major
        full = xp.clip((length - base) // tile, 0, n)       # wholly valid
        valid = xp.clip(-((base - length) // tile), 0, n)   # any row valid
        if not self.causal:
            tail = [(full, valid, True)] if self.ragged else []
            return [(0, full, False)] + tail
        first, last = i_res * res_tile, i_res * res_tile + res_tile - 1
        if not self.walks_q:
            # key tile j is seen if its first column <= the last query row,
            # and needs no mask if its last column <= the first query row
            seen = xp.clip(-((base - last - 1) // tile), 0, valid)
            under = xp.clip((first + 1 - base) // tile, 0,
                            xp.minimum(full, seen))
            return [(0, under, False), (under, seen, True)]
        # query tile j sees the key block if its last row >= the first key,
        # and needs no mask if its first row >= the last key
        lo = xp.clip((first - base) // tile, 0, valid)
        clear = -((base - last) // tile)
        diag_end = xp.clip(clear, lo, valid)
        segs = [(lo, diag_end, True), (xp.clip(clear, lo, full), full, False)]
        if self.ragged:
            segs.append((xp.maximum(full, diag_end), valid, True))
        return segs

    def _grid_walks(self):
        """:meth:`segments` of every grid step of a head, as plain ints."""
        return [tuple((int(lo), int(hi), m)
                      for lo, hi, m in self.segments(i, im, xp=np))
                for i in range(self.n_resident) for im in range(self.n_major)]

    def walks(self):
        """The distinct walks: what the kernels specialise their bodies
        on."""
        return sorted(set(self._grid_walks()))

    def counts(self):
        """The plan event's fields, a head: tiles the kernel runs, those of
        them that pay the mask, and the tiles of the whole square."""
        runs = [(max(0, hi - lo), m)
                for walk in self._grid_walks() for lo, hi, m in walk]
        return {"tile_q": self.tile_q, "tile_k": self.tile_k,
                "tiles_run": sum(n for n, _ in runs),
                "tiles_masked": sum(n for n, m in runs if m),
                "tiles_square": pl.cdiv(self.q_len, self.tile_q)
                * pl.cdiv(self.kv_len, self.tile_k)}


def tile_plan(kernel, q_len, kv_len, head_dim, dtype, causal,
              block_q=None, block_k=None):
    """The one place the tiling is decided, from what the call sees.
    ``block_q`` / ``block_k`` are the grid's blocks where the caller (or a
    ``DSTPU_FLASH_BLOCK_*`` variable) fixes them: the resident axis' block
    is that kernel's tile, the walked axis' block the major block.

    The backward's FORM is decided here too: asked for ``"dq_dkv"``, the
    fused kernel, the answer is the pair's ``"dkv"`` plan where a head's
    float32 dq, in whole major blocks, is more than ``_DQ_SUM_BYTES`` —
    by the query length, the head size and the dtype (it sets the major
    block), and by nothing else (:func:`backward_plans`)."""
    walks_q = kernel in ("dkv", "dq_dkv")
    res_blk, walk_blk = (block_k, block_q) if walks_q else (block_q, block_k)
    res_len, walk_len = (kv_len, q_len) if walks_q else (q_len, kv_len)
    # two walked operands, double-buffered, stay within ~4 MB of VMEM
    row_bytes = head_dim * jnp.dtype(dtype).itemsize
    major = min(walk_blk or (_MAJOR if row_bytes <= 512 else _MAJOR // 2),
                walk_len)
    res = min(res_blk or _TILE, res_len)
    # the largest tile that divides the major block; else the block whole
    tile = next((t for t in (_TILE, _TILE // 2, _TILE // 4)
                 if major % t == 0), major)
    tile_q, tile_k = (tile, res) if walks_q else (res, tile)
    block_q, block_k = (major, res) if walks_q else (res, major)
    if (kernel == "dq_dkv"
            and pl.cdiv(q_len, major) * major * head_dim * 4 > _DQ_SUM_BYTES):
        kernel = "dkv"      # no room for the head's dq: the pair's half
    return TilePlan(kernel, tile_q, tile_k, block_q, block_k,
                    q_len, kv_len, bool(causal))


def _planned_call(plan, name, kernel, **kw):
    """``pl.pallas_call`` under the plan's event: one
    ``dstpu.kernel.tile_plan`` span a traced call, the counts as its args."""
    # the fused backward sums dq over the resident (key) axis too
    resident = "arbitrary" if plan.kernel == "dq_dkv" else "parallel"
    call = pl.pallas_call(
        kernel, name=name, interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", resident, "arbitrary")),
        **kw)

    def run(*operands):
        with span("dstpu.kernel.tile_plan", kernel=name, **plan.counts()):
            return call(*operands)
    return run


def _for_each_walk(plan, i_res, i_major, fold, nothing=None):
    """Run ``fold(first, pieces)`` for THIS grid step's walk: tiles
    ``first ..`` of the major block as one slab, ``pieces`` its runs
    ``(a, b, masked)`` in tiles from ``first`` — or ``nothing()`` where
    the block lies wholly past the diagonal.  A walk's bounds depend on
    the grid step, a slab's width must not: every distinct walk of the
    plan (a handful — the diagonal's position inside a major block) is
    traced as its own straight-line body and the step's own is picked by
    its bounds.  One softmax pass (or one set of matmuls) a slab is why a
    slab and not a ``fori_loop`` over its tiles: a trip of such a loop
    pays two lane reductions a row and a rescale of the accumulator for
    512 columns, and the forward measured 1.45x this one (PERF.md §6,
    PR 32)."""
    mine = plan.segments(i_res, i_major)
    for walk in plan.walks():
        runs = sorted((lo, hi, m) for lo, hi, m in walk if hi > lo)
        hit = functools.reduce(
            jnp.logical_and,
            [c for (lo, hi, _), (wlo, whi, _) in zip(mine, walk)
             for c in (lo == wlo, hi == whi)])
        if runs:
            first = runs[0][0]
            pieces = [(lo - first, hi - first, m) for lo, hi, m in runs]
            pl.when(hit)(functools.partial(fold, first, pieces))
        elif nothing is not None:
            pl.when(hit)(nothing)


def _slab_rows(plan, ref, base, first, pieces):
    """Rows ``first .. first + width`` (in tiles) of a walked operand's
    major block, whose first row is position ``base``.  Rows past the
    axis' length are out-of-bounds reads — undefined, and garbage x
    0-probability still poisons a matmul with NaN — so a ragged plan's
    tail run zeroes them."""
    tile, _, length, _, _ = plan._axes
    x = ref[0, 0, first * tile:(first + pieces[-1][1]) * tile, :]
    if plan.ragged and pieces[-1][2]:
        pos = base + first * tile + jax.lax.broadcasted_iota(
            jnp.int32, (x.shape[0], 1), 0)
        x = jnp.where(pos < length, x, jnp.zeros_like(x))
    return x


def _mask_runs(plan, x, pieces, res0, walk0, fill):
    """``x``: a slab's scores, the resident block's positions (from
    ``res0``) down the rows and the walked axis' (from ``walk0``) along
    the lanes.  The runs the diagonal crosses (and a ragged tail) are
    masked to ``fill``; the others pass untouched — no iota, no compare,
    no select."""
    tile, _, length, _, _ = plan._axes
    res = res0 + jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    parts = []
    for a, b, masked in pieces:
        part = x[:, a * tile:b * tile] if len(pieces) > 1 else x
        if masked:
            walk = walk0 + a * tile + jax.lax.broadcasted_iota(
                jnp.int32, (1, part.shape[1]), 1)
            tail = walk < length
            # causal is top-left aligned: query i sees keys <= i
            qpos, kpos = (walk, res) if plan.walks_q else (res, walk)
            mask = qpos >= kpos if plan.causal else tail
            if plan.causal and plan.ragged:
                mask = mask & tail
            part = jnp.where(mask, part, fill)
        parts.append(part)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _dot(a, b, contract):
    # operands stay bf16 — the MXU accumulates in fp32 via
    # preferred_element_type; casting inputs to fp32 would halve matmul
    # throughput
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))          # a @ b.T
_NN = ((1,), (0,))          # a @ b
_TN = ((0,), (0,))          # a.T @ b


def _scaled(x, scale):
    return x if scale == 1.0 else x * scale   # 1.0: folded into q outside


# --------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------- #
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scr, scale, plan):
    iq, ik = pl.program_id(1), pl.program_id(2)
    base = ik * plan.block_k

    def finish(m, l, acc):
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc / safe_l).astype(o_ref.dtype)
        # Mosaic wants output blocks tiled on the last two dims, so the
        # LSE row rides a small broadcast trailing dim
        lse_ref[0, 0] = jnp.broadcast_to(m + jnp.log(safe_l),
                                         lse_ref.shape[2:])

    def fold(first, pieces):
        k = _slab_rows(plan, k_ref, base, first, pieces)
        v = _slab_rows(plan, v_ref, base, first, pieces)
        s = _scaled(_dot(q_ref[0, 0], k, _NT), scale)     # [tq, w] f32
        s = _mask_runs(plan, s, pieces, iq * plan.tile_q,
                       base + first * plan.tile_k, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        if scr:
            m_prev = m_scr[:, 0:1]
            m = jnp.maximum(m_prev, m)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        acc = _dot(p.astype(v.dtype), v, _NN)
        if not scr:
            return finish(m, l, acc)
        corr = jnp.exp(m_prev - m)
        m_scr[:] = jnp.broadcast_to(m, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_scr[:, 0:1] * corr + l, l_scr.shape)
        acc_scr[:] = acc_scr[:] * corr + acc

    # a single major block is one softmax pass; more of them merge online
    # through scratch, one rescale a block
    if scr:
        m_scr, l_scr, acc_scr = scr

        @pl.when(ik == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    _for_each_walk(plan, iq, ik, fold)

    if scr:
        @pl.when(ik == plan.n_major - 1)
        def _finish():
            finish(m_scr[:, 0:1], l_scr[:, 0:1], acc_scr[:])


def _index_maps(H, KVH, walks_q=False):
    """(own-head map of the query axis, kv-head map of the key axis) for a
    ``(B*H, resident, major)`` grid; GQA lives here (kv head =
    h * KVH // H)."""
    def q_map(bh, i, j):
        return (bh // H, bh % H, j if walks_q else i, 0)

    def kv_map(bh, i, j):
        return (bh // H, (bh % H) * KVH // H, i if walks_q else j, 0)
    return q_map, kv_map


def _fwd(q, k, v, scale, causal, block_q, block_k):
    B, H, S, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    plan = tile_plan("fwd", S, Sk, D, q.dtype, causal, block_q, block_k)
    q_map, kv_map = _index_maps(H, KVH)
    merged = [] if plan.n_major == 1 else [
        pltpu.VMEM((plan.block_q, 128), jnp.float32),
        pltpu.VMEM((plan.block_q, 128), jnp.float32),
        pltpu.VMEM((plan.block_q, D), jnp.float32)]
    out, lse = _planned_call(
        plan, "attn.flash_fwd",
        functools.partial(_fwd_kernel, scale=scale, plan=plan),
        grid=(B * H, plan.n_resident, plan.n_major),
        in_specs=[
            pl.BlockSpec((1, 1, plan.block_q, D), q_map),
            pl.BlockSpec((1, 1, plan.block_k, D), kv_map),
            pl.BlockSpec((1, 1, plan.block_k, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, plan.block_q, D), q_map),
            pl.BlockSpec((1, 1, plan.block_q, LSE_LANES), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, LSE_LANES), jnp.float32),
        ],
        scratch_shapes=merged,
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------- #
def _accumulate(plan, i_res, i_major, refs, scr, fold):
    """The backward kernels' sums over major blocks: with one block a walk
    writes its result; with more, scratch adds them up (a block past the
    diagonal adds nothing) and the last grid step writes."""
    if not scr:
        def write(first, pieces):
            for ref, x in zip(refs, fold(first, pieces)):
                ref[0, 0] = x.astype(ref.dtype)

        def zero():
            for ref in refs:
                ref[0, 0] = jnp.zeros(ref.shape[2:], ref.dtype)
        return _for_each_walk(plan, i_res, i_major, write, zero)

    @pl.when(i_major == 0)
    def _init():
        for acc in scr:
            acc[:] = jnp.zeros_like(acc)

    def add(first, pieces):
        for acc, x in zip(scr, fold(first, pieces)):
            acc[:] += x
    _for_each_walk(plan, i_res, i_major, add)

    @pl.when(i_major == plan.n_major - 1)
    def _finish():
        for ref, acc in zip(refs, scr):
            ref[0, 0] = acc[:].astype(ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *scr, scale, plan):
    iq, ik = pl.program_id(1), pl.program_id(2)
    base = ik * plan.block_k

    def fold(first, pieces):
        k = _slab_rows(plan, k_ref, base, first, pieces)
        v = _slab_rows(plan, v_ref, base, first, pieces)
        s = _scaled(_dot(q_ref[0, 0], k, _NT), scale)     # [tq, w]
        p = jnp.exp(s - lse_ref[0, 0][:, 0:1])
        p = _mask_runs(plan, p, pieces, iq * plan.tile_q,
                       base + first * plan.tile_k, 0.0)
        dp = _dot(do_ref[0, 0], v, _NT)
        ds = _scaled(p * (dp - delta_ref[0, 0][:, 0:1]), scale)
        return [_dot(ds.astype(k.dtype), k, _NN)]

    _accumulate(plan, iq, ik, [dq_ref], scr, fold)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, scale, plan):
    """A key block against the query tiles from its diagonal on.  Scores
    are held TRANSPOSED, ``[keys, queries]``: ``p.T @ do`` and ``ds.T @ q``
    are then plain matmuls (no transposed operand), and the per-query
    ``lse`` / ``delta`` are row vectors that broadcast along sublanes.

    As ``attn.flash_dq_dkv`` (the plan's kernel ``dq_dkv``) the same pass
    over the scores gives dq as well: ``k.T @ ds.T`` is the slab's share of
    dq, transposed — a plain matmul again, what is transposed for it is the
    key block ``[tile, D]`` and not the score slab — summed over the key
    blocks in ``dq_sum`` ``[major blocks, D, queries]``, which covers the
    head's whole query axis, and turned and stored once a head."""
    ik, iq = pl.program_id(1), pl.program_id(2)
    base = iq * plan.block_q
    fused = plan.kernel == "dq_dkv"
    if fused:
        dq_ref, dk_ref, dv_ref, *scr, dq_sum = rest
    else:
        dk_ref, dv_ref, *scr = rest

    def fold(first, pieces):
        k, v = k_ref[0, 0], v_ref[0, 0]                   # [tk, d]
        if fused and plan.kv_len % plan.tile_k:
            # dq is summed over the keys: rows past the last key are
            # out-of-bounds reads, and 0 x garbage must stay finite
            live = ik * plan.tile_k + jax.lax.broadcasted_iota(
                jnp.int32, (k.shape[0], 1), 0) < plan.kv_len
            k, v = jnp.where(live, k, 0.0), jnp.where(live, v, 0.0)
        q = _slab_rows(plan, q_ref, base, first, pieces)  # [w, d]
        do = _slab_rows(plan, do_ref, base, first, pieces)
        lanes = slice(first * plan.tile_q,
                      (first + pieces[-1][1]) * plan.tile_q)
        lse, delta = lse_ref[0, 0, :, lanes], delta_ref[0, 0, :, lanes]
        if plan.ragged and pieces[-1][2]:
            # past the last query these too are out-of-bounds reads, and
            # 0 x garbage must stay finite
            live = base + lanes.start + jax.lax.broadcasted_iota(
                jnp.int32, lse.shape, 1) < plan.q_len
            lse, delta = jnp.where(live, lse, 0.0), jnp.where(live, delta, 0.0)
        st = _scaled(_dot(k, q, _NT), scale)              # [tk, w]
        pt = _mask_runs(plan, jnp.exp(st - lse), pieces, ik * plan.tile_k,
                        base + first * plan.tile_q, 0.0)
        dv = _dot(pt.astype(do.dtype), do, _NN)
        dst = _scaled(pt * (_dot(v, do, _NT) - delta), scale).astype(q.dtype)
        if fused:
            dq_sum[iq, :, lanes] += _dot(k, dst, _TN)     # [d, w]
        return [_dot(dst, q, _NN), dv]

    if fused:
        @pl.when((ik == 0) & (iq == 0))
        def _init():
            dq_sum[:] = jnp.zeros_like(dq_sum)

    _accumulate(plan, ik, iq, [dk_ref, dv_ref], scr, fold)

    if fused:
        @pl.when((ik == plan.n_resident - 1) & (iq == plan.n_major - 1))
        def _finish():
            for im in range(plan.n_major):
                rows = slice(im * plan.block_q, (im + 1) * plan.block_q)
                dq_ref[0, 0, rows, :] = dq_sum[im].T.astype(dq_ref.dtype)


def backward_plans(q_len, kv_len, head_dim, dtype, causal,
                   block_q=None, block_k=None):
    """The plans of the backward's kernels: the fused one where
    :func:`tile_plan` has room for a head's dq, else the pair."""
    shape = q_len, kv_len, head_dim, dtype, causal, block_q, block_k
    plan = tile_plan("dq_dkv", *shape)
    return [plan] if plan.kernel == "dq_dkv" else [tile_plan("dq", *shape),
                                                   plan]


def _bwd(scale, causal, block_q, block_k, block_q_bwd, block_k_bwd, res, do):
    q, k, v, out, lse = res
    B, H, S, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def call(plan):
        q_map, kv_map = _index_maps(H, KVH, plan.walks_q)
        q_spec = pl.BlockSpec((1, 1, plan.block_q, D), q_map)
        kv_spec = pl.BlockSpec((1, 1, plan.block_k, D), kv_map)
        # per query row: a lane-dense row for the kernel that walks the
        # queries, a column for the one that holds them resident
        if plan.walks_q:
            kernel = _bwd_dkv_kernel
            stats = lse[..., 0][:, :, None, :], delta[:, :, None, :]
            stat_spec = pl.BlockSpec(
                (1, 1, 1, plan.block_q),
                lambda bh, i, j: (bh // H, bh % H, 0, j))
            # dk/dv per QUERY head (reduced over the group below): the
            # key axis' map with KVH = H
            outs = [(Sk, plan.block_k, _index_maps(H, H, True)[1])] * 2
        else:
            kernel = _bwd_dq_kernel
            stats = lse, jnp.broadcast_to(delta[..., None], lse.shape)
            stat_spec = pl.BlockSpec((1, 1, plan.block_q, LSE_LANES), q_map)
            outs = [(S, plan.block_q, q_map)]
        sums = [] if plan.n_major == 1 else [
            pltpu.VMEM((rows, D), jnp.float32) for _, rows, _ in outs]
        if plan.kernel == "dq_dkv":
            # the head's whole dq, in whole major blocks: one block of
            # the output, written back once a head
            head = plan.n_major * plan.block_q
            outs = [(head, head, lambda bh, i, j: (bh // H, bh % H, 0, 0)),
                    *outs]
            sums.append(pltpu.VMEM((plan.n_major, D, plan.block_q),
                                   jnp.float32))
        return _planned_call(
            plan, f"attn.flash_{plan.kernel}",
            functools.partial(kernel, scale=scale, plan=plan),
            grid=(B * H, plan.n_resident, plan.n_major),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
            out_specs=[pl.BlockSpec((1, 1, rows, D), out_map)
                       for _, rows, out_map in outs],
            out_shape=[jax.ShapeDtypeStruct((B, H, n, D), q.dtype)
                       for n, _, _ in outs],
            scratch_shapes=sums,
        )(q, k, v, do, *stats)

    dq, dk, dv = [x for plan in backward_plans(
        S, Sk, D, q.dtype, causal, block_q_bwd, block_k_bwd)
        for x in call(plan)]
    if KVH != H:
        rep = H // KVH
        dk = dk.reshape(B, KVH, rep, Sk, D).sum(axis=2)
        dv = dv.reshape(B, KVH, rep, Sk, D).sum(axis=2)
    return dq[:, :, :S], dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------- #
# Public API
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k,
                block_q_bwd, block_k_bwd):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k,
                    block_q_bwd, block_k_bwd):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k)
    # tag residuals so a remat policy can elect to SAVE them — without the
    # tags, any rematerialized layer re-runs the whole forward kernel inside
    # the backward pass just to regenerate lse.  What is tagged is each
    # residual's lane-dense form: out as [B, S, H*D] (the layout the model
    # takes it in anyway) and lse as [B, H, S] — saved as the kernel writes
    # them, [.., S, D=64] and [.., S, 8 lanes] are padded to 128 lanes in
    # HBM, 2x and 16x their bytes (2.4 GB where 0.5 GB will do at
    # opt-1.3b/4096 tokens).  The residuals below are derived from the
    # tagged values, so a backward that has them replays a transpose and a
    # broadcast, not the kernel.
    from jax.ad_checkpoint import checkpoint_name
    B, H, S, D = out.shape
    flat = checkpoint_name(
        out.transpose(0, 2, 1, 3).reshape(B, S, H * D), "flash_out")
    out = flat.reshape(B, S, H, D).transpose(0, 2, 1, 3)
    lse = jnp.broadcast_to(
        checkpoint_name(lse[..., 0], "flash_lse")[..., None], lse.shape)
    return out, (q, k, v, out, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, causal=True, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    block_q_bwd=None, block_k_bwd=None):
    """Flash attention on [B, S, H, D] tensors (model-native layout).

    ``k``/``v`` may have fewer heads (GQA).  Returns [B, S, H, D].
    ``block_q`` / ``block_k`` are the forward grid's blocks, ``block_q_bwd``
    / ``block_k_bwd`` the backward kernels' (default: DSTPU_FLASH_BLOCK_*);
    left unset, :func:`tile_plan` picks them per kernel.
    """
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    if block_q_bwd is None:
        block_q_bwd = DEFAULT_BLOCK_Q_BWD
    if block_k_bwd is None:
        block_k_bwd = DEFAULT_BLOCK_K_BWD
    # fold the softmax scale into q OUTSIDE the kernel when it is a power
    # of two (D a power of 4, e.g. D=64 → 0.125): saves a [bq, bk] f32
    # multiply per score block in fwd AND bwd, and the multiply is EXACT in
    # q.dtype (mantissa untouched; the chain rule through it restores dq's
    # scale automatically).  Other scales (D=128 → 2^-3.5) stay in-kernel
    # in f32 — pre-scaling bf16 q would round every logit.
    if scale > 0 and float(np.log2(scale)).is_integer():
        qt = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)
        kernel_scale = 1.0
    else:
        qt = q.transpose(0, 2, 1, 3)
        kernel_scale = float(scale)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_bhsd(qt, kt, vt, kernel_scale, bool(causal),
                      *(b and int(b) for b in (block_q, block_k,
                                                block_q_bwd, block_k_bwd)))
    return out.transpose(0, 2, 1, 3)


# parity alias for the reference inference op name
softmax_context = flash_attention
