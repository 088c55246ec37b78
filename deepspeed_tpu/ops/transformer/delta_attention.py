"""Gated delta-rule linear attention on a MATRIX STATE a slot (Kimi Delta
Attention, arXiv:2510.26692; ``models/solar_open2.py``).

A head keeps ``S [d_k, d_v]`` in float32 and no row a position.  With a
log-decay ``g_t [d_k]`` (a key CHANNEL's own: ``alpha_t = exp(g_t)`` in (0,
1)) and a step size ``beta_t`` (in (0, 2): past 1 the correction overshoots,
the negative-eigenvalue half)::

    Sb_t = Diag(alpha_t) S_{t-1}
    S_t  = Sb_t + beta_t k_t (v_t - Sb_t^T k_t)^T
    o_t  = S_t^T q_t

That recurrence, one position after the other, is the plain-XLA path here
(:func:`_scan_xla`, :func:`_step_xla`) and what the tests hold the kernels
to.  The pool is ``[KDA layers, state rows, heads, d_k, d_v]`` float32 — row
0 the trash row, a slot's row the last entry of its table row
(``paging.SlotPages``) — and both kernels take it in and hand it back
ALIASED: a dispatch touches its own rows' blocks and copies nothing else.

**A prefill chunk** (:func:`chunk_scan`, ``kda.chunk_scan``): ``T``
consecutive positions of one slot, ``grid = (heads / 4, T / 64)``: a grid
step takes FOUR heads' 64-row blocks (:func:`_chunk_heads`: of the shapes
alone) and emits their work stage by stage in one body, the states the
four carry in VMEM across their blocks.  A block is the chunked form: with
``G_i = sum_{j <= i} g_j`` inside the block,

    A_ij = beta_i sum_c k_ic k_jc e^{G_ic - G_jc}    (j < i)
    (I + A) V' = beta (v - (k e^G) S_in)
    O     = (q e^G) S_in + tril(sum_c q_ic k_jc e^{G_ic - G_jc}) V'
    S_out = Diag(e^{G_last}) S_in + (k e^{G_last - G})^T V'

Every exponent is a DIFFERENCE ``G_i - G_j`` with ``j <= i`` (at most 0):
between two 16-row sub-blocks it is split at the later one's first row
(``e^{G_i - G_ref} e^{G_ref - G_j}``, both at most 1), inside a sub-block it
is formed a key row at a time, and no ``e^{-G}`` is ever taken alone — a
channel may decay as fast as it likes.  ``(I + A)`` is solved by forward
substitution, a column at a time inside a sub-block and by one product
between them; no inverse is formed.  Rows past the chunk's last REAL row get
``g = 0`` and ``beta = 0``: they leave the state exactly as it is, and
blocks wholly past it are skipped.

**A decode step** (:func:`decode_step`, ``kda.decode_step``): one token a
lane, ``grid = (lanes, heads / 8)``; the lane's state row goes through VMEM
once — read, decayed, corrected by the rank-one term, read out, written
back: 2 x 4 MiB a lane and layer at 64 heads of 128, which is what the step
costs.  A DEAD lane (its table row on the trash row) hands its row back as
it found it.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import _interpret

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 64               # rows of a head a grid step of the chunk kernel takes
SUB = 16                 # ... in sub-blocks of these
_CHUNK_HEADS = (4, 2, 1)  # ... and heads: the most of these that divide them
_STEP_HEADS = 8          # heads a grid step of the decode kernel takes


# --------------------------------------------------------------------- #
# The recurrence, in plain XLA
# --------------------------------------------------------------------- #
def _step_xla(state, q, k, v, g, beta):
    """One position of every head: ``state [..., d_k, d_v]``, ``q`` / ``k``
    / ``g [..., d_k]``, ``v [..., d_v]``, ``beta [...]`` (float32).  Returns
    ``(state, o [..., d_v])``."""
    decayed = jnp.exp(g)[..., None] * state
    seen = jnp.einsum("...kv,...k->...v", decayed, k, precision=HIGHEST)
    step = beta[..., None] * (v - seen)
    state = decayed + k[..., None] * step[..., None, :]
    return state, jnp.einsum("...kv,...k->...v", state, q, precision=HIGHEST)


def _scan_xla(state, q, k, v, g, beta):
    """``T`` positions one after the other: ``q`` / ``k`` / ``v`` / ``g [T,
    H, d]``, ``beta [T, H]``, ``state [H, d_k, d_v]``."""
    def step(s, x):
        return _step_xla(s, *x)
    return jax.lax.scan(step, state, (q, k, v, g, beta))


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


# --------------------------------------------------------------------- #
# The chunk kernel
# --------------------------------------------------------------------- #
def _column(row):
    """``row [1, n]`` as a column ``[n, 1]``."""
    n = row.shape[1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=HIGHEST,
                               preferred_element_type=jnp.float32)


_NT = (((1,), (1,)), ((), ()))       # a @ b.T
_TN = (((0,), (0,)), ((), ()))       # a.T @ b


def _chunk_heads(H, D):
    """The heads a grid step of the chunk kernel takes: the most of the list
    that divide ``H``, where a head's lanes of a row are whole lane tiles —
    of the shapes alone."""
    return max(n for n in _CHUNK_HEADS
               if n == 1 or (H % n == 0 and D % 128 == 0))


def _chunk_kernel(meta, q_ref, k_ref, v_ref, g_ref, b_ref, s_in, o_ref,
                  s_out, s_scr, g_scr, k_scr, x_scr, o_scr, p_scr):
    """``meta``: layer, state row, fresh (the state starts at zero), real
    rows.  The row blocks hold ``heads`` heads side by side (``[BLOCK,
    heads * d]``); inside, the heads are the LEADING axis of every value
    (``[heads, rows, d]``), so each line below is every head's work at that
    point of the block: heads share nothing, and one head's waits — a
    product's six passes, a key row's lane sums, the substitution's chain —
    are filled with another's work.  Only the products go a head at a time.
    Scratch, ``[heads, ...]``: ``s_scr [d_k, d_v]`` the state between the
    blocks; ``g_scr`` / ``k_scr [BLOCK, d_k]`` the block's ``G`` and keys,
    read a row at a time; ``x_scr [BLOCK, d_v]`` the right-hand side that
    forward substitution turns into ``V'``; ``o_scr [BLOCK, d_v]`` ``(q e^G)
    S_in``; ``p_scr [BLOCK, BLOCK]`` the scores ``V'`` is read out through.
    Values are re-read from the scratch where they are used: nothing
    ``[BLOCK, d]`` is held across the body."""
    n, c = pl.program_id(0), pl.program_id(1)
    C = q_ref.shape[0]
    heads, D = s_scr.shape[:2]
    hs = range(heads)
    f32, bf16 = jnp.float32, jnp.bfloat16
    # rows of a [BLOCK, heads * d] block, the heads in front
    of_heads = lambda ref, rows=slice(None): jnp.stack(
        [ref[rows, h * D:(h + 1) * D] for h in hs])
    a_head = lambda f, *xs: jnp.stack([f(*(x[h] for x in xs)) for h in hs])

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.where(meta[2] != 0, 0.0, s_in[...])

    @pl.when(c * C >= meta[3])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c * C < meta[3])
    def _():
        real = c * C + jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) \
            < meta[3]
        lanes = jax.lax.broadcasted_iota(jnp.int32, (SUB, b_ref.shape[1]), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, 1, 1), 0)
        tri = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
               <= jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)).astype(bf16)
        col = jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0)

        # G = tri @ g, float32: ``tri`` is exact in bfloat16, so of the six
        # passes of the product the three over g's pieces are all there is
        g, pieces = jnp.where(real, of_heads(g_ref), 0.0), []
        for _ in range(3):
            pieces.append(g.astype(bf16))
            g = g - pieces[-1].astype(f32)
        g_scr[...] = a_head(lambda *xs: sum(
            jnp.dot(tri, x, preferred_element_type=f32)
            for x in reversed(xs)), *pieces)                     # [C, d_k]
        k_scr[...] = of_heads(k_ref).astype(f32)
        decay = jnp.exp(g_scr[...])
        both = a_head(_mm, jnp.concatenate(
            [k_scr[...] * decay, of_heads(q_ref).astype(f32) * decay],
            axis=1), s_scr)                      # one latch of S for the two
        x_scr[...] = of_heads(v_ref).astype(f32) - both[:, :C]
        o_scr[...] = both[:, C:]

        def scores(lo):
            """Sub-block ``lo`` against the keys up to its own, which needs
            no ``V'``: its rows of ``p_scr``, and the coefficients its solve
            takes — ``beta``, ``beta A`` over the earlier rows, the columns
            of ``beta A`` inside the sub-block."""
            part = slice(lo, lo + SUB)
            GI, kI = g_scr[:, part], k_scr[:, part]
            qI = of_heads(q_ref, part).astype(f32)
            bI = jnp.sum(jnp.where(lanes == n * heads + head,
                                   jnp.where(real[part], b_ref[part], 0.0),
                                   0.0), axis=2, keepdims=True)
            # [heads, SUB, 1], lane-replicated as the sums below are
            P, before = jnp.zeros((heads, SUB, C), f32), None
            if lo:
                # the earlier sub-blocks' keys (rows [:lo]: the later ones
                # would be masked), through this one's first row
                ref = g_scr[:, lo - 1:lo]
                since = jnp.exp(GI - ref)
                off = a_head(
                    functools.partial(_mm, dims=_NT),
                    jnp.concatenate([kI * since, qI * since], axis=1),
                    k_scr[:, :lo] * jnp.exp(jnp.minimum(
                        ref - g_scr[:, :lo], 0.0)))
                before = bI * off[:, :SUB]
                P = jnp.concatenate(
                    [off[:, SUB:], jnp.zeros((heads, SUB, C - lo), f32)],
                    axis=2)
            within = []
            for j in range(SUB):
                # this sub-block's key row j against its rows i >= j
                at_j = slice(lo + j, lo + j + 1)
                E = jnp.exp(jnp.minimum(GI - g_scr[:, at_j], 0.0)) \
                    * k_scr[:, at_j]
                P = jnp.where((col == lo + j) & (row >= j),
                              jnp.sum(qI * E, axis=2, keepdims=True), P)
                if j < SUB - 1:
                    within.append(jnp.where(row > j, bI * jnp.sum(
                        kI * E, axis=2, keepdims=True), 0.0))
            p_scr[:, part] = P
            return bI, before, within

        def solve(lo, bI, before, within):
            """Forward substitution over sub-block ``lo``: one product with
            the rows before it, then a column at a time in registers."""
            part = slice(lo, lo + SUB)
            xI = bI * x_scr[:, part]
            if lo:
                xI = xI - a_head(_mm, before, x_scr[:, :lo])
            for j, a in enumerate(within):
                xI = xI - a * xI[:, j:j + 1]
            x_scr[:, part] = xI

        # a sub-block's scores go out with the solve BEFORE its own: they
        # are lane sums and exponentials (XLU, EUP), the solves a chain of
        # small products (MXU), and the schedule overlaps what it is handed
        # side by side
        coef = scores(0)
        for lo in range(0, C, SUB):
            solve(lo, *coef)
            if lo + SUB < C:
                coef = scores(lo + SUB)
        last = g_scr[:, C - 1:C]
        fade, keys = jnp.exp(last), k_scr[...] * jnp.exp(last - g_scr[...])
        for h in hs:
            o_ref[:, h * D:(h + 1) * D] = (
                o_scr[h] + _mm(p_scr[h], x_scr[h])).astype(o_ref.dtype)
        for h in hs:
            s_scr[h] = _column(fade[h]) * s_scr[h] \
                + _mm(keys[h], x_scr[h], _TN)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_out[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_pallas(q, k, v, g, beta, pool, meta, *, interpret):
    T, H, D = q.shape
    heads = _chunk_heads(H, D)
    flat = lambda x: x.reshape(T, H * D)
    head = pl.BlockSpec((BLOCK, heads * D), lambda n, c, m: (c, n))
    state = pl.BlockSpec((None, None, heads, D, D),
                         lambda n, c, m: (m[0], m[1], n, 0, 0))
    block = lambda width: pltpu.VMEM((heads, BLOCK, width), jnp.float32)
    out, pool = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H // heads, T // BLOCK),
            in_specs=[head, head, head, head,
                      pl.BlockSpec((BLOCK, H), lambda n, c, m: (c, 0)),
                      state],
            out_specs=[head, state],
            scratch_shapes=[pltpu.VMEM((heads, D, D), jnp.float32),
                            block(D), block(D), block(D), block(D),
                            block(BLOCK)]),
        out_shape=[jax.ShapeDtypeStruct((T, H * D), v.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda.chunk_scan",
    )(meta, flat(q), flat(k), flat(v), flat(g), beta, pool)
    return out.reshape(T, H, D), pool


def chunk_scan(q, k, v, g, beta, pool, layer, row, *, fresh, real,
               pallas=True):
    """``T`` consecutive positions of ONE slot through layer ``layer`` of
    ``pool [layers, rows, H, d, d]``: ``q`` / ``k`` / ``v [T, H, d]``, ``g [T,
    H, d]`` and ``beta [T, H]`` float32; the state starts from zeros where
    ``fresh`` (the request's first chunk, whatever the row's last occupant
    left) and else from row ``row``, and the row is left holding the state
    after position ``real - 1``.  Returns ``(o [T, H, d]`` in ``v``'s
    dtype``, pool)``."""
    T = q.shape[0]
    layer, row, real = (jnp.asarray(x, jnp.int32) for x in (layer, row, real))
    g, beta = _f32(g, beta)
    if pallas:
        pad = -T % BLOCK
        padded = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        meta = jnp.stack([layer, row, jnp.asarray(fresh, jnp.int32), real])
        interpret = _interpret()                # a bool: static by value
        out, pool = _chunk_pallas(*map(padded, (q, k, v, g, beta)), pool,
                                  meta, interpret=interpret)
        return out[:T], pool
    live = jnp.arange(T) < real
    start = jnp.where(fresh, 0.0, pool[layer, row])
    state, out = _scan_xla(
        start, *_f32(q, k, v), jnp.where(live[:, None, None], g, 0.0),
        jnp.where(live[:, None], beta, 0.0))
    return out.astype(v.dtype), pool.at[layer, row].set(state)


# --------------------------------------------------------------------- #
# The decode kernel
# --------------------------------------------------------------------- #
def _step_kernel(layer, rows, live, q_ref, k_ref, v_ref, a_ref, b_ref, s_in,
                 o_ref, s_out):
    """Blocks ``[heads, d]`` of one lane (``a``: the decay ``exp(g)``,
    ``b``: ``beta`` over the head's lanes) and the state ``[heads, d_k,
    d_v]`` of its row."""
    n = pl.program_id(0)
    heads, D = q_ref.shape

    @pl.when(live[n] == 0)
    def _():
        s_out[...] = s_in[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live[n] != 0)
    def _():
        # the heads' decay, key and query rows as columns: one transpose
        tile = jnp.concatenate(
            [a_ref[...], k_ref[...], q_ref[...],
             jnp.zeros((D - 3 * heads, D), jnp.float32)]).T
        for h in range(heads):
            col = lambda i: tile[:, i * heads + h:i * heads + h + 1]
            decayed = col(0) * s_in[h]
            seen = jnp.sum(col(1) * decayed, axis=0, keepdims=True)
            state = decayed + col(1) * (b_ref[h:h + 1]
                                        * (v_ref[h:h + 1] - seen))
            s_out[h] = state
            o_ref[h:h + 1] = jnp.sum(col(2) * state, axis=0, keepdims=True)


def _step_heads(H, D):
    return max(n for n in (_STEP_HEADS, 4, 2, 1)
               if H % n == 0 and 3 * n <= D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(q, k, v, a, b, pool, layer, rows, live, *, interpret):
    N, H, D = q.shape
    heads = _step_heads(H, D)
    lane = pl.BlockSpec((None, heads, D), lambda n, h, *refs: (n, h, 0))
    state = pl.BlockSpec(
        (None, None, heads, D, D),
        lambda n, h, layer, rows, live: (layer[0], rows[n], h, 0, 0))
    return pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N, H // heads),
            in_specs=[lane] * 5 + [state], out_specs=[lane, state]),
        out_shape=[jax.ShapeDtypeStruct((N, H, D), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda.decode_step",
    )(layer, rows, live, q, k, v, a, b, pool)


def decode_step(q, k, v, g, beta, pool, layer, rows, live=None, *,
                pallas=True):
    """One token a lane through layer ``layer`` of ``pool``: ``q`` / ``k`` /
    ``v`` / ``g [N, H, d]``, ``beta [N, H]``, ``rows [N]`` the lanes' state
    rows, ``live [N]`` (None: all) — a dead lane's row is handed back as it
    was and its output is zero.  Returns ``(o [N, H, d]`` in ``v``'s
    dtype``, pool)``."""
    N = q.shape[0]
    layer = jnp.asarray(layer, jnp.int32)
    rows = rows.astype(jnp.int32)
    live = jnp.ones((N,), bool) if live is None else live.astype(bool)
    qf, kf, vf, g, beta = _f32(q, k, v, g, beta)
    if pallas:
        interpret = _interpret()                # a bool: static by value
        out, pool = _step_pallas(
            qf, kf, vf, jnp.exp(g),
            jnp.broadcast_to(beta[..., None], qf.shape), pool,
            layer.reshape(1), rows, live.astype(jnp.int32),
            interpret=interpret)
        return out.astype(v.dtype), pool
    before = pool[layer, rows]
    state, out = _step_xla(before, qf, kf, vf, g, beta)
    keep = live[:, None, None, None]
    # dead lanes share the trash row: each writes back what it read
    pool = pool.at[layer, rows].set(jnp.where(keep, state, before))
    return jnp.where(live[:, None, None], out, 0.0).astype(v.dtype), pool
