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
consecutive positions of one slot, ``grid = (heads, T / 64)``, the state a
head carried in VMEM across its 64-row blocks.  A block is the chunked
form: with ``G_i = sum_{j <= i} g_j`` inside the block,

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


def _chunk_kernel(meta, q_ref, k_ref, v_ref, g_ref, b_ref, s_in, o_ref,
                  s_out, s_scr, g_scr, k_scr, x_scr):
    """``meta``: layer, state row, fresh (the state starts at zero), real
    rows.  ``s_scr [d_k, d_v]``: the head's state between its blocks;
    ``g_scr`` / ``k_scr [BLOCK, d_k]``: the block's ``G`` and keys, read a
    row at a time; ``x_scr [BLOCK, d_v]``: the right-hand side that forward
    substitution turns into ``V'``."""
    h, c = pl.program_id(0), pl.program_id(1)
    C = q_ref.shape[0]
    f32 = jnp.float32

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.where(meta[2] != 0, 0.0, s_in[...])

    @pl.when(c * C >= meta[3])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c * C < meta[3])
    def _():
        rows = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        real = c * C + rows < meta[3]
        lanes = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 1)
        beta = jnp.sum(jnp.where(lanes == h, b_ref[...], 0.0), axis=1,
                       keepdims=True)
        beta = jnp.where(real, beta, 0.0)                       # [C, 1]
        tri = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
               <= jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)).astype(f32)
        G = _mm(tri, jnp.where(real, g_ref[...], 0.0))          # [C, d_k]
        q, k, v = _f32(q_ref[...], k_ref[...], v_ref[...])
        S = s_scr[...]
        g_scr[...] = G
        k_scr[...] = k
        decay = jnp.exp(G)
        x_scr[...] = beta * (v - _mm(k * decay, S))
        col = jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0)
        attend = []
        for lo in range(0, C, SUB):
            part = slice(lo, lo + SUB)
            GI, kI, qI, bI = G[part], k[part], q[part], beta[part]
            P = jnp.zeros((SUB, C), f32)
            if lo:
                # the earlier sub-blocks' keys, through this one's first row
                ref = g_scr[lo - 1:lo]
                since = jnp.exp(GI - ref)
                off = _mm(jnp.concatenate([kI * since, qI * since]),
                          k * jnp.exp(jnp.minimum(ref - G, 0.0)), _NT)
                before = col < lo
                x_scr[part] = x_scr[part] - _mm(
                    jnp.where(before, bI * off[:SUB], 0.0), x_scr[...])
                P = jnp.where(before, off[SUB:], 0.0)
            for j in range(SUB):
                # this sub-block's key row j against its rows i >= j
                E = jnp.exp(jnp.minimum(GI - g_scr[lo + j:lo + j + 1], 0.0)) \
                    * k_scr[lo + j:lo + j + 1]
                P = jnp.where((col == lo + j) & (row >= j),
                              jnp.sum(qI * E, axis=1, keepdims=True), P)
                if j < SUB - 1:
                    a = jnp.where(row > j, bI * jnp.sum(kI * E, axis=1,
                                                        keepdims=True), 0.0)
                    x_scr[part] = x_scr[part] - a * x_scr[lo + j:lo + j + 1]
            attend.append(P)
        Vp = x_scr[...]
        o_ref[...] = (_mm(q * decay, S)
                      + _mm(jnp.concatenate(attend), Vp)).astype(o_ref.dtype)
        last = g_scr[C - 1:C]
        s_scr[...] = _column(jnp.exp(last)) * S \
            + _mm(k * jnp.exp(last - G), Vp, _TN)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_out[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_pallas(q, k, v, g, beta, pool, meta, *, interpret):
    T, H, D = q.shape
    flat = lambda x: x.reshape(T, H * D)
    head = pl.BlockSpec((BLOCK, D), lambda h, c, m: (c, h))
    state = pl.BlockSpec((None, None, None, D, D),
                         lambda h, c, m: (m[0], m[1], h, 0, 0))
    out, pool = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H, T // BLOCK),
            in_specs=[head, head, head, head,
                      pl.BlockSpec((BLOCK, H), lambda h, c, m: (c, 0)),
                      state],
            out_specs=[head, state],
            scratch_shapes=[pltpu.VMEM((D, D), jnp.float32),
                            pltpu.VMEM((BLOCK, D), jnp.float32),
                            pltpu.VMEM((BLOCK, D), jnp.float32),
                            pltpu.VMEM((BLOCK, D), jnp.float32)]),
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
