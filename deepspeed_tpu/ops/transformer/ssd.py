"""A Mamba-2 state-space layer's scan on a MATRIX STATE a slot (state-space
duality, arXiv:2405.21060; ``models/granite_hybrid.py``).

A head keeps ``S [P, N]`` in float32 and no row a position.  With a step
size ``dt_t`` (a head's, after its softplus), a log-decay ``a_t = dt_t A``
(ONE scalar a head and position, at most 0) and ``B_t``, ``C_t [N]`` that
the heads of a GROUP share — ``b`` / ``c [T, N]``: one group, every head's
(Granite 4.0-H); ``[T, G, N]``: ``G`` groups of ``H / G`` consecutive heads
(Nemotron-H: 8 of 8)::

    S_t = exp(a_t) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t

(no delta correction, so no triangular solve; the skip ``D x_t`` is the
mixer's).  That recurrence, one position after the other, is the plain-XLA
path here (:func:`_scan_xla`, :func:`_step_xla`) and what the tests hold the
kernels to.

**The pool** is ``[SSM layers, state rows, H / per, N, per x P]`` float32
(:func:`state_shape`) — row 0 the trash row, a slot's row the last entry of
its table row (``paging.SlotPages``).  A head's state lies TRANSPOSED, ``N``
down the sublanes and ``P`` along the lanes, ``per = 128 / P`` heads side by
side in one 128-lane tile (two at ``P`` = 64): what differs a head and
channel (``dt x``, the decay) is then a ROW of 128 lanes, what every head
shares (``B``, ``C``) a column a grid step broadcasts once, and the read-out
``S C`` a sum DOWN the sublanes — whole-vreg multiplies and adds, no lane
reduction and no lane broadcast a head.  (:func:`heads_of` /
:func:`tiles_of` turn a row to ``[H, P, N]`` and back.)  Both kernels take
the pool in and hand it back ALIASED: a dispatch touches its own rows'
blocks and copies nothing else.

**A prefill chunk** (:func:`chunk_scan`, ``ssd.chunk_scan``): ``T``
consecutive positions of one slot in row blocks of 128, ``grid = (heads / 8,
T / 128)``, the eight heads' states carried in VMEM across the blocks.  With
``L_i = sum_{j <= i} a_j`` inside the block (a head's) and ``G = C B^T`` —
formed ONCE a row block and group, before the kernel, for all its heads (a
grid step's eight heads lie in one group): a multi-query linear attention,
every head of a group to its one ``B`` and ``C`` —

    Y_i   = sum_{j <= i} G_ij e^{L_i - L_j} dt_j x_j + e^{L_i} S_in C_i
    S_out = e^{L_last} S_in + sum_j e^{L_last - L_j} dt_j x_j (x) B_j

Every exponent is a DIFFERENCE ``L_i - L_j`` with ``j <= i`` (at most 0),
formed before the exponential: no ``e^{-L}`` is ever taken alone, so a head
may decay as fast as it likes (``L`` may span hundreds over a block).  Rows
past the chunk's last REAL row get ``a = 0`` and ``dt = 0``: they leave the
state exactly as it is, and blocks wholly past it are skipped.

**A decode step** (:func:`decode_step`, ``ssd.decode_step``): one token a
lane, ``grid = (lanes, heads / 32)``; the lane's state row goes through VMEM
once — read, decayed, added to, read out against ``C``, written back: 2 x 4
MiB a lane and layer at 128 heads of 64 x 128, which is what the step costs.
A grid step's heads are whole groups (or lie in one): it sends each group's
``B`` and ``C`` down the sublanes once.  A DEAD lane (its table row on the trash row) hands its row back as it found
it.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import _interpret

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 128              # rows a grid step of the chunk kernel takes: a tile
LANES = 128              # the lanes of a tile of the pool
_CHUNK_HEADS = 8         # heads a grid step of the chunk kernel takes
_STEP_TILES = (16, 8, 4, 2, 1)   # tiles a step of the decode kernel: the most


# --------------------------------------------------------------------- #
# The pool's layout
# --------------------------------------------------------------------- #
def heads_per_tile(H, P):
    """Heads whose states lie side by side along a tile's 128 lanes."""
    per = LANES // P if LANES % P == 0 else 1
    return per if H % per == 0 else 1


def state_shape(H, P, N):
    """A state row of the pool: ``[H / per, N, per x P]``."""
    per = heads_per_tile(H, P)
    return (H // per, N, per * P)


def tiles_of(state):
    """``[..., H, P, N]`` as the pool keeps it, ``[..., H / per, N, per x
    P]``."""
    *lead, H, P, N = state.shape
    per = heads_per_tile(H, P)
    t = state.reshape(*lead, H // per, per, P, N)
    return jnp.moveaxis(t, -1, -3).reshape(*lead, H // per, N, per * P)


def heads_of(tiles, P):
    """A pool row (or rows) ``[..., H / per, N, per x P]`` as ``[..., H, P,
    N]``."""
    *lead, G, N, W = tiles.shape
    t = jnp.moveaxis(tiles.reshape(*lead, G, N, W // P, P), -3, -1)
    return t.reshape(*lead, G * (W // P), P, N)


# --------------------------------------------------------------------- #
# The recurrence, in plain XLA
# --------------------------------------------------------------------- #
def _step_xla(state, x, dt, a, b, c):
    """One position of every head: ``state [..., H, P, N]``, ``x [..., H,
    P]``, ``dt`` / ``a [..., H]``, ``b`` / ``c [..., N]`` or ``[..., G,
    N]`` (float32).  Returns ``(state, y [..., H, P])``."""
    read = "...hpn,...n->...hp"
    if b.ndim == x.ndim:                 # in groups: a head reads its own's
        per = x.shape[-2] // b.shape[-2]
        b, c = (jnp.repeat(t, per, axis=-2) for t in (b, c))
        read = "...hpn,...hn->...hp"
    else:
        b = b[..., None, :]              # every head's
    state = jnp.exp(a)[..., None, None] * state \
        + (dt[..., None] * x)[..., None] * b[..., None, :]
    return state, jnp.einsum(read, state, c, precision=HIGHEST)


def _scan_xla(state, x, dt, a, b, c):
    """``T`` positions one after the other: ``x [T, H, P]``, ``dt`` / ``a
    [T, H]``, ``b`` / ``c [T, N]`` or ``[T, G, N]``, ``state [H, P, N]``."""
    def step(s, row):
        return _step_xla(s, *row)
    return jax.lax.scan(step, state, (x, dt, a, b, c))


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


def _one_group(b, c):
    """``[T, 1, N]`` is ``[T, N]``: one group takes the one-group forms."""
    return (b[:, 0], c[:, 0]) if b.ndim == 3 and b.shape[1] == 1 else (b, c)


def chunk_heads(H, P, groups=1):
    """The heads a grid step of the chunk kernel takes (whole tiles, inside
    ONE of the ``groups``), or None where the kernel has no form for them
    (the plain-XLA path then)."""
    per = heads_per_tile(H, P)
    if H % _CHUNK_HEADS == 0 and _CHUNK_HEADS % per == 0 \
            and (H // groups) % _CHUNK_HEADS == 0:
        return _CHUNK_HEADS
    return H if 2 * H <= BLOCK and groups == 1 else None


def step_tiles(G):
    """Of a row's ``G`` tiles, those a grid step of the decode kernel takes:
    whole rows of ``dt x``, eight at a time or all of them."""
    return next(n for n in _STEP_TILES + (G,)
                if G % n == 0 and (n % 8 == 0 or n == G))


def step_groups(H, P, groups=1):
    """The groups whose ``B`` and ``C`` a grid step of the decode kernel
    reads — its heads are that many whole groups, or lie in one (1) —, or
    None where they are neither (the plain-XLA path then)."""
    per = heads_per_tile(H, P)
    heads, each = step_tiles(H // per) * per, H // groups
    if each % per == 0 and (heads % each == 0 or each % heads == 0):
        return max(heads // each, 1)
    return None


# --------------------------------------------------------------------- #
# The chunk kernel
# --------------------------------------------------------------------- #
def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=HIGHEST,
                               preferred_element_type=jnp.float32)


_TN = (((0,), (0,)), ((), ()))       # a.T @ b


def _chunk_kernel(meta, x_ref, l_ref, dt_ref, g_ref, b_ref, c_ref, s_in,
                  y_ref, s_out, s_scr):
    """``meta``: layer, state row, fresh (the state starts at zero), real
    rows.  ``x_ref`` / ``y_ref [tiles, BLOCK, per x P]`` — ``per`` heads'
    channels side by side, as in the state's tiles ``s_scr [tiles, N, per x
    P]`` between the blocks; ``l_ref`` / ``dt_ref [heads, BLOCK]`` the
    block's cumulative log-decay and step sizes, a head a ROW (their
    transpose gives each head's as a column); ``g_ref [BLOCK, BLOCK]`` the
    block's ``C B^T``."""
    c = pl.program_id(1)
    heads, Q = l_ref.shape
    tiles, _, W = s_scr.shape
    per = heads // tiles
    P = W // per
    f32 = jnp.float32

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.where(meta[2] != 0, 0.0, s_in[...])

    @pl.when(c * Q >= meta[3])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(c * Q < meta[3])
    def _():
        # every head's L and dt down the rows: one transpose
        cols = jnp.concatenate(
            [l_ref[...], dt_ref[...],
             jnp.zeros((Q - 2 * heads, Q), f32)]).T
        seen = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1) \
            <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        G = jnp.where(seen, g_ref[...], 0.0)
        B, C = b_ref[...].astype(f32), c_ref[...].astype(f32)
        # the lanes of a tile that are head ``i``'s
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // P
        mine = lambda i, of: of(i) if per == 1 else sum(
            jnp.where(lane == k, of(i + k), 0.0) for k in range(per))
        # (a [1, 1] goes along the lanes first, then down the sublanes)
        wide = lambda v: jnp.broadcast_to(v, (v.shape[0], W))
        for t in range(tiles):
            h, x, S = t * per, x_ref[t].astype(f32), s_scr[t]
            last = mine(h, lambda i: wide(l_ref[i:i + 1, Q - 1:Q]))
            L = mine(h, lambda i: wide(cols[:, i:i + 1]))
            dt = mine(h, lambda i: wide(cols[:, heads + i:heads + i + 1]))
            y = jnp.exp(L) * _mm(C, S)
            for i in range(h, h + per):
                # j <= i: the difference is at most 0; elsewhere G is 0
                W_i = G * jnp.exp(jnp.minimum(
                    cols[:, i:i + 1] - l_ref[i:i + 1, :], 0.0)) \
                    * dt_ref[i:i + 1, :]
                y = y + _mm(W_i, x if per == 1
                            else jnp.where(lane == i - h, x, 0.0))
            y_ref[t] = y.astype(y_ref.dtype)
            s_scr[t] = jnp.exp(last) * S \
                + _mm(B, x * dt * jnp.exp(last - L), _TN)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_out[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_pallas(x, dt, a, b, c, pool, meta, *, interpret):
    """``x [T, H, P]``, ``dt`` / ``a [T, H]`` float32 (zero past the real
    rows), ``b`` / ``c [T, N]`` or ``[T, G, N]``; ``T`` whole blocks."""
    T, H, P = x.shape
    N, Q = b.shape[-1], BLOCK
    groups = b.shape[1] if b.ndim == 3 else 1
    heads, per = chunk_heads(H, P, groups), heads_per_tile(H, P)
    tiles, W = heads // per, per * P
    blocks = lambda t: t.reshape((T // Q, Q) + t.shape[1:])
    # what every head (of a group) shares, once a row block: the scores
    # C B^T (float32 sums of the stored rows' products) ...
    if b.ndim == 3:
        G = jnp.einsum("cqgn,ckgn->gcqk", blocks(c), blocks(b),
                       preferred_element_type=jnp.float32) \
            .reshape(groups, T, Q)
        b, c = jnp.swapaxes(b, 0, 1), jnp.swapaxes(c, 0, 1)
        steps = H // groups // heads         # grid steps a group
        shared = lambda w: pl.BlockSpec(
            (None, Q, w), lambda n, i, m: (n // steps, i, 0))
    else:
        G = jnp.einsum("cqn,ckn->cqk", blocks(c), blocks(b),
                       preferred_element_type=jnp.float32).reshape(T, Q)
        shared = lambda w: pl.BlockSpec((Q, w), lambda n, i, m: (i, 0))
    # ... and a head's log-decay summed down the block
    L = jnp.cumsum(blocks(a), axis=1).reshape(T, H)
    tile = pl.BlockSpec((tiles, Q, W), lambda n, i, m: (n, i, 0))
    row = pl.BlockSpec((heads, Q), lambda n, i, m: (n, i))
    state = pl.BlockSpec((None, None, tiles, N, W),
                         lambda n, i, m: (m[0], m[1], n, 0, 0))
    y, pool = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H // heads, T // Q),
            in_specs=[tile, row, row, shared(Q), shared(N), shared(N),
                      state],
            out_specs=[tile, state],
            scratch_shapes=[pltpu.VMEM((tiles, N, W), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((H // per, T, W), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd.chunk_scan",
    )(meta, jnp.swapaxes(x.reshape(T, H // per, W), 0, 1), L.T, dt.T, G, b,
      c, pool)
    return jnp.swapaxes(y, 0, 1).reshape(T, H, P), pool


def chunk_scan(x, dt, a, b, c, pool, layer, row, *, fresh, real,
               pallas=True):
    """``T`` consecutive positions of ONE slot through layer ``layer`` of
    ``pool [layers, rows, H / per, N, per x P]``: ``x [T, H, P]``, ``dt [T,
    H]`` the step sizes and ``a [T, H]`` the log-decays ``dt A`` (float32),
    ``b`` / ``c [T, N]`` or, in groups, ``[T, G, N]``; the state starts from zeros where ``fresh`` (the
    request's first chunk, whatever the row's last occupant left) and else
    from row ``row``, and the row is left holding the state after position
    ``real - 1``.  Returns ``(y [T, H, P]`` float32``, pool)``."""
    T, H, P = x.shape
    b, c = _one_group(b, c)
    layer, row, real = (jnp.asarray(t, jnp.int32) for t in (layer, row, real))
    live = (jnp.arange(T) < real)[:, None]
    dt, a = (jnp.where(live, t, 0.0) for t in _f32(dt, a))
    if pallas and chunk_heads(H, P, b.shape[1] if b.ndim == 3 else 1):
        pad = -T % BLOCK
        padded = lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        meta = jnp.stack([layer, row, jnp.asarray(fresh, jnp.int32), real])
        interpret = _interpret()                # a bool: static by value
        out, pool = _chunk_pallas(*map(padded, (x, dt, a, b, c)), pool, meta,
                                  interpret=interpret)
        return out[:T], pool
    start = jnp.where(fresh, 0.0, heads_of(pool[layer, row], P))
    state, out = _scan_xla(start, x.astype(jnp.float32), dt, a, *_f32(b, c))
    return out, pool.at[layer, row].set(tiles_of(state))


# --------------------------------------------------------------------- #
# The decode kernel
# --------------------------------------------------------------------- #
def _step_kernel(layer, rows, live, dx_ref, a_ref, b_ref, c_ref, s_in, o_ref,
                 s_out):
    """One lane's ``R`` tiles: ``dx_ref`` / ``a_ref [R, 128]`` their heads'
    ``dt x`` and decay ``exp(a)`` (a head's, over its ``P`` lanes);
    ``b_ref`` / ``c_ref [groups, N]``, the groups these tiles' heads are, in
    equal runs; the tiles ``[R, N, 128]`` of the lane's row.  ``B`` and
    ``C`` go down the sublanes once a step: one transpose, two lane
    broadcasts a group."""
    n = pl.program_id(0)
    R, N, W = s_in.shape
    groups = b_ref.shape[0]

    @pl.when(live[n] == 0)
    def _():
        s_out[...] = s_in[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live[n] != 0)
    def _():
        cols = jnp.concatenate(
            [b_ref[...], c_ref[...],
             jnp.zeros((N - 2 * groups, N), jnp.float32)]).T
        B, C = ([jnp.broadcast_to(cols[:, g:g + 1], (N, W))
                 for g in range(first, first + groups)]
                for first in (0, groups))
        for r in range(R):
            g = r * groups // R
            S = a_ref[r:r + 1, :] * s_in[r] + B[g] * dx_ref[r:r + 1, :]
            s_out[r] = S
            o_ref[r:r + 1, :] = jnp.sum(S * C[g], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(dx, decay, b, c, pool, layer, rows, live, *, interpret):
    """``dx`` / ``decay [lanes, tiles, 128]`` float32, ``b`` / ``c [lanes,
    1, states]`` float32 — or, in groups, ``[lanes, G / g, g, states]``:
    ``g`` the groups of a grid step's heads (:func:`step_groups`)."""
    lanes, G, W = dx.shape
    N = pool.shape[3]
    R = step_tiles(G)
    wide = pl.BlockSpec((None, R, W), lambda n, h, *refs: (n, h, 0))
    if b.ndim == 3:
        shared = pl.BlockSpec((None, 1, N), lambda n, h, *refs: (n, 0, 0))
    else:
        # a step's heads are ``g`` whole groups, or ``of`` steps share one
        of = (G // R) // b.shape[1]
        shared = pl.BlockSpec((None, None, b.shape[2], N),
                              lambda n, h, *refs: (n, h // of, 0, 0))
    state = pl.BlockSpec(
        (None, None, R, N, W),
        lambda n, h, layer, rows, live: (layer[0], rows[n], h, 0, 0))
    return pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(lanes, G // R),
            in_specs=[wide, wide, shared, shared, state],
            out_specs=[wide, state]),
        out_shape=[jax.ShapeDtypeStruct(dx.shape, jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd.decode_step",
    )(layer, rows, live, dx, decay, b, c, pool)


def decode_step(x, dt, a, b, c, pool, layer, rows, live=None, *,
                pallas=True):
    """One token a lane through layer ``layer`` of ``pool``: ``x [N, H,
    P]``, ``dt`` / ``a [N, H]``, ``b`` / ``c [N, states]`` or, in groups,
    ``[N, G, states]``, ``rows [N]`` the lanes' state rows, ``live [N]`` (None: all) — a dead lane's row is
    handed back as it was and its output is zero.  Returns ``(y [N, H, P]``
    float32``, pool)``."""
    N, H, P = x.shape
    b, c = _one_group(b, c)
    layer = jnp.asarray(layer, jnp.int32)
    rows = rows.astype(jnp.int32)
    live = jnp.ones((N,), bool) if live is None else live.astype(bool)
    xf, dt, a, b, c = _f32(x, dt, a, b, c)
    per = heads_per_tile(H, P)
    each = step_groups(H, P, b.shape[1]) if b.ndim == 3 else 1
    if pallas and each:
        interpret = _interpret()                # a bool: static by value
        wide = lambda t: t.reshape(N, H // per, per * P)
        shared = (lambda t: t[:, None]) if b.ndim == 2 \
            else lambda t: t.reshape(N, -1, each, t.shape[-1])
        out, pool = _step_pallas(
            wide(dt[..., None] * xf),
            wide(jnp.broadcast_to(jnp.exp(a)[..., None], xf.shape)),
            shared(b), shared(c), pool, layer.reshape(1), rows,
            live.astype(jnp.int32), interpret=interpret)
        return out.reshape(N, H, P), pool
    before = heads_of(pool[layer, rows], P)
    state, out = _step_xla(before, xf, dt, a, b, c)
    keep = live[:, None, None, None]
    # dead lanes share the trash row: each writes back what it read
    pool = pool.at[layer, rows].set(
        tiles_of(jnp.where(keep, state, before)))
    return jnp.where(live[:, None, None], out, 0.0), pool
