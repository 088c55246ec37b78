"""A short causal depthwise convolution's ROWS a slot: the last ``taps - 1``
inputs of every channel, kept between a slot's dispatches
(``models/lfm2.py``, ``models/solar_open2.py``, ``models/granite_hybrid.py``
behind ``registry.conv_state_update``).

**The pool** is ``[conv layers, state rows, R, 128]`` in the server's dtype
(:func:`rows_shape`) — row 0 the trash row, a slot's row the last entry of
its table row (``paging.SlotPages``).  A slot's ``(taps - 1) x width`` values
lie in that order along ``R x 128``, ``R`` padded up to whole tiles (a
bfloat16 tile is 16 sublanes: Granite's 198 become 208, LFM2's 32 and
Solar's 576 are exact; the pad holds zeros).  The ROW INDEX IS A LEADING,
UNTILED DIMENSION: XLA tiles an array's last two dimensions, so in the flat
``[layers, rows, (taps - 1) x width]`` this pool had before PR 57 a slot's
row was one SUBLANE of each of its tiles, sixteen slots to a tile, and a
decode step's write-back was a partial-tile read-modify-write a tile and
lane at a dynamic sublane offset (a twentieth of the bytes' rate at
Granite's sizes).  Here a row is whole contiguous tiles and moving it is a
copy.  A width that 128 does not divide keeps the flat form and plain XLA.

**A decode step** (:func:`decode_step`): one token a lane.  The lanes' rows
come out by their table entries (``conv.rows_read``), the taps are summed in
float32 on the rows AS THEY LIE — a tap is ``width / 128`` sublanes after
the last's, nothing is laid out anew (:func:`step_taps`) — and the rows to
keep go back IN PLACE (``conv.rows_write``, the pool aliased in and out).
Both kernels leave the pool in HBM and move a lane's row with one copy,
``pool[layer, rows[n]] <-> [n]``, every lane's in flight at once: those
bytes and no others (Granite: 176 x 53 KB each way a layer, 46 us on the
chip where the flat pool's gather and scatter took 265).  A lane's row is its
TABLE's: lanes on the trash row (dead, or released by the host and not yet
retired by the device) all land there, in no order, and nothing reads it.

**A prefill chunk** (:func:`chunk`): ``T`` consecutive positions of one
slot; its one row is read and put back with a ``dynamic_update_slice``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import _interpret

LANES = 128              # the lanes of a tile of the pool


# --------------------------------------------------------------------- #
# The pool's layout
# --------------------------------------------------------------------- #
def rows_shape(taps, width, dtype):
    """A state row of the pool: ``[R, 128]`` — ``(taps - 1) x width`` values
    in whole tiles of ``dtype`` — or, where 128 does not divide the width,
    the flat ``[(taps - 1) x width]``."""
    n = (taps - 1) * width
    if width % LANES:
        return (n,)
    sublanes = 32 // jnp.dtype(dtype).itemsize      # a tile: 8 x 4 bytes
    return (-(-n // LANES // sublanes) * sublanes, LANES)


def flat_of(rows, taps, width):
    """Pool rows ``[..., R, 128]`` as ``[..., (taps - 1) x width]`` (flat
    rows, those of a width 128 does not divide, as they are)."""
    if width % LANES:
        return rows
    return rows.reshape(rows.shape[:-2] + (-1,))[..., :(taps - 1) * width]


def tiles_of(flat, pool):
    """``[..., n]`` as ``pool [layers, rows, ...]`` keeps a row, in its
    dtype: the pad's zeros behind."""
    row = pool.shape[2:]
    if len(row) == 1:
        return flat.astype(pool.dtype)
    pad = [(0, 0)] * (flat.ndim - 1) + [(0, row[0] * row[1] - flat.shape[-1])]
    return jnp.pad(flat.astype(pool.dtype), pad).reshape(
        flat.shape[:-1] + row)


# --------------------------------------------------------------------- #
# The taps
# --------------------------------------------------------------------- #
def step_taps(before, z, w):
    """A causal depthwise convolution's step, one token a lane: ``before
    [N, (K - 1) x c, ...]`` each lane's last rows, tap after tap along axis
    1 (what follows them there is ignored), ``z [N, c, ...]`` its token's,
    ``w [K, c, ...]`` float32 (tap ``j`` weighs row ``t - K + 1 + j``) —
    ``c`` the width (flat rows) or the width's sublanes (rows in tiles,
    ``[..., 128]``).  Returns ``(conv [N, c, ...]`` float32``, the rows to
    keep [N, (K - 1) x c, ...])``."""
    K, c = w.shape[:2]
    rows = [before[:, j * c:(j + 1) * c] for j in range(K - 1)] + [z]
    conv = jnp.sum(jnp.stack(rows, axis=1).astype(jnp.float32) * w, axis=1)
    return conv, jnp.concatenate(rows[1:], axis=1)


def chunk_taps(before, z, w, start, last=None):
    """The same over ``T`` consecutive rows ``z [T, h]`` of ONE sequence
    from position ``start``: a request's first chunk starts from zeros,
    whatever its slot's last occupant left in ``before [(K - 1) x h]``, and
    the rows to keep are those that end at ``last``, the chunk's last real
    row (the padded tail's never reach the state).  Returns ``(conv [T,
    h]`` float32``, kept [(K - 1) x h])``."""
    K, h = w.shape
    before = jnp.where(start == 0, 0, before.reshape(K - 1, h))
    zz = jnp.concatenate([before.astype(z.dtype), z])          # [T+K-1, h]
    T = z.shape[0]
    conv = sum(zz[j:j + T].astype(jnp.float32) * w[j] for j in range(K))
    # rows (last - K + 2 .. last) of z: zz is ahead by K - 1
    last = T - 1 if last is None else last
    keep = jax.lax.dynamic_slice_in_dim(zz, last + 1, K - 1)
    return conv, keep.reshape(-1)


# --------------------------------------------------------------------- #
# The rows' read and write-back
# --------------------------------------------------------------------- #
def _each_lane(lanes, copy):
    """``copy(n)``, lane ``n``'s async copy: every lane's started before the
    first is waited for (a wait rebuilds the descriptor its start used)."""
    jax.lax.fori_loop(0, lanes, lambda n, c: copy(n).start(), None)
    jax.lax.fori_loop(0, lanes, lambda n, c: copy(n).wait(), None)


def _read_kernel(layer, rows, pool, out, sem):
    """``pool[layer, rows[n]] -> out[n]``, both whole in HBM."""
    _each_lane(out.shape[0], lambda n: pltpu.make_async_copy(
        pool.at[layer[0], rows[n]], out.at[n], sem.at[0]))


def _write_kernel(layer, rows, kept, pool_in, pool_out, sem):
    """``kept[n] -> pool[layer, rows[n]]``, the pool aliased in -> out."""
    del pool_in
    _each_lane(kept.shape[0], lambda n: pltpu.make_async_copy(
        kept.at[n], pool_out.at[layer[0], rows[n]], sem.at[0]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rows_pallas(pool, layer, rows, kept=None, *, interpret):
    write = kept is not None
    whole = pl.BlockSpec(memory_space=pl.ANY)
    lanes = (rows.shape[0],) + pool.shape[2:]
    return pl.pallas_call(
        _write_kernel if write else _read_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[whole] * (1 + write), out_specs=whole,
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct(pool.shape if write else lanes,
                                       pool.dtype),
        # operand indices INCLUDE the two scalar-prefetch args
        input_output_aliases={3: 0} if write else {},
        interpret=interpret,
        name="conv.rows_write" if write else "conv.rows_read",
    )(layer, rows, *((kept, pool) if write else (pool,)))


def move_rows(pool, layer, rows, kept=None, *, pallas=True):
    """Rows ``rows [N]`` of layer ``layer`` of ``pool [layers, rows, R,
    128]``: out, ``[N, R, 128]`` — or, given ``kept [N, R, 128]``, the pool
    with it in those rows and nothing else touched."""
    if not pallas:
        return pool[layer, rows] if kept is None \
            else pool.at[layer, rows].set(kept)
    interpret = _interpret()                    # a bool: static by value
    return _rows_pallas(pool, jnp.asarray(layer, jnp.int32).reshape(1),
                        rows.astype(jnp.int32), kept, interpret=interpret)


# --------------------------------------------------------------------- #
# A step, a chunk
# --------------------------------------------------------------------- #
def decode_step(z, w, pool, layer, rows, *, pallas=True):
    """One token a lane through layer ``layer`` of ``pool``: ``z [N, h]``,
    ``w [K, h]`` float32, ``rows [N]`` the lanes' state rows.  Rows in tiles
    stay in tiles from the read to the write-back — a tap's ``h / 128``
    sublanes after the last's, nothing re-laid.  Returns ``(conv [N, h]``
    float32``, pool)``."""
    K, h = w.shape
    if pool.ndim == 3:
        conv, kept = step_taps(pool[layer, rows], z, w)
        return conv, pool.at[layer, rows].set(kept.astype(pool.dtype))
    tiles = lambda x: x.reshape(x.shape[0], h // LANES, LANES)
    conv, kept = step_taps(move_rows(pool, layer, rows, pallas=pallas),
                           tiles(z), tiles(w))
    kept = jnp.pad(kept.astype(pool.dtype), (
        (0, 0), (0, pool.shape[2] - kept.shape[1]), (0, 0)))
    return conv.reshape(-1, h), move_rows(pool, layer, rows, kept,
                                          pallas=pallas)


def chunk(z, w, pool, layer, row, start, last=None):
    """``T`` consecutive positions ``z [T, h]`` of ONE slot from position
    ``start`` through layer ``layer`` of ``pool``, ``row`` the slot's state
    row; ``pool`` None: a sequence from its start, nothing kept.  Returns
    ``(conv [T, h]`` float32``, pool)``."""
    K, h = w.shape
    if pool is None:
        zeros = jnp.zeros(((K - 1) * h,), z.dtype)
        return chunk_taps(zeros, z, w, 0, last)[0], None
    conv, kept = chunk_taps(flat_of(pool[layer, row], K, h), z, w, start,
                            last)
    return conv, pool.at[layer, row].set(tiles_of(kept, pool))
