"""The convolution rows a slot (``ops/transformer/short_conv.py`` behind
``registry.conv_state_update``) at the three served models' widths — LFM2's
3 taps x 2,048, Granite's 4 x 8,448, Solar's 4 x 24,576: a row as whole
tiles under an untiled row index, a decode step's write-back in place
(``conv.rows_write``, interpreted here) — held BIT FOR BIT to what the flat
pool ``[layers, rows, (taps - 1) x width]`` and ``pool.at[layer,
rows].set(kept)`` gave before PR 57."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer import registry, short_conv

BF16, F32 = jnp.bfloat16, jnp.float32
SHAPES = {"lfm2": (3, 2048), "granite": (4, 8448), "solar": (4, 24576)}
LAYERS, LANES, LAYER = 2, 6, 1


def _pools(taps, width, dtype, seed=0):
    """The same random rows in both forms: flat ``[layers, rows, n]`` and
    as the pool keeps them."""
    n = (taps - 1) * width
    flat = jax.random.normal(jax.random.key(seed), (LAYERS, 1 + LANES, n),
                             F32).astype(dtype)
    tiled = short_conv.tiles_of(
        flat, jnp.zeros((LAYERS, 1 + LANES)
                        + short_conv.rows_shape(taps, width, dtype), dtype))
    return flat, tiled


def _inputs(taps, width, dtype, rows):
    w = jax.random.normal(jax.random.key(1), (taps, width), F32)
    z = jax.random.normal(jax.random.key(2), (rows, width), F32).astype(dtype)
    return z, w


def _same_rows(tiled, flat, rows):
    """Rows ``rows`` of every layer of the pool, bit for bit those of the
    flat pool, the pad's zeros behind."""
    n = flat.shape[-1]
    got = np.asarray(tiled.astype(F32))[:, rows]
    got = got.reshape(got.shape[:2] + (-1,))
    assert np.array_equal(got[..., :n], np.asarray(flat.astype(F32))[:, rows])
    assert (got[..., n:] == 0).all()


@pytest.mark.parametrize("name,dtype,sublanes", [
    ("lfm2", BF16, 32), ("granite", BF16, 208), ("solar", BF16, 576),
    ("lfm2", F32, 32), ("granite", F32, 200), ("solar", F32, 576)])
def test_a_row_is_whole_tiles_under_an_untiled_index(name, dtype, sublanes):
    """``(taps - 1) x width`` values a row on ``R x 128``, ``R`` whole tiles
    of the dtype (Granite's 198 sublanes pad to 208 in bfloat16); a width
    128 does not divide keeps the flat row."""
    taps, width = SHAPES[name]
    assert short_conv.rows_shape(taps, width, dtype) == (sublanes, 128)
    assert short_conv.rows_shape(4, 320, dtype) == (960,)      # a toy's
    assert short_conv.rows_shape(3, 64, dtype) == (128,)
    flat, tiled = _pools(taps, width, dtype)
    assert tiled.shape == (LAYERS, 1 + LANES, sublanes, 128)
    _same_rows(tiled, flat, np.arange(1 + LANES))
    assert np.array_equal(
        np.asarray(short_conv.flat_of(tiled, taps, width).astype(F32)),
        np.asarray(flat.astype(F32)))


@jax.jit
def _flat_step(flat, z, w, rows):
    """What the three mixers did before PR 57, on the flat pool."""
    K, h = w.shape
    taps = jnp.concatenate([flat[LAYER, rows], z], axis=-1).reshape(-1, K, h)
    conv = jnp.sum(taps.astype(F32) * w, axis=1)
    return conv, flat.at[LAYER, rows].set(taps[:, 1:].reshape(-1, (K - 1) * h))


LIVE = {
    # every lane live, its row its own
    "all_live": [3, 1, 6, 2, 5, 4],
    # three dead lanes share the trash row
    "dead_share_trash": [3, 0, 6, 0, 0, 4],
    # lane 1 released by the host (its table says trash) while row 2, the
    # row it had, already holds a new occupant's prefill
    "released_neighbours_live": [1, 0, 3, 4, 5, 6],
}


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
@pytest.mark.parametrize("lanes", sorted(LIVE))
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_a_decode_step_leaves_what_the_flat_scatter_left(name, lanes, kernel,
                                                         monkeypatch):
    """One token a lane through ``conv_state_update``: the same ``conv``,
    and every row but the trash row bit for bit ``pool.at[layer,
    rows].set(kept)`` on the flat layout — rows no lane's table names, and
    the other layer, untouched."""
    if kernel == "xla":
        monkeypatch.setenv("DSTPU_DISABLE_FLASH", "1")
    taps, width = SHAPES[name]
    rows = jnp.asarray(LIVE[lanes], jnp.int32)
    flat, tiled = _pools(taps, width, BF16)
    z, w = _inputs(taps, width, BF16, LANES)
    want_conv, want = _flat_step(flat, z, w, rows)
    conv, pool = jax.jit(lambda z, w, pool, rows: registry.conv_state_update(
        z, w, (pool, LAYER, rows)))(z, w, tiled, rows)
    assert conv.dtype == F32 and pool.dtype == tiled.dtype
    assert np.array_equal(np.asarray(conv), np.asarray(want_conv))
    _same_rows(pool, want, np.arange(1, 1 + LANES))
    named = sorted(set(LIVE[lanes]) - {0})
    untouched = sorted(set(range(1, 1 + LANES)) - set(named))
    moved = np.asarray(pool.astype(F32)) != np.asarray(tiled.astype(F32))
    assert moved[LAYER, named].any() and not moved[LAYER, untouched].any()
    assert not moved[1 - LAYER].any()


@pytest.mark.parametrize("case", ["continues", "padded_tail",
                                  "fresh_over_a_stale_row"])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_a_chunk_leaves_its_last_real_rows(name, case):
    """A chunk of one slot on the new layout: the row after it holds the
    inputs that end at the chunk's last REAL row — the padded tail's never
    reach it —, a request's first chunk starts from zeros whatever the
    slot's last occupant left in the row, and no other row moves."""
    taps, width = SHAPES[name]
    T, row = 16, 4
    start = 0 if case == "fresh_over_a_stale_row" else 32
    last = T - 5 if case == "padded_tail" else None
    flat, tiled = _pools(taps, width, BF16)
    z, w = _inputs(taps, width, BF16, T)
    before = jnp.zeros_like(flat[LAYER, row]) if start == 0 \
        else flat[LAYER, row]
    seq = jnp.concatenate([before.reshape(taps - 1, width), z])
    end = T if last is None else last + 1
    want_conv = jax.jit(lambda seq, w: sum(
        seq[j:j + T].astype(F32) * w[j] for j in range(taps)))(seq, w)
    want = flat.at[LAYER, row].set(seq[end:end + taps - 1].reshape(-1))
    conv, pool = jax.jit(
        lambda z, w, pool, row, start, last: registry.conv_state_update(
            z, w, (pool, LAYER, row), start=start, last=last))(
        z, w, tiled, jnp.int32(row), jnp.int32(start),
        None if last is None else jnp.int32(last))
    assert np.array_equal(np.asarray(conv), np.asarray(want_conv))
    _same_rows(pool, want, np.arange(1 + LANES))
    # a sequence from its start, nothing kept: the same conv as from zeros
    if start == 0:
        alone, none = jax.jit(lambda z, w: registry.conv_state_update(
            z, w, None, start=0, last=last))(z, w)
        assert none is None
        assert np.array_equal(np.asarray(alone), np.asarray(want_conv))
