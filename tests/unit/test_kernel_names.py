"""Every ``pl.pallas_call`` of the package carries a stable ``name=``: jax
wraps a named call in ``named_scope(name)``, so the name is in the lowered
program's locations (interpret mode here; on the chip it becomes the
``tpu_custom_call`` instruction's name, which the benchmark's trace readers
and ``breakdown.device_ops`` tell the kernels apart by)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.sparse_attention import block_sparse
from deepspeed_tpu.ops.transformer import (decode_attention as decode_mod,
                                           delta_attention as delta_mod,
                                           flash_attention as flash_mod,
                                           paged_attention as paged_mod,
                                           short_conv as conv_mod,
                                           ssd as ssd_mod)

H, D, L = 2, 64, 2
HD = H * D
F32, I32 = jnp.float32, jnp.int32


def _flash(grad, seq=256, head_dim=D):
    def loss(q, k, v):
        return flash_mod.flash_attention(q, k, v, causal=True).sum()
    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else loss
    return fn, [((1, seq, 1, head_dim), F32)] * 3


def _paged_decode():
    pool = ((L, 5, 64, HD), F32)

    def fn(q, k_pool, v_pool, lengths, pages):
        return paged_mod.paged_decode_attention(q, k_pool, v_pool, lengths,
                                                pages, layer=1)
    return fn, [((2, H, D), F32), pool, pool, ((2,), I32), ((2, 2), I32)]


def _paged_chunk():
    pool = ((L, 5, 64, HD), F32)

    def fn(q, k_pool, v_pool, starts, pages):
        return paged_mod.paged_chunk_prefill_attention(
            q, k_pool, v_pool, starts, pages, layer=1)
    return fn, [((1, 64, H, D), F32), pool, pool, ((1,), I32), ((1, 2), I32)]


def _mono_chunk():
    cache = ((L, 1, 256, HD), F32)

    def fn(q, k_cache, v_cache, starts):
        return decode_mod.chunk_prefill_attention(q, k_cache, v_cache, starts,
                                                  layer=1)
    return fn, [((1, 64, H, D), F32), cache, cache, ((1,), I32)]


def _mono_decode():
    cache = ((L, 2, 256, HD), F32)

    def fn(q, k_cache, v_cache, lengths):
        return decode_mod.decode_attention(q, k_cache, v_cache, lengths,
                                           layer=1)
    return fn, [((2, H, D), F32), cache, cache, ((2,), I32)]


def _block_sparse():
    layout = np.tril(np.ones((1, 2, 2), np.int32))

    def fn(q, k, v):
        return block_sparse.block_sparse_attention(q, k, v, layout, 128,
                                                   causal=True)
    return fn, [((1, 256, H, D), F32)] * 3


def _kda(chunk):
    """The two state kernels of the gated delta rule over a pool ``[layers,
    rows, heads, d, d]``."""
    pool, n = ((L, 3, H, 16, 16), F32), 64 if chunk else 2
    rows = ((n, H, 16), F32)

    def fn(q, k, v, g, beta, pool, at):
        if chunk:
            return delta_mod.chunk_scan(q, k, v, g, beta, pool, 1, at[0],
                                        fresh=False, real=n)
        return delta_mod.decode_step(q, k, v, g, beta, pool, 1, at)
    return fn, [rows] * 4 + [((n, H), F32), pool, ((n,), I32)]


def _ssd(chunk):
    """The two state kernels of the Mamba-2 scan over a pool ``[layers,
    rows, ...state_shape]``."""
    pool, n = ((L, 3) + ssd_mod.state_shape(H, 64, 16), F32), \
        128 if chunk else 2
    rows = [((n, H, 64), F32), ((n, H), F32), ((n, H), F32),
            ((n, 16), F32), ((n, 16), F32)]

    def fn(x, dt, a, b, c, pool, at):
        if chunk:
            return ssd_mod.chunk_scan(x, dt, a, b, c, pool, 1, at[0],
                                      fresh=False, real=n)
        return ssd_mod.decode_step(x, dt, a, b, c, pool, 1, at)
    return fn, rows + [pool, ((n,), I32)]


def _conv_step():
    """The two row kernels of a short convolution's decode step over a pool
    ``[layers, rows, ...rows_shape]``: the lanes' rows out, the rows to keep
    back in."""
    pool = ((L, 3) + conv_mod.rows_shape(3, 128, F32), F32)

    def fn(z, w, pool, at):
        return conv_mod.decode_step(z, w, pool, 1, at)
    return fn, [((2, 128), F32), ((3, 128), F32), pool, ((2,), I32)]


CASES = {
    "conv.rows_read": _conv_step,
    "conv.rows_write": _conv_step,
    "ssd.chunk_scan": lambda: _ssd(True),
    "ssd.decode_step": lambda: _ssd(False),
    "kda.chunk_scan": lambda: _kda(True),
    "kda.decode_step": lambda: _kda(False),
    "attn.paged_decode": _paged_decode,
    "attn.paged_chunk_prefill": _paged_chunk,
    "attn.flash_fwd": lambda: _flash(False),
    "attn.flash_dq_dkv": lambda: _flash(True),
    # a head whose dq the fused backward has no room to sum: the pair
    "attn.flash_dq": lambda: _flash(True, 8192, 128),
    "attn.flash_dkv": lambda: _flash(True, 8192, 128),
    "attn.chunk_prefill": _mono_chunk,
    "attn.decode": _mono_decode,
    "attn.block_sparse_fwd": _block_sparse,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_name_is_in_the_lowered_location(name):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    # a transform may wrap the scope: "transpose(jvp(attn.flash_dq))"
    assert any(name + end in text for end in ("/", ")", '"')), \
        f"no location names {name}"
