"""Block-sparse attention — the long-context workhorse.

TPU-native equivalent of the reference's Triton block-sparse SDD/DSD matmul +
sparse softmax (``deepspeed/ops/sparse_attention/{matmul.py,softmax.py}``,
``csrc/sparse_attention/utils.cpp``) behind ``SparseSelfAttention``
(``sparse_self_attention.py:12``).  Two execution paths:

* **Gather path (default backward, and CPU/XLA fallback)** — for each query
  block, gather its (static) active KV blocks with ``jnp.take`` and run
  attention on the packed ``[bq, A·bk]`` slab.  Pure jnp: differentiable by
  autodiff, fused by XLA, and the FLOPs/memory scale with the layout density
  (A = max active blocks per row), not S².
* **Pallas path (forward)** — a flash-style online-softmax kernel whose grid
  walks only active KV blocks via scalar-prefetched index tables
  (``PrefetchScalarGridSpec``), the splash-attention technique: the layout
  becomes a compile-time-shaped `[H, nq, A]` table, masked per-row by a
  count table.

The custom-vjp wrapper runs the Pallas forward and recomputes the backward
through the gather path — O(S·A·bk) residency, no S×S tensors anywhere.

Layouts come from ``sparsity_config.py`` as ``[num_layout_heads, nb, nb]``
numpy arrays (static at trace time).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import (
    _interpret, pallas_supported)

NEG_INF = -1e30


def layout_tables(layout):
    """Compress a [H, nb, nb] 0/1 layout into per-row index tables.

    Returns (idx, counts): idx [H, nb, A] int32 — the active kv-block
    indices per query block-row, padded with 0; counts [H, nb] int32.
    A = max active blocks over all rows/heads (static).
    """
    layout = np.asarray(layout)
    H, nb, _ = layout.shape
    counts = layout.sum(-1).astype(np.int32)            # [H, nb]
    A = max(1, int(counts.max()))
    idx = np.zeros((H, nb, A), np.int32)
    for h in range(H):
        for r in range(nb):
            cols = np.nonzero(layout[h, r])[0]
            idx[h, r, :len(cols)] = cols
    return idx, counts


def _expand_heads(layout, num_heads):
    layout = np.asarray(layout)
    if layout.shape[0] == 1 and num_heads > 1:
        layout = np.broadcast_to(layout, (num_heads,) + layout.shape[1:])
    assert layout.shape[0] == num_heads, \
        f"layout heads {layout.shape[0]} != attention heads {num_heads}"
    return layout


# --------------------------------------------------------------------- #
# Gather path (jnp; differentiable)
# --------------------------------------------------------------------- #
def _sparse_attn_gather(q, k, v, idx, counts, scale, causal, block):
    """q,k,v: [B, H, S, D]; idx [H, nq, A]; counts [H, nq]."""
    B, H, S, D = q.shape
    nb = S // block
    A = idx.shape[-1]
    qb = q.reshape(B, H, nb, block, D)
    kb = k.reshape(B, H, nb, block, D)
    vb = v.reshape(B, H, nb, block, D)
    idx_j = jnp.asarray(idx)
    # gather active kv blocks per (head, q-row): vmap over heads
    take = jax.vmap(lambda kb_h, idx_h: jnp.take(kb_h, idx_h, axis=1),
                    in_axes=(1, 0), out_axes=1)
    k_sel = take(kb, idx_j)        # [B, H, nq, A, bk, D]
    v_sel = take(vb, idx_j)
    scores = jnp.einsum("bhqid,bhqajd->bhqiaj", qb.astype(jnp.float32),
                        k_sel.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    # mask: inactive slots + causal element mask
    a_ids = jax.lax.broadcasted_iota(jnp.int32, (H, nb, A), 2)
    active = a_ids < jnp.asarray(counts)[:, :, None]     # [H, nq, A]
    mask = active[None, :, :, None, :, None]
    if causal:
        qpos = (jnp.arange(nb)[:, None] * block
                + jnp.arange(block)[None, :])            # [nq, bq]
        kvpos = (idx_j[..., None] * block
                 + jnp.arange(block)[None, None, None, :])  # [H, nq, A, bk]
        cmask = (kvpos[:, :, None, :, :]                  # [H,nq,1,A,bk]
                 <= qpos[None, :, :, None, None])         # -> [H,nq,bq,A,bk]
        mask = jnp.logical_and(mask, cmask[None])
    scores = jnp.where(mask, scores, NEG_INF)
    flat = scores.reshape(B, H, nb, block, A * block)
    m = jnp.max(flat, axis=-1, keepdims=True)
    e = jnp.exp(flat - m)
    # rows with no active kv at all produce 0 output, not NaN
    denom = jnp.sum(e, axis=-1, keepdims=True)
    probs = e / jnp.maximum(denom, 1e-30)
    probs = probs.reshape(B, H, nb, block, A, block)
    out = jnp.einsum("bhqiaj,bhqajd->bhqid", probs,
                     v_sel.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, S, D).astype(q.dtype)


# --------------------------------------------------------------------- #
# Pallas path (forward)
# --------------------------------------------------------------------- #
def _sparse_fwd_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, scale, block, causal, H, A):
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    a = pl.program_id(2)
    h = bh % H

    @pl.when(a == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = a < cnt_ref[h, iq]

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            ik = idx_ref[h, iq, a]
            qpos = iq * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            kvpos = ik * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            s = jnp.where(kvpos <= qpos, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(a == A - 1)
    def _finish():
        l = l_scr[:, 0:1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        # zero rows (no active kv) emit 0
        o_ref[0, 0] = jnp.where(
            l > 0.0, acc_scr[:] / safe_l, 0.0).astype(o_ref.dtype)


def _sparse_fwd_pallas(q, k, v, idx, counts, scale, causal, block):
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    nq = S // block
    A = idx.shape[-1]
    grid = (B * H, nq, A)

    def q_map(bh, iq, a, idx_ref, cnt_ref):
        return (bh // H, bh % H, iq, 0)

    def kv_map(bh, iq, a, idx_ref, cnt_ref):
        # walk only this row's active kv blocks, via the prefetched table
        return (bh // H, bh % H, idx_ref[bh % H, iq, a], 0)

    kernel = functools.partial(_sparse_fwd_kernel, scale=scale, block=block,
                               causal=causal, H=H, A=A)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block, D), q_map),
            pl.BlockSpec((1, 1, block, D), kv_map),
            pl.BlockSpec((1, 1, block, Dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block, Dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        interpret=_interpret(),
        name="attn.block_sparse_fwd",
    )(jnp.asarray(idx), jnp.asarray(counts), q, k, v)


# --------------------------------------------------------------------- #
# Public entry
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _sparse_attention_core(q, k, v, idx_t, cnt_t, scale, causal, block):
    idx, counts = np.asarray(idx_t), np.asarray(cnt_t)
    if pallas_supported():
        return _sparse_fwd_pallas(q, k, v, idx, counts, scale, causal, block)
    return _sparse_attn_gather(q, k, v, idx, counts, scale, causal, block)


def _core_fwd(q, k, v, idx_t, cnt_t, scale, causal, block):
    return (_sparse_attention_core(q, k, v, idx_t, cnt_t, scale, causal, block),
            (q, k, v))


def _core_bwd(idx_t, cnt_t, scale, causal, block, res, g):
    q, k, v = res
    idx, counts = np.asarray(idx_t), np.asarray(cnt_t)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _sparse_attn_gather(q_, k_, v_, idx, counts,
                                               scale, causal, block),
        q, k, v)
    return vjp(g)


_sparse_attention_core.defvjp(_core_fwd, _core_bwd)


def cached_layout(sparsity_config, seq_len, causal=False):
    """Per-config-instance layout cache (the analog of the reference's
    per-seq_len master_layout cache in ``SparseSelfAttention``).  Caching is
    essential for stateful-RNG configs (Variable/BigBird draw random blocks):
    without it every retrace would sample a *different* layout.  When
    ``causal``, strictly-upper blocks are dropped up front so they never
    count into the kernel's A (max-active-blocks) dimension."""
    cache = getattr(sparsity_config, "_layout_cache", None)
    if cache is None:
        cache = {}
        sparsity_config._layout_cache = cache
    key = (seq_len, causal)
    if key not in cache:
        lay = np.asarray(sparsity_config.make_layout(seq_len))
        if causal:
            lay = np.tril(lay)
        cache[key] = lay
    return cache[key]


def block_sparse_attention(q, k, v, layout, block, scale=None, causal=False,
                           key_padding_mask=None):
    """Block-sparse attention over a static layout.

    Args:
      q, k, v: [B, S, H, D] (model-native layout, matching flash_attention).
      layout: [num_layout_heads, nb, nb] 0/1 array (numpy; static).
      block: block size in tokens; S must be divisible.
      causal: additionally mask within diagonal blocks.
      key_padding_mask: optional [B, S] (1 = attend, 0 = pad).  Folded in by
        appending a constant-1 feature to q and a 0/-1e4 bias feature to k —
        padded keys' scores go to -inf without any S×S mask tensor.
    Returns [B, S, H, D].
    """
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if key_padding_mask is not None:
        keep = jnp.asarray(key_padding_mask).astype(bool)        # [B, S]
        big = jnp.where(keep[:, :, None, None], 0.0, -1e4)
        big = jnp.broadcast_to(big, k.shape[:-1] + (1,)).astype(k.dtype)
        ones = jnp.ones(q.shape[:-1] + (1,), q.dtype)
        q = jnp.concatenate([q, ones], axis=-1)
        k = jnp.concatenate([k, big], axis=-1)
    B, S, H, D = q.shape
    assert S % block == 0, f"seq {S} not divisible by block {block}"
    layout = _expand_heads(layout, H)
    assert layout.shape[1] == S // block, \
        f"layout built for {layout.shape[1]} blocks, seq has {S // block}"
    if causal:
        layout = np.tril(layout)  # upper blocks are fully masked anyway
    idx, counts = layout_tables(layout)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # tables ride as hashable static aux via tuples (trace-time constants)
    out = _sparse_attention_core(qt, kt, vt,
                                 _Hashable(idx), _Hashable(counts),
                                 float(scale), bool(causal), int(block))
    return out.transpose(0, 2, 1, 3)


class _Hashable:
    """Wrap a numpy array as a hashable static argument for custom_vjp."""

    def __init__(self, arr):
        self.arr = np.asarray(arr)

    def __hash__(self):
        return hash(self.arr.tobytes())

    def __eq__(self, other):
        return isinstance(other, _Hashable) and \
            np.array_equal(self.arr, other.arr)

    def __array__(self, dtype=None):
        return self.arr if dtype is None else self.arr.astype(dtype)


def sparse_attention_reference(q, k, v, layout, block, scale=None,
                               causal=False):
    """Dense O(S²) reference with the layout as an explicit mask — for tests
    (the analog of the reference's torch reference in
    ``tests/unit/ops/sparse_attention/test_sparse_attention.py``)."""
    B, S, H, D = q.shape
    layout = _expand_heads(layout, H)
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    mask = np.kron(layout, np.ones((block, block)))      # [H, S, S]
    if causal:
        mask = np.tril(np.ones((S, S)))[None] * mask
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bhid,bhjd->bhij", qt, kt) * scale
    s = jnp.where(jnp.asarray(mask[None]) > 0, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    denom = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhij,bhjd->bhid", e / denom, vt)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
