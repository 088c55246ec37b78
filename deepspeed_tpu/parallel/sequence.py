"""Sequence/context parallelism — long-context attention over a seq-sharded
mesh axis.

The reference (v0.9.3) predates DeepSpeed-Ulysses/ring attention (SURVEY §5:
absent; long context = sparse attention + curriculum).  On TPU sequence
sharding is idiomatic, so this module goes beyond parity with both standard
schemes, as differentiable primitives callable inside ``shard_map`` over an
``sp`` axis:

* ``ulysses_attention`` — DeepSpeed-Ulysses style: all_to_all scatters heads
  / gathers sequence, each device runs FULL-sequence attention on H/sp heads
  (the Pallas flash kernel unchanged), all_to_all back.  Comm = 2 all_to_alls
  of activation size; attention math unchanged.  Requires H % sp == 0.
* ``ring_attention`` — KV blocks rotate around the ring (ppermute) while
  queries stay put; online-softmax accumulation combines per-block partial
  results, O(S/sp) live KV per device with no head-count constraint.
  Causal block skipping: a fully-future KV block contributes nothing and is
  skipped via ``jnp.where`` masking of the whole block.

Both are pure jax (scan + collectives) so jax.grad differentiates them;
ring's backward replays the rotation in reverse via autodiff through
``ppermute``.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


# --------------------------------------------------------------------- #
# Ulysses (all-to-all) sequence parallelism
# --------------------------------------------------------------------- #
def ulysses_attention(q, k, v, axis="sp", causal=True, attn_fn=None):
    """q/k/v: this device's [B, S_local, H, D] shard.  Returns the local
    [B, S_local, H, D] output shard."""
    if attn_fn is None:
        from deepspeed_tpu.ops.transformer.flash_attention import (
            flash_attention, pallas_supported)
        if pallas_supported():
            attn_fn = flash_attention
        else:
            from deepspeed_tpu.models.transformer import reference_attention
            attn_fn = reference_attention
    # [B, S/W, H, D] -> [B, S, H/W, D]: scatter heads, gather sequence
    qg = lax.all_to_all(q, axis, split_axis=2, concat_axis=1, tiled=True)
    kg = lax.all_to_all(k, axis, split_axis=2, concat_axis=1, tiled=True)
    vg = lax.all_to_all(v, axis, split_axis=2, concat_axis=1, tiled=True)
    out = attn_fn(qg, kg, vg, causal=causal)
    # back: scatter sequence, gather heads
    return lax.all_to_all(out, axis, split_axis=1, concat_axis=2, tiled=True)


# --------------------------------------------------------------------- #
# Ring attention
# --------------------------------------------------------------------- #
def _block_attn(q, k, v, scale, mask):
    """One KV block's contribution: returns (scores_max, exp-sum, weighted
    values) in fp32 for online combination.  q/k/v: [B, Sq, H, D]."""
    s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)              # [B,H,Sq,1]
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)              # [B,H,Sq,1]
    o = jnp.einsum("bhst,bthd->bhsd", p, v.astype(jnp.float32))
    return m_safe, l, o


def ring_attention(q, k, v, axis="sp", axis_size=None, causal=True,
                   scale=None):
    """Ring flash attention over mesh axis ``axis``.

    q/k/v: [B, S_local, H, D] shards (sequence dim sharded contiguously in
    rank order).  KV rotates ``axis_size`` times; a numerically stable online
    softmax merges block results.  Memory: one KV shard + one [B,H,Sl,Sl]
    block of scores live at a time.
    """
    if axis_size is None:
        axis_size = lax.psum(1, axis)
    W = int(axis_size)
    B, Sl, H, D = q.shape
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    r = lax.axis_index(axis)
    perm = [(j, (j + 1) % W) for j in range(W)]

    rows = jnp.arange(Sl)[:, None]      # local q positions
    cols = jnp.arange(Sl)[None, :]      # local kv positions

    def block_mask_for(src):
        if not causal:
            return None
        # block-level causality: strictly-future chunk → fully masked;
        # same chunk → intra-block causal; past chunk → fully visible
        intra = rows >= cols
        return jnp.where(src == r, intra[None, None],
                         jnp.broadcast_to(src < r, (1, 1, Sl, Sl)))

    def merge(acc, blk):
        m_acc, l_acc, o_acc = acc
        m_b, l_b, o_b = blk
        m_new = jnp.maximum(m_acc, m_b)
        c_acc = jnp.exp(m_acc - m_new)
        c_b = jnp.exp(m_b - m_new)
        return (m_new, l_acc * c_acc + l_b * c_b,
                o_acc * c_acc + o_b * c_b)

    # local chunk first, then rotate W-1 times with the ppermute at the loop
    # head — no wasted final rotation
    acc0 = _block_attn(q, k, v, scale, block_mask_for(r))

    def body(carry, i):
        m_acc, l_acc, o_acc, k_cur, v_cur = carry
        k_cur = lax.ppermute(k_cur, axis, perm)
        v_cur = lax.ppermute(v_cur, axis, perm)
        src = jnp.mod(r - i, W)   # chunk held after i rotations
        blk = _block_attn(q, k_cur, v_cur, scale, block_mask_for(src))
        m_new, l_new, o_new = merge((m_acc, l_acc, o_acc), blk)
        return (m_new, l_new, o_new, k_cur, v_cur), None

    (m, l, o, _, _), _ = lax.scan(body, (*acc0, k, v), jnp.arange(1, W))
    out = o / jnp.maximum(l, 1e-20)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)    # [B, Sl, H, D]


# --------------------------------------------------------------------- #
# dispatcher + mesh-level wrapper
# --------------------------------------------------------------------- #
def sequence_parallel_attention(q, k, v, impl="ulysses", axis="sp",
                                axis_size=None, causal=True):
    if impl == "ulysses":
        return ulysses_attention(q, k, v, axis=axis, causal=causal)
    if impl == "ring":
        return ring_attention(q, k, v, axis=axis, axis_size=axis_size,
                              causal=causal)
    raise ValueError(f"unknown sequence-parallel impl {impl!r} "
                     "(choices: ulysses, ring)")


def shard_map_attention(mesh, impl="ulysses", axis="sp", causal=True,
                        batch_axes=None, head_axes=None):
    """Build a [B, S, H, D] → [B, S, H, D] function where S is sharded over
    ``axis`` of ``mesh`` — the entry point for model integration (callable
    under jit; XLA sees the collectives explicitly).

    ``batch_axes``/``head_axes``: mesh axes the batch / head dims are sharded
    over (dp, tp).  Declaring them keeps shard_map from all-gathering the
    dp-sharded batch onto every device — each device computes only its own
    batch/head shard, with collectives riding the sp axis alone."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map as _shard_map

    def smap(f, **kw):
        return _shard_map(f, mesh=kw["mesh"], in_specs=kw["in_specs"],
                          out_specs=kw["out_specs"], check_vma=False)

    axis_size = int(np.prod([mesh.shape[a] for a in
                             ((axis,) if isinstance(axis, str) else axis)]))
    spec = P(batch_axes, axis, head_axes, None)

    def local(q, k, v):
        return sequence_parallel_attention(q, k, v, impl=impl, axis=axis,
                                           axis_size=axis_size, causal=causal)

    return smap(local, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec)
