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

Causal masking skips fully-masked KV blocks via ``pl.when`` predication.
The backward pass uses the saved LSE (log-sum-exp) rows, with two kernels:
one accumulating dq over kv blocks, one accumulating (dk, dv) over q blocks —
the standard flash-attention-2 decomposition.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import os as _os

# tuned on v5e at seq 2048/head_dim 64: large kv blocks amortize the
# VPU-bound online-softmax bookkeeping; q=512 beats 256 and 1024 on the
# OPT-1.3B train workload (larger bwd blocks overflow scoped vmem)
DEFAULT_BLOCK_Q = int(_os.environ.get("DSTPU_FLASH_BLOCK_Q", "512"))
DEFAULT_BLOCK_K = int(_os.environ.get("DSTPU_FLASH_BLOCK_K", "2048"))
DEFAULT_BLOCK_Q_BWD = int(_os.environ.get("DSTPU_FLASH_BLOCK_Q_BWD", "1024"))
DEFAULT_BLOCK_K_BWD = int(_os.environ.get("DSTPU_FLASH_BLOCK_K_BWD", "1024"))
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
# Forward
# --------------------------------------------------------------------- #
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, block_q, block_k, causal, nk, kv_len):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # block classification: interior blocks (fully inside the causal
    # triangle and inside the sequence) skip all mask/iota VPU work — with
    # online softmax that work is a large share of kernel time at small D
    even_kv = kv_len % block_k == 0
    run = (not causal) or (ik * block_k <= iq * block_q + block_q - 1)
    diag = causal and (ik * block_k + block_k > iq * block_q)
    needs_mask = diag if even_kv else True

    def _softmax_update(s, v):
        m_prev = m_scr[:, 0:1]                        # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # [bq, bk] f32
        corr = jnp.exp(m_prev - m_new)                # [bq, 1]
        l_new = l_scr[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(run & jnp.logical_not(needs_mask))
    def _interior():
        # operands stay bf16 — the MXU accumulates in fp32 via
        # preferred_element_type; casting inputs to fp32 would halve
        # matmul throughput
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:        # scale is folded into q by the wrapper
            s = s * scale
        _softmax_update(s, v_ref[0, 0])

    @pl.when(run & needs_mask)
    def _masked():
        q = q_ref[0, 0]                              # [bq, d]
        k = k_ref[0, 0]                              # [bk, d]
        v = v_ref[0, 0]                              # [bk, d]
        if not even_kv:
            # zero padded tail rows: OOB block reads are undefined, and
            # garbage * 0-probability still poisons the matmul with NaN
            kv_rows = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                              (block_k, 1), 0)
            valid_kv = kv_rows < kv_len
            k = jnp.where(valid_kv, k, jnp.zeros_like(k))
            v = jnp.where(valid_kv, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:
            s = s * scale
        cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 1)
        if even_kv:
            # only diagonal blocks reach here — causal mask alone
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
            mask = rows >= cols
        else:
            mask = cols < kv_len       # tail-block padding
            if causal:
                rows = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                mask = mask & (rows >= cols)
        s = jnp.where(mask, s, NEG_INF)
        _softmax_update(s, v)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:, 0:1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # LSE rides a 128-lane trailing dim: Mosaic requires output block
        # shapes tiled (8, 128) on the last two dims, so a [block_q]-shaped
        # row per (b, h) cannot be written directly
        lse_ref[0, 0] = jnp.broadcast_to(m_scr[:, 0:1] + jnp.log(safe_l),
                                         lse_ref.shape[2:])


def _fwd(q, k, v, scale, causal, block_q, block_k):
    B, H, S, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(Sk, block_k)
    grid = (B * H, nq, nk)

    def q_map(bh, iq, ik):
        return (bh // H, bh % H, iq, 0)

    def kv_map(bh, iq, ik):
        return (bh // H, (bh % H) * KVH // H, ik, 0)

    kernel = functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal, nk=nk, kv_len=Sk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_q, LSE_LANES),
                         lambda bh, iq, ik: (bh // H, bh % H, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="attn.flash_fwd",
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------- #
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, block_q, block_k, causal, nk, kv_len):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    even_kv = kv_len % block_k == 0
    run = (not causal) or (ik * block_k <= iq * block_q + block_q - 1)
    diag = causal and (ik * block_k + block_k > iq * block_q)
    needs_mask = diag if even_kv else True

    def _accum(p, do, v, k, delta):
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if scale != 1.0:
            ds = ds * scale
        ds = ds.astype(k.dtype)
        dq_scr[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(run & jnp.logical_not(needs_mask))
    def _interior():
        lse = lse_ref[0, 0][:, 0:1]                  # [bq, 1]
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:        # scale is folded into q by the wrapper
            s = s * scale
        p = jnp.exp(s - lse)                          # [bq, bk]
        _accum(p, do_ref[0, 0], v_ref[0, 0], k_ref[0, 0],
               delta_ref[0, 0][:, 0:1])

    @pl.when(run & needs_mask)
    def _masked():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]                  # [bq, 1]
        delta = delta_ref[0, 0][:, 0:1]              # [bq, 1]
        if not even_kv:
            kv_rows = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                              (block_k, 1), 0)
            valid_kv = kv_rows < kv_len
            k = jnp.where(valid_kv, k, jnp.zeros_like(k))
            v = jnp.where(valid_kv, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:
            s = s * scale
        cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 1)
        if even_kv:
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
            mask = rows >= cols
        else:
            mask = cols < kv_len
            if causal:
                rows = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                mask = mask & (rows >= cols)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)    # [bq, bk]
        _accum(p, do, v, k, delta)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, block_q, block_k, causal, nq, q_len):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    even_q = q_len % block_q == 0
    run = (not causal) or (iq * block_q + block_q - 1 >= ik * block_k)
    diag = causal and (iq * block_q < ik * block_k + block_k)
    needs_mask = diag if even_q else True

    def _accum(p, q, v, do, delta):
        dv_scr[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                         # [bq, bk]
        if scale != 1.0:
            ds = ds * scale
        ds = ds.astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(run & jnp.logical_not(needs_mask))
    def _interior():
        lse = lse_ref[0, 0][:, 0:1]
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:        # scale is folded into q by the wrapper
            s = s * scale
        p = jnp.exp(s - lse)
        _accum(p, q_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
               delta_ref[0, 0][:, 0:1])

    @pl.when(run & needs_mask)
    def _masked():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]
        delta = delta_ref[0, 0][:, 0:1]
        if not even_q:
            q_rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                             (block_q, 1), 0)
            valid_q = q_rows < q_len
            q = jnp.where(valid_q, q, jnp.zeros_like(q))
            do = jnp.where(valid_q, do, jnp.zeros_like(do))
            # delta/lse of padded rows are OOB reads; 0*garbage must stay
            # finite
            delta = jnp.where(valid_q, delta, 0.0)
            lse = jnp.where(valid_q, lse, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:
            s = s * scale
        rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 0)
        if even_q:
            cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 1)
            mask = rows >= cols
        else:
            mask = rows < q_len
            if causal:
                cols = ik * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                mask = mask & (rows >= cols)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)    # [bq, bk]
        _accum(p, q, v, do, delta)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, block_q_bwd, block_k_bwd, res, do):
    q, k, v, out, lse = res
    B, H, S, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    block_q = min(block_q_bwd, S)
    block_k = min(block_k_bwd, Sk)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(Sk, block_k)

    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1)[..., None],
        lse.shape)

    def q_map(bh, iq, ik):
        return (bh // H, bh % H, iq, 0)

    def kv_map(bh, iq, ik):
        return (bh // H, (bh % H) * KVH // H, ik, 0)

    def lse_map(bh, iq, ik):
        return (bh // H, bh % H, iq, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, nk=nk, kv_len=Sk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_q, LSE_LANES), lse_map),
            pl.BlockSpec((1, 1, block_q, LSE_LANES), lse_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="attn.flash_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv computed per (b, h) then reduced over the query-head group for GQA
    def kv_out_map(bh, ik, iq):
        return (bh // H, bh % H, ik, 0)

    def q_map2(bh, ik, iq):
        return (bh // H, bh % H, iq, 0)

    def kv_map2(bh, ik, iq):
        return (bh // H, (bh % H) * KVH // H, ik, 0)

    def lse_map2(bh, ik, iq):
        return (bh // H, bh % H, iq, 0)

    dk_full, dv_full = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, nq=nq, q_len=S),
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map2),
            pl.BlockSpec((1, 1, block_k, D), kv_map2),
            pl.BlockSpec((1, 1, block_k, D), kv_map2),
            pl.BlockSpec((1, 1, block_q, D), q_map2),
            pl.BlockSpec((1, 1, block_q, LSE_LANES), lse_map2),
            pl.BlockSpec((1, 1, block_q, LSE_LANES), lse_map2),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), kv_out_map),
            pl.BlockSpec((1, 1, block_k, D), kv_out_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="attn.flash_dkv",
    )(q, k, v, do, lse, delta)

    if KVH != H:
        rep = H // KVH
        dk = dk_full.reshape(B, KVH, rep, Sk, D).sum(axis=2)
        dv = dv_full.reshape(B, KVH, rep, Sk, D).sum(axis=2)
    else:
        dk, dv = dk_full, dv_full
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


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
    # the backward pass just to regenerate lse (out: bf16 B·S·H·D; lse: 8-lane
    # f32 — together ~20MB/layer at opt-350m/2048, far cheaper than a
    # recompute)
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, causal=True, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    block_q_bwd=None, block_k_bwd=None):
    """Flash attention on [B, S, H, D] tensors (model-native layout).

    ``k``/``v`` may have fewer heads (GQA).  Returns [B, S, H, D].
    The backward kernels tile independently (their accumulators iterate the
    opposite grid dim; v5e sweep favors 1024x1024 there): ``block_q_bwd`` /
    ``block_k_bwd`` default from DSTPU_FLASH_BLOCK_{Q,K}_BWD.
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
                      int(block_q), int(block_k),
                      int(block_q_bwd), int(block_k_bwd))
    return out.transpose(0, 2, 1, 3)


# parity alias for the reference inference op name
softmax_context = flash_attention
