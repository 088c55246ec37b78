"""Pallas decode-attention kernel vs the XLA cached_attention reference.

Caches are S-major with flattened heads — [B, S_max, KVH*D] (layer-stacked:
[L, B, S_max, KVH*D]) — the decode kernel's full-lane-width DMA layout.
Helpers below build them from head-major [B, KVH, S, D] test data.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import cached_attention
from deepspeed_tpu.ops.transformer.decode_attention import decode_attention


def to_smajor(head_major):
    """[.., KVH, S, D] → [.., S, KVH*D]"""
    *lead, KVH, S, D = head_major.shape
    x = jnp.moveaxis(head_major, -3, -2)                 # [.., S, KVH, D]
    return x.reshape(*lead, S, KVH * D)


def xla_cached_attention(*args, **kwargs):
    """cached_attention forced down the einsum path — WITHOUT this guard the
    S==1 dispatch would route both sides of every comparison through the
    kernel under test."""
    os.environ["DSTPU_DISABLE_FLASH"] = "1"
    try:
        return cached_attention(*args, **kwargs)
    finally:
        del os.environ["DSTPU_DISABLE_FLASH"]


@pytest.mark.parametrize("kvh", [8, 2])   # MHA + GQA
@pytest.mark.parametrize("length", [1, 17, 64])
def test_decode_matches_cached_attention(kvh, length):
    B, H, D, S_max = 2, 8, 16, 64
    rng = np.random.default_rng(length * 10 + kvh)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.zeros((B, kvh, S_max, D), jnp.float32)
    v = jnp.zeros((B, kvh, S_max, D), jnp.float32)
    k = k.at[:, :, :length].set(rng.standard_normal((B, kvh, length, D)))
    v = v.at[:, :, :length].set(rng.standard_normal((B, kvh, length, D)))
    k, v = to_smajor(k), to_smajor(v)
    pos = jnp.full((B, 1), length - 1, jnp.int32)
    want = np.asarray(xla_cached_attention(q, k, v, pos))          # [B,1,H,D]
    got = np.asarray(decode_attention(
        q[:, 0], k, v, jnp.full((B,), length, jnp.int32)))     # [B,H,D]
    np.testing.assert_allclose(got, want[:, 0], rtol=2e-5, atol=2e-5)


def test_decode_per_batch_lengths():
    """Each batch row masks by its own cache length."""
    B, H, D, S_max = 3, 4, 8, 32
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    lengths = jnp.asarray([1, 16, 32], jnp.int32)
    got = np.asarray(decode_attention(q, ks, vs, lengths))
    for b, L in enumerate([1, 16, 32]):
        pos = jnp.asarray([[L - 1]], jnp.int32)
        want = np.asarray(xla_cached_attention(
            q[b:b + 1, None], ks[b:b + 1], vs[b:b + 1], pos))[0, 0]
        np.testing.assert_allclose(got[b], want, rtol=2e-5, atol=2e-5)


def test_decode_blocked_cache():
    """Cache longer than one KV block exercises the online accumulation."""
    B, H, D, S_max = 1, 8, 16, 2048
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    L = 1500
    got = np.asarray(decode_attention(q, ks, vs,
                                      jnp.asarray([L], jnp.int32),
                                      block_k=512))
    want = np.asarray(xla_cached_attention(
        q[:, None], ks, vs, jnp.asarray([[L - 1]], jnp.int32)))[:, 0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_decode_stacked_layer_indexing():
    """The layer-stacked cache path (kernel DMAs the layer's blocks via a
    scalar-prefetch index map — no per-layer slice materializes) is
    bit-identical to slicing the layer out first."""
    rng = np.random.default_rng(0)
    L, B, KVH, S, D, H = 3, 2, 4, 64, 32, 8
    k = jnp.asarray(rng.standard_normal((L, B, KVH, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, B, KVH, S, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    lengths = jnp.asarray([30, 50], jnp.int32)
    for li in range(L):
        stacked = decode_attention(q, ks, vs, lengths, layer=jnp.asarray(li))
        sliced = decode_attention(q, ks[li], vs[li], lengths)
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(sliced))
    # stacked caches demand a layer index
    with pytest.raises(ValueError):
        decode_attention(q, ks, vs, lengths)


def quantize_smajor(cache_smajor, kvh):
    """[.., S, KVH*D] float → (int8 payload, [.., S, KVH] scales)."""
    *lead, S, KVHD = cache_smajor.shape
    d = KVHD // kvh
    r = np.asarray(cache_smajor).reshape(*lead, S, kvh, d)
    s = np.max(np.abs(r), axis=-1) / 127.0
    safe = np.where(s == 0.0, 1.0, s)
    pay = np.clip(np.round(r / safe[..., None]), -127, 127)
    return (jnp.asarray(pay.reshape(*lead, S, KVHD), jnp.int8),
            jnp.asarray(s, jnp.float32))


@pytest.mark.parametrize("kvh", [8, 2])   # MHA + GQA
def test_decode_int8_kv_matches_dequantized_reference(kvh):
    """int8-KV decode: the kernel's in-tile dequant (k-scale on the score
    tile, v-scale on the probability tile) must match attention computed
    on the explicitly dequantized payload — same ints in, same math."""
    B, H, D, S_max, L = 2, 8, 16, 96, 70
    rng = np.random.default_rng(kvh)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = rng.standard_normal((B, kvh, S_max, D)) * 3.0
    v = rng.standard_normal((B, kvh, S_max, D))
    ks, vs = to_smajor(jnp.asarray(k, jnp.float32)), \
        to_smajor(jnp.asarray(v, jnp.float32))
    kq, ksc = quantize_smajor(ks, kvh)
    vq, vsc = quantize_smajor(vs, kvh)
    lengths = jnp.asarray([L, 31], jnp.int32)
    got = np.asarray(decode_attention(q, kq, vq, lengths, block_k=32,
                                      k_scale=ksc, v_scale=vsc))
    # reference on the dequantized payload through the dense path
    kdq = (np.asarray(kq, np.float32).reshape(B, S_max, kvh, D)
           * np.asarray(ksc)[..., None]).reshape(B, S_max, kvh * D)
    vdq = (np.asarray(vq, np.float32).reshape(B, S_max, kvh, D)
           * np.asarray(vsc)[..., None]).reshape(B, S_max, kvh * D)
    for b, Lb in enumerate([L, 31]):
        pos = jnp.asarray([[Lb - 1]], jnp.int32)
        want = np.asarray(xla_cached_attention(
            q[b:b + 1, None], jnp.asarray(kdq[b:b + 1]),
            jnp.asarray(vdq[b:b + 1]), pos))[0, 0]
        np.testing.assert_allclose(got[b], want, rtol=2e-5, atol=2e-5)


def test_decode_int8_kv_stacked_layer():
    """Layer-stacked int8 cache: scale blocks index the layer the same way
    the payload blocks do."""
    rng = np.random.default_rng(3)
    Lyr, B, KVH, S, D, H = 3, 2, 4, 64, 32, 8
    k = jnp.asarray(rng.standard_normal((Lyr, B, KVH, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((Lyr, B, KVH, S, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    kq, ksc = quantize_smajor(ks, KVH)
    vq, vsc = quantize_smajor(vs, KVH)
    lengths = jnp.asarray([30, 50], jnp.int32)
    for li in range(Lyr):
        stacked = decode_attention(q, kq, vq, lengths,
                                   layer=jnp.asarray(li),
                                   k_scale=ksc, v_scale=vsc)
        sliced = decode_attention(q, kq[li], vq[li], lengths,
                                  k_scale=ksc[li], v_scale=vsc[li])
        np.testing.assert_array_equal(np.asarray(stacked),
                                      np.asarray(sliced))


def test_int8_kv_generation_end_to_end():
    """kv_cache_quant through the full model decode: logits after several
    cached decode steps stay close to the bf16-cache logits (int8
    per-(position, head) scales keep the attention error ~1%)."""
    from deepspeed_tpu.models.transformer import Transformer, TransformerConfig
    ids = np.random.default_rng(0).integers(0, 64, (2, 12)).astype(np.int32)

    def run(quant):
        cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2,
                                num_heads=4, max_seq_len=32, dtype="float32",
                                use_flash_attention=False, scan_layers=False,
                                kv_cache_quant=quant)
        model = Transformer(cfg)
        params = model.init(jax.random.key(0), {"input_ids": ids})
        cache = model.init_cache(2, 32)
        if quant:
            assert cache["k"].dtype == jnp.int8 and "k_scale" in cache
        logits, cache = model.apply(params, jnp.asarray(ids), cache, 0,
                                    method=Transformer.decode)
        outs = [np.asarray(logits[:, -1])]
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        for step in range(3):
            logits, cache = model.apply(params, tok, cache, 12 + step,
                                        method=Transformer.decode)
            outs.append(np.asarray(logits[:, -1]))
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        return np.stack(outs)

    ref = run(False)
    got = run(True)
    err = np.abs(got - ref).mean()
    assert err < 0.02 * np.abs(ref).mean() + 1e-3, err


@pytest.mark.parametrize("kvh", [8, 2])
def test_decode_int8_mxu_matmuls_accuracy(kvh):
    """Full-int8 MXU decode (int8_matmuls): q and the probability rows are
    additionally quantized so the score and PV matmuls run int8×int8 —
    the output must stay within ~1% of the exact dequantized-reference
    attention."""
    B, H, D, S_max, L = 2, 8, 16, 96, 70
    rng = np.random.default_rng(kvh + 100)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = rng.standard_normal((B, kvh, S_max, D)) * 2.0
    v = rng.standard_normal((B, kvh, S_max, D))
    ks, vs = to_smajor(jnp.asarray(k, jnp.float32)), \
        to_smajor(jnp.asarray(v, jnp.float32))
    kq, ksc = quantize_smajor(ks, kvh)
    vq, vsc = quantize_smajor(vs, kvh)
    lengths = jnp.asarray([L, 31], jnp.int32)
    exact = np.asarray(decode_attention(q, kq, vq, lengths, block_k=32,
                                        k_scale=ksc, v_scale=vsc))
    fast = np.asarray(decode_attention(q, kq, vq, lengths, block_k=32,
                                       k_scale=ksc, v_scale=vsc,
                                       int8_matmuls=True))
    err = np.abs(fast - exact).mean() / (np.abs(exact).mean() + 1e-9)
    assert err < 0.015, err
    # int8_matmuls without quantized caches is rejected
    with pytest.raises(ValueError, match="int8_matmuls"):
        decode_attention(q, ks, vs, lengths, int8_matmuls=True)


@pytest.mark.parametrize("window", [8, 40, 200])
def test_decode_sliding_window(window):
    """Sliding-window decode (mistral-style) in-kernel: only the last
    `window` positions before the query are live — matches the dense
    cached_attention window path per batch row, including per-batch
    lengths and windows larger than the live cache."""
    B, H, D, S_max = 3, 4, 16, 128
    rng = np.random.default_rng(window)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    lens = [5, 64, 128]
    got = np.asarray(decode_attention(q[:, 0], ks, vs,
                                      jnp.asarray(lens, jnp.int32),
                                      block_k=32, window=window))
    for b, L in enumerate(lens):
        pos = jnp.asarray([[L - 1]], jnp.int32)
        want = np.asarray(xla_cached_attention(
            q[b:b + 1], ks[b:b + 1], vs[b:b + 1], pos,
            window=window))[0, 0]
        np.testing.assert_allclose(got[b], want, rtol=2e-5, atol=2e-5)


def test_decode_short_lengths_exact():
    """Dead-region DMA pinning (indices past `lengths` pin to the last live
    block so Mosaic skips their copies) must not change results, including
    degenerate lengths and block-boundary lengths."""
    rng = np.random.default_rng(0)
    B, KVH, S, D, H = 4, 4, 256, 32, 4
    k = jnp.asarray(rng.standard_normal((B, KVH, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KVH, S, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    for lens in ([1, 5, 64, 65], [256, 128, 127, 2]):
        lengths = jnp.asarray(lens, jnp.int32)
        got = np.asarray(decode_attention(q, ks, vs, lengths, block_k=64))
        for b in range(B):
            for h in range(KVH):
                s = (np.asarray(q[b, h]) @ np.asarray(k[b, h]).T) / np.sqrt(D)
                s[lens[b]:] = -np.inf
                p = np.exp(s - s.max())
                p /= p.sum()
                ref = p @ np.asarray(v[b, h])
                np.testing.assert_allclose(got[b, h], ref, rtol=2e-5,
                                           atol=2e-5)


# --------------------------------------------------------------------- #
# chunk_prefill_attention — the chunked-prefill kernel
# --------------------------------------------------------------------- #

from deepspeed_tpu.ops.transformer.decode_attention import \
    chunk_prefill_attention


@pytest.mark.parametrize("kvh", [8, 2])   # MHA + GQA
@pytest.mark.parametrize("start", [0, 24])
def test_chunk_prefill_matches_cached_attention(kvh, start):
    """A C-token chunk at offset ``start`` must match the dense cached
    path (causal within the chunk + full attention to the prefix)."""
    B, H, D, S_max, C = 2, 8, 16, 64, 16
    rng = np.random.default_rng(start * 10 + kvh)
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, kvh, S_max, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, kvh, S_max, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    pos = start + jnp.broadcast_to(jnp.arange(C), (B, C))
    want = np.asarray(xla_cached_attention(q, ks, vs, pos.astype(jnp.int32)))
    got = np.asarray(chunk_prefill_attention(
        q, ks, vs, jnp.full((B,), start, jnp.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_chunk_prefill_blocked_and_per_row_starts():
    """Multi-block cache + per-row starts: each row's chunk begins at its
    own offset (padded-prompt chunked prefill)."""
    B, H, D, S_max, C = 2, 4, 8, 256, 32
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    starts = jnp.asarray([64, 128], jnp.int32)
    got = np.asarray(chunk_prefill_attention(q, ks, vs, starts, block_k=64))
    for b in range(B):
        pos = (int(starts[b]) + jnp.arange(C))[None].astype(jnp.int32)
        want = np.asarray(xla_cached_attention(
            q[b:b + 1], ks[b:b + 1], vs[b:b + 1], pos))[0]
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-4)


def test_chunk_prefill_stacked_int8():
    """Layer-stacked int8 cache through the chunk kernel == dense math on
    the dequantized payload."""
    rng = np.random.default_rng(3)
    L, B, KVH, S_max, D, H, C = 2, 2, 4, 96, 16, 8, 16
    k = rng.standard_normal((L, B, KVH, S_max, D)) * 3.0
    v = rng.standard_normal((L, B, KVH, S_max, D))
    ks = to_smajor(jnp.asarray(k, jnp.float32))
    vs = to_smajor(jnp.asarray(v, jnp.float32))
    kq, ksc = quantize_smajor(ks, KVH)
    vq, vsc = quantize_smajor(vs, KVH)
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
    starts = jnp.asarray([32, 5], jnp.int32)
    for li in range(L):
        got = np.asarray(chunk_prefill_attention(
            q, kq, vq, starts, block_k=32, layer=jnp.asarray(li),
            k_scale=ksc, v_scale=vsc))
        kdq = (np.asarray(kq[li], np.float32).reshape(B, S_max, KVH, D)
               * np.asarray(ksc[li])[..., None]).reshape(B, S_max, KVH * D)
        vdq = (np.asarray(vq[li], np.float32).reshape(B, S_max, KVH, D)
               * np.asarray(vsc[li])[..., None]).reshape(B, S_max, KVH * D)
        for b in range(B):
            pos = (int(starts[b]) + jnp.arange(C))[None].astype(jnp.int32)
            want = np.asarray(xla_cached_attention(
                q[b:b + 1], jnp.asarray(kdq[b:b + 1]),
                jnp.asarray(vdq[b:b + 1]), pos))[0]
            np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------- #
# fused in-kernel cache write (new_k/new_v)
# --------------------------------------------------------------------- #

def _write_rows_ref(cache, rows, lengths):
    """Reference: write rows [B, KVH*D] at per-row positions lengths-1."""
    out = np.asarray(cache).copy()
    for b in range(out.shape[0]):
        out[b, int(lengths[b]) - 1] = rows[b]
    return jnp.asarray(out)


@pytest.mark.parametrize("kvh", [4, 2])
def test_fused_write_matches_prewrite(kvh):
    """decode_attention(new_k=, new_v=) must equal pre-writing the row
    then attending — same outputs AND same cache contents afterward."""
    B, H, D, S_max = 3, 4, 16, 128
    rng = np.random.default_rng(kvh)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, kvh, S_max, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, kvh, S_max, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    lengths = jnp.asarray([5, 64, 128], jnp.int32)   # incl. a block edge
    kn = rng.standard_normal((B, kvh, D)).astype(np.float32)
    vn = rng.standard_normal((B, kvh, D)).astype(np.float32)
    # reference: write first, then plain kernel
    ks_w = _write_rows_ref(ks, kn.reshape(B, kvh * D), lengths)
    vs_w = _write_rows_ref(vs, vn.reshape(B, kvh * D), lengths)
    want = np.asarray(decode_attention(q, ks_w, vs_w, lengths, block_k=32))
    got, ko, vo = decode_attention(q, ks, vs, lengths, block_k=32,
                                   new_k=jnp.asarray(kn),
                                   new_v=jnp.asarray(vn))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ko), np.asarray(ks_w),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vs_w),
                               rtol=1e-6, atol=1e-6)


def test_fused_write_int8_stacked():
    """Quantized + layer-stacked fused write: payload/scale rows written
    by the kernel must match the model's quantization, and the attention
    must match the unfused write-then-read path."""
    rng = np.random.default_rng(0)
    L, B, KVH, S_max, D, H = 2, 2, 4, 96, 16, 8
    k = rng.standard_normal((L, B, KVH, S_max, D)) * 3.0
    v = rng.standard_normal((L, B, KVH, S_max, D))
    ksm = to_smajor(jnp.asarray(k, jnp.float32))
    vsm = to_smajor(jnp.asarray(v, jnp.float32))
    kq, ksc = quantize_smajor(ksm, KVH)
    vq, vsc = quantize_smajor(vsm, KVH)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    lengths = jnp.asarray([33, 80], jnp.int32)
    kn = jnp.asarray(rng.standard_normal((B, KVH, D)) * 3.0, jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, KVH, D)), jnp.float32)
    for li in range(L):
        got, ko, vo, kso, vso = decode_attention(
            q, kq, vq, lengths, block_k=32, layer=jnp.asarray(li),
            k_scale=ksc, v_scale=vsc, new_k=kn, new_v=vn)
        # reference: quantize the rows the model's way, write, then attend
        def quant_rows(new):
            r = np.asarray(new, np.float32)
            s = np.max(np.abs(r), axis=-1) / 127.0
            safe = np.where(s == 0.0, 1.0, s)
            pay = np.clip(np.round(r / safe[..., None]), -127, 127)
            return pay, s
        kpay, ksn = quant_rows(kn)
        vpay, vsn = quant_rows(vn)
        kq_w = np.asarray(kq).copy()
        vq_w = np.asarray(vq).copy()
        ksc_w = np.asarray(ksc).copy()
        vsc_w = np.asarray(vsc).copy()
        for b in range(B):
            p = int(lengths[b]) - 1
            kq_w[li, b, p] = kpay[b].reshape(-1)
            vq_w[li, b, p] = vpay[b].reshape(-1)
            ksc_w[li, b, p] = ksn[b]
            vsc_w[li, b, p] = vsn[b]
        want = np.asarray(decode_attention(
            q, jnp.asarray(kq_w), jnp.asarray(vq_w), lengths, block_k=32,
            layer=jnp.asarray(li), k_scale=jnp.asarray(ksc_w),
            v_scale=jnp.asarray(vsc_w)))
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(ko)[li, [0, 1],
                                                     lengths - 1],
                                      kq_w[li, [0, 1], lengths - 1])
        np.testing.assert_array_equal(np.asarray(vo)[li, [0, 1],
                                                     lengths - 1],
                                      vq_w[li, [0, 1], lengths - 1])
        np.testing.assert_allclose(
            np.asarray(kso)[li, [0, 1], lengths - 1],
            ksc_w[li, [0, 1], lengths - 1], rtol=1e-6, atol=1e-6)
        # untouched rows preserved through the aliased outputs
        np.testing.assert_array_equal(np.asarray(ko)[li, 0, :32],
                                      np.asarray(kq)[li, 0, :32])


def test_fused_write_sliding_window():
    """Fused write + sliding-window decode: the fresh row's score
    substitution and the window's live mask interact at the write block —
    must match pre-writing the row then windowed attention."""
    B, H, D, S_max, W = 2, 4, 16, 128, 48
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    # lengths straddling block edges AND the window boundary
    lengths = jnp.asarray([40, 104], jnp.int32)
    kn = rng.standard_normal((B, H, D)).astype(np.float32)
    vn = rng.standard_normal((B, H, D)).astype(np.float32)
    ks_w = _write_rows_ref(ks, kn.reshape(B, H * D), lengths)
    vs_w = _write_rows_ref(vs, vn.reshape(B, H * D), lengths)
    want = np.asarray(decode_attention(q, ks_w, vs_w, lengths, block_k=32,
                                       window=W))
    got, ko, vo = decode_attention(q, ks, vs, lengths, block_k=32,
                                   window=W, new_k=jnp.asarray(kn),
                                   new_v=jnp.asarray(vn))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ko), np.asarray(ks_w),
                               rtol=1e-6, atol=1e-6)


def test_fused_write_rejects_bad_blocks():
    B, H, D, S_max = 1, 4, 16, 96
    q = jnp.zeros((B, H, D), jnp.float32)
    c = jnp.zeros((B, S_max, H * D), jnp.float32)
    n = jnp.zeros((B, H, D), jnp.float32)
    with pytest.raises(ValueError, match="block_k % 8"):
        decode_attention(q, c, c, jnp.asarray([5], jnp.int32), block_k=20,
                         new_k=n, new_v=n)
    odd = jnp.zeros((B, 92, H * D), jnp.float32)
    with pytest.raises(ValueError, match="S_max % 8"):
        decode_attention(q, odd, odd, jnp.asarray([5], jnp.int32),
                         new_k=n, new_v=n)


def test_fused_write_zero_length_row_clamped():
    """A zero-length row (invalid input — lengths include the fresh token,
    so the minimum is 1) must NOT corrupt cache rows 0-7: unclamped, its
    in-kernel write row computes (-1) % block_k = block_k-1 and the far
    stripe's stale rows get merged over the cache head (ADVICE round 5).
    Clamped, it degenerates to the benign length=1 write at row 0 and
    every other row of the stripe survives byte-for-byte."""
    B, H, D, S_max = 3, 4, 16, 64
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    kn = rng.standard_normal((B, H, D)).astype(np.float32)
    vn = rng.standard_normal((B, H, D)).astype(np.float32)
    lengths = jnp.asarray([5, 0, 33], jnp.int32)      # row 1: zero-length
    _, ko, vo = decode_attention(q, ks, vs, lengths, block_k=32,
                                 new_k=jnp.asarray(kn),
                                 new_v=jnp.asarray(vn))
    ko, vo = np.asarray(ko), np.asarray(vo)
    # the zero-length row's write clamps to position 0; positions 1-7 (the
    # rest of its 8-row write stripe) and everything beyond stay intact
    np.testing.assert_array_equal(ko[1, 1:], np.asarray(ks)[1, 1:])
    np.testing.assert_array_equal(vo[1, 1:], np.asarray(vs)[1, 1:])
    np.testing.assert_allclose(ko[1, 0], kn[1].reshape(-1), rtol=1e-6)
    # the VALID rows still write at lengths-1 exactly
    for b, pos in ((0, 4), (2, 32)):
        np.testing.assert_allclose(ko[b, pos], kn[b].reshape(-1), rtol=1e-6)
        other = np.delete(np.arange(S_max), pos)
        np.testing.assert_array_equal(ko[b, other], np.asarray(ks)[b, other])


# --------------------------------------------------------------------- #
# cached_attention chunk-branch contract (models/transformer.py)
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("starts", [(0, 0), (3, 11)])
def test_cached_attention_chunk_branch_matches_dense(starts):
    """The ``1 < S <= 512`` Pallas chunk branch of cached_attention derives
    row positions as ``q_positions[:, 0] + iota`` — for its documented
    contract (per-row CONTIGUOUS ascending positions, possibly different
    per row) it must agree with the dense einsum fallback, which masks per
    position."""
    B, S, H, D, S_max = 2, 8, 4, 16, 64
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S_max, D)), jnp.float32)
    ks, vs = to_smajor(k), to_smajor(v)
    q_pos = jnp.asarray([[s + i for i in range(S)] for s in starts],
                        jnp.int32)
    got = cached_attention(q, ks, vs, q_pos)          # chunk kernel branch
    want = xla_cached_attention(q, ks, vs, q_pos)     # dense fallback
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---- the chunk fold's layout (PR 53) -------------------------------------- #
# ``_chunk_block_update`` keeps its running max and sum lane-replicated and
# selects a masked score once.  Held, bitwise, to the form it replaced: the
# statistics as ``[C, 1]`` columns of ``[H, C, LSE_LANES]`` tiles, the max
# started at NEG_INF, the masked probabilities selected to 0 a second time —
# transcribed below in plain ``jnp`` and folded through the SAME driver in
# the same block order.

def _column_fold():
    """The parent's ``_chunk_scratch`` / ``_init_chunk`` /
    ``_chunk_block_update`` / ``_finish_chunk``, heads walked statically."""
    from jax.experimental.pallas import tpu as pltpu
    from deepspeed_tpu.ops.transformer.flash_attention import (LSE_LANES,
                                                               NEG_INF)

    def scratch(c, h, d):
        return [pltpu.VMEM((h, c, LSE_LANES), jnp.float32),
                pltpu.VMEM((h, c, LSE_LANES), jnp.float32),
                pltpu.VMEM((c, h * d), jnp.float32)]

    def init(st):
        st.m_scr[...] = jnp.full_like(st.m_scr, NEG_INF)
        st.l_scr[...] = jnp.zeros_like(st.l_scr)
        st.acc_scr[...] = jnp.zeros_like(st.acc_scr)

    def update(st, ik, start, k_ref, v_ref, ks, vs, *, scale, block_k, c,
               kvh, g, d, masked=True, prefix=False, pos0=None, window=None,
               limit=None):
        quant = ks is not None
        if quant:
            kst, vst = ks.astype(jnp.float32).T, vs.astype(jnp.float32).T
        if masked:
            pos = (ik * block_k if pos0 is None else pos0) \
                + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            if prefix:
                live = pos < start
            else:
                qpos = start + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
                live = pos <= qpos
                if window is not None:
                    live = jnp.logical_and(live, pos > qpos - window)
                if limit is not None:
                    live = jnp.logical_and(live, pos < limit)
        for h in range(kvh * g):
            hk = h // g
            cols, kvcols = slice(h * d, (h + 1) * d), \
                slice(hk * d, (hk + 1) * d)
            qh, kh, vh = st.q_ref[0, :, cols], k_ref[:, kvcols], \
                v_ref[:, kvcols]
            if quant:
                kh, vh = kh.astype(qh.dtype), vh.astype(qh.dtype)
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quant:
                s = s * kst[hk:hk + 1]
            if masked:
                s = jnp.where(live, s, NEG_INF)
            m_prev = st.m_scr[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(live, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = st.l_scr[h, :, 0:1] * corr + jnp.sum(p, axis=1,
                                                          keepdims=True)
            st.l_scr[h] = jnp.broadcast_to(l_new, (c, LSE_LANES))
            st.m_scr[h] = jnp.broadcast_to(m_new, (c, LSE_LANES))
            if quant:
                p = p * vst[hk:hk + 1]
            o = jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            st.acc_scr[:, cols] = st.acc_scr[:, cols] * corr + o

    def finish(st, o_ref, *, heads, d):
        for h in range(heads):
            cols = slice(h * d, (h + 1) * d)
            l = st.l_scr[h, :, 0:1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, cols] = (st.acc_scr[:, cols] / safe_l
                                 ).astype(o_ref.dtype)

    return scratch, init, update, finish


def _fold_blocks(fold, q, k, v, plan, *, kvh, g, d, scales=None):
    """``plan``'s blocks (one dict of ``_chunk_block_update``'s block
    arguments each; block ``i`` is ``k[i]`` / ``v[i]``) folded in order by
    ``fold`` = (scratch, init, update, finish) inside ONE interpreted
    kernel; the chunk's output ``[C, H*D]``."""
    from jax.experimental import pallas as pl
    from deepspeed_tpu.ops.transformer.decode_attention import _ChunkState
    scratch, init, update, finish = fold
    c, bk, heads = q.shape[0], k.shape[1], kvh * g

    def kernel(q_ref, k_ref, v_ref, *rest):
        ks_ref, vs_ref = rest[:2] if scales else (None, None)
        o_ref, m_scr, l_scr, acc_scr = rest[2 if scales else 0:]
        st = _ChunkState(q_ref, m_scr, l_scr, acc_scr)
        init(st)
        for i, block in enumerate(plan):
            update(st, block["ik"], block["start"], k_ref.at[i], v_ref.at[i],
                   ks_ref[i] if scales else None,
                   vs_ref[i] if scales else None,
                   scale=float(1.0 / np.sqrt(d)), block_k=bk, c=c,
                   kvh=kvh, g=g, d=d,
                   **{n: x for n, x in block.items()
                      if n not in ("ik", "start")})
        finish(st, o_ref, heads=heads, d=d)

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((1,) + q.shape, q.dtype),
        scratch_shapes=scratch(c, heads, d), interpret=True,
    )(q[None], k, v, *(scales or ()))[0]


_C, _BK = 16, 128
_FOLD_PLANS = {
    # a causal chunk at ``start``: blocks wholly under the diagonal
    # unmasked, then the blocks that reach it
    "causal": [dict(ik=0, start=280, masked=False),
               dict(ik=1, start=280, masked=False),
               dict(ik=2, start=280)],
    "causal_all_masked": [dict(ik=0, start=5), dict(ik=1, start=120)],
    # EVA: summary rows up to a bound every query sees alike, then the ring
    "prefix": [dict(ik=0, start=200, prefix=True),
               dict(ik=1, start=200, prefix=True),
               dict(ik=0, start=130, masked=False),
               dict(ik=1, start=130)],
    # a bound of 0 keeps nothing: every row ends at l == 0 and gets zeros
    "prefix_nothing_kept": [dict(ik=0, start=0, prefix=True)],
    # Trinity's ring walk: the first block (the late queries' band starts
    # past it: rows that have kept nothing yet), an inner block wholly in
    # the band, the last with rows at ``limit`` and past it; then the
    # chunk's own keys up to the diagonal
    "band": [dict(ik=0, start=300, pos0=0, window=296, limit=300),
             dict(ik=0, start=300, masked=False),
             dict(ik=0, start=300, pos0=256, window=296, limit=300),
             dict(ik=0, start=300, pos0=300, window=296)],
    # the band's first block alone: the late queries keep nothing, and
    # stay so
    "band_first_only": [dict(ik=0, start=300, pos0=0, window=180,
                             limit=300)],
}


@pytest.mark.parametrize("plan", sorted(_FOLD_PLANS))
@pytest.mark.parametrize("kvh,g,d,bk,quant", [
    (2, 1, 64, _BK, False),      # OPT: two heads a lane tile
    (2, 4, 64, _BK, False),      # LFM2
    (4, 1, 64, _BK, True),       # the int8 pool's branch (heads unrolled)
    (1, 1, 128, _BK, False),     # EvaByte / OLMoE
    (2, 4, 128, 2 * _BK, False),   # Trinity; the tile laid side by side
    (1, 8, 128, _BK, False),
    (2, 2, 128, _BK, True),
    (3, 1, 48, _BK, False),      # a head that tiles no lane: columns
    (2, 2, 32, 48, False),       # a block that is no lane tile: columns
    (4, 1, 32, _BK, False),      # four heads a lane tile
], ids=lambda x: str(x))
def test_chunk_fold_is_bitwise_the_column_form(kvh, g, d, bk, quant, plan):
    """The lane-replicated, one-select fold gives the column form's bits —
    every block class a driver hands it, every head layout its static
    shapes choose between — and a row that kept nothing comes out zero."""
    from deepspeed_tpu.ops.transformer import decode_attention as da
    blocks = _FOLD_PLANS[plan]
    if bk != _BK:
        # the same positions in blocks of ``bk``
        blocks = [dict(b, **{n: b[n] * bk // _BK for n in
                             ("start", "pos0", "window", "limit") if n in b})
                  for b in blocks]
    rng = np.random.default_rng(kvh * 1000 + g * 100 + d)
    heads, n = kvh * g, len(blocks)
    dtype = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((_C, heads * d)), dtype)
    k = jnp.asarray(rng.standard_normal((n, bk, kvh * d)), dtype)
    v = jnp.asarray(rng.standard_normal((n, bk, kvh * d)), dtype)
    scales = None
    if quant:
        k = jnp.asarray(rng.integers(-127, 128, k.shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, v.shape), jnp.int8)
        scales = tuple(jnp.asarray(rng.uniform(0.004, 0.02, (n, bk, kvh)),
                                   jnp.float32) for _ in range(2))
    new = _fold_blocks((da._chunk_scratch, da._init_chunk,
                        da._chunk_block_update, da._finish_chunk),
                       q, k, v, blocks, kvh=kvh, g=g, d=d, scales=scales)
    old = _fold_blocks(_column_fold(), q, k, v, blocks, kvh=kvh, g=g, d=d,
                       scales=scales)
    new, old = np.asarray(new, np.float32), np.asarray(old, np.float32)
    assert np.isfinite(new).all()
    np.testing.assert_array_equal(new, old)
    if plan == "prefix_nothing_kept":
        assert not new.any()
    if plan == "band_first_only":
        # query r sees positions > start + r - window of the block's bk
        kept = blocks[0]["window"] - blocks[0]["start"] + bk - 1
        assert 0 < kept < _C
        assert not new[kept:].any() and new[:kept].any(axis=1).all()


@pytest.mark.parametrize("d,block_k,rescale,spread", [
    (128, 512, "tiles", "tiles"),      # Trinity, EvaByte, OLMoE
    (64, 512, "select", "tiles"),      # OPT, LFM2: two heads a lane tile
    (256, 128, "tiles", "tiles"),
    (48, 512, "columns", "tiles"),     # a head that tiles no lane
    (64, 64, "select", "columns"),     # a page of 64 keys a block (int8)
    (96, 48, "columns", "columns"),
])
def test_chunk_fold_lays_statistics_out_by_shape(d, block_k, rescale,
                                                 spread):
    """Which form a shape takes, from its static widths alone: whole lane
    tiles side by side, a lane select between the heads that share a tile,
    or the ``[:, :1]`` column's broadcast where a width tiles no lane."""
    from deepspeed_tpu.ops.transformer import decode_attention as da

    def primitives(jaxpr):
        for e in jaxpr.eqns:
            yield str(e.primitive)
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from primitives(sub)

    def form(fn, *shapes):
        prims = set(primitives(jax.make_jaxpr(fn)(
            *[jnp.zeros(s, jnp.float32) for s in shapes]).jaxpr))
        if "slice" in prims:
            return "columns"
        return "select" if "select_n" in prims else "tiles"

    tile = (8, da.STAT_LANES)
    heads = 2 * max(1, da.STAT_LANES // d)
    assert form(lambda *t: da._per_head(list(t), d),
                *[tile] * heads) == rescale
    assert form(lambda x: da._across(x, block_k), tile) == spread
    out = da._per_head([jnp.full(tile, float(h)) for h in range(heads)], d)
    assert out.shape == (8, heads * d)
    np.testing.assert_array_equal(
        np.asarray(out[0]), np.repeat(np.arange(heads, dtype=np.float32), d))
