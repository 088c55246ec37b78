"""Flash-attention kernel vs jnp golden reference (the test pattern the
reference uses for its CUDA kernels, e.g. ``tests/unit/ops/transformer/``) —
forward and gradients, MHA and GQA, causal and full."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import reference_attention
from deepspeed_tpu.monitor import trace
from deepspeed_tpu.ops.transformer import flash_attention as flash_mod
from deepspeed_tpu.ops.transformer.flash_attention import (backward_plans,
                                                           flash_attention,
                                                           tile_plan)


def make_qkv(B=2, S=256, H=4, KVH=None, D=64, seed=0, dtype=jnp.float32,
             Sk=None):
    rng = np.random.default_rng(seed)
    KVH = KVH or H
    Sk = Sk or S
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((B, Sk, KVH, D)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((B, Sk, KVH, D)), dtype=dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_forward_gqa():
    q, k, v = make_qkv(H=8, KVH=2)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_forward_uneven_blocks():
    # seq not a multiple of the block size exercises padding/cdiv paths
    q, k, v = make_qkv(S=192)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = make_qkv(B=1, S=128, H=2, D=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name} mismatch")


def test_gradients_gqa():
    q, k, v = make_qkv(B=1, S=128, H=4, KVH=2, D=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name} mismatch")


def test_bf16_forward_close():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               atol=3e-2, rtol=3e-2)
    assert out.dtype == jnp.bfloat16


# --------------------------------------------------------------------- #
# The tile plan: what the kernels walk, and that they walk it
# --------------------------------------------------------------------- #
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("kernel,shape,blocks,tile,want", [
    # opt13b-sft-1chip (D=64) and opt67b-zero3-4chip (D=128), sequence 2048:
    # ten 512-tiles of sixteen, the four on the diagonal masked
    ("fwd", (2048, 2048, 64, BF16, True), (), 512, (512, 512, 10, 4, 16)),
    ("dq", (2048, 2048, 64, BF16, True), (), 512, (512, 512, 10, 4, 16)),
    ("dkv", (2048, 2048, 64, BF16, True), (), 512, (512, 512, 10, 4, 16)),
    ("fwd", (2048, 2048, 128, BF16, True), (), 512, (512, 512, 10, 4, 16)),
    ("dkv", (2048, 2048, 128, BF16, True), (), 512, (512, 512, 10, 4, 16)),
    ("dq", (2048, 2048, 64, BF16, True), (), 256, (256, 256, 36, 8, 64)),
    # the fused backward walks what dkv walks
    ("dq_dkv", (2048, 2048, 64, BF16, True), (), 512, (512, 512, 10, 4, 16)),
    ("dq_dkv", (2048, 2048, 128, BF16, True), (), 512,
     (512, 512, 10, 4, 16)),
    ("dq_dkv", (4096, 4096, 128, BF16, True), (), 512,
     (512, 512, 36, 8, 64)),
    ("dq_dkv", (192, 192, 64, F32, True), (128, 128), 512,
     (128, 128, 3, 3, 4)),
    # what the kernels ran before the walk: one [512, 2048] tile a query
    # block, all masked; three of four [1024, 1024] tiles backward
    ("fwd", (2048, 2048, 64, BF16, True), (512, 2048), 2048,
     (512, 2048, 4, 4, 4)),
    ("dq", (2048, 2048, 64, BF16, True), (1024, 1024), 1024,
     (1024, 1024, 3, 2, 4)),
    ("dkv", (2048, 2048, 64, BF16, True), (1024, 1024), 1024,
     (1024, 1024, 3, 2, 4)),
    # non-causal: every tile, none masked
    ("fwd", (2048, 2048, 64, BF16, False), (), 512, (512, 512, 16, 0, 16)),
    ("dkv", (2048, 2048, 64, BF16, False), (), 512, (512, 512, 16, 0, 16)),
    # ragged: S=192 in 128-blocks — the WALKED axis' tail tile masks (for
    # dkv that is the query tile under the diagonal too); whole in one
    # block when nothing fixes the blocks
    ("fwd", (192, 192, 64, F32, True), (128, 128), 512, (128, 128, 3, 2, 4)),
    ("dkv", (192, 192, 64, F32, True), (128, 128), 512, (128, 128, 3, 3, 4)),
    ("fwd", (192, 192, 64, F32, False), (128, 128), 512,
     (128, 128, 4, 2, 4)),
    ("dq", (192, 192, 64, F32, True), (), 512, (192, 192, 1, 1, 1)),
    # a second, ragged major block: 2304 keys = 2048 + 256
    ("fwd", (2304, 2304, 64, BF16, True), (), 512, (512, 512, 15, 5, 25)),
    # more keys than queries, top-left aligned: one tile is all there is
    ("fwd", (512, 2048, 64, F32, True), (), 512, (512, 512, 1, 1, 4)),
    ("dkv", (512, 2048, 64, F32, True), (), 512, (512, 512, 1, 1, 4)),
])
def test_tile_plan_counts(kernel, shape, blocks, tile, want, monkeypatch):
    monkeypatch.setattr(flash_mod, "_TILE", tile)
    got = tile_plan(kernel, *shape, *blocks).counts()
    assert tuple(got[f] for f in ("tile_q", "tile_k", "tiles_run",
                                  "tiles_masked", "tiles_square")) == want


def test_plan_event_a_traced_call():
    """One ``dstpu.kernel.tile_plan`` event a traced call of each kernel,
    with the counts of the plan the kernel's loops are bounded by — at the
    sft cell's shape, traced only (nothing runs)."""
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(F32).sum()
    arg = jax.ShapeDtypeStruct((2, 2048, 32, 64), BF16)
    ring = trace.enable()
    try:
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(arg, arg, arg)
        spans, _ = ring.span_snapshot()
    finally:
        trace.disable()
    events = {s[5]["kernel"]: s[5] for s in spans
              if s[0] == "dstpu.kernel.tile_plan"}
    assert sorted(events) == ["attn.flash_dq_dkv", "attn.flash_fwd"]
    for name, args in events.items():
        assert (args["tile_q"], args["tile_k"]) == (512, 512), name
        assert (args["tiles_run"], args["tiles_masked"],
                args["tiles_square"]) == (10, 4, 16), name


def _assert_parity(q, k, v, causal, tol, **blocks):
    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=causal, **kw).astype(F32) ** 2)

    wide = [x.astype(F32) for x in (q, k, v)]
    out = flash_attention(q, k, v, causal=causal, **blocks)
    ref = reference_attention(*wide, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)
    gf = jax.grad(loss(flash_attention, **blocks), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(*wide)
    for a, b, name in zip(gf, gr, "qkv"):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol * 10 * scale, rtol=tol * 10,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("D,dtype,KVH,causal", [
    (64, F32, 2, True),
    (128, F32, 1, True),          # GQA
    (64, BF16, 2, True),
    (128, BF16, 1, False),        # GQA, every tile unmasked
    (64, F32, 2, False),
], ids=["d64-f32", "d128-f32-gqa", "d64-bf16", "d128-bf16-gqa-full",
        "d64-f32-full"])
def test_walk_of_several_tiles_matches_reference(D, dtype, KVH, causal,
                                                 monkeypatch):
    """S=1024 in 256-tiles: four trips a query block at the end, interior
    AND diagonal tiles, in all three kernels."""
    monkeypatch.setattr(flash_mod, "_TILE", 256)
    plan = tile_plan("dkv", 1024, 1024, D, dtype, causal).counts()
    assert plan["tiles_run"] == (10 if causal else 16)
    assert plan["tiles_masked"] == (4 if causal else 0)
    q, k, v = make_qkv(B=1, S=1024, H=2, KVH=KVH, D=D, dtype=dtype)
    _assert_parity(q, k, v, causal, 3e-2 if dtype == BF16 else 5e-5)


@pytest.mark.parametrize("form", ["fused", "pair"])
@pytest.mark.parametrize("causal", [True, False])
def test_walk_across_major_blocks_with_a_ragged_tail(causal, form,
                                                     monkeypatch):
    """S=320 with the walked axis in 256-blocks of 128-tiles: the running
    statistics cross a grid step, and the second block is a ragged tile.
    Backward, the fused kernel sums dq over two key blocks (the second
    ragged: its dead rows must add nothing) AND two major query blocks;
    with no room for a head's dq the pair does the same work."""
    monkeypatch.setattr(flash_mod, "_TILE", 128)
    if form == "pair":
        monkeypatch.setattr(flash_mod, "_DQ_SUM_BYTES", 0)
    plan = tile_plan("fwd", 320, 320, 32, F32, causal, None, 256)
    assert (plan.n_major, plan.tile_k, plan.ragged) == (2, 128, True)
    plans = backward_plans(320, 320, 32, F32, causal, 256, 256)
    assert [p.kernel for p in plans] == (
        ["dq_dkv"] if form == "fused" else ["dq", "dkv"])
    assert (plans[-1].n_resident, plans[-1].n_major) == (2, 2)
    q, k, v = make_qkv(B=1, S=320, H=2, D=32)
    _assert_parity(q, k, v, causal, 5e-5, block_k=256, block_q_bwd=256,
                   block_k_bwd=256)


@pytest.mark.parametrize("D,dtype,causal", [
    (64, F32, True), (128, BF16, True), (64, F32, False)],
    ids=["d64-f32", "d128-bf16", "d64-f32-full"])
def test_fused_backward_sums_dq_over_key_and_major_blocks(D, dtype, causal,
                                                          monkeypatch):
    """S=1024 in 256-tiles with the queries in 512-row major blocks and
    GROUPED heads: a head's dq is the sum of four key blocks' shares in
    each of two major blocks — what ``attn.flash_dq``, a query block
    resident, never had to add up."""
    monkeypatch.setattr(flash_mod, "_TILE", 256)
    plan, = backward_plans(1024, 1024, D, dtype, causal, 512, None)
    assert (plan.kernel, plan.n_resident, plan.n_major) == ("dq_dkv", 4, 2)
    assert plan.counts()["tiles_run"] == (10 if causal else 16)
    q, k, v = make_qkv(B=1, S=1024, H=4, KVH=2, D=D, dtype=dtype, seed=3)
    _assert_parity(q, k, v, causal, 3e-2 if dtype == BF16 else 5e-5,
                   block_q_bwd=512)


def test_form_of_the_backward_follows_the_shape():
    """``tile_plan`` grants the fused kernel where a head's float32 dq fits
    ``_DQ_SUM_BYTES`` of VMEM — every length a cell or a test trains at —
    and hands back the pair's ``dkv`` plan where it does not; nothing else
    decides."""
    names = lambda *shape: [p.kernel for p in backward_plans(*shape, True)]
    for s, d in [(2048, 64), (2048, 128), (4096, 128), (8192, 64)]:
        assert names(s, s, d, BF16) == ["dq_dkv"], (s, d)
    assert 8192 * 64 * 4 == flash_mod._DQ_SUM_BYTES
    # one row past the budget: whole major blocks are what is held
    assert names(8193, 8193, 64, BF16) == ["dq", "dkv"]
    assert names(8192, 8192, 128, BF16) == ["dq", "dkv"]
    assert names(4096, 4096, 256, F32) == ["dq", "dkv"]
    # the keys' length is not in the rule
    assert names(2048, 16384, 128, BF16) == ["dq_dkv"]
    # the traced call says which form ran
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(F32).sum()
    arg = jax.ShapeDtypeStruct((1, 8192, 1, 128), BF16)
    ring = trace.enable()
    try:
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), arg, arg, arg)
        spans, _ = ring.span_snapshot()
    finally:
        trace.disable()
    assert sorted(s[5]["kernel"] for s in spans
                  if s[0] == "dstpu.kernel.tile_plan") == [
        "attn.flash_dkv", "attn.flash_dq", "attn.flash_fwd"]


@pytest.mark.parametrize("S,Sk,H,KVH,tile", [(512, 2048, 1, 1, 512),
                                             (256, 1024, 2, 1, 256)],
                         ids=["s512-k2048", "s256-k1024-gqa"])
def test_skipped_tiles_are_not_computed(S, Sk, H, KVH, tile, monkeypatch):
    """Causal, fewer queries than keys (top-left aligned): keys from ``S``
    on are above every query's diagonal.  NaN there must not reach the
    output or a gradient — a computed-then-masked tile turns 0 x NaN into
    NaN, and the fused backward SUMS dq over all four key blocks, three of
    them wholly unseen: every gradient is finite, the seen keys' match the
    reference, the unseen keys' are exactly zero."""
    monkeypatch.setattr(flash_mod, "_TILE", tile)
    plan, = backward_plans(S, Sk, 64, F32, True)
    assert (plan.kernel, plan.n_resident) == ("dq_dkv", 4)
    q, k, v = make_qkv(B=1, S=S, Sk=Sk, H=H, KVH=KVH, D=64, seed=5)
    poison = jnp.arange(Sk)[None, :, None, None] >= S
    k_bad = jnp.where(poison, jnp.nan, k)
    v_bad = jnp.where(poison, jnp.nan, v)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    out = flash_attention(q, k_bad, v_bad, causal=True)
    ref = reference_attention(q, k[:, :S], v[:, :S], causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k_bad, v_bad)
    want = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(
        q, k[:, :S], v[:, :S])
    for g, w, name in zip(got, want, "qkv"):
        assert np.isfinite(np.asarray(g)).all(), f"d{name} not finite"
        np.testing.assert_allclose(np.asarray(g[:, :S]), np.asarray(w),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")
    # keys no query sees get a zero gradient, not a NaN
    assert not np.asarray(got[1][:, S:]).any()
    assert not np.asarray(got[2][:, S:]).any()
