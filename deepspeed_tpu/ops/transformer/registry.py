"""Attention-kernel registry — the ONE dispatch point for cached attention.

Every cached-attention consumer (the monolithic generate()/serving decode
path, the paged serving path, speculative verify's per-row multi-token
blocks, chunked prefill) routes through this module instead of
hand-threading its own "which kernel can run here?" branch:

* :func:`select_kernel` — the capability-probed dispatch table.  Probes
  are STATIC (shapes, config, backend support — never traced values), so
  the decision is made once at trace time and every caller, including the
  host-side attribution in ``InferenceEngine.prefill_plan()`` and the
  serving engine's stats, sees the same answer the compiled program took.
* :func:`write_and_attend` — the single write-then-attend implementation
  behind ``models.transformer.Attention``: cache-layout resolution
  (monolithic / layer-stacked / paged pool), this step's K/V row write
  (scatter, DUS or kernel-fused aliased write), and the attend through
  the selected kernel.  This collapses what used to be three near-copies
  of the write/gather branch in ``Attention.__call__`` plus the separate
  fused-decode special case.

Modes (the ``KERNEL_MODES`` table, probed in order, first hit wins):

==========================  ==================================================
``pallas_paged_decode``     single-token decode straight over the paged pool
                            (``ops/transformer/paged_attention.py``) — split-K
                            across block-table pages, no gathered virtual view
``pallas_decode``           single-token decode over a monolithic cache
                            (``ops/transformer/decode_attention.py``)
``pallas_ring_decode``      single-token decode of a SLIDING-WINDOW layer whose
                            K/V lie in a ring a slot (the cache's ``ring``
                            marker): the row goes to ring row ``pos %
                            window``, then ``attn.paged_decode`` over the
                            ring's table at ``min(pos + 1, window)`` rows —
                            the ring holds exactly the window, so the band
                            needs no mask
``pallas_window_chunk``     a prefill chunk of such a layer
                            (``attn.gqa_window_chunk``): the ring's rows
                            before the chunk and the chunk's own keys under
                            the band, blocks outside it skipped; the chunk's
                            rows enter the ring after
``pallas_chunked_prefill``  multi-token block (chunked prefill, multi-token
                            decode, speculative verify) vs either cache
                            layout, S <= MAX_CHUNK_S — over a paged pool also
                            whole multiples of it, as that many rows of
                            MAX_CHUNK_S queries over the one table row
``reference_fallback``      the XLA reference path: paged caches first
                            materialize the ``take_along_axis`` gathered view
                            (``_paged_gather``) and then take whatever
                            ``cached_attention`` does on it — dense masked
                            attention when Pallas is unavailable or a bias
                            rides along.  Paged DECODE landing here is the
                            BENCH_r04 bs128 cliff: it warns once and the
                            serving engine counts it
                            (``stats["paged_attention_fallback"]``)
==========================  ==================================================

The gather path is also the paged kernels' bitwise reference in the
tests: ``DSTPU_DISABLE_FLASH=1`` (``pallas_supported()`` false) routes
every call to it.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.flash_attention import pallas_supported
from deepspeed_tpu.utils.logging import warning_once

# the chunk kernel's q block + f32 accumulator scale with S x H x D;
# longer blocks would blow VMEM and keep the dense fallback
MAX_CHUNK_S = 512

KERNEL_MODES = (
    "pallas_ring_decode",
    "pallas_window_chunk",
    "pallas_paged_decode",
    "pallas_decode",
    "pallas_chunked_prefill",
    "reference_fallback",
)


def _probe_ring_decode(s, paged, has_bias, has_window, ring):
    return (paged and ring and has_window and s == 1 and not has_bias
            and pallas_supported())


def _probe_window_chunk(s, paged, has_bias, has_window, ring):
    from deepspeed_tpu.ops.transformer.paged_attention import (
        window_chunk_queries)
    return (paged and ring and has_window and s > 1 and not has_bias
            and window_chunk_queries(s) is not None and pallas_supported())


def _probe_paged_decode(s, paged, has_bias, has_window, ring):
    # a windowed layer over LANE pages (no ring) has no paged kernel: it
    # keeps the gather path (whose monolithic kernel masks the window)
    return (paged and s == 1 and not has_bias and not has_window
            and pallas_supported())


def _probe_decode(s, paged, has_bias, has_window, ring):
    # monolithic decode masks sliding windows in-kernel
    return (not paged and s == 1 and not has_bias and pallas_supported())


def _probe_chunk(s, paged, has_bias, has_window, ring):
    return ((1 < s <= MAX_CHUNK_S or (paged and s % MAX_CHUNK_S == 0))
            and not has_bias and not has_window and pallas_supported())


_REGISTRY = (
    ("pallas_ring_decode", _probe_ring_decode),
    ("pallas_window_chunk", _probe_window_chunk),
    ("pallas_paged_decode", _probe_paged_decode),
    ("pallas_decode", _probe_decode),
    ("pallas_chunked_prefill", _probe_chunk),
)


def select_kernel(*, s, paged=False, has_bias=False, has_window=False,
                  ring=False):
    """The attention-kernel dispatch decision for one cached-attention
    call.  All inputs are static: ``s`` (this block's token count),
    ``paged`` (block-table pool vs monolithic lanes), ``has_bias``
    (alibi), ``has_window`` (sliding-window layer) and ``ring`` (the
    window's K/V lie in a ring a slot, exactly the window long).  Returns a
    :data:`KERNEL_MODES` name; ``reference_fallback`` when no Pallas
    kernel applies."""
    for mode, probe in _REGISTRY:
        if probe(s, paged, has_bias, has_window, ring):
            return mode
    return "reference_fallback"


def kernel_modes(*, paged, has_bias=False, has_window=False, ring=False):
    """Host-side attribution of which kernel mode each serving program
    class will take (what ``prefill_plan`` reasons and bench records
    report).  Probes the same table the traced programs dispatch
    through, so the attribution cannot drift from reality."""
    return {
        "decode": select_kernel(s=1, paged=paged, has_bias=has_bias,
                                has_window=has_window, ring=ring),
        "prefill_chunk": select_kernel(s=2, paged=paged, has_bias=has_bias,
                                       has_window=has_window, ring=ring),
    }


def paged_write_form(block, page, *, page_runs):
    """How a ``block``-token write reaches a paged pool of ``page``-row
    pages: ``"page_runs"`` — one in-place block write a page run — or
    ``"row_scatter"`` through the table, row by row.  All static, like
    :func:`select_kernel`: the traced write (``_write_cache``) and the
    host-side attribution (``ServingEngine.stats["chunk_write"]``,
    ``prefill_plan`` reasons) ask this one predicate.

    Page runs need a multi-token block that is whole pages (``block %
    page == 0``) or one run inside a page (``page % block == 0``), AND a
    run-aligned start — which no shape shows, so the caller says it with
    the cache's ``page_runs`` marker (the serving chunk program does:
    ``SlotPages.reserve`` starts every chunk on a common multiple of page
    and chunk).  The promise is of every ROW's start: marked, a per-row
    block (the chunk program's rows, ``serving/slots.py``) is page runs
    like a row-uniform one; unmarked (speculative verify: a window
    starts wherever its slot stands) it keeps the scatter."""
    if page_runs and block > 1 \
            and (block % page == 0 or page % block == 0):
        return "page_runs"
    return "row_scatter"


def _cache_markers(cache):
    """The bookkeeping keys a write must thread through unchanged."""
    return {kk: cache[kk] for kk in ("layer", "pages", "per_row",
                                     "page_runs", "ring") if kk in cache}


def _quant_rows(new, kvh):
    """Per-(position, kv-head) symmetric int8 for this step's rows: the
    scale rides a tiny side buffer; the payload keeps the raw
    projection-output layout."""
    B_, S_, KVHD = new.shape
    r = new.reshape(B_, S_, kvh, KVHD // kvh).astype(jnp.float32)
    s = jnp.max(jnp.abs(r), axis=-1) / 127.0
    safe = jnp.where(s == 0.0, 1.0, s)
    pay = jnp.clip(jnp.round(r / safe[..., None]), -127, 127)
    return pay.reshape(B_, S_, KVHD), s


def _write_cache(cache, k_new, v_new, ks_new, vs_new, positions):
    """This step's K/V rows into the cache — ONE implementation of what
    used to be three branch copies: paged pools take page runs or scatter
    through the page table (:func:`paged_write_form`); monolithic caches
    (layer-stacked or per-layer) pick the per-row-single-token scatter,
    the per-row multi-token scatter (speculative verify) or the
    row-uniform dynamic_update_slice."""
    import jax
    from deepspeed_tpu.models.transformer import _paged_write
    markers = _cache_markers(cache)
    if "pages" in cache:
        per_row = "per_row" in cache
        form = paged_write_form(k_new.shape[1], cache["k"].shape[-2],
                                page_runs="page_runs" in cache)
        data = _paged_write(cache, k_new, v_new, ks_new, vs_new, positions,
                            per_row=per_row, page_runs=form == "page_runs")
        return {**data, **markers}
    B_, S_ = k_new.shape[0], k_new.shape[1]
    li = cache.get("layer")
    if "per_row" in cache and S_ == 1:
        # padded-prompt decode: each row writes at ITS OWN position
        # (generated tokens overwrite the right-pad slots, keeping the
        # live cache region contiguous for the decode kernel's length
        # mask).  One native scatter — NOT the default path: the
        # row-uniform dynamic_update_slice below is cheaper and proven
        # on the big stacked cache.
        pos_rows = positions[:, 0]
        rows = jnp.arange(B_)

        def write_rows(buf, new):
            if li is None:
                return buf.at[rows, pos_rows].set(
                    new[:, 0].astype(buf.dtype))
            return buf.at[li, rows, pos_rows].set(
                new[:, 0].astype(buf.dtype))
    elif "per_row" in cache:
        # per-row MULTI-token block (the serving engine's speculative
        # verify): each row writes S_ contiguous positions from ITS OWN
        # start in one batched scatter.  Positions past the buffer (dead
        # lanes' clamped windows) are dropped by scatter's out-of-bounds
        # rule; in-bounds writes land inside the row's own lane.
        rows2d = jnp.arange(B_)[:, None]                 # [B, 1]

        def write_rows(buf, new):
            if li is None:
                return buf.at[rows2d, positions].set(new.astype(buf.dtype))
            return buf.at[li, rows2d, positions].set(new.astype(buf.dtype))
    else:
        # row-uniform write: decode at a shared position, or a
        # multi-token prefill block from the start position
        start = positions[0, 0]

        def write_rows(buf, new):
            if li is None:
                return jax.lax.dynamic_update_slice(
                    buf, new.astype(buf.dtype), (0, start, 0))
            return jax.lax.dynamic_update_slice(
                buf, new[None].astype(buf.dtype), (li, 0, start, 0))

    data = {"k": write_rows(cache["k"], k_new),
            "v": write_rows(cache["v"], v_new)}
    if ks_new is not None:
        data["k_scale"] = write_rows(cache["k_scale"], ks_new)
        data["v_scale"] = write_rows(cache["v_scale"], vs_new)
    return {**data, **markers}


def _fused_decode(cfg, q, k, v, positions, cache, mode, window):
    """Single-token decode through the FUSED-WRITE kernels: the kernel
    writes this step's K/V row (quantizing when the cache is int8) via
    aliased outputs AND attends — no out-of-kernel scatter /
    dynamic_update_slice on the multi-GB cache at all.  Returns
    ``(out [B,1,H,D], new_cache)`` or None when this step must take the
    write-then-attend path (the opt-in int8-MXU mode, unaligned
    layouts, or a non-decode kernel mode).

    Why this exists: the out-of-kernel cache-update chain interleaved
    with the kernel's cache reads makes XLA copy the cache per step once
    it exceeds ~2.2 GB (measured 129 ms/step vs 12.7 fused at
    bs16 x 4k x 24 layers) — the in-place write the reference gets from
    its workspace pointer arithmetic (``inference_context.h:24-87``)
    has to live INSIDE the kernel here."""
    if cfg.decode_int8_matmuls:
        # the int8-MXU score/PV matmuls are unsupported with the fused
        # write (per-row requantization would race the aliased stripe)
        return None
    lengths = (positions[:, 0] + 1).astype(jnp.int32)
    if mode == "pallas_paged_decode":
        if cache["k"].shape[-2] % 8 != 0:
            # write stripes are 8-sublane-aligned; ServingConfig rounds
            # page_size to a multiple of 8, hand-built pools may not
            return None
        from deepspeed_tpu.ops.transformer.paged_attention import (
            paged_decode_attention)
        res = paged_decode_attention(
            q[:, 0], cache["k"], cache["v"], lengths, cache["pages"],
            layer=cache["layer"], k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale"), new_k=k[:, 0], new_v=v[:, 0],
            **_stated_scale(cfg))
    elif mode == "pallas_decode":
        if cache["k"].shape[-2] % 8 != 0 or _stated_scale(cfg):
            # odd cache lengths (hand-allocated test caches) take the
            # unfused path (required_cache_len rounds engine workspaces
            # to a multiple of 8)
            return None
        from deepspeed_tpu.ops.transformer.decode_attention import (
            decode_attention)
        res = decode_attention(
            q[:, 0], cache["k"], cache["v"], lengths,
            layer=cache.get("layer"), k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale"), window=window,
            new_k=k[:, 0], new_v=v[:, 0])
    else:
        return None
    if cfg.kv_cache_quant:
        out_f, kc, vc, ksc, vsc = res
        data = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
    else:
        out_f, kc, vc = res
        data = {"k": kc, "v": vc}
    return out_f[:, None], {**data, **_cache_markers(cache)}


def _stated_scale(cfg):
    """``{"scale": s}`` where the config STATES the softmax's scale
    (``attention_scale``: a model whose scores are not over ``sqrt(d)``),
    else nothing: the kernels' own default, and the programs of every
    config without the field, stay as they are."""
    scale = getattr(cfg, "attention_scale", None)
    return {} if scale is None else {"scale": scale}


def _attend(cfg, mode, q, cache, positions, bias, window):
    """The attend half, through the selected kernel mode."""
    from deepspeed_tpu.models.transformer import (_paged_gather,
                                                  cached_attention)
    if _stated_scale(cfg) and not (
            "pages" in cache and mode in ("pallas_paged_decode",
                                          "pallas_chunked_prefill")):
        # the plain paths divide by sqrt(d): hand them q times what is left
        q = (q.astype(jnp.float32) * (cfg.attention_scale
                                      * q.shape[-1] ** 0.5)).astype(q.dtype)
    if "pages" in cache:
        if mode == "pallas_paged_decode":
            from deepspeed_tpu.ops.transformer.paged_attention import (
                paged_decode_attention)
            lengths = (positions[:, 0] + 1).astype(jnp.int32)
            return paged_decode_attention(
                q[:, 0], cache["k"], cache["v"], lengths, cache["pages"],
                layer=cache["layer"], k_scale=cache.get("k_scale"),
                v_scale=cache.get("v_scale"),
                int8_matmuls=cfg.decode_int8_matmuls,
                **_stated_scale(cfg))[:, None]
        if mode == "pallas_chunked_prefill":
            from deepspeed_tpu.ops.transformer.paged_attention import (
                paged_chunk_prefill_attention)
            starts = positions[:, 0].astype(jnp.int32)
            pages = cache["pages"]
            B_, S_ = q.shape[:2]
            rows = -(-S_ // MAX_CHUNK_S)
            if rows > 1:
                # past the kernel's bound: the block's K/V are in the pool,
                # so it is ``rows`` rows of MAX_CHUNK_S queries, each from
                # its own start over the same table row
                q = q.reshape((B_ * rows, S_ // rows) + q.shape[2:])
                starts = (starts[:, None] + (S_ // rows) * jnp.arange(
                    rows, dtype=jnp.int32)).reshape(-1)
                pages = jnp.repeat(pages, rows, axis=0)
            return paged_chunk_prefill_attention(
                q, cache["k"], cache["v"], starts, pages,
                layer=cache["layer"], k_scale=cache.get("k_scale"),
                v_scale=cache.get("v_scale"), **_stated_scale(cfg)).reshape(
                    (B_, S_) + q.shape[2:])
        # reference/gather fallback — the pre-kernel paged path: one
        # take_along_axis virtual-view copy per layer, then whatever
        # cached_attention does on the monolithic view.  For DECODE this
        # is the BENCH_r04 bs128 cliff, so it never happens silently.
        if q.shape[1] == 1:
            warning_once(
                "paged decode fell back to the take_along_axis gather "
                "path (" + _fallback_reason(bias, window)
                + ") — expect the BENCH_r04 bs128 decode cliff; see "
                "docs/serving.md 'Paged attention kernels'")
        g = _paged_gather(cache)
        return cached_attention(
            q, g["k"], g["v"], positions, bias=bias, window=window,
            k_scale=g.get("k_scale"), v_scale=g.get("v_scale"),
            int8_matmuls=cfg.decode_int8_matmuls)
    layer = cache.get("layer")
    return cached_attention(
        q, cache["k"], cache["v"], positions, bias=bias, window=window,
        layer=layer, k_scale=cache.get("k_scale"),
        v_scale=cache.get("v_scale"),
        int8_matmuls=cfg.decode_int8_matmuls)


def _band_attention(q, k, v, band):
    """Softmax attention over the keys ``band [S, T]`` allows a query — the
    ring paths' plain form.  q ``[B, S, H, D]``, k / v ``[B, T, KVH, D]``."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    logits = jnp.where(band[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _ring_write_and_attend(mode, q, k, v, positions, cache, window):
    """A sliding-window layer over a K/V RING a slot (``cache["ring"]``):
    ``cache["k"]`` / ``["v"]`` are ``[window layers, pages, page, KVH*D]``,
    ``cache["pages"] [B, n]`` the slots' ring pages, ``n * page ==
    window`` — position ``p`` lies in ring row ``p % window``, so the ring
    holds exactly the window once it is full.

    A decode step (one token a lane) writes its row, then attends the
    ring's ``min(p + 1, window)`` rows: all of them are in the band, in
    whatever order.  A chunk (one slot's, from ``positions[0, 0]``)
    attends the ring as it FOUND it — the ``window - 1`` positions before
    the chunk — and its own keys, then writes: of its ``cache["live"]``
    rows (all, where absent) the last ``window``, so that a padded tail
    pushes out nothing the next step attends."""
    from deepspeed_tpu.models.latent_attention import write_rows
    from deepspeed_tpu.models.transformer import (_paged_gather,
                                                  cached_attention)
    B_, S_, KVH, D = k.shape
    li, table = cache["layer"], cache["pages"]
    k_new = k.reshape(B_, S_, KVH * D)
    v_new = v.reshape(B_, S_, KVH * D)
    if table.shape[1] * cache["k"].shape[-2] != window:
        raise ValueError(
            f"a ring of {table.shape[1]} pages of {cache['k'].shape[-2]} "
            f"rows does not hold the window's {window} positions exactly")

    def write(tab, pos, kn, vn, keep=None):
        with jax.named_scope("cache.write"):
            return {"k": write_rows(cache["k"], li, tab, pos, kn, keep),
                    "v": write_rows(cache["v"], li, tab, pos, vn, keep),
                    **_cache_markers(cache)}

    if S_ == 1:
        pos = positions[:, 0].astype(jnp.int32)
        new = write(table, pos, k_new[:, 0], v_new[:, 0])
        lengths = jnp.minimum(pos + 1, window)
        if mode == "pallas_ring_decode":
            from deepspeed_tpu.ops.transformer.paged_attention import (
                paged_decode_attention)
            out = paged_decode_attention(q[:, 0], new["k"], new["v"],
                                         lengths, table, layer=li)[:, None]
        else:
            g = _paged_gather(new)          # row r: the position r mod ring
            out = cached_attention(q, g["k"], g["v"], (lengths - 1)[:, None])
        return out, new
    if B_ != 1:
        raise ValueError("a ring's chunk is one slot's")
    pos = positions[0].astype(jnp.int32)
    start = pos[0]
    if mode == "pallas_window_chunk":
        from deepspeed_tpu.ops.transformer.paged_attention import (
            window_chunk_attention)
        out = window_chunk_attention(
            q[0], k_new[0], v_new[0], cache["k"], cache["v"], start,
            table[0], window=window, layer=li)[None]
    else:
        # the ring's rows in position order, then the chunk's: a masked
        # dense band
        before = start - window + jnp.arange(window, dtype=jnp.int32)
        g = _paged_gather(cache)
        keys = lambda held, new: jnp.concatenate(
            [held[:, before % window], new], axis=1
        ).reshape(1, window + S_, KVH, D)
        key_pos = jnp.concatenate([before, pos])
        band = (key_pos[None, :] >= 0) & (key_pos[None, :] <= pos[:, None]) \
            & (key_pos[None, :] > pos[:, None] - window)
        out = _band_attention(q, keys(g["k"], k_new), keys(g["v"], v_new),
                              band)
    live = cache.get("live")
    keep = jnp.ones((S_,), bool) if live is None else live
    last = jnp.max(jnp.where(keep, pos, -1))
    return out, write(table[0], pos, k_new[0], v_new[0],
                      keep & (pos > last - window))


def _fallback_reason(bias, window):
    if bias is not None:
        return "alibi bias"
    if window is not None:
        return "sliding-window layer"
    if not pallas_supported():
        return "DSTPU_DISABLE_FLASH=1"
    return "unsupported configuration"


def write_and_attend(cfg, q, k, v, positions, cache, *, bias=None,
                     window=None, prefill=False):
    """Write this step's K/V rows into the cache and attend — the single
    entry point behind ``Attention.__call__``'s cached path for EVERY
    cache layout and program class.  Returns ``(out [B,S,H,D],
    new_cache)``.

    ``prefill`` (static): a from-zero multi-token block attends only
    within itself — the attend swaps to causal flash over the fresh
    q/k/v (the dense cached fallback would materialize a [B, H, S,
    S_max] fp32 score tensor, ~33 GB at a 4k prompt); the cache write
    still happens.  (Alibi models keep the dense path: their bias is
    sized to the cache, not the prompt.)"""
    from deepspeed_tpu.models.transformer import _prefill_attention
    B_, S_ = k.shape[0], k.shape[1]
    KVHD = k.shape[-2] * k.shape[-1]
    paged = "pages" in cache
    prefill_from_zero = bool(prefill) and S_ > 1 and bias is None
    mode = select_kernel(s=S_, paged=paged, has_bias=bias is not None,
                         has_window=window is not None,
                         ring="ring" in cache)
    if "ring" in cache:
        return _ring_write_and_attend(mode, q, k, v, positions, cache,
                                      window)
    if not prefill_from_zero:
        fused = _fused_decode(cfg, q, k, v, positions, cache, mode, window)
        if fused is not None:
            return fused
    k_new = k.reshape(B_, S_, KVHD)
    v_new = v.reshape(B_, S_, KVHD)
    ks_new = vs_new = None
    if cfg.kv_cache_quant:
        kvh = k.shape[-2]
        k_new, ks_new = _quant_rows(k_new, kvh)
        v_new, vs_new = _quant_rows(v_new, kvh)
    with jax.named_scope("cache.write"):
        new_cache = _write_cache(cache, k_new, v_new, ks_new, vs_new,
                                 positions)
    if prefill_from_zero:
        # one shared prefill attend for every cache layout: the cache
        # was written above; the attention itself is plain causal flash
        # over this block's fresh q/k/v
        out = _prefill_attention(q, k, v, cfg, window=window)
    else:
        out = _attend(cfg, mode, q, new_cache, positions, bias, window)
    return out, new_cache


# ---- a convolution's rows a slot ----------------------------------------- #
def conv_state_update(z, w, state, *, start=None, last=None):
    """Run a short causal depthwise convolution's positions over its ROWS a
    slot — the last ``K - 1`` inputs — and leave the new ones in the pool:
    ``state = (pool [layers, rows, ...short_conv.rows_shape], layer, rows)``
    or None (a sequence from its start, nothing kept).  ``w [K, h]`` float32.
    A chunk (``start`` a scalar): ``z [T, h]`` consecutive positions of ONE
    slot, ``rows`` its state row, from zeros where ``start == 0``, the rows
    kept those that end at ``last`` (None: the chunk's last).  A step
    (``start`` None): row ``n`` is lane ``n``'s one token, ``rows [N]`` by
    the lanes' tables — a lane on the trash row writes there.  Returns
    ``(conv`` float32``, pool)``."""
    from deepspeed_tpu.ops.transformer import short_conv
    pool, layer, rows = (None, None, None) if state is None else state
    if start is None:
        return short_conv.decode_step(z, w, pool, layer, rows,
                                      pallas=pallas_supported())
    return short_conv.chunk(z, w, pool, layer, rows, start, last)


# ---- a matrix state a slot: the gated delta rule ------------------------- #
def delta_state_update(q, k, v, g, beta, state, *, start=None, real=None,
                       live=None):
    """Run a gated delta-rule layer's positions through its MATRIX STATE and
    leave the state in the pool: ``state = (pool [layers, rows, H, d, d]``
    float32``, layer, rows)``.  A chunk (``start`` a scalar): ``q`` / ``k`` /
    ``v`` / ``g [T, H, d]``, ``beta [T, H]`` consecutive positions of ONE
    slot, ``rows`` its state row, from zeros where ``start == 0`` and
    through the chunk's ``real`` rows (None: all).  A step (``start``
    None): row ``n`` is lane ``n``'s one token, ``rows [N]``, dead lanes
    (``live [N]``) write nothing.  Returns ``(o, pool)``."""
    from deepspeed_tpu.ops.transformer import delta_attention
    pool, layer, rows = state
    pallas = pallas_supported()
    if start is None:
        return delta_attention.decode_step(q, k, v, g, beta, pool, layer,
                                           rows, live, pallas=pallas)
    return delta_attention.chunk_scan(
        q, k, v, g, beta, pool, layer, rows, fresh=start == 0,
        real=q.shape[0] if real is None else real, pallas=pallas)


# ---- a matrix state a slot: a state-space layer's scan ------------------- #
def ssm_state_update(x, dt, a, b, c, state, *, start=None, real=None,
                     live=None):
    """Run a Mamba-2 layer's positions through its MATRIX STATE and leave
    the state in the pool: ``state = (pool [layers, rows, H, P, N]``
    float32``, layer, rows)``.  A chunk (``start`` a scalar): ``x [T, H,
    P]``, ``dt`` / ``a [T, H]`` (step size, log-decay), ``b`` / ``c [T, N]``
    (one group: every head's) or ``[T, G, N]`` (a head reads group ``h // (H
    / G)``'s), consecutive positions of ONE slot, ``rows`` its state row, from zeros
    where ``start == 0`` and through the chunk's ``real`` rows (None: all).
    A step (``start`` None): row ``n`` is lane ``n``'s one token, ``rows
    [N]``, dead lanes (``live [N]``) write nothing.  Returns ``(y, pool)``
    — ``y = S C``, without the layer's skip."""
    from deepspeed_tpu.ops.transformer import ssd
    pool, layer, rows = state
    pallas = pallas_supported()
    if start is None:
        return ssd.decode_step(x, dt, a, b, c, pool, layer, rows, live,
                               pallas=pallas)
    return ssd.chunk_scan(
        x, dt, a, b, c, pool, layer, rows, fresh=start == 0,
        real=x.shape[0] if real is None else real, pallas=pallas)
