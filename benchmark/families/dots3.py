"""The dots3 family (dots-studio, ``model_type: dots3_note``): weights from a
seed, the adapter that hands them to the program, and the plain reference.

**Reference.**  The language model as ``config.json`` spells it (the vision
and audio towers and the MTP module are not in it).  ``x`` is a block's input
after its RMSNorm.

*Full-attention layer*: ``c_q = RMSNorm(x W_qa) * sqrt(hidden / q_rank)``;
``q = c_q W_qb`` -> heads of ``nope + rope``, the rope part roped;
``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv) * sqrt(hidden / kv_rank)``,
``k_r`` roped, one for all heads; ``[k_nope_h | v_h] = c_kv W_kvb``; logit
``(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``.  The indexer:
``q^I = c_q W^I_q`` (64 heads of 128), ``k^I = LayerNorm(x W^I_k)``, rope on
the first ``rope`` features of both, ``w = x W^I_w``;
``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) / sqrt(64 x 128)``; the
softmax runs over the ``index_topk`` positions ``s <= t`` of largest ``I``
(all while ``t < index_topk``).  Index scores always in float32.  Headwise
gate ``sigmoid(x W_g)`` on each head's output before ``W_o``.

*Window layer*: the same latent attention at the ``swa_*`` sizes, no
indexer, keys ``t - window < s <= t``.

*FFN*: dense SwiGLU in the first ``first_k_dense_replace`` layers; then
``s = sigmoid(x W_r)`` over ALL published experts in float32, the top 8 of
``s + b``, gates ``s_e / sum_chosen s``; ``y = shared(x) + sum over the
chosen experts that are HELD of gate_e E_e(x)`` — the chip's share, experts
``held[0] .. held[0] + held[1] - 1``; what the absent experts would add is
left out here as in the program.

Plain ``jax.numpy`` in float32 with matmul precision ``highest``; no kernel,
no cache, no batching; ONE sequence, a jitted program a half-layer and
attention in blocks of 64 queries against all keys, so that 16k positions
fit beside a served model's weights; the expert layer a loop over the
held experts, each computed over every token and masked by the token's
choice.  Weights are regenerated from the seed alone, tensor by tensor (an
expert at a time inside the loop), so the reference shares no array with the
program.

**What is assumed** (the configuration file lists the same): the
``sqrt(hidden / rank)`` reading of ``apply_mla_qkv_lora_rescale``; rotary
positions in the half-split layout; the indexer's LayerNorm with scale and
bias, its rope at the layer's theta on the first 64 features, its score's
``64^-1/2 128^-1/2`` factors; ``sliding_window_size`` 513 as the token and
its 512 predecessors; no expert groups.

**The weights' draw, and why** (normal, from ``--seed``, rounded to
bfloat16; std 0.02 but where said; norm gains 1 +- 0.1).  Token embeddings
std 2: a token's own embedding decides its routing, and uniform tokens
route near-uniformly (``families/olmoe.py``).  With std 0.02 the rescaled
latents (std sqrt(5), sqrt(10)) already give a query or key feature std
~1.4 and attention logits a standard deviation of ~2 units: a softmax over
2,048 kept keys rests on a few tens of them, so WHICH keys were kept
decides the output (``recent_topk`` reads hundreds of times sound); at std
0.03 the logits' std is 4.2, attention is near one-hot and every rounding
flips it (sound 0.42–0.57, no control above it).  ``o_proj`` std 0.04:
attention adds ~0.6 a feature to a stream of ~2.5.  The shared expert's
and the routed experts' down-projections std 0.06 (the held experts' part
is ~10% of the stream: ``held_dropped`` reads it): gates are renormalised
over the chosen 8 and a chip holds ~1 of a token's 8, so one routing flip
(the 8th and 9th of 256 scores lie 6% of a standard deviation apart;
bfloat16 moves the router's input by 0.1–1%) swaps the token's WHOLE held
contribution; at std 0.15 (a quarter of the stream) those flips alone put
float32 and bfloat16 0.19 apart in mean logit gap and drowned every
control (chip, PR 31); at 0.06 they cost ~0.001, at 0.1 ~0.008 (CPU,
hidden 512, 2,048 positions).  The selection bias std 0.02.

**The distilled indexer** (:func:`_distilled`).  With every tensor drawn
on its own the indexer's scores have nothing to do with the attention they
select for: the keys at its threshold weigh as much as any, bfloat16
inputs move ~1% of the kept set across the threshold, and those flips
alone were the comparison's floor — 0.081 against float32 with the
selection, 0.002 without it (the reference's own bfloat16 emulation, CPU,
hidden 512, 2,048 positions, top 256; on the chip 0.024 sound beside
``float8_latent`` 0.045, which no limit separates).  A TRAINED indexer is
distilled from the attention's own distribution, so its threshold keys are
keys no head attends.  The draw imitates that: the embeddings share a
common component (std 0.25 a feature beside the tokens' own 2 — trained
embeddings lie in a narrow cone), every head's logit has a part in common
(80% of the variance of the rotary columns and of 64 of the 128 nope
columns of ``W_qb``, of those nope columns of ``W_kvb``), and the indexer
computes that common part — its query heads the common columns plus a
quarter of a draw of their own, its key the rotary key's and the common
nope key's projections of the block's input, its head weights positive
along the embeddings' common direction.  Same CPU reading after it:
bfloat16 0.004, ``float8_latent`` 0.020, ``float8_experts`` 0.025,
``held_dropped`` 0.075, ``recent_topk`` 1.7.  The chip's readings are in
the cell's file under ``defined_by`` and in PERF.md §2.

``precision`` selects the control: ``"float32"`` (the reference),
``"bfloat16"`` (what a sound program computes), ``"float8"`` (every matmul
operand rounded to e4m3 with a per-tensor scale), and this family's own,
each bfloat16 but for one thing: ``"float8_experts"`` (the experts' three
matmuls in float8), ``"float8_latent"`` (the cached rows ``[c_kv | k_r]``
of both layer kinds rounded to float8 — an 8-bit cache), ``"recent_topk"``
(the kept set replaced by the most recent ``index_topk`` positions — an
indexer that does nothing), ``"held_dropped"`` (the held experts' part
left out — an expert layer that routes and computes only the shared
expert).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.opt import HIGHEST, _tensor, seed_key

_W, _G, _EMBED, _DOWN, _SHARED, _ATTN, _OUT, _BIAS = \
    0.02, 0.1, 2.0, 0.06, 0.06, 0.02, 0.04, 0.02
# the distilled indexer (the docstring's "weights' draw"): the embeddings'
# common component, the share of a FOLLOWED attention column's variance
# that every head has in common, and how far the indexer's own heads
# differ from their common part
_EMBED_MEAN, _FOLLOW, _INDEX_OWN = 0.25, 0.8, 0.25
GAP_ROWS = 64            # the longest answer a cell may ask for
QUERY_BLOCK = 64


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    if model.get("rope_scaling") or model.get("attention_bias") \
            or model.get("tie_word_embeddings") \
            or model.get("scoring_func") != "sigmoid" \
            or model.get("topk_method") != "noaux_tc" \
            or model.get("hidden_act", "silu") != "silu":
        raise ValueError("this reference is dots3_note as released: no rope "
                         "scaling, no biases, untied head, sigmoid + "
                         "noaux_tc routing, SwiGLU")
    kinds = tuple(model["layer_types"])
    if len(kinds) != model["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    published = model.get("n_routed_experts_published",
                          model["n_routed_experts"])
    held = tuple(model.get("held_experts", (0, model["n_routed_experts"])))
    if held[1] != model["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    latent = lambda pre, **more: tuple(sorted(dict(
        heads=model[pre + "num_attention_heads"],
        q_rank=model[pre + "q_lora_rank"],
        kv_rank=model[pre + "kv_lora_rank"],
        nope=model[pre + "qk_nope_head_dim"],
        rope=model[pre + "qk_rope_head_dim"], v=model[pre + "v_head_dim"],
        theta=float(model[pre + "rope_theta"]), **more).items()))
    return dict(
        h=model["hidden_size"], kinds=kinds, layers=len(kinds),
        dense_layers=model["first_k_dense_replace"],
        f=model["intermediate_size"], ef=model["moe_intermediate_size"],
        experts=published, held=held, shared=model["n_shared_experts"],
        top_k=model["num_experts_per_tok"],
        norm_topk=bool(model["norm_topk_prob"]),
        scaling=float(model["routed_scaling_factor"]),
        vocab=model["vocab_size"], eps=float(model["rms_norm_eps"]),
        rescale=bool(model["apply_mla_qkv_lora_rescale"]),
        positions=model["max_position_embeddings"],
        full=latent("", window=0, index_heads=model["index_n_heads"],
                    index_dim=model["index_head_dim"],
                    index_topk=model["index_topk"]),
        swa=latent("swa_", window=model["sliding_window_size"]))


def _kind(z, layer):
    return dict(z["full"] if z["kinds"][layer] == "full_attention"
                else z["swa"])


def _attn_kinds(z, a):
    h, H = z["h"], a["heads"]
    kinds = [("q_a", (h, a["q_rank"]), _W, 0.0),
             ("q_a_norm", (a["q_rank"],), _G, 1.0),
             ("q_b", (a["q_rank"], H * (a["nope"] + a["rope"])), _ATTN, 0.0),
             ("kv_a", (h, a["kv_rank"] + a["rope"]), _W, 0.0),
             ("kv_a_norm", (a["kv_rank"],), _G, 1.0),
             ("kv_b", (a["kv_rank"], H * (a["nope"] + a["v"])), _ATTN, 0.0),
             ("o_proj", (H * a["v"], h), _OUT, 0.0),
             ("gate", (h, H), _W, 0.0)]
    if not a["window"]:
        J, D = a["index_heads"], a["index_dim"]
        kinds += [("index_q", (a["q_rank"], J * D), _ATTN, 0.0),
                  ("index_k", (h, D), _ATTN, 0.0),
                  ("index_k_norm_scale", (D,), _G, 1.0),
                  ("index_k_norm_bias", (D,), _BIAS, 0.0),
                  ("index_w", (h, J), _W, 0.0),
                  # what every head's followed columns have in common:
                  # [rope | nope] on the query side, nope on the key side
                  ("common_q", (a["q_rank"], D), _ATTN, 0.0),
                  ("common_k", (a["kv_rank"], D - a["rope"]), _ATTN, 0.0)]
    return kinds


def _distilled(z, a, w, mean):
    """A full layer's drawn tensors ``w`` with the indexer TIED to the
    attention it selects for, as a trained indexer is distilled from the
    attention's own logits (the docstring's "weights' draw" has the why).

    The indexer follows ``n = index_dim - rope`` nope features and the
    ``rope`` rotary ones.  Every head's followed columns of ``W_qb`` are
    ``sqrt(f) common + sqrt(1 - f) own`` (the per-element std stays), the
    first ``n`` nope columns of ``W_kvb`` likewise; the rotary key is one
    for all heads already.  ``W^I_q`` is the common part (each index head
    adds a little of its own draw), ``W^I_k`` the rotary key's projection
    beside the common nope key's THROUGH ``W_kva`` and its norm gain, the
    two halves weighed as the attention logit weighs them, and ``W^I_w``
    gains the embeddings' common direction, on which every position's
    input has the same sign: the index score is then a rising function of
    the logit part that all heads share."""
    f32 = lambda t: t.astype(jnp.float32)
    H, J, D = a["heads"], a["index_heads"], a["index_dim"]
    nope, rope, rank = a["nope"], a["rope"], a["kv_rank"]
    n = D - rope
    mix = lambda share, common, own: \
        np.sqrt(share) * common + np.sqrt(1.0 - share) * own
    cq, ck = f32(w["common_q"]), f32(w["common_k"])
    q_b = f32(w["q_b"]).reshape(-1, H, nope + rope)
    q_b = jnp.concatenate([
        mix(_FOLLOW, cq[:, None, rope:], q_b[..., :n]), q_b[..., n:nope],
        mix(_FOLLOW, cq[:, None, :rope], q_b[..., nope:])], -1)
    kv_b = f32(w["kv_b"]).reshape(rank, H, nope + a["v"])
    kv_b = jnp.concatenate([
        mix(_FOLLOW, ck[:, None], kv_b[..., :n]), kv_b[..., n:]], -1)
    index_q = cq[:, None] + _INDEX_OWN * f32(w["index_q"]).reshape(-1, J, D)
    # attention's nope key is the NORMED latent (rms sqrt(hidden / rank) if
    # rescaled) through sqrt(f) common_k; the indexer's key starts from the
    # block's input, whose latent has rms ~ sqrt(hidden) _W before the norm
    kv_a = f32(w["kv_a"])
    up = np.sqrt(z["h"] / rank) if z["rescale"] else 1.0
    through = jnp.matmul(kv_a[:, :rank] * f32(w["kv_a_norm"]), ck,
                         precision=HIGHEST) \
        * (np.sqrt(_FOLLOW) * up / (np.sqrt(z["h"]) * _W))
    index_k = jnp.concatenate([kv_a[:, rank:], through], -1)
    along = f32(mean) / jnp.sqrt(jnp.sum(jnp.square(f32(mean))))
    out = {k: v for k, v in w.items() if not k.startswith("common_")}
    bf = lambda t, like: t.reshape(like.shape).astype(jnp.bfloat16)
    out.update(q_b=bf(q_b, w["q_b"]), kv_b=bf(kv_b, w["kv_b"]),
               index_q=bf(index_q, w["index_q"]),
               index_k=bf(index_k, w["index_k"]),
               index_w=bf(f32(w["index_w"]) + along[:, None], w["index_w"]))
    return out


def _layer_kinds(z, layer):
    """``[(name, shape, std, mean)]`` of one layer's tensors but its routed
    experts' (those are drawn an expert at a time, :func:`expert_weights`)."""
    h = z["h"]
    kinds = [("ln1_g", (h,), _G, 1.0), ("ln2_g", (h,), _G, 1.0)] \
        + _attn_kinds(z, _kind(z, layer))
    if layer < z["dense_layers"]:
        return kinds + [("w_gate", (h, z["f"]), _W, 0.0),
                        ("w_up", (h, z["f"]), _W, 0.0),
                        ("w_down", (z["f"], h), _W, 0.0)]
    sf = z["shared"] * z["ef"]
    return kinds + [("router", (h, z["experts"]), _W, 0.0),
                    ("select_bias", (z["experts"],), _BIAS, 0.0),
                    ("shared_gate", (h, sf), _W, 0.0),
                    ("shared_up", (h, sf), _W, 0.0),
                    ("shared_down", (sf, h), _SHARED, 0.0)]


def _global_kinds(z):
    h = z["h"]
    return [("embed", (z["vocab"], h), _EMBED, 0.0), ("lnf_g", (h,), _G, 1.0),
            ("head", (h, z["vocab"]), _W, 0.0),
            ("embed_mean", (h,), _EMBED_MEAN, 0.0)]


def _embed_mean(z, key, draw):
    i = [k[0] for k in _global_kinds(z)].index("embed_mean")
    return draw(key, i, 0, (z["h"],), _EMBED_MEAN, 0.0)


def layer_weights(z, key, layer, draw=_tensor):
    w = {name: draw(key, 100 + i, layer, shape, std, mean)
         for i, (name, shape, std, mean) in enumerate(
             _layer_kinds(z, layer))}
    a = _kind(z, layer)
    return w if a["window"] else _distilled(z, a, w,
                                            _embed_mean(z, key, draw))


def global_weights(z, key, draw=_tensor):
    """``embed`` is the drawn table plus the embeddings' common component
    (one vector, added to every row)."""
    g = {name: draw(key, i, 0, shape, std, mean)
         for i, (name, shape, std, mean) in enumerate(_global_kinds(z))}
    mean = g.pop("embed_mean")
    g["embed"] = (g["embed"].astype(jnp.float32)
                  + mean.astype(jnp.float32)).astype(jnp.bfloat16)
    return g


# the reference draws a tensor in a program of its own: threefry's
# temporaries for one [16384, 5120] matrix are 3 GB, freed before the next
_tensor_alone = jax.jit(_tensor, static_argnums=(3, 4, 5))


def expert_weights(z, key, layer, expert):
    """The three matrices of published expert ``expert`` (traced or not)
    of ``layer``: a pure function of ``(seed, layer, expert)``."""
    h, f = z["h"], z["ef"]
    k = jax.random.fold_in(jax.random.fold_in(key, 90), layer)
    draw = lambda i, shape, std: (std * jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(k, expert), i), shape,
        jnp.float32)).astype(jnp.bfloat16)
    return {"wg": draw(0, (h, f), _W), "wu": draw(1, (h, f), _W),
            "wd": draw(2, (f, h), _DOWN)}


# --------------------------------------------------------------------- #
# The program's side: its module, and its parameter tree from the seed
# --------------------------------------------------------------------- #
def program_model(model, **overrides):
    """The program's own module at the file's sizes, holding the file's
    share of the experts."""
    from deepspeed_tpu.models.dots3 import dots3_model
    z = sizes_of(model)                  # refuses what the reference lacks
    return dots3_model(model, held_experts=z["held"],
                       **{"dtype": "bfloat16", **overrides})


_ATTN_LEAVES = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
                "o_proj", "gate", "index_q", "index_k",
                "index_k_norm_scale", "index_k_norm_bias", "index_w")
_PROGRAM_LEAVES = {      # the program's leaf path -> the plain tensor
    **{("attn", n): n for n in _ATTN_LEAVES},
    ("input_norm", "scale"): "ln1_g", ("post_attn_norm", "scale"): "ln2_g",
    ("mlp", "gate_proj", "kernel"): "w_gate",
    ("mlp", "up_proj", "kernel"): "w_up",
    ("mlp", "down_proj", "kernel"): "w_down",
    ("moe_mlp", "gate_kernel"): "router",
    ("moe_mlp", "select_bias"): "select_bias",
    ("moe_mlp", "shared_gate", "kernel"): "shared_gate",
    ("moe_mlp", "shared_up", "kernel"): "shared_up",
    ("moe_mlp", "shared_down", "kernel"): "shared_down",
    ("embed_tokens", "embedding"): "embed",
    ("final_norm", "scale"): "lnf_g", ("lm_head", "kernel"): "head",
}
_EXPERT_LEAVES = {"experts_wg": "wg", "experts_wi": "wu", "experts_wo": "wd"}


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves) from ``seed``, on the
    device, in one jitted call whose compiled form serves every seed.  Each
    tensor is drawn where it lands; the held experts of a layer are drawn
    an expert at a time, as the reference draws them."""
    z = sizes_of(model)
    first, count = z["held"]
    abstract = jax.eval_shape(module.init, jax.random.key(0),
                              {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key):
        glob = global_weights(z, key)
        layers, leaves = {}, []
        for path, leaf in flat:
            names = tuple(p.key for p in path)[1:]       # drop 'params'
            if not names[0].startswith("layers_"):
                x = glob[_PROGRAM_LEAVES[names]]
            else:
                l = int(names[0][7:])
                if names[-1] in _EXPERT_LEAVES:
                    x = jax.vmap(lambda e: expert_weights(z, key, l, e)[
                        _EXPERT_LEAVES[names[-1]]])(
                            first + jnp.arange(count))
                else:
                    if l not in layers:
                        layers[l] = layer_weights(z, key, l)
                    x = layers[l][_PROGRAM_LEAVES[names[1:]]]
            leaves.append(x.reshape(leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(seed_key(seed))


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #
# The rounding rules are ``families/opt.py``'s — bfloat16 is 8 exponent and
# 7 mantissa bits, float8 is e4m3 with a per-tensor scale — but written
# with ``lax.reduce_precision``, not ``astype`` there and back: on the TPU
# XLA removes a convert to a narrower float and back where nothing else
# reads the narrow value (0.0 and 1.8e-8 mean relative change through
# bfloat16 and float8 on the chip, PR 31), which left ``_store`` and an
# element-wise float8 rounding — this family's 8-bit cache rows — doing
# nothing there.  (e4m3 by ``reduce_precision`` tops out at 240, not
# e4m3fn's 448: the scale is taken from that.)
def _store(x, precision):
    """What a program of that precision keeps between operations."""
    return x if precision == "float32" else jax.lax.reduce_precision(x, 8, 7)


def _round(x, precision):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return jax.lax.reduce_precision(x, 8, 7)
    if precision == "float8":
        scale = jnp.max(jnp.abs(x)) / 240.0 + 1e-30
        return jax.lax.reduce_precision(x / scale, 4, 3) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(a, b, precision):
    """``a [..., K] @ b [K, N]``, operands rounded and the result stored
    as ``precision`` says."""
    return _store(jnp.matmul(_round(a, precision),
                             _round(b.astype(jnp.float32), precision),
                             precision=HIGHEST), precision)


def _parts(precision):
    """``precision`` -> what each part of the model computes in:
    ``(everything else, the experts' matmuls, the cached rows, whether the
    indexer selects, whether the held experts add their part)``."""
    if precision == "float8_experts":
        return "bfloat16", "float8", "bfloat16", True, True
    if precision == "float8_latent":
        return "bfloat16", "bfloat16", "float8", True, True
    if precision == "recent_topk":
        return "bfloat16", "bfloat16", "bfloat16", False, True
    if precision == "held_dropped":
        return "bfloat16", "bfloat16", "bfloat16", True, False
    return precision, precision, precision, True, True


def _f32(t):
    return t.astype(jnp.float32)


def _rms_norm(x, g, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(g)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(g) + _f32(b)


def _rope(t, theta, dims=None, start=0):
    """Rotary positions ``start ..`` on the first ``dims`` features of
    ``t [S, ..., D]`` (default all), half-split layout."""
    dims = dims or t.shape[-1]
    half = dims // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = (start + jnp.arange(t.shape[0])).astype(jnp.float32)[:, None] \
        * freqs
    ang = ang.reshape((t.shape[0],) + (1,) * (t.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    t1, t2 = t[..., :half], t[..., half:dims]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin,
                            t[..., dims:]], axis=-1)


def _kept(z, a, x, c_q, w, select):
    """``[S, S]``-shaped answers a block of queries at a time: a function
    ``block start -> kept [B, S] bool`` for a full layer (the indexer's
    top-k, in float32 always) or a window layer (the band)."""
    S = x.shape[0]
    keys = jnp.arange(S)[None, :]

    def rows(start):
        return (start + jnp.arange(QUERY_BLOCK))[:, None]

    if a["window"]:
        return lambda start: (keys <= rows(start)) \
            & (keys > rows(start) - a["window"])
    k = a["index_topk"]
    if not select:
        return lambda start: (keys <= rows(start)) & (keys > rows(start) - k)
    J, D = a["index_heads"], a["index_dim"]
    hi = lambda u, v: jnp.matmul(u, _f32(v), precision=HIGHEST)
    qi = _rope(hi(c_q, w["index_q"]).reshape(S, J, D), a["theta"], a["rope"])
    ki = _rope(_layer_norm(hi(x, w["index_k"]), w["index_k_norm_scale"],
                           w["index_k_norm_bias"], z["eps"]),
               a["theta"], a["rope"])
    wi = hi(x, w["index_w"]) * (J ** -0.5 * D ** -0.5)

    def kept(start):
        q = jax.lax.dynamic_slice_in_dim(qi, start, QUERY_BLOCK)
        wq = jax.lax.dynamic_slice_in_dim(wi, start, QUERY_BLOCK)
        s = jnp.einsum("qjd,sd->qjs", q, ki, precision=HIGHEST)
        score = jnp.einsum("qjs,qj->qs", jax.nn.relu(s), wq,
                           precision=HIGHEST)
        visible = keys <= rows(start)
        score = jnp.where(visible, score, -jnp.inf)
        _, top = jax.lax.top_k(score, min(k, S))      # ties: lower index
        chosen = jnp.zeros(score.shape, bool).at[
            jnp.arange(QUERY_BLOCK)[:, None], top].set(True)
        return visible & chosen

    return kept


def _attention(z, a, x, w, precision):
    """Latent attention of ONE sequence ``x [S, h]`` (normed input)."""
    outer, _, cached, select, _ = _parts(precision)
    S, H = x.shape[0], a["heads"]
    up = lambda rank: np.sqrt(z["h"] / rank) if z["rescale"] else 1.0
    c_q = _store(_rms_norm(_mm(x, w["q_a"], outer), w["q_a_norm"], z["eps"])
                 * up(a["q_rank"]), outer)
    kv = _mm(x, w["kv_a"], outer)
    row = _store(jnp.concatenate([
        _rms_norm(kv[:, :a["kv_rank"]], w["kv_a_norm"], z["eps"])
        * up(a["kv_rank"]), _rope(kv[:, a["kv_rank"]:], a["theta"])], -1),
        outer)
    if cached != outer:                  # what an 8-bit cache would hold
        row = _store(_round(row, cached), outer)
    c_kv, k_r = row[:, :a["kv_rank"]], row[:, a["kv_rank"]:]
    kv_b = w["kv_b"].reshape(a["kv_rank"], H, a["nope"] + a["v"])
    k_nope = _mm(c_kv, kv_b[..., :a["nope"]].reshape(a["kv_rank"], -1),
                 outer).reshape(S, H, a["nope"])
    v = _mm(c_kv, kv_b[..., a["nope"]:].reshape(a["kv_rank"], -1),
            outer).reshape(S, H, a["v"])
    kept = _kept(z, a, x, c_q, w, select)
    scale = 1.0 / np.sqrt(a["nope"] + a["rope"])
    r = lambda t: _round(t, outer)
    k_nope, k_r, v = r(k_nope), r(k_r), r(v)     # matmul operands, once

    def block(start):
        # the block's queries from its slice of the query latent; its
        # heads' outputs gated and through W_o before the next block
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, QUERY_BLOCK)
        qb = _mm(cut(c_q), w["q_b"], outer).reshape(
            QUERY_BLOCK, H, a["nope"] + a["rope"])
        qb = _store(jnp.concatenate(
            [qb[..., :a["nope"]],
             _rope(qb[..., a["nope"]:], a["theta"], start=start)], -1),
            outer)
        s = jnp.einsum("qhd,shd->hqs", r(qb[..., :a["nope"]]), k_nope,
                       precision=HIGHEST) \
            + jnp.einsum("qhd,sd->hqs", r(qb[..., a["nope"]:]), k_r,
                         precision=HIGHEST)
        p = jax.nn.softmax(jnp.where(kept(start)[None], s * scale, -1e30),
                           axis=-1)
        out = jnp.einsum("hqs,shd->qhd", r(_store(p, outer)), v,
                         precision=HIGHEST)
        g = jax.nn.sigmoid(_mm(cut(x), w["gate"], outer))
        return _mm(_store(out * g[..., None], outer).reshape(
            QUERY_BLOCK, -1), w["o_proj"], outer)

    return jax.lax.map(block, jnp.arange(0, S, QUERY_BLOCK)).reshape(S, -1)


def _swiglu(a, wg, wu, wd, precision):
    hid = _store(jax.nn.silu(_mm(a, wg, precision)) * _mm(a, wu, precision),
                 precision)
    return _mm(hid, wd, precision)


def expert_layer(z, key, layer, a, w, precision, held=None, shared=True):
    """The routed expert layer on ``a [S, h]``: the experts ``held``
    (default the configuration's share; ``(0, experts)`` is the uncut
    layer) each computed over every token and masked by the token's choice,
    plus — ``shared`` — the shared expert.  Nothing held is dropped."""
    outer, inner, _, _, routed = _parts(precision)
    first, count = held or z["held"]
    scores = jax.nn.sigmoid(jnp.matmul(
        _round(a, outer), _round(_f32(w["router"]), outer),
        precision=HIGHEST))                             # float32, kept
    _, top_i = jax.lax.top_k(scores + _f32(w["select_bias"]), z["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=1)
    if z["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * z["scaling"]

    def one(acc, e):
        ew = expert_weights(z, key, layer, e)
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(a, ew["wg"], ew["wu"],
                                               ew["wd"], inner), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(a),
                          first + jnp.arange(count if routed else 0))
    if shared and z["shared"]:         # an expert too: ``inner``
        acc = acc + _swiglu(a, w["shared_gate"], w["shared_up"],
                            w["shared_down"], inner)
    return _store(acc, outer)


# Every layer is two jitted programs of its own — attention, then the FFN —
# and so are the embedding, the head and each tensor's draw: at 16k
# positions the whole forward in ONE program needs 17.7 GB (the compiler
# keeps every draw's temporaries and every layer's keys and values alive
# at once), and a served model's 8.2 GB of weights sit beside it.
@functools.partial(jax.jit, static_argnames=("sizes", "precision", "layer"))
def _attention_jit(x, w, *, sizes, precision, layer):
    z, outer = dict(sizes), _parts(precision)[0]
    a = _store(_rms_norm(x, w["ln1_g"], z["eps"]), outer)
    return _store(x + _attention(z, _kind(z, layer), a, w, precision), outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "layer"))
def _ffn_jit(key, x, w, *, sizes, precision, layer):
    z, outer = dict(sizes), _parts(precision)[0]
    a = _store(_rms_norm(x, w["ln2_g"], z["eps"]), outer)
    if layer < z["dense_layers"]:
        return _store(x + _swiglu(a, w["w_gate"], w["w_up"], w["w_down"],
                                  outer), outer)
    return _store(x + expert_layer(z, key, layer, a, w, precision), outer)


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed_jit(g, tokens, *, precision):
    return _store(_f32(g["embed"])[tokens], _parts(precision)[0])


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _head_jit(g, x, positions, *, sizes, precision):
    z, outer = dict(sizes), _parts(precision)[0]
    h = _store(_rms_norm(x[positions], g["lnf_g"], z["eps"]), outer)
    return _mm(h, g["head"], outer)


def _logits(z, key, tokens, positions, precision):
    """Logits ``[R, V]`` at ``positions [R]`` of one sequence ``tokens
    [S]`` (``S`` a multiple of 64)."""
    kw = dict(sizes=_static(z), precision=precision)
    g = global_weights(z, key, _tensor_alone)
    x = _embed_jit(g, tokens, precision=precision)
    for layer in range(z["layers"]):
        w = layer_weights(z, key, layer, _tensor_alone)
        x = _attention_jit(x, w, layer=layer, **kw)
        x = _ffn_jit(key, x, w, layer=layer, **kw)
        del w
    return _head_jit(g, x, positions, **kw)


def _static(z):
    return tuple(sorted(z.items()))


def _padded(tokens, pad_to=None):
    n = -(-max(len(tokens), pad_to or 0) // QUERY_BLOCK) * QUERY_BLOCK
    row = np.zeros(n, np.int32)
    row[:len(tokens)] = tokens
    return jnp.asarray(row)


def logits(z, seed, tokens, precision="float32"):
    """All logits ``[S, V]`` of ONE sequence ``tokens [S]`` — what the CPU
    tests compare the program with."""
    return _logits(z, seed_key(seed), _padded(tokens),
                   jnp.arange(len(tokens)), precision)


def nll_at(z, seed, tokens, positions, precision="float32"):
    """Next-token negative log-likelihood ``[B, R]``: row ``b``'s loss of
    predicting ``tokens[b, p + 1]`` at each ``p`` of ``positions[b]``."""
    out = []
    for row, pos in zip(np.asarray(tokens), np.asarray(positions)):
        lg = _logits(z, seed_key(seed), _padded(row),
                     jnp.asarray(pos, jnp.int32), precision)
        gold = jnp.take_along_axis(lg, jnp.asarray(row[pos + 1])[:, None],
                                   -1)[:, 0]
        out.append(jax.scipy.special.logsumexp(lg, axis=-1) - gold)
    return jnp.stack(out)


# the float32 rows of the last requests compared: a calibration reads the
# same requests again under each control (``serving.check_outputs(...,
# chooser=)``), and a reference pass is 11 s a request at 16k positions
_ROWS_KEPT, _rows = 16, {}


def _reference_rows(z, seed, tokens, positions):
    at = (_static(z), int(seed), int(positions[0]),
          np.asarray(tokens).tobytes())
    if at not in _rows:
        while len(_rows) >= _ROWS_KEPT:
            del _rows[next(iter(_rows))]
        _rows[at] = _logits(z, seed_key(seed), tokens, positions, "float32")
    return _rows[at]


def gaps_under(z, seed, tokens, prompt_len, n_new, pad_to, choosers):
    """``{chooser: gaps [n_new]}`` for each of ``choosers`` (``None``: the
    served tokens), the float32 reference computed ONCE for all of them —
    :func:`chosen_gaps` is this for one chooser."""
    if n_new > GAP_ROWS:
        raise ValueError(f"answers of at most {GAP_ROWS} tokens")
    key, tokens = seed_key(seed), _padded(tokens, pad_to)
    # position p predicts token p + 1: the generated tokens sit at
    # prompt_len .. prompt_len + n_new - 1, at most GAP_ROWS of them
    positions = prompt_len - 1 + jnp.arange(GAP_ROWS)
    lg = _reference_rows(z, seed, tokens, positions)
    out = {}
    for chooser in choosers:
        if chooser is None:             # the tokens that were served
            ids = tokens[positions + 1]
        else:                           # what ``chooser`` precision picks
            ids = jnp.argmax(_logits(z, key, tokens, positions, chooser),
                             axis=-1)
        chosen = jnp.take_along_axis(lg, ids[:, None], axis=-1)[:, 0]
        out[chooser] = np.asarray(jnp.max(lg, axis=-1) - chosen)[:n_new]
    return out


def chosen_gaps(z, seed, tokens, prompt_len, n_new, pad_to, chooser=None):
    """For one served request (``tokens`` = prompt + generated): how far
    below the reference's largest logit each generated token's reference
    logit lies, teacher-forced over the request's own tokens, padded to
    ``pad_to`` so every request of a cell shares one compiled program
    (causal attention never sees the padding).  With ``chooser`` (a
    precision), the CONTROL: the token that the reference computed in that
    precision would have picked stands in the served token's place —
    ``families/opt.py::chosen_gaps`` has the long form."""
    return gaps_under(z, seed, tokens, prompt_len, n_new, pad_to,
                      [chooser])[chooser]


def greedy(z, seed, prompt, n_new, pad_to, precision):
    """The reference in the program's place: greedy decoding by full
    recomputation, in ``precision`` — the control's generator (a full
    forward a token: for short requests only)."""
    toks = list(np.asarray(prompt))
    for _ in range(n_new):
        at = jnp.asarray([len(toks) - 1], jnp.int32)
        lg = _logits(z, seed_key(seed), _padded(toks, pad_to), at,
                     precision)
        toks.append(int(np.argmax(np.asarray(lg[0]))))
    return np.asarray(toks, np.int32)
