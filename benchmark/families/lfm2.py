"""The LFM2 mixture-of-experts family (LiquidAI, ``model_type: lfm2_moe``):
weights from a seed, the adapter that hands them to the program, and the
plain reference.

**Reference.**  The decoder as ``config.json`` spells it, layer ``i`` on the
residual stream ``h``; RMSNorm everywhere is ``x * rsqrt(mean(x^2) + eps) *
g`` in float32.

*Operator*, on ``u = RMSNorm_op(h)``.  A **conv** layer: ``[B | C | X] = u
W_in`` (three ``hidden``-wide thirds in that order), ``z = B * X``, ``c_t =
sum_j w[:, j] z_{t - K + 1 + j}`` over the ``K = conv_L_cache`` taps
(depthwise, causal, ``z_s = 0`` before the sequence, no bias), ``o = (C * c)
W_out``.  A **full_attention** layer: ``q = u W_q`` (``heads x d``), ``k = u
W_k``, ``v = u W_v`` (``kv_heads x d``); an RMSNorm over each HEAD's ``d``
features of q and of k (one gain of ``d`` each), rope on all ``d`` features
(half-split layout, theta from ``rope_parameters``), causal softmax at
``1 / sqrt(d)``, KV head ``j`` serving query heads ``j G .. j G + G - 1``,
``o = attn W_o``.  No biases.  ``h <- h + o``.

*Feed-forward*, on ``m = RMSNorm_ffn(h)``: a dense SwiGLU of
``intermediate_size`` in the first ``num_dense_layers`` layers; in the rest
``s = sigmoid(m W_r)`` in float32 over all experts, the ``top_k`` largest
of ``s + b`` chosen (``b`` the stored ``expert_bias``; ties to the lower
index), ``g_e = routed_scaling_factor * s_e / (sum_chosen s + 1e-6)``,
``h <- h + sum_chosen g_e E_e(m)``, each expert a SwiGLU of
``moe_intermediate_size``.  No shared expert, nothing dropped.

After the last layer held: the final RMSNorm (HF's ``embedding_norm``, on
the OUTPUT) and the head, tied to the embedding.

Plain ``jax.numpy`` in float32 with matmul precision ``highest``; no kernel,
no cache, no batching; ONE sequence, a jitted program a layer KIND (the
layer's index traced, so ten layers are three programs), the expert layer a
loop over ALL experts, each drawn inside the loop, computed over every token
and masked by the token's choice.  Weights are regenerated from the seed
alone, tensor by tensor, so the reference shares no array with the program.

**What is assumed** (the configuration file lists the same): the order of
``W_in``'s thirds and that ``B * X`` is what the convolution sees; the
``1e-6``; half-split rope; per-head q/k norms; the tied head; bfloat16.

**The weights' draw, and why** (normal, from ``--seed``, rounded to
bfloat16; std 0.02 but where said; norm gains 1 +- 0.1).  Token embeddings
std 0.03, NOT the other expert families' 2: the head is TIED, so a
position's own token gets ``|e|^2 / rms`` on its logit — at std 2 thirty
standard deviations over every other token's, and greedy decoding repeats
the prompt's last token whatever the layers compute (CPU, toy and real
widths).  At 0.03 logits have std ~1.4 as under the other cells' untied
heads, and the stream a router reads is layers 0-1's output — a function
of the last three tokens — so uniform tokens still route near-uniformly.
The convolution's taps std 0.5: with ``W_in`` at 0.02 a gate is ~0.9 a
feature, ``z`` ~0.8, and the operator adds ~0.55 a feature to a stream of
2-3 — two thirds of it from the two taps that live in the slot's STATE,
which is what ``stale_conv_state`` takes away.  The q/k head gains 1.41 +-
0.1 (attention logits of std ~2: a softmax over some tens of keys) and
attention's ``W_o`` std 0.04, so that the two attention layers carry ~0.4
a feature.  The selection bias std 0.05 beside scores of std ~0.2.

**The experts share most of what they compute** (:func:`expert_weights`):
an expert's three matrices are the layer's COMMON SwiGLU (down-projection
std 0.05: a routed layer adds ~0.5 a feature, a fifth of the stream) plus
``_OWN`` = 0.15 of a draw of its own.  With every expert drawn on its own
the comparison has no floor to stand on: gates are renormalised over FOUR
near-equal scores, so a flip of the 4th and 5th of 64 scores — bfloat16
moves one token-layer in twenty across that threshold — swaps a quarter of
the layer's output, 13% of the stream; the next layers' routers then flip
too, and the conv layers carry it to the next positions.  Readings
(the reference's own bfloat16 emulation against float32, real widths, 128
generated positions, CPU, PR 33): experts on their own at down-projection
std 0.025 / 0.05 / 0.1 read 0.056 / 0.18 / 0.99 with 27-77% of tokens off
the reference's choice, and ``float8_experts`` 0.061 / 0.33 / 1.23 beside
them — no limit separates 1.1-1.8 x.  Trained experts that grew from one
MLP share most of their function, and a flip between two of them costs
what they differ by: at ``_OWN`` 0.1 / 0.2 bfloat16 reads 0.0046-0.0057 /
0.011-0.020, ``float8_experts`` 0.016 / 0.033 (its noise is on the WHOLE
expert, common part included), ``top3`` 0.018 / 0.082 (it drops an
expert's own part only: the common part's gates are renormalised).  0.15
keeps both about three times over sound.

``precision`` selects the control: ``"float32"`` (the reference),
``"bfloat16"`` (what a sound program computes), ``"float8"`` (every matmul
operand rounded to e4m3 with a per-tensor scale), and, each bfloat16 but for
one thing: ``"float8_experts"`` (the experts' three matmuls in float8),
``"stale_conv_state"`` (``z_{t-1}``, ``z_{t-2}`` taken as zero at every
generated position: what a state that is reset, lost at the chunk/decode
hand-over or written to the wrong row computes), ``"top3"`` (one expert
fewer a token than the configuration's).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.dots3 import (_f32, _mm, _rms_norm, _rope, _round,
                                      _static, _store, _swiglu)
from benchmark.families.opt import HIGHEST, _tensor, seed_key

_W, _G, _EMBED, _DOWN, _TAPS, _QK_GAIN, _OUT, _BIAS, _OWN = \
    0.02, 0.1, 0.03, 0.05, 0.5, 1.41, 0.04, 0.05, 0.15
GATE_SUM_EPS = 1e-6
GAP_ROWS = 768           # the longest answer a cell may ask for


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    rope = model.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default" \
            or model.get("conv_bias") or model.get("attention_bias") \
            or not model.get("use_expert_bias", True) \
            or model.get("tie_word_embeddings") is False:
        raise ValueError("this reference is lfm2_moe as released: default "
                         "rope, no biases, a stored expert bias, tied head")
    kinds = tuple(model["layer_types"])
    if len(kinds) != model["num_hidden_layers"] \
            or set(kinds) - {"conv", "full_attention"}:
        raise ValueError("layer_types must name every layer: conv or "
                         "full_attention")
    h, heads = model["hidden_size"], model["num_attention_heads"]
    kv = model["num_key_value_heads"]
    if h % heads or heads % kv:
        raise ValueError("heads divide hidden_size, KV heads the heads")
    return dict(
        h=h, heads=heads, kv_heads=kv, d=h // heads, kinds=kinds,
        layers=len(kinds), dense_layers=model["num_dense_layers"],
        dense_f=model["intermediate_size"],
        f=model["moe_intermediate_size"], experts=model["num_experts"],
        top_k=model["num_experts_per_tok"],
        norm_topk=bool(model["norm_topk_prob"]),
        scaling=float(model["routed_scaling_factor"]),
        taps=model["conv_L_cache"], vocab=model["vocab_size"],
        positions=model["max_position_embeddings"],
        eps=float(model["norm_eps"]),
        theta=float(rope.get("rope_theta", 1e6)))


def parameters_by_part(z):
    """Parameters counted from the shapes, by part."""
    h, d = z["h"], z["d"]
    conv = 3 * h * h + z["taps"] * h + h * h
    attn = 2 * h * z["heads"] * d + 2 * h * z["kv_heads"] * d + 2 * d
    dense = 3 * h * z["dense_f"]
    routed = z["experts"] * 3 * h * z["f"] + h * z["experts"] + z["experts"]
    n_conv = sum(k == "conv" for k in z["kinds"])
    return {"embedding_and_tied_head": z["vocab"] * h,
            "conv_operators": n_conv * conv,
            "attention_operators": (z["layers"] - n_conv) * attn,
            "dense_swiglu": z["dense_layers"] * dense,
            "routed_layers": (z["layers"] - z["dense_layers"]) * routed,
            "norms": 2 * z["layers"] * h + h}


# --------------------------------------------------------------------- #
# Weights from the seed
# --------------------------------------------------------------------- #
def _layer_kinds(z):
    """Every tensor a layer of either kind may hold, by name; a layer draws
    the ones its kind has (the index in this list keys the draw)."""
    h, d, E = z["h"], z["d"], z["experts"]
    return [("op_g", (h,), _G, 1.0), ("ffn_g", (h,), _G, 1.0),
            ("w_in", (h, 3 * h), _W, 0.0), ("w_conv", (h, z["taps"]),
                                            _TAPS, 0.0),
            ("w_out", (h, h), _W, 0.0),
            ("wq", (h, z["heads"] * d), _W, 0.0),
            ("wk", (h, z["kv_heads"] * d), _W, 0.0),
            ("wv", (h, z["kv_heads"] * d), _W, 0.0),
            ("qn_g", (d,), _G, _QK_GAIN), ("kn_g", (d,), _G, _QK_GAIN),
            ("wo", (z["heads"] * d, h), _OUT, 0.0),
            ("w1", (h, z["dense_f"]), _W, 0.0),
            ("w3", (h, z["dense_f"]), _W, 0.0),
            ("w2", (z["dense_f"], h), _W, 0.0),
            ("router", (h, E), _W, 0.0), ("expert_bias", (E,), _BIAS, 0.0)]


_CONV = ("w_in", "w_conv", "w_out")
_ATTN = ("wq", "wk", "wv", "qn_g", "kn_g", "wo")
_DENSE = ("w1", "w3", "w2")
_ROUTED = ("router", "expert_bias")


def _holds(conv, dense):
    return ("op_g", "ffn_g") + (_CONV if conv else _ATTN) \
        + (_DENSE if dense else _ROUTED)


def layer_weights(z, key, layer, conv, dense):
    """The tensors of ``layer`` (traced or not), a layer of the given
    kind (static); the experts are :func:`expert_weights`'."""
    names = _holds(conv, dense)
    return {name: _tensor(key, 100 + i, layer, shape, std, mean)
            for i, (name, shape, std, mean) in enumerate(_layer_kinds(z))
            if name in names}


def global_weights(z, key):
    return {"embed": _tensor(key, 0, 0, (z["vocab"], z["h"]), _EMBED, 0.0),
            "lnf_g": _tensor(key, 1, 0, (z["h"],), _G, 1.0)}


def _expert_draw(z, k, i, scale):
    shape, std = [((z["h"], z["f"]), _W), ((z["h"], z["f"]), _W),
                  ((z["f"], z["h"]), _DOWN)][i]
    return scale * std * jax.random.normal(jax.random.fold_in(k, i), shape,
                                           jnp.float32)


def common_expert(z, key, layer):
    """What every expert of ``layer`` shares: one SwiGLU's three matrices,
    float32, a pure function of ``(seed, layer)``."""
    k = jax.random.fold_in(jax.random.fold_in(key, 91), layer)
    return [_expert_draw(z, k, i, 1.0) for i in range(3)]


def expert_weights(z, key, layer, expert, common=None):
    """The three matrices of expert ``expert`` (traced or not) of
    ``layer``: the layer's common SwiGLU plus ``_OWN`` of a draw of the
    expert's own — a pure function of ``(seed, layer, expert)``.  A caller
    that loops over the experts hands the common part in."""
    common = common_expert(z, key, layer) if common is None else common
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, 90),
                                              layer), expert)
    return {name: (common[i] + _expert_draw(z, k, i, _OWN))
            .astype(jnp.bfloat16) for i, name in enumerate(("wg", "wu",
                                                            "wd"))}


# --------------------------------------------------------------------- #
# The program's side: its module, and its parameter tree from the seed
# --------------------------------------------------------------------- #
def program_model(model, **overrides):
    """The program's own module at the file's sizes."""
    from deepspeed_tpu.models.lfm2 import lfm2_model
    sizes_of(model)                      # refuses what the reference lacks
    return lfm2_model(model, **{"dtype": "bfloat16", **overrides})


_PROGRAM_LEAVES = {      # the program's leaf path in a layer -> the tensor
    ("operator_norm", "scale"): "op_g", ("ffn_norm", "scale"): "ffn_g",
    ("conv", "in_proj", "kernel"): "w_in",
    ("conv", "conv_kernel"): "w_conv",
    ("conv", "out_proj", "kernel"): "w_out",
    ("self_attn", "q_proj", "kernel"): "wq",
    ("self_attn", "k_proj", "kernel"): "wk",
    ("self_attn", "v_proj", "kernel"): "wv",
    ("self_attn", "q_norm"): "qn_g", ("self_attn", "k_norm"): "kn_g",
    ("self_attn", "out_proj", "kernel"): "wo",
    ("feed_forward", "gate_proj", "kernel"): "w1",
    ("feed_forward", "up_proj", "kernel"): "w3",
    ("feed_forward", "down_proj", "kernel"): "w2",
    ("moe_mlp", "gate_kernel"): "router",
    ("moe_mlp", "select_bias"): "expert_bias",
}
_EXPERT_LEAVES = {"experts_wg": "wg", "experts_wi": "wu", "experts_wo": "wd"}


@functools.partial(jax.jit, static_argnames=("sizes", "conv", "dense",
                                             "shapes"))
def _build_layer(key, layer, *, sizes, conv, dense, shapes):
    """One layer's leaves ``{path: array}`` in the program's shapes."""
    z = dict(sizes)
    w = layer_weights(z, key, layer, conv, dense)
    out = {}
    for path, shape in shapes:
        if path[-1] in _EXPERT_LEAVES:
            # an expert at a time: threefry's temporaries for one expert
            common = common_expert(z, key, layer)
            out[path] = jax.lax.map(
                lambda e: expert_weights(z, key, layer, e, common)[
                    _EXPERT_LEAVES[path[-1]]], jnp.arange(z["experts"]))
        else:
            x = w[_PROGRAM_LEAVES[path]]
            # the program keeps the taps as [tap, feature]
            out[path] = (x.T if path[-1] == "conv_kernel" else x) \
                .reshape(shape)
    return out


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves) from ``seed``, on the
    device.  A jitted call a LAYER, the layer's index traced, so that the
    compiled forms — three, one a kind of layer — serve every layer and
    every seed, and the draw's temporaries are one layer's at a time."""
    z = sizes_of(model)
    key = seed_key(seed)
    abstract = jax.eval_shape(module.init, jax.random.key(0),
                              {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [tuple(p.key for p in path)[1:] for path, _ in flat]
    glob = _globals_jit(key, sizes=_static(z))
    built = {}
    for l in range(z["layers"]):
        shapes = tuple((p[1:], leaf.shape) for p, (_, leaf)
                       in zip(paths, flat) if p[0] == f"layers_{l}")
        built[l] = _build_layer(
            key, jnp.int32(l), sizes=_static(z),
            conv=z["kinds"][l] == "conv", dense=l < z["dense_layers"],
            shapes=shapes)
    leaves = []
    for p, (_, leaf) in zip(paths, flat):
        if p[0].startswith("layers_"):
            leaves.append(built[int(p[0][7:])][p[1:]])
        else:
            leaves.append(glob[{"embed_tokens": "embed",
                                "embedding_norm": "lnf_g"}[p[0]]])
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _globals_jit(key, *, sizes):
    return global_weights(dict(sizes), key)


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #
def _parts(precision):
    """``precision`` -> what each part of the model computes in:
    ``(everything else, the experts' matmuls, whether the generated
    positions' conv state is stale, experts fewer a token)``."""
    if precision == "float8_experts":
        return "bfloat16", "float8", False, 0
    if precision == "stale_conv_state":
        return "bfloat16", "bfloat16", True, 0
    if precision == "top3":
        return "bfloat16", "bfloat16", False, 1
    return precision, precision, False, 0


def short_conv(z, u, w, precision, stale_from=None):
    """The gated short convolution on ``u [S, h]``.  ``stale_from``: from
    that position on the taps before the token's own see zeros."""
    K = z["taps"]
    b, c, x = jnp.split(_mm(u, w["w_in"], precision), 3, axis=-1)
    zt = _store(b * x, precision)
    S = zt.shape[0]
    zz = jnp.concatenate([jnp.zeros((K - 1, z["h"]), zt.dtype), zt])
    taps = _f32(w["w_conv"])                               # [h, K]
    conv = taps[:, K - 1] * zt
    before = sum(taps[:, j] * zz[j:j + S] for j in range(K - 1))
    if stale_from is not None:
        before = jnp.where((jnp.arange(S) >= stale_from)[:, None], 0.0,
                           before)
    y = _store(c * _store(conv + before, precision), precision)
    return _mm(y, w["w_out"], precision)


def attention(z, u, w, precision):
    """Causal grouped-query attention of one sequence ``u [S, h]``."""
    S, d, H, KVH = u.shape[0], z["d"], z["heads"], z["kv_heads"]
    heads = lambda t, n: t.reshape(S, n, d)
    q = heads(_mm(u, w["wq"], precision), H)
    k = heads(_mm(u, w["wk"], precision), KVH)
    v = heads(_mm(u, w["wv"], precision), KVH)
    q = _store(_rms_norm(q, w["qn_g"], z["eps"]), precision)
    k = _store(_rms_norm(k, w["kn_g"], z["eps"]), precision)
    q = _store(_rope(q, z["theta"]), precision)
    k = _store(_rope(k, z["theta"]), precision)
    q = q.reshape(S, KVH, H // KVH, d)                 # head j G + g
    scores = jnp.einsum("sjgd,tjd->jgst", _round(q, precision),
                        _round(k, precision), precision=HIGHEST) \
        / np.sqrt(d)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    out = jnp.einsum("jgst,tjd->sjgd", _round(probs, precision),
                     _round(v, precision), precision=HIGHEST)
    return _mm(_store(out.reshape(S, H * d), precision), w["wo"], precision)


def route(z, m, w, precision, fewer=0):
    """``(top_i [S, k], gates [S, k])`` of ``m [S, h]``: float32 sigmoid
    scores, the top ``k`` of score + bias, gates from the scores."""
    scores = jax.nn.sigmoid(jnp.matmul(
        _round(m, precision), _round(_f32(w["router"]), precision),
        precision=HIGHEST))                                # float32, kept
    _, top_i = jax.lax.top_k(scores + _f32(w["expert_bias"]),
                             z["top_k"] - fewer)
    top_w = jnp.take_along_axis(scores, top_i, axis=1)
    if z["norm_topk"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True)
                         + GATE_SUM_EPS)
    return top_i, top_w * z["scaling"]


def expert_layer(z, key, layer, m, w, precision):
    """The routed expert layer on ``m [S, h]``: every expert over every
    token, masked by the token's choice.  Nothing is dropped."""
    outer, inner, _, fewer = _parts(precision)
    top_i, top_w = route(z, m, w, outer, fewer)

    common = common_expert(z, key, layer)

    def one(acc, e):
        ew = expert_weights(z, key, layer, e, common)
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(m, ew["wg"], ew["wu"],
                                               ew["wd"], inner), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(z["experts"]))
    return _store(acc, outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "conv",
                                             "dense"))
def _layer_jit(key, layer, x, stale_from, *, sizes, precision, conv, dense):
    """One layer of its kind on ``x [S, h]``, its weights drawn here."""
    z, (outer, _, stale, _) = dict(sizes), _parts(precision)
    w = layer_weights(z, key, layer, conv, dense)
    u = _store(_rms_norm(x, w["op_g"], z["eps"]), outer)
    o = short_conv(z, u, w, outer, stale_from if stale else None) if conv \
        else attention(z, u, w, outer)
    x = _store(x + o, outer)
    m = _store(_rms_norm(x, w["ffn_g"], z["eps"]), outer)
    y = _swiglu(m, w["w1"], w["w3"], w["w2"], outer) if dense \
        else expert_layer(z, key, layer, m, w, precision)
    return _store(x + y, outer)


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed_jit(g, tokens, *, precision):
    return _store(_f32(g["embed"])[tokens], _parts(precision)[0])


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _head_jit(g, x, positions, *, sizes, precision):
    z, outer = dict(sizes), _parts(precision)[0]
    h = _store(_rms_norm(x[positions], g["lnf_g"], z["eps"]), outer)
    return _mm(h, g["embed"].T, outer)                 # the tied head


def _logits(z, key, tokens, positions, precision, stale_from=0):
    """Logits ``[R, V]`` at ``positions [R]`` of one sequence ``tokens
    [S]``; ``stale_from``: where the generated positions start (read by
    the ``stale_conv_state`` control alone)."""
    kw = dict(sizes=_static(z), precision=precision)
    g = _globals_jit(key, sizes=_static(z))
    x = _embed_jit(g, tokens, precision=precision)
    for layer in range(z["layers"]):
        x = _layer_jit(key, jnp.int32(layer), x, jnp.int32(stale_from),
                       conv=z["kinds"][layer] == "conv",
                       dense=layer < z["dense_layers"], **kw)
    return _head_jit(g, x, positions, **kw)


def _padded(tokens, pad_to=None):
    row = np.zeros(max(len(tokens), pad_to or 0), np.int32)
    row[:len(tokens)] = tokens
    return jnp.asarray(row)


def logits(z, seed, tokens, precision="float32", stale_from=0):
    """All logits ``[S, V]`` of ONE sequence ``tokens [S]`` — what the CPU
    tests compare the program with."""
    return _logits(z, seed_key(seed), _padded(tokens),
                   jnp.arange(len(tokens)), precision, stale_from)


def conv_states(z, seed, tokens):
    """``[conv layers, taps - 1, h]``: what a slot's state rows must hold
    after ``tokens`` — each conv layer's last ``taps - 1`` rows of ``z``
    (zeros before the sequence), in float32.  For the tests."""
    key, g = seed_key(seed), global_weights(z, seed_key(seed))
    x = _f32(g["embed"])[jnp.asarray(tokens)]
    K, out = z["taps"], []
    for layer in range(z["layers"]):
        conv, dense = z["kinds"][layer] == "conv", layer < z["dense_layers"]
        if conv:
            w = layer_weights(z, key, layer, conv, dense)
            u = _rms_norm(x, w["op_g"], z["eps"])
            b, _, xx = jnp.split(_mm(u, w["w_in"], "float32"), 3, axis=-1)
            zz = jnp.concatenate([jnp.zeros((K - 1, z["h"])), b * xx])
            out.append(zz[-(K - 1):])
        x = _layer_jit(key, jnp.int32(layer), x, jnp.int32(0),
                       sizes=_static(z), precision="float32", conv=conv,
                       dense=dense)
    return jnp.stack(out)


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
# same requests again under each control
_ROWS_KEPT, _rows = 8, {}


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
    key = seed_key(seed)
    tokens = _padded(tokens, max(pad_to or 0, prompt_len + GAP_ROWS))
    # position p predicts token p + 1: the generated tokens sit at
    # prompt_len .. prompt_len + n_new - 1, at most GAP_ROWS of them
    positions = prompt_len - 1 + jnp.arange(GAP_ROWS)
    lg = _reference_rows(z, seed, tokens, positions)
    out = {}
    for chooser in choosers:
        if chooser is None:             # the tokens that were served
            ids = tokens[positions + 1]
        else:                           # what ``chooser`` precision picks
            ids = jnp.argmax(_logits(z, key, tokens, positions, chooser,
                                     stale_from=prompt_len), axis=-1)
        chosen = jnp.take_along_axis(lg, ids[:, None], axis=-1)[:, 0]
        out[chooser] = np.asarray(jnp.max(lg, axis=-1) - chosen)[:n_new]
    return out


def chosen_gaps(z, seed, tokens, prompt_len, n_new, pad_to, chooser=None):
    """For one served request (``tokens`` = prompt + generated): how far
    below the reference's largest logit each generated token's reference
    logit lies, teacher-forced over the request's own tokens, padded to
    ``pad_to`` so every request of a cell shares one compiled program
    (a causal model never sees the padding).  With ``chooser`` (a
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
                     precision, stale_from=len(prompt))
        toks.append(int(np.argmax(np.asarray(lg[0]))))
    return np.asarray(toks, np.int32)
