"""The longcat family (meituan-longcat LongCat-Flash-Chat): weights from a
seed, the adapter that hands them to the program, and the plain reference.

**Reference.**  A shortcut-connected DOUBLE layer with input ``x``
(RMSNorm: eps 1e-5, float32 gain)::

    a1 = x  + MLA_0(RMSNorm(x))            h1 = RMSNorm(a1)
    m  = Experts(h1)                        # the shortcut branch
    b1 = a1 + FFN_0(h1)                     # SwiGLU
    a2 = b1 + MLA_1(RMSNorm(b1))           h2 = RMSNorm(a2)
    y  = a2 + FFN_1(h2) + m

*MLA*: ``c_q = sqrt(hidden / q_rank) RMSNorm(x W_qa)``; ``q = c_q W_qb`` ->
heads of ``[nope | rope]``; ``[c_kv | k_r] = x W_kva``, ``c_kv = sqrt(hidden
/ kv_rank) RMSNorm(c_kv)``; rotary positions (theta 1e7, pairs ``(2i, 2i +
1)``) on the rope part of ``q`` and on the one shared ``k_r``; ``[k_nope_h |
v_h] = c_kv W_kvb``; causal softmax of ``(q_nope . k_nope + q_rope . k_r) /
sqrt(nope + rope)``; ``o = concat_h(softmax . v) W_o``.  No bias, no gate,
no indexer.  *Experts*: ``s = softmax(h W_r)`` in float32 over
``n_routed_experts + zero_expert_num`` outputs; the ``moe_topk`` largest of
``s + b`` (ties to the lower index); ``g_e = routed_scaling_factor s_e`` (no
division by the chosen sum); ``out = sum over chosen real experts that are
HELD of g_e SwiGLU_e(h) + (sum over chosen zero experts of g_e) h``; what
the absent real experts would add is left out here as in the program.
Embedding, the layers, a final RMSNorm, an untied head.

Plain ``jax.numpy`` in float32 with matmul precision ``highest``; no kernel,
no cache, no batching; ONE sequence, attention in blocks of 64 queries
against all keys, and a jitted program a SUBLAYER with that sublayer's
weights drawn when it runs and dropped after it: a double layer's weights
are 2.5 GB in bfloat16 and a served model's 10.35 GB sit beside the
reference on the chip.  The rounding rules, the matmul, the norm, the
SwiGLU and the tensor draw are ``families/dots3.py``'s own functions, the
rotary pairing ``families/glm5.py``'s, imported.

**The weights' draw** (normal, from ``--seed``, rounded to bfloat16).
``families/dots3.py``'s scales wherever the block is the same, for that
family's reasons (its docstring): std 0.02, norm gains 1 +- 0.1, token
embeddings std 2, ``o_proj`` 0.04 — with both latents rescaled (x 2 and x
sqrt(12)) a query or key feature has std ~1.6 and a logit ~2.5 units, as
there.  What differs is the expert branch.  The router is a softmax over
768 outputs: a chosen score is ~0.007-0.04, twelve of them sum to ~0.17 and
the gates (x 6) to ~1, a third of it on zero experts — the identity part is
~0.33 h a layer, a tenth of the stream.  This chip holds 16 of 512 real
experts, a QUARTER of a pick a token at a gate of ~0.08: at dots3's
down-projection std (0.06) a held pick adds ~0.35 a feature to a stream
that each dense FFN moves by ~3.5, and no comparison could tell a program
that left the held experts out from a sound one (``families/olmoe.py``:
weights that hide a layer from the comparison make its control read like
the sound side).  The routed experts' down-projections are drawn at std
0.2, a held pick ~1.2 a feature: ``held_dropped`` reads 0.44-0.46 beside a
sound 0.0070-0.0094 (chip, PR 47, call 1).  ``float8_experts`` does NOT
separate, at this std or another: 0.0115-0.0131 here, and at std 0.6 (a
held pick as large as a dense FFN's output) 0.042-0.051 beside a sound
0.029-0.042 (call 2) - 1.4 times sound both times.  A larger held part
makes a ROUTING flip dearer in step with the float8 error (bfloat16 moves
the router's input by 0.1-1% and the 12th and 13th of 768 softmax scores
lie close; a flip swaps a held pick's whole contribution), so the sound
side rises with the control; with a quarter of a pick a token held, the
experts' arithmetic is too small a share of the logits' error to be told
apart by precision alone.  PERF.md section 7 has the open question.  The
selection bias is drawn at 0.001 (a score's own size) and then BALANCED
(:func:`balanced_biases`, ``families/glm5.py``'s rule at a softmax score's
scale) over all 768 outputs, on 32 sequences of 1,024 drawn ids: every seed
sends a third of the choices to zero experts and touches the held sixteen
equally often ON THE TRAFFIC — a bias fitted to one sequence evened that
sequence and left the seeds 1% apart in speed (``BALANCE_SEQUENCES``).

**What is assumed** is listed in the configuration file.

``precision`` selects the control: ``"float32"`` (the reference),
``"bfloat16"`` (what a sound program computes), ``"float8"`` (every matmul
operand rounded to e4m3 with a per-tensor scale), and, each bfloat16 but for
one thing: ``"float8_experts"`` (the held experts' three matmuls in float8),
``"float8_latent"`` (the cached rows ``[c_kv | k_r]`` of all eight attention
sublayers rounded to float8 — an 8-bit cache), ``"zero_dropped"`` (the
identity part left out — a router that treats a zero expert as an absent
one), ``"shortcut_misplaced"`` (the expert branch joined after ``FFN_0``,
so the second attention and FFN read it — a plain sequential block),
``"no_latent_scale"`` (both latents left at RMSNorm's scale) and
``"held_dropped"`` (the held experts' part left out).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.dots3 import (HIGHEST, QUERY_BLOCK, _f32, _mm,
                                      _padded, _rms_norm, _round, _static,
                                      _store, _swiglu, _tensor,
                                      _tensor_alone, seed_key)
from benchmark.families.glm5 import _rope

_W, _G, _EMBED, _DOWN, _ATTN, _OUT, _BIAS = \
    0.02, 0.1, 2.0, 0.2, 0.02, 0.04, 0.001
GAP_ROWS = 1024          # the longest answer a cell may ask for
CONTROLS = ("float8_experts", "float8_latent", "zero_dropped",
            "shortcut_misplaced", "no_latent_scale", "held_dropped")


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    if model.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if model.get("zero_expert_type") != "identity":
        raise ValueError(f"zero_expert_type "
                         f"{model.get('zero_expert_type')!r}: this reference "
                         f"has identity zero experts only")
    if model.get("attention_bias") or model.get("tie_word_embeddings") \
            or model.get("attention_method", "MLA") != "MLA" \
            or bool(model["mla_scale_q_lora"]) \
            != bool(model["mla_scale_kv_lora"]):
        raise ValueError("this reference is LongCat-Flash as released: MLA "
                         "without biases, both latents rescaled or neither, "
                         "an untied head")
    published = model.get("n_routed_experts_published",
                          model["n_routed_experts"])
    held = tuple(model.get("held_experts", (0, model["n_routed_experts"])))
    if held[1] != model["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    layers = model["num_layers"]
    full = tuple(sorted(dict(
        heads=model["num_attention_heads"], q_rank=model["q_lora_rank"],
        kv_rank=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], v=model["v_head_dim"],
        theta=float(model["rope_theta"]), window=0, index_topk=0).items()))
    return dict(
        h=model["hidden_size"], layers=layers,
        # the pool's layers, as the benchmark's readers count them: two
        # attention sublayers a double layer
        kinds=("full_attention",) * (2 * layers),
        f=model["ffn_hidden_size"], ef=model["expert_ffn_hidden_size"],
        experts=published, zero=model["zero_expert_num"], held=held,
        top_k=model["moe_topk"],
        scaling=float(model["routed_scaling_factor"]),
        vocab=model["vocab_size"], eps=float(model["rms_norm_eps"]),
        rescale=bool(model["mla_scale_q_lora"]),
        positions=model["max_position_embeddings"], full=full)


# --------------------------------------------------------------------- #
# The draw: a tensor a (kind, sublayer); sublayer ``2 layer + which``
# --------------------------------------------------------------------- #
def _attn_kinds(z):
    a, h = dict(z["full"]), z["h"]
    H = a["heads"]
    return [("ln_g", (h,), _G, 1.0),
            ("q_a", (h, a["q_rank"]), _W, 0.0),
            ("q_a_norm", (a["q_rank"],), _G, 1.0),
            ("q_b", (a["q_rank"], H * (a["nope"] + a["rope"])), _ATTN, 0.0),
            ("kv_a", (h, a["kv_rank"] + a["rope"]), _W, 0.0),
            ("kv_a_norm", (a["kv_rank"],), _G, 1.0),
            ("kv_b", (a["kv_rank"], H * (a["nope"] + a["v"])), _ATTN, 0.0),
            ("o_proj", (H * a["v"], h), _OUT, 0.0)]


def _ffn_kinds(z):
    h, f = z["h"], z["f"]
    return [("ln_g", (h,), _G, 1.0), ("w_gate", (h, f), _W, 0.0),
            ("w_up", (h, f), _W, 0.0), ("w_down", (f, h), _W, 0.0)]


def _router_kinds(z):
    wide = z["experts"] + z["zero"]
    return [("router", (z["h"], wide), _W, 0.0),
            ("select_bias", (wide,), _BIAS, 0.0)]


def _global_kinds(z):
    h = z["h"]
    return [("embed", (z["vocab"], h), _EMBED, 0.0), ("lnf_g", (h,), _G, 1.0),
            ("head", (h, z["vocab"]), _W, 0.0)]


def _drawn(kinds, first, key, at, draw):
    return {name: draw(key, first + i, at, shape, std, mean)
            for i, (name, shape, std, mean) in enumerate(kinds)}


def attn_weights(z, key, layer, which, draw=_tensor):
    """Attention sublayer ``which`` (0, 1) of double layer ``layer``, with
    the gain of the norm before it."""
    return _drawn(_attn_kinds(z), 100, key, 2 * layer + which, draw)


def ffn_weights(z, key, layer, which, draw=_tensor):
    """Dense FFN ``which`` of double layer ``layer``, with the gain of the
    norm before it (``which`` 0: the expert branch reads the same ``h1``)."""
    return _drawn(_ffn_kinds(z), 120, key, 2 * layer + which, draw)


def router_weights(z, key, layer, draw=_tensor, bias=None):
    """The router of double layer ``layer``; ``bias`` (the layer's row of
    :func:`balanced_biases`) stands in the drawn selection bias."""
    w = _drawn(_router_kinds(z), 140, key, layer, draw)
    return w if bias is None else dict(w, select_bias=bias)


def global_weights(z, key, draw=_tensor):
    return _drawn(_global_kinds(z), 0, key, 0, draw)


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
    from deepspeed_tpu.models.longcat import longcat_model
    z = sizes_of(model)                  # refuses what the reference lacks
    return longcat_model(model, held_experts=z["held"],
                         **{"dtype": "bfloat16", **overrides})


_ATTN_LEAVES = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
                "o_proj")
_MLP_LEAVES = {"gate_proj": "w_gate", "up_proj": "w_up",
               "down_proj": "w_down"}
_ROUTER_LEAVES = {"gate_kernel": "router", "select_bias": "select_bias"}
_GLOBAL_LEAVES = {("embed_tokens", "embedding"): "embed",
                  ("final_norm", "scale"): "lnf_g",
                  ("lm_head", "kernel"): "head"}
_EXPERT_LEAVES = {"experts_wg": "wg", "experts_wi": "wu", "experts_wo": "wd"}


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves) from ``seed``, on the
    device, in one jitted call whose compiled form serves every seed."""
    z = sizes_of(model)
    first, count = z["held"]
    abstract = jax.eval_shape(module.init, jax.random.key(0),
                              {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key, biases):
        glob = global_weights(z, key)
        drawn, leaves = {}, []

        def sub(make, layer, which):
            at = (make, layer, which)
            if at not in drawn:
                drawn[at] = make(z, key, layer, which)
            return drawn[at]

        def layer_leaf(layer, names):
            part, which = names[0].rpartition("_")[::2]
            if names[-1] in _EXPERT_LEAVES:
                return jax.vmap(lambda e: expert_weights(z, key, layer, e)[
                    _EXPERT_LEAVES[names[-1]]])(first + jnp.arange(count))
            if part == "moe":                    # moe_mlp's own leaves
                return router_weights(z, key, layer, bias=biases[layer])[
                    _ROUTER_LEAVES[names[1]]]
            which = int(which)
            if part == "attn":
                return sub(attn_weights, layer, which)[names[1]]
            if part == "input_norm":
                return sub(attn_weights, layer, which)["ln_g"]
            if part == "post_attn_norm":
                return sub(ffn_weights, layer, which)["ln_g"]
            return sub(ffn_weights, layer, which)[_MLP_LEAVES[names[1]]]

        for path, leaf in flat:
            names = tuple(p.key for p in path)[1:]       # drop 'params'
            x = layer_leaf(int(names[0][7:]), names[1:]) \
                if names[0].startswith("layers_") \
                else glob[_GLOBAL_LEAVES[names]]
            leaves.append(x.reshape(leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = seed_key(seed)
    return build(key, balanced_biases(z, key))


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #
def _parts(precision):
    """``precision`` -> what each part of the model computes in, and what
    it computes: ``dict(outer, experts, cached, zero, placed, scaled,
    held)``."""
    sound = dict(outer="bfloat16", experts="bfloat16", cached="bfloat16",
                 zero=True, placed=True, scaled=True, held=True)
    other = {"float8_experts": dict(experts="float8"),
             "float8_latent": dict(cached="float8"),
             "zero_dropped": dict(zero=False),
             "shortcut_misplaced": dict(placed=False),
             "no_latent_scale": dict(scaled=False),
             "held_dropped": dict(held=False)}
    if precision in other:
        return dict(sound, **other[precision])
    return dict(sound, outer=precision, experts=precision, cached=precision)


def _attention(z, x, w, precision):
    """Causal latent attention of ONE sequence ``x [S, h]`` (normed
    input)."""
    a, p = dict(z["full"]), _parts(precision)
    outer = p["outer"]
    S, H = x.shape[0], a["heads"]
    up = lambda rank: np.sqrt(z["h"] / rank) \
        if z["rescale"] and p["scaled"] else 1.0
    c_q = _store(_rms_norm(_mm(x, w["q_a"], outer), w["q_a_norm"], z["eps"])
                 * up(a["q_rank"]), outer)
    kv = _mm(x, w["kv_a"], outer)
    row = _store(jnp.concatenate([
        _rms_norm(kv[:, :a["kv_rank"]], w["kv_a_norm"], z["eps"])
        * up(a["kv_rank"]), _rope(kv[:, a["kv_rank"]:], a["theta"])], -1),
        outer)
    if p["cached"] != outer:             # what an 8-bit cache would hold
        row = _store(_round(row, p["cached"]), outer)
    c_kv, k_r = row[:, :a["kv_rank"]], row[:, a["kv_rank"]:]
    kv_b = w["kv_b"].reshape(a["kv_rank"], H, a["nope"] + a["v"])
    k_nope = _mm(c_kv, kv_b[..., :a["nope"]].reshape(a["kv_rank"], -1),
                 outer).reshape(S, H, a["nope"])
    v = _mm(c_kv, kv_b[..., a["nope"]:].reshape(a["kv_rank"], -1),
            outer).reshape(S, H, a["v"])
    scale = 1.0 / np.sqrt(a["nope"] + a["rope"])
    r = lambda t: _round(t, outer)
    k_nope, k_r, v = r(k_nope), r(k_r), r(v)     # matmul operands, once
    keys = jnp.arange(S)[None, :]

    def block(start):
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
        seen = keys <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s * scale, -1e30),
                              axis=-1)
        out = jnp.einsum("hqs,shd->qhd", r(_store(prob, outer)), v,
                         precision=HIGHEST)
        return _mm(_store(out, outer).reshape(QUERY_BLOCK, -1), w["o_proj"],
                   outer)

    return jax.lax.map(block, jnp.arange(0, S, QUERY_BLOCK)).reshape(S, -1)


def _scores(h, w, outer):
    """The router's scores ``[S, experts + zero]`` of ``h [S, h]``: a
    float32 softmax, kept."""
    return jax.nn.softmax(jnp.matmul(
        _round(h, outer), _round(_f32(w["router"]), outer),
        precision=HIGHEST), axis=-1)


def expert_layer(z, key, layer, h, w, precision, held=None, zero=True):
    """The expert branch on ``h [S, h]``: the real experts ``held``
    (default the configuration's share; ``(0, experts)`` is the uncut
    layer) each computed over every token and masked by the token's choice,
    plus — ``zero`` — the chosen zero experts' gates times ``h``.  Nothing
    held is dropped."""
    p = _parts(precision)
    first, count = held or z["held"]
    scores = _scores(h, w, p["outer"])
    _, top_i = jax.lax.top_k(scores + _f32(w["select_bias"]), z["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=1) * z["scaling"]

    def one(acc, e):
        ew = expert_weights(z, key, layer, e)
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(h, ew["wg"], ew["wu"],
                                               ew["wd"], p["experts"]), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          first + jnp.arange(count if p["held"] else 0))
    if zero and p["zero"]:
        kept = jnp.sum(jnp.where(top_i >= z["experts"], top_w, 0.0), axis=-1)
        acc = acc + kept[:, None] * h
    return _store(acc, p["outer"])


# A sublayer is one jitted program, and so are the embedding, the head and
# each tensor's draw: the caller draws a sublayer's weights, runs it, and
# drops them before the next
@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _attention_jit(x, w, *, sizes, precision):
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, w["ln_g"], z["eps"]), outer)
    return _store(x + _attention(z, normed, w, precision), outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _ffn_jit(x, w, *, sizes, precision):
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, w["ln_g"], z["eps"]), outer)
    return _store(x + _swiglu(normed, w["w_gate"], w["w_up"], w["w_down"],
                              outer), outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "layer"))
def _experts_jit(key, x, ln_g, w, *, sizes, precision, layer):
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, ln_g, z["eps"]), outer)
    return expert_layer(z, key, layer, normed, w, precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _join_jit(x, m, *, precision):
    return _store(x + m, _parts(precision)["outer"])


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed_jit(g, tokens, *, precision):
    return _store(_f32(g["embed"])[tokens], _parts(precision)["outer"])


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _head_jit(g, x, positions, *, sizes, precision):
    z, outer = dict(sizes), _parts(precision)["outer"]
    h = _store(_rms_norm(x[positions], g["lnf_g"], z["eps"]), outer)
    return _mm(h, g["head"], outer)


def _attend(x, w, sequences, **kw):
    """The attention sublayer on a stream of ``sequences`` equal parts, each
    a sequence that attends alone."""
    if sequences == 1:
        return _attention_jit(x, w, **kw)
    return jnp.concatenate([_attention_jit(part, w, **kw)
                            for part in jnp.split(x, sequences)])


def _double_layer(z, key, layer, x, precision, bias=None, balance=None,
                  sequences=1):
    """One double layer on the stream ``x [S, h]`` (``sequences`` of them
    end to end), a sublayer at a time.  ``bias``: the layer's balanced
    selection bias; ``balance``: a function ``(a1, norm gain, router
    weights) -> bias`` run in its place (and recorded by the caller)."""
    kw = dict(sizes=_static(z), precision=precision)
    a1 = _attend(x, attn_weights(z, key, layer, 0, _tensor_alone), sequences,
                 **kw)
    ffn = ffn_weights(z, key, layer, 0, _tensor_alone)
    router = router_weights(z, key, layer, _tensor_alone, bias)
    if balance is not None:
        router["select_bias"] = balance(a1, ffn["ln_g"], router)
    m = _experts_jit(key, a1, ffn["ln_g"], router, layer=layer, **kw)
    b1 = _ffn_jit(a1, ffn, **kw)
    del ffn, router
    placed = _parts(precision)["placed"]
    if not placed:                       # the branch joins a sublayer early
        b1 = _join_jit(b1, m, precision=precision)
    a2 = _attend(b1, attn_weights(z, key, layer, 1, _tensor_alone), sequences,
                 **kw)
    y = _ffn_jit(a2, ffn_weights(z, key, layer, 1, _tensor_alone), **kw)
    return _join_jit(y, m, precision=precision) if placed else y


# --------------------------------------------------------------------- #
# The selection bias: the loads evened out, as training leaves them
# --------------------------------------------------------------------- #
# 32 sequences of 1,024 ids: 512 choices an output.  On ONE such sequence
# (16 choices an output) the bias fitted that sample: the held sixteen's real
# rates then spread 27-54% about their mean, a decode step touched 51.5-53.9
# of 4 x 16 experts according to the seed, and batch_tokens_per_s followed it
# to 1.0% — in ONE process, a seed repeating to 0.02% (chip, PR 47, call 8).
# At 16 sequences the rates spread 7-26% and four seeds read within 0.23%, at
# 32 5-20% and 0.10%, for 10 s and 21 s of set-up (call 9)
BALANCE_SEQUENCES, BALANCE_LENGTH = 32, 1024
BALANCE_STEPS, _BALANCE_RATE, _BALANCE_DECAY = 300, 0.002, 0.98


@functools.partial(jax.jit, static_argnames=("sizes",))
def _balance_jit(x, ln_g, w, *, sizes):
    """``families/glm5.py::_balance_jit`` at a softmax score's scale: from
    the drawn bias, every router output's bias moved against its share of
    the ``S x top_k`` choices, in shrinking steps."""
    z = dict(sizes)
    scores = _scores(_rms_norm(x, ln_g, z["eps"]), w, "float32")
    wide = scores.shape[1]
    mean = scores.shape[0] * z["top_k"] / wide

    def step(bias, rate):
        _, top = jax.lax.top_k(scores + bias, z["top_k"])
        load = jnp.zeros((wide,), jnp.float32).at[top.reshape(-1)].add(1.0)
        return bias - rate * jnp.clip(load / mean - 1.0, -1.0, 1.0), None

    rates = _BALANCE_RATE * _BALANCE_DECAY ** jnp.arange(BALANCE_STEPS)
    bias, _ = jax.lax.scan(step, _f32(w["select_bias"]), rates)
    return bias.astype(jnp.bfloat16)


_BIASES_KEPT, _biases = 4, {}


def balanced_biases(z, key):
    """``[double layers, experts + zero]`` bfloat16: the selection biases as
    LongCat's expert-bias controller leaves them — every router output,
    real or zero, chosen equally often.  A DRAWN router's loads are not
    even, and which of this chip's sixteen a decode step leaves untouched —
    weights unread — would move the cell's speed from seed to seed
    (``families/glm5.py::balanced_biases``, PERF.md section 2).  The float32
    reference runs ``BALANCE_SEQUENCES`` sequences of ``BALANCE_LENGTH``
    drawn ids (:func:`balance_ids`), each attending alone, layer by layer,
    and each layer's bias is balanced on the stream the balanced layers
    before it hand on.  The sample has to be large enough that the loads are
    even on the TRAFFIC and not on the sample alone.  Kept a few seeds
    long: the program's tree and the reference read the same rows."""
    at = (_static(z), np.asarray(jax.random.key_data(key)).tobytes())
    if at not in _biases:
        while len(_biases) >= _BIASES_KEPT:
            del _biases[next(iter(_biases))]
        _biases[at] = _balanced(z, key)
    return _biases[at]


def balance_ids(z, key):
    """``[BALANCE_SEQUENCES, BALANCE_LENGTH]`` drawn ids: the sequences the
    biases are balanced on."""
    return jax.random.randint(jax.random.fold_in(key, 91),
                              (BALANCE_SEQUENCES, BALANCE_LENGTH), 0,
                              z["vocab"])


def _balanced(z, key):
    g = global_weights(z, key, _tensor_alone)
    x = _embed_jit(g, balance_ids(z, key).reshape(-1), precision="float32")
    rows = []

    def balance(a1, ln_g, router):
        rows.append(_balance_jit(a1, ln_g, router, sizes=_static(z)))
        return rows[-1]

    for layer in range(z["layers"]):
        x = _double_layer(z, key, layer, x, "float32", balance=balance,
                          sequences=BALANCE_SEQUENCES)
    return jnp.stack(rows)


def _forward(z, key, tokens, positions, precision):
    """Logits ``[R, V]`` at ``positions [R]`` of one sequence ``tokens
    [S]`` (``S`` a multiple of 64)."""
    g = global_weights(z, key, _tensor_alone)
    biases = balanced_biases(z, key)
    x = _embed_jit(g, tokens, precision=precision)
    for layer in range(z["layers"]):
        x = _double_layer(z, key, layer, x, precision, biases[layer])
    return _head_jit(g, x, positions, sizes=_static(z), precision=precision)


def logits(z, seed, tokens, precision="float32"):
    """All logits ``[S, V]`` of ONE sequence ``tokens [S]`` — what the CPU
    tests compare the program with."""
    return _forward(z, seed_key(seed), _padded(tokens),
                    jnp.arange(len(tokens)), precision)


def nll_at(z, seed, tokens, positions, precision="float32"):
    """Next-token negative log-likelihood ``[B, R]``."""
    out = []
    for row, pos in zip(np.asarray(tokens), np.asarray(positions)):
        lg = _forward(z, seed_key(seed), _padded(row),
                      jnp.asarray(pos, jnp.int32), precision)
        gold = jnp.take_along_axis(lg, jnp.asarray(row[pos + 1])[:, None],
                                   -1)[:, 0]
        out.append(jax.scipy.special.logsumexp(lg, axis=-1) - gold)
    return jnp.stack(out)


# the float32 rows of the last requests compared (a calibration reads the
# same requests again under each control)
_ROWS_KEPT, _rows = 4, {}


def _reference_rows(z, seed, tokens, positions):
    at = (_static(z), int(seed), int(positions[0]),
          np.asarray(tokens).tobytes())
    if at not in _rows:
        while len(_rows) >= _ROWS_KEPT:
            del _rows[next(iter(_rows))]
        _rows[at] = _forward(z, seed_key(seed), tokens, positions, "float32")
    return _rows[at]


def gaps_under(z, seed, tokens, prompt_len, n_new, pad_to, choosers):
    """``{chooser: gaps [n_new]}`` for each of ``choosers`` (``None``: the
    served tokens), the float32 reference computed ONCE for all of them."""
    if n_new > GAP_ROWS:
        raise ValueError(f"answers of at most {GAP_ROWS} tokens")
    key, tokens = seed_key(seed), _padded(tokens, pad_to)
    # position p predicts token p + 1: the generated tokens sit at
    # prompt_len .. prompt_len + n_new - 1
    positions = jnp.minimum(prompt_len - 1 + jnp.arange(GAP_ROWS),
                            tokens.shape[0] - 2)
    lg = _reference_rows(z, seed, tokens, positions)
    out = {}
    for chooser in choosers:
        if chooser is None:             # the tokens that were served
            ids = tokens[positions + 1]
        else:                           # what ``chooser`` precision picks
            ids = jnp.argmax(_forward(z, key, tokens, positions, chooser),
                             axis=-1)
        chosen = jnp.take_along_axis(lg, ids[:, None], axis=-1)[:, 0]
        out[chooser] = np.asarray(jnp.max(lg, axis=-1) - chosen)[:n_new]
    return out


def chosen_gaps(z, seed, tokens, prompt_len, n_new, pad_to, chooser=None):
    """For one served request (``tokens`` = prompt + generated): how far
    below the reference's largest logit each generated token's reference
    logit lies, teacher-forced over the request's own tokens, padded to
    ``pad_to`` — ``families/opt.py::chosen_gaps`` has the long form.  With
    ``chooser`` (a precision), the CONTROL: the token that the reference
    computed in that precision would have picked stands in the served
    token's place."""
    return gaps_under(z, seed, tokens, prompt_len, n_new, pad_to,
                      [chooser])[chooser]


def greedy(z, seed, prompt, n_new, pad_to, precision):
    """The reference in the program's place: greedy decoding by full
    recomputation, in ``precision`` (a full forward a token: for short
    requests only)."""
    toks = list(np.asarray(prompt))
    for _ in range(n_new):
        at = jnp.asarray([len(toks) - 1], jnp.int32)
        lg = _forward(z, seed_key(seed), _padded(toks, pad_to), at,
                      precision)
        toks.append(int(np.argmax(np.asarray(lg[0]))))
    return np.asarray(toks, np.int32)
