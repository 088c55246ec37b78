"""The OLMoE family (allenai, ``model_type: olmoe``): weights from a seed,
the adapter that hands them to the program, and the plain reference.

**Reference.**  OLMoE's decoder as published (Muennighoff et al. 2024, and
HF ``OlmoeModel``): token embeddings; pre-RMSNorm blocks of causal
multi-head attention — no biases, an RMSNorm over the WHOLE projected query
and key vectors before the head split, rotary positions (half-split layout,
theta 10000) — and, in every layer, a routed expert layer: router logits,
softmax over all experts, the top ``num_experts_per_tok`` gates used as
they are (``norm_topk_prob: false``), each chosen expert a SwiGLU MLP of
width ``intermediate_size``.  Final RMSNorm, untied head.  Plain
``jax.numpy`` in float32 with matmul precision ``highest``; no kernel, no
cache, no batching tricks; the expert layer is a loop over ALL experts, each
computed over every token and masked by whether the token chose it — no
token is ever dropped.  Weights are regenerated from the seed alone, one
layer at a time inside the scan (a layer is 420 M parameters), so the
reference shares no array with the program.

Departures from HF, each shared with the program: router logits are
computed in float32 from the residual as stored (HF computes the gate
matmul in the model's dtype and only the softmax in float32); bfloat16 is
the configuration's 16-bit type; weights are normal draws of std 0.02 from
``--seed`` (norm gains 1 +- 0.1; token embeddings std 2; the experts'
down-projections std 0.2), rounded to bfloat16; no dropout.

Why the embeddings are drawn at std 2: with random weights attention adds
to every position of a sequence nearly the same vector — a running mean
over its context, ~0.07 a feature a layer — and at std 0.02 that mean, not
the token, decides the routing: a 128-token chunk of a 1,088-token
sequence sent its tokens to 23 of 64 experts (10 at the least), the
busiest 7.6x the mean, HOW many changing with the seed, and with it a
chunk's time and the cell's rate (6,145 against 6,626 tokens/s at std 0.5,
where two seeds agree and a third does not).  At std 2 a token's own
embedding decides, as it does in a trained model whose router was balanced
over its data, and uniform tokens route near-uniformly: every call touches
all 64 experts (busiest ~1.7x the mean), whatever the seed.

Why the experts' down-projections are drawn at std 0.2: beside embeddings
of std 2 an expert layer of std 0.02 adds 0.048 a feature to a residual of
2.0 — 2.4% — and the comparison that decides ``correct`` cannot see it:
with the experts' matmuls in float8 the reference picks tokens as good as
it does in bfloat16 (mean logit gap 0.000088 against 0.000077; 0.000111
against the served program's own 0.000126 over 256 requests), so 8-bit
expert weights would pass whatever the limit.  At 0.2 the layer adds 0.48 to a residual of 2.0–2.4, a quarter
as a trained model's blocks are a sizeable part of their stream, routing
stays where it was (64 touched, busiest 1.7x), and float8 experts read
4.8x bfloat16 (0.0209 against 0.0044), whole-model float8 9.3x; at 0.1 the
ratios are 4.0x and 11.8x, at 0.4 5.5x and 9.1x.  (All readings: the
reference alone on the chip, 6 x 1,087 positions, PERF.md PR 27.)

``precision`` selects the control, as in ``families/opt.py``: ``"float8"``
(every matmul operand — the router's too — rounded to e4m3 with a
per-tensor scale, results stored in bfloat16) and ``"bfloat16"`` (what a
sound program computes); and, this family's own, ``"float8_experts"``:
bfloat16 everywhere but the three matmuls of each expert, which are
float8 — what 8-bit expert weights would compute, the control the cell's
limit is set under (the whole-model float8 control fails through the head
and the residual, and would wave that through).  The generic pieces (seed
keys, the rounding rules, the precision-controlled matmul, one row's
causal attention) are ``families/opt.py``'s own.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.opt import (HIGHEST, _attention_row, _mm, _round,
                                    _store, _tensor, seed_key)

_W, _G, _EMBED, _DOWN = 0.02, 0.1, 2.0, 0.2


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    h, heads = model["hidden_size"], model["num_attention_heads"]
    if h % heads or model.get("num_key_value_heads", heads) != heads:
        raise ValueError("this reference is multi-head attention with "
                         "hidden_size divisible by the heads")
    if model.get("hidden_act", "silu") != "silu" or model.get("clip_qkv") \
            or model.get("rope_scaling") or model.get("attention_bias") \
            or model.get("tie_word_embeddings"):
        raise ValueError("this reference is OLMoE as released: SwiGLU, no "
                         "clip_qkv, no rope scaling, no biases, untied head")
    return dict(h=h, heads=heads, d=h // heads, f=model["intermediate_size"],
                experts=model["num_experts"],
                top_k=model["num_experts_per_tok"],
                norm_topk=bool(model.get("norm_topk_prob", False)),
                layers=model["num_hidden_layers"], vocab=model["vocab_size"],
                positions=model["max_position_embeddings"],
                eps=float(model.get("rms_norm_eps", 1e-5)),
                theta=float(model.get("rope_theta", 10000.0)))


def _layer_kinds(z):
    h, f, E = z["h"], z["f"], z["experts"]
    return [("ln1_g", (h,), _G, 1.0),
            ("wq", (h, h), _W, 0.0), ("wk", (h, h), _W, 0.0),
            ("wv", (h, h), _W, 0.0), ("wo", (h, h), _W, 0.0),
            ("qn_g", (h,), _G, 1.0), ("kn_g", (h,), _G, 1.0),
            ("ln2_g", (h,), _G, 1.0), ("router", (h, E), _W, 0.0),
            ("wg", (E, h, f), _W, 0.0), ("wu", (E, h, f), _W, 0.0),
            ("wd", (E, f, h), _DOWN, 0.0)]


def _global_kinds(z):
    h = z["h"]
    return [("embed", (z["vocab"], h), _EMBED, 0.0), ("lnf_g", (h,), _G, 1.0),
            ("head", (h, z["vocab"]), _W, 0.0)]


def layer_weights(z, key, layer):
    return {name: _tensor(key, 100 + i, layer, shape, std, mean)
            for i, (name, shape, std, mean) in enumerate(_layer_kinds(z))}


def global_weights(z, key):
    return {name: _tensor(key, i, 0, shape, std, mean)
            for i, (name, shape, std, mean) in enumerate(_global_kinds(z))}


# --------------------------------------------------------------------- #
# The program's side: its module, and its parameter tree from the seed
# --------------------------------------------------------------------- #
def program_model(model, **overrides):
    """The program's own module at the file's sizes."""
    from deepspeed_tpu.models.olmoe import olmoe_model
    sizes_of(model)                     # refuses what the reference lacks
    return olmoe_model(model, **{"dtype": "bfloat16", **overrides})


_PROGRAM_LEAVES = {      # the program's leaf path -> the plain tensor
    ("attn", "q_proj", "kernel"): "wq", ("attn", "k_proj", "kernel"): "wk",
    ("attn", "v_proj", "kernel"): "wv", ("attn", "o_proj", "kernel"): "wo",
    ("attn", "q_norm", "scale"): "qn_g", ("attn", "k_norm", "scale"): "kn_g",
    ("input_norm", "scale"): "ln1_g", ("post_attn_norm", "scale"): "ln2_g",
    ("moe_mlp", "gate_kernel"): "router",
    ("moe_mlp", "ExpertsMLP_0", "experts_wg"): "wg",
    ("moe_mlp", "ExpertsMLP_0", "experts_wi"): "wu",
    ("moe_mlp", "ExpertsMLP_0", "experts_wo"): "wd",
    ("embed_tokens", "embedding"): "embed",
    ("final_norm", "scale"): "lnf_g", ("lm_head", "kernel"): "head",
}


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves) from ``seed``, on the
    device, in one jitted call whose compiled form serves every seed.  Each
    layer's tensors are drawn where they land — never stacked over the
    layers first, which would hold the experts twice."""
    z = sizes_of(model)
    abstract = jax.eval_shape(module.init, jax.random.key(0),
                              {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key):
        glob = global_weights(z, key)
        layers = {}
        leaves = []
        for path, leaf in flat:
            names = tuple(p.key for p in path)[1:]       # drop 'params'
            if names[0].startswith("layers_"):
                l = int(names[0][7:])
                if l not in layers:
                    layers[l] = layer_weights(z, key, l)
                x = layers[l][_PROGRAM_LEAVES[names[1:]]]
            else:
                x = glob[_PROGRAM_LEAVES[names]]
            leaves.append(x.reshape(leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(seed_key(seed))


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #
def _rms_norm(x, g, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(t, z):
    """Rotary positions on ``t [B, S, h]``, head by head, half-split
    layout: feature ``i`` of a head pairs with ``i + d/2``."""
    B, S, _ = t.shape
    half = z["d"] // 2
    freqs = 1.0 / (z["theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs    # [S, half]
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    t = t.reshape(B, S, z["heads"], z["d"])
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                           axis=-1).reshape(B, S, z["h"])


def _split(precision):
    """``precision`` -> (that of everything but the experts' matmuls, that
    of the experts' matmuls)."""
    return ("bfloat16", "float8") if precision == "float8_experts" \
        else (precision, precision)


def _experts(z, a, w, precision):
    """The routed expert layer on ``a [B, S, h]``: every expert over every
    token, masked by the token's choice.  Nothing is dropped."""
    precision, inner = _split(precision)
    logits = jnp.matmul(_round(a, precision),
                        _round(w["router"].astype(jnp.float32), precision),
                        precision=HIGHEST)                  # float32, kept
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(gates, z["top_k"])
    if z["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    def one(acc, xs):
        e, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        hid = _store(jax.nn.silu(_mm(a, wg, inner))
                     * _mm(a, wu, inner), inner)
        out = _mm(hid, wd, inner)
        return acc + weight[..., None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(a),
                          (jnp.arange(z["experts"]), w["wg"], w["wu"],
                           w["wd"]))
    return _store(acc, precision)


def _block(z, x, w, precision):
    """One decoder block on ``x [B, S, h]`` with the layer's weights."""
    whole, precision = precision, _split(precision)[0]
    a = _store(_rms_norm(x, w["ln1_g"], z["eps"]), precision)
    q, k, v = (_mm(a, w[m], precision) for m in ("wq", "wk", "wv"))
    q = _store(_rms_norm(q, w["qn_g"], z["eps"]), precision)
    k = _store(_rms_norm(k, w["kn_g"], z["eps"]), precision)
    q, k = _store(_rope(q, z), precision), _store(_rope(k, z), precision)
    att = jax.lax.map(lambda qkv: _attention_row(*qkv, z),
                      (q, k, _store(v, precision)))
    x = _store(x + _mm(_store(att, precision), w["wo"], precision), precision)
    a = _store(_rms_norm(x, w["ln2_g"], z["eps"]), precision)
    return _store(x + _experts(z, a, w, whole), precision)


def hidden_states(z, key, tokens, precision="float32"):
    """``tokens [B, S]`` -> final-normed hidden states ``[B, S, h]``."""
    g = global_weights(z, key)
    outer = _split(precision)[0]
    x = _store(g["embed"].astype(jnp.float32)[tokens], outer)

    def block(x, layer):
        return _block(z, x, layer_weights(z, key, layer), precision), None

    x, _ = jax.lax.scan(block, x, jnp.arange(z["layers"]))
    return _store(_rms_norm(x, g["lnf_g"], z["eps"]), outer), g


def _logits(z, key, tokens, positions, precision):
    """Logits ``[B, R, V]`` at ``positions [B, R]`` only."""
    h, g = hidden_states(z, key, tokens, precision)
    rows = jnp.take_along_axis(h, positions[..., None], axis=1)
    return _mm(rows, g["head"], _split(precision)[0])


def _static(z):
    return tuple(sorted(z.items()))


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _logits_jit(key, tokens, *, sizes, precision):
    z = dict(sizes)
    S = tokens.shape[1]
    return _logits(z, key, tokens,
                   jnp.broadcast_to(jnp.arange(S), tokens.shape), precision)


def logits(z, seed, tokens, precision="float32"):
    """All logits ``[B, S, V]`` of ``tokens [B, S]`` — what the CPU tests
    compare the program with."""
    return _logits_jit(seed_key(seed), jnp.asarray(tokens, jnp.int32),
                       sizes=_static(z), precision=precision)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _nll_jit(key, tokens, positions, *, sizes, precision):
    z = dict(sizes)
    lg = _logits(z, key, tokens, positions, precision)
    labels = jnp.take_along_axis(tokens, positions + 1, axis=1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jax.scipy.special.logsumexp(lg, axis=-1) - gold


def nll_at(z, seed, tokens, positions, precision="float32"):
    """Next-token negative log-likelihood ``[B, R]``: row ``b``'s loss of
    predicting ``tokens[b, p + 1]`` at each ``p`` of ``positions[b]``."""
    return _nll_jit(seed_key(seed), jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(positions, jnp.int32), sizes=_static(z),
                    precision=precision)


@functools.partial(jax.jit, static_argnames=("sizes", "chooser"))
def _gap_jit(key, tokens, start, count, *, sizes, chooser):
    z = dict(sizes)
    S = tokens.shape[0]
    positions = jnp.arange(S - 1)
    lg = _logits(z, key, tokens[None], positions[None], "float32")[0]
    if chooser is None:                 # the tokens that were served
        chosen_ids = tokens[1:]
    else:                               # what ``chooser`` precision picks
        chosen_ids = jnp.argmax(_logits(z, key, tokens[None], positions[None],
                                        chooser)[0], axis=-1)
    chosen = jnp.take_along_axis(lg, chosen_ids[:, None], axis=-1)[:, 0]
    gap = jnp.max(lg, axis=-1) - chosen
    # position p predicts token p+1: generated tokens sit at start..start+count-1
    live = (positions + 1 >= start) & (positions + 1 < start + count)
    return jnp.where(live, gap, 0.0)


def chosen_gaps(z, seed, tokens, prompt_len, n_new, pad_to, chooser=None):
    """For one served request (``tokens`` = prompt + generated): how far
    below the reference's largest logit each generated token's reference
    logit lies, teacher-forced over the request's own tokens, padded to
    ``pad_to`` so every request of a cell shares one compiled program
    (causal attention never sees the padding, and a token's experts do not
    depend on its neighbours).  With ``chooser`` (a precision), the
    CONTROL: the token that the reference computed in that precision would
    have picked stands in the served token's place at every position —
    ``families/opt.py::chosen_gaps`` has the long form."""
    row = np.zeros(pad_to, np.int32)
    row[:len(tokens)] = tokens
    gaps = _gap_jit(seed_key(seed), jnp.asarray(row), prompt_len, n_new,
                    sizes=_static(z), chooser=chooser)
    return np.asarray(gaps)[prompt_len - 1:prompt_len - 1 + n_new]


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "n_new"))
def _greedy_jit(key, tokens, prompt_len, *, sizes, precision, n_new):
    z = dict(sizes)

    def step(i, toks):
        at = prompt_len - 1 + i
        lg = _logits(z, key, toks[None], at[None, None], precision)[0, 0]
        return toks.at[at + 1].set(jnp.argmax(lg).astype(jnp.int32))

    return jax.lax.fori_loop(0, n_new, step, tokens)


def greedy(z, seed, prompt, n_new, pad_to, precision):
    """The reference in the program's place: greedy decoding by full
    recomputation, in ``precision`` — the control's generator."""
    row = np.zeros(pad_to, np.int32)
    row[:len(prompt)] = prompt
    out = _greedy_jit(seed_key(seed), jnp.asarray(row), jnp.int32(len(prompt)),
                      sizes=_static(z), precision=precision, n_new=n_new)
    return np.asarray(out)[:len(prompt) + n_new]
