"""The solar_open2 family (Upstage Solar Open 2, ``model_type: solar_open2``):
weights from a seed, the adapter that hands them to the program, and the
plain reference.

**Reference.**  ``x0 = Embed[ids]``; a layer is pre-norm (RMSNorm: eps 1e-5,
float32 gain)::

    a = x + Mixer( RMSNorm_in(x) )
    y = a + Experts( RMSNorm_post(a) )

*Mixer*, a layer in ``gqa_layers`` — grouped-query softmax attention with NO
positional encoding: ``q = h Wq -> [heads, d]``, ``k = h Wk``, ``v = h Wv ->
[kv heads, d]``, ``p = softmax_f32(q k^T / sqrt(d))`` over keys ``j <= i``,
``out = ((p v) flattened * sigmoid(h Wgate)) Wo``.  Every other layer — the
gated delta rule (Kimi Delta Attention, arXiv:2510.26692), ``H`` heads of
``d``: ``q~, k~, v = SiLU(conv(h Wq)), SiLU(conv(h Wk)), SiLU(conv(h Wv))``
with ``conv`` a causal depthwise convolution of ``short_conv_kernel_size``
taps a channel (zeros before position 0, no bias); ``q = q~ / |q~| d^-1/2``,
``k = k~ / |k~|`` a head; ``g = -exp(A_log[head]) softplus((h Wf_a) Wf_b +
dt_bias)`` a head and key channel, ``beta = 2 sigmoid(h Wb)`` a head
(``kda_allow_neg_eigval``); a float32 state a head, zero before position 0::

    Sb_t = Diag(exp(g_t)) S_{t-1}
    S_t  = Sb_t + beta_t k_t (v_t - Sb_t^T k_t)^T
    o_t  = S_t^T q_t

``y = [RMSNorm_head(o; gain [d]) * sigmoid((h Wg_a) Wg_b)] Wo``.  It is the
RECURRENCE, one position after the other by ``jax.lax.scan`` — no chunked
form, no kernel: what the program's chunk kernel computes in blocks is held
to this.  (The rows AROUND the recurrence — projections, convolutions,
gates — are made 2,048 positions at a time, the state handed on: memory,
not arithmetic.)  *Experts* (every layer): ``s = sigmoid(h Wr)`` in float32
over ``n_routed_experts`` outputs, the ``num_experts_per_tok`` largest of ``s +
b`` (``b`` the stored selection bias; ties to the lower index), ``w =
s[chosen] / sum`` (``norm_topk_prob``) ``* routed_scaling_factor``,
``Shared(h) + sum over chosen experts that are HELD of w_e Expert_e(h)``;
what the absent experts would add is left out here as in the program.  A
final RMSNorm and an untied head.

Plain ``jax.numpy`` in float32 with matmul precision ``highest``; no kernel,
no cache, no batching; ONE sequence, softmax attention in blocks of 64
queries against all keys, and a jitted program a SUBLAYER with that
sublayer's weights drawn when it runs and dropped after it (the served
model's 6.6 GB sit beside the reference on the chip; a layer's held experts
are 1.26 GB).  The rounding rules, the matmul, the norm, the SwiGLU and the
tensor draw are ``families/dots3.py``'s own functions, the vocabulary tables'
draw in blocks of rows and the padding ``families/trinity.py``'s, imported.

**The weights' draw** (normal, from ``--seed``, rounded to bfloat16; std
0.02 but where said; norm gains 1 +- 0.1; token embeddings std 2).  What
must DECIDE the output is the state and its three controls of it — decay,
step size, normalisation — so each is drawn where it works:

* the mixers' ``o_proj`` at std 0.04 and the routed experts' down-projections
  at 0.06 (``families/dots3.py``'s scales and reasons): a linear layer's
  normed, gated heads (unit RMS times a gate about a half, 8,192 wide) add
  ~1.8 a feature to a stream that starts at 2 — three of four layers, so the
  state's contents decide the logits;
* ``A_log`` 0 +- 0.3 and ``dt_bias`` UNIFORM over ``log 0.001 .. log 0.1``:
  ``softplus`` of it is ~its exponential, so a channel's per-token decay
  ``exp(g)`` lies LOG-uniformly between ~0.9 and ~0.999 — memories of ten to
  a thousand positions in every head — and the token's own part ``(h Wf_a)
  Wf_b`` (std ~0.3) moves it by a third either way: a head's channels differ
  by two orders of magnitude, which is what ``scalar_decay`` erases;
* ``Wb`` at 0.02: ``h Wb`` has std ~1.3, ``beta = 2 sigmoid`` spreads over
  ~0.4 .. 1.6 and a third of the steps overshoot (``beta > 1``, the
  negative-eigenvalue half that ``beta_unscaled`` cuts off);
* the convolutions' taps at std 0.5 (four of them: the convolved rows keep
  the projections' scale), ``q`` and ``k`` normalised after them, so
  ``qk_unnormalised`` reads rows ~8 long where the state expects 1;
* the softmax layer's ``Wq``, ``Wk`` at 0.025: logits of std ~2.6, so that a
  query over thousands of NoPE keys rests on a few tens of them and the
  attended values do not average to nothing; its gate at 0.02.

The selection bias is drawn at 0.02 and then BALANCED over the 320 experts
on 32 sequences of 1,024 drawn ids (``families/longcat.py``'s construction
and reason: a bias fitted to one sequence evened that sequence, not the
traffic, and the seeds then lay 1% apart in speed).

**What is assumed** is listed in the configuration file.

``precision`` selects the control: ``"float32"`` (the reference),
``"bfloat16"`` (what a sound program computes: q, k, v, the projections and
the stream in bfloat16; gates, log-decay, the state and its update in
float32), ``"float8"`` (every matmul operand rounded to e4m3 with a
per-tensor scale), and, each bfloat16 but for one thing:
``"bfloat16_state"`` (the state rounded to bfloat16 after every position),
``"scalar_decay"`` (a head's mean log-decay for all its channels),
``"beta_unscaled"`` (``beta = sigmoid``, in (0, 1)),
``"tail_advances_state"`` (after the prompt's last row the state is advanced
over the padded tail of a ``TAIL_CHUNK``-token chunk, each pad row carrying
the last real row's inputs), ``"state_not_cleared"`` (the state starts from
what the sequence's own first ``STALE_ROWS`` rows leave — a slot's last
occupant — not from zero), ``"gate_dropped"`` (no sigmoid gate on either
mixer's heads), ``"qk_unnormalised"`` (``q~ d^-1/2`` and ``k~`` as they
come), ``"float8_experts"`` (the routed and shared experts' matmuls in
float8) and ``"bias_dropped"`` (the top-k of the scores alone).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.dots3 import (HIGHEST, QUERY_BLOCK, _f32, _mm,
                                      _rms_norm, _round, _static, _store,
                                      _swiglu, _tensor, _tensor_alone,
                                      seed_key)
from benchmark.families.trinity import (TABLE_BLOCKS, _padded, _table,
                                        _table_alone)

_W, _G, _EMBED, _OUT, _DOWN, _BIAS = 0.02, 0.1, 2.0, 0.04, 0.06, 0.02
_QK, _TAPS, _A_LOG = 0.025, 0.5, 0.3
_DECAY = (1e-3, 1e-1)    # softplus(dt_bias): -log of a channel's decay
L2_EPS = 1e-6            # the program's guard under a head's norm
GAP_ROWS = 1024          # the longest answer a cell may ask for
PAD_TO = 4096            # a compared sequence is padded to whole such blocks
TAIL_CHUNK = 2048        # the chunk whose padded tail ``tail_advances_state``
STALE_ROWS = 1024        # ... and the rows ``state_not_cleared`` inherits
CONTROLS = ("bfloat16_state", "scalar_decay", "beta_unscaled",
            "tail_advances_state", "state_not_cleared", "gate_dropped",
            "qk_unnormalised", "float8_experts", "bias_dropped")


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    linear = model["linear_attn_config"]
    if model.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if model.get("use_rope") or model.get("kda_use_full_proj") \
            or linear.get("num_kv_heads") is not None \
            or model.get("n_group", 1) != 1 \
            or model.get("topk_group", 1) != 1 \
            or model.get("first_k_dense_replace", 0) \
            or model.get("tie_word_embeddings"):
        raise ValueError("this reference is solar_open2 as released: NoPE "
                         "softmax layers, low-rank decay and gate, one k/v "
                         "head a linear head, an ungrouped router, experts "
                         "in every layer, an untied head")
    layers = model["num_hidden_layers"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    if heads % kv:
        raise ValueError("KV heads divide the heads")
    gqa = tuple(i for i in model["gqa_layers"] if i < layers)
    published = model.get("n_routed_experts_published",
                          model["n_routed_experts"])
    held = tuple(model.get("held_experts", (0, model["n_routed_experts"])))
    if held[1] != model["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    return dict(
        h=model["hidden_size"], heads=heads, kv_heads=kv,
        d=model["head_dim"], kda_heads=linear["num_heads"],
        kda_d=linear["head_dim"], taps=linear["short_conv_kernel_size"],
        rank=linear["head_dim"],
        neg=bool(model.get("kda_allow_neg_eigval", False)),
        gate=bool(model.get("use_gqa_gate", False)), gqa=gqa, layers=layers,
        # the pool's layers, as the benchmark's readers count them
        kinds=tuple("full_attention" if i in gqa else "linear_attention"
                    for i in range(layers)),
        # the expert width under both names the benchmark's readers use
        f=model["moe_intermediate_size"], ef=model["moe_intermediate_size"],
        experts=published, held=held, top_k=model["num_experts_per_tok"],
        shared=model["n_shared_experts"],
        norm_topk=bool(model["norm_topk_prob"]),
        scaling=float(model["routed_scaling_factor"]),
        vocab=model["vocab_size"], eps=float(model["rms_norm_eps"]),
        positions=model["max_position_embeddings"])


def parameters_by_part(z):
    """Parameters counted from the shapes, by part."""
    h, hd, kvd = z["h"], z["heads"] * z["d"], z["kv_heads"] * z["d"]
    w, r = z["kda_heads"] * z["kda_d"], z["rank"]
    expert = 3 * h * z["ef"]
    linear = len(z["kinds"]) - len(z["gqa"])
    parts = {
        "gqa_mixer_each": 2 * h * hd + 2 * h * kvd + z["gate"] * h * hd,
        "kda_mixer_each": 4 * h * w + 2 * (h * r + r * w)
        + h * z["kda_heads"] + z["taps"] * 3 * w + z["kda_heads"] + w
        + z["kda_d"],
        "one_expert": expert, "router_each": h * z["experts"],
        "shared_expert_each": z["shared"] * expert,
        "held_experts_each": z["held"][1] * expert,
        "embedding": z["vocab"] * h, "head": z["vocab"] * h}
    parts["expert_layer_ffn_each"] = parts["router_each"] \
        + parts["shared_expert_each"] + parts["held_experts_each"]
    parts["norm_gains_and_biases"] = z["layers"] * (2 * h + z["experts"]) + h
    parts["all"] = len(z["gqa"]) * parts["gqa_mixer_each"] \
        + linear * parts["kda_mixer_each"] \
        + z["layers"] * parts["expert_layer_ffn_each"] \
        + parts["embedding"] + parts["head"] + parts["norm_gains_and_biases"]
    return parts


# --------------------------------------------------------------------- #
# The draw
# --------------------------------------------------------------------- #
def _layer_kinds(z, layer):
    h, f = z["h"], z["shared"] * z["ef"]
    kinds = [("ln_in", (h,), _G, 1.0), ("ln_post", (h,), _G, 1.0),
             ("router", (h, z["experts"]), _W, 0.0),
             ("select_bias", (z["experts"],), _BIAS, 0.0),
             ("shared_gate", (h, f), _W, 0.0), ("shared_up", (h, f), _W, 0.0),
             ("shared_down", (f, h), _DOWN, 0.0)]
    if layer in z["gqa"]:
        hd, kvd = z["heads"] * z["d"], z["kv_heads"] * z["d"]
        return kinds + [("wq", (h, hd), _QK, 0.0), ("wk", (h, kvd), _QK, 0.0),
                        ("wv", (h, kvd), _W, 0.0), ("wg", (h, hd), _W, 0.0),
                        ("wo", (hd, h), _OUT, 0.0)]
    w, r, taps = z["kda_heads"] * z["kda_d"], z["rank"], z["taps"]
    return kinds + [("wq", (h, w), _W, 0.0), ("wk", (h, w), _W, 0.0),
                    ("wv", (h, w), _W, 0.0),
                    ("taps_q", (taps, w), _TAPS, 0.0),
                    ("taps_k", (taps, w), _TAPS, 0.0),
                    ("taps_v", (taps, w), _TAPS, 0.0),
                    ("wf_a", (h, r), _W, 0.0), ("wf_b", (r, w), _W, 0.0),
                    ("wb", (h, z["kda_heads"]), _W, 0.0),
                    ("a_log", (z["kda_heads"],), _A_LOG, 0.0),
                    ("dt_bias", (w,), None, None),
                    ("wg_a", (h, r), _W, 0.0), ("wg_b", (r, w), _W, 0.0),
                    ("o_norm", (z["kda_d"],), _G, 1.0),
                    ("wo", (w, h), _OUT, 0.0)]


def _decay_bias(key, index, layer, shape):
    """``dt_bias``: uniform over the logarithms of ``_DECAY``, so that
    ``softplus`` of it — a channel's ``-log`` decay a token — lies
    log-uniformly between them."""
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    lo, hi = np.log(_DECAY[0]), np.log(_DECAY[1])
    return jax.random.uniform(k, shape, jnp.float32, lo, hi) \
        .astype(jnp.bfloat16)


_decay_bias_alone = jax.jit(_decay_bias, static_argnums=(3,))


def layer_weights(z, key, layer, draw=_tensor, bias=None):
    """Layer ``layer``'s tensors but its routed experts'; ``bias`` (the
    layer's row of :func:`balanced_biases`) stands in the drawn selection
    bias."""
    uniform = _decay_bias if draw is _tensor else _decay_bias_alone
    w = {name: uniform(key, 100 + i, layer, shape) if std is None
         else draw(key, 100 + i, layer, shape, std, mean)
         for i, (name, shape, std, mean) in enumerate(_layer_kinds(z, layer))}
    return w if bias is None else dict(w, select_bias=bias)


def expert_weights(z, key, layer, expert):
    """The three matrices of published expert ``expert`` (traced or not) of
    ``layer``: a pure function of ``(seed, layer, expert)``."""
    h, f = z["h"], z["ef"]
    k = jax.random.fold_in(jax.random.fold_in(key, 90), layer)
    draw = lambda i, shape, std: (std * jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(k, expert), i), shape,
        jnp.float32)).astype(jnp.bfloat16)
    return {"wg": draw(0, (h, f), _W), "wu": draw(1, (h, f), _W),
            "wd": draw(2, (f, h), _DOWN)}


def global_weights(z, key, table=_table, draw=_tensor,
                   only=("embed", "lnf_g", "head_t")):
    """``embed [vocab, h]``, the final norm's gain, and the head as ``head_t
    [vocab, h]`` — those of ``only`` (the reference holds one table at a
    time)."""
    make = {"embed": lambda: table(key, 0, z["vocab"], z["h"], _EMBED),
            "lnf_g": lambda: draw(key, 1, 0, (z["h"],), _G, 1.0),
            "head_t": lambda: table(key, 2, z["vocab"], z["h"], _W)}
    return {name: make[name]() for name in only}


# --------------------------------------------------------------------- #
# The program's side: its module, and its parameter tree from the seed
# --------------------------------------------------------------------- #
def program_model(model, **overrides):
    """The program's own module at the file's sizes, holding the file's
    share of the experts."""
    from deepspeed_tpu.models.solar_open2 import solar_open2_model
    z = sizes_of(model)                  # refuses what the reference lacks
    return solar_open2_model(model, held_experts=z["held"],
                             **{"dtype": "bfloat16", **overrides})


_LAYER_LEAVES = {         # the program's leaf path in a layer -> the tensor
    ("input_layernorm", "scale"): "ln_in",
    ("post_attention_layernorm", "scale"): "ln_post",
    ("self_attn", "q_proj", "kernel"): "wq",
    ("self_attn", "k_proj", "kernel"): "wk",
    ("self_attn", "v_proj", "kernel"): "wv",
    ("self_attn", "gate_proj", "kernel"): "wg",
    ("self_attn", "o_proj", "kernel"): "wo",
    ("linear_attn", "q_proj", "kernel"): "wq",
    ("linear_attn", "k_proj", "kernel"): "wk",
    ("linear_attn", "v_proj", "kernel"): "wv",
    ("linear_attn", "q_conv1d"): "taps_q",
    ("linear_attn", "k_conv1d"): "taps_k",
    ("linear_attn", "v_conv1d"): "taps_v",
    ("linear_attn", "f_a_proj", "kernel"): "wf_a",
    ("linear_attn", "f_b_proj", "kernel"): "wf_b",
    ("linear_attn", "b_proj", "kernel"): "wb",
    ("linear_attn", "A_log"): "a_log", ("linear_attn", "dt_bias"): "dt_bias",
    ("linear_attn", "g_a_proj", "kernel"): "wg_a",
    ("linear_attn", "g_b_proj", "kernel"): "wg_b",
    ("linear_attn", "o_norm"): "o_norm",
    ("linear_attn", "o_proj", "kernel"): "wo",
    ("moe_mlp", "gate_kernel"): "router",
    ("moe_mlp", "select_bias"): "select_bias",
    ("moe_mlp", "shared_gate", "kernel"): "shared_gate",
    ("moe_mlp", "shared_up", "kernel"): "shared_up",
    ("moe_mlp", "shared_down", "kernel"): "shared_down"}
_EXPERT_LEAVES = {"experts_wg": "wg", "experts_wi": "wu", "experts_wo": "wd"}


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves) from ``seed``, on the
    device, in one jitted call whose compiled form serves every seed.  The
    held experts are drawn one after the other."""
    z = sizes_of(model)
    first, count = z["held"]
    abstract = jax.eval_shape(module.init, jax.random.key(0),
                              {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key, biases):
        glob = global_weights(z, key)
        glob = {("embed_tokens", "embedding"): glob["embed"],
                ("norm", "scale"): glob["lnf_g"],
                ("lm_head", "kernel"): glob["head_t"].T}
        layers, leaves = {}, []

        def layer_leaf(layer, names):
            if names[-1] in _EXPERT_LEAVES:
                return jax.lax.map(
                    lambda e: expert_weights(z, key, layer, e)[
                        _EXPERT_LEAVES[names[-1]]],
                    first + jnp.arange(count))
            if layer not in layers:
                layers[layer] = layer_weights(z, key, layer,
                                              bias=biases[layer])
            return layers[layer][_LAYER_LEAVES[names]]

        for path, leaf in flat:
            names = tuple(p.key for p in path)[1:]       # drop 'params'
            x = layer_leaf(int(names[0][7:]), names[1:]) \
                if names[0].startswith("layers_") else glob[names]
            leaves.append(x.reshape(leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = seed_key(seed)
    return build(key, balanced_biases(z, key))


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #
def _parts(precision):
    """``precision`` -> what each part computes in, and what it computes."""
    sound = dict(outer="bfloat16", experts="bfloat16", state="float32",
                 scalar_decay=False, beta2=True, tail=False, stale=False,
                 gate=True, unit=True, bias=True)
    other = {"bfloat16_state": dict(state="bfloat16"),
             "scalar_decay": dict(scalar_decay=True),
             "beta_unscaled": dict(beta2=False),
             "tail_advances_state": dict(tail=True),
             "state_not_cleared": dict(stale=True),
             "gate_dropped": dict(gate=False),
             "qk_unnormalised": dict(unit=False),
             "float8_experts": dict(experts="float8"),
             "bias_dropped": dict(bias=False)}
    if precision in other:
        return dict(sound, **other[precision])
    return dict(sound, outer=precision, experts=precision)


def _softmax_mixer(z, x, w, precision):
    """The NoPE grouped-query mixer on ONE sequence ``x [S, h]`` (normed
    input)."""
    p = _parts(precision)
    outer = p["outer"]
    S, H, KVH, D = x.shape[0], z["heads"], z["kv_heads"], z["d"]
    r = lambda t: _round(t, outer)
    q = r(_mm(x, w["wq"], outer)).reshape(S, KVH, H // KVH, D)
    k = r(_mm(x, w["wk"], outer)).reshape(S, KVH, D)
    v = r(_mm(x, w["wv"], outer)).reshape(S, KVH, D)
    gate = _mm(x, w["wg"], outer)
    keys = jnp.arange(S)[None, :]
    scale = 1.0 / np.sqrt(D)

    def block(start):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, QUERY_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", cut(q), k, precision=HIGHEST)
        seen = keys <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None, None], s * scale, -1e30),
                              axis=-1)
        out = jnp.einsum("kgqs,skd->qkgd", r(_store(prob, outer)), v,
                         precision=HIGHEST)
        out = _store(out, outer).reshape(QUERY_BLOCK, H * D)
        if p["gate"] and z["gate"]:
            out = _store(out * jax.nn.sigmoid(cut(gate)), outer)
        return _mm(out, w["wo"], outer)

    return jax.lax.map(block, jnp.arange(0, S, QUERY_BLOCK)).reshape(S, -1)


MIXER_BLOCK = 2048       # rows of a sequence the linear mixer holds at once


def _delta_step(state, S, row):
    """One position of every head: ``S [H, d, d]``, ``row`` = ``(q, k, v, g
    [H, d], beta [H])``.  ``state``: the precision the state is kept in."""
    q_t, k_t, v_t, g_t, b_t = row
    decayed = jnp.exp(g_t)[:, :, None] * S
    seen = jnp.einsum("hkv,hk->hv", decayed, k_t, precision=HIGHEST)
    S = decayed + k_t[:, :, None] * (b_t[:, None] * (v_t - seen))[:, None, :]
    S = _store(S, state)
    return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HIGHEST)


def _delta_mixer(z, x, w, precision, prompt_len, real=None):
    """The gated delta-rule mixer on ONE sequence ``x [S, h]`` (normed
    input); ``prompt_len`` (traced) is where ``tail_advances_state`` puts
    its tail.  Returns ``(y [S, h], the state after row real - 1)`` —
    ``real`` (static; default ``S``): rows from it on are padding, scanned
    by nobody.  The RECURRENCE one position after the other; the rows
    around it (projections, convolutions, gates, ``o_proj``) are made
    ``MIXER_BLOCK`` positions at a time with the state handed on, so that a
    33.8k-position sequence's float32 rows (five arrays of 1.2 GB) never
    lie beside a served model all at once."""
    p = _parts(precision)
    outer = p["outer"]
    S, H, D, K = x.shape[0], z["kda_heads"], z["kda_d"], z["taps"]
    B = MIXER_BLOCK if S % MIXER_BLOCK == 0 else S
    heads = lambda t: t.reshape(B, H, D)
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(t * t, -1, keepdims=True) + L2_EPS)
    # the causal taps reach K - 1 rows back: zeros before position 0
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])

    def rows_of(start):
        """``(q, k, v, g [B, H, d], beta [B, H], the gate [B, H d])`` of
        positions ``start .. start + B - 1``."""
        wide = jax.lax.dynamic_slice_in_dim(padded, start, B + K - 1)
        own = wide[K - 1:]

        def act(name, taps):
            rows = _mm(wide, w[name], outer)
            return heads(jax.nn.silu(sum(
                rows[j:j + B] * _f32(w[taps][j]) for j in range(K))))

        q, k, v = act("wq", "taps_q"), act("wk", "taps_k"), act("wv", "taps_v")
        if p["unit"]:
            q, k = unit(q), unit(k)
        q, k, v = (_round(_store(t, outer), outer)
                   for t in (q * D ** -0.5, k, v))
        g = -jnp.exp(_f32(w["a_log"]))[:, None] * heads(jax.nn.softplus(
            _mm(_mm(own, w["wf_a"], outer), w["wf_b"], outer)
            + _f32(w["dt_bias"])))
        if p["scalar_decay"]:
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        beta = jax.nn.sigmoid(_mm(own, w["wb"], outer)) \
            * (2.0 if z["neg"] and p["beta2"] else 1.0)
        gate = jax.nn.sigmoid(_mm(_mm(own, w["wg_a"], outer), w["wg_b"],
                                  outer))
        return (q, k, v, g, beta), gate

    def scan_all(state, limit):
        """Every block in turn from ``state``; positions from ``limit`` on
        leave it alone.  ``(y [S, h], state)``."""
        def block(state, start):
            rows, gate = rows_of(start)

            def step(S_t, t):
                row = tuple(r[t] for r in rows)
                S_new, o = _delta_step(p["state"], S_t, row)
                if p["tail"]:
                    # the chunk's pad rows after the prompt's last: that
                    # row's inputs again, their outputs nobody's
                    pads = jnp.where(start + t == prompt_len - 1,
                                     (-prompt_len) % TAIL_CHUNK, 0)
                    S_new = jax.lax.fori_loop(
                        0, pads, lambda _, s: _delta_step(p["state"], s,
                                                          row)[0], S_new)
                return jnp.where(start + t < limit, S_new, S_t), o

            state, o = jax.lax.scan(step, state, jnp.arange(B))
            o = _store(_rms_norm(_round(_store(o, outer), outer), w["o_norm"],
                                 z["eps"]), outer).reshape(B, H * D)
            if p["gate"]:
                o = _store(o * gate, outer)
            return state, _mm(o, w["wo"], outer)

        state, y = jax.lax.scan(block, state, jnp.arange(0, S, B))
        return y.reshape(S, -1), state

    state = jnp.zeros((H, D, D), jnp.float32)
    if p["stale"]:
        _, state = scan_all(state, min(S, STALE_ROWS))
    return scan_all(state, real or S)


def _scores(h, w, outer):
    """The router's scores ``[S, experts]`` of ``h [S, h]``: float32
    sigmoids, kept."""
    return jax.nn.sigmoid(jnp.matmul(
        _round(h, outer), _round(_f32(w["router"]), outer),
        precision=HIGHEST))


def expert_layer(z, key, layer, h, w, precision, held=None, shared=True):
    """The expert layer on ``h [S, h]``: the experts ``held`` (default the
    configuration's share; ``(0, experts)`` is the uncut layer) each
    computed over every token and masked by the token's choice, plus —
    ``shared`` — the shared expert.  Nothing held is dropped."""
    p = _parts(precision)
    first, count = held or z["held"]
    scores = _scores(h, w, p["outer"])
    _, top_i = jax.lax.top_k(
        scores + (_f32(w["select_bias"]) if p["bias"] else 0.0), z["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=1)
    if z["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * z["scaling"]

    def one(acc, e):
        ew = expert_weights(z, key, layer, e)
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(h, ew["wg"], ew["wu"],
                                               ew["wd"], p["experts"]), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), first + jnp.arange(count))
    if shared and z["shared"]:
        acc = acc + _swiglu(h, w["shared_gate"], w["shared_up"],
                            w["shared_down"], p["experts"])
    return _store(acc, p["outer"])


# A sublayer is one jitted program, and so are the embedding, the head and
# each tensor's draw: the caller draws a layer's weights, runs it, and drops
# them before the next.  A control changes ONE thing, so every sublayer it
# does not reach runs (and is compiled) as bfloat16's: what each reads
_READ_BY = {"softmax": ("gate_dropped",),
            "linear": tuple(c for c in CONTROLS
                            if c not in ("float8_experts", "bias_dropped")),
            "ffn": ("float8_experts", "bias_dropped"), "ends": ()}


def _seen_by(sublayer, precision):
    """``precision`` as ``sublayer`` computes it."""
    return "bfloat16" if precision in CONTROLS \
        and precision not in _READ_BY[sublayer] else precision


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "softmax",
                                             "real"))
def _mixer_jit(x, w, prompt_len, *, sizes, precision, softmax, real=None):
    """``(the stream after the mixer, a linear layer's state or None)``."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, w["ln_in"], z["eps"]), outer)
    a, state = (_softmax_mixer(z, normed, w, precision), None) if softmax \
        else _delta_mixer(z, normed, w, precision, prompt_len, real)
    return _store(x + a, outer), state


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _ffn_jit(key, x, w, layer, *, sizes, precision):
    """``layer`` is traced (it keys the experts' draw): the expert layers of
    one length share one compiled program."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, w["ln_post"], z["eps"]), outer)
    return _store(x + expert_layer(z, key, layer, normed, w, precision),
                  outer)


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed_jit(embed, tokens, *, precision):
    return _store(_f32(embed[tokens]), _parts(precision)["outer"])


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _head_jit(lnf_g, head_t, x, positions, *, sizes, precision):
    """Logits at ``positions``, a block of the vocabulary's rows at a
    time."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    h = _round(_store(_rms_norm(x[positions], lnf_g, z["eps"]), outer), outer)
    blocks = TABLE_BLOCKS if head_t.shape[0] % TABLE_BLOCKS == 0 else 1
    out = jax.lax.map(
        lambda w: jnp.matmul(h, _round(_f32(w), outer).T, precision=HIGHEST),
        head_t.reshape(blocks, -1, head_t.shape[1]))
    return _store(jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1), outer)


def _mix(x, w, sequences, prompt_len, **kw):
    """The mixer sublayer on a stream of ``sequences`` equal parts, each a
    sequence of its own."""
    at = jnp.asarray(x.shape[0] // sequences if prompt_len is None
                     else prompt_len, jnp.int32)
    if sequences == 1:
        return _mixer_jit(x, w, at, **kw)
    return jnp.concatenate([_mixer_jit(part, w, at, **kw)[0]
                            for part in jnp.split(x, sequences)]), None


def _layer(z, key, layer, x, precision, bias=None, balance=None,
           sequences=1, prompt_len=None, states=None, real=None):
    """One layer on the stream ``x [S, h]`` (``sequences`` of them end to
    end).  ``bias``: the layer's balanced selection bias; ``balance``: a
    function ``(stream, weights) -> bias`` run in its place; ``states``: a
    list that a linear layer's state after row ``real - 1`` is added to."""
    softmax = layer in z["gqa"]
    w = layer_weights(z, key, layer, _tensor_alone, bias)
    x, state = _mix(
        x, w, sequences, prompt_len, softmax=softmax, sizes=_static(z),
        precision=_seen_by("softmax" if softmax else "linear", precision),
        **({} if real is None else {"real": real}))
    if states is not None and state is not None:
        states.append(state)
    if balance is not None:
        w["select_bias"] = balance(x, w)
    return _ffn_jit(key, x, w, jnp.int32(layer), sizes=_static(z),
                    precision=_seen_by("ffn", precision))


# --------------------------------------------------------------------- #
# The selection bias: the loads evened out, as training leaves them
# --------------------------------------------------------------------- #
BALANCE_SEQUENCES, BALANCE_LENGTH = 32, 1024
BALANCE_STEPS, _BALANCE_RATE, _BALANCE_DECAY = 200, 0.05, 0.975


@functools.partial(jax.jit, static_argnames=("sizes",))
def _balance_jit(x, w, *, sizes):
    """``families/glm5.py::_balance_jit``: from the drawn bias, every
    expert's bias moved against its share of the ``S x top_k`` choices, in
    shrinking steps."""
    z = dict(sizes)
    scores = _scores(_rms_norm(x, w["ln_post"], z["eps"]), w, "float32")
    experts = scores.shape[1]
    mean = scores.shape[0] * z["top_k"] / experts

    def step(bias, rate):
        _, top = jax.lax.top_k(scores + bias, z["top_k"])
        load = jnp.zeros((experts,), jnp.float32).at[top.reshape(-1)].add(1.0)
        return bias - rate * jnp.clip(load / mean - 1.0, -1.0, 1.0), None

    rates = _BALANCE_RATE * _BALANCE_DECAY ** jnp.arange(BALANCE_STEPS)
    bias, _ = jax.lax.scan(step, _f32(w["select_bias"]), rates)
    return bias.astype(jnp.bfloat16)


_BIASES_KEPT, _biases = 4, {}


def balanced_biases(z, key):
    """``[layers, experts]`` bfloat16: the selection biases as aux-loss-free
    training leaves them — every one of the router's 320 outputs chosen
    equally often (``families/longcat.py::balanced_biases`` has the why).
    The float32 reference runs ``BALANCE_SEQUENCES`` sequences of
    ``BALANCE_LENGTH`` drawn ids, each a sequence of its own, layer by
    layer, and each layer's bias is balanced on the stream the balanced
    layers before it hand on.  Kept a few seeds long: the program's tree and
    the reference read the same rows."""
    at = (_static(z), np.asarray(jax.random.key_data(key)).tobytes())
    if at not in _biases:
        while len(_biases) >= _BIASES_KEPT:
            del _biases[next(iter(_biases))]
        _biases[at] = _balanced(z, key)
    return _biases[at]


def balance_ids(z, key):
    return jax.random.randint(jax.random.fold_in(key, 91),
                              (BALANCE_SEQUENCES, BALANCE_LENGTH), 0,
                              z["vocab"])


def _embedded(z, key, tokens, precision):
    """The stream's start; the table is drawn for it and dropped."""
    embed = global_weights(z, key, _table_alone, _tensor_alone,
                           only=("embed",))["embed"]
    return _embed_jit(embed, tokens, precision=precision)


def _balanced(z, key):
    x = _embedded(z, key, balance_ids(z, key).reshape(-1), "float32")
    rows = []

    def balance(stream, w):
        rows.append(_balance_jit(stream, w, sizes=_static(z)))
        return rows[-1]

    for layer in range(z["layers"]):
        x = _layer(z, key, layer, x, "float32", balance=balance,
                   sequences=BALANCE_SEQUENCES)
    return jnp.stack(rows)


def _forward(z, key, tokens, positions, precision, prompt_len=None):
    """Logits ``[R, V]`` at ``positions [R]`` of one sequence ``tokens
    [S]`` (``S`` a multiple of 64); ``prompt_len``: where the request's
    prompt ends (the controls of the serving path read it)."""
    biases = balanced_biases(z, key)
    x = _embedded(z, key, tokens, _seen_by("ends", precision))
    for layer in range(z["layers"]):
        x = _layer(z, key, layer, x, precision, biases[layer],
                   prompt_len=prompt_len)
    g = global_weights(z, key, _table_alone, _tensor_alone,
                       only=("lnf_g", "head_t"))
    return _head_jit(g["lnf_g"], g["head_t"], x, positions, sizes=_static(z),
                     precision=_seen_by("ends", precision))


def kda_states(z, seed, tokens, precision="float32"):
    """``[linear layers, H, d, d]``: every gated delta-rule layer's state
    after the LAST of ``tokens`` — what a slot's state row holds when the
    program has run exactly these positions."""
    key, states = seed_key(seed), []
    biases = balanced_biases(z, key)
    x = _embedded(z, key, _padded(tokens), precision)
    for layer in range(z["layers"]):
        x = _layer(z, key, layer, x, precision, biases[layer], states=states,
                   real=len(tokens))
    return jnp.stack(states)


def logits(z, seed, tokens, precision="float32", prompt_len=None):
    """All logits ``[S, V]`` of ONE sequence ``tokens [S]`` — what the CPU
    tests compare the program with."""
    return _forward(z, seed_key(seed), _padded(tokens),
                    jnp.arange(len(tokens)), precision, prompt_len)


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
_ROWS_KEPT, _rows = 1, {}        # 0.1 GB a request at 24,576 ids


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
    served tokens), the float32 reference computed ONCE for all of them.
    ``pad_to`` (a cell's ``max_cache_len``, 33.8k here) is not padded to: the
    forward is causal, so a request is padded to whole ``PAD_TO`` blocks of
    its own length — nine shapes at most.  A chooser whose logits are not
    finite (``qk_unnormalised``'s state grows without bound) reads as an
    infinite gap."""
    if n_new > GAP_ROWS:
        raise ValueError(f"answers of at most {GAP_ROWS} tokens")
    key, tokens = seed_key(seed), _padded(
        tokens, PAD_TO if len(tokens) > QUERY_BLOCK * 8 else QUERY_BLOCK)
    # position p predicts token p + 1: the generated tokens sit at
    # prompt_len .. prompt_len + n_new - 1
    positions = jnp.minimum(prompt_len - 1 + jnp.arange(GAP_ROWS),
                            tokens.shape[0] - 2)
    lg = _reference_rows(z, seed, tokens, positions)
    out = {}
    for chooser in choosers:
        finite = True
        if chooser is None:             # the tokens that were served
            ids = tokens[positions + 1]
        else:                           # what ``chooser`` precision picks
            other = _forward(z, key, tokens, positions, chooser, prompt_len)
            finite = jnp.all(jnp.isfinite(other), axis=-1)
            ids = jnp.argmax(other, axis=-1)
        chosen = jnp.take_along_axis(lg, ids[:, None], axis=-1)[:, 0]
        gap = jnp.where(finite, jnp.max(lg, axis=-1) - chosen, jnp.inf)
        out[chooser] = np.asarray(gap)[:n_new]
    return out


def chosen_gaps(z, seed, tokens, prompt_len, n_new, pad_to, chooser=None):
    """For one served request (``tokens`` = prompt + generated): how far
    below the reference's largest logit each generated token's reference
    logit lies, teacher-forced over the request's own tokens —
    ``families/opt.py::chosen_gaps`` has the long form.  With ``chooser`` (a
    precision), the CONTROL: the token that the reference computed in that
    precision would have picked stands in the served token's place."""
    return gaps_under(z, seed, tokens, prompt_len, n_new, pad_to,
                      [chooser])[chooser]


def greedy(z, seed, prompt, n_new, pad_to, precision):
    """The reference in the program's place: greedy decoding by full
    recomputation, in ``precision`` (a full forward a token: for short
    requests only)."""
    toks = list(np.asarray(prompt))
    for _ in range(n_new):
        at = jnp.asarray([len(toks) - 1], jnp.int32)
        row = np.zeros(max(pad_to, len(toks) + 1), np.int32)
        row[:len(toks)] = toks
        lg = _forward(z, seed_key(seed), _padded(row), at, precision,
                      len(prompt))
        toks.append(int(np.argmax(np.asarray(lg[0]))))
    return np.asarray(toks, np.int32)
