"""The trinity family (Arcee Trinity, ``model_type: afmoe``): weights from a
seed, the adapter that hands them to the program, and the plain reference.

**Reference.**  ``x0 = Embed[ids] * sqrt(hidden_size)`` (``mup_enabled``);
a layer is a SANDWICH (RMSNorm: eps 1e-5, float32 gain, four a layer)::

    a = x + RMSNorm_post_attn( Attn( RMSNorm_in(x) ) )
    y = a + RMSNorm_post_ffn ( FFN ( RMSNorm_pre_ffn(a) ) )

*Attn(h)*: ``q = h Wq -> [heads, d]``, ``k = h Wk``, ``v = h Wv -> [kv heads,
d]``, ``g = h Wg -> [heads d]``; an RMSNorm over each HEAD of q and k (gains
``[d]``); on a ``sliding_attention`` layer rotary positions over the whole
head (theta ``rope_theta``, half-split pairing), on a ``full_attention``
layer NO positional encoding; ``p = softmax_f32(q k^T / sqrt(d))`` over keys
``j <= i`` and, on a sliding layer, ``j > i - sliding_window`` (the query's
own position among its ``sliding_window`` keys); ``out = ((p v) flattened *
sigmoid(g)) Wo``.  *FFN*: SwiGLU of width ``intermediate_size`` in the
first ``num_dense_layers`` layers; in the rest ``s = sigmoid(h Wr)`` in
float32 over ``num_experts`` outputs, the ``num_experts_per_tok`` largest of
``s + b`` (``b`` the stored selection bias; ties to the lower index), ``w =
s[chosen] / (sum + 1e-20)`` (``route_norm``) ``* route_scale``, ``Shared(h)
+ sum_e w_e Expert_e(h)``.  A final RMSNorm and an untied head.

Plain ``jax.numpy`` in float32 with matmul precision ``highest``; no kernel,
no cache, no batching; ONE sequence, attention in blocks of 64 queries
against all keys, and a jitted program a SUBLAYER with that sublayer's
weights drawn when it runs and dropped after it (the served model's 8.48 GB
sit beside the reference on the chip; an expert layer is 1.68 GB).  The
rounding rules, the matmul, the norm, the SwiGLU, the rotary pairing and
the tensor draw are ``families/dots3.py``'s own functions, imported.

**The weights' draw** (normal, from ``--seed``, rounded to bfloat16; std
0.02 but where said; norm gains 1 +- 0.1).  ``families/dots3.py``'s scales
where the block is the same, with what the sandwich changes: a sublayer's
output goes through an RMSNorm before it joins the stream, so every
sublayer adds a unit-RMS vector times its gain WHATEVER its matrices' scale
— the down-projections' and ``o_proj``'s std (0.06 / 0.04 there) decide
nothing here and stay at 0.02.  Token embeddings are drawn at ``2 /
sqrt(hidden_size)``: after the ``sqrt(hidden_size)`` multiplier the stream
starts at dots3's std 2 (a token's own embedding decides its routing), and a
program that left the multiplier out would start 45 times smaller.  The
per-head QK-norm leaves q and k at unit RMS times their gains, so the
logits' standard deviation is the product of the two gains: they are drawn
about 1.5 (logits ~2.2 units, dots3's level: a softmax over 2,048 keys rests
on a few tens of them, so which keys are in the band and what the ring holds
for them decide the output; at 1.0 the softmax is near flat and a wrong key
weighs 1/2048).  Inside an expert layer the routed part (gates summing to
``route_scale`` 2.826) and the shared expert are summed BEFORE the post-norm,
so their relative size is the matrices': both down-projections 0.02, the
routed sum ~2.8 x one expert's output beside the shared expert's.  The
selection bias is drawn at 0.02 and then BALANCED over the 128 experts on 32
sequences of 1,024 drawn ids (``families/longcat.py``'s construction and
reason: a bias fitted to one sequence evened that sequence, not the traffic,
and the seeds then lay 1% apart in speed).

**What is assumed** is listed in the configuration file.

``precision`` selects the control: ``"float32"`` (the reference),
``"bfloat16"`` (what a sound program computes), ``"float8"`` (every matmul
operand rounded to e4m3 with a per-tensor scale), and, each bfloat16 but for
one thing: ``"float8_experts"`` (the routed and shared experts' matmuls in
float8), ``"rope_on_full"`` (rotary positions on the full layers too),
``"gate_dropped"`` (no ``sigmoid(g)`` on the heads' outputs),
``"stale_ring_row"`` (ring row ``STALE_ROW`` of every sliding layer never
overwritten: a key at a position ``p >= sliding_window`` with ``p %
sliding_window == STALE_ROW`` reads position ``STALE_ROW``'s K and V),
``"window_off_by_one"`` (``sliding_window - 1`` keys) and ``"bias_dropped"``
(the top-k of the scores alone).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.dots3 import (HIGHEST, QUERY_BLOCK, _f32, _mm,
                                      _rms_norm, _rope, _round, _static,
                                      _store, _swiglu, _tensor,
                                      _tensor_alone, seed_key)

_W, _G, _EMBED, _QK_GAIN, _BIAS = 0.02, 0.1, 2.0, 1.5, 0.02
GATE_SUM_EPS = 1e-20
GAP_ROWS = 1024          # the longest answer a cell may ask for
PAD_TO = 2048            # a compared sequence is padded to whole such blocks
STALE_ROW = 5
TABLE_BLOCKS = 16        # a vocabulary table is drawn this many rows' blocks
CONTROLS = ("float8_experts", "rope_on_full", "gate_dropped",
            "stale_ring_row", "window_off_by_one", "bias_dropped")


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    if model.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if model.get("attention_bias") or model.get("tie_word_embeddings") \
            or model.get("score_func", "sigmoid") != "sigmoid" \
            or model.get("n_group", 1) != 1 \
            or model.get("topk_group", 1) != 1 \
            or model.get("hidden_act", "silu") != "silu":
        raise ValueError("this reference is afmoe as released: no biases, "
                         "an untied head, sigmoid scores without expert "
                         "groups, SwiGLU")
    kinds = tuple(model["layer_types"])
    if len(kinds) != model["num_hidden_layers"] \
            or set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError("layer_types must name every layer: "
                         "sliding_attention or full_attention")
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    if heads % kv:
        raise ValueError("KV heads divide the heads")
    return dict(
        h=model["hidden_size"], heads=heads, kv_heads=kv,
        d=model["head_dim"], kinds=kinds, layers=len(kinds),
        dense_layers=model["num_dense_layers"],
        dense_f=model["intermediate_size"],
        # the expert width under both names the benchmark's readers use
        f=model["moe_intermediate_size"], ef=model["moe_intermediate_size"],
        experts=model["num_experts"], held=(0, model["num_experts"]),
        top_k=model["num_experts_per_tok"],
        shared=model["num_shared_experts"],
        route_norm=bool(model["route_norm"]),
        scaling=float(model["route_scale"]),
        window=model["sliding_window"], theta=float(model["rope_theta"]),
        mup=bool(model.get("mup_enabled", False)),
        vocab=model["vocab_size"], eps=float(model["rms_norm_eps"]),
        positions=model["max_position_embeddings"])


def parameters_by_part(z):
    """Parameters counted from the shapes, by part."""
    h, hd = z["h"], z["heads"] * z["d"]
    attn = 2 * h * hd + 2 * h * z["kv_heads"] * z["d"] + h * hd
    expert = 3 * h * z["ef"]
    experts = z["layers"] - z["dense_layers"]
    parts = {
        "attention_each": attn, "dense_ffn_each": 3 * h * z["dense_f"],
        "one_expert": expert, "router_each": h * z["experts"],
        "shared_expert_each": z["shared"] * expert,
        "expert_layer_ffn_each": (z["experts"] + z["shared"]) * expert
        + h * z["experts"],
        "embedding": z["vocab"] * h, "head": z["vocab"] * h}
    parts["matrices"] = z["layers"] * attn \
        + z["dense_layers"] * parts["dense_ffn_each"] \
        + experts * parts["expert_layer_ffn_each"] \
        + parts["embedding"] + parts["head"]
    parts["norm_gains_and_biases"] = z["layers"] * (4 * h + 2 * z["d"]) \
        + h + experts * z["experts"]
    return parts


# --------------------------------------------------------------------- #
# The draw
# --------------------------------------------------------------------- #
def _layer_kinds(z, layer):
    h, hd, kvd = z["h"], z["heads"] * z["d"], z["kv_heads"] * z["d"]
    kinds = [("ln_in", (h,), _G, 1.0), ("ln_post_attn", (h,), _G, 1.0),
             ("ln_pre_ffn", (h,), _G, 1.0), ("ln_post_ffn", (h,), _G, 1.0),
             ("wq", (h, hd), _W, 0.0), ("wk", (h, kvd), _W, 0.0),
             ("wv", (h, kvd), _W, 0.0), ("wg", (h, hd), _W, 0.0),
             ("wo", (hd, h), _W, 0.0),
             ("q_norm", (z["d"],), _G, _QK_GAIN),
             ("k_norm", (z["d"],), _G, _QK_GAIN)]
    if layer < z["dense_layers"]:
        f = z["dense_f"]
        return kinds + [("w_gate", (h, f), _W, 0.0), ("w_up", (h, f), _W, 0.0),
                        ("w_down", (f, h), _W, 0.0)]
    f = z["shared"] * z["ef"]
    return kinds + [("router", (h, z["experts"]), _W, 0.0),
                    ("select_bias", (z["experts"],), _BIAS, 0.0),
                    ("shared_gate", (h, f), _W, 0.0),
                    ("shared_up", (h, f), _W, 0.0),
                    ("shared_down", (f, h), _W, 0.0)]


def layer_weights(z, key, layer, draw=_tensor, bias=None):
    """Layer ``layer``'s tensors but its routed experts'; ``bias`` (the
    layer's row of :func:`balanced_biases`) stands in the drawn selection
    bias."""
    w = {name: draw(key, 100 + i, layer, shape, std, mean)
         for i, (name, shape, std, mean) in enumerate(_layer_kinds(z, layer))}
    return w if bias is None else dict(w, select_bias=bias)


def expert_weights(z, key, layer, expert):
    """The three matrices of expert ``expert`` (traced or not) of ``layer``:
    a pure function of ``(seed, layer, expert)``."""
    h, f = z["h"], z["ef"]
    k = jax.random.fold_in(jax.random.fold_in(key, 90), layer)
    draw = lambda i, shape: (_W * jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(k, expert), i), shape,
        jnp.float32)).astype(jnp.bfloat16)
    return {"wg": draw(0, (h, f)), "wu": draw(1, (h, f)),
            "wd": draw(2, (f, h))}


def _table(key, index, vocab, h, std):
    """A ``[vocab, h]`` table drawn ``TABLE_BLOCKS`` blocks of rows one
    after the other: the draw's temporaries for 200,192 x 2,048 values at
    once are 15 GB (``families/dots3.py``: 3 GB for 16,384 x 5,120)."""
    blocks = TABLE_BLOCKS if vocab % TABLE_BLOCKS == 0 else 1
    k = jax.random.fold_in(key, index)
    rows = jax.lax.map(
        lambda b: (std * jax.random.normal(
            jax.random.fold_in(k, b), (vocab // blocks, h),
            jnp.float32)).astype(jnp.bfloat16), jnp.arange(blocks))
    return rows.reshape(vocab, h)


_table_alone = jax.jit(_table, static_argnums=(1, 2, 3, 4))


def global_weights(z, key, table=_table, draw=_tensor,
                   only=("embed", "lnf_g", "head_t")):
    """``embed [vocab, h]`` (times ``sqrt(h)`` in the forward), the final
    norm's gain, and the head as ``head_t [vocab, h]`` — those of ``only``
    (the reference holds one table at a time)."""
    make = {"embed": lambda: table(
                key, 0, z["vocab"], z["h"],
                _EMBED / float(np.sqrt(z["h"])) if z["mup"] else _EMBED),
            "lnf_g": lambda: draw(key, 1, 0, (z["h"],), _G, 1.0),
            "head_t": lambda: table(key, 2, z["vocab"], z["h"], _W)}
    return {name: make[name]() for name in only}


# --------------------------------------------------------------------- #
# The program's side: its module, and its parameter tree from the seed
# --------------------------------------------------------------------- #
def program_model(model, **overrides):
    """The program's own module at the file's sizes."""
    from deepspeed_tpu.models.trinity import trinity_model
    sizes_of(model)                      # refuses what the reference lacks
    return trinity_model(model, **{"dtype": "bfloat16", **overrides})


_LAYER_LEAVES = {         # the program's leaf path in a layer -> the tensor
    ("input_layernorm", "scale"): "ln_in",
    ("post_attention_layernorm", "scale"): "ln_post_attn",
    ("pre_mlp_layernorm", "scale"): "ln_pre_ffn",
    ("post_mlp_layernorm", "scale"): "ln_post_ffn",
    ("self_attn", "q_proj", "kernel"): "wq",
    ("self_attn", "k_proj", "kernel"): "wk",
    ("self_attn", "v_proj", "kernel"): "wv",
    ("self_attn", "gate_proj", "kernel"): "wg",
    ("self_attn", "o_proj", "kernel"): "wo",
    ("self_attn", "q_norm"): "q_norm", ("self_attn", "k_norm"): "k_norm",
    ("mlp", "gate_proj", "kernel"): "w_gate",
    ("mlp", "up_proj", "kernel"): "w_up",
    ("mlp", "down_proj", "kernel"): "w_down",
    ("moe_mlp", "gate_kernel"): "router",
    ("moe_mlp", "select_bias"): "select_bias",
    ("moe_mlp", "shared_gate", "kernel"): "shared_gate",
    ("moe_mlp", "shared_up", "kernel"): "shared_up",
    ("moe_mlp", "shared_down", "kernel"): "shared_down"}
_EXPERT_LEAVES = {"experts_wg": "wg", "experts_wi": "wu", "experts_wo": "wd"}


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves) from ``seed``, on the
    device, in one jitted call whose compiled form serves every seed.  The
    experts are drawn one after the other (a layer's 128 at once would keep
    10 GB of the draw's temporaries)."""
    z = sizes_of(model)
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
                        _EXPERT_LEAVES[names[-1]]], jnp.arange(z["experts"]))
            if layer not in layers:
                at = layer - z["dense_layers"]
                layers[layer] = layer_weights(
                    z, key, layer, bias=biases[at] if at >= 0 else None)
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
    """``precision`` -> what each part computes in, and what it computes:
    ``dict(outer, experts, rope_full, gate, stale, window_less, bias)``."""
    sound = dict(outer="bfloat16", experts="bfloat16", rope_full=False,
                 gate=True, stale=False, window_less=0, bias=True)
    other = {"float8_experts": dict(experts="float8"),
             "rope_on_full": dict(rope_full=True),
             "gate_dropped": dict(gate=False),
             "stale_ring_row": dict(stale=True),
             "window_off_by_one": dict(window_less=1),
             "bias_dropped": dict(bias=False)}
    if precision in other:
        return dict(sound, **other[precision])
    return dict(sound, outer=precision, experts=precision)


def _attention(z, sliding, x, w, precision):
    """Attention of ONE sequence ``x [S, h]`` (normed input) in a layer of
    either kind."""
    p = _parts(precision)
    outer = p["outer"]
    S, H, KVH, D = x.shape[0], z["heads"], z["kv_heads"], z["d"]
    heads = lambda t, n, g: _store(
        _rms_norm(t.reshape(S, n, D), g, z["eps"]), outer)
    q = heads(_mm(x, w["wq"], outer), H, w["q_norm"])
    k = heads(_mm(x, w["wk"], outer), KVH, w["k_norm"])
    v = _mm(x, w["wv"], outer).reshape(S, KVH, D)
    if sliding or p["rope_full"]:
        q = _store(_rope(q, z["theta"]), outer)
        k = _store(_rope(k, z["theta"]), outer)
    window = z["window"] - p["window_less"] if sliding else S + 1
    if sliding and p["stale"]:
        # what the ring would hold had row STALE_ROW never been written
        # again: its first occupant's K and V
        at = jnp.arange(S)
        stale = (at >= z["window"]) & (at % z["window"] == STALE_ROW)
        k = jnp.where(stale[:, None, None], k[STALE_ROW], k)
        v = jnp.where(stale[:, None, None], v[STALE_ROW], v)
    r = lambda t: _round(t, outer)
    q, k, v = r(q).reshape(S, KVH, H // KVH, D), r(k), r(v)
    gate = _mm(x, w["wg"], outer)
    keys = jnp.arange(S)[None, :]
    scale = 1.0 / np.sqrt(D)

    def block(start):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, QUERY_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", cut(q), k, precision=HIGHEST)
        at = (start + jnp.arange(QUERY_BLOCK))[:, None]
        seen = (keys <= at) & (keys > at - window)
        prob = jax.nn.softmax(jnp.where(seen[None, None], s * scale, -1e30),
                              axis=-1)
        out = jnp.einsum("kgqs,skd->qkgd", r(_store(prob, outer)), v,
                         precision=HIGHEST)
        out = _store(out, outer).reshape(QUERY_BLOCK, H * D)
        if p["gate"]:
            out = _store(out * jax.nn.sigmoid(cut(gate)), outer)
        return _mm(out, w["wo"], outer)

    return jax.lax.map(block, jnp.arange(0, S, QUERY_BLOCK)).reshape(S, -1)


def _scores(h, w, outer):
    """The router's scores ``[S, experts]`` of ``h [S, h]``: float32
    sigmoids, kept."""
    return jax.nn.sigmoid(jnp.matmul(
        _round(h, outer), _round(_f32(w["router"]), outer),
        precision=HIGHEST))


def expert_layer(z, key, layer, h, w, precision):
    """The expert layer on ``h [S, h]``: every expert computed over every
    token and masked by the token's choice, plus the shared expert."""
    p = _parts(precision)
    scores = _scores(h, w, p["outer"])
    _, top_i = jax.lax.top_k(
        scores + (_f32(w["select_bias"]) if p["bias"] else 0.0), z["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=1)
    if z["route_norm"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True)
                         + GATE_SUM_EPS)
    top_w = top_w * z["scaling"]

    def one(acc, e):
        ew = expert_weights(z, key, layer, e)
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(h, ew["wg"], ew["wu"],
                                               ew["wd"], p["experts"]), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(z["experts"]))
    if z["shared"]:
        acc = acc + _swiglu(h, w["shared_gate"], w["shared_up"],
                            w["shared_down"], p["experts"])
    return _store(acc, p["outer"])


# A sublayer is one jitted program, and so are the embedding, the head and
# each tensor's draw: the caller draws a layer's weights, runs it, and drops
# them before the next
@functools.partial(jax.jit, static_argnames=("sizes", "precision", "sliding"))
def _attention_jit(x, w, *, sizes, precision, sliding):
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, w["ln_in"], z["eps"]), outer)
    a = _attention(z, sliding, normed, w, precision)
    return _store(x + _store(_rms_norm(a, w["ln_post_attn"], z["eps"]),
                             outer), outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _ffn_jit(key, x, w, layer, *, sizes, precision):
    """``layer`` is traced (it keys the experts' draw): the expert layers of
    one length share one compiled program.  A dense layer's weights say so."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, w["ln_pre_ffn"], z["eps"]), outer)
    if "w_gate" in w:
        y = _swiglu(normed, w["w_gate"], w["w_up"], w["w_down"], outer)
    else:
        y = expert_layer(z, key, layer, normed, w, precision)
    return _store(x + _store(_rms_norm(y, w["ln_post_ffn"], z["eps"]),
                             outer), outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _embed_jit(embed, tokens, *, sizes, precision):
    z, outer = dict(sizes), _parts(precision)["outer"]
    x = _store(_f32(embed[tokens]), outer)
    return _store(x * _round(jnp.float32(np.sqrt(z["h"])), outer), outer) \
        if z["mup"] else x


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _head_jit(lnf_g, head_t, x, positions, *, sizes, precision):
    """Logits at ``positions``, a block of the vocabulary's rows at a time
    (the whole table in float32 is 1.6 GB beside a served model)."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    h = _round(_store(_rms_norm(x[positions], lnf_g, z["eps"]), outer), outer)
    blocks = TABLE_BLOCKS if head_t.shape[0] % TABLE_BLOCKS == 0 else 1
    out = jax.lax.map(
        lambda w: jnp.matmul(h, _round(_f32(w), outer).T, precision=HIGHEST),
        head_t.reshape(blocks, -1, head_t.shape[1]))
    return _store(jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1), outer)


def _attend(x, w, sequences, **kw):
    """The attention sublayer on a stream of ``sequences`` equal parts, each
    a sequence that attends alone."""
    if sequences == 1:
        return _attention_jit(x, w, **kw)
    return jnp.concatenate([_attention_jit(part, w, **kw)
                            for part in jnp.split(x, sequences)])


def _layer(z, key, layer, x, precision, bias=None, balance=None,
           sequences=1):
    """One layer on the stream ``x [S, h]`` (``sequences`` of them end to
    end).  ``bias``: the layer's balanced selection bias; ``balance``: a
    function ``(stream, weights) -> bias`` run in its place."""
    kw = dict(sizes=_static(z), precision=precision)
    w = layer_weights(z, key, layer, _tensor_alone, bias)
    x = _attend(x, w, sequences,
                sliding=z["kinds"][layer] == "sliding_attention", **kw)
    if balance is not None and layer >= z["dense_layers"]:
        w["select_bias"] = balance(x, w)
    return _ffn_jit(key, x, w, jnp.int32(layer), **kw)


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
    scores = _scores(_rms_norm(x, w["ln_pre_ffn"], z["eps"]), w, "float32")
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
    """``[expert layers, experts]`` bfloat16: the selection biases as
    aux-loss-free training leaves them — every expert chosen equally often
    (``families/longcat.py::balanced_biases`` has the why: which experts a
    decode step leaves untouched, weights unread, would move the cell's
    speed from seed to seed).  The float32 reference runs
    ``BALANCE_SEQUENCES`` sequences of ``BALANCE_LENGTH`` drawn ids, each
    attending alone, layer by layer, and each expert layer's bias is
    balanced on the stream the balanced layers before it hand on.  Kept a
    few seeds long: the program's tree and the reference read the same
    rows."""
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
    return _embed_jit(embed, tokens, sizes=_static(z), precision=precision)


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


def _forward(z, key, tokens, positions, precision):
    """Logits ``[R, V]`` at ``positions [R]`` of one sequence ``tokens
    [S]`` (``S`` a multiple of 64)."""
    biases = balanced_biases(z, key)
    x = _embedded(z, key, tokens, precision)
    for layer in range(z["layers"]):
        at = layer - z["dense_layers"]
        x = _layer(z, key, layer, x, precision,
                   biases[at] if at >= 0 else None)
    g = global_weights(z, key, _table_alone, _tensor_alone,
                       only=("lnf_g", "head_t"))
    return _head_jit(g["lnf_g"], g["head_t"], x, positions, sizes=_static(z),
                     precision=precision)


def _padded(tokens, block=QUERY_BLOCK):
    """``tokens`` padded with zeros to whole ``block``s: what is behind a
    position decides nothing before it."""
    row = np.zeros(-(-len(tokens) // block) * block, np.int32)
    row[:len(tokens)] = tokens
    return jnp.asarray(row)


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
_ROWS_KEPT, _rows = 1, {}        # 0.8 GB a request at 200k ids


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
    ``pad_to`` (a cell's ``max_cache_len``, 17k here) is not padded to: the
    forward is causal, so a request is padded to whole ``PAD_TO`` blocks of
    its own length — nine shapes at most, and a 600-token request costs the
    reference 2,048 positions, not 17,472."""
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
        lg = _forward(z, seed_key(seed), _padded(row), at, precision)
        toks.append(int(np.argmax(np.asarray(lg[0]))))
    return np.asarray(toks, np.int32)
