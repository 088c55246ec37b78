"""The granite_hybrid family (IBM Granite 4.0-H, ``model_type:
granitemoehybrid``): weights from a seed, the adapter that hands them to the
program, and the plain reference.

**Reference.**  ``x0 = embedding_multiplier * Embed[ids]``; a layer is
pre-norm (RMSNorm: eps 1e-5, float32 gain) with ``r = residual_multiplier``::

    a = x + r * Mixer( RMSNorm_in(x) )
    y = a + r * Experts( RMSNorm_post(a) )

*Mixer*, a layer of type ``attention`` — grouped-query softmax attention with
NO positional encoding: ``q = h Wq -> [heads, d]``, ``k = h Wk``, ``v = h Wv
-> [kv heads, d]``, ``p = softmax_f32(q k^T * attention_multiplier)`` over
keys ``j <= i``, ``out = (p v) Wo``.  Every other layer — a Mamba-2
state-space layer (arXiv:2405.21060), ``H`` heads of ``P`` on a state of
``N``: ``[z | xBC | dt] = h W_in``; ``xBC <- SiLU(conv(xBC) + b_conv)`` with
``conv`` a causal depthwise convolution of ``mamba_d_conv`` taps a channel
over ALL of ``xBC`` (zeros before position 0); ``x [H, P]``, ``B [N]``, ``C
[N]`` (one group: every head's); ``dt = softplus(dt + dt_bias)`` a head (no
clamp), ``A = -exp(A_log)``; a float32 state a head, zero before position 0::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

``out = RMSNorm( y * SiLU(z) ; gain [H P] ) W_out`` — the gate FIRST, then
ONE norm over the whole inner width.  It is the RECURRENCE, one position
after the other by ``jax.lax.scan`` — no chunked form, no kernel: what the
program's chunk kernel computes in blocks is held to this.  *Experts* (every
layer): ``l = h Wr`` in float32 over ``num_local_experts`` outputs, the
``num_experts_per_tok`` largest (ties to the lower index), gates
``softmax(l[chosen])``, ``Shared(h) + sum over chosen experts that are HELD
of g_e Expert_e(h)``; what the absent experts would add is left out here as
in the program.  A final RMSNorm and the TIED head, over ``logits_scaling``.

Plain ``jax.numpy`` in float32 with matmul precision ``highest``; no kernel,
no cache, no batching; ONE sequence, softmax attention in blocks of 64
queries against all keys, and a jitted program a SUBLAYER with that
sublayer's weights drawn when it runs and dropped after it (the served
model's 5.9 GB and, in a calibration, its 7.7 GB of pools sit beside the
reference on the chip).  The rounding rules, the matmul, the norm, the
SwiGLU and the tensor draw are ``families/dots3.py``'s own functions, the
vocabulary table's draw in blocks of rows and the padding
``families/trinity.py``'s, imported.

**The weights' draw** (normal, from ``--seed``, rounded to bfloat16; std
0.02 but where said; norm gains 1 +- 0.1).  What must DECIDE the output is
the state-space layers' state and what steers it — step size, decay, skip,
gate and norm — so each is drawn where it works:

* the TIED table at std 0.25: the head's logits of a unit-RMS row are
  ``sqrt(4096) * 0.25 / 16`` = 1.0 apart over the slice — order 1 after the
  three multipliers — but a tied head reads the row's OWN embedding back:
  the input token's logit lies ``768 * 0.25 / |x_L|`` of that spread above
  the rest, so the sublayers must carry the stream far from ``x0`` (RMS 3):
* ``W_out`` of the state-space mixers at std 1.2 (a normed, gated ``y``,
  8,192 wide: ~108 a feature, 24 after ``x 0.22``), the attention layer's
  ``Wo`` at 0.8, the shared MLP's down-projection at 1.0 and the routed
  experts' at 2.0 (a held quarter of ten picks): after ten layers the stream
  is ~80 a feature, nine tenths of it the state-space layers' — the input
  token's pull is ~2.4 spreads, one candidate among the largest of 25,088;
* ``A_log = log U(1, 16)`` a head and ``dt_bias`` the inverse softplus of a
  LOG-uniform 0.001 .. 0.1 (the public Mamba-2 layer's initialisation):
  with the token's own part (``h W_in``'s ``dt`` columns, std 1.28, through
  the softplus) per-token decays ``exp(dt A)`` from ~0.2 to ~0.999 —
  memories of one to a thousand positions in every layer; ``D`` 1 +- 0.1;
* the convolution's taps at std 0.5 (four of them: the convolved rows keep
  the projection's scale) and its bias at 0.5, so that ``conv_bias_dropped``
  moves every channel by a third of its spread;
* the attention layer's ``Wq``, ``Wk`` at 0.085: at the config's scale
  ``1/128`` the scores have std ~2.6, so that a query over hundreds of NoPE
  keys rests on a few of them; at ``1/sqrt(128)`` they would be 11 times
  that (``attention_scale_sqrt``);
* the router at 0.03 (logits of std ~1.9: the largest of ten gates ~0.4).
  NO selection bias exists to balance the loads, and a stream whose every
  token carries the mixers' common component (``SiLU``'s mean through
  ``W_out``) would send every token to the same experts: the router's
  columns are drawn and then made ORTHOGONAL to that component — the mean
  of the normed stream at each expert layer, measured by this reference on
  ``BALANCE_SEQUENCES`` sequences of drawn ids, layer by layer
  (:func:`router_means`) — which is what a load-balancing loss leaves: a
  router that reads what tells tokens apart.

**What is assumed** is listed in the configuration file.

``precision`` selects the control: ``"float32"`` (the reference),
``"bfloat16"`` (what a sound program computes: x, B, C, z, the projections
and the stream in bfloat16; ``dt``, the decay, the state and its update in
float32), ``"float8"`` (every matmul operand rounded to e4m3 with a
per-tensor scale), and, each bfloat16 but for one thing:
``"bfloat16_state"`` (the state rounded to bfloat16 after every position),
``"state_not_cleared"`` (the state starts from what the sequence's own first
``STALE_ROWS`` rows leave — a slot's last occupant — not from zero),
``"tail_advances_state"`` (after the prompt's last row the state is advanced
over the padded tail of a ``TAIL_CHUNK``-token chunk, each pad row carrying
the last real row's inputs), ``"dt_bias_dropped"`` (``softplus(dt)``
alone), ``"skip_dropped"`` (no ``D x``), ``"norm_before_gate"``
(``RMSNorm(y) * SiLU(z)``), ``"conv_bias_dropped"``,
``"residual_multiplier_dropped"`` (plain residual adds),
``"attention_scale_sqrt"`` (``1/sqrt(d)`` for ``attention_multiplier``) and
``"float8_experts"`` (the routed and shared experts' matmuls in float8).
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

_W, _G, _EMBED = 0.02, 0.1, 0.25
_SSM_OUT, _ATT_OUT, _SHARED_DOWN, _DOWN = 1.2, 0.8, 1.0, 2.0
_QK, _TAPS, _CONV_BIAS, _ROUTER = 0.085, 0.5, 0.5, 0.03
_STEP = (1e-3, 1e-1)     # softplus(dt_bias): a head's step size
_A = (1.0, 16.0)         # exp(A_log)
GAP_ROWS = 768           # the longest answer a cell may ask for
TAIL_CHUNK = 256         # the chunk whose padded tail ``tail_advances_state``
STALE_ROWS = 512         # ... and the rows ``state_not_cleared`` inherits
CONTROLS = ("bfloat16_state", "state_not_cleared", "tail_advances_state",
            "dt_bias_dropped", "skip_dropped", "norm_before_gate",
            "conv_bias_dropped", "residual_multiplier_dropped",
            "attention_scale_sqrt", "float8_experts")


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    if model.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if model.get("position_embedding_type", "nope") != "nope" \
            or model.get("mamba_n_groups", 1) != 1 \
            or model.get("mamba_proj_bias") or model.get("attention_bias") \
            or not model.get("mamba_conv_bias", True) \
            or not model.get("tie_word_embeddings", True) \
            or model.get("hidden_act", "silu") != "silu" \
            or model.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError("this reference is granitemoehybrid as released: "
                         "NoPE attention, one B / C group, a convolution "
                         "bias and no other, RMSNorm, SiLU, a tied head")
    layers = model["num_hidden_layers"]
    kinds = tuple(model["layer_types"])[:layers]
    if len(kinds) != layers or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {kinds!r}")
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    if heads % kv or model["hidden_size"] % heads:
        raise ValueError("KV heads divide the heads, the heads the width")
    if model["mamba_n_heads"] * model["mamba_d_head"] \
            != model["mamba_expand"] * model["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x "
                         "hidden_size")
    published = model.get("num_local_experts_published",
                          model["num_local_experts"])
    held = tuple(model.get("held_experts", (0, model["num_local_experts"])))
    if held[1] != model["num_local_experts"]:
        raise ValueError("num_local_experts counts the experts held here")
    return dict(
        h=model["hidden_size"], heads=heads, kv_heads=kv,
        d=model["hidden_size"] // heads, ssm_heads=model["mamba_n_heads"],
        ssm_d=model["mamba_d_head"], ssm_n=model["mamba_d_state"],
        taps=model["mamba_d_conv"], layers=layers,
        # the pool's layers, as the benchmark's readers count them
        kinds=tuple("full_attention" if k == "attention" else "state_space"
                    for k in kinds),
        # the expert width under both names the benchmark's readers use
        f=model["intermediate_size"], ef=model["intermediate_size"],
        sf=model["shared_intermediate_size"], experts=published, held=held,
        top_k=model["num_experts_per_tok"], vocab=model["vocab_size"],
        eps=float(model["rms_norm_eps"]),
        embed_x=float(model["embedding_multiplier"]),
        residual_x=float(model["residual_multiplier"]),
        logits_over=float(model["logits_scaling"]),
        scale=float(model["attention_multiplier"]),
        positions=model["max_position_embeddings"])


def parameters_by_part(z):
    """Parameters counted from the shapes, by part (the tied table once)."""
    h, hd, kvd = z["h"], z["heads"] * z["d"], z["kv_heads"] * z["d"]
    w = z["ssm_heads"] * z["ssm_d"]
    cw = w + 2 * z["ssm_n"]
    expert = 3 * h * z["ef"]
    ssm = z["kinds"].count("state_space")
    parts = {
        "gqa_mixer_each": 2 * h * hd + 2 * h * kvd,
        "mamba_mixer_each": h * (w + cw + z["ssm_heads"]) + w * h
        + (z["taps"] + 1) * cw + 3 * z["ssm_heads"] + w,
        "one_expert": expert, "router_each": h * z["experts"],
        "shared_mlp_each": 3 * h * z["sf"],
        "held_experts_each": z["held"][1] * expert,
        "embedding_tied_head": z["vocab"] * h}
    parts["expert_layer_ffn_each"] = parts["router_each"] \
        + parts["shared_mlp_each"] + parts["held_experts_each"]
    parts["norm_gains"] = z["layers"] * 2 * h + h
    parts["all"] = (z["layers"] - ssm) * parts["gqa_mixer_each"] \
        + ssm * parts["mamba_mixer_each"] \
        + z["layers"] * parts["expert_layer_ffn_each"] \
        + parts["embedding_tied_head"] + parts["norm_gains"]
    return parts


# --------------------------------------------------------------------- #
# The draw
# --------------------------------------------------------------------- #
def _layer_kinds(z, layer):
    """``(name, shape, std, mean)``; std None: a draw of its own
    (:func:`_uniform_log`)."""
    h = z["h"]
    kinds = [("ln_in", (h,), _G, 1.0), ("ln_post", (h,), _G, 1.0),
             ("router", (h, z["experts"]), _ROUTER, 0.0),
             ("shared_gate", (h, z["sf"]), _W, 0.0),
             ("shared_up", (h, z["sf"]), _W, 0.0),
             ("shared_down", (z["sf"], h), _SHARED_DOWN, 0.0)]
    if z["kinds"][layer] == "full_attention":
        hd, kvd = z["heads"] * z["d"], z["kv_heads"] * z["d"]
        return kinds + [("wq", (h, hd), _QK, 0.0), ("wk", (h, kvd), _QK, 0.0),
                        ("wv", (h, kvd), _W, 0.0),
                        ("wo", (hd, h), _ATT_OUT, 0.0)]
    H = z["ssm_heads"]
    w = H * z["ssm_d"]
    cw = w + 2 * z["ssm_n"]
    return kinds + [("w_in", (h, w + cw + H), _W, 0.0),
                    ("taps", (z["taps"], cw), _TAPS, 0.0),
                    ("conv_bias", (cw,), _CONV_BIAS, 0.0),
                    ("dt_bias", (H,), None, _STEP),
                    ("a_log", (H,), None, _A),
                    ("skip", (H,), _G, 1.0), ("norm", (w,), _G, 1.0),
                    ("w_out", (w, h), _SSM_OUT, 0.0)]


def _uniform_log(key, index, layer, shape, between, inverse_softplus):
    """A value drawn LOG-uniformly ``between`` two bounds, as its logarithm
    (``A_log``) or as the inverse softplus of it (``dt_bias``)."""
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    lo, hi = np.log(between[0]), np.log(between[1])
    if not inverse_softplus:
        # A itself uniform over the bounds (the public layer's draw)
        drawn = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                           between[0], between[1]))
    else:
        step = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        drawn = step + jnp.log(-jnp.expm1(-step))
    return drawn.astype(jnp.bfloat16)


_uniform_log_alone = jax.jit(_uniform_log, static_argnums=(3, 4, 5))


def layer_weights(z, key, layer, draw=_tensor, centre=None):
    """Layer ``layer``'s tensors but its routed experts'; ``centre [h]`` (the
    layer's row of :func:`router_means`): the direction the router's columns
    are made orthogonal to."""
    uniform = _uniform_log if draw is _tensor else _uniform_log_alone
    w = {name: uniform(key, 100 + i, layer, shape, mean, name == "dt_bias")
         if std is None else draw(key, 100 + i, layer, shape, std, mean)
         for i, (name, shape, std, mean) in enumerate(_layer_kinds(z, layer))}
    if centre is not None:
        r, c = _f32(w["router"]), _f32(centre)
        w["router"] = (r - c[:, None] * (c @ r)[None, :]) \
            .astype(jnp.bfloat16)
    return w


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
                   only=("embed", "lnf_g")):
    """``embed [vocab, h]`` — the table AND the head — and the final norm's
    gain."""
    make = {"embed": lambda: table(key, 0, z["vocab"], z["h"], _EMBED),
            "lnf_g": lambda: draw(key, 1, 0, (z["h"],), _G, 1.0)}
    return {name: make[name]() for name in only}


# --------------------------------------------------------------------- #
# The program's side: its module, and its parameter tree from the seed
# --------------------------------------------------------------------- #
def program_model(model, **overrides):
    """The program's own module at the file's sizes, holding the file's
    share of the experts."""
    from deepspeed_tpu.models.granite_hybrid import granite_hybrid_model
    z = sizes_of(model)                  # refuses what the reference lacks
    return granite_hybrid_model(model, held_experts=z["held"],
                                **{"dtype": "bfloat16", **overrides})


_LAYER_LEAVES = {         # the program's leaf path in a layer -> the tensor
    ("input_layernorm", "scale"): "ln_in",
    ("post_attention_layernorm", "scale"): "ln_post",
    ("self_attn", "q_proj", "kernel"): "wq",
    ("self_attn", "k_proj", "kernel"): "wk",
    ("self_attn", "v_proj", "kernel"): "wv",
    ("self_attn", "o_proj", "kernel"): "wo",
    ("mamba", "in_proj", "kernel"): "w_in", ("mamba", "conv1d"): "taps",
    ("mamba", "conv1d_bias"): "conv_bias", ("mamba", "dt_bias"): "dt_bias",
    ("mamba", "A_log"): "a_log", ("mamba", "D"): "skip",
    ("mamba", "norm"): "norm", ("mamba", "out_proj", "kernel"): "w_out",
    ("moe_mlp", "gate_kernel"): "router",
    ("moe_mlp", "shared_gate", "kernel"): "shared_gate",
    ("moe_mlp", "shared_up", "kernel"): "shared_up",
    ("moe_mlp", "shared_down", "kernel"): "shared_down"}
_EXPERT_LEAVES = {"experts_wg": "wg", "experts_wi": "wu", "experts_wo": "wd"}


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves) from ``seed``, on the
    device, in one jitted call whose compiled form serves every seed.  The
    held experts are drawn one after the other; the selection bias the
    scored router form carries is zeros (the model has none)."""
    z = sizes_of(model)
    first, count = z["held"]
    abstract = jax.eval_shape(module.init, jax.random.key(0),
                              {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key, means):
        glob = global_weights(z, key)
        glob = {("embed_tokens", "embedding"): glob["embed"],
                ("norm", "scale"): glob["lnf_g"]}
        layers, leaves = {}, []

        def layer_leaf(layer, names, leaf):
            if names[-1] in _EXPERT_LEAVES:
                return jax.lax.map(
                    lambda e: expert_weights(z, key, layer, e)[
                        _EXPERT_LEAVES[names[-1]]],
                    first + jnp.arange(count))
            if names == ("moe_mlp", "select_bias"):
                return jnp.zeros(leaf.shape, jnp.bfloat16)
            if layer not in layers:
                layers[layer] = layer_weights(z, key, layer,
                                              centre=means[layer])
            return layers[layer][_LAYER_LEAVES[names]]

        for path, leaf in flat:
            names = tuple(p.key for p in path)[1:]       # drop 'params'
            x = layer_leaf(int(names[0][7:]), names[1:], leaf) \
                if names[0].startswith("layers_") else glob[names]
            leaves.append(x.reshape(leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = seed_key(seed)
    return build(key, router_means(z, key))


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #
def _parts(precision):
    """``precision`` -> what each part computes in, and what it computes."""
    sound = dict(outer="bfloat16", experts="bfloat16", state="float32",
                 stale=False, tail=False, dt_bias=True, skip=True,
                 gate_first=True, conv_bias=True, residual=True,
                 sqrt_scale=False)
    other = {"bfloat16_state": dict(state="bfloat16"),
             "state_not_cleared": dict(stale=True),
             "tail_advances_state": dict(tail=True),
             "dt_bias_dropped": dict(dt_bias=False),
             "skip_dropped": dict(skip=False),
             "norm_before_gate": dict(gate_first=False),
             "conv_bias_dropped": dict(conv_bias=False),
             "residual_multiplier_dropped": dict(residual=False),
             "attention_scale_sqrt": dict(sqrt_scale=True),
             "float8_experts": dict(experts="float8")}
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
    keys = jnp.arange(S)[None, :]
    scale = 1.0 / np.sqrt(D) if p["sqrt_scale"] else z["scale"]

    def block(start):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, QUERY_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", cut(q), k, precision=HIGHEST)
        seen = keys <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None, None], s * scale, -1e30),
                              axis=-1)
        out = jnp.einsum("kgqs,skd->qkgd", r(_store(prob, outer)), v,
                         precision=HIGHEST)
        return _mm(_store(out, outer).reshape(QUERY_BLOCK, H * D), w["wo"],
                   outer)

    return jax.lax.map(block, jnp.arange(0, S, QUERY_BLOCK)).reshape(S, -1)


def _ssm_step(state, S, row):
    """One position of every head: ``S [H, P, N]``, ``row`` = ``(x [H, P],
    dt [H], a [H], B [N], C [N])``.  ``state``: the precision the state is
    kept in.  Returns ``(S, S C)``."""
    x_t, dt_t, a_t, b_t, c_t = row
    S = jnp.exp(a_t)[:, None, None] * S \
        + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
    S = _store(S, state)
    return S, jnp.einsum("hpn,n->hp", S, c_t, precision=HIGHEST)


def _mamba_mixer(z, x, w, precision, prompt_len, real=None):
    """The state-space mixer on ONE sequence ``x [S, h]`` (normed input);
    ``prompt_len`` (traced) is where ``tail_advances_state`` puts its tail.
    Returns ``(y [S, h], the state after row real - 1)`` — ``real`` (static;
    default ``S``): rows from it on are padding, scanned by nobody.  The
    RECURRENCE, one position after the other."""
    p = _parts(precision)
    outer = p["outer"]
    S, H, P, N, K = x.shape[0], z["ssm_heads"], z["ssm_d"], z["ssm_n"], \
        z["taps"]
    W = H * P
    gate, xbc, dt = jnp.split(_mm(x, w["w_in"], outer), [W, 2 * W + 2 * N],
                              axis=-1)
    # the causal taps reach K - 1 rows back: zeros before position 0
    wide = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(wide[j:j + S] * _f32(w["taps"][j]) for j in range(K))
    if p["conv_bias"]:
        conv = conv + _f32(w["conv_bias"])
    xbc = _round(_store(jax.nn.silu(conv), outer), outer)
    xs, b, c = jnp.split(xbc, [W, W + N], axis=-1)
    xs = xs.reshape(S, H, P)
    step = jax.nn.softplus(dt + (_f32(w["dt_bias"]) if p["dt_bias"] else 0.0))
    rows = (xs, step, -jnp.exp(_f32(w["a_log"])) * step, b, c)

    def scan_all(state, limit):
        """Every position in turn from ``state``; positions from ``limit``
        on leave it alone.  ``(S C [S, H, P], state)``."""
        def at(S_t, t):
            row = tuple(r[t] for r in rows)
            S_new, y = _ssm_step(p["state"], S_t, row)
            if p["tail"]:
                # the chunk's pad rows after the prompt's last: that row's
                # inputs again, their outputs nobody's
                pads = jnp.where(t == prompt_len - 1,
                                 (-prompt_len) % TAIL_CHUNK, 0)
                S_new = jax.lax.fori_loop(
                    0, pads, lambda _, s: _ssm_step(p["state"], s, row)[0],
                    S_new)
            return jnp.where(t < limit, S_new, S_t), y

        state, y = jax.lax.scan(at, state, jnp.arange(S))
        return y, state

    state = jnp.zeros((H, P, N), jnp.float32)
    if p["stale"]:
        _, state = scan_all(state, min(S, STALE_ROWS))
    y, state = scan_all(state, real or S)
    if p["skip"]:
        y = y + _f32(w["skip"])[:, None] * xs
    y = _round(_store(y, outer), outer).reshape(S, W)
    silu = jax.nn.silu(gate)
    if p["gate_first"]:
        y = _store(_rms_norm(_round(_store(y * silu, outer), outer),
                             w["norm"], z["eps"]), outer)
    else:
        y = _store(_store(_rms_norm(y, w["norm"], z["eps"]), outer) * silu,
                   outer)
    return _mm(y, w["w_out"], outer), state


def _router_logits(h, w, outer):
    """The router's logits ``[S, experts]`` of ``h [S, h]``: float32, kept."""
    return jnp.matmul(_round(h, outer), _round(_f32(w["router"]), outer),
                      precision=HIGHEST)


def expert_layer(z, key, layer, h, w, precision, held=None, shared=True):
    """The expert layer on ``h [S, h]``: the experts ``held`` (default the
    configuration's share; ``(0, experts)`` is the uncut layer) each
    computed over every token and masked by the token's choice, plus —
    ``shared`` — the shared MLP.  Nothing held is dropped."""
    p = _parts(precision)
    first, count = held or z["held"]
    top_l, top_i = jax.lax.top_k(_router_logits(h, w, p["outer"]), z["top_k"])
    top_w = jax.nn.softmax(top_l, axis=-1)

    def one(acc, e):
        ew = expert_weights(z, key, layer, e)
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(h, ew["wg"], ew["wu"],
                                               ew["wd"], p["experts"]), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), first + jnp.arange(count))
    if shared:
        acc = acc + _swiglu(h, w["shared_gate"], w["shared_up"],
                            w["shared_down"], p["experts"])
    return _store(acc, p["outer"])


# A sublayer is one jitted program, and so are the embedding, the head and
# each tensor's draw: the caller draws a layer's weights, runs it, and drops
# them before the next.  A control changes ONE thing, so every sublayer it
# does not reach runs (and is compiled) as bfloat16's: what each reads
_READ_BY = {"softmax": ("attention_scale_sqrt",
                        "residual_multiplier_dropped"),
            "ssm": tuple(c for c in CONTROLS
                         if c not in ("float8_experts",
                                      "attention_scale_sqrt")),
            "ffn": ("float8_experts", "residual_multiplier_dropped"),
            "ends": ()}


def _seen_by(sublayer, precision):
    """``precision`` as ``sublayer`` computes it."""
    return "bfloat16" if precision in CONTROLS \
        and precision not in _READ_BY[sublayer] else precision


def _residual(z, precision):
    return z["residual_x"] if _parts(precision)["residual"] else 1.0


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "softmax",
                                             "real"))
def _mixer_jit(x, w, prompt_len, *, sizes, precision, softmax, real=None):
    """``(the stream after the mixer, a state-space layer's state or
    None)``."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, w["ln_in"], z["eps"]), outer)
    a, state = (_softmax_mixer(z, normed, w, precision), None) if softmax \
        else _mamba_mixer(z, normed, w, precision, prompt_len, real)
    return _store(x + _residual(z, precision) * a, outer), state


@functools.partial(jax.jit, static_argnames=("sizes",))
def _ffn_input_mean(x, w, *, sizes):
    """The unit vector along the mean of the expert layer's normed input."""
    z = dict(sizes)
    mean = jnp.mean(_rms_norm(x, w["ln_post"], z["eps"]), axis=0)
    return mean * jax.lax.rsqrt(jnp.sum(mean * mean) + 1e-30)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _ffn_jit(key, x, w, layer, *, sizes, precision):
    """``layer`` is traced (it keys the experts' draw): the expert layers of
    one length share one compiled program."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, w["ln_post"], z["eps"]), outer)
    return _store(x + _residual(z, precision)
                  * expert_layer(z, key, layer, normed, w, precision), outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _embed_jit(embed, tokens, *, sizes, precision):
    return _store(dict(sizes)["embed_x"] * _f32(embed[tokens]),
                  _parts(precision)["outer"])


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _head_jit(lnf_g, embed, x, positions, *, sizes, precision):
    """Logits at ``positions``, a block of the tied table's rows at a
    time."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    h = _round(_store(_rms_norm(x[positions], lnf_g, z["eps"]), outer), outer)
    blocks = TABLE_BLOCKS if embed.shape[0] % TABLE_BLOCKS == 0 else 1
    out = jax.lax.map(
        lambda w: jnp.matmul(h, _round(_f32(w), outer).T, precision=HIGHEST),
        embed.reshape(blocks, -1, embed.shape[1]))
    out = _store(jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1), outer)
    return _store(out / z["logits_over"], outer)


def _mix(x, w, sequences, prompt_len, **kw):
    """The mixer sublayer on a stream of ``sequences`` equal parts, each a
    sequence of its own."""
    at = jnp.asarray(x.shape[0] // sequences if prompt_len is None
                     else prompt_len, jnp.int32)
    if sequences == 1:
        return _mixer_jit(x, w, at, **kw)
    return jnp.concatenate([_mixer_jit(part, w, at, **kw)[0]
                            for part in jnp.split(x, sequences)]), None


def _layer(z, key, layer, x, precision, centre=None, measure=None,
           sequences=1, prompt_len=None, states=None, real=None):
    """One layer on the stream ``x [S, h]`` (``sequences`` of them end to
    end).  ``centre``: the layer's row of :func:`router_means`; ``measure``:
    a function ``(stream, weights) -> centre`` run in its place; ``states``:
    a list that a state-space layer's state after row ``real - 1`` is added
    to."""
    softmax = z["kinds"][layer] == "full_attention"
    w = layer_weights(z, key, layer, _tensor_alone,
                      centre if measure is None else None)
    x, state = _mix(
        x, w, sequences, prompt_len, softmax=softmax, sizes=_static(z),
        precision=_seen_by("softmax" if softmax else "ssm", precision),
        **({} if real is None else {"real": real}))
    if states is not None and state is not None:
        states.append(state)
    if measure is not None:
        w = layer_weights(z, key, layer, _tensor_alone, measure(x, w))
    return _ffn_jit(key, x, w, jnp.int32(layer), sizes=_static(z),
                    precision=_seen_by("ffn", precision))


# --------------------------------------------------------------------- #
# The router's columns, orthogonal to the stream's common component
# --------------------------------------------------------------------- #
BALANCE_SEQUENCES, BALANCE_LENGTH = 8, 512
_MEANS_KEPT, _means = 4, {}


def router_means(z, key):
    """``[layers, h]`` float32 unit vectors: the mean of each expert layer's
    normed input over ``BALANCE_SEQUENCES`` sequences of ``BALANCE_LENGTH``
    drawn ids, each a sequence of its own, read by the float32 reference
    layer by layer — each layer's on the stream the layers before it, their
    routers already centred, hand on.  Kept a few seeds long: the program's
    tree and the reference read the same rows."""
    at = (_static(z), np.asarray(jax.random.key_data(key)).tobytes())
    if at not in _means:
        while len(_means) >= _MEANS_KEPT:
            del _means[next(iter(_means))]
        _means[at] = _measured(z, key)
    return _means[at]


def balance_ids(z, key):
    return jax.random.randint(jax.random.fold_in(key, 91),
                              (BALANCE_SEQUENCES, BALANCE_LENGTH), 0,
                              z["vocab"])


def _embedded(z, key, tokens, precision):
    """The stream's start; the table is drawn for it and dropped."""
    embed = global_weights(z, key, _table_alone, _tensor_alone,
                           only=("embed",))["embed"]
    return _embed_jit(embed, tokens, sizes=_static(z), precision=precision)


def _measured(z, key):
    x = _embedded(z, key, balance_ids(z, key).reshape(-1), "float32")
    rows = []

    def measure(stream, w):
        rows.append(_ffn_input_mean(stream, w, sizes=_static(z)))
        return rows[-1]

    for layer in range(z["layers"]):
        x = _layer(z, key, layer, x, "float32", measure=measure,
                   sequences=BALANCE_SEQUENCES)
    return jnp.stack(rows)


def _forward(z, key, tokens, positions, precision, prompt_len=None):
    """Logits ``[R, V]`` at ``positions [R]`` of one sequence ``tokens
    [S]`` (``S`` a multiple of 64); ``prompt_len``: where the request's
    prompt ends (the controls of the serving path read it)."""
    means = router_means(z, key)
    x = _embedded(z, key, tokens, _seen_by("ends", precision))
    for layer in range(z["layers"]):
        x = _layer(z, key, layer, x, precision, means[layer],
                   prompt_len=prompt_len)
    g = global_weights(z, key, _table_alone, _tensor_alone)
    return _head_jit(g["lnf_g"], g["embed"], x, positions, sizes=_static(z),
                     precision=_seen_by("ends", precision))


def ssm_states(z, seed, tokens, precision="float32"):
    """``[state-space layers, H, P, N]``: every state-space layer's state
    after the LAST of ``tokens`` — what a slot's state row holds when the
    program has run exactly these positions."""
    key, states = seed_key(seed), []
    means = router_means(z, key)
    x = _embedded(z, key, _padded(tokens), precision)
    for layer in range(z["layers"]):
        x = _layer(z, key, layer, x, precision, means[layer], states=states,
                   real=len(tokens))
    return jnp.stack(states)


def logits(z, seed, tokens, precision="float32", prompt_len=None):
    """All logits ``[S, V]`` of ONE sequence ``tokens [S]`` — what the CPU
    tests compare the program with."""
    return _forward(z, seed_key(seed), _padded(tokens),
                    jnp.arange(len(tokens)), precision, prompt_len)


# the float32 rows of the last requests compared (a calibration reads the
# same requests again under each control)
_ROWS_KEPT, _rows = 4, {}        # 77 MB a request at 25,088 ids


def _reference_rows(z, seed, tokens, positions):
    at = (_static(z), int(seed), int(positions[0]),
          np.asarray(tokens).tobytes())
    if at not in _rows:
        while len(_rows) >= _ROWS_KEPT:
            del _rows[next(iter(_rows))]
        _rows[at] = _forward(z, seed_key(seed), tokens, positions, "float32")
    return _rows[at]


PAD_TO = 512             # a compared sequence is padded to whole such blocks


def gaps_under(z, seed, tokens, prompt_len, n_new, pad_to, choosers):
    """``{chooser: gaps [n_new]}`` for each of ``choosers`` (``None``: the
    served tokens), the float32 reference computed ONCE for all of them.
    ``pad_to`` (a cell's ``max_cache_len``) is not padded to: the forward is
    causal, so a request is padded to whole ``PAD_TO`` blocks of its own
    length — six shapes at most.  A chooser whose logits are not finite
    reads as an infinite gap."""
    if n_new > GAP_ROWS:
        raise ValueError(f"answers of at most {GAP_ROWS} tokens")
    key, tokens = seed_key(seed), _padded(
        tokens, PAD_TO if len(tokens) > QUERY_BLOCK * 2 else QUERY_BLOCK)
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
