"""The nemotron_h family (NVIDIA Nemotron-H, ``model_type: nemotron_h``:
Nemotron 3 Nano 30B-A3B): weights from a seed, the adapter that hands them to
the program, and the plain reference.

**Reference.**  ``x0 = Embed[ids]``; block ``i`` is ONE sublayer by character
``i`` of ``hybrid_override_pattern`` (RMSNorm: eps 1e-5, float32 gain; no
multiplier)::

    x <- x + Mixer_i( RMSNorm_i(x) )

``M`` — a Mamba-2 state-space layer (arXiv:2405.21060), ``H`` heads of ``P``
on a state of ``N`` in ``G`` groups: ``[z | xBC | dt] = h W_in`` (no bias);
``xBC <- SiLU(conv(xBC) + b_conv)``, ``conv`` a causal depthwise convolution
of ``conv_kernel`` taps a channel over ALL of ``xBC`` (zeros before position
0); ``x [H, P]``, ``B [G, N]``, ``C [G, N]``, head ``h`` reading group ``h //
(H / G)``; ``dt = softplus(dt + dt_bias)`` a head (no clamp), ``A =
-exp(A_log)``; a float32 state a head, zero before position 0::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_{g(h),t}
    y_t = S_t C_{g(h),t} + D x_t

``out = GroupNorm_G( y * SiLU(z) ; gain [H P] ) W_out`` — the gate FIRST,
then an RMS norm over each GROUP's ``H P / G`` channels separately.  It is
the RECURRENCE, one position after the other by ``jax.lax.scan`` — no
chunked form, no kernel.  ``*`` — grouped-query softmax attention with NO
positional encoding: ``q = h Wq -> [heads, d]``, ``k = h Wk``, ``v = h Wv ->
[kv heads, d]``, ``p = softmax_f32(q k^T / sqrt(d))`` over keys ``j <= i``,
``out = (p v) Wo``.  ``E`` — the expert layer alone: ``s = sigmoid(h Wr)`` in
float32 over ``n_routed_experts`` outputs, the ``num_experts_per_tok``
largest of ``s + b`` (``b`` the stored selection bias; ties to the lower
index), ``w = routed_scaling_factor * s[chosen] / (sum + 1e-20)`` — from the
SCORES —, ``Shared(h) + sum over chosen experts that are HELD of w_e
Expert_e(h)`` with ``Expert(h) = relu(h U)^2 D``: TWO matrices, no gate;
what the absent experts would add is left out here as in the program.  A
final RMSNorm and an UNTIED head.

Plain ``jax.numpy`` in float32 with matmul precision ``highest``; no kernel,
no cache, no batching; ONE sequence, softmax attention in blocks of 64
queries against all keys, and a jitted program a BLOCK with that block's
weights drawn when it runs and dropped after it (the served model's 9.2 GB
sit beside the reference on the chip).  The rounding rules, the matmul, the
norm and the tensor draw are ``families/dots3.py``'s own functions, the
vocabulary tables' draw in blocks of rows and the padding
``families/trinity.py``'s, the log-uniform draw ``families/
granite_hybrid.py``'s, imported.

**The weights' draw** (normal, from ``--seed``, rounded to bfloat16; std
0.02 but where said; norm gains and ``D`` 1 +- 0.1).  A block adds its
sublayer's output to the stream as it is — no multiplier, no post-norm — so
the matrices' scales decide what each block weighs, and at 0.02 each weighs
about what the stream's start does:

* the embedding at std 1.0 and the untied head at 0.02: logits of a normed
  row ``sqrt(2688) * 0.02`` = 1.04 apart;
* a Mamba block's ``W_out`` at 0.02 on a group-normed, gated ``y`` 4,096
  wide: ~1.3 a feature; ``A_log = log U(1, 16)`` a head and ``dt_bias`` the
  inverse softplus of a LOG-uniform 0.001 .. 0.1 (the public layer's
  initialisation, ``time_step_min`` / ``time_step_max`` of the config):
  per-token decays from ~0.2 to ~0.999, memories of one to a thousand
  positions in every block; the taps at 0.5 (four of them: the convolved
  rows keep the projection's scale) and their bias at 0.5;
* an attention block's ``Wq``, ``Wk`` at 0.03: scores of std ~2.4 at ``1 /
  sqrt(128)``, so that a query over thousands of NoPE keys rests on a few
  tens of them; ``Wv``, ``Wo`` at 0.02 (~1 a feature);
* an expert's ``U`` and ``D`` at 0.02: ``relu(N(0, 1.04))^2`` has RMS 1.3, an
  expert's output ~0.75 a feature, the held ~3 of 6 chosen at gates ~0.42
  ~0.55 together beside the shared expert's ~1.6 (twice the width);
* the router at 0.02 (logits of std ~1: scores 0.27 .. 0.73, the six chosen
  about equal), the selection bias drawn at 0.02 and then BALANCED over the
  128 outputs on ``BALANCE_SEQUENCES`` sequences of ``BALANCE_LENGTH`` drawn
  ids (``families/longcat.py``'s construction and reason: which of the held
  experts a decode step leaves untouched — weights unread — would move the
  cell's speed from seed to seed), so that the 64 held experts see ~9 rows
  each a step at 192 lanes and about half of the choices fall elsewhere.

**Departures from the published description**: none known.  What the config
does not settle is listed under ``assumed`` in the configuration file.

``precision`` selects the control: ``"float32"`` (the reference),
``"bfloat16"`` (what a sound program computes: x, B, C, z, the projections
and the stream in bfloat16; ``dt``, the decay, the state and its update, the
router's scores in float32), ``"float8"`` (every matmul operand rounded to
e4m3 with a per-tensor scale), and, each bfloat16 but for one thing:
``"bfloat16_state"`` (the state rounded to bfloat16 after every position),
``"state_not_cleared"`` (the state starts from what the sequence's own first
``STALE_ROWS`` rows leave — a slot's last occupant — not from zero),
``"tail_advances_state"`` (after the prompt's last row the state is advanced
over the padded tail of a ``TAIL_CHUNK``-token chunk, each pad row carrying
the last real row's inputs), ``"relu_not_squared"`` (``relu`` for ``relu^2``
in the routed and shared experts), ``"gate_from_biased_scores"`` (gates from
``s + b``), ``"scaling_dropped"`` (no ``routed_scaling_factor``),
``"shared_dropped"`` (no shared expert), ``"one_group_bc"`` (group 0's ``B``
and ``C`` for every head), ``"norm_whole_width"`` (ONE mean of squares over
the whole inner width), ``"rope_on_attention"`` (rotary positions, theta
``rope_theta``, on q and k) and ``"float8_experts"`` (the routed and shared
experts' matmuls in float8).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.dots3 import (HIGHEST, QUERY_BLOCK, _f32, _mm,
                                      _rms_norm, _rope, _round, _static,
                                      _store, _tensor, _tensor_alone,
                                      seed_key)
from benchmark.families.granite_hybrid import (_uniform_log,
                                               _uniform_log_alone)
from benchmark.families.trinity import (TABLE_BLOCKS, _padded, _table,
                                        _table_alone)

_W, _G, _EMBED, _QK, _TAPS, _CONV_BIAS, _BIAS = \
    0.02, 0.1, 1.0, 0.03, 0.5, 0.5, 0.02
_STEP = (1e-3, 1e-1)     # softplus(dt_bias): a head's step size
_A = (1.0, 16.0)         # exp(A_log)
GATE_SUM_EPS = 1e-20
GAP_ROWS = 1536          # the longest answer a cell may ask for
PAD_TO = 512             # a compared sequence is padded to whole such blocks
TAIL_CHUNK = 1024        # the chunk whose padded tail ``tail_advances_state``
STALE_ROWS = 512         # ... and the rows ``state_not_cleared`` inherits
CONTROLS = ("bfloat16_state", "state_not_cleared", "tail_advances_state",
            "relu_not_squared", "gate_from_biased_scores", "scaling_dropped",
            "shared_dropped", "one_group_bc", "norm_whole_width",
            "rope_on_attention", "float8_experts")
_KINDS = {"M": "state_space", "E": "experts", "*": "full_attention"}


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    layers = model["num_hidden_layers"]
    pattern = model["hybrid_override_pattern"][:layers]
    if len(pattern) != layers or set(pattern) - set(_KINDS) \
            or model.get("n_group", 1) != 1 \
            or model.get("topk_group", 1) != 1 \
            or any(model.get(k) for k in ("mamba_proj_bias", "attention_bias",
                                          "mlp_bias", "use_bias")) \
            or not model.get("use_conv_bias", True) \
            or model.get("tie_word_embeddings", False) \
            or model.get("mlp_hidden_act", "relu2") != "relu2" \
            or model.get("n_shared_experts", 1) != 1 \
            or not model.get("norm_topk_prob", True):
        raise ValueError("this reference is nemotron_h as released: blocks "
                         "M, E and *, no group-limited routing, a "
                         "convolution bias and no other, relu2 experts and "
                         "one shared, gates over their sum, an untied head")
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    ssm_heads, groups = model["mamba_num_heads"], model["n_groups"]
    if heads % kv or ssm_heads % groups:
        raise ValueError("KV heads divide the heads, the groups the Mamba "
                         "heads")
    published = model.get("n_routed_experts_published",
                          model["n_routed_experts"])
    held = tuple(model.get("held_experts", (0, model["n_routed_experts"])))
    if held[1] != model["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    return dict(
        h=model["hidden_size"], heads=heads, kv_heads=kv,
        d=model["head_dim"], ssm_heads=ssm_heads,
        ssm_d=model["mamba_head_dim"], ssm_n=model["ssm_state_size"],
        ssm_groups=groups, taps=model["conv_kernel"], layers=layers,
        pattern=pattern,
        # the pool's layers, as the benchmark's readers count them
        kinds=tuple(_KINDS[c] for c in pattern),
        # the expert width under both names the benchmark's readers use
        f=model["moe_intermediate_size"], ef=model["moe_intermediate_size"],
        sf=model["moe_shared_expert_intermediate_size"], experts=published,
        held=held, top_k=model["num_experts_per_tok"],
        scaling=float(model["routed_scaling_factor"]),
        vocab=model["vocab_size"],
        eps=float(model.get("layer_norm_epsilon", 1e-5)),
        theta=float(model.get("rope_theta", 10000)),
        positions=model["max_position_embeddings"])


def parameters_by_part(z):
    """Parameters counted from the shapes, by part — an expert at its
    PUBLISHED width, two matrices."""
    h, hd, kvd = z["h"], z["heads"] * z["d"], z["kv_heads"] * z["d"]
    w = z["ssm_heads"] * z["ssm_d"]
    cw = w + 2 * z["ssm_groups"] * z["ssm_n"]
    expert = 2 * h * z["ef"]
    count = z["pattern"].count
    parts = {
        "gqa_mixer_each": 2 * h * hd + 2 * h * kvd,
        "mamba_mixer_each": h * (w + cw + z["ssm_heads"]) + w * h
        + (z["taps"] + 1) * cw + 3 * z["ssm_heads"] + w,
        "one_expert": expert, "router_each": h * z["experts"],
        "select_bias_each": z["experts"],
        "shared_expert_each": 2 * h * z["sf"],
        "held_experts_each": z["held"][1] * expert,
        "embedding": z["vocab"] * h, "head": z["vocab"] * h}
    parts["expert_block_each"] = parts["router_each"] \
        + parts["select_bias_each"] + parts["shared_expert_each"] \
        + parts["held_experts_each"]
    parts["norm_gains"] = z["layers"] * h + h
    parts["all"] = count("*") * parts["gqa_mixer_each"] \
        + count("M") * parts["mamba_mixer_each"] \
        + count("E") * parts["expert_block_each"] \
        + parts["embedding"] + parts["head"] + parts["norm_gains"]
    return parts


# --------------------------------------------------------------------- #
# The draw
# --------------------------------------------------------------------- #
def _block_kinds(z, layer):
    """``(name, shape, std, mean)``; std None: a draw of its own
    (``families/granite_hybrid.py::_uniform_log``)."""
    h, kind = z["h"], z["pattern"][layer]
    kinds = [("ln", (h,), _G, 1.0)]
    if kind == "*":
        hd, kvd = z["heads"] * z["d"], z["kv_heads"] * z["d"]
        return kinds + [("wq", (h, hd), _QK, 0.0), ("wk", (h, kvd), _QK, 0.0),
                        ("wv", (h, kvd), _W, 0.0), ("wo", (hd, h), _W, 0.0)]
    if kind == "E":
        return kinds + [("router", (h, z["experts"]), _W, 0.0),
                        ("select_bias", (z["experts"],), _BIAS, 0.0),
                        ("shared_up", (h, z["sf"]), _W, 0.0),
                        ("shared_down", (z["sf"], h), _W, 0.0)]
    H = z["ssm_heads"]
    w = H * z["ssm_d"]
    cw = w + 2 * z["ssm_groups"] * z["ssm_n"]
    return kinds + [("w_in", (h, w + cw + H), _W, 0.0),
                    ("taps", (z["taps"], cw), _TAPS, 0.0),
                    ("conv_bias", (cw,), _CONV_BIAS, 0.0),
                    ("dt_bias", (H,), None, _STEP),
                    ("a_log", (H,), None, _A),
                    ("skip", (H,), _G, 1.0), ("norm", (w,), _G, 1.0),
                    ("w_out", (w, h), _W, 0.0)]


def block_weights(z, key, layer, draw=_tensor, bias=None):
    """Block ``layer``'s tensors but its routed experts'; ``bias``: an
    expert block's balanced selection bias in the drawn one's place."""
    uniform = _uniform_log if draw is _tensor else _uniform_log_alone
    w = {name: uniform(key, 100 + i, layer, shape, mean, name == "dt_bias")
         if std is None else draw(key, 100 + i, layer, shape, std, mean)
         for i, (name, shape, std, mean) in enumerate(_block_kinds(z, layer))}
    if bias is not None:
        w["select_bias"] = bias
    return w


def expert_weights(z, key, layer, expert):
    """The two matrices of published expert ``expert`` (traced or not) of
    block ``layer``: a pure function of ``(seed, layer, expert)``."""
    h, f = z["h"], z["ef"]
    k = jax.random.fold_in(jax.random.fold_in(key, 90), layer)
    draw = lambda i, shape: (_W * jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(k, expert), i), shape,
        jnp.float32)).astype(jnp.bfloat16)
    return {"wu": draw(1, (h, f)), "wd": draw(2, (f, h))}


def global_weights(z, key, table=_table, draw=_tensor,
                   only=("embed", "lnf_g", "head_t")):
    """``embed [vocab, h]``, the final norm's gain and the head as ``head_t
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
    from deepspeed_tpu.models.nemotron_h import nemotron_h_model
    z = sizes_of(model)                  # refuses what the reference lacks
    return nemotron_h_model(model, held_experts=z["held"],
                            **{"dtype": "bfloat16", **overrides})


# the program's leaf path in a block -> the tensor.  The checkpoint's
# ``backbone.layers.N.mixer`` is ``mamba`` / ``self_attn`` / ``moe_mlp`` by the
# block's kind (``models/nemotron_h.py``, "Names")
_BLOCK_LEAVES = {
    ("norm", "scale"): "ln",
    ("self_attn", "q_proj", "kernel"): "wq",
    ("self_attn", "k_proj", "kernel"): "wk",
    ("self_attn", "v_proj", "kernel"): "wv",
    ("self_attn", "o_proj", "kernel"): "wo",
    ("mamba", "in_proj", "kernel"): "w_in", ("mamba", "conv1d"): "taps",
    ("mamba", "conv1d_bias"): "conv_bias", ("mamba", "dt_bias"): "dt_bias",
    ("mamba", "A_log"): "a_log", ("mamba", "D"): "skip",
    ("mamba", "norm"): "norm", ("mamba", "out_proj", "kernel"): "w_out",
    ("moe_mlp", "gate_kernel"): "router",
    ("moe_mlp", "select_bias"): "select_bias",
    ("moe_mlp", "shared_up", "kernel"): "shared_up",
    ("moe_mlp", "shared_down", "kernel"): "shared_down"}
_EXPERT_LEAVES = {"experts_wi": "wu", "experts_wo": "wd"}


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves) from ``seed``, on the
    device, in one jitted call whose compiled form serves every seed.  The
    held experts are drawn one after the other."""
    z = sizes_of(model)
    first, count = z["held"]
    expert_blocks = [i for i, c in enumerate(z["pattern"]) if c == "E"]
    abstract = jax.eval_shape(module.init, jax.random.key(0),
                              {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key, biases):
        glob = global_weights(z, key)
        glob = {("embed_tokens", "embedding"): glob["embed"],
                ("norm_f", "scale"): glob["lnf_g"],
                ("lm_head", "kernel"): glob["head_t"].T}
        blocks, leaves = {}, []

        def block_leaf(layer, names, leaf):
            if names[-1] in _EXPERT_LEAVES:
                # the program stores an expert's width in whole lane tiles:
                # zero columns of U, zero rows of D (relu(0)^2 = 0)
                drawn = jax.lax.map(
                    lambda e: expert_weights(z, key, layer, e)[
                        _EXPERT_LEAVES[names[-1]]],
                    first + jnp.arange(count))
                return jnp.pad(drawn, [(0, have - got) for have, got
                                       in zip(leaf.shape, drawn.shape)])
            if layer not in blocks:
                blocks[layer] = block_weights(
                    z, key, layer,
                    bias=biases[expert_blocks.index(layer)]
                    if layer in expert_blocks else None)
            return blocks[layer][_BLOCK_LEAVES[names]]

        for path, leaf in flat:
            names = tuple(p.key for p in path)[1:]       # drop 'params'
            x = block_leaf(int(names[0][7:]), names[1:], leaf) \
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
                 stale=False, tail=False, squared=True, biased_gates=False,
                 scaling=True, shared=True, groups=True, group_norm=True,
                 rope=False)
    other = {"bfloat16_state": dict(state="bfloat16"),
             "state_not_cleared": dict(stale=True),
             "tail_advances_state": dict(tail=True),
             "relu_not_squared": dict(squared=False),
             "gate_from_biased_scores": dict(biased_gates=True),
             "scaling_dropped": dict(scaling=False),
             "shared_dropped": dict(shared=False),
             "one_group_bc": dict(groups=False),
             "norm_whole_width": dict(group_norm=False),
             "rope_on_attention": dict(rope=True),
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
    q = _mm(x, w["wq"], outer).reshape(S, H, D)
    k = _mm(x, w["wk"], outer).reshape(S, KVH, D)
    if p["rope"]:
        q, k = (_store(_rope(t, z["theta"]), outer) for t in (q, k))
    q, k = r(q).reshape(S, KVH, H // KVH, D), r(k)
    v = r(_mm(x, w["wv"], outer)).reshape(S, KVH, D)
    keys = jnp.arange(S)[None, :]

    def block(start):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, QUERY_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", cut(q), k, precision=HIGHEST)
        seen = keys <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        prob = jax.nn.softmax(
            jnp.where(seen[None, None], s / np.sqrt(D), -1e30), axis=-1)
        out = jnp.einsum("kgqs,skd->qkgd", r(_store(prob, outer)), v,
                         precision=HIGHEST)
        return _mm(_store(out, outer).reshape(QUERY_BLOCK, H * D), w["wo"],
                   outer)

    return jax.lax.map(block, jnp.arange(0, S, QUERY_BLOCK)).reshape(S, -1)


def _ssm_step(state, S, row):
    """One position of every head: ``S [H, P, N]``, ``row`` = ``(x [H, P],
    dt [H], a [H], B [H, N], C [H, N])`` — a head's group's ``B`` and ``C``.
    ``state``: the precision the state is kept in.  Returns ``(S, S C)``."""
    x_t, dt_t, a_t, b_t, c_t = row
    S = jnp.exp(a_t)[:, None, None] * S \
        + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
    S = _store(S, state)
    return S, jnp.einsum("hpn,hn->hp", S, c_t, precision=HIGHEST)


def _mamba_mixer(z, x, w, precision, prompt_len, real=None):
    """The state-space mixer on ONE sequence ``x [S, h]`` (normed input);
    ``prompt_len`` (traced) is where ``tail_advances_state`` puts its tail.
    Returns ``(y [S, h], the state after row real - 1)`` — ``real`` (static;
    default ``S``): rows from it on are padding, scanned by nobody.  The
    RECURRENCE, one position after the other."""
    p = _parts(precision)
    outer = p["outer"]
    S, H, P, N, G, K = x.shape[0], z["ssm_heads"], z["ssm_d"], z["ssm_n"], \
        z["ssm_groups"], z["taps"]
    W = H * P
    gate, xbc, dt = jnp.split(_mm(x, w["w_in"], outer),
                              [W, 2 * W + 2 * G * N], axis=-1)
    # the causal taps reach K - 1 rows back: zeros before position 0
    wide = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(wide[j:j + S] * _f32(w["taps"][j]) for j in range(K)) \
        + _f32(w["conv_bias"])
    xbc = _round(_store(jax.nn.silu(conv), outer), outer)
    xs, b, c = jnp.split(xbc, [W, W + G * N], axis=-1)
    xs = xs.reshape(S, H, P)
    # a head's group's B and C
    of = jnp.arange(H) // (H // G) if p["groups"] else jnp.zeros(H, jnp.int32)
    b, c = (t.reshape(S, G, N)[:, of] for t in (b, c))
    step = jax.nn.softplus(dt + _f32(w["dt_bias"]))
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
    y = y + _f32(w["skip"])[:, None] * xs
    y = _round(_store(y, outer), outer).reshape(S, W)
    y = _round(_store(y * jax.nn.silu(gate), outer), outer)
    if p["group_norm"]:
        y = _rms_norm(y.reshape(S, G, W // G),
                      _f32(w["norm"]).reshape(G, W // G),
                      z["eps"]).reshape(S, W)
    else:
        y = _rms_norm(y, w["norm"], z["eps"])
    return _mm(_store(y, outer), w["w_out"], outer), state


def _scores(h, w, outer):
    """The router's scores ``[S, experts]`` of ``h [S, h]``: float32
    sigmoids, kept."""
    return jax.nn.sigmoid(jnp.matmul(
        _round(h, outer), _round(_f32(w["router"]), outer),
        precision=HIGHEST))


def _relu2(a, wu, wd, precision, squared=True):
    """``relu(a U)^2 D`` — or, ``squared`` False, ``relu(a U) D``."""
    hid = jax.nn.relu(_mm(a, wu, precision))
    return _mm(_store(jnp.square(hid) if squared else hid, precision), wd,
               precision)


def expert_layer(z, key, layer, h, w, precision, held=None, shared=True):
    """The expert layer on ``h [S, h]``: the experts ``held`` (default the
    configuration's share; ``(0, experts)`` is the uncut layer) each
    computed over every token and masked by the token's choice, plus —
    ``shared`` — the shared expert.  Nothing held is dropped."""
    p = _parts(precision)
    first, count = held or z["held"]
    scores = _scores(h, w, p["outer"])
    biased = scores + _f32(w["select_bias"])
    _, top_i = jax.lax.top_k(biased, z["top_k"])
    top_w = jnp.take_along_axis(biased if p["biased_gates"] else scores,
                                top_i, axis=1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + GATE_SUM_EPS)
    if p["scaling"]:
        top_w = top_w * z["scaling"]

    def one(acc, e):
        ew = expert_weights(z, key, layer, e)
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return acc + weight[:, None] * _relu2(
            h, ew["wu"], ew["wd"], p["experts"], p["squared"]), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), first + jnp.arange(count))
    if shared and p["shared"]:
        acc = acc + _relu2(h, w["shared_up"], w["shared_down"], p["experts"],
                           p["squared"])
    return _store(acc, p["outer"])


# A block is one jitted program, and so are the embedding, the head and each
# tensor's draw: the caller draws a block's weights, runs it, and drops them
# before the next.  A control changes ONE thing, so every block it does not
# reach runs (and is compiled) as bfloat16's: what each kind reads
_READ_BY = {"*": ("rope_on_attention",),
            "M": ("bfloat16_state", "state_not_cleared",
                  "tail_advances_state", "one_group_bc", "norm_whole_width"),
            "E": ("relu_not_squared", "gate_from_biased_scores",
                  "scaling_dropped", "shared_dropped", "float8_experts"),
            "ends": ()}


def _seen_by(kind, precision):
    """``precision`` as a block of ``kind`` computes it."""
    return "bfloat16" if precision in CONTROLS \
        and precision not in _READ_BY[kind] else precision


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "kind",
                                             "real"))
def _block_jit(key, x, w, layer, prompt_len, *, sizes, precision, kind,
               real=None):
    """``(the stream after the block, a Mamba block's state or None)``;
    ``layer`` is traced (it keys the experts' draw): blocks of one kind and
    length share one compiled program."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    normed = _store(_rms_norm(x, w["ln"], z["eps"]), outer)
    state = None
    if kind == "*":
        a = _softmax_mixer(z, normed, w, precision)
    elif kind == "E":
        a = expert_layer(z, key, layer, normed, w, precision)
    else:
        a, state = _mamba_mixer(z, normed, w, precision, prompt_len, real)
    return _store(x + a, outer), state


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _embed_jit(embed, tokens, *, sizes, precision):
    return _store(_f32(embed[tokens]), _parts(precision)["outer"])


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _head_jit(lnf_g, head_t, x, positions, *, sizes, precision):
    """Logits at ``positions``, a block of the head's rows at a time."""
    z, outer = dict(sizes), _parts(precision)["outer"]
    h = _round(_store(_rms_norm(x[positions], lnf_g, z["eps"]), outer), outer)
    blocks = TABLE_BLOCKS if head_t.shape[0] % TABLE_BLOCKS == 0 else 1
    out = jax.lax.map(
        lambda w: jnp.matmul(h, _round(_f32(w), outer).T, precision=HIGHEST),
        head_t.reshape(blocks, -1, head_t.shape[1]))
    return _store(jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1), outer)


def _block(z, key, layer, x, precision, bias=None, balance=None,
           sequences=1, prompt_len=None, states=None, real=None):
    """One block on the stream ``x [S, h]`` (``sequences`` of them end to
    end, each a sequence of its own).  ``bias``: an expert block's balanced
    selection bias; ``balance``: a function ``(stream, weights) -> bias``
    run in its place; ``states``: a list that a Mamba block's state after
    row ``real - 1`` is added to."""
    kind = z["pattern"][layer]
    w = block_weights(z, key, layer, _tensor_alone, bias)
    if balance is not None and kind == "E":
        w["select_bias"] = balance(x, w)
    kw = dict(sizes=_static(z), precision=_seen_by(kind, precision),
              kind=kind, **({} if real is None else {"real": real}))
    at = jnp.asarray(x.shape[0] // sequences if prompt_len is None
                     else prompt_len, jnp.int32)
    layer = jnp.int32(layer)
    if sequences == 1 or kind == "E":        # an expert block: row by row
        x, state = _block_jit(key, x, w, layer, at, **kw)
    else:
        x, state = jnp.concatenate(
            [_block_jit(key, part, w, layer, at, **kw)[0]
             for part in jnp.split(x, sequences)]), None
    if states is not None and state is not None:
        states.append(state)
    return x


# --------------------------------------------------------------------- #
# The selection bias: the loads evened out, as training leaves them
# --------------------------------------------------------------------- #
BALANCE_SEQUENCES, BALANCE_LENGTH = 32, 512
BALANCE_STEPS, _BALANCE_RATE, _BALANCE_DECAY = 200, 0.05, 0.975


@functools.partial(jax.jit, static_argnames=("sizes",))
def _balance_jit(x, w, *, sizes):
    """``families/glm5.py::_balance_jit``: from the drawn bias, every
    expert's bias moved against its share of the ``S x top_k`` choices, in
    shrinking steps."""
    z = dict(sizes)
    scores = _scores(_rms_norm(x, w["ln"], z["eps"]), w, "float32")
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
    """``[expert blocks, experts]`` bfloat16: the selection biases as
    aux-loss-free training leaves them — every expert chosen equally often
    (``families/longcat.py::balanced_biases`` has the why).  The float32
    reference runs ``BALANCE_SEQUENCES`` sequences of ``BALANCE_LENGTH``
    drawn ids, each a sequence of its own, block by block, and each expert
    block's bias is balanced on the stream the blocks before it — their
    biases balanced, their held share alone adding — hand on.  Kept a few
    seeds long: the program's tree and the reference read the same rows."""
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
        x = _block(z, key, layer, x, "float32", balance=balance,
                   sequences=BALANCE_SEQUENCES)
    return jnp.stack(rows)


def _biases_by_block(z, key):
    """``{expert block: its balanced bias}``."""
    biases = balanced_biases(z, key)
    blocks = [i for i, c in enumerate(z["pattern"]) if c == "E"]
    return {layer: biases[n] for n, layer in enumerate(blocks)}


def _forward(z, key, tokens, positions, precision, prompt_len=None,
             states=None, real=None):
    """Logits ``[R, V]`` at ``positions [R]`` of one sequence ``tokens
    [S]`` (``S`` a multiple of 64); ``prompt_len``: where the request's
    prompt ends (the controls of the serving path read it)."""
    biases = _biases_by_block(z, key)
    x = _embedded(z, key, tokens, _seen_by("ends", precision))
    for layer in range(z["layers"]):
        x = _block(z, key, layer, x, precision, biases.get(layer),
                   prompt_len=prompt_len, states=states, real=real)
    if positions is None:
        return None
    g = global_weights(z, key, _table_alone, _tensor_alone,
                       only=("lnf_g", "head_t"))
    return _head_jit(g["lnf_g"], g["head_t"], x, positions, sizes=_static(z),
                     precision=_seen_by("ends", precision))


def ssm_states(z, seed, tokens, precision="float32"):
    """``[Mamba blocks, H, P, N]``: every Mamba block's state after the LAST
    of ``tokens`` — what a slot's state row holds when the program has run
    exactly these positions."""
    states = []
    _forward(z, seed_key(seed), _padded(tokens), None, precision,
             states=states, real=len(tokens))
    return jnp.stack(states)


def logits(z, seed, tokens, precision="float32", prompt_len=None):
    """All logits ``[S, V]`` of ONE sequence ``tokens [S]`` — what the CPU
    tests compare the program with."""
    return _forward(z, seed_key(seed), _padded(tokens),
                    jnp.arange(len(tokens)), precision, prompt_len)


def nll_at(z, seed, tokens, positions, precision="float32"):
    """Negative log-likelihood of ``tokens[p + 1]`` at each of ``positions``
    of one sequence."""
    tokens = np.asarray(tokens)
    lg = _forward(z, seed_key(seed), _padded(tokens),
                  jnp.asarray(positions), precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -np.asarray(jnp.take_along_axis(
        logp, jnp.asarray(tokens[np.asarray(positions) + 1])[:, None],
        axis=-1))[:, 0]


# the float32 rows of the last requests compared (a calibration reads the
# same requests again under each control)
_ROWS_KEPT, _rows = 2, {}        # 403 MB a request at 65,536 ids


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
    ``pad_to`` (a cell's ``max_cache_len``) is not padded to: the forward is
    causal, so a request is padded to whole ``PAD_TO`` blocks of its own
    length.  A chooser whose logits are not finite reads as an infinite
    gap."""
    if n_new > GAP_ROWS:
        raise ValueError(f"answers of at most {GAP_ROWS} tokens")
    key, tokens = seed_key(seed), _padded(
        tokens, PAD_TO if len(tokens) > QUERY_BLOCK * 2 else QUERY_BLOCK)
    rows = min(GAP_ROWS, -(-n_new // 256) * 256)
    # position p predicts token p + 1: the generated tokens sit at
    # prompt_len .. prompt_len + n_new - 1
    positions = jnp.minimum(prompt_len - 1 + jnp.arange(rows),
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
