"""The EvaByte family (EvaByte/EvaByte, ``model_type: evabyte``,
``attention_class: "eva"``): weights from a seed, the adapter that hands them
to the program, and the plain reference.

**Reference.**  The decoder as ``config.json`` sizes it, layer by layer on a
float32 residual stream ``x`` (``fp32_skip_add``); RMSNorm is ``x *
rsqrt(mean(x^2) + eps) * (1 + g)`` (``norm_add_unit_offset``), eps 1e-5::

    h = x + Attn(RMSNorm_1(x));  y = h + W_down(silu(W_gate u') * (W_up u')),
                                                          u' = RMSNorm_2(h)
    q, k, v = u W_q, u W_k, u W_v      heads of d, no bias
    rope(q, k; theta, absolute position p)  on all d features, half-split
    s = d^-1/2;  per head:  phi, mu in R^d
    chunk j = positions c j .. c j + c - 1:
        a[j, t] = softmax over the chunk's c positions t of  s * (k_t . phi)
        ksum_j = sum_t a[j, t] k_t + mu          vsum_j = sum_t a[j, t] v_t
    query at p, w = p // W:
        L(p) = {t : W w <= t <= p}       (its own window, causal, exact)
        R(p) = {j : j < (W / c) w}       (every chunk of every EARLIER window)
        o_p = (sum_L e^{s q.k_t} v_t + sum_R e^{s q.ksum_j} vsum_j)
              / (sum_L e^{s q.k_t} + sum_R e^{s q.ksum_j})
    Attn = concat_heads(o) W_o
    logits = RMSNorm_f(y) W_head,  W_head [hidden, heads x vocab], float32;
    columns 0 .. vocab - 1 (prediction head 0) are the next byte's

This is EVA (Zheng et al., ICLR 2023, "Efficient Attention via Control
Variates") with the random feature replaced by the learned ``phi`` and the
chunk key offset by the learned ``mu`` (EvaByte's ``adaptive_phi`` /
``adaptive_mu_k``).  Plain ``jax.numpy`` in float32 with matmul precision
``highest``; no kernel, no cache, no batching; ONE sequence, one jitted
program for every layer (the layer's index traced, its weights drawn inside),
attention computed WINDOW BY WINDOW (``lax.map``: a window's queries against
its own keys and every summary) so that 13k positions fit.  Weights are
regenerated from the seed alone, so the reference shares no array with the
program.

**What is assumed** — the config gives sizes, not code (the configuration
file lists the same): the pooling weights ``a`` are shared by ``ksum`` and
``vsum``; ``mu`` is added after pooling; rope comes before pooling, at
absolute positions, half-split; windows are BLOCKS (not a sliding band); a
window's summaries are visible from the NEXT window on (a query never sees a
summary of its own window); head 0 is the next byte's; the head is one
``[hidden, heads x vocab]`` matrix; bfloat16 weights.

**The weights' draw, and why** (normal, from ``--seed``, rounded to
bfloat16).  Every matrix's std is a GAIN over ``sqrt(fan-in)``, so that a
toy size has the statistics of the real one (the CPU tests read the same
controls).  Projections q / k / v / gate / up and the head: gain 1.28 (0.02
at hidden 4096): attention logits ``s q.k`` have a spread of ~1.6 — a
softmax over a thousand keys that some tens of them carry, order 1 as PR 27
set OLMoE's —, next-byte logits one of ~1.3 over 320.  ``W_o`` gain ``_OUT``
= 2 and ``W_down`` gain ``_DOWN`` = 1: the MLP then writes ~1.0 a feature a
layer into the stream and attention 0.45 (layer 0) to 1.8 (layer 7), so what
a query sees decides about half of what the next layer reads (CPU, PR 37,
the real head size and window at hidden 256: at ``_OUT`` 1.28 attention
writes 0.3-0.4 and the controls below read 0.14 / 0.26 / 0.03; at 4 and 6
its share feeds on itself — 6.4 a feature by layer 3 — and readings swing
tenfold between seeds).  ``phi`` std ``_PHI`` = 1.5:
pooling logits ``s k.phi`` of spread ~1.9, so a chunk's summary is mostly
two or three of its 16 keys (uniform pooling is the control
``mean_pooled``).  ``mu`` std ``_MU`` = 0.5: a per-head offset of spread
~0.6 on every summary's logit.  Norm offsets ``g`` std 0.1.  Embeddings
std 1.

``precision`` selects the control: ``"float32"`` (the reference),
``"bfloat16"`` (what a sound program computes), ``"float8"`` (every matmul
operand rounded to e4m3 with a per-tensor scale), and, each bfloat16 but for
one thing: ``"summaries_dropped"`` (``R`` empty: what a lane whose summary
rows are never written, written to the wrong row or never read computes),
``"stale_ring"`` (the ring rows of the window BEFORE not masked: ``L(p)``
becomes the last ``W`` positions — what a mask by "what was written"
computes), ``"mean_pooled"`` (``a = 1 / c``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.dots3 import (_f32, _mm, _rope, _round, _static,
                                      _store, _swiglu)
from benchmark.families.opt import HIGHEST, _tensor, seed_key

_PROJ, _OUT, _DOWN, _PHI, _MU, _G, _EMBED = 1.28, 2.0, 1.0, 1.5, 0.5, 0.1, 1.0
GAP_ROWS = 1024          # the longest answer a cell may ask for


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    heads = model["num_attention_heads"]
    if model.get("attention_class") != "eva" or model.get("rope_scaling") \
            or model.get("attention_bias") \
            or model.get("tie_word_embeddings") \
            or model.get("num_key_value_heads", heads) != heads \
            or not (model.get("norm_add_unit_offset")
                    and model.get("fp32_skip_add")
                    and model.get("fp32_logits")):
        raise ValueError("this reference is evabyte as released: EVA "
                         "attention, one K/V head a head, no biases, an "
                         "untied head, norm gain 1 + g, float32 stream and "
                         "logits")
    h, c, W = model["hidden_size"], model["chunk_size"], model["window_size"]
    if h % heads or W % c:
        raise ValueError("heads divide hidden_size, chunks the window")
    return dict(
        h=h, heads=heads, d=h // heads, f=model["intermediate_size"],
        layers=model["num_hidden_layers"], vocab=model["vocab_size"],
        pred_heads=model["num_pred_heads"], chunk=c, window=W,
        positions=model["max_position_embeddings"],
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]))


def parameters_by_part(z):
    """Parameters counted from the shapes, by part."""
    h, f = z["h"], z["f"]
    layer = 4 * h * h + 3 * h * f + 2 * z["heads"] * z["d"] + 2 * h
    return {"layers": z["layers"] * layer, "a_layer": layer,
            "embedding": z["vocab"] * h,
            "head": h * z["pred_heads"] * z["vocab"], "final_norm": h}


# --------------------------------------------------------------------- #
# Weights from the seed
# --------------------------------------------------------------------- #
def _layer_kinds(z):
    h, f, hd = z["h"], z["f"], (z["heads"], z["d"])
    fan = lambda gain, n: gain / np.sqrt(n)
    return [("ln1_g", (h,), _G, 0.0), ("ln2_g", (h,), _G, 0.0),
            ("wq", (h, h), fan(_PROJ, h), 0.0),
            ("wk", (h, h), fan(_PROJ, h), 0.0),
            ("wv", (h, h), fan(_PROJ, h), 0.0),
            ("wo", (h, h), fan(_OUT, h), 0.0),
            ("phi", hd, _PHI, 0.0), ("mu", hd, _MU, 0.0),
            ("w1", (h, f), fan(_PROJ, h), 0.0),
            ("w3", (h, f), fan(_PROJ, h), 0.0),
            ("w2", (f, h), fan(_DOWN, f), 0.0)]


def layer_weights(z, key, layer):
    """The tensors of ``layer`` (traced or not)."""
    return {name: _tensor(key, 100 + i, layer, shape, std, mean)
            for i, (name, shape, std, mean) in enumerate(_layer_kinds(z))}


def global_weights(z, key):
    h = z["h"]
    return {"embed": _tensor(key, 0, 0, (z["vocab"], h), _EMBED, 0.0),
            "lnf_g": _tensor(key, 1, 0, (h,), _G, 0.0),
            "head": _tensor(key, 2, 0, (h, z["pred_heads"] * z["vocab"]),
                            _PROJ / np.sqrt(h), 0.0)}


# --------------------------------------------------------------------- #
# The program's side: its module, and its parameter tree from the seed
# --------------------------------------------------------------------- #
def program_model(model, **overrides):
    """The program's own module at the file's sizes."""
    from deepspeed_tpu.models.evabyte import evabyte_model
    sizes_of(model)                      # refuses what the reference lacks
    return evabyte_model(model, **{"dtype": "bfloat16", **overrides})


_PROGRAM_LEAVES = {      # the program's leaf path in a layer -> the tensor
    ("input_norm", "scale"): "ln1_g", ("post_attn_norm", "scale"): "ln2_g",
    ("attn", "q_proj", "kernel"): "wq", ("attn", "k_proj", "kernel"): "wk",
    ("attn", "v_proj", "kernel"): "wv", ("attn", "o_proj", "kernel"): "wo",
    ("attn", "phi"): "phi", ("attn", "mu"): "mu",
    ("mlp", "gate_proj", "kernel"): "w1", ("mlp", "up_proj", "kernel"): "w3",
    ("mlp", "down_proj", "kernel"): "w2",
}
_GLOBAL_LEAVES = {"embed_tokens": "embed", "final_norm": "lnf_g",
                  "lm_head": "head"}


@functools.partial(jax.jit, static_argnames=("sizes", "shapes"))
def _build_layer(key, layer, *, sizes, shapes):
    """One layer's leaves ``{path: array}`` in the program's shapes."""
    w = layer_weights(dict(sizes), key, layer)
    return {path: w[_PROGRAM_LEAVES[path]].reshape(shape)
            for path, shape in shapes}


@functools.partial(jax.jit, static_argnames=("sizes",))
def _globals_jit(key, *, sizes):
    return global_weights(dict(sizes), key)


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves) from ``seed``, on the
    device: a jitted call a LAYER with the index traced — one compiled form
    for every layer and seed, one layer's temporaries at a time."""
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
        built[l] = _build_layer(key, jnp.int32(l), sizes=_static(z),
                                shapes=shapes)
    leaves = []
    for p, (_, leaf) in zip(paths, flat):
        if p[0].startswith("layers_"):
            leaves.append(built[int(p[0][7:])][p[1:]])
        else:
            leaves.append(glob[_GLOBAL_LEAVES[p[0]]].reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #
def _parts(precision):
    """``precision`` -> ``(what everything computes in, whether R is empty,
    whether the window before stays visible, whether pooling is uniform)``."""
    if precision == "summaries_dropped":
        return "bfloat16", True, False, False
    if precision == "stale_ring":
        return "bfloat16", False, True, False
    if precision == "mean_pooled":
        return "bfloat16", False, False, True
    return precision, False, False, False


def _norm(x, g, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + _f32(g))


def summaries(z, k, v, w, precision, uniform=False):
    """``(ksum, vsum) [S / c, heads, d]`` of ``k`` / ``v [S, heads, d]``
    (``S`` whole chunks), as the cache would hold them."""
    c = z["chunk"]
    kc = k.reshape(-1, c, z["heads"], z["d"])
    vc = v.reshape(-1, c, z["heads"], z["d"])
    logit = jnp.sum(kc * _f32(w["phi"]), -1) / np.sqrt(z["d"])
    a = jnp.full_like(logit, 1.0 / c) if uniform \
        else jax.nn.softmax(logit, axis=1)                    # [n, c, heads]
    ksum = jnp.sum(a[..., None] * kc, 1) + _f32(w["mu"])
    vsum = jnp.sum(a[..., None] * vc, 1)
    return _store(ksum, precision), _store(vsum, precision)


def attention(z, u, w, precision):
    """EVA attention of one sequence ``u [S, h]`` (``S`` whole windows)."""
    outer, dropped, stale, uniform = _parts(precision)
    S, H, d, W = u.shape[0], z["heads"], z["d"], z["window"]
    per = W // z["chunk"]
    heads = lambda t: t.reshape(S, H, d)
    q = _store(_rope(heads(_mm(u, w["wq"], outer)), z["theta"]), outer)
    k = _store(_rope(heads(_mm(u, w["wk"], outer)), z["theta"]), outer)
    v = heads(_mm(u, w["wv"], outer))
    ksum, vsum = summaries(z, k, v, w, outer, uniform)
    before = W if stale else 0         # keys of the window before, kept
    kk = jnp.concatenate([jnp.zeros((before, H, d)), k])
    vv = jnp.concatenate([jnp.zeros((before, H, d)), v])
    rd = lambda t: _round(t, outer)

    def window(i):
        qw = jax.lax.dynamic_slice_in_dim(q, i * W, W)
        kw = jax.lax.dynamic_slice_in_dim(kk, i * W, W + before)
        vw = jax.lax.dynamic_slice_in_dim(vv, i * W, W + before)
        p = i * W + jnp.arange(W)[:, None]                       # [W, 1]
        t = i * W - before + jnp.arange(W + before)[None, :]
        local = (t <= p) & (t > p - W) & (t >= 0) if stale \
            else (t <= p)
        remote = (jnp.arange(S // z["chunk"])[None, :] < per * i) \
            & (not dropped)
        scores = jnp.concatenate(
            [jnp.einsum("phd,thd->hpt", rd(qw), rd(kw), precision=HIGHEST),
             jnp.einsum("phd,jhd->hpj", rd(qw), rd(ksum),
                        precision=HIGHEST)], -1) / np.sqrt(d)
        mask = jnp.concatenate(
            [local, jnp.broadcast_to(remote, (W, remote.shape[1]))], -1)
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return jnp.einsum("hpt,thd->phd", rd(probs),
                          rd(jnp.concatenate([vw, vsum])), precision=HIGHEST)

    out = jax.lax.map(window, jnp.arange(S // W)).reshape(S, H * d)
    return _mm(_store(out, outer), w["wo"].reshape(H * d, -1), outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _layer_jit(key, layer, x, *, sizes, precision):
    """One layer on the float32 stream ``x [S, h]``, its weights drawn
    here."""
    z, outer = dict(sizes), _parts(precision)[0]
    w = layer_weights(z, key, layer)
    x = x + attention(z, _norm(x, w["ln1_g"], z["eps"]), w, precision)
    return x + _swiglu(_norm(x, w["ln2_g"], z["eps"]), w["w1"], w["w3"],
                       w["w2"], outer)


@jax.jit
def _embed_jit(g, tokens):
    return _f32(g["embed"])[tokens]


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "heads"))
def _head_jit(g, x, positions, *, sizes, precision, heads):
    """float32 logits of ``heads`` prediction heads at ``positions``."""
    z, outer = dict(sizes), _parts(precision)[0]
    h = _norm(x[positions], g["lnf_g"], z["eps"])
    return jnp.matmul(_round(h, outer),
                      _round(_f32(g["head"][:, :heads * z["vocab"]]), outer),
                      precision=HIGHEST)


def _logits(z, key, tokens, positions, precision, heads=1):
    """Logits ``[R, heads x V]`` at ``positions [R]`` of one sequence
    ``tokens [S]`` (``S`` whole windows)."""
    kw = dict(sizes=_static(z), precision=precision)
    g = _globals_jit(key, sizes=_static(z))
    x = _embed_jit(g, tokens)
    for layer in range(z["layers"]):
        x = _layer_jit(key, jnp.int32(layer), x, **kw)
    return _head_jit(g, x, positions, heads=heads, **kw)


def _padded(z, tokens, pad_to=None):
    W = z["window"]
    row = np.zeros(-(-max(len(tokens), pad_to or 0) // W) * W, np.int32)
    row[:len(tokens)] = tokens
    return jnp.asarray(row)


def logits(z, seed, tokens, precision="float32", heads=None):
    """All logits ``[S, heads x V]`` of ONE sequence ``tokens [S]`` (every
    prediction head unless ``heads`` says fewer) — what the CPU tests
    compare the program with."""
    return _logits(z, seed_key(seed), _padded(z, tokens),
                   jnp.arange(len(tokens)), precision,
                   heads or z["pred_heads"])


def summary_rows(z, seed, tokens):
    """``(ksum, vsum) [layers, len(tokens) // c, heads x d]`` float32: what
    a slot's summary rows must hold after ``tokens`` — for the tests."""
    key = seed_key(seed)
    g = global_weights(z, key)
    n = len(tokens) // z["chunk"]
    x = _f32(g["embed"])[_padded(z, tokens)]
    out = []
    for layer in range(z["layers"]):
        w = layer_weights(z, key, layer)
        u = _norm(x, w["ln1_g"], z["eps"])
        heads = lambda t: t.reshape(-1, z["heads"], z["d"])
        k = _rope(heads(_mm(u, w["wk"], "float32")), z["theta"])
        v = heads(_mm(u, w["wv"], "float32"))
        out.append([t[:n].reshape(n, -1)
                    for t in summaries(z, k, v, w, "float32")])
        x = _layer_jit(key, jnp.int32(layer), x, sizes=_static(z),
                       precision="float32")
    return tuple(jnp.stack(t) for t in zip(*out))


def nll_at(z, seed, tokens, positions, precision="float32"):
    """Next-token negative log-likelihood ``[B, R]``: row ``b``'s loss of
    predicting ``tokens[b, p + 1]`` at each ``p`` of ``positions[b]``."""
    out = []
    for row, pos in zip(np.asarray(tokens), np.asarray(positions)):
        lg = _logits(z, seed_key(seed), _padded(z, row),
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
    :func:`chosen_gaps` is this for one chooser.  Head 0's logits."""
    if n_new > GAP_ROWS:
        raise ValueError(f"answers of at most {GAP_ROWS} tokens")
    key = seed_key(seed)
    tokens = _padded(z, tokens, max(pad_to or 0, prompt_len + GAP_ROWS))
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
    whole windows past ``pad_to`` so every request of a cell shares one
    compiled program (a causal model never sees the padding).  With
    ``chooser`` (a precision), the CONTROL: the token that the reference
    computed in that precision would have picked stands in the served
    token's place — ``families/opt.py::chosen_gaps`` has the long form."""
    return gaps_under(z, seed, tokens, prompt_len, n_new, pad_to,
                      [chooser])[chooser]


def greedy(z, seed, prompt, n_new, pad_to, precision):
    """The reference in the program's place: greedy decoding by full
    recomputation, in ``precision`` — the control's generator (a full
    forward a token: for short requests only)."""
    toks = list(np.asarray(prompt))
    for _ in range(n_new):
        at = jnp.asarray([len(toks) - 1], jnp.int32)
        lg = _logits(z, seed_key(seed), _padded(z, toks, pad_to), at,
                     precision)
        toks.append(int(np.argmax(np.asarray(lg[0]))))
    return np.asarray(toks, np.int32)
