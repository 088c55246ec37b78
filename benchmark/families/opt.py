"""The OPT family: weights from a seed, the adapter that hands them to the
program, and the plain reference.

**Weights.**  Every tensor is a pure function of ``(seed, tensor kind,
layer)``: normal draws (threefry) scaled per kind and rounded to bfloat16, so
the values are exact in every dtype the program may hold them in.  The
program's parameter tree is built from them on the device in ONE jitted call
(`program_params`); the reference draws the same tensors again, layer by
layer inside its own scan, and so shares no array, scale or table with the
program — only the seed.

**Reference.**  OPT's decoder as published (Zhang et al. 2022, and the HF
``OPTDecoder``): token + learned position embeddings, pre-LayerNorm blocks of
causal multi-head attention and a ReLU MLP, final LayerNorm, head tied to the
token embedding.  Plain ``jax.numpy`` in float32 with matmul precision
``highest``; no kernel, no cache, no batching tricks.  One departure, shared
with the program: positions are 0-based into a table of
``max_position_embeddings`` rows (HF offsets by 2 into 2050 rows) — with
weights from a seed this moves no number.

``precision`` selects the control the contract asks for: ``"float8"`` (the
nearest precision below the configuration's bfloat16: every matmul operand
rounded to e4m3 with a per-tensor scale, results stored in bfloat16) and
``"bfloat16"`` (operands and results rounded to bfloat16 — what a sound
program computes; used by the tests to show the comparison passes it).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
# per-layer tensors: name -> (shape from sizes, std, mean)
_W, _B, _G = 0.02, 0.02, 0.1


def sizes_of(model):
    """The family's sizes from a configuration file's ``model`` block (HF
    key names)."""
    h, heads = model["hidden_size"], model["num_attention_heads"]
    if h % heads:
        raise ValueError("hidden_size must divide by num_attention_heads")
    if model.get("word_embed_proj_dim", h) != h or \
            not model.get("do_layer_norm_before", True):
        raise ValueError("this reference is the pre-LN OPT without "
                         "projected embeddings (opt-350m is neither)")
    return dict(h=h, heads=heads, d=h // heads, f=model["ffn_dim"],
                layers=model["num_hidden_layers"], vocab=model["vocab_size"],
                positions=model["max_position_embeddings"])


def _layer_kinds(z):
    h, f = z["h"], z["f"]
    return [("ln1_g", (h,), _G, 1.0), ("ln1_b", (h,), _B, 0.0),
            ("wq", (h, h), _W, 0.0), ("bq", (h,), _B, 0.0),
            ("wk", (h, h), _W, 0.0), ("bk", (h,), _B, 0.0),
            ("wv", (h, h), _W, 0.0), ("bv", (h,), _B, 0.0),
            ("wo", (h, h), _W, 0.0), ("bo", (h,), _B, 0.0),
            ("ln2_g", (h,), _G, 1.0), ("ln2_b", (h,), _B, 0.0),
            ("w1", (h, f), _W, 0.0), ("b1", (f,), _B, 0.0),
            ("w2", (f, h), _W, 0.0), ("b2", (h,), _B, 0.0)]


def _global_kinds(z):
    h = z["h"]
    return [("embed", (z["vocab"], h), _W, 0.0),
            ("pos", (z["positions"], h), _W, 0.0),
            ("lnf_g", (h,), _G, 1.0), ("lnf_b", (h,), _B, 0.0)]


def seed_key(seed):
    """A PRNG key from any whole number up to 2**64: low and high words."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(np.uint32(seed & 0xFFFFFFFF)),
                              np.uint32(seed >> 32))


def _tensor(key, index, layer, shape, std, mean):
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    x = mean + std * jax.random.normal(k, shape, jnp.float32)
    return x.astype(jnp.bfloat16)


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
    from deepspeed_tpu.models.opt import opt_config
    from deepspeed_tpu.models.transformer import Transformer
    z = sizes_of(model)
    return Transformer(opt_config(
        "opt-125m", **{**dict(
            hidden_size=z["h"], num_layers=z["layers"], num_heads=z["heads"],
            ffn_hidden_size=z["f"], vocab_size=z["vocab"],
            max_seq_len=z["positions"], dtype="bfloat16"), **overrides}))


_PROGRAM_LEAVES = {      # the program's leaf path -> the plain tensor
    ("attn", "q_proj", "kernel"): "wq", ("attn", "q_proj", "bias"): "bq",
    ("attn", "k_proj", "kernel"): "wk", ("attn", "k_proj", "bias"): "bk",
    ("attn", "v_proj", "kernel"): "wv", ("attn", "v_proj", "bias"): "bv",
    ("attn", "o_proj", "kernel"): "wo", ("attn", "o_proj", "bias"): "bo",
    ("input_norm", "scale"): "ln1_g", ("input_norm", "bias"): "ln1_b",
    ("post_attn_norm", "scale"): "ln2_g", ("post_attn_norm", "bias"): "ln2_b",
    ("mlp", "up_proj", "kernel"): "w1", ("mlp", "up_proj", "bias"): "b1",
    ("mlp", "down_proj", "kernel"): "w2", ("mlp", "down_proj", "bias"): "b2",
    ("embed_tokens", "embedding"): "embed",
    ("embed_positions", "embedding"): "pos",
    ("final_norm", "scale"): "lnf_g", ("final_norm", "bias"): "lnf_b",
}


def program_params(module, model, seed):
    """The program's parameter tree (bfloat16 leaves; every engine casts to
    what it holds, exactly) from ``seed``, on the device, in one jitted
    call whose compiled form serves every seed."""
    z = sizes_of(model)
    abstract = jax.eval_shape(module.init, jax.random.key(0),
                              {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key):
        stacked = jax.vmap(lambda l: layer_weights(z, key, l))(
            jnp.arange(z["layers"]))
        glob = global_weights(z, key)
        leaves = []
        for path, leaf in flat:
            names = tuple(p.key for p in path)[1:]       # drop 'params'
            if names[0] == "layers":                      # scanned: [L, ...]
                x = stacked[_PROGRAM_LEAVES[names[1:]]]
            elif names[0].startswith("layers_"):
                x = stacked[_PROGRAM_LEAVES[names[1:]]][int(names[0][7:])]
            else:
                x = glob[_PROGRAM_LEAVES[names]]
            leaves.append(x.reshape(leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(seed_key(seed))


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #
def _round(x, precision):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30      # e4m3's largest
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"unknown precision {precision!r}")


def _store(x, precision):
    """What a program of that precision keeps between operations."""
    return x if precision == "float32" else \
        x.astype(jnp.bfloat16).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm(a, b, precision):
    """``a [..., K] @ b [K, N]``.  The backward is written out so that a
    control's gradients are computed in the control's precision too: the
    operands of both backward matmuls are rounded as the forward's are
    (in float32 it is the ordinary gradient)."""
    out = jnp.matmul(_round(a, precision),
                     _round(b.astype(jnp.float32), precision),
                     precision=HIGHEST)
    return _store(out, precision)


def _mm_fwd(a, b, precision):
    return _mm(a, b, precision), (a, b)


def _mm_bwd(precision, saved, g):
    a, b = saved
    g = _round(g, precision)
    da = jnp.matmul(g, _round(b.astype(jnp.float32), precision).T,
                    precision=HIGHEST)
    db = jnp.einsum("...k,...n->kn", _round(a, precision), g,
                    precision=HIGHEST)
    return _store(da, precision), _store(db, precision).astype(b.dtype)


_mm.defvjp(_mm_fwd, _mm_bwd)


def _layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _attention_row(q, k, v, z):
    """Causal multi-head attention of ONE sequence, ``[S, h]`` each."""
    S = q.shape[0]
    split = lambda t: t.reshape(S, z["heads"], z["d"]).transpose(1, 0, 2)
    q, k, v = split(q), split(k), split(v)
    scores = jnp.einsum("hsd,htd->hst", q, k, precision=HIGHEST) \
        / np.sqrt(z["d"])
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
    out = jnp.einsum("hst,htd->hsd", probs, v, precision=HIGHEST)
    return out.transpose(1, 0, 2).reshape(S, z["h"])


def _embed(g, tokens, precision):
    f32 = lambda t: t.astype(jnp.float32)
    S = tokens.shape[1]
    return _store(f32(g["embed"])[tokens] + f32(g["pos"])[jnp.arange(S)][None],
                  precision)


def _block(z, x, w, precision):
    """One pre-LN decoder block on ``x [B, S, h]`` with the layer's weights
    ``w``."""
    f32 = lambda t: t.astype(jnp.float32)
    a = _store(_layer_norm(x, w["ln1_g"], w["ln1_b"]), precision)
    q, k, v = (_mm(a, w[m], precision) + f32(w[b])
               for m, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    # one row at a time, recomputed in a backward: a row's [heads, S, S]
    # scores are the largest thing the reference holds
    att = jax.lax.map(jax.checkpoint(lambda qkv: _attention_row(*qkv, z)),
                      (_store(q, precision), _store(k, precision),
                       _store(v, precision)))
    x = _store(x + _mm(_store(att, precision), w["wo"], precision)
               + f32(w["bo"]), precision)
    a = _store(_layer_norm(x, w["ln2_g"], w["ln2_b"]), precision)
    up = _store(jax.nn.relu(_mm(a, w["w1"], precision) + f32(w["b1"])),
                precision)
    return _store(x + _mm(up, w["w2"], precision) + f32(w["b2"]), precision)


def hidden_states(z, key, tokens, precision="float32"):
    """``tokens [B, S]`` -> final-normed hidden states ``[B, S, h]``."""
    g = global_weights(z, key)
    x = _embed(g, tokens, precision)

    def block(x, layer):
        return _block(z, x, layer_weights(z, key, layer), precision), None

    x, _ = jax.lax.scan(block, x, jnp.arange(z["layers"]))
    return _store(_layer_norm(x, g["lnf_g"], g["lnf_b"]), precision), g


def _logits(z, key, tokens, positions, precision):
    """Logits ``[B, R, V]`` at ``positions [B, R]`` only."""
    h, g = hidden_states(z, key, tokens, precision)
    rows = jnp.take_along_axis(h, positions[..., None], axis=1)
    return _mm(rows, g["embed"].T, precision)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _nll_jit(key, tokens, positions, *, sizes, precision):
    z = dict(sizes)
    logits = _logits(z, key, tokens, positions, precision)
    labels = jnp.take_along_axis(tokens, positions + 1, axis=1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jax.scipy.special.logsumexp(logits, axis=-1) - gold


def nll_at(z, seed, tokens, positions, precision="float32"):
    """Next-token negative log-likelihood ``[B, R]``: row ``b``'s loss of
    predicting ``tokens[b, p + 1]`` at each ``p`` of ``positions[b]``."""
    return _nll_jit(seed_key(seed), jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(positions, jnp.int32),
                    sizes=tuple(sorted(z.items())), precision=precision)


# The reference's backward.  Layer by layer, so that no more than one
# layer's weights and gradients are ever held: the forward keeps each
# block's input on the host, the backward sweeps the blocks in reverse and
# recomputes each from its input (``jax.vjp``), and a layer's gradient
# leaves only its sum of squares behind.
def _f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _embed_jit(key, tokens, *, sizes, precision):
    z = dict(sizes)
    return _embed(global_weights(z, key), tokens, precision)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _block_jit(key, layer, x, *, sizes, precision):
    z = dict(sizes)
    return _block(z, x, _f32(layer_weights(z, key, layer)), precision)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _block_vjp_jit(key, layer, x, dy, acc, *, sizes, precision):
    """Gradient of one block: w.r.t. its input, and w.r.t. its weights
    added to ``acc``."""
    z = dict(sizes)
    w = _f32(layer_weights(z, key, layer))
    _, vjp = jax.vjp(lambda x, w: _block(z, x, w, precision), x, w)
    dx, dw = vjp(dy)
    return dx, jax.tree.map(jnp.add, acc, dw)


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "count"))
def _head_vjp_jit(key, x, tokens, acc, *, sizes, precision, count):
    """Summed next-token NLL of ``tokens`` over ``count`` positions in all
    (the whole batch's, so that groups of rows add up to its mean), and its
    gradient w.r.t. the last block's output and the head's weights (the
    final LayerNorm and the tied embedding as the head), added to ``acc``."""
    z = dict(sizes)
    g = _f32(global_weights(z, key))
    head = {k: g[k] for k in ("embed", "lnf_g", "lnf_b")}

    def loss(x, head):
        def row(args):          # one row's [S, V] logits at a time
            xr, tr = args
            h = _store(_layer_norm(xr, head["lnf_g"], head["lnf_b"]),
                       precision)
            logits = _mm(h[:-1], head["embed"].T, precision)
            gold = jnp.take_along_axis(logits, tr[1:, None], axis=-1)[:, 0]
            return jnp.sum(jax.scipy.special.logsumexp(logits, axis=-1)
                           - gold)
        return jnp.sum(jax.lax.map(jax.checkpoint(row), (x, tokens))) / count

    value, (dx, dhead) = jax.value_and_grad(loss, argnums=(0, 1))(x, head)
    return value, dx, jax.tree.map(jnp.add, acc, dhead)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _embed_vjp_jit(key, tokens, dx, acc, *, sizes, precision):
    z = dict(sizes)
    g = _f32(global_weights(z, key))
    tables = {k: g[k] for k in ("embed", "pos")}
    _, vjp = jax.vjp(lambda t: _embed(t, tokens, precision), tables)
    return jax.tree.map(jnp.add, acc, vjp(dx)[0])


@jax.jit
def _sum_squares(tree):
    return sum(jnp.sum(jnp.square(t)) for t in jax.tree.leaves(tree))


def gradient_sample(z):
    """The tensors whose gradients are compared one by one: every vector of
    every layer, the query and output projections (their inputs and
    cotangents are the attention kernel's, forward and backward), both
    embedding tables and the final LayerNorm."""
    return [n for n, shape, _, _ in _layer_kinds(z)
            if len(shape) == 1 or n in ("wq", "wo")] + \
        [n for n, _, _, _ in _global_kinds(z)]


def loss_and_gradients(z, seed, tokens, precision="float32", group=4):
    """What one training step on ``tokens [B, S]`` should see, by the plain
    reference, ``group`` rows at a time: ``loss``, the mean next-token NLL
    over all ``B x (S - 1)`` positions; ``grad_norm``, the L2 norm of its
    gradient over every weight (the tied embedding once); and
    ``gradients``, those of ``gradient_sample`` (a layer kind stacked
    ``[layers, ...]``), on the host."""
    key, kw = seed_key(seed), dict(sizes=tuple(sorted(z.items())),
                                   precision=precision)
    tokens = np.asarray(tokens, np.int32)
    B, S = tokens.shape
    groups = [jnp.asarray(tokens[r:r + group]) for r in range(0, B, group)]
    zeros = lambda kinds: {n: jnp.zeros(shape, jnp.float32)
                           for n, shape, _, _ in kinds}
    keep = set(gradient_sample(z))
    inputs = []                   # [layer][group], on the host
    xs = [_embed_jit(key, t, **kw) for t in groups]
    for layer in range(z["layers"]):
        inputs.append([np.asarray(x) for x in xs])
        xs = [_block_jit(key, layer, x, **kw) for x in xs]
    loss, dxs, sq = 0.0, [], 0.0
    acc = zeros(_global_kinds(z))
    for x, t in zip(xs, groups):
        value, dx, head = _head_vjp_jit(
            key, x, t, {k: acc[k] for k in ("embed", "lnf_g", "lnf_b")},
            count=B * (S - 1), **kw)
        acc.update(head)
        loss += float(value)
        dxs.append(dx)
    del xs
    kept = {}
    for layer in reversed(range(z["layers"])):
        dw = zeros(_layer_kinds(z))
        for i in range(len(groups)):
            dxs[i], dw = _block_vjp_jit(key, layer, inputs[layer][i], dxs[i],
                                        dw, **kw)
        inputs.pop()
        sq += float(_sum_squares(dw))
        for n in keep & set(dw):
            kept.setdefault(n, []).insert(0, np.asarray(dw[n]))
    for t, dx in zip(groups, dxs):
        acc.update(_embed_vjp_jit(
            key, t, dx, {k: acc[k] for k in ("embed", "pos")}, **kw))
    sq += float(_sum_squares(acc))
    gradients = {n: np.stack(v) for n, v in kept.items()}
    gradients.update({n: np.asarray(acc[n]) for n in keep & set(acc)})
    return {"loss": loss, "grad_norm": float(np.sqrt(sq)),
            "gradients": gradients}


def program_tensor(leaf_of, name, z):
    """The program's leaf for the plain tensor ``name`` — a parameter, or a
    same-shaped slot of the optimizer's state — in the reference's shape
    and the program's own type.
    ``leaf_of('/'-joined path)`` returns the leaf as an array, or None."""
    path = "/".join(next(k for k, v in _PROGRAM_LEAVES.items() if v == name))
    shapes = {n: shape for n, shape, _, _ in _layer_kinds(z) + _global_kinds(z)}
    if name in [n for n, *_ in _global_kinds(z)]:
        return np.asarray(leaf_of(f"params/{path}")).reshape(shapes[name])
    stacked = leaf_of(f"params/layers/{path}")       # scanned: [L, ...]
    if stacked is None:
        stacked = np.stack([leaf_of(f"params/layers_{l}/{path}")
                            for l in range(z["layers"])])
    return np.asarray(stacked).reshape((z["layers"],) + shapes[name])


def relative_error(got, want):
    """``|got - want| / |want|`` in L2 over all the tensors of ``want``
    together (dicts by name; float32 differences, summed in float64)."""
    sq = lambda x: float(np.sum(np.square(x, out=x), dtype=np.float64))
    num = sum(sq(np.subtract(got[n], want[n], dtype=np.float32))
              for n in want)
    den = sum(sq(np.array(want[n], np.float32)) for n in want)
    return float(np.sqrt(num / den))


@functools.partial(jax.jit, static_argnames=("sizes", "chooser"))
def _gap_jit(key, tokens, start, count, *, sizes, chooser):
    z = dict(sizes)
    S = tokens.shape[0]
    positions = jnp.arange(S - 1)
    logits = _logits(z, key, tokens[None], positions[None], "float32")[0]
    if chooser is None:                 # the tokens that were served
        chosen_ids = tokens[1:]
    else:                               # what ``chooser`` precision picks
        chosen_ids = jnp.argmax(_logits(z, key, tokens[None], positions[None],
                                        chooser)[0], axis=-1)
    chosen = jnp.take_along_axis(logits, chosen_ids[:, None], axis=-1)[:, 0]
    gap = jnp.max(logits, axis=-1) - chosen
    # position p predicts token p+1: generated tokens sit at start..start+count-1
    live = (positions + 1 >= start) & (positions + 1 < start + count)
    return jnp.where(live, gap, 0.0)


def chosen_gaps(z, seed, tokens, prompt_len, n_new, pad_to, chooser=None):
    """For one served request (``tokens`` = prompt + generated): how far
    below the reference's largest logit each generated token's reference
    logit lies, teacher-forced over the request's own tokens.  Padded to
    ``pad_to`` so every request of a cell shares one compiled program (the
    padding sits after the last real position; causal attention never sees
    it).

    With ``chooser`` (a precision), the CONTROL: at each of the same
    positions, over the same context, the token that the reference computed
    in that precision would have picked stands in the served token's place —
    the reference in the program's place for every single decoding
    decision."""
    row = np.zeros(pad_to, np.int32)
    row[:len(tokens)] = tokens
    gaps = _gap_jit(seed_key(seed), jnp.asarray(row), prompt_len, n_new,
                    sizes=tuple(sorted(z.items())), chooser=chooser)
    return np.asarray(gaps)[prompt_len - 1:prompt_len - 1 + n_new]


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "n_new"))
def _greedy_jit(key, tokens, prompt_len, *, sizes, precision, n_new):
    z = dict(sizes)

    def step(i, toks):
        at = prompt_len - 1 + i
        logits = _logits(z, key, toks[None], at[None, None], precision)[0, 0]
        return toks.at[at + 1].set(jnp.argmax(logits).astype(jnp.int32))

    return jax.lax.fori_loop(0, n_new, step, tokens)


def greedy(z, seed, prompt, n_new, pad_to, precision):
    """The reference in the program's place: greedy decoding by full
    recomputation, in ``precision`` — the control's generator."""
    row = np.zeros(pad_to, np.int32)
    row[:len(prompt)] = prompt
    out = _greedy_jit(seed_key(seed), jnp.asarray(row), jnp.int32(len(prompt)),
                      sizes=tuple(sorted(z.items())), precision=precision,
                      n_new=n_new)
    return np.asarray(out)[:len(prompt) + n_new]
