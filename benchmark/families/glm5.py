"""The glm5 family (zai-org GLM-5, ``model_type: glm_moe_dsa``): weights
from a seed, the adapter that hands them to the program, and the plain
reference of the main model AND of its multi-token-prediction module.

**Reference.**  ``x`` is a block's input after its RMSNorm (eps 1e-5).

*Attention, every layer*: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` ->
heads of ``nope + rope``; ``[c_kv | k_r] = x W_kva``, ``c_kv =
RMSNorm(c_kv)``; rotary positions (theta 1e6, pairs ``(2i, 2i + 1)``) on
the rope part of ``q`` and on the one shared ``k_r``; ``[k_nope_h | v_h] =
c_kv W_kvb``; logit ``(q_nope . k_nope + q_rope . k_r) / sqrt(nope +
rope)``; ``o = concat_h(softmax . v) W_o`` — no output gate, no latent
rescale.  The indexer: ``q^I = c_q W^I_q`` (32 heads of 128), ``k^I =
LayerNorm(x W^I_k)``, rope in the same pairing on the first ``rope``
features of both, ``w = x W^I_w``; ``I[t, s] = sum_j w[t, j] relu(q^I[t, j]
. k^I[s]) / sqrt(32 x 128)`` in float32; the softmax runs over the
``index_topk`` positions ``s <= t`` of largest ``I`` (all while ``t <
index_topk``).

*FFN*: dense SwiGLU in the first ``first_k_dense_replace`` layers; then
``s = sigmoid(x W_r)`` over ALL published experts in float32, the top 8 of
``s + b``, gates ``2.5 s_e / sum_chosen s``; ``y = shared(x) + sum over the
chosen experts that are HELD of gate_e E_e(x)``; what the absent experts
would add is left out here as in the program.

*The multi-token-prediction module* (one; DeepSeek-V3's form): at position
``t``, from the main model's final-normed state ``h_t`` and the NEXT token,
``u_t = [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(h_t)] W_eh``, one block as
above over the module's own rows at positions ``<= t`` (expert layer, the
same held share), ``RMSNorm_s`` and the main model's head: logits for
``x_{t+2}``.  :func:`drafts` is the module's greedy guess along a given
sequence, :func:`accepted_along` what a self-drafting server accepts of
them.

Plain ``jax.numpy`` in float32 with matmul precision ``highest``; no kernel,
no cache, no batching; ONE sequence, a jitted program a half-layer and
attention in blocks of 64 queries against all keys.  Weights are regenerated
from the seed alone, tensor by tensor.  The rounding rules, the matmul, the
norms, the SwiGLU and the tensor draw are ``families/dots3.py``'s and
``families/opt.py``'s own functions, imported.

**The weights' draw.**  ``families/dots3.py``'s scales wherever the block is
the same, for that family's reasons (its docstring): std 0.02, norm gains 1
+- 0.1, token embeddings std 2 plus one common component of std 0.25,
``o_proj`` 0.04, the shared and the routed experts' down-projections 0.06,
and the DISTILLED indexer (every head's followed columns share 80% of their
variance, the indexer computes that common part).  What differs: no
``gate``; no rescale, so the distilled index key's projection carries no
``sqrt(hidden / rank)``; and the selection bias is drawn at 0.02 and then
BALANCED (:func:`balanced_biases`): a decode window hands this chip's
sixteen experts ~4 rows each, so which of them a drawn router leaves
untouched decides the window's weight stream, seed by seed.

**The head's successor component, and the distilled module.**  A trained
multi-token-prediction module agrees with the model it drafts for (85-90%
of its drafts are accepted, DeepSeek-V3's report); one drawn on its own
never does, and a cell at acceptance 0 measures only what speculation
costs.  A one-block module cannot recompute five layers, so agreement has
to come from what BOTH read: a trained model's next token is largely
decided by its last token (bigram statistics), the rest by context.  The
draw imitates that, and so that the rate is the SAME on every seed — a
window's acceptance moves every lane's lifetime, and a cell whose rate
scatters by seed cannot be judged at a 1% bound (PR 40's first
construction read 0.80-0.90 by seed and the cell spread 11.6%) — it fixes
WHICH drafts fail, not how many on average:

* Every id has a phase, ``id mod PHASES`` (9), and one likely successor
  ``succ(x)`` of the NEXT phase (``successor``: a permutation of each
  phase's ids onto the next's).  The head's column of ``succ(x)`` carries
  ``_SUCC / hidden`` times ``x``'s embedding — a lead of ~0.45 ``_SUCC``
  logit units over the other ids' ~1.6-std scatter (their largest of
  19,359 lies ~6.4 up; measured on the chip, PR 40: the successor stood
  0.744 / 0.939 / 0.9956 of the time at ``_SUCC`` 15 / 18 / 24).  At
  ``_SUCC`` 48 the main model follows the successor after every id — but
  one of phase 0:
* the ids of phase 0 (``unread``, one in nine) have three more
  candidates, ``rivals(x)``, of the same phase as the successor and with
  the same lead: which of the four follows is decided by the context, five
  layers deep —
  these are the model's close calls, where a rounding can flip a token and
  where the controls below are told from a sound program.  Whatever is
  chosen, a token's phase is its predecessor's + 1.
* The module is distilled, not drawn: ``eh_proj`` passes the embedding
  half through (``diag(rms(Emb) / g_e)``; the hidden half a draw of std
  ``_EH``), its block is main layer ``L - 1``'s tensors mixed with a
  quarter of an own draw (``_MTP_OWN``, variance kept; the routed experts
  likewise, expert by expert; the indexer distilled from the MIXED
  attention), its last norm the main model's.  It reads the successor off
  the shared head as the main model does, one block deep, and drafts it
  always — but after an id of phase 0, which it cannot read: those ids'
  embeddings carry a flag (coordinate 0: ``_FLAG`` x the embeddings' std,
  0 for every other id), and ``eh_proj``'s row of it turns the module's
  input into ``_MISTAKE`` times a fixed vector that the head's column
  ``BLIND`` (an id of phase 3) carries: the module drafts ``BLIND``, the
  model picks an id of phase 1, the draft is rejected.

So a window is rejected iff its committed token has phase 0, a rejection
moves a lane one position and an acceptance two, and every lane settles
into windows at phases 1, 3, 5, 7, 0: four accepted of five, nine tokens
in five windows, on every seed and in every lane — acceptance
``(PHASES - 1) / (PHASES + 1)`` = 0.80, short only of the windows a budget
cuts.  Nothing here was tuned on the chip but the margin of ``_SUCC``.

**What is assumed** is listed in the configuration file.

``precision`` selects the control: ``"float32"`` (the reference),
``"bfloat16"`` (what a sound program computes), ``"float8"`` (every matmul
operand rounded to e4m3 with a per-tensor scale), and, each bfloat16 but for
one thing: ``"float8_experts"`` (the experts' three matmuls in float8),
``"recent_topk"`` (the kept set replaced by the most recent ``index_topk``
positions), ``"held_dropped"`` (the held experts' part left out) and
``"stale_window_row"`` — at every position where the module's draft was
REJECTED, all later queries attend the rows (latent and index, every main
layer) that the rejected draft token left there, not the committed
token's: what a verify window that failed to overwrite would compute.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.dots3 import (HIGHEST, QUERY_BLOCK, _f32,
                                      _layer_norm, _mm, _padded, _rms_norm,
                                      _round, _static, _store, _swiglu,
                                      _tensor, _tensor_alone, seed_key)

_W, _G, _EMBED, _DOWN, _SHARED, _ATTN, _OUT, _BIAS = \
    0.02, 0.1, 2.0, 0.06, 0.06, 0.02, 0.04, 0.02
_EMBED_MEAN, _FOLLOW, _INDEX_OWN = 0.25, 0.8, 0.25
# the successor's lead in the head (x hidden^-1 x the embedding), the
# module's hidden half of eh_proj, and its block's own share
_SUCC, _EH, _MTP_OWN = 48.0, 0.0013, 0.25
SUCC_A, SUCC_B = 7919, 1234
RIVALS = ((15485863, 977), (32452843, 4099), (49979687, 7907))
# ids have a phase, id mod PHASES, and a successor's is its id's + 1; the
# ids of phase 0 are the ones the module cannot read (their embeddings'
# coordinate 0 is the flag, _FLAG x the embeddings' std; every other id's
# is 0), and what it drafts after one is id BLIND, _MISTAKE times as sure
PHASES, BLIND, _FLAG, _MISTAKE = 9, 3, 2.0, 3.0
MTP_DRAW = 1000                  # the module's own draws' "layer" index


def sizes_of(model):
    """The family's sizes from a configuration file (HF key names)."""
    rope = model.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default" \
            or model.get("attention_bias") \
            or model.get("tie_word_embeddings") \
            or model.get("scoring_func") != "sigmoid" \
            or model.get("topk_method") != "noaux_tc" \
            or model.get("n_group", 1) != 1 \
            or model.get("hidden_act", "silu") != "silu" \
            or not model.get("rope_interleave") \
            or not model.get("indexer_rope_interleave", True):
        raise ValueError("this reference is glm_moe_dsa as released: no rope "
                         "scaling, no biases, untied head, sigmoid + "
                         "noaux_tc routing in one group, SwiGLU, interleaved "
                         "rotary pairs")
    published = model.get("n_routed_experts_published",
                          model["n_routed_experts"])
    held = tuple(model.get("held_experts", (0, model["n_routed_experts"])))
    if held[1] != model["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    layers, mtp = model["num_hidden_layers"], \
        model.get("num_nextn_predict_layers", 0)
    full = tuple(sorted(dict(
        heads=model["num_attention_heads"], q_rank=model["q_lora_rank"],
        kv_rank=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], v=model["v_head_dim"],
        theta=float(rope["rope_theta"]), window=0,
        index_heads=model["index_n_heads"],
        index_dim=model["index_head_dim"],
        index_topk=model["index_topk"]).items()))
    return dict(
        h=model["hidden_size"], layers=layers, mtp=mtp,
        # the pools' layers, as the benchmark's readers count them: the
        # main model's and the module's, every one a full layer
        kinds=("full_attention",) * (layers + mtp),
        dense_layers=model["first_k_dense_replace"],
        f=model["intermediate_size"], ef=model["moe_intermediate_size"],
        experts=published, held=held, shared=model["n_shared_experts"],
        top_k=model["num_experts_per_tok"],
        norm_topk=bool(model["norm_topk_prob"]),
        scaling=float(model["routed_scaling_factor"]),
        vocab=model["vocab_size"], eps=float(model["rms_norm_eps"]),
        positions=model["max_position_embeddings"], full=full)


# --------------------------------------------------------------------- #
# The draw
# --------------------------------------------------------------------- #
def _attn_kinds(z, a):
    h, H, J, D = z["h"], a["heads"], a["index_heads"], a["index_dim"]
    return [("q_a", (h, a["q_rank"]), _W, 0.0),
            ("q_a_norm", (a["q_rank"],), _G, 1.0),
            ("q_b", (a["q_rank"], H * (a["nope"] + a["rope"])), _ATTN, 0.0),
            ("kv_a", (h, a["kv_rank"] + a["rope"]), _W, 0.0),
            ("kv_a_norm", (a["kv_rank"],), _G, 1.0),
            ("kv_b", (a["kv_rank"], H * (a["nope"] + a["v"])), _ATTN, 0.0),
            ("o_proj", (H * a["v"], h), _OUT, 0.0),
            ("index_q", (a["q_rank"], J * D), _ATTN, 0.0),
            ("index_k", (h, D), _ATTN, 0.0),
            ("index_k_norm_scale", (D,), _G, 1.0),
            ("index_k_norm_bias", (D,), _BIAS, 0.0),
            ("index_w", (h, J), _W, 0.0),
            # what every head's followed columns have in common
            ("common_q", (a["q_rank"], D), _ATTN, 0.0),
            ("common_k", (a["kv_rank"], D - a["rope"]), _ATTN, 0.0)]


def _distilled(z, a, w, mean):
    """``families/dots3.py::_distilled`` without the latent rescale: the
    drawn tensors ``w`` with the indexer TIED to the attention it selects
    for (that docstring has the construction and the why)."""
    H, J, D = a["heads"], a["index_heads"], a["index_dim"]
    nope, rope, rank = a["nope"], a["rope"], a["kv_rank"]
    n = D - rope
    mix = lambda share, common, own: \
        np.sqrt(share) * common + np.sqrt(1.0 - share) * own
    cq, ck = _f32(w["common_q"]), _f32(w["common_k"])
    q_b = _f32(w["q_b"]).reshape(-1, H, nope + rope)
    q_b = jnp.concatenate([
        mix(_FOLLOW, cq[:, None, rope:], q_b[..., :n]), q_b[..., n:nope],
        mix(_FOLLOW, cq[:, None, :rope], q_b[..., nope:])], -1)
    kv_b = _f32(w["kv_b"]).reshape(rank, H, nope + a["v"])
    kv_b = jnp.concatenate([
        mix(_FOLLOW, ck[:, None], kv_b[..., :n]), kv_b[..., n:]], -1)
    index_q = cq[:, None] + _INDEX_OWN * _f32(w["index_q"]).reshape(-1, J, D)
    kv_a = _f32(w["kv_a"])
    # the constant goes onto the small operand BEFORE the product: after
    # it, a compiler may fold it into either operand, and a jitted build
    # (the program's) and a tensor-by-tensor one (the reference's) then
    # round a few elements apart
    through = jnp.matmul(
        kv_a[:, :rank] * _f32(w["kv_a_norm"]),
        ck * np.float32(np.sqrt(_FOLLOW) / (np.sqrt(z["h"]) * _W)),
        precision=HIGHEST)
    index_k = jnp.concatenate([kv_a[:, rank:], through], -1)
    along = _f32(mean) / jnp.sqrt(jnp.sum(jnp.square(_f32(mean))))
    out = {k: v for k, v in w.items() if not k.startswith("common_")}
    bf = lambda t, like: t.reshape(like.shape).astype(jnp.bfloat16)
    out.update(q_b=bf(q_b, w["q_b"]), kv_b=bf(kv_b, w["kv_b"]),
               index_q=bf(index_q, w["index_q"]),
               index_k=bf(index_k, w["index_k"]),
               index_w=bf(_f32(w["index_w"]) + along[:, None], w["index_w"]))
    return out


def _layer_kinds(z, dense):
    """``[(name, shape, std, mean)]`` of one layer's tensors but its routed
    experts' (those are drawn an expert at a time, :func:`expert_weights`)."""
    h = z["h"]
    kinds = [("ln1_g", (h,), _G, 1.0), ("ln2_g", (h,), _G, 1.0)] \
        + _attn_kinds(z, dict(z["full"]))
    if dense:
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


def _drawn_layer(z, key, layer, draw):
    dense = layer < z["dense_layers"]
    return {name: draw(key, 100 + i, layer, shape, std, mean)
            for i, (name, shape, std, mean) in enumerate(
                _layer_kinds(z, dense))}


def layer_weights(z, key, layer, draw=_tensor, bias=None):
    """Main layer ``layer``'s tensors; ``bias`` (an expert layer's row of
    :func:`balanced_biases`) stands in the drawn selection bias."""
    w = _distilled(z, dict(z["full"]), _drawn_layer(z, key, layer, draw),
                   _embed_mean(z, key, draw))
    return w if bias is None else dict(w, select_bias=bias)


def _next_phase(vocab, a, b):
    ids = np.arange(vocab, dtype=np.int64)
    groups = vocab // PHASES
    return (PHASES * ((a * (ids // PHASES % groups) + b) % groups)
            + (ids + 1) % PHASES) % vocab


def successor(vocab):
    """``succ [vocab]``: every id's one likely next id, of the next phase
    (a permutation where ``PHASES`` divides ``vocab``)."""
    return _next_phase(vocab, SUCC_A, SUCC_B)


def rivals(vocab):
    """``[len(RIVALS), vocab]``: further next ids of the same phase as the
    successor — the head gives them the successor's lead after an id of
    phase 0."""
    return np.stack([_next_phase(vocab, a, b) for a, b in RIVALS])


def unread(vocab):
    """``[vocab]`` bool: the ids the module cannot read (phase 0)."""
    return np.arange(vocab) % PHASES == 0


def _blind(z, key, draw):
    """What the module takes an unreadable id for: one vector ``[h]``,
    which the head's column ``BLIND`` carries as a column carries its
    predecessor's embedding."""
    return _f32(draw(key, 13, MTP_DRAW, (z["h"],), _EMBED, 0.0))


def global_weights(z, key, draw=_tensor):
    """``embed`` is the drawn table plus the embeddings' common component
    (one vector, added to every row), coordinate 0 the flag of the ids the
    module cannot read; ``head`` the drawn matrix plus the successor
    component (the docstring's)."""
    g = {name: draw(key, i, 0, shape, std, mean)
         for i, (name, shape, std, mean) in enumerate(_global_kinds(z))}
    mean = g.pop("embed_mean")
    vocab = z["vocab"]
    hard = unread(vocab)
    embed = (_f32(g["embed"]) + _f32(mean)).at[:, 0].set(
        _FLAG * _EMBED * hard)
    g["embed"] = embed.astype(jnp.bfloat16)
    lead = _f32(g["embed"]).T
    follow = jnp.zeros_like(lead).at[:, successor(vocab)].add(lead) \
        .at[:, BLIND].add(_blind(z, key, draw))
    for rival in rivals(vocab):
        follow = follow.at[:, rival[hard]].add(lead[:, hard])
    g["head"] = (_f32(g["head"])
                 + (_SUCC / z["h"]) * follow).astype(jnp.bfloat16)
    return g


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


def _mixed(copied, own, mean=0.0):
    """A copy that went its own way a little: variance kept (of a gain,
    the scatter about its ``mean``).  Rounded by ``reduce_precision``: a
    jitted build drops a plain convert to bfloat16 and back, and what is
    computed FROM the mix (the distilled index key) would then differ from
    a tensor-by-tensor build's."""
    keep = np.sqrt(1.0 - _MTP_OWN ** 2)
    mix = keep * _f32(copied) + _MTP_OWN * (_f32(own) - mean) \
        + mean * (1.0 - keep)
    return jax.lax.reduce_precision(mix, 8, 7).astype(jnp.bfloat16)


def _mean_of(kinds):
    return {name: mean for name, _, _, mean in kinds}


def mtp_weights(z, key, draw=_tensor, bias=None):
    """The module's tensors but its routed experts': the input norms,
    ``eh_proj`` ``[2h, h]``, the block — main layer ``layers - 1``'s drawn
    tensors mixed with an own draw, the indexer distilled from the mixed
    attention —, and the last norm (the main model's).  ``bias``: as
    :func:`layer_weights`'."""
    h, last = z["h"], z["layers"] - 1
    kinds = _layer_kinds(z, dense=False)
    means = _mean_of(kinds)
    copied = _drawn_layer(z, key, last, draw)
    own = _drawn_layer(z, key, MTP_DRAW, draw)
    w = {n: _mixed(copied[n], own[n], means[n]) for n in copied}
    w = _distilled(z, dict(z["full"]), w, _embed_mean(z, key, draw))
    e_g = draw(key, 10, MTP_DRAW, (h,), _G, 1.0)
    rms = float(np.sqrt(_EMBED ** 2 + _EMBED_MEAN ** 2))
    top = jnp.diag(rms / _f32(e_g))
    # the flag's row: an unreadable id reads as _MISTAKE x the blind vector
    top = top.at[0].set(rms / _f32(e_g)[0] * _MISTAKE / (_FLAG * _EMBED)
                        * _blind(z, key, draw))
    bottom = _f32(draw(key, 12, MTP_DRAW, (h, h), _EH, 0.0))
    glob = {name: draw(key, i, 0, shape, std, mean)
            for i, (name, shape, std, mean) in enumerate(_global_kinds(z))
            if name == "lnf_g"}
    w.update(embed_norm_g=e_g,
             hidden_norm_g=draw(key, 11, MTP_DRAW, (h,), _G, 1.0),
             eh_proj=jnp.concatenate([top, bottom]).astype(jnp.bfloat16),
             head_norm_g=glob["lnf_g"])
    return w if bias is None else dict(w, select_bias=bias)


def mtp_expert_weights(z, key, expert):
    copied = expert_weights(z, key, z["layers"] - 1, expert)
    own = expert_weights(z, key, MTP_DRAW, expert)
    return {n: _mixed(copied[n], own[n]) for n in copied}


# --------------------------------------------------------------------- #
# The program's side: its module, and its parameter tree from the seed
# --------------------------------------------------------------------- #
def program_model(model, **overrides):
    """The program's own module at the file's sizes, holding the file's
    share of the experts."""
    from deepspeed_tpu.models.glm5 import glm5_model
    z = sizes_of(model)                  # refuses what the reference lacks
    return glm5_model(model, held_experts=z["held"],
                      **{"dtype": "bfloat16", **overrides})


_ATTN_LEAVES = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
                "o_proj", "index_q", "index_k", "index_k_norm_scale",
                "index_k_norm_bias", "index_w")
_BLOCK_LEAVES = {        # a block's leaf path -> the plain tensor
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
}
_GLOBAL_LEAVES = {("embed_tokens", "embedding"): "embed",
                  ("final_norm", "scale"): "lnf_g",
                  ("lm_head", "kernel"): "head"}
_MTP_LEAVES = {("embed_norm", "scale"): "embed_norm_g",
               ("hidden_norm", "scale"): "hidden_norm_g",
               ("eh_proj", "kernel"): "eh_proj",
               ("head_norm", "scale"): "head_norm_g"}
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
        blocks, leaves = {}, []

        def block_leaf(at, names):
            """``at``: a main layer's index, or "mtp"."""
            if names[-1] in _EXPERT_LEAVES:
                one = (lambda e: mtp_expert_weights(z, key, e)) \
                    if at == "mtp" else \
                    (lambda e: expert_weights(z, key, at, e))
                return jax.vmap(lambda e: one(e)[_EXPERT_LEAVES[names[-1]]])(
                    first + jnp.arange(count))
            if at not in blocks:
                bias = _bias_row(z, biases, at)
                blocks[at] = mtp_weights(z, key, bias=bias) if at == "mtp" \
                    else layer_weights(z, key, at, bias=bias)
            return blocks[at][_BLOCK_LEAVES[names]]

        for path, leaf in flat:
            names = tuple(p.key for p in path)[1:]       # drop 'params'
            if names[0].startswith("layers_"):
                x = block_leaf(int(names[0][7:]), names[1:])
            elif names[0] != "mtp":
                x = glob[_GLOBAL_LEAVES[names]]
            elif names[1] == "block":
                x = block_leaf("mtp", names[2:])
            else:
                if "mtp" not in blocks:
                    blocks["mtp"] = mtp_weights(
                        z, key, bias=_bias_row(z, biases, "mtp"))
                x = blocks["mtp"][_MTP_LEAVES[names[1:]]]
            leaves.append(x.reshape(leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = seed_key(seed)
    return build(key, balanced_biases(z, key))


# --------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------- #
def _parts(precision):
    """``precision`` -> what each part of the model computes in:
    ``(everything else, the experts' matmuls, whether the indexer selects,
    whether the held experts add their part)``."""
    if precision == "float8_experts":
        return "bfloat16", "float8", True, True
    if precision == "recent_topk":
        return "bfloat16", "bfloat16", False, True
    if precision == "held_dropped":
        return "bfloat16", "bfloat16", True, False
    if precision == "stale_window_row":      # the rows differ, not the math
        return "bfloat16", "bfloat16", True, True
    return precision, precision, True, True


def _rope(t, theta, dims=None, start=0):
    """Rotary positions ``start ..`` on the first ``dims`` features of
    ``t [S, ..., D]`` (default all), pairs ``(2i, 2i + 1)``."""
    dims = dims or t.shape[-1]
    half = dims // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = (start + jnp.arange(t.shape[0])).astype(jnp.float32)[:, None] \
        * freqs
    ang = ang.reshape((t.shape[0],) + (1,) * (t.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = t[..., 0:dims:2], t[..., 1:dims:2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                       axis=-1).reshape(t.shape[:-1] + (dims,))
    return jnp.concatenate([turned, t[..., dims:]], axis=-1)


def _kept(z, a, x, c_q, w, select):
    """A function ``block start -> kept [B, S] bool``: the indexer's top-k
    (in float32 always) of the keys whose index key comes from ``x`` — the
    KEY side's input; ``c_q`` is the query side's."""
    S = x.shape[0]
    keys = jnp.arange(S)[None, :]
    rows = lambda start: (start + jnp.arange(QUERY_BLOCK))[:, None]
    k = a["index_topk"]
    if not select:
        return lambda start: (keys <= rows(start)) & (keys > rows(start) - k)
    J, D = a["index_heads"], a["index_dim"]
    hi = lambda u, v: jnp.matmul(u, _f32(v), precision=HIGHEST)
    qi = _rope(hi(c_q[0], w["index_q"]).reshape(S, J, D), a["theta"],
               a["rope"])
    ki = _rope(_layer_norm(hi(x, w["index_k"]), w["index_k_norm_scale"],
                           w["index_k_norm_bias"], z["eps"]),
               a["theta"], a["rope"])
    wi = hi(c_q[1], w["index_w"]) * (J ** -0.5 * D ** -0.5)

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


def _attention(z, a, x, w, precision, rows_from=None):
    """Latent attention of ONE sequence ``x [S, h]`` (normed input).
    ``rows_from`` (default ``x``): the normed input that the CACHED rows —
    latent row and index key of every position — are computed from, where
    it is not the queries' own (the ``stale_window_row`` control)."""
    outer, _, select, _ = _parts(precision)
    S, H = x.shape[0], a["heads"]
    kx = x if rows_from is None else rows_from
    c_q = _store(_rms_norm(_mm(x, w["q_a"], outer), w["q_a_norm"], z["eps"]),
                 outer)
    kv = _mm(kx, w["kv_a"], outer)
    row = _store(jnp.concatenate([
        _rms_norm(kv[:, :a["kv_rank"]], w["kv_a_norm"], z["eps"]),
        _rope(kv[:, a["kv_rank"]:], a["theta"])], -1), outer)
    c_kv, k_r = row[:, :a["kv_rank"]], row[:, a["kv_rank"]:]
    kv_b = w["kv_b"].reshape(a["kv_rank"], H, a["nope"] + a["v"])
    k_nope = _mm(c_kv, kv_b[..., :a["nope"]].reshape(a["kv_rank"], -1),
                 outer).reshape(S, H, a["nope"])
    v = _mm(c_kv, kv_b[..., a["nope"]:].reshape(a["kv_rank"], -1),
            outer).reshape(S, H, a["v"])
    # the index query from the query latent, its head weights from the
    # queries' own input, the index KEY from the rows' input
    kept = _kept(z, a, kx, (c_q, x), w, select)
    scale = 1.0 / np.sqrt(a["nope"] + a["rope"])
    r = lambda t: _round(t, outer)
    k_nope, k_r, v = r(k_nope), r(k_r), r(v)     # matmul operands, once

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
        p = jax.nn.softmax(jnp.where(kept(start)[None], s * scale, -1e30),
                           axis=-1)
        out = jnp.einsum("hqs,shd->qhd", r(_store(p, outer)), v,
                         precision=HIGHEST)
        return _mm(_store(out, outer).reshape(QUERY_BLOCK, -1), w["o_proj"],
                   outer)

    return jax.lax.map(block, jnp.arange(0, S, QUERY_BLOCK)).reshape(S, -1)


def _scores(a, w, outer):
    """The router's scores ``[S, experts]`` of ``a [S, h]``: float32, kept."""
    return jax.nn.sigmoid(jnp.matmul(
        _round(a, outer), _round(_f32(w["router"]), outer),
        precision=HIGHEST))


def expert_layer(z, key, layer, a, w, precision, held=None, shared=True):
    """The routed expert layer on ``a [S, h]`` (``layer``: a main layer's
    index, or ``"mtp"``): the experts ``held`` (default the configuration's
    share; ``(0, experts)`` is the uncut layer) each computed over every
    token and masked by the token's choice, plus — ``shared`` — the shared
    expert.  Nothing held is dropped."""
    outer, inner, _, routed = _parts(precision)
    first, count = held or z["held"]
    scores = _scores(a, w, outer)
    _, top_i = jax.lax.top_k(scores + _f32(w["select_bias"]), z["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=1)
    if z["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * z["scaling"]

    def one(acc, e):
        ew = mtp_expert_weights(z, key, e) if layer == "mtp" \
            else expert_weights(z, key, layer, e)
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(a, ew["wg"], ew["wu"],
                                               ew["wd"], inner), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(a),
                          first + jnp.arange(count if routed else 0))
    if shared and z["shared"]:         # an expert too: ``inner``
        acc = acc + _swiglu(a, w["shared_gate"], w["shared_up"],
                            w["shared_down"], inner)
    return _store(acc, outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _attention_jit(x, w, rows_from=None, *, sizes, precision):
    z, outer = dict(sizes), _parts(precision)[0]
    norm = lambda t: _store(_rms_norm(t, w["ln1_g"], z["eps"]), outer)
    return _store(x + _attention(
        z, dict(z["full"]), norm(x), w, precision,
        None if rows_from is None else norm(rows_from)), outer)


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "layer"))
def _ffn_jit(key, x, w, *, sizes, precision, layer):
    z, outer = dict(sizes), _parts(precision)[0]
    a = _store(_rms_norm(x, w["ln2_g"], z["eps"]), outer)
    if layer != "mtp" and layer < z["dense_layers"]:
        return _store(x + _swiglu(a, w["w_gate"], w["w_up"], w["w_down"],
                                  outer), outer)
    return _store(x + expert_layer(z, key, layer, a, w, precision), outer)


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed_jit(g, tokens, *, precision):
    return _store(_f32(g["embed"])[tokens], _parts(precision)[0])


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _final_jit(g, x, *, sizes, precision):
    z = dict(sizes)
    return _store(_rms_norm(x, g["lnf_g"], z["eps"]), _parts(precision)[0])


@functools.partial(jax.jit, static_argnames=("precision",))
def _head_jit(g, h, positions, *, precision):
    return _mm(h[positions], g["head"], _parts(precision)[0])


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _combine_jit(m, embedded, h, *, sizes, precision):
    z, outer = dict(sizes), _parts(precision)[0]
    both = jnp.concatenate(
        [_rms_norm(embedded, m["embed_norm_g"], z["eps"]),
         _rms_norm(h, m["hidden_norm_g"], z["eps"])], axis=-1)
    return _mm(_store(both, outer), m["eh_proj"], outer)


# --------------------------------------------------------------------- #
# The selection bias: the loads evened out, as ``noaux_tc`` leaves them
# --------------------------------------------------------------------- #
BALANCE_TOKENS, BALANCE_STEPS, _BALANCE_RATE, _BALANCE_DECAY = \
    1024, 200, 0.05, 0.975


def _bias_row(z, biases, at):
    """Layer ``at``'s row of :func:`balanced_biases` (``at``: a main
    layer's index, or ``"mtp"``); None for a dense layer."""
    if at == "mtp":
        return biases[-1]
    return None if at < z["dense_layers"] else biases[at - z["dense_layers"]]


@functools.partial(jax.jit, static_argnames=("sizes",))
def _balance_jit(x, w, *, sizes):
    """``noaux_tc``'s own rule run to rest on the stream ``x [S, h]`` before
    a block's expert layer: from the drawn bias, every expert's bias moved
    against its share of the ``S x top_k`` choices, in shrinking steps."""
    z = dict(sizes)
    scores = _scores(_rms_norm(x, w["ln2_g"], z["eps"]), w, "float32")
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
    """``[expert layers (+ 1, the module's), experts]`` bfloat16: the
    selection biases as aux-loss-free training leaves them — every
    published expert chosen equally often.  A trained router's loads are
    even; a DRAWN one's are not (the stream has a common component, so
    ``mean(x) . W_r`` favours some experts by 10x), and which of this
    chip's sixteen a decode window leaves untouched — weights unread —
    then moves the cell's speed by 1-4% from seed to seed (PR 40).  So the
    bias is distilled like the indexer: the float32 reference runs
    ``BALANCE_TOKENS`` drawn ids, layer by layer, and each expert layer's
    bias is balanced on the stream the balanced layers before it hand on.
    Kept a few seeds long: the program's tree and the reference read the
    same rows."""
    at = (_static(z), np.asarray(jax.random.key_data(key)).tobytes())
    if at not in _biases:
        while len(_biases) >= _BIASES_KEPT:
            del _biases[next(iter(_biases))]
        _biases[at] = _balanced(z, key)
    return _biases[at]


def _balanced(z, key):
    kw = dict(sizes=_static(z), precision="float32")
    g = global_weights(z, key, _tensor_alone)
    tokens = jax.random.randint(jax.random.fold_in(key, 91),
                                (BALANCE_TOKENS,), 0, z["vocab"])
    x = _embed_jit(g, tokens, precision="float32")
    rows = []
    for layer in range(z["layers"]):
        w = layer_weights(z, key, layer, _tensor_alone)
        x = _attention_jit(x, w, **kw)
        if layer >= z["dense_layers"]:
            rows.append(_balance_jit(x, w, sizes=kw["sizes"]))
            w["select_bias"] = rows[-1]
        x = _ffn_jit(key, x, w, layer=layer, **kw)
        del w
    if z["mtp"]:
        m = mtp_weights(z, key, _tensor_alone)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        u = _combine_jit(m, _embed_jit(g, nxt, precision="float32"),
                         _final_jit(g, x, **kw), **kw)
        rows.append(_balance_jit(_attention_jit(u, m, **kw), m,
                                 sizes=kw["sizes"]))
    return jnp.stack(rows)


def _hidden(z, key, g, tokens, precision, stale=None):
    """The main model's final-normed states ``[S, h]`` of ``tokens [S]``.
    ``stale [S]`` (the ``stale_window_row`` control): ids whose cached rows
    stand in the tokens' own — where they differ a second stream runs
    beside the first, and every query attends ITS rows."""
    kw = dict(sizes=_static(z), precision=precision)
    x = _embed_jit(g, tokens, precision=precision)
    xs = None if stale is None else _embed_jit(g, stale, precision=precision)
    biases = balanced_biases(z, key)
    for layer in range(z["layers"]):
        w = layer_weights(z, key, layer, _tensor_alone,
                          _bias_row(z, biases, layer))
        if xs is None:
            x = _attention_jit(x, w, **kw)
        else:
            x, xs = _attention_jit(x, w, xs, **kw), _attention_jit(xs, w,
                                                                   **kw)
            xs = _ffn_jit(key, xs, w, layer=layer, **kw)
        x = _ffn_jit(key, x, w, layer=layer, **kw)
        del w
    return _final_jit(g, x, **kw)


def _mtp_hidden(z, key, g, tokens, h, precision):
    """The module's last-normed states ``[S, h]`` along ``tokens``: row
    ``t`` from ``h[t]`` and token ``t + 1`` (the last row's reads id 0)."""
    kw = dict(sizes=_static(z), precision=precision)
    m = mtp_weights(z, key, _tensor_alone,
                    _bias_row(z, balanced_biases(z, key), "mtp"))
    nxt = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    u = _combine_jit(m, _embed_jit(g, nxt, precision=precision), h, **kw)
    u = _attention_jit(u, m, **kw)
    u = _ffn_jit(key, u, m, layer="mtp", **kw)
    return _final_jit({"lnf_g": m["head_norm_g"]}, u, **kw)


def _forward(z, key, tokens, positions, precision, drafts=False,
             stale=None):
    """Logits ``[R, V]`` at ``positions [R]`` of one sequence ``tokens
    [S]`` (``S`` a multiple of 64); with ``drafts`` a pair, the second the
    module's logits at the same positions."""
    g = global_weights(z, key, _tensor_alone)
    h = _hidden(z, key, g, tokens, precision, stale)
    main = _head_jit(g, h, positions, precision=precision)
    if not drafts:
        return main
    u = _mtp_hidden(z, key, g, tokens, h, precision)
    return main, _head_jit(g, u, positions, precision=precision)


def logits(z, seed, tokens, precision="float32", drafts=False):
    """All logits ``[S, V]`` of ONE sequence ``tokens [S]`` — what the CPU
    tests compare the program with; ``drafts``: ``(main, module)``."""
    return _forward(z, seed_key(seed), _padded(tokens),
                    jnp.arange(len(tokens)), precision, drafts)


def drafts(z, seed, tokens, precision="float32", pad_to=None):
    """The module's greedy guesses along ``tokens [S]``: ``d [S]``, ``d[t]``
    its guess at token ``t + 2`` from ``h_t`` and token ``t + 1`` (the last
    entry has no next token and means nothing).  ``pad_to``: the length
    every request of a cell is padded to, so that they share one compiled
    program."""
    padded = _padded(tokens, pad_to)
    _, guess = _forward(z, seed_key(seed), padded,
                        jnp.arange(padded.shape[0]), precision, drafts=True)
    return np.asarray(jnp.argmax(guess, axis=-1), np.int32)[:len(tokens)]


def accepted_along(tokens, guesses, prompt_len):
    """What a self-drafting server does along a finished request (``tokens``
    = prompt + generated, ``guesses`` = :func:`drafts` of it): ``(windows,
    accepted, rejected positions)``.  The first generated token comes with
    the admission; a window holds the committed token at ``p`` and the
    draft for ``p + 1`` (``guesses[p - 1]``), commits ``p + 1`` always and,
    the draft right, ``p + 2`` — the last window may have no room for it."""
    n, p, windows, accepted, rejected = len(tokens), prompt_len, 0, 0, []
    while p + 1 < n:
        windows += 1
        if guesses[p - 1] != tokens[p + 1]:
            rejected.append(p + 1)
            p += 1
        elif p + 2 < n:
            accepted += 1
            p += 2
        else:                   # the budget ended inside the window
            p += 1
    return windows, accepted, rejected


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


GAP_ROWS = 1536          # the longest answer a cell may ask for
# the float32 rows of the last requests compared (a calibration reads the
# same requests again under each control)
_ROWS_KEPT, _rows = 4, {}


def _reference_rows(z, seed, tokens, positions):
    """The float32 logits at ``positions``, kept a few requests long."""
    at = (_static(z), int(seed), int(positions[0]),
          np.asarray(tokens).tobytes())
    if at not in _rows:
        while len(_rows) >= _ROWS_KEPT:
            del _rows[next(iter(_rows))]
        _rows[at] = _forward(z, seed_key(seed), tokens, positions, "float32")
    return _rows[at]


def _stale_ids(z, seed, tokens, n, prompt_len):
    """The ids whose rows a server that never overwrote a rejected window
    row would hold: the tokens, but at every rejected position the
    module's rejected draft (bfloat16, as a program's module guesses)."""
    served = np.asarray(tokens)[:n]
    guess = drafts(z, seed, served, "bfloat16", pad_to=tokens.shape[0])
    _, _, rejected = accepted_along(served, guess, prompt_len)
    stale = np.asarray(tokens).copy()
    for p in rejected:
        stale[p] = guess[p - 2]
    return jnp.asarray(stale)


def gaps_under(z, seed, tokens, prompt_len, n_new, pad_to, choosers):
    """``{chooser: gaps [n_new]}`` for each of ``choosers`` (``None``: the
    served tokens), the float32 reference computed ONCE for all of them."""
    if n_new > GAP_ROWS:
        raise ValueError(f"answers of at most {GAP_ROWS} tokens")
    n = len(tokens)
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
            stale = _stale_ids(z, seed, tokens, n, prompt_len) \
                if chooser == "stale_window_row" else None
            ids = jnp.argmax(_forward(z, key, tokens, positions, chooser,
                                      stale=stale), axis=-1)
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
