"""OLMoE (allenai, ``model_type: olmoe``) — HF ``config.json`` keys to a
:class:`TransformerConfig`.

The block, per HF ``OlmoeDecoderLayer``: pre-RMSNorm; attention with no
biases, an RMSNorm over the whole projected query and key vectors
(``q_norm`` / ``k_norm``) before the head split's rope; then — in EVERY
layer, there is no dense MLP and no shared expert — a router (softmax over
all ``num_experts``, top ``num_experts_per_tok``, the chosen gates used as
they are unless ``norm_topk_prob``) over SwiGLU experts of width
``intermediate_size``.  No token is ever dropped: the trunk is built
dropless (``moe_capacity_factor=None``, ``moe/dropless.py``).  Untied head.
"""

from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

# allenai/OLMoE-1B-7B-0125-Instruct, config.json
OLMOE_1B_7B = dict(
    hidden_size=2048, intermediate_size=1024, num_hidden_layers=16,
    num_attention_heads=16, num_key_value_heads=16, num_experts=64,
    num_experts_per_tok=8, norm_topk_prob=False, hidden_act="silu",
    rms_norm_eps=1e-5, rope_theta=10000.0, vocab_size=50304,
    max_position_embeddings=4096, attention_bias=False, clip_qkv=None,
    tie_word_embeddings=False)


def olmoe_config(hf=None, **overrides):
    """``hf``: a dict of HF ``config.json`` keys (default the 1B-7B
    release); ``overrides``: :class:`TransformerConfig` fields."""
    hf = {**OLMOE_1B_7B, **(hf or {})}
    if hf.get("clip_qkv") is not None:
        raise ValueError("clip_qkv is not implemented (the 1B-7B releases "
                         "leave it null)")
    if hf.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        ffn_hidden_size=hf["intermediate_size"],
        max_seq_len=hf["max_position_embeddings"],
        activation=hf["hidden_act"], gated_mlp=True,
        position_embedding="rope", rope_theta=float(hf["rope_theta"]),
        layernorm_epsilon=hf["rms_norm_eps"], rms_norm=True,
        attention_bias=bool(hf["attention_bias"]), mlp_bias=False,
        qk_norm=True, tie_word_embeddings=hf["tie_word_embeddings"],
        moe_num_experts=hf["num_experts"], moe_every=1, moe_layer_offset=0,
        moe_top_k=hf["num_experts_per_tok"],
        moe_norm_topk_prob=hf["norm_topk_prob"], moe_capacity_factor=None,
        scan_layers=False)
    base.update(overrides)
    return TransformerConfig(**base)


def olmoe_model(hf=None, **overrides):
    return Transformer(olmoe_config(hf, **overrides))
