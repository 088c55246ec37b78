"""Sharded MoE: gating + expert-parallel dispatch.

TPU-native re-design of reference ``deepspeed/moe/sharded_moe.py``
(``top1gating:179``, ``top2gating:277``, ``TopKGate:343``, ``MOELayer:420``,
``_AllToAll:90``).  The reference dispatches tokens with an explicit
``all_to_all_single`` over an expert process group; here dispatch is the
GShard einsum formulation — dispatch/combine tensors contracted against
expert-sharded arrays, letting GSPMD place the all-to-alls on ICI:

    expert_in  = einsum('tec,tm->ecm', dispatch, x)   # → a2a when E sharded
    expert_out = expert_fn(expert_in)                 # E sharded over 'ep'
    y          = einsum('ecm,tec->tm', expert_out, combine)

Capacity, token dropping, load-balancing aux loss, and the noisy gate
policies keep the reference's semantics.
"""

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp


def _one_hot(idx, num):
    return jax.nn.one_hot(idx, num, dtype=jnp.float32)


def _capacity(num_tokens, num_experts, capacity_factor, min_capacity, k=1):
    cap = int(np.ceil(k * num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def top1gating(logits, capacity_factor=1.0, min_capacity=4,
               noisy_gate_policy=None, rng=None, drop_tokens=True,
               used_token_mask=None):
    """Top-1 gating (Switch-style; reference ``sharded_moe.py:179``).

    logits: [T, E].  Returns (aux_loss, combine [T,E,C], dispatch bool
    [T,E,C], exp_counts [E]).
    """
    T, E = logits.shape
    C = _capacity(T, E, capacity_factor, min_capacity, k=1)
    if noisy_gate_policy == "RSample" and rng is not None:
        logits_for_choice = logits + jax.random.normal(rng, logits.shape) / E
    else:
        logits_for_choice = logits
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(logits_for_choice, axis=-1)          # [T]
    mask1 = _one_hot(expert_idx, E)                               # [T, E]
    if used_token_mask is not None:
        mask1 = mask1 * used_token_mask[:, None]

    # position of each token within its expert's queue
    pos_in_expert = jnp.cumsum(mask1, axis=0) * mask1             # [T, E]
    exp_counts = jnp.sum(mask1, axis=0)
    if drop_tokens:
        keep = pos_in_expert <= C
        mask1 = mask1 * keep
        pos_in_expert = pos_in_expert * keep

    # load-balancing loss (fraction of tokens * mean gate prob per expert)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    aux_loss = jnp.sum(me * ce) * E

    gate1 = jnp.sum(gates * mask1, axis=-1, keepdims=True)        # [T, 1]
    slot = _one_hot(jnp.int32(jnp.sum(pos_in_expert, axis=-1)) - 1, C)  # [T, C]
    combine = gate1[:, :, None] * mask1[:, :, None] * slot[:, None, :]
    dispatch = combine > 0
    return aux_loss, combine, dispatch, exp_counts


def topkgating(logits, k=2, capacity_factor=1.0, min_capacity=4,
               noisy_gate_policy=None, rng=None, drop_tokens=True,
               norm_topk_prob=True):
    """Top-k gating (reference top2gating ``sharded_moe.py:277``
    generalized).  ``norm_topk_prob``: divide the chosen gates by their
    sum, as the reference does; False uses them as the softmax gives them
    (HF ``norm_topk_prob: false`` — OLMoE)."""
    T, E = logits.shape
    C = _capacity(T, E, capacity_factor, min_capacity, k=k)
    if noisy_gate_policy == "RSample" and rng is not None:
        choice_logits = logits + jax.random.normal(rng, logits.shape) / E
    else:
        choice_logits = logits
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # [T, E]

    combine = jnp.zeros((T, E, C), jnp.float32)
    used = jnp.zeros((T, E), jnp.float32)
    slots_taken = jnp.zeros((E,), jnp.float32)
    aux_masks = []
    masked_logits = choice_logits
    gate_sum = jnp.zeros((T, 1), jnp.float32)
    picks = []
    for i in range(k):
        idx = jnp.argmax(masked_logits, axis=-1)
        mask = _one_hot(idx, E)
        aux_masks.append(mask)
        pos = (jnp.cumsum(mask, axis=0) - 1) * mask + slots_taken[None, :] * mask
        if drop_tokens:
            keep = pos < C
            mask = mask * keep
        gate_i = jnp.sum(gates * mask, axis=-1, keepdims=True)    # [T,1]
        slot = _one_hot(jnp.int32(jnp.sum(pos * mask, axis=-1)), C)
        combine = combine + gate_i[:, :, None] * mask[:, :, None] * slot[:, None, :]
        gate_sum = gate_sum + gate_i
        slots_taken = slots_taken + jnp.sum(mask, axis=0)
        masked_logits = jnp.where(aux_masks[-1] > 0, -1e30, masked_logits)
        used = used + mask

    if norm_topk_prob:
        # normalize by the sum of selected gates
        combine = combine / jnp.maximum(gate_sum, 1e-9)[:, :, None]
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(aux_masks[0], axis=0)
    aux_loss = jnp.sum(me * ce) * E
    dispatch = combine > 0
    exp_counts = jnp.sum(used, axis=0)
    return aux_loss, combine, dispatch, exp_counts


top2gating = lambda logits, **kw: topkgating(logits, k=2, **kw)


class TopKGate:
    """Gate wrapper (reference ``TopKGate:343``) — functional: the engine
    owns the gate weight; this class carries hyperparameters."""

    def __init__(self, model_dim, num_experts, k=1, capacity_factor=1.0,
                 eval_capacity_factor=1.0, min_capacity=4,
                 noisy_gate_policy=None, drop_tokens=True, use_rts=True,
                 norm_topk_prob=True):
        self.model_dim = model_dim
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.noisy_gate_policy = noisy_gate_policy
        self.drop_tokens = drop_tokens
        self.norm_topk_prob = norm_topk_prob

    def __call__(self, logits, train=True, rng=None):
        cf = self.capacity_factor if train else self.eval_capacity_factor
        if self.k == 1:
            return top1gating(logits, cf, self.min_capacity,
                              self.noisy_gate_policy if train else None, rng,
                              self.drop_tokens)
        return topkgating(logits, self.k, cf, self.min_capacity,
                          self.noisy_gate_policy if train else None, rng,
                          self.drop_tokens, self.norm_topk_prob)


def moe_dispatch_combine(x, combine, dispatch, expert_fn):
    """The MOELayer dataflow (reference ``MOELayer.forward :472``):
    dispatch-einsum → experts → combine-einsum.  ``x``: [T, M]."""
    expert_in = jnp.einsum("tec,tm->ecm", dispatch.astype(x.dtype), x)
    expert_out = expert_fn(expert_in)                             # [E, C, M']
    return jnp.einsum("ecm,tec->tm", expert_out, combine.astype(expert_out.dtype))
