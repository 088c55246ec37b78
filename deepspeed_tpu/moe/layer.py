"""MoE layer facade — parity with reference ``deepspeed/moe/layer.py:16``
(``MoE``) and ``moe/experts.py:10`` (``Experts``), as a flax module.

Expert parameters carry a leading expert dim E; the sharding plan places it
on the ``ep`` mesh axis (see ``EXPERT_PARAM_PATTERN`` in
``runtime/zero/partition.py``), so the dispatch/combine einsums in
``sharded_moe.py`` lower to all-to-alls over ICI and expert-parameter
gradients reduce only over the expert-data-parallel group — the semantics
``utils/groups.py:108`` builds with explicit process groups.

The DROPLESS layer (``capacity_factor=None``, ``moe/dropless.py``: the
serving path) has two routers — softmax top-k (``dropless.route``) and the
scored form (sigmoid or softmax scores + a stored selection bias, a held
share, a shared expert, zero experts) — over experts that are gated (three
matrices) or not (two); its shared expert is gated where they are; and one
decision, ``MoE._sorted``: from ``GROUPED_MIN_ROWS`` rows a call the rows
reach the experts sorted by expert (``moe.experts_grouped``), under it as
they lie (``moe.experts_gmm``) — whichever the router and the gating.
"""

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.moe import dropless
from deepspeed_tpu.moe.sharded_moe import TopKGate, moe_dispatch_combine


class ExpertsMLP(nn.Module):
    """Default expert: the standard 2-layer MLP, vectorized over experts
    (reference wraps arbitrary expert modules; ``Experts`` replicates them —
    here one einsum-batched module computes all local experts on the MXU).
    ``gated``: the three-matrix form ``(act(x wg) * (x wi)) wo`` (SwiGLU
    with ``activation=silu``; OLMoE, Mixtral); else ``act(x wi) wo``.
    ``stored_size``: the width the matrices are STORED at, ``ffn_hidden_size``
    rounded up (Nemotron-H: 1856 = 14.5 lane tiles as 1920) — the added
    columns of ``wi`` / ``wg`` and rows of ``wo`` zeros, which is exact
    where ``act(0) = 0``.

    Two call forms over the same parameters: ``experts(x [E, C, M])`` —
    the GShard batch, one capacity-sized queue an expert — and
    ``experts(tokens [T, M], routed=(combine, counts))`` — the dropless
    layer of ``moe/dropless.py``, returning ``[T, M]`` (``grouped=(local,
    gate)``: its sorted-by-expert form for a chunk's many rows)."""
    num_experts: int
    hidden_size: int
    ffn_hidden_size: int
    activation: Callable = nn.gelu
    dtype: Any = jnp.bfloat16
    use_bias: bool = False
    gated: bool = False
    stored_size: Optional[int] = None

    @nn.compact
    def __call__(self, x, routed=None, grouped=None):
        E, M, F = self.num_experts, self.hidden_size, self.ffn_hidden_size
        S = self.stored_size or F
        if S != F and (self.use_bias or S < F):
            raise ValueError("stored_size pads bias-free experts' width")
        lecun = nn.initializers.lecun_normal()

        def matrix(name, axis):
            """A ``[E, M, S]`` (``axis`` 2) or ``[E, S, M]`` (1) parameter,
            drawn at the real width and zero beyond it."""
            def padded(key, shape, dtype):
                real = tuple(F if i == axis else n
                             for i, n in enumerate(shape))
                return jnp.pad(lecun(key, real, dtype),
                               [(0, n - r) for n, r in zip(shape, real)])
            return self.param(name, lecun if S == F else padded,
                              (E, M, S) if axis == 2 else (E, S, M),
                              jnp.float32).astype(x.dtype)

        wi, wo = matrix("experts_wi", 2), matrix("experts_wo", 1)
        wg = matrix("experts_wg", 2) if self.gated else None
        if self.use_bias and (grouped is not None or routed is not None):
            raise ValueError("the dropless expert kernels carry no "
                             "per-expert biases")
        if grouped is not None:
            # a chunk's rows sorted by expert: ``(local, gate)``
            return dropless.experts_grouped(x, *grouped, wg, wi, wo,
                                            self.activation)
        if routed is not None:
            return dropless.experts(x, *routed, wg, wi, wo, self.activation)
        # x: [E, C, M]
        h = jnp.einsum("ecm,emf->ecf", x, wi)
        if self.use_bias:
            # Megatron-style experts carry per-expert biases
            bi = self.param("experts_bi", nn.initializers.zeros, (E, F),
                            jnp.float32)
            h = h + bi[:, None, :].astype(x.dtype)
        if self.gated:
            h = self.activation(jnp.einsum("ecm,emf->ecf", x, wg)) * h
        else:
            h = self.activation(h)
        y = jnp.einsum("ecf,efm->ecm", h, wo)
        if self.use_bias:
            bo = self.param("experts_bo", nn.initializers.zeros, (E, M),
                            jnp.float32)
            y = y + bo[:, None, :].astype(x.dtype)
        return y


class MoE(nn.Module):
    """Mixture-of-experts block (reference ``layer.py:16``).

    ``__call__(x)`` with x [..., M] returns (y, aux_loss, exp_counts) —
    the reference's output triple.

    ``capacity_factor=None`` is the DROPLESS layer (``moe/dropless.py``):
    no capacity and no dropped token in either regime, ``aux_loss`` 0,
    ``exp_counts`` int32 over the ``live`` tokens only — also sown as
    ``moe_stats/expert_tokens`` for a caller that applies the model with
    that collection mutable (the serving programs).
    """
    hidden_size: int
    num_experts: int = 1
    ep_size: int = 1
    k: int = 1
    capacity_factor: Optional[float] = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_residual: bool = False
    ffn_hidden_size: Optional[int] = None
    ffn_stored_size: Optional[int] = None    # ``ExpertsMLP.stored_size``
    expert: Optional[nn.Module] = None
    dtype: Any = jnp.bfloat16
    expert_bias: bool = False
    gated: bool = False
    activation: Callable = nn.gelu
    norm_topk_prob: bool = True
    # the dropless layer's further forms (config, not a fork), over gated
    # experts (three matrices) or un-gated ones (two: ``act(x wi) wo``,
    # Nemotron-H's ``relu2``) alike:
    # ``scoring="sigmoid"`` with a stored selection bias (``noaux_tc``:
    # the top-k of score + bias, gates from the scores), a shared expert
    # every token takes — gated or not as the routed ones are —, and ``held_experts=(first, count)`` — the chip's
    # share under expert parallelism: the router scores all
    # ``num_experts``, this layer holds and computes ``count`` of them
    # and adds nothing for the absent ones (``moe/dropless.py``);
    # ``gate_sum_eps`` is what a family adds to the chosen scores' sum
    # before dividing by it (LFM2: 1e-6; 0 leaves the division as it is).
    # ``noaux_tc=True`` gives ``scoring="softmax"`` the same scored
    # form (a softmax over the router's outputs, the top-k of score + a
    # stored bias), and ``zero_experts`` widens the router by that many
    # zero-compute IDENTITY experts (LongCat-Flash): outputs
    # ``num_experts ..`` are no expert — a chosen one adds its gate times
    # the layer's input, computed here for every token, on every chip
    scoring: str = "softmax"
    routed_scaling: float = 1.0
    gate_sum_eps: float = 0.0
    shared_ffn_hidden_size: int = 0
    held_experts: Optional[tuple] = None
    noaux_tc: bool = False
    zero_experts: int = 0

    @nn.compact
    def __call__(self, x, train=True, live=None):
        M = self.hidden_size
        orig_shape = x.shape
        tokens = x.reshape(-1, M)

        gate_w = self.param("gate_kernel", nn.initializers.lecun_normal(),
                            (M, self.num_experts + self.zero_experts),
                            jnp.float32)
        first, held = self.held_experts or (0, self.num_experts)
        experts = self.expert or ExpertsMLP(
            held, M, self.ffn_hidden_size or 4 * M,
            activation=self.activation, dtype=self.dtype,
            use_bias=self.expert_bias, gated=self.gated,
            stored_size=self.ffn_stored_size)
        if self.scoring == "sigmoid" or self.noaux_tc:
            if self.capacity_factor is not None:
                raise ValueError("score + bias routing is the dropless "
                                 "layer's (capacity_factor=None)")
            return self._scored(tokens, gate_w, experts, first, held,
                                live).reshape(orig_shape).astype(x.dtype), \
                0.0, None
        if self.held_experts is not None or self.shared_ffn_hidden_size \
                or self.zero_experts:
            raise ValueError("a share of the experts, a shared expert and "
                             "zero experts are implemented for the scored "
                             "form (scoring='sigmoid', or noaux_tc)")
        if self.capacity_factor is None:
            combine, exp_counts = dropless.route(
                tokens, gate_w, self.k,
                renormalize=self.norm_topk_prob and self.k > 1,
                live=None if live is None else live.reshape(-1))
            if self._sorted(tokens):
                y = experts(tokens, grouped=dropless.picks_of(
                    combine[:tokens.shape[0]], self.k))
            else:
                y = experts(tokens, routed=(combine, exp_counts))
            if not self.is_initializing():
                self.sow("moe_stats", "expert_tokens", exp_counts,
                         reduce_fn=lambda _, new: new, init_fn=lambda: None)
            return y.reshape(orig_shape).astype(x.dtype), 0.0, exp_counts

        logits = tokens.astype(jnp.float32) @ gate_w
        gate = TopKGate(M, self.num_experts, self.k, self.capacity_factor,
                        self.eval_capacity_factor, self.min_capacity,
                        self.noisy_gate_policy, self.drop_tokens,
                        norm_topk_prob=self.norm_topk_prob)
        rng = self.make_rng("gating") if (train and self.noisy_gate_policy
                                          and self.has_rng("gating")) else None
        aux_loss, combine, dispatch, exp_counts = gate(logits, train, rng)
        y = moe_dispatch_combine(tokens, combine, dispatch, experts)

        if self.use_residual:
            # residual MoE (reference layer.py use_residual): blend with a
            # dense MLP through a learned coefficient
            mlp_out = nn.Dense(M, dtype=x.dtype, name="residual_mlp")(tokens)
            coef = nn.Dense(2, dtype=x.dtype, name="coefficient")(tokens)
            coef = jax.nn.softmax(coef, axis=-1)
            y = y * coef[..., 0:1] + mlp_out * coef[..., 1:2]

        return y.reshape(orig_shape).astype(x.dtype), aux_loss, exp_counts

    def _sorted(self, tokens):
        """Whether this call's rows reach the experts sorted by expert
        (``moe.experts_grouped``: a chunk dispatch's many rows) or as they
        lie (``moe.experts_gmm``: a decode step's few) — the dropless
        layer's one decision, both routers', from the row count."""
        return not self.is_initializing() \
            and tokens.shape[0] >= dropless.GROUPED_MIN_ROWS

    def _scored(self, tokens, gate_w, experts, first, held, live):
        """``shared(x) + sum over the chosen experts that are HELD of
        gate_e * E_e(x)`` (+ the chosen zero experts' gates times ``x``);
        sows the held experts' tokens, the choices that fell on absent
        experts and — with ``zero_experts`` — those that fell on zero
        experts."""
        M, F = self.hidden_size, self.shared_ffn_hidden_size
        real = self.num_experts if self.zero_experts else None
        bias = self.param("select_bias", nn.initializers.zeros,
                          (gate_w.shape[1],), jnp.float32)
        choice, gate = dropless.route_scored(
            tokens, gate_w, bias, self.k,
            renormalize=self.norm_topk_prob and self.k > 1,
            scaling=self.routed_scaling,
            live=None if live is None else live.reshape(-1),
            sum_eps=self.gate_sum_eps, scoring=self.scoring)
        local, counts, elsewhere = dropless.held_load(choice, first, held,
                                                      real)
        if self._sorted(tokens):
            y = experts(tokens, grouped=(local, gate))
        else:
            y = experts(tokens, routed=(
                dropless.combine_of(local, gate, held), counts))
        if F:
            dense = lambda n, name: nn.Dense(n, use_bias=False,
                                             dtype=tokens.dtype, name=name)
            if self.gated:
                y = y + dense(M, "shared_down")(
                    self.activation(dense(F, "shared_gate")(tokens))
                    * dense(F, "shared_up")(tokens))
            else:
                y = y + dense(M, "shared_down")(
                    self.activation(dense(F, "shared_up")(tokens)))
        sown = [("expert_tokens", counts), ("elsewhere", elsewhere)]
        if self.zero_experts:
            with jax.named_scope("moe.zero_experts"):
                kept, picks = dropless.zero_gate(choice, gate, real)
                y = (y.astype(jnp.float32) + kept[:, None]
                     * tokens.astype(jnp.float32)).astype(y.dtype)
            sown.append(("zero", picks))
        if not self.is_initializing():
            for name, value in sown:
                self.sow("moe_stats", name, value,
                         reduce_fn=lambda _, new: new, init_fn=lambda: None)
        return y
