"""One skeleton for the page-and-state-row model families
(``models/lfm2.py``, ``models/solar_open2.py``, ``models/granite_hybrid.py``,
``models/nemotron_h.py``) and the grouped-query attention module they share
with ``models/trinity.py``.

A model of this kind is an embedding; a list of layers; a final norm and a
head.  A layer is ``norm -> mixer -> residual -> norm -> MLP -> residual``
— or, where the family declares ``expert_blocks`` (Nemotron-H), ONE
sublayer: ``norm -> sublayer -> residual``, the sublayer a mixer OR the
expert layer, so that the layers that attend, those that keep a state and
those that route experts are three counts (:meth:`Hybrid.state_layers`,
:meth:`Hybrid.expert_layers`), not two.  By the layer's index the mixer is
either

* grouped-query softmax attention (:class:`GroupedQueryAttention`) whose K/V
  rows lie in LANE pages under the slot's page table, growing with the
  context, or
* the family's STATE MIXER, a recurrence that hands a FIXED-SIZE state from a
  token to the next and keeps no row a position.  In the slot engine that
  state is a kind of its own beside the K/V pages (``paging.SlotPages``,
  ``state_kinds``): a pool a kind, one row a slot, row 0 the trash row, the
  slot's row index the LAST entry of its page-table row.  A request's first
  chunk starts every kind from zeros, a chunk leaves each as it stands after
  its last REAL row, and a dead lane of a decode block writes the trash row.

A family's file holds its config, its state mixer, and a :class:`HybridModel`
subclass whose static ``declare(config)`` returns one :class:`Hybrid`: the
names its checkpoint gives the layer's parts, which layers attend, the
attention's :class:`Attention`, the state kinds and the MLPs.  Everything the
slot engine asks of a model (``models/contract.py``) is written here, once,
from that declaration; a family overrides ``_embed``, ``_head`` or
``residual`` where its arithmetic differs and nothing else.  What the
skeleton reads of a family's config: ``vocab_size``, ``hidden_size``,
``num_layers``, ``max_seq_len``, ``dtype`` and ``jnp_dtype``.

A state mixer is called ``mixer(u [T, hidden], state, start, last, live=)``
and returns ``(out, pools)``.  ``state`` is ``None`` (a sequence from its
start, nothing kept) or ``(*pools, layer index among the state layers,
rows)``, the pools in the declaration's order — ``rows [N]`` for one token a
lane (a STEP: ``start`` None, row ``n`` lane ``n``'s token, ``live [N]`` the
lanes that are), a scalar row for a chunk of one slot (``T`` consecutive
positions from the scalar ``start``, ``last`` its last real row: the padded
tail reaches no state).  ``pools`` is the tuple of pools as they stand after
the call.

These are serving models: :meth:`HybridModel.decode` over the slot engine's
pools and a plain uncached forward (``__call__``).  They have no
``generate()`` cache and no training step (the state scans and the dropless
expert kernels have no VJP).
"""

import contextlib
import dataclasses
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.models.parts import _Mlp, _Norm, _rms, causal_pairs
from deepspeed_tpu.models.transformer import _rope, reference_attention
from deepspeed_tpu.moe.layer import MoE

CHUNK_CAP = 2048             # whole 512-query blocks of the paged chunk kernel


@dataclasses.dataclass(frozen=True)
class Attention:
    """One grouped-query attention layer, as its family declares it — and
    the ``cfg`` ``ops/transformer/registry.py::write_and_attend`` is handed:
    on the path these layers take it reads ``attention_scale`` and the two
    constants below off it and nothing else."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: Any
    qk_norm_eps: Optional[float] = None    # an RMSNorm over each HEAD of q, k
    rope_theta: Optional[float] = None     # rope on the whole head, half-split
    out_gate: bool = False       # the heads' outputs times sigmoid(u W_gate)
    attention_scale: Optional[float] = None    # stated; else head_dim ** -0.5
    window: Optional[int] = None           # the token and window - 1 before it
    out_proj: str = "o_proj"               # the output projection's name ...
    out_by_head: bool = False    # ... and whether its kernel keeps [H, D, :]
    scopes: bool = True          # the steps' ``attn.*`` scopes (LFM2: none)
    # int8 K/V pools and int8 score matmuls are ``models/transformer.py``'s
    kv_cache_quant = False
    decode_int8_matmuls = False


class GroupedQueryAttention(nn.Module):
    """q / k / v projections, QK-norm, rope, ``write_and_attend``, an output
    gate and the output projection, each step as the :class:`Attention`
    says; no biases."""
    spec: Attention

    @nn.compact
    def __call__(self, u, positions, cache=None):
        """``u [B, S, hidden]``, ``positions [B, S]``; ``cache``: what
        ``write_and_attend`` takes (the K/V pools — lane pools under the
        slot's page table, or rings and their table under the ``ring``
        marker —, this layer's index in them, the table) or None for plain
        causal attention over ``u`` alone."""
        z = self.spec
        H, KVH, D = z.num_heads, z.num_kv_heads, z.head_dim
        scope = jax.named_scope if z.scopes \
            else lambda name: contextlib.nullcontext()
        dense = lambda n, name: nn.DenseGeneral(
            (n, D), use_bias=False, dtype=z.dtype, name=name)
        q, k = dense(H, "q_proj")(u), dense(KVH, "k_proj")(u)
        v = dense(KVH, "v_proj")(u)
        if z.qk_norm_eps is not None:
            gain = lambda name: self.param(name, nn.initializers.ones, (D,),
                                           jnp.float32)
            with scope("attn.qk_norm"):
                q = _rms(q, gain("q_norm"), z.qk_norm_eps)
                k = _rms(k, gain("k_norm"), z.qk_norm_eps)
        if z.rope_theta is not None:
            with scope("attn.rope"):
                q, k = _rope(q, k, positions, D, z.rope_theta)
        if cache is None:
            out = reference_attention(q, k, v, causal=True, window=z.window,
                                      scale=z.attention_scale)
        else:
            from deepspeed_tpu.ops.transformer.registry import (
                write_and_attend)
            with scope("attn.window" if z.window else "attn.full"):
                out, cache = write_and_attend(z, q, k, v, positions, cache,
                                              window=z.window)
        if z.out_by_head:
            return nn.DenseGeneral(
                z.hidden_size, axis=(-2, -1), use_bias=False, dtype=z.dtype,
                name=z.out_proj)(out), cache
        if z.out_gate:
            gate = nn.Dense(H * D, use_bias=False, dtype=z.dtype,
                            name="gate_proj")(u)
            with scope("attn.out_gate"):
                out = out.reshape(out.shape[:2] + (H * D,)) \
                    * jax.nn.sigmoid(gate.astype(jnp.float32)) \
                    .astype(out.dtype)
        else:
            out = out.reshape(out.shape[:2] + (H * D,))
        return nn.Dense(z.hidden_size, use_bias=False, dtype=z.dtype,
                        name=z.out_proj)(out), cache


@dataclasses.dataclass(frozen=True)
class StateKind:
    """One pool behind the slot's state row: the cache's key, a row's shape
    (or ``row(dtype)``, where the shape follows the pool's dtype) and the
    pool's dtype — None: the server's."""
    name: str
    row: Union[Tuple[int, ...], Callable[[Any], Tuple[int, ...]]]
    dtype: Any = None


@dataclasses.dataclass(frozen=True)
class Hybrid:
    """What a family declares to the skeleton.  The names are its
    checkpoint's: flax names a submodule by the attribute it is assigned to,
    so they are the parameter tree's paths and the ``op_name`` frames the
    profiler's by-part table reads."""
    norm_eps: float
    attention_layers: Tuple[int, ...]      # the rest run the state mixer
    attention: Attention                   # as ``self_attn``
    mixer: Tuple[str, Any]       # its name and its class, built on the config
    state: Tuple[StateKind, ...]
    # the family's arguments of ``moe/layer.py::MoE``, as ``moe_mlp``, beside
    # what every family of this kind has: dropless experts at the config's
    # ``hidden_size`` and dtype — gated SiLU ones unless it says otherwise
    # (``gated``, ``activation``)
    moe: dict
    # a dense SwiGLU's name, its width and how many FIRST layers carry it
    dense: Tuple[Optional[str], int, int] = (None, 0, 0)
    # the norm before each sublayer of a layer (one name: one sublayer)
    norms: Tuple[str, ...] = ("input_layernorm", "post_attention_layernorm")
    final_norm: str = "norm"
    # a layer is ONE sublayer where given: these layers are the expert layer
    # alone (after any ``dense`` ones), ``attention_layers`` attention alone
    # and the rest the state mixer alone.  None: a mixer AND an MLP a layer
    expert_blocks: Optional[Tuple[int, ...]] = None
    tied: bool = False           # the head is the embedding; else ``lm_head``
    # the prefix of the family's work counters — ``<p>_scan_rows``,
    # ``<p>_state_rows``, beside ``full_keys`` — where it has a chunk path
    # of its own: up to ``CHUNK_CAP`` positions, one chunk a dispatch (the
    # state is a slot's)
    work: Optional[str] = None

    def sublayers(self, i):
        """Layer ``i``'s sublayers in order, each by the attribute it is."""
        mlp = self.dense[0] if i < self.dense[2] else "moe_mlp"
        if self.expert_blocks is not None and i in self.expert_blocks:
            return (mlp,)
        mixer = "self_attn" if i in self.attention_layers else self.mixer[0]
        return (mixer,) if self.expert_blocks is not None else (mixer, mlp)

    def state_layers(self, num_layers):
        """The layers whose mixer is the state mixer: the state pools'
        layers, in order."""
        return tuple(i for i in range(num_layers)
                     if self.mixer[0] in self.sublayers(i))

    def expert_layers(self, num_layers):
        """How many layers route experts: the load vector's rows."""
        return sum("moe_mlp" in self.sublayers(i) for i in range(num_layers))


class HybridLayer(nn.Module):
    family: Any                  # the model's class: ``declare``, ``residual``
    config: Any
    layer_idx: int

    def setup(self):
        cfg, i = self.config, self.layer_idx
        d = self.family.declare(cfg)
        build = {
            "self_attn": lambda: GroupedQueryAttention(d.attention),
            d.mixer[0]: lambda: d.mixer[1](cfg),
            d.dense[0]: lambda: _Mlp(d.dense[1], cfg.jnp_dtype),
            "moe_mlp": lambda: MoE(**{
                "hidden_size": cfg.hidden_size, "capacity_factor": None,
                "dtype": cfg.jnp_dtype, "gated": True, "activation": nn.silu,
                **d.moe})}
        for norm, name in zip(d.norms, d.sublayers(i)):
            setattr(self, norm, _Norm(d.norm_eps))
            setattr(self, name, build[name]())

    def __call__(self, x, mix, live=None):
        """``mix(mixer, normed x) -> (out, cache)``: the call form the model
        chose (chunk or step) with this layer's cache — None from a layer
        that is its experts alone."""
        d, cfg = self.family.declare(self.config), self.config
        cache = None
        for norm, name in zip(d.norms, d.sublayers(self.layer_idx)):
            h = getattr(self, norm)(x)
            if name == "moe_mlp":
                y, _, _ = self.moe_mlp(h, train=False, live=live)
            elif name == d.dense[0]:
                y = getattr(self, name)(h)
            else:
                y, cache = mix(getattr(self, name), h)
            x = self.family.residual(cfg, x, y)
        return x, cache


class HybridModel(nn.Module):
    config: Any

    @staticmethod
    def residual(cfg, x, t):
        return x + t

    def setup(self):
        cfg, d = self.config, self.declare(self.config)
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.jnp_dtype)
        self.layers = [HybridLayer(type(self), cfg, i)
                       for i in range(cfg.num_layers)]
        setattr(self, d.final_norm, _Norm(d.norm_eps))
        if not d.tied:
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                    dtype=cfg.jnp_dtype)

    def _embed(self, ids):
        return self.embed_tokens(ids)

    def _head(self, h, at=None):
        """Logits of ``h [B, S, hidden]``, or of row ``at[b]`` of each."""
        d = self.declare(self.config)
        with jax.named_scope("head.logits"):
            if at is not None:
                h = jnp.take_along_axis(
                    h, at.astype(jnp.int32)[:, None, None], axis=1)
            h = getattr(self, d.final_norm)(h)
            return self.embed_tokens.attend(h) if d.tied else self.lm_head(h)

    def __call__(self, batch):
        """Logits ``[B, S, V]`` of ``batch["input_ids"] [B, S]``: the plain
        causal forward, a row at a time, no cache."""
        d, rows = self.declare(self.config), []
        for ids in batch["input_ids"]:
            x = self._embed(ids)
            positions = jnp.arange(ids.shape[0])[None]
            for i, layer in enumerate(self.layers):
                # (a layer that is its experts alone calls neither)
                if i in d.attention_layers:
                    mix = lambda op, u: (op(u[None], positions)[0][0], None)
                else:
                    mix = lambda op, u: (op(u, start=0)[0], None)
                x, _ = layer(x, mix)
            rows.append(self._head(x[None])[0])
        return jnp.stack(rows)

    # ---- the serving path ---- #
    def slot_contract(self):
        """For the slot engine (``models/contract.py``): K/V pages under the
        slot's table for the attention layers; behind its STATE ROW the
        state layers' kinds; dropless experts in the layers that route
        them, where the family holds a share of them the held ones."""
        cfg, d = self.config, self.declare(self.config)
        held = d.moe.get("held_experts")
        own_path = {} if d.work is None else dict(
            lane_layers=len(d.attention_layers), kv_pages=True,
            chunk_cap=CHUNK_CAP, chunk_fault=self._chunk_fault,
            own_chunk_path=True, chunk_work=self._chunk_work,
            block_work=self._block_work,
            work_counters=(d.work + "_scan_rows", d.work + "_state_rows",
                           "full_keys"))
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=cfg.num_layers,
            state_kinds=tuple(kind.name for kind in d.state),
            routes_experts=True, holds_share=held is not None,
            expert_layers=d.expert_layers(cfg.num_layers),
            experts=(held or (0, d.moe["num_experts"]))[1], **own_path)

    @staticmethod
    def _chunk_fault(chunk):
        from deepspeed_tpu.ops.transformer.registry import MAX_CHUNK_S
        if chunk > MAX_CHUNK_S and chunk % MAX_CHUNK_S:
            return (f"a chunk over {MAX_CHUNK_S} is whole {MAX_CHUNK_S}-query "
                    f"blocks of the paged chunk kernel; {chunk} is not")
        return None

    def _chunk_work(self, start, end, page_size, ring_pages, layers):
        """What a prefill chunk over REAL positions ``start .. end - 1``
        does, as its dispatch span's args: ``<p>_scan_rows`` — positions x
        state layers the state scan advanced over —, ``<p>_state_rows`` —
        state rows read and written, one a state layer — and ``full_keys``,
        (query, key) pairs the attention layers attend."""
        return self._work(end - start, 1, causal_pairs(start, end, end))

    def _block_work(self, live, ring_pages, layers):
        """The same for a decode block, from ``live`` — ``(context, steps)``
        a live slot: a step scans one position and moves one state row a
        live lane and state layer."""
        steps = sum(n for _, n in live)
        return self._work(steps, steps, sum(
            first + i for first, n in live for i in range(n)))

    def _work(self, scanned, moved, keys):
        d = self.declare(self.config)
        attention = len(d.attention_layers)
        state = len(d.state_layers(self.config.num_layers))
        return {d.work + "_scan_rows": state * scanned,
                d.work + "_state_rows": state * moved,
                "full_keys": attention * keys}

    def init_paged_cache(self, num_pages, page_size, dtype=None,
                         state_rows=1):
        """``k`` / ``v [attention layers, num_pages, page, KV heads x
        head_dim]`` behind the slot's page table, and a pool a declared
        state kind behind its state row, ``[state layers, state_rows, ...a
        row's shape]`` (``paging.SlotPages`` sizes them: trash + one row a
        slot) in ``dtype`` or, where the kind states one, its own (a float32
        state is summed into over the whole context whatever the server's
        dtype).  In each the row's index is a LEADING dimension because XLA
        tiles the last two: were it one of them, a slot's row would be a
        sublane of every tile it touches and a step's write-back a masked
        store a tile."""
        cfg, d = self.config, self.declare(self.config)
        dtype = dtype or cfg.jnp_dtype
        z, attention = d.attention, len(d.attention_layers)
        kv = (attention, int(num_pages), int(page_size),
              z.num_kv_heads * z.head_dim)
        rows = (len(d.state_layers(cfg.num_layers)), int(state_rows))
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                **{kind.name: jnp.zeros(
                    rows + tuple(kind.row(dtype) if callable(kind.row)
                                 else kind.row), kind.dtype or dtype)
                   for kind in d.state}}

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None):
        """The slot programs' call: a prefill chunk of one slot
        (``input_ids [1, C]``, scalar ``start_pos``) or one token a lane
        (``[N, 1]``, ``start_pos [N]``).  ``cache["pages"]`` is the table
        row(s): the slot's pages, then its state row."""
        d = self.declare(self.config)
        per_row = jnp.ndim(start_pos) == 1
        lift = (lambda t: t[:, None]) if per_row else (lambda t: t[None])
        kv = {"k": cache["k"], "v": cache["v"]}
        pools = tuple(cache[kind.name] for kind in d.state)
        flat_live = None if live is None else live.reshape(-1)
        with jax.named_scope("slots.tables"):
            table, rows = cache["pages"][:, :-1], cache["pages"][:, -1]
            ids = input_ids[:, 0] if per_row else input_ids[0]
            if per_row:
                positions = start_pos[:, None]
                marker = {"per_row": jnp.zeros((), jnp.int32)}
            else:
                positions = (start_pos
                             + jnp.arange(input_ids.shape[1]))[None]
                marker = {"page_runs": cache["page_runs"]} \
                    if "page_runs" in cache else {}
                rows = rows[0]
            last = None if logits_at is None \
                else logits_at[0].astype(jnp.int32)

        # a layer calls its ``mix`` inside its own call: both read ``kv``,
        # ``pools``, ``attended`` and ``scanned`` as the loop below has them
        # then
        def attend(op, u):
            out, new = op(lift(u), positions, {
                **kv, "pages": table, **marker,
                "layer": jnp.asarray(attended, jnp.int32)})
            return (out[:, 0] if per_row else out[0]), new

        def scan(op, u):
            state = (*pools, scanned, rows)
            if per_row:
                return op(u, state, live=flat_live)
            return op(u, state, start_pos, last)

        state_layers = d.state_layers(self.config.num_layers)
        x, attended, scanned = self._embed(ids), 0, 0      # layers so far
        for i, layer in enumerate(self.layers):
            if i in d.attention_layers:
                x, new = layer(x, attend, live=flat_live)
                kv, attended = {"k": new["k"], "v": new["v"]}, attended + 1
            elif i in state_layers:
                x, pools = layer(x, scan, live=flat_live)
                scanned += 1
            else:                        # its experts alone: no cache
                x, _ = layer(x, None, live=flat_live)
        with jax.named_scope("slots.tables"):
            h = lift(x)
        return self._head(h, logits_at), {
            **kv, **{kind.name: pool for kind, pool in zip(d.state, pools)}}
