"""Slot programs for the continuous-batching serving engine.

The fixed-shape contract (``docs/serving.md``): the KV workspace is one
page POOL ``[L, num_pages, page_size, KVH*D]`` shared by all slots; the
per-slot page tables (``[num_slots, pages_per_slot]`` int32 — the host
allocates, frees and shares pages, ``paging.py``) and every piece of
per-slot occupancy state (last token, write position, live flag, steps
remaining, eos id) are TRACED arguments — so admissions, EOS
retirements, request and page churn never change a program shape, and
exactly ONE decode-step executable serves the whole server lifetime
(compiled once per process — the serving programs bypass the persistent
caches, see ``ServingEngine.__init__``).

The programs:

* :func:`make_decode_block_fn` — the decode step.  One call advances every
  slot ``block`` tokens through the model's per-row decode path (rank-1
  ``start_pos`` selects the per-row cache write and length masks, both
  routed through the page table; free/retired lanes write masked garbage
  to the trash page).  The pool AND the slot state are donated — the
  workspace updates in place.
* :func:`make_chunk_fn` — one admission-prefill dispatch: up to
  :func:`chunk_rows` chunks, of one prompt or of several, each written
  straight into its slot's pool pages through its own table row, in one
  pass of the weights (pool donated).
* :func:`make_admit_fn` — admission, one dispatch: sample the first token
  from the prefill's last-position logits (the SAME sampling rule the
  decode step uses, ``build_sample_fn`` — keeping serving outputs bitwise
  equal to solo ``generate()`` runs under greedy decoding) and write the
  slot's state entries in-program — so the host scheduler never
  synchronizes inside the admission path.
* :func:`make_spec_block_fn` — self-drafting (a model with a
  multi-token-prediction module of its own: ``contract.drafts_itself``):
  ``block`` verify windows of two rows a lane in one program, the module
  drafting from the pools' own last layer; the chunk and admit programs
  carry the module's rows and the first draft.
* :func:`make_spec_verify_fn` and the draft side's three
  (:func:`make_draft_propose_fn`, :func:`make_draft_chunk_fn`,
  :func:`make_draft_admit_fn`) — speculative decoding; the DRAFT model
  keeps a monolithic cache ``[L_d, num_slots, cache_len, KVH*D]`` and
  single-lane prefill caches of its own.

Per-step semantics mirror ``make_generate_fn``'s decode loop exactly
(write K/V at ``pos``, sample from the new logits, emit ``eos`` once done,
advance ``pos``) — that is what makes the scheduler-correctness contract
("every request's tokens == its solo generate() run") hold bitwise.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.tools.lint.hotpath import hot_path

# the slot-state pytree: every leaf is a [num_slots] vector, every one a
# traced argument of the decode step (and donated through it)
SLOT_STATE_KEYS = ("token", "pos", "active", "remaining", "eos")


def init_slot_state(num_slots, draft=False):
    """Host-side slot state: all lanes free.  ``eos=-1`` never matches a
    sampled token (ids are >= 0), so free lanes emit -1 and retire nothing.
    ``draft``: one more leaf, the self-drafting programs' pending draft a
    lane (:func:`make_spec_block_fn`)."""
    import numpy as np
    state = {
        "token": np.zeros((num_slots,), np.int32),
        "pos": np.zeros((num_slots,), np.int32),
        "active": np.zeros((num_slots,), bool),
        "remaining": np.zeros((num_slots,), np.int32),
        "eos": np.full((num_slots,), -1, np.int32),
    }
    if draft:
        state["draft"] = np.zeros((num_slots,), np.int32)
    return state


def _sown_counts(sown, share):
    """``counts [expert layers, experts (+ len(share))]`` from what the
    expert layers of one ``apply`` sowed, in layer order: every
    ``moe_mlp`` under ``moe_stats``, whatever holds it (``layers_<i>``, a
    drafting module's block), shorter names first.  ``share``: the sown
    scalars that follow the experts' tokens (``contract.load_columns``)."""
    found = []

    def walk(tree):
        for name in sorted(tree, key=lambda n: (len(n), n)):
            if name == "moe_mlp":
                found.append(tree[name])
            elif isinstance(tree[name], dict):
                walk(tree[name])

    walk(sown["moe_stats"])
    counts = jnp.stack([f["expert_tokens"] for f in found])
    if share:
        # the last columns: choices that fell on experts held elsewhere,
        # and on zero-compute experts
        counts = jnp.concatenate(
            [counts] + [jnp.stack([f[name] for f in found])[:, None]
                        for name in share], axis=1)
    return counts


def _decode(module, variables, ids, cache, pos, live=None, method=None,
            share=(), **kw):
    """``module.decode`` as the slot programs call it: ``(logits, cache,
    counts)`` — ``(logits, hidden, cache, counts)`` where ``hidden=True``
    asks the model for its rows' last hidden state too.  Dense model
    (``live`` None): the plain call, ``counts`` None.  Expert model: only
    ``live [B, S]`` tokens are routed, and ``counts [expert layers,
    experts]`` int32 are the (token, expert) assignments each expert layer
    computed — what its ``MoE`` sows (``share``: :func:`_sown_counts`).
    ``method``: another method of the same call form (a self-drafting
    model's ``draft``, whose first argument is the hidden state)."""
    method = method or type(module).decode
    if live is None:
        return module.apply(variables, ids, cache, pos, method=method,
                            **kw) + (None,)
    out, sown = module.apply(variables, ids, cache, pos, method=method,
                             live=live, mutable=["moe_stats"], **kw)
    return out + (_sown_counts(sown, share),)


def _expert_load(counts, share=()):
    """The load summary a slot program of an expert model returns beside
    its other outputs, from ``counts [calls, expert layers, experts]`` —
    ONE int32 vector (one device read for the scheduler):
    ``expert_tokens [layers x experts]`` (assignments summed over the
    calls), then ``touched`` (experts with a live token, summed over
    layers and calls — each is one expert's weights read) and
    ``max_tokens`` (the busiest expert's tokens, summed likewise).
    ``share`` (``contract.load_columns``): the counts' last columns are
    the choices of absent experts and of zero-compute experts — each
    column's sum goes between the held experts' tokens and ``touched``."""
    with jax.named_scope("slots.expert_load"):
        held = counts[..., :-len(share)] if share else counts
        return jnp.concatenate(
            [jnp.sum(held, axis=0).reshape(-1)]
            + [jnp.sum(counts[..., i - len(share)])[None]
               for i in range(len(share))]
            + [jnp.sum(held > 0).astype(jnp.int32)[None],
               jnp.sum(jnp.max(held, axis=-1))[None]])


def make_decode_block_fn(module, contract, sample_fn, param_transform,
                         block, cache_len):
    """The single reusable decode-step program:
    ``fn(params, cache, state, pages, rng) -> (tokens [block, N], cache,
    state)`` with the page POOL and the slot state donated (argnums 1, 2)
    and the page table a plain traced input (tiny; rebuilt host-side per
    dispatch).  ``cache_len`` is the VIRTUAL lane length
    (pages_per_slot * page_size) — the dead-lane position clamp bound.

    Each of the ``block`` in-program steps writes every slot's pending
    token at its own ``pos`` (per-row write + per-row length mask, both
    through the page table — ``models/transformer.py`` ``_paged_write``,
    ``ops/transformer/registry.py``), samples the next token, emits the
    slot's ``eos`` for lanes that already finished, and flips ``active``
    off when a lane emits its eos or exhausts ``remaining`` — identical
    math to ``make_generate_fn``'s loop body, so greedy serving tokens
    match solo ``generate()`` bitwise.  Retired/free lanes keep decoding
    as masked no-ops for at most ``block - 1`` steps until the host
    scheduler reclaims them.

    For a model with dropless expert layers (``contract.routes_experts``)
    a lane that is not ``active`` at a step is routed to no expert — it
    reads no expert's weights and is not counted — and the program
    returns a fourth output, the block's :func:`_expert_load`."""
    deq = param_transform if param_transform is not None else (lambda p: p)
    routed, share = contract.routes_experts, contract.load_columns

    @hot_path("serving.decode_step")
    def decode_block(params, cache, state, pages, rng):
        eos = state["eos"]

        def step(carry, _):
            cache, tok, pos, active, remaining, rng = carry
            # inactive lanes decode as masked no-ops but still WRITE a
            # k/v row each step — point their whole table row at the
            # trash page so the write can never land in pages the host
            # already handed to a newer occupant.  (Prefill writes the
            # pool directly BEFORE the admit flips `active`, so an
            # unmasked free-lane write here would corrupt a freshly
            # prefilled prompt.)
            safe_pages = jnp.where(active[:, None], pages, 0)
            logits, cache, counts = _decode(
                module, deq(params), tok[:, None],
                {**cache, "pages": safe_pages},
                pos, live=active[:, None] if routed else None, share=share)
            with jax.named_scope("head.sample"):
                rng, sub = jax.random.split(rng)
                nxt = sample_fn(logits[:, -1], sub).astype(jnp.int32)
            with jax.named_scope("slots.state"):
                nxt = jnp.where(active, nxt, eos)
                done_now = active & ((nxt == eos) | (remaining <= 1))
                active = active & jnp.logical_not(done_now)
                # dead lanes clamp to the last virtual position — its
                # table entry is the trash page once the host processed
                # retirement
                pos = jnp.minimum(pos + 1, cache_len - 1)
                remaining = jnp.maximum(remaining - 1, 0)
            return (cache, nxt, pos, active, remaining, rng), (nxt, counts)

        (cache, tok, pos, active, remaining, _), (toks, counts) = \
            jax.lax.scan(
                step, (cache, state["token"], state["pos"], state["active"],
                       state["remaining"], rng), None, length=block)
        new_state = {"token": tok, "pos": pos, "active": active,
                     "remaining": remaining, "eos": eos}
        if routed:
            return toks, cache, new_state, _expert_load(counts, share)
        return toks, cache, new_state

    return jax.jit(decode_block, donate_argnums=(1, 2))


def admission_chunk(contract, prefill_chunk):
    """``serving.prefill_chunk`` as the server runs it: aligned like the
    engine's ``prefill_chunk_size`` (multiple of 8, floor 8), capped at the
    contract's ``chunk_cap``; a model whose chunk must fit its cache's
    geometry — ``models/evabyte.py``: no chunk straddles a window — says
    why a chunk does not (``chunk_fault``)."""
    chunk = min(contract.chunk_cap, max(8, -(-int(prefill_chunk) // 8) * 8))
    fault = contract.chunk_fault(chunk)
    if fault:
        raise ValueError(f"serving.prefill_chunk={prefill_chunk}: {fault}")
    return chunk


def chunk_write_form(contract, chunk, page):
    """The form in which :func:`make_chunk_fn`'s program writes a chunk's
    K/V into the pool: ``registry.paged_write_form`` at the server's
    chunk and page under the marker that program sets — what the traced
    write asks — or ``None`` for a model whose pools hold no K/V pages
    (``kv_pages`` False: latent attention writes its own rows,
    ``models/dots3.py``)."""
    from deepspeed_tpu.ops.transformer.registry import paged_write_form
    if not contract.kv_pages:
        return None
    return paged_write_form(chunk, page, page_runs=True)


def chunk_rows(contract, chunk, page, speculative=False):
    """How many ``chunk``-token rows one dispatch of :func:`make_chunk_fn`'s
    program takes: as many as the chunk kernel's bound holds
    (``registry.MAX_CHUNK_S // chunk`` — 4 at a chunk of 128), so one pass
    of the weights serves up to 512 prompt tokens — where rows depend on
    each other through the K/V pages alone, written as page runs
    (:func:`chunk_write_form`), which ``write_and_attend`` orders: a layer
    writes every row's K/V before any row attends.  Dropless experts
    (``routes_experts``) take rows like a dense model: an expert layer
    flattens the rows to tokens, a dead row's tokens are routed nowhere,
    and the dispatch returns ONE load vector (:func:`_expert_load`), which
    the server only ever sums.  ONE row — the scalar-``start`` program —
    where rows depend through more: per-slot state (``state_kinds``) or a
    chunk geometry of the model's own (``own_chunk_path``: windows, latent
    lanes), and under speculation (the draft lane mirrors one chunk at a
    time)."""
    from deepspeed_tpu.ops.transformer.registry import MAX_CHUNK_S
    own_path = (speculative
                or contract.state_kinds or contract.own_chunk_path
                or chunk_write_form(contract, chunk, page) != "page_runs")
    return 1 if own_path else max(1, MAX_CHUNK_S // chunk)


def make_chunk_fn(module, contract, param_transform, self_draft=False):
    """The admission-prefill chunk program:
    ``fn(params, cache, pages, chunk_ids, start, logits_at)`` — same
    body as the engine's per-chunk program (``generate()``'s split
    prefill) but writing straight into the slots' pool pages through
    their table rows (no single-lane staging cache, no admit-time
    insert).  The POOL is donated (argnum 1); the table rows are a
    separate traced input so the donation aliases cleanly.

    The scheduler hands it ``R`` = :func:`chunk_rows` rows a dispatch:
    ``pages [R, table_width]``, ``chunk_ids [R, C]``, ``start [R]``,
    ``logits_at [R]`` → ``logits [R, 1, V]`` — each row one chunk with its
    own table row, start and last real position.  Rows may be consecutive
    chunks of ONE prompt (row r+1 attends what row r wrote: a layer's K/V
    write precedes its attention call) or chunks of different prompts; a
    dead row carries an all-trash table row, start 0 and ``logits_at``
    -1, and its logits are never read.  At ``R`` = 1 ``start`` is a SCALAR
    (the row-uniform program, no ``per_row`` marker).

    ``logits_at`` is each chunk's LAST REAL row (the scheduler passes
    ``chunk - 1`` for a whole chunk, the prompt's last token for the
    final one and -1 for a dead row), so the rows past it are the padded
    tail.  For a model with dropless expert layers
    (``contract.routes_experts``) the tail — all of a dead row — is routed
    to no expert and counted nowhere, and the program returns ``(logits,
    cache, load)`` with the DISPATCH's :func:`_expert_load`: its ``R x C``
    tokens are one call of every expert layer.

    ``self_draft`` (the model ``drafts_itself``; ``R`` = 1): the chunk also
    fills the multi-token-prediction module's rows — row ``t`` from the
    main model's ``h_t`` and token ``t + 1``.  The token after the chunk's
    LAST real position is not in the chunk: a seventh argument, ``next_id
    [1]``, is the next chunk's first token, or negative for the prompt's
    last chunk, whose last row takes the first sampled token — the greedy
    choice from this chunk's own logits, the one the admit program makes.
    One more output, ``draft [1]``: the module's guess after that token,
    the slot's first pending draft (read of the last chunk only)."""
    deq = param_transform if param_transform is not None else (lambda p: p)
    routed, share = contract.routes_experts, contract.load_columns

    @hot_path("serving.prefill_chunk")
    def chunk_step(params, cache, pages, chunk_ids, start, logits_at,
                   *next_id):
        live = jnp.arange(chunk_ids.shape[1])[None, :] \
            <= logits_at[:, None] if routed else None
        if self_draft:
            return drafted_chunk(params, cache, pages, chunk_ids, start,
                                 logits_at, next_id[0], live)
        # SlotPages.reserve starts every chunk on a common multiple of
        # page and chunk, which no shape shows: the marker says it — of
        # every row's start — and the K/V write goes in as page runs
        # (registry.paged_write_form)
        logits, cache, counts = _decode(
            module, deq(params), chunk_ids,
            {**cache, "pages": pages,
             "page_runs": jnp.zeros((), jnp.int32)}, start, live=live,
            share=share, logits_at=logits_at)
        if routed:
            return logits, cache, _expert_load(counts[None], share)
        return logits, cache

    def drafted_chunk(params, cache, pages, chunk_ids, start, logits_at,
                      next_id, live):
        variables = deq(params)
        paged = lambda pools: {**pools, "pages": pages,
                               "page_runs": jnp.zeros((), jnp.int32)}
        logits, hidden, cache, counts = _decode(
            module, variables, chunk_ids, paged(cache), start, live=live,
            share=share, logits_at=logits_at, hidden=True)
        first = jnp.argmax(logits[:, 0].astype(jnp.float32), axis=-1)
        after = jnp.where(next_id >= 0, next_id, first.astype(jnp.int32))
        at_last = jnp.arange(chunk_ids.shape[1])[None, :] \
            == logits_at[:, None]
        nxt = jnp.where(at_last, after[:, None],
                        jnp.roll(chunk_ids, -1, axis=1))
        guess, cache, drafted = _decode(
            module, variables, nxt, paged(cache), start, live=live,
            method=type(module).draft, share=share, hidden=hidden,
            logits_at=logits_at)
        draft = jnp.argmax(guess[:, 0].astype(jnp.float32),
                           axis=-1).astype(jnp.int32)
        if routed:
            load = _expert_load(
                jnp.concatenate([counts, drafted])[None], share)
            return logits, cache, load, draft
        return logits, cache, draft

    return jax.jit(chunk_step, donate_argnums=(1,))


# --------------------------------------------------------------------- #
# Speculative decoding (docs/serving.md "Speculative decoding"): a small
# DRAFT model proposes k tokens per live slot, the target model verifies
# all of them in ONE batched forward, and the accepted prefix advances
# both KV caches through the existing per-row writes.  Fixed k,
# accept math entirely in-program, the accept-mask and per-slot accepted
# length as traced values riding the donated slot state — so exactly one
# draft-propose program and one verify-and-commit program serve the whole
# server lifetime, like every other slot program.  Greedy committed
# tokens are the TARGET's sample_fn outputs over the committed history,
# which is what keeps speculative serving bitwise equal to the
# non-speculative decode step.
# --------------------------------------------------------------------- #

def _spec_commit(t, draft, state, k, cache_len):
    """The verify program's in-program accept-and-commit rule.

    ``t`` ``[N, k+1]``: the target's sampled token at every window
    position (``t[:, i]`` is sampled from the logits AFTER feeding
    ``[token, d_1..d_i]``); ``draft`` ``[N, k]``: the draft proposals.
    Token ``t[:, i]`` is committed iff every earlier draft matched
    (``d_j == t_j`` for ``j < i`` — the leading-match prefix, so every
    committed token is exactly what the non-speculative decode step
    would have sampled), the slot still had budget (``i < remaining``),
    no earlier committed token was the slot's ``eos``, and the lane is
    live.  Returns ``(tokens [k+1, N], accepted [N], new_state)`` with
    the same emit/retire conventions as ``make_decode_block_fn``:
    uncommitted positions emit the slot's ``eos``, lanes retire
    in-program on eos or budget exhaustion, dead lanes commit nothing."""
    eos, active = state["eos"], state["active"]
    remaining, pos = state["remaining"], state["pos"]
    # leading-match prefix: how many drafts the target reproduced
    match = (draft == t[:, :k]).astype(jnp.int32)            # [N, k]
    n_match = jnp.sum(jnp.cumprod(match, axis=1), axis=1)    # [N]
    m_raw = 1 + n_match                                      # 1..k+1
    idx = jnp.arange(k + 1)[None, :]
    base = (idx < m_raw[:, None]) & (idx < remaining[:, None]) \
        & active[:, None]
    eos_hit = base & (t == eos[:, None])
    # commit stops AFTER the first committed eos (inclusive) — the same
    # per-step rule the non-spec block applies, folded over the window
    ex_eos = jnp.cumsum(eos_hit.astype(jnp.int32), axis=1) \
        - eos_hit.astype(jnp.int32)
    committed = base & (ex_eos == 0)                         # [N, k+1]
    m_eff = jnp.sum(committed.astype(jnp.int32), axis=1)     # [N]
    last = jnp.take_along_axis(
        t, jnp.clip(m_eff - 1, 0, k)[:, None], axis=1)[:, 0]
    done_now = active & (jnp.any(eos_hit, axis=1)
                         | (remaining <= m_eff))
    new_state = {
        "token": jnp.where(active, last, eos),
        # live lanes stay in bounds by submit()'s spec window reserve;
        # the clamp keeps dead lanes' masked writes inside the buffer
        "pos": jnp.minimum(pos + m_eff, cache_len - 1),
        "active": active & jnp.logical_not(done_now),
        "remaining": jnp.maximum(remaining - m_eff, 0),
        "eos": eos,
    }
    toks = jnp.where(committed, t, eos[:, None]).T           # [k+1, N]
    return toks, m_eff, new_state


def make_draft_propose_fn(draft_module, param_transform, k, cache_len):
    """The draft-propose program:
    ``fn(draft_params, draft_cache, state) -> (draft [N, k], draft_cache)``
    with ONLY the draft KV workspace donated (argnum 1) — the slot state
    is read-only here (the verify program owns its donation).

    ``k+1`` greedy single-token draft steps in one in-program scan:
    write the pending token at ``pos``, argmax the draft logits, repeat.
    The extra (k+1)-th step is WRITE-ONLY bookkeeping (its sample is
    discarded): a fully-accepted window advances ``pos`` by ``k+1``, and
    without it the draft cache would hold a one-position hole at
    ``pos+k`` that the next window's queries would attend as garbage.
    The draft samples greedily regardless of the serving sampling config
    — draft quality only moves the ACCEPT RATE, never the committed
    tokens (those are always the target's)."""
    deq = param_transform if param_transform is not None else (lambda p: p)

    @hot_path("serving.spec_propose")
    def propose(draft_params, draft_cache, state):
        eos, active = state["eos"], state["active"]

        def step(carry, _):
            cache, tok, pos = carry
            logits, cache = draft_module.apply(
                deq(draft_params), tok[:, None], cache, pos,
                method=type(draft_module).decode)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, eos)
            pos = jnp.minimum(pos + 1, cache_len - 1)
            return (cache, nxt, pos), nxt

        (draft_cache, _, _), drafts = jax.lax.scan(
            step, (draft_cache, state["token"], state["pos"]), None,
            length=k + 1)
        return drafts[:k].T, draft_cache            # [N, k]

    return jax.jit(propose, donate_argnums=(1,))


def make_spec_verify_fn(module, sample_fn, param_transform, k, cache_len):
    """The verify-and-commit program:
    ``fn(params, cache, state, pages, draft, rng) -> (tokens [k+1, N],
    accepted [N], cache, state)`` with the TARGET pool and the slot
    state donated (argnums 1, 2), the per-slot page tables a plain
    traced input.

    ONE batched target forward over ``[token, d_1..d_k]`` per slot
    (per-row start positions — the cache write is the per-row
    MULTI-token scatter through the page table), then the
    :func:`_spec_commit` accept rule.  Every committed token is the
    target's ``sample_fn`` output over exactly the committed history
    (the accepted drafts match it position by position), which is the
    bitwise-greedy contract; K/V written for rejected window positions
    is overwritten position-by-position by later windows before any
    query can attend it — the same argument chunked prefill's padded
    tail already relies on.  Like the decode step, inactive lanes' whole
    table row redirects to the trash page so their window writes can
    never land in pages the host already handed to a newer occupant."""
    deq = param_transform if param_transform is not None else (lambda p: p)

    @hot_path("serving.spec_verify")
    def verify(params, cache, state, pages, draft, rng):
        safe_pages = jnp.where(state["active"][:, None], pages, 0)
        ids = jnp.concatenate([state["token"][:, None], draft], axis=1)
        logits, cache = module.apply(deq(params), ids,
                                     {**cache, "pages": safe_pages},
                                     state["pos"],
                                     method=type(module).decode)
        rngs = jax.random.split(rng, k + 1)
        t = jnp.stack([sample_fn(logits[:, i], rngs[i]).astype(jnp.int32)
                       for i in range(k + 1)], axis=1)
        toks, accepted, new_state = _spec_commit(t, draft, state, k,
                                                 cache_len)
        return toks, accepted, cache, new_state

    return jax.jit(verify, donate_argnums=(1, 2))


def make_spec_block_fn(module, contract, sample_fn, param_transform, block,
                       cache_len):
    """The self-drafting decode program (a model that ``drafts_itself``):
    ``fn(params, cache, state, pages, rng) -> (tokens [block, 2, N],
    accepted [block, N], cache, state[, load])`` — ``block`` verify
    WINDOWS in one program, as the decode block carries steps; the pool and
    the slot state (with its ``draft`` leaf) donated (argnums 1, 2).

    A window, per live lane holding committed token ``x_p`` (not yet in the
    cache) and pending draft ``d``:

    1. verify — ONE main forward over rows ``[x_p, d]`` at ``p, p + 1``:
       both rows' cache rows written through the lane's table, row ``p +
       1`` attending row ``p``, each row its own kept set — the target's
       tokens ``t_0, t_1`` and hidden states ``h_p, h_{p+1}``;
    2. :func:`_spec_commit` at ``k = 1``: ``t_0`` always, ``t_1`` iff ``d
       == t_0``, budget and eos as in every decode path — so the committed
       tokens are the non-speculative step's, token for token;
    3. draft — the module over rows ``(h_p, t_0)``, ``(h_{p+1}, t_1)`` at
       ``p, p + 1``, writing ITS rows there; the next pending draft is the
       row's guess that the commit names (row 0 after one token, row 1
       after two).  Both rows every window: when both tokens commit the
       module's lane has no hole at ``p`` (``d == t_0`` then, so row ``p``
       is what a step at ``p`` would have written).

    A rejected ``p + 1`` row — the main model's and the module's — is
    overwritten by the next window, whose first row sits there, before any
    query can attend it (:func:`make_spec_verify_fn`'s argument).  Inactive
    lanes' table rows go to the trash page; for a model with expert layers
    a dead lane's rows are routed nowhere, a live lane's BOTH rows are
    routed and counted (a rejected row's experts are what speculation
    costs), and ``load`` is the block's :func:`_expert_load` over the main
    model's expert layers and then the module's."""
    deq = param_transform if param_transform is not None else (lambda p: p)
    routed, share = contract.routes_experts, contract.load_columns

    @hot_path("serving.spec_block")
    def spec_block(params, cache, state, pages, rng):
        variables = deq(params)

        def window(carry, _):
            cache, state, rng = carry
            active, pos = state["active"], state["pos"]
            draft = jnp.maximum(state["draft"], 0)
            paged = lambda pools: {
                **pools, "pages": jnp.where(active[:, None], pages, 0)}
            live = jnp.repeat(active[:, None], 2, axis=1) if routed \
                else None
            ids = jnp.stack([state["token"], draft], axis=1)
            logits, hidden, cache, counts = _decode(
                module, variables, ids, paged(cache), pos, live=live,
                share=share, hidden=True)
            with jax.named_scope("head.sample"):
                rng, *subs = jax.random.split(rng, 3)
                t = jnp.stack(
                    [sample_fn(logits[:, i], subs[i]).astype(jnp.int32)
                     for i in range(2)], axis=1)
            with jax.named_scope("slots.state"):
                toks, accepted, new = _spec_commit(
                    t, draft[:, None], state, 1, cache_len)
            guess, cache, drafted = _decode(
                module, variables, t, paged(cache), pos, live=live,
                method=type(module).draft, share=share, hidden=hidden)
            with jax.named_scope("slots.state"):
                guess = jnp.argmax(guess.astype(jnp.float32),
                                   axis=-1).astype(jnp.int32)
                new["draft"] = jnp.take_along_axis(
                    guess, jnp.clip(accepted - 1, 0, 1)[:, None],
                    axis=1)[:, 0]
            both = None if counts is None \
                else jnp.concatenate([counts, drafted])
            return (cache, new, rng), (toks, accepted, both)

        (cache, state, _), (toks, accepted, counts) = jax.lax.scan(
            window, (cache, state, rng), None, length=block)
        if routed:
            return toks, accepted, cache, state, _expert_load(counts, share)
        return toks, accepted, cache, state

    return jax.jit(spec_block, donate_argnums=(1, 2))


def make_draft_chunk_fn(draft_module, param_transform):
    """The draft-side admission-prefill chunk program — same body as the
    engine's per-chunk program, bound to the DRAFT module: speculation
    needs the prompt's K/V in the draft cache too, so admission streams
    every chunk through both models (the draft lane is donated, argnum
    1).  The selected logits are computed for body parity but discarded
    — the first token is sampled by the TARGET admit program."""
    deq = param_transform if param_transform is not None else (lambda p: p)

    @hot_path("serving.spec_draft_prefill")
    def chunk_step(draft_params, lane, chunk_ids, start, logits_at):
        return draft_module.apply(deq(draft_params), chunk_ids, lane,
                                  start, method=type(draft_module).decode,
                                  logits_at=logits_at)

    return jax.jit(chunk_step, donate_argnums=(1,))


def make_draft_admit_fn():
    """The draft-side admission program: insert the prefilled draft lane
    into slot ``slot`` of the draft cache (``dynamic_update_slice`` over
    the traced slot index; draft cache donated, argnum 0).  No sampling,
    no state write — the target admit program owns both."""

    @hot_path("serving.spec_draft_admit")
    def admit(draft_cache, lane, slot):
        def ins(buf, lbuf):
            return jax.lax.dynamic_update_slice(
                buf, lbuf.astype(buf.dtype), (0, slot, 0, 0))

        return {kk: ins(draft_cache[kk], lane[kk]) for kk in draft_cache}

    return jax.jit(admit, donate_argnums=(0,))


def make_admit_fn(sample_fn, rows=1, self_draft=False):
    """The admission program:
    ``fn(state, logits, rng, slot, pos0, max_new, eos) -> (state,
    first_token)`` with the slot state donated (argnum 0).  The prefill
    already wrote the prompt's K/V into the slot's pages, so admission
    is just the first-token sample (same ``build_sample_fn`` rule — the
    bitwise contract) plus the in-program slot-state write — inactive
    when the request already finished at admission (first token == eos,
    or ``max_new == 1``).  Because the state write happens in-program,
    the host scheduler never has to synchronize on the first token
    before the next decode block can be dispatched: it reads
    ``first_token`` lazily, one block behind (see ``ServingEngine``).

    ``rows`` > 1 (:func:`chunk_rows`): ``logits`` are a whole prefill
    dispatch's ``[rows, 1, V]`` and an eighth argument, ``row``, says
    which row held this prompt's last real position — selected
    in-program, so a dispatch that finished several prompts costs no
    slicing dispatch of its own.  ``self_draft``: the last argument is
    the chunk program's ``draft [1]``, written as the slot's pending
    draft."""

    @hot_path("serving.admit")
    def admit(state, logits, rng, slot, pos0, max_new, eos, *more):
        with jax.named_scope("head.sample"):
            if rows > 1:
                logits = jax.lax.dynamic_slice_in_dim(logits, more[0], 1)
            first = sample_fn(logits[:, 0], rng).astype(jnp.int32)[0]
        with jax.named_scope("slots.state"):
            # finished-at-admission: eos on the first token (eos=-1 never
            # matches: sampled ids are >= 0), or a 1-token request
            active0 = (max_new > 1) & jnp.logical_not(first == eos)
            upd = lambda arr, val: arr.at[slot].set(val)
            state = {"token": upd(state["token"], first),
                     "pos": upd(state["pos"], pos0),
                     "active": upd(state["active"], active0),
                     "remaining": upd(state["remaining"],
                                      jnp.maximum(max_new - 1, 0)),
                     "eos": upd(state["eos"], eos),
                     **({"draft": upd(state["draft"], more[-1][0])}
                        if self_draft else {})}
        return state, first

    return jax.jit(admit, donate_argnums=(0,))
