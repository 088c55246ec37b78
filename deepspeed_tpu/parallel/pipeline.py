"""SPMD pipeline parallelism over the ``pp`` mesh axis.

TPU-native re-design of the reference pipeline engine
(``runtime/pipe/engine.py:42``, ``schedule.py:135,189``, ``p2p.py:50,71``).
The reference interprets an instruction schedule per-rank and exchanges
activations with NCCL point-to-point sends.  Under single-controller SPMD the
whole schedule becomes ONE differentiable program:

* stages are shards of the ``pp`` axis inside ``shard_map`` (manual over
  ``pp`` only — dp/tp/sp/ep stay GSPMD-automatic);
* the schedule is a ``lax.scan`` over ticks; stage *s* works on microbatch
  ``m = t - s`` (the classic pipeline wavefront);
* activation transfer is one ``lax.ppermute`` per tick riding ICI neighbors
  (both halves of the reference's send/recv pair);
* the backward pipeline is **not hand-written**: differentiating the scan
  yields the reverse wavefront with reversed ppermutes automatically, with
  the per-tick stage inputs as residuals (= the reference's activation
  stash).  ``jax.checkpoint`` on the stage body gives the same memory
  behavior as its activation-checkpointed stages.

Schedule menu (``pipeline.schedule`` + ``max_in_flight_microbatches``):

* ``spmd_pipeline`` (fill_drain, default) — all M microbatches flow
  forward, then backward via autodiff.  Bubble ``(P-1)/(M+P-1)`` (the
  1F1B number — throughput-optimal), but the activation stash grows with
  M where the reference's ``TrainSchedule`` (1F1B, ``schedule.py:189``)
  bounds in-flight microbatches to ~P.
* ``spmd_pipeline_1f1b`` (schedule="1f1b") — hand-rolled interleaved
  one-forward-one-backward ticks with an O(P) input ring and in-region
  boundary layers, staged as three scans (P-1 forward-only warmup ticks,
  M combined fwd+bwd steady ticks, P-1 backward-only cooldown ticks) so
  the fill/drain ticks cost only their live half.  Bubble
  ``(P-1)/(M+P-1)`` — the reference ``TrainSchedule`` number (see
  ``one_f_one_b_phase_ticks``).  The memory-bounded mode of choice.
* chunked accumulation (``max_in_flight_microbatches=C``) — fill-drain
  over chunks of C; O(C) stash at a per-chunk bubble ``(P-1)/(C+P-1)``.
  Kept for when C must be tuned independently of P.

Activations may be arbitrary pytrees (e.g. ``(hidden, aux_loss)`` for MoE
trunks); every per-tick primitive is tree-mapped.
"""

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import PP_AXIS
from jax import shard_map as _shard_map


def spmd_pipeline(stage_fn, stacked_params, x0, num_micro, mesh,
                  pp_axis=PP_AXIS, remat_stage=True):
    """Run the pipelined forward: returns last-stage outputs ``[M, ...]``.

    ``stage_fn(stage_params, x) -> y`` maps one stage over one microbatch
    activation (a pytree; same structure/shapes in and out).
    ``stacked_params`` leaves have leading dim P (one slice per stage).
    ``x0``: pytree of ``[M, ...]`` microbatch activations entering stage 0.
    Fully differentiable.
    """
    n_stages = mesh.shape[pp_axis]
    if remat_stage:
        stage_fn = jax.checkpoint(stage_fn)

    # XLA's CPU backend (the simulated test mesh) crashes promoting bf16
    # all-reduces, which the region's backward emits for the replicated x0
    # cotangent.  Run the region in f32 on CPU; TPU stays bf16.
    cast_back = None
    if jax.default_backend() == "cpu" and any(
            l.dtype == jnp.bfloat16 for l in jax.tree.leaves(x0)):
        orig_dtypes = jax.tree.map(lambda l: l.dtype, x0)
        cast_back = orig_dtypes
        up = lambda t: jax.tree.map(
            lambda l: l.astype(jnp.float32)
            if l.dtype == jnp.bfloat16 else l, t)
        down = lambda t: jax.tree.map(
            lambda l, d: l.astype(d), t, orig_dtypes)
        inner_stage_fn = stage_fn
        stage_fn = lambda p, x: up(inner_stage_fn(p, down(x)))
        x0 = up(x0)

    def region(params, x0):
        sid = lax.axis_index(pp_axis)
        M = num_micro
        T = M + n_stages - 1
        params_local = jax.tree.map(lambda a: jnp.squeeze(a, 0), params)
        state0 = jax.tree.map(lambda l: jnp.zeros_like(l[0]), x0)

        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(state, t):
            # receive previous stage's activation (stage 0 receives zeros)
            recv = jax.tree.map(
                lambda l: lax.ppermute(l, pp_axis, fwd_perm),
                state) if n_stages > 1 else state
            x_t = jax.tree.map(
                lambda l: lax.dynamic_index_in_dim(
                    l, jnp.minimum(t, M - 1), 0, keepdims=False), x0)
            inp = jax.tree.map(lambda a, b: jnp.where(sid == 0, a, b),
                               x_t, recv)
            m = t - sid
            active = jnp.logical_and(m >= 0, m < M)
            y = stage_fn(params_local, inp)
            y = jax.tree.map(
                lambda l: jnp.where(active, l, jnp.zeros_like(l)), y)
            # emit only the last stage's finished microbatches
            emit = jnp.logical_and(active, sid == n_stages - 1)
            out = jax.tree.map(
                lambda l: jnp.where(emit, l, jnp.zeros_like(l)), y)
            return y, out

        _, outs = lax.scan(tick, state0, jnp.arange(T))
        # outs[t] holds microbatch m = t-(P-1) on the last stage, zeros
        # elsewhere; psum over pp broadcasts last-stage values to all shards.
        outs = jax.tree.map(lambda l: l[n_stages - 1:], outs)
        if n_stages > 1:
            outs = lax.psum(outs, pp_axis)
        return outs

    in_specs = (jax.tree.map(lambda _: P(pp_axis), stacked_params), P())  # tpu-lint: disable=TL010 -- every stage needs the full microbatch stream: the region slices its own microbatch per tick in-program; batch sharding over edp runs manually inside
    out = _shard_map(
        region, mesh=mesh, in_specs=in_specs, out_specs=P(),
        axis_names=frozenset({pp_axis}), check_vma=False,
    )(stacked_params, x0)
    if cast_back is not None:
        out = jax.tree.map(lambda l, d: l.astype(d), out, cast_back)
    return out  # structure matches x0 (stage in == stage out)


def pipeline_bubble_fraction(num_micro, num_stages):
    return (num_stages - 1) / (num_micro + num_stages - 1)


def one_f_one_b_ticks(num_micro, num_stages):
    """Total scan-tick count of the interleaved 1F1B schedule: M + 2(P-1).

    See ``one_f_one_b_phase_ticks`` — the first P-1 ticks are
    forward-only and the last P-1 backward-only, so only the M steady
    ticks pay a full fwd+bwd slot and the wall-clock bubble is the
    reference ``TrainSchedule``'s (P-1)/(M+P-1)."""
    return num_micro + 2 * (num_stages - 1)


def one_f_one_b_phase_ticks(num_micro, num_stages):
    """Per-phase tick counts ``(warmup, steady, cooldown)`` of the
    interleaved 1F1B schedule: ``(P-1, M, P-1)``.

    The schedule's global tick grid is M + 2(P-1) ticks — stage *s*
    forwards microbatch ``t - s`` and backwards ``t - 2(P-1) + s`` — but
    no stage has live backward work before tick P-1 and none has live
    forward (or loss) work from tick M+P-1 on.  Staging the scan as three
    bodies (fwd-only / fwd+bwd / bwd-only) therefore drops only dead
    compute: warmup ticks cost one forward, cooldown ticks one backward,
    for a wall-clock of ``(P-1)·tf + M·(tf+tb) + (P-1)·tb =
    (M+P-1)·(tf+tb)`` — a bubble fraction of ``(P-1)/(M+P-1)``, exactly
    the reference's asynchronous 1F1B (``runtime/pipe/schedule.py:189``).
    It keeps 1F1B's O(P) activation stash and strictly beats chunked
    fill-drain at the same memory bound (M/C chunks × (C+P-1) full ticks;
    e.g. P=4, M=16, C=4: 28 chunked full ticks vs 19 equivalent here)."""
    return num_stages - 1, num_micro, num_stages - 1



def spmd_pipeline_1f1b(stage_fn, stacked_params, first_fn, first_params,
                       last_fn, last_params, inputs, labels, num_micro, mesh,
                       cotangent_seed=1.0, pp_axis=PP_AXIS):
    """Interleaved 1F1B pipeline with hand-rolled per-tick backward.

    TPU-native rendering of the reference ``TrainSchedule``
    (``runtime/pipe/schedule.py:189``): three ``lax.scan`` phases over one
    global grid of ``one_f_one_b_ticks(M, P)`` ticks inside ``shard_map``
    over ``pp`` — P-1 forward-only warmup ticks, M combined fwd+bwd steady
    ticks, P-1 backward-only cooldown ticks (``one_f_one_b_phase_ticks``)
    — matching the reference's (P-1)/(M+P-1) bubble.
    Like the reference's stage placement, the boundary layers live INSIDE
    the schedule — ``first_fn`` (embedding/pre chain) runs on stage 0 and
    ``last_fn`` (post chain + per-microbatch loss) on the last stage — so
    the only M-sized buffers in the program are the raw ``inputs``/
    ``labels`` (token ids), exactly as in the reference.  Per tick,
    stage *s*:

    * forward of microbatch ``m_f = t - s`` (stage 0 embeds
      ``inputs[m_f]`` via ``first_fn``; other stages receive via the
      forward ``ppermute``), stashing its input activation in a ring of
      depth ``2P-1`` — the O(P) bound that replaces autodiff's O(M)
      residual stash (stage 0 also rings the raw input for its pre-chain
      backward);
    * on the LAST stage, ``last_fn`` runs for ``m_l = t-(P-1)`` and its
      vjp seeds the backward wavefront THE SAME TICK (``cotangent_seed``
      is the loss-scale/mean factor);
    * backward of microbatch ``m_b = t - 2(P-1) + s``: the stage input is
      re-read from the ring and the stage re-linearized (``jax.vjp``) —
      rematerialized backward, exactly like the fill-drain mode's
      ``jax.checkpoint``-ed stages; the input-cotangent rides the reverse
      ``ppermute`` to stage s-1, where stage 0 instead backpropagates it
      through ``first_fn``.

    Returns ``(loss_sum, body_grads_stacked, first_grads, last_grads)``:
    ``loss_sum`` is the RAW sum of per-microbatch losses (unscaled); the
    gradient sums are scaled by ``cotangent_seed`` (seed with ``scale/M``
    to get gradients of ``mean(loss)*scale``).
    """
    n_stages = mesh.shape[pp_axis]
    M = num_micro
    R = 2 * n_stages - 1
    T = one_f_one_b_ticks(M, n_stages)

    def region(params, first_p, last_p, inputs, labels, seed):
        sid = lax.axis_index(pp_axis)
        last_sid = n_stages - 1
        params_local = jax.tree.map(lambda a: jnp.squeeze(a, 0), params)

        in0 = jax.tree.map(lambda l: l[0], inputs)
        act0 = jax.eval_shape(lambda p, i: first_fn(p, i), first_p, in0)
        act0 = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), act0)
        ring_act0 = jax.tree.map(
            lambda l: jnp.zeros((R, *l.shape), l.dtype), act0)
        ring_in0 = jax.tree.map(
            lambda l: jnp.zeros((R, *l.shape[1:]), l.dtype), inputs)
        zeros_f32 = lambda t: jax.tree.map(
            lambda p: jnp.zeros(jnp.shape(p), jnp.float32), t)
        gbody0, gfirst0, glast0 = (zeros_f32(params_local),
                                   zeros_f32(first_p), zeros_f32(last_p))

        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
        bwd_perm = [(i + 1, i) for i in range(n_stages - 1)]

        def at(tree, idx):
            return jax.tree.map(
                lambda l: lax.dynamic_index_in_dim(l, idx, 0, keepdims=False),
                tree)

        def put(tree, val, idx):
            return jax.tree.map(
                lambda l, v: lax.dynamic_update_index_in_dim(
                    l, v.astype(l.dtype), idx, 0), tree, val)

        def mask(tree, cond):
            return jax.tree.map(
                lambda l: jnp.where(cond, l, jnp.zeros_like(l)), tree)

        # NOTE control-flow discipline: every lax.cond predicate below
        # depends on the tick counter t ONLY (globally uniform), never
        # on the stage id — a sid-dependent branch containing the
        # tp-sharded head/embedding diverged the pp groups' collective
        # sequences and deadlocked the mesh.  sid-dependence is
        # expressed with jnp.where masks on uniformly-executed compute.

        def fwd_unit(y_state, ring_act, ring_in, t):
            recv = jax.tree.map(
                lambda l: lax.ppermute(l, pp_axis, fwd_perm),
                y_state) if n_stages > 1 else y_state
            m_f = t - sid
            f_active = jnp.logical_and(m_f >= 0, m_f < M)
            in_m = at(inputs, jnp.clip(m_f, 0, M - 1))
            x_first = lax.cond(t < M,
                               lambda: first_fn(first_p, in_m),
                               lambda: jax.tree.map(jnp.zeros_like, recv))
            x_in = jax.tree.map(
                lambda a, b: jnp.where(sid == 0, a, b), x_first, recv)
            y = mask(stage_fn(params_local, x_in), f_active)
            ring_act = put(ring_act, x_in, t % R)
            ring_in = put(ring_in, in_m, t % R)
            return y, ring_act, ring_in

        def seed_unit(t, y):
            # loss + backward seed on the last stage; steady ticks only
            # (t in [P-1, M+P-2] ⇒ m_l in [0, M-1], always in-window)
            m_l = t - last_sid
            l_active = jnp.logical_and(m_l >= 0, m_l < M)
            lab = at(labels, jnp.clip(m_l, 0, M - 1))
            loss_m, lvjp = jax.vjp(
                lambda lp, yy: last_fn(lp, yy, lab), last_p, y)
            dlast, dy = lvjp(seed.astype(loss_m.dtype))
            on_last = jnp.logical_and(sid == last_sid, l_active)
            return jnp.where(on_last, loss_m.astype(jnp.float32), 0.0), \
                mask(jax.tree.map(lambda g: g.astype(jnp.float32),
                                  dlast), on_last), \
                mask(dy, on_last)

        def bwd_unit(dx_state, ring_act, ring_in, gbody, gfirst,
                     dy_seed, y_ref, t):
            brecv = jax.tree.map(
                lambda l: lax.ppermute(l, pp_axis, bwd_perm),
                dx_state) if n_stages > 1 else dx_state
            m_b = t - 2 * (n_stages - 1) + sid
            b_active = jnp.logical_and(m_b >= 0, m_b < M)
            dy_in = jax.tree.map(
                lambda a, b: jnp.where(sid == last_sid, a, b),
                dy_seed, brecv)
            # the stashed input of this stage's forward of m_b (tick
            # t_f = t - 2(P-1) + 2s); re-linearize = rematerialized backward
            t_f = t - 2 * (n_stages - 1) + 2 * sid
            slot = jnp.clip(t_f, 0, T - 1) % R
            x_b = at(ring_act, slot)
            _, svjp = jax.vjp(stage_fn, params_local, x_b)
            dp, dx = svjp(jax.tree.map(
                lambda l, yl: l.astype(yl.dtype), dy_in, y_ref))
            gbody = jax.tree.map(
                lambda g, d: g + jnp.where(b_active,
                                           d.astype(jnp.float32), 0.0),
                gbody, dp)
            dx = mask(dx, b_active)

            # stage 0 backpropagates its input-cotangent through first_fn
            # (uniform-predicate window; sid-dependence via masks, as above)
            b0_window = jnp.logical_and(t >= 2 * (n_stages - 1),
                                        t < 2 * (n_stages - 1) + M)

            def first_b_branch():
                in_b = at(ring_in, slot)
                _, fvjp = jax.vjp(lambda fp: first_fn(fp, in_b), first_p)
                (dfp,) = fvjp(jax.tree.map(
                    lambda l, xl: l.astype(xl.dtype), dx, x_b))
                return mask(jax.tree.map(
                    lambda g: g.astype(jnp.float32), dfp),
                    jnp.logical_and(sid == 0, b_active))

            dfirst_m = lax.cond(b0_window, first_b_branch,
                                lambda: zeros_f32(first_p))
            gfirst = jax.tree.map(jnp.add, gfirst, dfirst_m)
            return dx, gbody, gfirst

        # Three scan phases over one global tick grid (see
        # one_f_one_b_phase_ticks): ticks [0, P-1) have no live backward
        # anywhere and ticks [M+P-1, T) no live forward/loss anywhere, so
        # the warmup body is fwd-only (costs tf) and the cooldown body
        # bwd-only (costs tb) — the wall-clock bubble is (P-1)/(M+P-1),
        # the reference TrainSchedule's.
        def warmup_tick(carry, t):
            (y_state, dx_state, ring_act, ring_in, gbody, gfirst, glast,
             loss_acc) = carry
            y, ring_act, ring_in = fwd_unit(y_state, ring_act, ring_in, t)
            return (y, dx_state, ring_act, ring_in, gbody, gfirst, glast,
                    loss_acc), None

        def steady_tick(carry, t):
            (y_state, dx_state, ring_act, ring_in, gbody, gfirst, glast,
             loss_acc) = carry
            y, ring_act, ring_in = fwd_unit(y_state, ring_act, ring_in, t)
            loss_m, dlast_m, dy_seed = seed_unit(t, y)
            loss_acc = loss_acc + loss_m
            glast = jax.tree.map(jnp.add, glast, dlast_m)
            dx, gbody, gfirst = bwd_unit(dx_state, ring_act, ring_in,
                                         gbody, gfirst, dy_seed, y, t)
            return (y, dx, ring_act, ring_in, gbody, gfirst, glast,
                    loss_acc), None

        def cooldown_tick(carry, t):
            (y_state, dx_state, ring_act, ring_in, gbody, gfirst, glast,
             loss_acc) = carry
            dy_zero = jax.tree.map(jnp.zeros_like, y_state)
            dx, gbody, gfirst = bwd_unit(dx_state, ring_act, ring_in,
                                         gbody, gfirst, dy_zero, y_state, t)
            return (y_state, dx, ring_act, ring_in, gbody, gfirst, glast,
                    loss_acc), None

        carry = (act0, jax.tree.map(jnp.zeros_like, act0), ring_act0,
                 ring_in0, gbody0, gfirst0, glast0,
                 jnp.zeros((), jnp.float32))
        warm, steady, cool = one_f_one_b_phase_ticks(M, n_stages)
        carry, _ = lax.scan(warmup_tick, carry, jnp.arange(warm))
        carry, _ = lax.scan(steady_tick, carry,
                            jnp.arange(warm, warm + steady))
        carry, _ = lax.scan(cooldown_tick, carry,
                            jnp.arange(warm + steady, T))
        (_, _, _, _, gbody, gfirst, glast, loss_acc) = carry
        # loss/last-grads live on the last stage, first-grads on stage 0;
        # psum broadcasts each to every pp shard
        if n_stages > 1:
            loss_acc = lax.psum(loss_acc, pp_axis)
            glast = lax.psum(glast, pp_axis)
            gfirst = lax.psum(gfirst, pp_axis)
        gbody = jax.tree.map(lambda g: g[None], gbody)
        return loss_acc, gbody, gfirst, glast

    in_specs = (jax.tree.map(lambda _: P(pp_axis), stacked_params),
                P(), P(), P(), P(), P())  # tpu-lint: disable=TL010 -- the 1F1B region consumes the full [M, ...] microbatch stream and slices per tick in-program (stages see different microbatches at different ticks); edp batch sharding runs manually inside the region
    out_specs = (P(), jax.tree.map(lambda _: P(pp_axis), stacked_params),
                 P(), P())
    return _shard_map(
        region, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset({pp_axis}), check_vma=False,
    )(stacked_params, first_params, last_params, inputs, labels,
      jnp.asarray(cotangent_seed, jnp.float32))


def stack_stage_params(per_layer_params, num_stages):
    """Group L per-layer param trees (identical structure) into
    ``[P, L/P, ...]`` stacked pytrees for the SPMD pipeline."""
    L = len(per_layer_params)
    if L % num_stages != 0:
        raise ValueError(f"{L} body layers not divisible by {num_stages} stages")
    per_stage = L // num_stages
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *per_layer_params)
    return jax.tree.map(
        lambda a: a.reshape(num_stages, per_stage, *a.shape[1:]), stacked)
