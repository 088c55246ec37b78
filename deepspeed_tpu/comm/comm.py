"""Communication verbs over mesh axes.

TPU-native re-design of the reference dispatch module
(``deepspeed/comm/comm.py:214-562``).  The verb set is preserved —
``all_reduce``, ``all_gather_into_tensor``, ``reduce_scatter_tensor``,
``all_to_all_single``, ``ppermute``/``send_recv_next`` (the p2p analog), ``broadcast``,
``barrier`` — but groups are mesh axis names, not NCCL communicators, and the
hot path runs *inside* jitted/shard_mapped programs where XLA schedules the
collectives onto ICI.

Two execution regimes:

* **traced** (inside ``shard_map``): verbs lower directly to ``jax.lax``
  collectives.  This is the hot path; XLA overlaps these with compute.
* **eager** (plain Python, multi-host): verbs operate across JAX *processes*
  via multihost utilities — used for bootstrap, barriers, and scalar control
  decisions, mirroring how the reference uses eager torch.distributed calls
  outside the step function.

Every eager verb is wrapped with ``timed_op`` feeding the ``CommsLogger``
(parity with reference ``comm/comm.py:104`` + ``utils/comms_logging.py:61``).
"""

import functools
import os
import time
from enum import Enum

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.comm.backend import XlaBackend
from jax.lax import axis_size as _axis_size
from deepspeed_tpu.utils.comms_logging import CommsLogger, get_msg_size_from_args
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.parallel import topology as topo


class ReduceOp(Enum):
    SUM = 0
    PRODUCT = 1
    MIN = 2
    MAX = 3
    AVG = 4


cdb = None  # "communication data backend" — name kept for parity
comms_logger = CommsLogger()
_timers_enabled = False


def _is_traced(x):
    return isinstance(x, jax.core.Tracer)


def _axes(group):
    """Normalize a group argument to a tuple of mesh axis names.

    ``group=None`` means the data-parallel group (the common case for grad
    reductions).  Expert-parameter gradients must pass
    ``topology.EXPERT_GRAD_AXES`` explicitly — they reduce over expert-data
    parallel only, never over ``ep`` (reference ``stage_1_and_2.py:1781``).
    """
    if group is None:
        return topo.DP_AXES
    if isinstance(group, str):
        return (group,)
    return tuple(group)


# --------------------------------------------------------------------- #
# Init / identity
# --------------------------------------------------------------------- #
def init_distributed(dist_backend="xla", auto_mpi_discovery=True, verbose=True,
                     timeout=None, init_method=None, dist_init_required=None,
                     config=None, rank=-1, world_size=-1):
    """Bootstrap multi-process JAX (analog of reference ``comm.py:562``)."""
    global cdb
    if cdb is not None and cdb.is_initialized():
        return cdb
    if auto_mpi_discovery and "OMPI_COMM_WORLD_SIZE" in os.environ \
            and "DSTPU_COORDINATOR_ADDRESS" not in os.environ:
        mpi_discovery(verbose=verbose)
    cdb = XlaBackend(timeout=timeout, init_method=init_method)
    cdb.init_process_group()
    return cdb


def mpi_discovery(distributed_port=29500, verbose=True):
    """Map OpenMPI env vars to the JAX coordinator env (analog of reference
    ``comm.py:627`` which maps MPI ranks to MASTER_ADDR/RANK/WORLD_SIZE)."""
    rank = int(os.environ["OMPI_COMM_WORLD_RANK"])
    world = int(os.environ["OMPI_COMM_WORLD_SIZE"])
    master = os.environ.get("MASTER_ADDR", "127.0.0.1")
    os.environ.setdefault("DSTPU_COORDINATOR_ADDRESS", f"{master}:{distributed_port}")
    os.environ.setdefault("DSTPU_NUM_PROCESSES", str(world))
    os.environ.setdefault("DSTPU_PROCESS_ID", str(rank))
    if verbose:
        logger.info(f"MPI discovery: rank {rank}/{world} coordinator "
                    f"{os.environ['DSTPU_COORDINATOR_ADDRESS']}")


def is_initialized():
    return cdb is not None and cdb.is_initialized()


def get_rank(group=None):
    """Process rank (eager) — for the in-trace device rank use ``axis_index``."""
    return jax.process_index()


def get_world_size(group=None):
    if group is None:
        return jax.device_count()
    t = topo.get_topology()
    size = 1
    for ax in _axes(group):
        size *= t.axis_size(ax)
    return size


def get_local_rank():
    return int(os.environ.get("LOCAL_RANK", 0))


def axis_index(group):
    """Device coordinate along a group's axes — in-trace rank
    (replaces reference per-communicator ``get_rank``)."""
    axes = _axes(group)
    idx = lax.axis_index(axes[0])
    for ax in axes[1:]:
        idx = idx * _axis_size(ax) + lax.axis_index(ax)
    return idx


def new_group(ranks=None, axes=None):
    """Groups are mesh axes; ``new_group`` just validates and returns the axis
    tuple (reference ``comm.py:380`` creates NCCL communicators here)."""
    if axes is None:
        raise ValueError("TPU groups are mesh axes: pass axes=('dp',...) — "
                         "rank-list groups are not meaningful under GSPMD")
    return tuple(axes)


# --------------------------------------------------------------------- #
# timed_op — eager-path profiling decorator (reference comm.py:104)
# --------------------------------------------------------------------- #
def timed_op(fn):

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        arg0 = args[0] if args else None
        if not comms_logger.enabled or _is_traced(arg0):
            return fn(*args, **kwargs)
        # prof_all=False restricts logging to the prof_ops allowlist
        # (reference comms_logger semantics)
        if not getattr(comms_logger, "prof_all", True):
            name = kwargs.get("log_name", fn.__name__)
            allowed = getattr(comms_logger, "prof_ops", None) or []
            if fn.__name__ not in allowed and name not in allowed:
                return fn(*args, **kwargs)
        t0 = time.perf_counter()
        result = fn(*args, **kwargs)
        try:
            jax.block_until_ready(result)
        except Exception:
            pass
        latency = time.perf_counter() - t0
        comms_logger.append(fn.__name__, kwargs.get("log_name", fn.__name__),
                            latency, get_msg_size_from_args(arg0))
        return result

    return wrapper


def configure(deepspeed_config=None, enabled=None, prof_all=None, prof_ops=None,
              verbose=None, debug=None):
    if deepspeed_config is not None and getattr(deepspeed_config, "comms_config", None):
        comms_logger.configure(deepspeed_config.comms_config)
    if enabled is not None:
        comms_logger.enabled = enabled
    if verbose is not None:
        comms_logger.verbose = verbose
    if debug is not None:
        comms_logger.debug = debug
    if prof_all is not None:
        comms_logger.prof_all = prof_all
    if prof_ops is not None:
        comms_logger.prof_ops = prof_ops


def log_summary():
    return comms_logger.log_all()


# --------------------------------------------------------------------- #
# Collectives
# --------------------------------------------------------------------- #
@timed_op
def all_reduce(tensor, op=ReduceOp.SUM, group=None, async_op=False, log_name=None):
    """SUM/AVG/MAX/MIN/PROD reduction over a mesh-axis group.

    Traced: lowers to ``lax.psum``/``pmax``/``pmin`` (reference
    ``comm.py:454`` → NCCL allreduce).  Eager: reduces across processes via
    allgather + local reduce (control-plane use only).
    """
    axes = _axes(group)
    if _is_traced(tensor):
        if op == ReduceOp.SUM:
            return lax.psum(tensor, axes)
        if op == ReduceOp.AVG:
            return lax.pmean(tensor, axes)
        if op == ReduceOp.MAX:
            return lax.pmax(tensor, axes)
        if op == ReduceOp.MIN:
            return lax.pmin(tensor, axes)
        if op == ReduceOp.PRODUCT:
            return jnp.exp(lax.psum(jnp.log(tensor), axes))
        raise ValueError(f"unsupported op {op}")
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(jnp.asarray(tensor))
    reducers = {ReduceOp.SUM: jnp.sum, ReduceOp.AVG: jnp.mean,
                ReduceOp.MAX: jnp.max, ReduceOp.MIN: jnp.min,
                ReduceOp.PRODUCT: jnp.prod}
    return reducers[op](gathered, axis=0)


@timed_op
def all_gather_into_tensor(tensor, group=None, axis=0, tiled=True, log_name=None):
    """Concatenated all-gather (reference ``comm.py:310``
    all_gather_into_tensor)."""
    axes = _axes(group)
    if _is_traced(tensor):
        return lax.all_gather(tensor, axes, axis=axis, tiled=tiled)
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(jnp.asarray(tensor))


# reference comm.py:308 allgather_fn capability fallback — one impl on TPU
allgather_fn = all_gather_into_tensor


@timed_op
def reduce_scatter_tensor(tensor, op=ReduceOp.SUM, group=None, scatter_dimension=0,
                          tiled=True, log_name=None):
    """Reduce+scatter (reference ``comm.py:257`` reduce_scatter_tensor →
    ``lax.psum_scatter``)."""
    axes = _axes(group)
    if not _is_traced(tensor):
        raise RuntimeError("reduce_scatter is a device collective: call inside "
                           "shard_map/jit (eager grads never materialize on host on TPU)")
    out = lax.psum_scatter(tensor, axes, scatter_dimension=scatter_dimension, tiled=tiled)
    if op == ReduceOp.AVG:
        out = out / get_world_size(axes)
    return out


reduce_scatter_fn = reduce_scatter_tensor


@timed_op
def all_to_all_single(tensor, group=None, split_axis=0, concat_axis=0, tiled=True,
                      log_name=None):
    """All-to-all (reference ``comm.py:337``) — the MoE dispatch collective."""
    axes = _axes(group)
    if not _is_traced(tensor):
        raise RuntimeError("all_to_all is a device collective: call inside shard_map")
    return lax.all_to_all(tensor, axes, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def ppermute(tensor, group, perm):
    """Collective permute — the TPU replacement for pipeline ``send``/``recv``
    pairs (reference ``runtime/pipe/p2p.py:50,71``): both halves of the
    exchange are one ``lax.ppermute`` riding ICI neighbors."""
    axes = _axes(group)
    assert len(axes) == 1, "ppermute takes a single axis"
    return lax.ppermute(tensor, axes[0], perm)


def send_recv_next(tensor, group):
    """Shift +1 along the group axis (stage i → stage i+1)."""
    axes = _axes(group)
    n = get_world_size(axes)
    return ppermute(tensor, group, [(i, (i + 1) % n) for i in range(n)])


def send_recv_prev(tensor, group):
    axes = _axes(group)
    n = get_world_size(axes)
    return ppermute(tensor, group, [(i, (i - 1) % n) for i in range(n)])


def p2p(tensor, src, dst, group):
    """Rank-addressed point-to-point as ONE collective: the SPMD rendering
    of a reference ``send(dst)`` / ``recv(src)`` pair (``comm.py:428``).
    Every device calls it; device ``dst`` returns ``src``'s value, all
    others return their own tensor unchanged.  Runs inside
    ``shard_map``/``jit`` like every device collective here."""
    if not any(_is_traced(l) for l in jax.tree.leaves(tensor)):
        raise RuntimeError("p2p is a device collective: call inside "
                           "shard_map/jit")
    axes = _axes(group)
    if len(axes) != 1:
        raise ValueError(f"p2p takes a single mesh axis, got {axes}")
    n = get_world_size(axes)
    if not (0 <= src < n and 0 <= dst < n):
        # an out-of-range endpoint would make the ppermute deliver nothing
        # and the masked merge silently keep every device's own tensor
        raise ValueError(f"p2p src={src}/dst={dst} out of range for axis "
                         f"{axes[0]!r} of size {n}")
    moved = ppermute(tensor, group, [(src, dst)])
    idx = lax.axis_index(axes[0])
    return jax.tree.map(
        lambda m, t: jnp.where(idx == dst, m, t), moved, tensor)


@timed_op
def broadcast(tensor, src=0, group=None, log_name=None):
    """Traced: everyone takes src's value via a masked psum.  Eager on global
    arrays: replicate via device_put (reference ``comm.py:224``)."""
    axes = _axes(group)
    if _is_traced(tensor):
        idx = axis_index(axes)
        masked = jnp.where(idx == src, tensor, jnp.zeros_like(tensor))
        return lax.psum(masked, axes)
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(tensor, is_source=jax.process_index() == src)


def barrier(group=None):
    """Cross-process sync (reference ``comm.py:398``)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("dstpu_barrier")
    else:
        jnp.zeros(()).block_until_ready()


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None):
    # On a mesh every participant holds the reduction; dst is vestigial.
    return all_reduce(tensor, op=op, group=group)


def all_gather(tensor_list_or_tensor, tensor=None, group=None, log_name=None):
    """List-style all_gather (reference ``comm.py:284``): returns the gathered
    shards stacked on a leading axis.  ``tensor_list_or_tensor`` may be the
    torch-style output list (ignored — jax is functional) or the input.
    Timing is owned by the inner ``all_gather_into_tensor`` (one log record
    per call, not two)."""
    x = tensor if tensor is not None else tensor_list_or_tensor
    return all_gather_into_tensor(x, group=group, axis=0, tiled=False,
                                  log_name=log_name or "all_gather")


def gather(tensor, gather_list=None, dst=0, group=None, log_name=None):
    """Gather-to-dst (reference ``comm.py:362``).  On a mesh the all-gather
    result is available everywhere; ``dst`` is vestigial.  Timing owned by
    the inner collective."""
    return all_gather_into_tensor(tensor, group=group, axis=0, tiled=False,
                                  log_name=log_name or "gather")


@timed_op
def scatter(tensor, scatter_list=None, src=0, group=None, log_name=None):
    """Scatter-from-src (reference ``comm.py:375``): each participant takes
    its own slice of src's leading axis (src's value is authoritative via
    broadcast; on a mesh all copies already agree)."""
    axes = _axes(group)
    if not _is_traced(tensor):
        raise RuntimeError("scatter is a device collective: call inside "
                           "shard_map/jit")
    idx = axis_index(axes)
    return jax.lax.dynamic_index_in_dim(tensor, idx, axis=0, keepdims=False)


def isend(tensor, dst, group=None, tag=0):
    """Async point-to-point verbs (reference ``comm.py:420`` isend/irecv)
    are NOT supported as standalone eager ops on TPU — this always raises
    with guidance.  Rank-addressed p2p has no XLA analog outside a compiled
    collective: the one-call SPMD equivalent of a send/recv PAIR is
    :func:`p2p` (or :func:`ppermute` / :func:`send_recv_next` /
    :func:`send_recv_prev`) inside ``shard_map`` — both halves of each
    exchange are one collective-permute riding ICI, which is how the
    pipeline engine moves activations.  Synchronous reference-shaped
    ``send``+``recv`` pairs with static endpoints ARE supported — see
    :func:`send`."""
    raise NotImplementedError(
        "isend/irecv have no eager analog on TPU: call "
        "dist.p2p(tensor, src, dst, group) — the send/recv pair as ONE "
        "collective — or ppermute/send_recv_next inside shard_map "
        "(pipeline p2p rides ICI); statically-paired send()+recv() also "
        "works inside shard_map")


irecv = isend

# outstanding sends awaiting their recv, each keyed by the trace that made
# it (see send/recv below): pairing is only legal WITHIN one traced
# program, and scoping the queue by trace identity means a send whose
# trace aborted can never poison a later, innocent trace with a
# leaked-tracer error.  Foreign entries are NEVER dropped eagerly — a
# nested jit's send must not discard a still-live enclosing trace's
# pending entry — only at a failing recv, where pairing is impossible
# anyway and the stale entries get called out.
_pending_send = []      # [(opaque_trace_state, tensor, dst, axes, tag)]


def _current_trace_state():
    return jax.core.get_opaque_trace_state()


_warned_missing_trace_ref = False


def _check_trace_ref(state):
    """One-time canary: dead-trace pruning leans on the PRIVATE
    ``OpaqueTraceState._trace_ref`` weakref.  If a JAX upgrade renames it,
    the ``getattr`` fallback below degrades to "always live" — correct but
    leak-prone (aborted traces' sends pin their tensors until a failing
    recv) — and that regression must be VISIBLE, not silent.  Guarded by a
    unit test too (tests/unit/test_comm.py)."""
    global _warned_missing_trace_ref
    if _warned_missing_trace_ref or hasattr(state, "_trace_ref"):
        return
    _warned_missing_trace_ref = True
    logger.warning(
        "OpaqueTraceState._trace_ref is missing on this JAX version — "
        "dead-trace pruning of queued send()s is disabled (every queued "
        "send reads as live).  Aborted traces' sends now persist until a "
        "failing recv; update _prune_dead_sends for the new "
        "OpaqueTraceState internals.")


def _prune_dead_sends():
    """Drop queued sends whose trace has been garbage-collected (an aborted
    or completed-without-recv trace).  ``OpaqueTraceState`` holds a WEAKREF
    to its trace, so deadness is precise: a live enclosing trace (nested
    jit) is never touched, but repeated aborted traces cannot accumulate
    entries (each pinning its traced tensor) for the life of the process.
    Called opportunistically from the happy path of send()/recv()."""
    if _pending_send:
        _check_trace_ref(_pending_send[0][0])
    # identity-based filtering: tuple equality would compare the queued
    # TRACED tensors (ambiguous truth value / leaked-tracer errors)
    dead_ids = {id(e) for e in _pending_send
                if getattr(e[0], "_trace_ref", lambda: True)() is None}
    if dead_ids:
        _pending_send[:] = [e for e in _pending_send
                            if id(e) not in dead_ids]
        logger.warning(
            f"send/recv shim: pruned {len(dead_ids)} queued send(s) from "
            f"dead trace(s) (their recv never executed — likely aborted "
            f"traces; send/recv pairs must complete in ONE traced function)")


def _drop_foreign_sends(state):
    """Discard queued sends from other traces.  Called only from a recv
    that found nothing to pair with in ITS trace: at that point the
    foreign entries are either from aborted traces (dead) or evidence of
    a pair split across jit boundaries (a bug being reported right now) —
    either way they must not linger to confuse the next diagnosis."""
    stale = [e for e in _pending_send if e[0] != state]
    if stale:
        _pending_send[:] = [e for e in _pending_send if e[0] == state]
        logger.warning(
            f"send/recv shim: dropping {len(stale)} unmatched send(s) "
            f"queued by an earlier trace (their recv never executed — "
            f"likely an aborted trace or a send/recv pair split across "
            f"jit boundaries; pairs must live in ONE traced function)")


def send(tensor, dst, group=None, tag=0):
    """Compatibility shim for reference-shaped ``send``/``recv`` pairs
    (reference ``comm.py:428``).  Under SPMD every rank executes BOTH
    calls, so a pair with STATIC endpoints

    .. code-block:: python

        dist.send(x, dst=5, group=("edp",))
        out = dist.recv(buf, src=2, group=("edp",))

    is statically resolvable to one mesh-axis permute: the matched pair
    lowers to ONE :func:`p2p` collective (rank ``dst``'s ``recv`` returns
    rank ``src``'s ``x``; every other rank keeps its ``buf``).  Endpoints
    must be Python ints and each ``recv`` pairs with the OLDEST pending
    ``send`` *of the same trace* (FIFO, like tag-free torch p2p
    ordering), matching on group and tag.  Genuinely dynamic patterns
    (traced endpoints, a ``recv`` with no pending ``send``, group/tag
    mismatches) raise with guidance, because no single SPMD program can
    express them.  The pending queue is scoped to the live trace: a
    ``send`` can never pair across traces, so an aborted step cannot
    poison the one after it (stale entries sit inert until a failing
    ``recv`` reports and drops them); a nested jit's own send/recv pair
    coexists with an enclosing trace's pending send."""
    if not any(_is_traced(l) for l in jax.tree.leaves(tensor)):
        raise NotImplementedError(
            "send/recv are compiled collectives here: call the pair inside "
            "shard_map/jit (or use dist.p2p directly)")
    if not isinstance(dst, int):
        raise NotImplementedError(
            "send(dst=...) must be a static Python int: a traced endpoint "
            "is rank-dynamic and has no single-program SPMD lowering — "
            "use dist.p2p/ppermute to express the whole exchange")
    _prune_dead_sends()
    _pending_send.append((_current_trace_state(), tensor, int(dst),
                          _axes(group), tag))
    return tensor


def recv(tensor, src, group=None, tag=0):
    """The receive half of a statically-paired send/recv — see
    :func:`send`.  ``tensor`` is the receive buffer: returned unchanged on
    every rank except the send's ``dst``, which gets rank ``src``'s sent
    value."""
    state = _current_trace_state()
    _prune_dead_sends()
    mine = [e for e in _pending_send if e[0] == state]
    if not mine:
        n_foreign = len(_pending_send)
        _drop_foreign_sends(state)
        raise NotImplementedError(
            "recv() without a preceding send() in this trace: under SPMD "
            "both halves of the exchange execute on every rank — call "
            "send(x, dst) then recv(buf, src) in the SAME traced function, "
            "or use dist.p2p(tensor, src, dst, group) directly"
            + (f" ({n_foreign} stale send(s) from an earlier trace were "
               f"queued and have been dropped)" if n_foreign else ""))
    entry = mine[0]                                   # FIFO pairing
    _pending_send.remove(entry)
    _, sent, dst, saxes, stag = entry
    if not isinstance(src, int):
        raise NotImplementedError(
            "recv(src=...) must be a static Python int (see send())")
    if _axes(group) != saxes or tag != stag:
        raise ValueError(
            f"recv(group={_axes(group)}, tag={tag}) does not match the "
            f"pending send(group={saxes}, tag={stag})")
    moved = p2p(sent, src, dst, group)
    idx = lax.axis_index(saxes[0])
    return jax.tree.map(
        lambda m, buf: jnp.where(idx == dst, m, buf), moved, tensor)


def monitored_barrier(group=None, timeout=None, wait_all_ranks=False):
    """Barrier with failure attribution (reference ``comm.py:405``).  XLA
    collectives already fail loudly on rank drop-out; delegate to barrier."""
    return barrier(group)


def inference_all_reduce(tensor, op=ReduceOp.SUM, group=None):
    """TP allreduce inside injected inference layers (reference
    ``pt_binding.cpp`` inference_all_reduce) — same psum on TPU."""
    return all_reduce(tensor, op=op, group=group)


def destroy_process_group():
    global cdb
    if cdb is not None:
        cdb.destroy_process_group()
        cdb = None
