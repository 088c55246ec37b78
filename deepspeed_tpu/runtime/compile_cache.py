"""Persistent compilation & executable cache — compile once per machine.

Compilation is this framework's dominant cold-path cost: every train step,
prefill chunk and unrolled decode program is a multi-minute XLA compile at
OPT-1.3B+ scale (the round-5 bench lost its whole record to ONE ~40-min
cold compile).  This module makes compilation a per-machine cost instead of
a per-process cost, at two layers:

1. **Persistent XLA compilation cache** (:func:`configure_persistent_cache`)
   — JAX's on-disk cache keyed by the optimized HLO + compile options (+
   :data:`SCOPES_VERSION`, see there), under a framework-owned directory.
   Transparent: any jit anywhere in the process benefits.  Hits/misses
   are counted through JAX's monitoring
   events (:func:`stats`).
2. **Serialized executables** (:class:`ExecutableStore`) — AOT-compiled
   ``jax.stages.Compiled`` programs (``jax.experimental
   .serialize_executable``) stored whole, keyed by a framework cache key
   (:func:`cache_key`: program tag + abstract arg signature + engine
   context) and fingerprinted by jax/jaxlib version, backend, device kind &
   count and ``XLA_FLAGS``.  A warm process skips tracing AND lowering AND
   compilation; any mismatch or load error falls back to a fresh compile
   (the cache can only ever cost a retrace, never correctness).

Engines consume both through :class:`ProgramCache` (built from the
``compile_cache`` config block, see ``docs/compile_cache.md``) and expose
``warmup()``/``precompile()`` so all shape buckets compile up front with
per-program compile times reported through the monitor.

Invalidation: executable entries are dropped (ignored) whenever the
fingerprint changes; the XLA cache is content-addressed and never stale.
Delete the cache directory to reclaim space — both layers rebuild on the
next cold run.
"""

import collections
import contextlib
import hashlib
import json
import os
import pickle
import time
from typing import Any, Dict, Optional

from deepspeed_tpu.monitor.trace import setup_open, span
from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel
from deepspeed_tpu.utils.logging import logger, log_dist, warning_once


class CompileCacheConfig(DeepSpeedConfigModel):
    """``compile_cache`` config block (shared by the training and inference
    engines; see ``docs/compile_cache.md``)."""
    enabled: bool = False
    # cache root.  $JAX_COMPILATION_CACHE_DIR, when set, wins over this key;
    # None → :func:`default_cache_dir`
    cache_dir: Optional[str] = None
    # below this, XLA-cache writes are skipped (tiny programs recompile
    # faster than they deserialize); jax default is 1s
    min_compile_time_secs: float = 1.0
    # serialize/reload whole AOT executables (layer 2 above)
    executables: bool = True
    # executable store directory; None → <cache_dir>/executables
    executable_dir: Optional[str] = None


# the one path code ever chooses on its own: fixed (the path is part of
# JAX's cache key, so a directory that moves never hits), inside the
# checkout, git-ignored
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

# Both cache layers key a program WITHOUT its instructions' metadata: a
# process is handed the executable — and with it the ``op_name``s that
# every device profile shows and ``profiler.device_time_by_scope`` reads —
# of whichever process compiled that computation first, from before a
# ``jax.named_scope`` or a module was renamed.  This number is in both
# keys (JAX's, through its ``custom_hook``; the executable store's, through
# :func:`runtime_fingerprint`): whoever changes a name that
# ``profiler.SCOPE_PARTS`` reads raises it, and every entry is compiled
# once more — not on every moved source line, which metadata in JAX's key
# would cost.
SCOPES_VERSION = 2


def env_cache_dir():
    """``$JAX_COMPILATION_CACHE_DIR`` or None.  Where it is set, JAX's own
    handling of it is the only placement: nothing here re-points the
    cache, and the executable store lives under it too."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None


def default_cache_dir():
    return env_cache_dir() or _CHECKOUT_CACHE_DIR


# --------------------------------------------------------------------- #
# Cache-hit accounting (process-global; read deltas, not absolutes)
# --------------------------------------------------------------------- #
# JAX's compile-phase duration events -> the CacheStats sum each feeds
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_seconds",
    "/jax/core/compile/backend_compile_duration": "backend_compile_seconds",
}
COMPILE_EVENTS_KEPT = 8192               # newest (t, event, seconds) kept
COMPILE_EVENT_MIN_SECS = 1e-3            # shorter ones are only summed


class CacheStats:
    """Counters for both cache layers.  ``persistent_*`` come from JAX's
    monitoring events (the on-disk XLA cache); ``executable_*`` from the
    framework's :class:`ExecutableStore`.  ``trace_seconds`` /
    ``lower_seconds`` / ``backend_compile_seconds`` sum JAX's own
    compile-phase durations (Python tracing to a jaxpr — a jit traced
    inside another's trace counted once —, lowering to MLIR, the
    backend compile — a persistent-cache hit's load counts there too)
    over every jit in the process since the first engine configured
    the cache; ``compile_events`` keeps
    the newest :data:`COMPILE_EVENTS_KEPT` of at least a millisecond
    (JAX reports thousands of microsecond-long cached traces) as
    ``(t_monotonic, event, seconds)`` — the seconds each ADDED to its
    sum, so a reader sums a stretch of the list and needs no nesting
    rule of its own."""

    def __init__(self):
        self.persistent_requests = 0     # compiles that consulted the cache
        self.persistent_hits = 0
        self.executable_hits = 0
        self.executable_misses = 0
        self.executable_mismatches = 0   # fingerprint said "not this build"
        self.executable_saves = 0
        self.executable_errors = 0
        self.aot_fallbacks = 0           # AOT compiles that raised (→ plain jit)
        self.compile_seconds: Dict[str, float] = {}  # tag -> last compile time
        self.trace_seconds = 0.0
        self.lower_seconds = 0.0
        self.backend_compile_seconds = 0.0
        self.compile_events = collections.deque(maxlen=COMPILE_EVENTS_KEPT)

    def snapshot(self):
        d = {k: v for k, v in self.__dict__.items()
             if isinstance(v, (int, float))}
        d["compile_seconds"] = dict(self.compile_seconds)
        d["compile_events"] = list(self.compile_events)
        return d


_STATS = CacheStats()


def stats() -> CacheStats:
    return _STATS


_listener_registered = False


def _on_jax_event(event, **kwargs):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _STATS.persistent_requests += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _STATS.persistent_hits += 1


_open_traces = []                        # (start, seconds) already summed
_OPEN_TRACES_KEPT = 1 << 16


def _on_jax_duration(event, duration_secs, **kwargs):
    field = _COMPILE_PHASES.get(event)
    if field is None:
        return
    t = time.monotonic()
    add = duration_secs
    if field == "trace_seconds":
        # a jit traced inside another's trace reports first and lies
        # inside the outer's duration: count that time once
        start = t - duration_secs
        while _open_traces and _open_traces[-1][0] >= start:
            add -= _open_traces.pop()[1]
        _open_traces.append((start, duration_secs))
        if len(_open_traces) > 2 * _OPEN_TRACES_KEPT:
            # top-level traces are never popped; the siblings inside one
            # unrolled 24-layer trace run to thousands, and all of them
            # must still be here when their outer trace reports
            del _open_traces[:-_OPEN_TRACES_KEPT]
    setattr(_STATS, field, getattr(_STATS, field) + add)
    if duration_secs >= COMPILE_EVENT_MIN_SECS:
        _STATS.compile_events.append((t, event, add))


def _register_jax_listener():
    global _listener_registered
    if _listener_registered:
        return
    from jax import monitoring
    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _listener_registered = True


# --------------------------------------------------------------------- #
# Layer 1: the persistent XLA compilation cache
# --------------------------------------------------------------------- #
_configured_dir = None


def configure_persistent_cache(cache_dir=None, min_compile_time_secs=None):
    """Point JAX's persistent compilation cache at a framework-owned
    directory (idempotent; process-wide).  Returns the directory."""
    global _configured_dir
    import jax
    placed = env_cache_dir()
    if placed is not None:
        if cache_dir not in (None, placed):
            warning_once(
                f"compile_cache: JAX_COMPILATION_CACHE_DIR={placed} is set "
                f"and places the cache; cache_dir={cache_dir} is ignored")
        cache_dir = placed
    else:
        cache_dir = cache_dir or _CHECKOUT_CACHE_DIR
        # the XLA cache dir is PROCESS-GLOBAL: re-pointing it (a second
        # engine with a different cache_dir) is last-wins and fragments
        # the cache — allowed, but never silent
        current = jax.config.jax_compilation_cache_dir
        if current not in (None, cache_dir):
            logger.warning(
                f"compile_cache: re-pointing the process-global XLA "
                f"compilation cache from {current} to {cache_dir} (the dir "
                f"is one-per-process; every engine and jit in this process "
                f"now writes there — use one cache_dir per process to "
                f"avoid fragmenting the cache)")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    from jax._src import cache_key
    cache_key.custom_hook = lambda: f"dstpu.scopes={SCOPES_VERSION}"
    if min_compile_time_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
    _register_jax_listener()
    if _configured_dir is None:
        log_dist(f"persistent compilation cache at {cache_dir}", ranks=[0])
    _configured_dir = cache_dir
    return cache_dir


def _reset_jax_cache_state():
    """Drop jax's initialized-once compilation-cache module state so the
    next compile re-reads the live config: jax memoizes the use-the-cache
    decision AND the cache object at the first compile, so flipping
    ``jax_compilation_cache_dir`` alone does NOT detach a cache already
    in use."""
    from jax.experimental.compilation_cache import compilation_cache as jcc
    jcc.reset_cache()


_suspended = 0                           # open suspensions (opt-out compiles)


@contextlib.contextmanager
def suspended_persistent_cache():
    """Temporarily detach the process from the XLA persistent cache for
    the compiles inside the block (no reads, no writes).  For programs
    whose RELOADED form is unsafe to reuse across processes — the
    serving slot programs chain one donated workspace across three
    executables, and reloading ANY of them from either cache layer in a
    fresh process nondeterministically corrupts the slot cache or
    segfaults (bisected with the serving kill-harness driver; the train
    and whole-batch generate paths show no such failures and keep both
    layers).  Compiles are synchronous on the calling thread, so the
    process-global config flip is safe."""
    global _suspended
    import jax
    prev = jax.config.jax_compilation_cache_dir
    _suspended += 1
    try:
        if prev is None:
            yield
            return
        jax.config.update("jax_compilation_cache_dir", None)
        _reset_jax_cache_state()
        try:
            yield
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
            # re-attach lazily: the next ordinary compile re-initializes
            # from the restored config
            _reset_jax_cache_state()
    finally:
        _suspended -= 1


def deconfigure_persistent_cache():
    """Undo :func:`configure_persistent_cache` — for scripts/harnesses that
    must detach the process from a temporary cache directory before it is
    deleted (the dir is process-global; JAX would otherwise keep writing
    there)."""
    global _configured_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", None)
    # the config flip alone does not detach an already-initialized cache
    # (jax caches the decision in module globals) — reset it too
    _reset_jax_cache_state()
    _configured_dir = None


# --------------------------------------------------------------------- #
# Cache keys
# --------------------------------------------------------------------- #
def runtime_fingerprint():
    """Everything that invalidates a serialized executable: compiler
    version, backend, device model & count, and compiler flags.  (The
    program itself is in the cache key, not the fingerprint.)"""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "n_devices": jax.device_count(),
        "n_processes": jax.process_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "scopes": SCOPES_VERSION,
    }


def abstract_signature(tree):
    """(shape, dtype, weak_type) of every array leaf — the shape/dtype half
    of a program's identity (topology/dtype context rides in the key
    parts).  weak_type matters: an executable compiled for a weak-typed
    scalar refuses a strong-typed one of the same dtype at call time."""
    import jax
    return tuple((tuple(l.shape), str(l.dtype),
                  bool(getattr(l, "weak_type", False)))
                 for l in jax.tree.leaves(tree) if hasattr(l, "shape"))


def cache_key(tag, *parts, fingerprint=None):
    """Stable hex key for one compiled program: tag + context parts +
    runtime fingerprint, hashed.  Parts are ``repr``'d — pass only values
    with deterministic reprs (tuples, strings, numbers, dataclasses)."""
    payload = {"tag": str(tag),
               "parts": [repr(p) for p in parts],
               "fp": fingerprint or runtime_fingerprint()}
    h = hashlib.sha256(json.dumps(payload, sort_keys=True,
                                  default=repr).encode())
    return h.hexdigest()[:40]


# --------------------------------------------------------------------- #
# Layer 2: serialized executables
# --------------------------------------------------------------------- #
class ExecutableStore:
    """On-disk store of serialized ``jax.stages.Compiled`` executables.

    Layout: ``<dir>/<key>.bin`` (pickled ``serialize_executable.serialize``
    triple) + ``<dir>/<key>.json`` (fingerprint metadata, written LAST so a
    half-written entry is never loadable).  Every failure path is a miss,
    never an error to the caller."""

    def __init__(self, directory, fingerprint=None):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self._fp = fingerprint or runtime_fingerprint()

    def _paths(self, key):
        base = os.path.join(self.directory, key)
        return base + ".bin", base + ".json"

    def load(self, key):
        """Deserialized executable, or None (miss / mismatch / error)."""
        bin_path, meta_path = self._paths(key)
        if not (os.path.exists(bin_path) and os.path.exists(meta_path)):
            _STATS.executable_misses += 1
            return None
        try:
            with open(meta_path) as f:
                meta = json.load(f)
            if meta.get("fingerprint") != self._fp:
                _STATS.executable_mismatches += 1
                _STATS.executable_misses += 1
                logger.debug(
                    f"executable cache {key}: fingerprint mismatch "
                    f"(entry {meta.get('fingerprint')} vs live {self._fp}) "
                    f"— recompiling")
                return None
            with open(bin_path, "rb") as f:
                payload, in_tree, out_tree = pickle.loads(f.read())
            import jax
            from jax.experimental import serialize_executable
            # load onto the devices the program was compiled for — the
            # default is EVERY local device, which turns a one-device
            # program into an n-shard one
            by_id = {d.id: d for d in jax.devices()}
            exe = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in meta["device_ids"]])
        except Exception as e:
            _STATS.executable_errors += 1
            _STATS.executable_misses += 1
            logger.debug(f"executable cache load failed for {key}: {e}")
            return None
        _STATS.executable_hits += 1
        return exe

    def save(self, key, compiled) -> bool:
        """Serialize + persist; atomic (tmp + rename), meta written last."""
        bin_path, meta_path = self._paths(key)
        try:
            from jax.experimental import serialize_executable
            blob = pickle.dumps(serialize_executable.serialize(compiled))
            device_ids = [d.id for d in compiled._executable
                          ._unloaded_executable.device_list]
            tmp = bin_path + f".tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, bin_path)
            tmp = meta_path + f".tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({"fingerprint": self._fp, "key": key,
                           "device_ids": device_ids,
                           "bytes": len(blob), "created": time.time()}, f)
            os.replace(tmp, meta_path)
        except Exception as e:
            _STATS.executable_errors += 1
            logger.debug(f"executable cache save failed for {key}: {e}")
            return False
        _STATS.executable_saves += 1
        return True


# --------------------------------------------------------------------- #
# Engine facade
# --------------------------------------------------------------------- #
class ProgramCache:
    """What an engine holds: the persistent-cache wiring plus (optionally)
    an executable store, with per-program compile-time accounting."""

    def __init__(self, config: CompileCacheConfig):
        self.config = config
        cache_dir = configure_persistent_cache(
            config.cache_dir, config.min_compile_time_secs)
        # what engines memoise about a program besides the program itself
        self.notes_dir = os.path.join(cache_dir, "notes")
        self.store = None
        if config.executables:
            self.store = ExecutableStore(
                config.executable_dir
                or os.path.join(cache_dir, "executables"))

    @classmethod
    def from_config(cls, config) -> Optional["ProgramCache"]:
        """None when the block is absent/disabled — engines keep the plain
        jit path untouched in that case."""
        if config is None:
            return None
        if isinstance(config, dict):
            config = CompileCacheConfig(**config)
        if not config.enabled:
            return None
        return cls(config)

    def _note_path(self, tag, key_parts):
        return os.path.join(
            self.notes_dir, f"{tag}-{cache_key(tag, *key_parts)}.json")

    def load_note(self, tag, key_parts):
        """A small JSON fact an engine memoised beside its programs
        (:meth:`save_note`), or None: missing, unreadable, or written by
        another build (the fingerprint is part of the key)."""
        try:
            with open(self._note_path(tag, key_parts)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def save_note(self, tag, key_parts, note):
        """Persist ``note`` (JSON) under the executable store's keying;
        atomic, and best-effort like the store."""
        path = self._note_path(tag, key_parts)
        tmp = path + f".tmp.{os.getpid()}"
        try:
            os.makedirs(self.notes_dir, exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(note, f)
            os.replace(tmp, path)
        except OSError as e:
            logger.debug(f"compile cache note {tag} not saved: {e}")

    def get_or_compile(self, tag, key_parts, compile_fn, program=None):
        """Returns ``(compiled, seconds, hit)``.  ``compile_fn`` runs only
        on a store miss; the seconds — the whole look-up, compile and save,
        :func:`compile_span`'s one timing — are recorded under ``tag`` in
        :func:`stats` and the fresh executable is persisted."""
        key = cache_key(tag, *key_parts)
        with compile_span(tag, program) as sp:
            exe = self.store.load(key) if self.store is not None else None
            hit = exe is not None
            if not hit:
                exe = compile_fn()
                if self.store is not None:
                    self.store.save(key, exe)
            sp.set(store_hit=int(hit))
        if hit:
            log_dist(f"compile cache hit: {tag}", ranks=[0])
            return exe, 0.0, True
        _STATS.compile_seconds[str(tag)] = sp.dur_s
        log_dist(f"compiled {tag} in {sp.dur_s:.1f}s", ranks=[0])
        return exe, sp.dur_s, False


@contextlib.contextmanager
def compile_span(tag, program=None):
    """ONE ``dstpu.setup.compile`` span (``docs/observability.md``
    "Start-up") around a program's compile, whoever asks for it — a
    warm-up or a first use (``after_warmup`` 1: no ``dstpu.setup.warmup``
    span is open on this thread).  ``program`` is the short kind the
    dispatch spans use (``prefill_chunk``, ``decode``, ``admit``,
    ``train_step``; default: the tag).  At its close the span carries what
    the process's counters moved by inside it: the persistent cache's
    requests and hits, and JAX's three compile phases (``trace_s`` /
    ``lower_s`` / ``backend_s``; all 0 where a stored executable was
    loaded).  Its ``dur_s`` is THE compile time every report takes."""
    _register_jax_listener()
    st = _STATS
    counters = lambda: {
        "persistent_requests": st.persistent_requests,
        "persistent_hits": st.persistent_hits,
        "trace_s": st.trace_seconds, "lower_s": st.lower_seconds,
        "backend_s": st.backend_compile_seconds}
    before = counters()
    with span("dstpu.setup.compile", cat="setup",
              program=str(program or tag), tag=str(tag), store_hit=0,
              opt_out=int(_suspended > 0),
              after_warmup=int(not setup_open("dstpu.setup.warmup"))) as sp:
        try:
            yield sp
        finally:
            sp.set(**{k: v - before[k] for k, v in counters().items()})


def aot_compile_with_store(program_cache, tag, key_parts, fn, args,
                           program=None):
    """Lower+compile ``fn`` for ``args`` through ``program_cache``'s
    executable store (or inline when it is None) — the one copy of the
    AOT-with-jit-fallback block all three engines share, under ONE
    :func:`compile_span` named by ``program``.  Returns
    ``(exe, seconds, hit)``; exe is None on any failure (warned — the
    caller runs the plain jit call, which recompiles on its own clock, so
    a failure must never masquerade as a 0.0s compile or a store hit)."""
    try:
        if program_cache is not None:
            return program_cache.get_or_compile(
                tag, key_parts, lambda: fn.lower(*args).compile(),
                program=program)
        with compile_span(tag, program) as sp:
            exe = fn.lower(*args).compile()
        return exe, sp.dur_s, False
    except Exception as e:
        _STATS.aot_fallbacks += 1
        logger.warning(f"AOT compile of {tag} failed ({e}); falling back "
                       f"to the plain jit call")
        return None, 0.0, False
