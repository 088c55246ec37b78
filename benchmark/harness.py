"""What every driver shares on the measuring side: the device it must find,
the one compile cache, the profiler slice, the lines a run prints.  The
measuring path has no CPU mode — ``require_chips`` fails without a TPU."""

import json
import os
import shutil
import sys
import time

T_PROCESS_START = time.monotonic()      # setup_s counts from here


def say(**record):
    """An earlier line of standard output: one JSON object, never the last."""
    print(json.dumps(record), flush=True)


def setup_compile_cache(root):
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache`` (the path is part of
    the cache's key).  The program's engines are handed the same directory
    (``compile_cache_block``), so they set no other."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


def compile_cache_block(cache_dir):
    """The program's ``compile_cache`` config block, pointed at the
    benchmark's directory.  Layer 2 (serialized executables) stays off, as
    in chip_smoke.py."""
    return {"enabled": True, "cache_dir": cache_dir, "executables": False,
            "min_compile_time_secs": 0.5}


def require_chips(chips):
    """The device as JAX reports it; exits non-zero with a clear message,
    and no result line, unless it is ``chips`` TPU chips."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        sys.exit(f"benchmark: JAX found no TPU (platform "
                 f"{info['platform']!r}); the measuring path has no CPU mode "
                 f"and nothing was run")
    if info["count"] < chips:
        sys.exit(f"benchmark: the cell needs {chips} chip(s), JAX reports "
                 f"{info['count']}")
    if info["count"] != chips:
        sys.exit(f"benchmark: the cell is defined on {chips} chip(s) but this "
                 f"machine shows {info['count']}; run it on a machine of its "
                 f"own size")
    return info


def open_cell(root, workload, seed, seconds=0.0, trace=False, chips=None):
    """What a driver is handed: the cell's data, the device it was checked
    against, the family's code, the compile cache, and the profiler slice.
    Fails without the cell's TPU chips (``chips``: another number, for a
    calibration that runs the reference alone)."""
    import types
    from benchmark import spec
    bench = spec.Benchmark(root)
    cell = bench.cell(workload)
    # the library's logger writes to stderr; stdout is the benchmark's
    import deepspeed_tpu  # noqa: F401  (fails here in a bare checkout)
    cache_dir = setup_compile_cache(root)
    device = require_chips(chips or cell["chips"])
    ctx = types.SimpleNamespace(
        bench=bench, cell=cell, root=root, cache_dir=cache_dir, seed=seed,
        seconds=seconds, trace=trace, device=device,
        peaks=bench.peaks(device["kind"]),
        family=bench.family(cell["config"]["family"]),
        profiler=ProfilerSlice(
            root, trace, start_s=0.3 * seconds,
            length_s=min(cell["traffic"].get("trace_slice_s", 3.0),
                         0.4 * seconds)),
        setup_s=None)

    def window_started(t0):
        ctx.setup_s = t0 - T_PROCESS_START
    ctx.window_started = window_started
    return ctx


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip."""
    import jax
    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.devices())


class ProfilerSlice:
    """Opens ``jax.profiler`` over a short steady slice of a traced run and
    reduces what it wrote.  The driver's loop calls ``poll`` with the
    seconds since the window began."""

    def __init__(self, root, enabled, start_s, length_s):
        self.enabled, self.start_s, self.length_s = enabled, start_s, length_s
        self.dir = os.path.join(root, ".bench_trace")
        self.state = "idle" if enabled else "off"
        self.t_started = self.traced_s = None

    def poll(self, now_s):
        """Start the trace when its slice begins, stop it when it ends."""
        if self.state == "idle" and now_s >= self.start_s:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.state, self.t_started = "on", time.monotonic()
        elif self.state == "on" and now_s >= self.start_s + self.length_s:
            self.finish()

    def finish(self):
        """Stop a trace that is still on (the window ended inside it)."""
        if self.state == "on":
            import jax
            self.traced_s = time.monotonic() - self.t_started
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self):
        """A ``benchmark.trace.Trace`` over the slice, or None.  The
        profiler's files stay in ``.bench_trace`` until the next traced run
        (``python3 benchmark/trace.py .bench_trace`` prints them)."""
        self.finish()
        if self.state != "done":
            return None
        from benchmark import trace
        return trace.Trace(trace.read_events(self.dir))
