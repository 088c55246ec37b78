"""Accelerator abstraction.

TPU-native re-design of the reference's ``accelerator/abstract_accelerator.py:10``
(``DeepSpeedAccelerator`` ABC).  The reference surface is organized around
torch.cuda concepts (streams, events, per-device RNG); the JAX/XLA execution
model replaces explicit streams with async dispatch, so the TPU surface keeps
the *capabilities* (device enumeration, memory stats, dtype support, RNG,
synchronization, op-builder indirection, communication-backend selection) in
idiomatic JAX terms.
"""

import abc
from abc import ABC


class Accelerator(ABC):
    """Device abstraction: every device-touching layer goes through this.

    Mirrors the capability surface of the reference ABC
    (``accelerator/abstract_accelerator.py:10``): naming, device management,
    RNG, synchronization, memory introspection, dtype support, and the
    communication-backend / op-builder hooks.
    """

    def __init__(self):
        self._name = None
        self._communication_backend_name = None

    # ------------------------------------------------------------------ #
    # Identity
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def device_name(self, device_index=None):
        ...

    @abc.abstractmethod
    def is_available(self):
        ...

    @abc.abstractmethod
    def device_count(self):
        """Number of addressable (local-process-visible) devices."""
        ...

    @abc.abstractmethod
    def global_device_count(self):
        """Number of devices across all processes."""
        ...

    @abc.abstractmethod
    def devices(self):
        """The jax.Device list for this accelerator."""
        ...

    @abc.abstractmethod
    def current_device(self):
        ...

    @abc.abstractmethod
    def current_device_name(self):
        ...

    def process_index(self):
        import jax
        return jax.process_index()

    def process_count(self):
        import jax
        return jax.process_count()

    # ------------------------------------------------------------------ #
    # Execution
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def synchronize(self, device_index=None):
        """Block until all dispatched device work completes."""
        ...

    def default_matmul_precision(self):
        return "bfloat16"

    # ------------------------------------------------------------------ #
    # RNG — JAX RNG is functional; the accelerator hands out seeds/keys.
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def manual_seed(self, seed):
        ...

    @abc.abstractmethod
    def initial_seed(self):
        ...

    @abc.abstractmethod
    def rng_key(self):
        """Current root jax.random key (split on use)."""
        ...

    # ------------------------------------------------------------------ #
    # Memory
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def memory_stats(self, device_index=None):
        """dict with at least bytes_in_use / bytes_limit when available."""
        ...

    @abc.abstractmethod
    def memory_allocated(self, device_index=None):
        ...

    @abc.abstractmethod
    def max_memory_allocated(self, device_index=None):
        ...

    @abc.abstractmethod
    def reset_peak_memory_stats(self, device_index=None):
        ...

    @abc.abstractmethod
    def total_memory(self, device_index=None):
        ...

    @abc.abstractmethod
    def available_memory(self, device_index=None):
        ...

    def memory_snapshot(self, device_index=None):
        """The canonical normalized per-device memory view every
        device-memory consumer reads through (``see_memory_usage``, the
        flops profiler's budget, the autotuner's cost model, the
        serving memory sampler, bench watermarks): ``{device, platform,
        bytes_in_use, peak_bytes_in_use, bytes_limit, limit_source}``.
        The base implementation normalizes :meth:`memory_stats`
        (``limit_source`` ``"runtime"``, or ``"unknown"`` with limit 0
        where the backend reports none — the CPU test backend);
        ``TPU_Accelerator`` raises when a TPU reports no limit."""
        stats = self.memory_stats(device_index)
        limit = int(stats.get("bytes_limit") or 0)
        return {
            "device": self.device_name(device_index or 0),
            "platform": self._name,
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": limit,
            "limit_source": "runtime" if limit else "unknown",
        }

    def memory_snapshots(self):
        """One :meth:`memory_snapshot` per local device."""
        return [self.memory_snapshot(i)
                for i in range(self.device_count())]

    # ------------------------------------------------------------------ #
    # Dtype support
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def is_bf16_supported(self):
        ...

    @abc.abstractmethod
    def is_fp16_supported(self):
        ...

    @abc.abstractmethod
    def supported_dtypes(self):
        ...

    def preferred_dtype(self):
        import jax.numpy as jnp
        return jnp.bfloat16

    # ------------------------------------------------------------------ #
    # Communication / op-builder hooks (reference:
    # abstract_accelerator.py:177 communication_backend_name;
    # cuda_accelerator.py op_builder indirection)
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def communication_backend_name(self):
        ...

    @abc.abstractmethod
    def get_op_builder(self, class_name):
        ...

    @abc.abstractmethod
    def on_accelerator(self, array):
        """True if ``array`` is committed to this accelerator's devices."""
        ...

    # Profiler range annotations (reference: range_push/range_pop
    # abstract_accelerator.py:165-170) — the one span helper's stack
    # (utils/nvtx.py → monitor/trace.py::span).
    def range_push(self, msg):
        from deepspeed_tpu.utils import nvtx
        nvtx.range_push(msg)

    def range_pop(self):
        from deepspeed_tpu.utils import nvtx
        nvtx.range_pop()
