"""TPU accelerator implementation.

The TPU analog of the reference's ``accelerator/cuda_accelerator.py`` —
every ABC method mapped onto JAX device APIs instead of torch.cuda.
"""

import os

import jax
import jax.numpy as jnp

from .abstract_accelerator import Accelerator

class TPU_Accelerator(Accelerator):

    def __init__(self, platform="tpu"):
        super().__init__()
        self._name = platform
        self._communication_backend_name = "xla"
        self._seed = 42
        self._key = None
        self._peak_bytes = {}

    # ----------------------------------------------------------------- #
    def device_name(self, device_index=None):
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def is_available(self):
        return len(self.devices()) > 0

    def devices(self):
        return jax.local_devices()

    def device_count(self):
        return jax.local_device_count()

    def global_device_count(self):
        return jax.device_count()

    def current_device(self):
        return self.devices()[0]

    def current_device_name(self):
        return self.device_name(0)

    # ----------------------------------------------------------------- #
    def synchronize(self, device_index=None):
        # XLA dispatch is async; a tiny reduction forced to completion acts
        # as a full device barrier for profiling/timers.
        jnp.zeros(()).block_until_ready()

    # ----------------------------------------------------------------- #
    def manual_seed(self, seed):
        self._seed = int(seed)
        self._key = jax.random.key(self._seed)

    def initial_seed(self):
        return self._seed

    def rng_key(self):
        if self._key is None:
            self._key = jax.random.key(self._seed)
        self._key, sub = jax.random.split(self._key)
        return sub

    # ----------------------------------------------------------------- #
    def memory_stats(self, device_index=None):
        dev = self.devices()[device_index or 0]
        stats = dev.memory_stats() or {}    # None on the CPU backend
        in_use = stats.get("bytes_in_use", 0)
        peak = max(self._peak_bytes.get(dev.id, 0),
                   stats.get("peak_bytes_in_use", 0))
        if in_use > peak:
            peak = in_use
        self._peak_bytes[dev.id] = peak
        stats.setdefault("peak_bytes_in_use", peak)
        return stats

    def memory_snapshot(self, device_index=None):
        """The base normalization (one canonical reader — see the ABC
        docstring).  A TPU's runtime reports its ``bytes_limit``; one
        that does not is an error, never an assumed capacity.  The CPU
        backend reports no stats at all: limit 0 / ``"unknown"`` — host
        RAM is not a fixed budget, callers skip budget checks."""
        snap = super().memory_snapshot(device_index)
        if not snap["bytes_limit"] and self._name == "tpu":
            raise RuntimeError(
                f"{snap['device']}: memory_stats() reports no bytes_limit "
                f"— refusing to assume a capacity for a device the "
                f"runtime cannot describe")
        return snap

    def memory_allocated(self, device_index=None):
        return self.memory_stats(device_index).get("bytes_in_use", 0)

    def max_memory_allocated(self, device_index=None):
        s = self.memory_stats(device_index)
        return max(s.get("peak_bytes_in_use", 0), s.get("bytes_in_use", 0))

    def reset_peak_memory_stats(self, device_index=None):
        dev = self.devices()[device_index or 0]
        self._peak_bytes[dev.id] = 0

    def total_memory(self, device_index=None):
        return self.memory_stats(device_index).get("bytes_limit", 0)

    def available_memory(self, device_index=None):
        s = self.memory_stats(device_index)
        return s.get("bytes_limit", 0) - s.get("bytes_in_use", 0)

    # ----------------------------------------------------------------- #
    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        return True

    def supported_dtypes(self):
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8]

    # ----------------------------------------------------------------- #
    def communication_backend_name(self):
        return self._communication_backend_name

    def get_op_builder(self, class_name):
        from deepspeed_tpu.ops.op_builder import get_builder
        return get_builder(class_name)

    def on_accelerator(self, array):
        try:
            shards = getattr(array, "sharding", None)
            if shards is None:
                return False
            platforms = {d.platform for d in shards.device_set}
            return platforms <= {self._name}
        except Exception:
            return False


class CPU_Accelerator(TPU_Accelerator):
    """CPU-simulated accelerator for hostless CI (the analog of the
    reference's fake-backend test path, ``tests/unit/common.py:92``) —
    identical surface, ``platform == "cpu"``."""

    def __init__(self):
        super().__init__(platform="cpu")

    def is_bf16_supported(self):
        return True

    def total_memory(self, device_index=None):
        try:
            import psutil
            return psutil.virtual_memory().total
        except Exception:
            return int(os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"))

    def available_memory(self, device_index=None):
        try:
            return int(os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_AVPHYS_PAGES"))
        except Exception:
            return 0
