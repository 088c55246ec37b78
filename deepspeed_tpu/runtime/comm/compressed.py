"""Compressed (1-bit) collectives — TPU-native re-design of the reference's
cupy/NCCL compressed allreduce (``runtime/comm/nccl.py:54``
``NcclBackend.compressed_allreduce``, ``runtime/comm/mpi.py`` MpiBackend).

The algorithm (Tang et al.) is unchanged:

1. compensate: ``buf = x + worker_error``
2. worker-compress to ``sign(buf) × scale`` (scale = ‖buf‖₂/√n), update
   worker error feedback
3. exchange sign *bits* chunk-wise (all_to_all) + per-worker scales
4. server-decode: average the workers' signed chunks, compensate with the
   server error, re-compress, update server error
5. all_gather the server-compressed chunks → every worker holds the result

The NCCL igather/cupy packing machinery maps to ``lax`` collectives over a
mesh axis inside ``shard_map``, and cupy ``packbits`` to ``jnp.packbits`` —
the wire format really is 1 bit/element + one f32 scale per worker-chunk.
Over ICI this buys little (GSPMD reduces grads in hardware), so this backend
is the DCN-tier analog: compress what crosses the slow fabric.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.utils.logging import logger


def pack_signs(x):
    """bool/± tensor → uint8 bitmap (1 bit per element; length padded to 8)."""
    bits = (x >= 0).astype(jnp.uint8)
    n = bits.shape[-1]
    pad = (-n) % 8
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    return jnp.packbits(bits, axis=-1)


def unpack_signs(packed, n):
    """uint8 bitmap → ±1.0 float tensor of length ``n``."""
    bits = jnp.unpackbits(packed, axis=-1)[..., :n]
    return bits.astype(jnp.float32) * 2.0 - 1.0


def compressed_allreduce(x, worker_error, server_error, axis):
    """1-bit compressed mean-allreduce of ``x`` over mesh axis ``axis``.

    Must run inside ``shard_map``/``pjit`` with ``axis`` bound.  ``x`` is each
    device's full local tensor (like a plain allreduce input);
    ``worker_error`` has ``x``'s (padded) flat shape, ``server_error`` is the
    per-device chunk's shape.  Returns ``(avg, new_worker_error,
    new_server_error)``.
    """
    W = lax.psum(1, axis)
    shape = x.shape
    n = int(np.prod(shape))
    chunk = -(-n // W) * W // W  # ceil to divide evenly
    n_pad = chunk * W
    flat = jnp.pad(x.astype(jnp.float32).ravel(), (0, n_pad - n))

    # 1-2. worker compression with error feedback (scale over the n REAL
    # elements — pad zeros must not dilute it)
    buf = flat + worker_error
    my_scale = jnp.linalg.norm(buf) / jnp.sqrt(float(n))
    new_worker_error = buf - my_scale * jnp.sign(buf)

    # 3. chunk-wise sign exchange: worker j receives every worker's chunk j
    packed = pack_signs(buf.reshape(W, chunk))             # [W, chunk/8] u8
    recv = lax.all_to_all(packed, axis, split_axis=0, concat_axis=0,
                          tiled=True)                      # [W, chunk/8]
    scales = lax.all_gather(my_scale, axis)                # [W]

    # 4. server decode + re-compress.  Pad elements (global index ≥ n, all in
    # the last chunk) decode as +1 bits with no compensating error feedback —
    # mask them out of the decode AND the server scale, else they bias every
    # round (sign(0)=0 never cancels a transmitted +scale)
    my_chunk_start = lax.axis_index(axis) * chunk
    valid = (my_chunk_start + jnp.arange(chunk)) < n       # [chunk]
    n_valid = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    signs = unpack_signs(recv, chunk)                      # [W, chunk] ±1
    decoded = jnp.where(valid,
                        jnp.mean(signs * scales[:, None], axis=0), 0.0)
    sbuf = decoded + server_error
    s_scale = jnp.linalg.norm(sbuf) / jnp.sqrt(n_valid)
    new_server_error = jnp.where(valid, sbuf - s_scale * jnp.sign(sbuf), 0.0)

    # 5. broadcast server-compressed chunks to everyone
    all_packed = lax.all_gather(pack_signs(sbuf[None, :])[0], axis)  # [W, chunk/8]
    all_scales = lax.all_gather(s_scale, axis)             # [W]
    out = (unpack_signs(all_packed, chunk) * all_scales[:, None]).ravel()[:n]
    return out.reshape(shape), new_worker_error, new_server_error


class CompressedBackend:
    """Stateful wrapper holding the error-feedback buffers per named tensor
    (the reference backend keeps ``worker_errors``/``server_errors`` the same
    way).  ``allreduce(name, x)`` returns the compressed-mean result; buffers
    are created lazily on first use and live on device."""

    def __init__(self, mesh, axis):
        self.mesh = mesh
        self.axis = axis
        self.worker_errors = {}
        self.server_errors = {}
        self._fns = {}

    def size(self):
        return int(np.prod([self.mesh.shape[a] for a in
                            ((self.axis,) if isinstance(self.axis, str)
                             else self.axis)]))

    def _buffers(self, name, n):
        """Error-feedback buffers, one row per device (sharded over the
        compression axis so every device owns exactly its own feedback).
        A name reused at a different size resets its feedback (it is a new
        tensor as far as the algorithm is concerned)."""
        W = self.size()
        n_pad = -(-n // W) * W
        if name in self.worker_errors and \
                self.worker_errors[name].shape[1] != n_pad:
            logger.warning(f"CompressedBackend: tensor {name!r} reused with a "
                           f"different size; resetting its error feedback")
            del self.worker_errors[name], self.server_errors[name]
        if name not in self.worker_errors:
            from jax.sharding import NamedSharding, PartitionSpec as P
            row = NamedSharding(self.mesh, P(self.axis))
            self.worker_errors[name] = jax.device_put(
                jnp.zeros((W, n_pad), jnp.float32), row)
            self.server_errors[name] = jax.device_put(
                jnp.zeros((W, n_pad // W), jnp.float32), row)
        return self.worker_errors[name], self.server_errors[name]

    def allreduce(self, name, x):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        n = int(np.prod(x.shape))
        we, se = self._buffers(name, n)
        key = (name, x.shape, x.dtype)
        if key not in self._fns:
            axis = self.axis

            @functools.partial(
                shard_map, mesh=self.mesh,
                in_specs=(P(), P(axis), P(axis)),  # tpu-lint: disable=TL010 -- the 1-bit collective's input IS each worker's full local gradient by contract; compression + reduction happen inside, error feedback stays sharded
                out_specs=(P(), P(axis), P(axis)),
                check_vma=False)
            def fn(x, we, se):
                out, nwe, nse = compressed_allreduce(x, we[0], se[0], axis)
                return out, nwe[None, :], nse[None, :]

            self._fns[key] = jax.jit(fn)
        out, new_we, new_se = self._fns[key](x, we, se)
        self.worker_errors[name] = new_we
        self.server_errors[name] = new_se
        return out
