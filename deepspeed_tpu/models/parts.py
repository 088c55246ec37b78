"""The leaves the serving model files share: an RMSNorm and its arithmetic,
a SwiGLU MLP, and the host-side count of a chunk's (query, key) pairs.
Defined here once and imported from here by every model file that uses one
(``models/transformer.py`` keeps its own: the OPT and OLMoE path).
"""

from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn


def _rms(x, scale, eps, factor=1.0):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32) * factor).astype(x.dtype)


def causal_pairs(start, end, limit):
    """(query, key) pairs of a chunk's queries ``start .. end - 1`` when
    query ``t`` sees ``min(t + 1, limit)`` keys — the host-side count
    behind a model's ``chunk_work``."""
    low = max(min(limit, end) - start, 0)      # queries under the limit
    return low * start + low * (low + 1) // 2 + (end - start - low) * limit


class _Norm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return _rms(x, scale, self.eps)


class _Mlp(nn.Module):
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         name=name)
        return dense(x.shape[-1], "down_proj")(
            nn.silu(dense(self.width, "gate_proj")(x))
            * dense(self.width, "up_proj")(x))
