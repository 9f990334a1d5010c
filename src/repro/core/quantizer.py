"""Linear symmetric quantization (paper Eq. 1, Distiller-compatible grid).

The paper uses symmetric k-bit quantization with ``2^k - 1`` grid points
(sign-magnitude: a grid point at zero, ``2^(k-1) - 1`` positive and the same
number of negative points)::

    LinearQuant(x) = round(x * (2^(k-1) - 1) / max|x|) * max|x| / (2^(k-1) - 1)

We expose three layers of API:

* ``compute_scale`` / ``quantize_int`` / ``dequantize`` — the true integer path
  (int8/int16 storage + float scale), used by the serving kernels.
* ``fake_quant`` — quantize+dequantize in float, used for accuracy evaluation
  (bit-exact with the integer path by construction).
* ``QuantParams`` — a pytree bundling the integer tensor, scale, and metadata.

Per-tensor scales are the paper-faithful default; per-(output)-channel scales are
the beyond-paper option (axis-wise max).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "qmax",
    "compute_scale",
    "quantize_int",
    "dequantize",
    "fake_quant",
    "QuantParams",
    "quantize_tensor",
    "storage_dtype",
]


def qmax(bits: int) -> int:
    """Largest positive integer level: 2^(k-1) - 1 (sign-magnitude grid)."""
    if bits < 2:
        raise ValueError(f"need >=2 bits for signed symmetric quant, got {bits}")
    return (1 << (bits - 1)) - 1


def storage_dtype(bits: int):
    """Smallest integer dtype that can hold a k-bit signed value."""
    if bits <= 8:
        return jnp.int8
    if bits <= 16:
        return jnp.int16
    return jnp.int32


@partial(jax.jit, static_argnames=("channel_axis",))
def _reduce_absmax(x: jnp.ndarray, channel_axis: Optional[int]) -> jnp.ndarray:
    if channel_axis is None:
        return jnp.max(jnp.abs(x))
    axes = tuple(i for i in range(x.ndim) if i != channel_axis % x.ndim)
    return jnp.max(jnp.abs(x), axis=axes, keepdims=False)


def compute_scale(
    x: jnp.ndarray,
    bits: int,
    *,
    channel_axis: Optional[int] = None,
    clip: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Scale s such that q = round(x / s), q in [-qmax, qmax].

    ``clip`` overrides the dynamic range (the clipping threshold T); otherwise
    the full max|x| is used (paper Eq. 1). Returns a scalar (per-tensor) or a
    vector over ``channel_axis`` (per-channel).
    """
    if clip is not None:
        rng = jnp.asarray(clip, dtype=jnp.float32)
    else:
        rng = _reduce_absmax(x.astype(jnp.float32), channel_axis)
    # Clamp so the resulting scale is a *normal* float: a subnormal scale is
    # flushed to zero by XLA's FTZ mode and dequantization collapses
    # (hypothesis-found edge case at max|x| ~ 1.2e-38).
    rng = jnp.maximum(rng, jnp.finfo(jnp.float32).tiny * qmax(bits))
    return rng / qmax(bits)


def _broadcast_scale(scale: jnp.ndarray, ndim: int, channel_axis: Optional[int]):
    if channel_axis is None or scale.ndim == 0:
        return scale
    shape = [1] * ndim
    shape[channel_axis % ndim] = -1
    return scale.reshape(shape)


def quantize_int(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    bits: int,
    *,
    channel_axis: Optional[int] = None,
) -> jnp.ndarray:
    """Round-to-nearest, ties up: Q(v) = floor(v + 1/2), then saturate.

    This is the paper's §3.3 rounding function — the Hermite-identity proof of
    quantization-aware splitting holds *exactly* for this mode (ties-to-even
    would break ``Q(w) == Q(w1) + Q(w2)`` at grid midpoints).
    """
    s = _broadcast_scale(scale, x.ndim, channel_axis)
    q = jnp.floor(x.astype(jnp.float32) / s + 0.5)
    q = jnp.clip(q, -qmax(bits), qmax(bits))
    return q.astype(storage_dtype(bits))


_quantize_int = jax.jit(quantize_int, static_argnames=("bits", "channel_axis"))


def dequantize(
    q: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    channel_axis: Optional[int] = None,
    dtype=jnp.float32,
) -> jnp.ndarray:
    s = _broadcast_scale(scale, q.ndim, channel_axis)
    return (q.astype(jnp.float32) * s).astype(dtype)


def fake_quant(
    x: jnp.ndarray,
    bits: int,
    *,
    channel_axis: Optional[int] = None,
    clip: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Quantize-dequantize in float. Values beyond ``clip`` saturate."""
    scale = compute_scale(x, bits, channel_axis=channel_axis, clip=clip)
    q = quantize_int(x, scale, bits, channel_axis=channel_axis)
    return dequantize(q, scale, channel_axis=channel_axis, dtype=x.dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantParams:
    """A quantized tensor: integer values + scale (+ static metadata)."""

    values: jnp.ndarray  # int8/int16 storage
    scale: jnp.ndarray  # scalar or per-channel vector (f32)
    bits: int = dataclasses.field(metadata=dict(static=True), default=8)
    channel_axis: Optional[int] = dataclasses.field(
        metadata=dict(static=True), default=None
    )

    def dequant(self, dtype=jnp.float32) -> jnp.ndarray:
        return dequantize(
            self.values, self.scale, channel_axis=self.channel_axis, dtype=dtype
        )

    @property
    def shape(self):
        return self.values.shape


def quantize_tensor(
    x: jnp.ndarray,
    bits: int,
    *,
    channel_axis: Optional[int] = None,
    clip: Optional[jnp.ndarray] = None,
) -> QuantParams:
    """The range reduction and the integer values each run as one compiled
    program: on a large host array the elementwise steps fuse instead of
    each materializing a float copy. (The scale's division by ``qmax`` stays
    eager: compiled, a division by a constant becomes a multiplication by
    its rounded reciprocal.)"""
    scale = compute_scale(x, bits, channel_axis=channel_axis, clip=clip)
    q = _quantize_int(x, scale, bits, channel_axis=channel_axis)
    return QuantParams(values=q, scale=scale, bits=bits, channel_axis=channel_axis)
