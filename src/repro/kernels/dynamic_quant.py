"""Pallas TPU kernel: fused per-row dynamic activation quantization.

Serving with W8A8 needs activations quantized *per step*: ``scale[m] =
max|x[m, :]| / 127`` then ``q = round(x / scale)``. Doing this with separate
XLA ops costs three HBM passes over ``x`` (abs-max reduce, divide, round);
this kernel fuses them into one read + one (quarter-sized) write.

Blocking: ``grid = (M/bm, K/bk)`` with K innermost; a ``[bm, 1]`` VMEM
scratch carries the running row abs-max across K tiles (pass 1), and a
second sweep re-reads the row tiles from VMEM... which Pallas cannot do
across grid steps — so instead the kernel uses the **two-output one-pass**
formulation: K is *not* gridded; each program owns ``bm`` full rows
(``[bm, K]`` resident in VMEM), computes the row max and quantizes in one
shot. For LM serving K = d_model (1.6k-8k) so a 128-row tile is 0.5-4 MiB —
fits VMEM. The wrapper falls back to two-pass XLA for K beyond the VMEM
budget.

Rounding matches the paper's Q(v) = floor(v + 1/2) exactly (ties up), so the
kernel is bit-identical to :func:`repro.kernels.ref.dynamic_quant_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .budget import VMEM_BUDGET_BYTES, reroute

__all__ = ["dynamic_quant_kernel", "dynamic_quant", "VMEM_BUDGET_BYTES"]


def _kernel(x_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # [bm, 1]
    scale = jnp.maximum(amax, 1e-30) / qmax
    q = jnp.clip(jnp.floor(x / scale + 0.5), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def dynamic_quant_kernel(
    x: jnp.ndarray, *, bits: int = 8, bm: int = 128, interpret: bool = False
):
    """x: [M, K] float, M % bm == 0 -> (q int8 [M, K], scale f32 [M, 1])."""
    m, k = x.shape
    assert m % bm == 0, (m, bm)
    qmax = float((1 << (bits - 1)) - 1)
    return pl.pallas_call(
        functools.partial(_kernel, qmax=qmax),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)


def _dynamic_quant_xla(x: jnp.ndarray, bits: int):
    """Two-pass XLA fallback (abs-max reduce, then quantize) for rows too
    large to keep resident in VMEM. Delegates to the oracle so the rounding
    stays in lockstep with the kernel by construction."""
    from .ref import dynamic_quant_ref

    return dynamic_quant_ref(x, bits)


def dynamic_quant(
    x: jnp.ndarray,
    *,
    bits: int = 8,
    bm: int = 128,
    interpret: bool = False,
    vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
):
    """Shape-safe wrapper: pads M to the tile size, returns (q, scale [M]).

    When the resident [bm, K] tile would blow the VMEM budget (K beyond
    ~d_model scales), falls back to the two-pass XLA formulation — two HBM
    reads of x instead of one, but correct at any K.
    """
    m, k = x.shape
    # The [bm, K] input tile plus the int8 output tile, double-buffered.
    need = 2 * bm * k * (x.dtype.itemsize + 1)
    if need > vmem_budget_bytes:
        reroute("dynamic_quant", x.shape, need, vmem_budget_bytes)
        return _dynamic_quant_xla(x, bits)
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    q, s = dynamic_quant_kernel(x, bits=bits, bm=bm, interpret=interpret)
    return q[:m], s[:m, 0]
