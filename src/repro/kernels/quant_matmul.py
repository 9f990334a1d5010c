"""Pallas TPU kernel: blocked quantized matmul (W8A8 and weight-only int8).

The serving hot-spot of the paper's deployment scenario: activations hit an
int8 (OCS-expanded) weight matrix. Two numeric modes share one kernel body:

* **W8A8** — ``x: int8 [M, K]``, ``w: int8 [K, N]`` -> int32 MXU accumulation,
  scaled to float in the epilogue by ``x_scale [M] * w_scale [N]`` (either may
  be a scalar). This is the production int-serving mode.
* **weight-only** — ``x: bf16/f32`` -> the weight block is dequantized in VMEM
  (the int8 load from HBM is the point: the memory-roofline term halves vs
  bf16) and accumulated in f32; the epilogue applies ``w_scale`` only
  (``x_scale`` is all-ones).

Blocking: ``grid = (M/bm, N/bn, K/bk)`` with K innermost ("arbitrary"
dimension semantics); a ``[bm, bn]`` VMEM scratch accumulates across K steps
and is written once on the last step. Default tiles are 128-aligned for the
MXU (128x128 systolic array); the accumulator occupies ``bm*bn*4 = 64 KiB``
of VMEM at the defaults and each x/w tile is 16-64 KiB — comfortable with
double buffering inside the ~16 MiB v5e VMEM.

Validated in interpret mode against :mod:`repro.kernels.ref` (CPU has no MXU;
TPU is the deployment target).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["quant_matmul_kernel", "quant_matmul"]


def _kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, nk: int, int_path: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if int_path:
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...],
            w_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    else:
        # Weight-only: dequantize the int8 tile in VMEM, accumulate in f32.
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32),
            w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        scale = xs_ref[...] * ws_ref[...]  # [bm,1] * [1,bn] -> [bm,bn]
        o_ref[...] = (acc * scale).astype(o_ref.dtype)


def quant_matmul_kernel(
    x: jnp.ndarray,
    w8: jnp.ndarray,
    x_scale: jnp.ndarray,
    w_scale: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call; shapes must already be multiples of the tile sizes.

    ``x_scale``: [M, 1] f32 (all-ones for the weight-only float path);
    ``w_scale``: [1, N] f32. Per-tensor scales are passed pre-broadcast.
    """
    m, kdim = x.shape
    k2, n = w8.shape
    assert kdim == k2, (x.shape, w8.shape)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (
        x.shape, w8.shape, (bm, bn, bk),
    )
    int_path = x.dtype == jnp.int8
    nk = kdim // bk

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, int_path=int_path),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32 if int_path else jnp.float32)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, w8, x_scale, w_scale)


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def quant_matmul(
    x: jnp.ndarray,
    w8: jnp.ndarray,
    w_scale: jnp.ndarray,
    x_scale: Optional[jnp.ndarray] = None,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Shape-safe wrapper: pads M/N/K to tile multiples, slices the result.

    x: [M, K] (int8 with ``x_scale`` [M]|scalar, or float for weight-only);
    w8: [K, N] int8; w_scale: [N] | scalar. Returns [M, N] ``out_dtype``
    (defaults: f32 for the int path, x.dtype otherwise).
    """
    m, kdim = x.shape
    _, n = w8.shape
    int_path = x.dtype == jnp.int8
    if out_dtype is None:
        out_dtype = jnp.float32 if int_path else x.dtype
    if x_scale is None:
        x_scale = jnp.ones((), jnp.float32)

    xs = jnp.broadcast_to(jnp.asarray(x_scale, jnp.float32).reshape(-1, 1), (m, 1))
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(1, -1), (1, n))

    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w8, bk, 0), bn, 1)
    xsp = _pad_to(xs, bm, 0)
    wsp = _pad_to(ws, bn, 1)
    out = quant_matmul_kernel(
        xp, wp, xsp, wsp, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[:m, :n]
