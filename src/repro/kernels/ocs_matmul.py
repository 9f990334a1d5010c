"""Pallas TPU kernel: quantized matmul with *fused OCS channel expansion*.

The paper's transformation makes the contraction dim ragged: the expanded
weight ``W_exp`` has ``K + S`` rows (S = split channels, §3.4) and the
activations must be duplicated to match (§3.5 "a custom layer which simply
copies and scales the appropriate channels"). A GPU implementation
materializes the expanded activation tensor in HBM; on TPU that is a wasted
round-trip of ``M*(K+S)`` bytes.

This kernel instead exploits the **layout invariant** established by
``repro.core.ocs``: duplicated channels are appended *after* the K original
channels, so ``x_exp = [x | x[:, src_tail]]``. The tiny tail gather
(S ≈ 1-5% of K, padded to one or two 128-lanes tiles) is done by XLA; the
kernel then consumes *both* operands and accumulates base and tail into one
VMEM scratch:

    y = x @ W_exp[:K] + x_tail @ W_exp[K:]        (one epilogue, one y write)

Grid ``(M/bm, N/bn, (K+S)/bk)`` — K innermost. For k-steps < K/bk the x
block feeds the MXU; after that the x_tail block does. Index maps clamp the
unused operand's block index so every grid step stays in bounds (the unused
DMA is dead but legal; it costs one ≤64 KiB VMEM copy on <2% of steps).

Modes match :mod:`repro.kernels.quant_matmul`: int8 x / int8 w -> int32
accumulation (W8A8) or float x / int8 w -> f32 (weight-only int8).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["ocs_matmul_kernel", "ocs_quant_matmul"]


def _kernel(
    x_ref, xt_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref,
    *, nk_base: int, nk: int, int_path: bool,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_t = jnp.int32 if int_path else jnp.float32

    def contract(a, b):
        if not int_path:
            a = a.astype(jnp.float32)
            b = b.astype(jnp.float32)
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), preferred_element_type=acc_t
        )

    @pl.when(k < nk_base)
    def _base():
        acc_ref[...] += contract(x_ref[...], w_ref[...])

    @pl.when(k >= nk_base)
    def _tail():
        acc_ref[...] += contract(xt_ref[...], w_ref[...])

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * (xs_ref[...] * ws_ref[...])).astype(o_ref.dtype)


def ocs_matmul_kernel(
    x: jnp.ndarray,
    x_tail: jnp.ndarray,
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
    """Raw pallas_call. x: [M, K]; x_tail: [M, S]; w8: [K+S, N] (all padded).

    ``x_scale``: [M, 1] f32; ``w_scale``: [1, N] f32.
    """
    m, kdim = x.shape
    m2, s = x_tail.shape
    ke, n = w8.shape
    assert m == m2 and ke == kdim + s, (x.shape, x_tail.shape, w8.shape)
    assert all(d % b == 0 for d, b in [(m, bm), (n, bn), (kdim, bk), (s, bk)])
    int_path = x.dtype == jnp.int8
    nk_base = kdim // bk
    nk = ke // bk

    return pl.pallas_call(
        functools.partial(_kernel, nk_base=nk_base, nk=nk, int_path=int_path),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            # Clamp the base index on tail steps (dead DMA, in bounds).
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, jnp.minimum(k, nk_base - 1))),
            # Clamp the tail index on base steps.
            pl.BlockSpec(
                (bm, bk), lambda i, j, k: (i, jnp.maximum(k - nk_base, 0))
            ),
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
    )(x, x_tail, w8, x_scale, w_scale)


def _pad_axis(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def ocs_quant_matmul(
    x: jnp.ndarray,
    w8: jnp.ndarray,
    w_scale: jnp.ndarray,
    src_tail: jnp.ndarray,
    x_scale: Optional[jnp.ndarray] = None,
    *,
    tail_mult: Optional[jnp.ndarray] = None,
    tail_is_mask: bool = False,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """OCS-expanded matmul without materializing the expanded activations.

    x: [M, K] (int8 + ``x_scale`` or float); w8: [K+S_pad, N] int8 expanded
    weights (rows ``K:`` are the OCS duplicates, zero rows as alignment
    padding); src_tail: [S_pad] int32 source channel per duplicated row;
    ``tail_mult``: optional per-duplicate multiplier (activation-OCS halves;
    weight-OCS leaves None = 1). Padding rows must carry mult 0 via
    ``tail_mult`` or map to a zero weight row.

    On the int8 path ``tail_mult`` must be integer-safe: pass a *concrete*
    0/1 array, or a traced one with the static flag ``tail_is_mask=True``
    (the caller's declaration that every value is 0 or 1 — e.g. the
    padding-row mask). Fractional multipliers need the offline weight
    packing (:func:`repro.core.ocs.fold_expansion_mult`).
    """
    m, kdim = x.shape
    ke, n = w8.shape
    s = ke - kdim
    assert s >= 0 and s == src_tail.shape[0], (x.shape, w8.shape, src_tail.shape)
    int_path = x.dtype == jnp.int8
    if out_dtype is None:
        out_dtype = jnp.float32 if int_path else x.dtype
    if x_scale is None:
        x_scale = jnp.ones((), jnp.float32)

    x_tail = jnp.take(x, src_tail, axis=1)
    if tail_mult is not None:
        if int_path:
            # Integer-safe multipliers (0/1 masks — e.g. the padding-row
            # mask) apply directly; fractional multipliers (activation-OCS
            # halving) would need requantization, so they must be folded
            # into the packed weight rows *offline* instead. Traced masks
            # (the jitted ops dispatch) are accepted on the caller's static
            # declaration ``tail_is_mask``.
            if tail_is_mask:
                x_tail = x_tail * tail_mult.astype(jnp.int8)
            else:
                try:
                    tm = np.asarray(tail_mult)
                except Exception:  # traced value: cannot prove integer-safety
                    tm = None
                if tm is not None and np.all((tm == 0.0) | (tm == 1.0)):
                    x_tail = x_tail * jnp.asarray(tm, jnp.int8)
                else:
                    raise ValueError(
                        "fractional (or traced) tail_mult on the int8 path "
                        "would need requantization; pack the weights with "
                        "repro.core.ocs.fold_expansion_mult (or declare a "
                        "traced 0/1 mask with tail_is_mask=True)"
                    )
        else:
            x_tail = x_tail * tail_mult

    xs = jnp.broadcast_to(jnp.asarray(x_scale, jnp.float32).reshape(-1, 1), (m, 1))
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(1, -1), (1, n))

    # Pad every dim to tile multiples (K and S pad independently; the w rows
    # between them are realigned by construction in repro.core.ocs pad_to=128).
    if kdim % bk or s % bk:
        kp = (-kdim) % bk
        sp = (-s) % bk
        x = _pad_axis(x, bk, 1)
        x_tail = _pad_axis(x_tail, bk, 1)
        w8 = jnp.concatenate(
            [
                _pad_axis(w8[:kdim], bk, 0),
                _pad_axis(w8[kdim:], bk, 0),
            ],
            axis=0,
        )
        kdim, s = kdim + kp, s + sp
    x = _pad_axis(x, bm, 0)
    x_tail = _pad_axis(x_tail, bm, 0)
    w8 = _pad_axis(w8, bn, 1)
    xsp = _pad_axis(xs, bm, 0)
    wsp = _pad_axis(ws, bn, 1)

    if s == 0:  # no splits: fall back to the plain kernel
        from .quant_matmul import quant_matmul_kernel

        out = quant_matmul_kernel(
            x, w8, xsp, wsp, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
            interpret=interpret,
        )
        return out[:m, :n]

    out = ocs_matmul_kernel(
        x, x_tail, w8, xsp, wsp, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[:m, :n]
