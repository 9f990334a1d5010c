"""Pure-jnp oracles for every Pallas kernel (the allclose reference)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "quant_matmul_ref",
    "dynamic_quant_ref",
    "ocs_gather_ref",
    "fused_quant_matmul_ref",
    "w4a8_matmul_ref",
    "moe_gmm_ref",
]


def quant_matmul_ref(
    x8: jnp.ndarray,
    w8: jnp.ndarray,
    x_scale: jnp.ndarray,
    w_scale: jnp.ndarray,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """W8A8 matmul oracle: int8 x int8 -> int32 -> f32 epilogue.

    x8: [M, K] int8; w8: [K, N] int8; x_scale: [M] or scalar; w_scale: [N] or
    scalar. y = (x8 @ w8) * x_scale[:, None] * w_scale[None, :].
    """
    acc = jax.lax.dot_general(
        x8, w8, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    xs = jnp.asarray(x_scale, jnp.float32)
    ws = jnp.asarray(w_scale, jnp.float32)
    if xs.ndim == 1:
        xs = xs[:, None]
    if ws.ndim == 1:
        ws = ws[None, :]
    return (acc.astype(jnp.float32) * xs * ws).astype(out_dtype)


def dynamic_quant_ref(x: jnp.ndarray, bits: int = 8):
    """Per-row dynamic quantization oracle.

    x: [M, K] float -> (q [M, K] int8, scale [M] f32) with
    scale = max|row| / qmax and q = clip(floor(x/scale + 0.5)).
    """
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1)
    scale = jnp.maximum(amax, 1e-30) / qmax
    q = jnp.clip(jnp.floor(x.astype(jnp.float32) / scale[:, None] + 0.5), -qmax, qmax)
    return q.astype(jnp.int8), scale


def ocs_gather_ref(
    x: jnp.ndarray, src: jnp.ndarray, mult: jnp.ndarray, bias: jnp.ndarray
) -> jnp.ndarray:
    """OCS channel-expansion oracle: y[m, c] = x[m, src[c]] * mult[c] + bias[c]."""
    return jnp.take(x, src, axis=-1) * mult + bias


def fused_quant_matmul_ref(
    x: jnp.ndarray,
    w8: jnp.ndarray,
    w_scale: jnp.ndarray,
    src_tail: jnp.ndarray,
    bits: int = 8,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """Oracle for the fused serving path: dynamic-quant -> expand -> int matmul.

    x: [M, K] float; w8: [K+S, N] int8 *packed* expanded weights (activation
    multipliers folded into the duplicate rows, padding rows zero — see
    ``repro.core.ocs.fold_expansion_mult``); src_tail: [S] int32. The
    activation scale is per-row over the K original channels; duplicates
    reuse their source's quantized value (bit-exact with the kernel).
    """
    if out_dtype is None:
        out_dtype = jnp.float32
    q, scale = dynamic_quant_ref(x, bits)
    q_exp = jnp.concatenate([q, jnp.take(q, src_tail, axis=1)], axis=1) \
        if src_tail.shape[0] else q
    acc = jax.lax.dot_general(
        q_exp, w8, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    ws = jnp.asarray(w_scale, jnp.float32).reshape(1, -1)
    # acc * (scale * ws): grouped like the kernel epilogue so the interpret-
    # mode bit-equivalence test can assert exact equality (f32 product
    # ordering matters at the ulp level).
    return (acc.astype(jnp.float32) * (scale[:, None] * ws)).astype(out_dtype)


def w4a8_matmul_ref(
    x: jnp.ndarray,
    w4: jnp.ndarray,
    s4: jnp.ndarray,
    w8: jnp.ndarray,
    s8: jnp.ndarray,
    src_tail: jnp.ndarray,
    outlier_idx: jnp.ndarray,
    bits: int = 8,
    out_dtype=None,
) -> jnp.ndarray:
    """Oracle for the W4A8 outlier-separated serving path.

    x: [M, K] float; w4: [(K+S)//2, N] uint8 split-half packed int4 weights
    with outlier rows zeroed (``repro.core.ocs.W4A8Linear`` layout); w8:
    [T, N] int8 outlier rows; s4/s8: [N] f32 per-column scales; src_tail:
    [S] int32 OCS duplicate sources; outlier_idx: [T] int32 rows of the
    expanded K kept at 8-bit.

    Two exact integer accumulations (the zeroed rows in ``w4`` make them a
    partition of the sum) with the f32 epilogue grouped like the kernel —
    ``acc4*(a_s*s4) + acc8*(a_s*s8)`` — so interpret-mode equivalence tests
    can assert bit-exact equality. The activation quant is the
    reciprocal-multiply form of ``paged_attention.quant_rows`` (not
    ``dynamic_quant_ref``): inside a compiled loop body XLA rewrites a
    loop-invariant ``amax / qmax`` into ``amax * (1/qmax)`` (a 1-ulp
    difference), so the division form cannot be reproduced bit-exactly by
    a grid-looped kernel.
    """
    from .paged_attention import quant_rows, unpack_int4

    if out_dtype is None:
        out_dtype = jnp.float32
    q, a_s = quant_rows(x, qmax=float((1 << (bits - 1)) - 1))
    q_exp = jnp.concatenate([q, jnp.take(q, src_tail, axis=1)], axis=1) \
        if src_tail.shape[0] else q
    wq = unpack_int4(w4.T).T  # int8 [K+S, N], outlier rows zero
    acc4 = jax.lax.dot_general(
        q_exp, wq, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    ).astype(jnp.float32)
    s4r = jnp.asarray(s4, jnp.float32).reshape(1, -1)
    y = acc4 * (a_s[:, None] * s4r)
    if outlier_idx.shape[0]:
        acc8 = jax.lax.dot_general(
            jnp.take(q_exp, outlier_idx, axis=1), w8,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
        s8r = jnp.asarray(s8, jnp.float32).reshape(1, -1)
        y = y + acc8 * (a_s[:, None] * s8r)
    return y.astype(out_dtype)


def ocs_quant_matmul_ref(
    x: jnp.ndarray,
    w8: jnp.ndarray,
    w_scale: jnp.ndarray,
    src_tail: jnp.ndarray,
    x_scale=None,
    tail_mult=None,
    out_dtype=None,
) -> jnp.ndarray:
    """OCS-expanded matmul oracle: materialize x_exp = [x | x[:, src]] then matmul.

    Mirrors :func:`repro.kernels.ocs_matmul.ocs_quant_matmul` (same scale
    semantics, same accumulation dtypes) but pays the HBM materialization the
    kernel avoids.
    """
    int_path = x.dtype == jnp.int8
    if out_dtype is None:
        out_dtype = jnp.float32 if int_path else x.dtype
    tail = jnp.take(x, src_tail, axis=1)
    if tail_mult is not None:
        tail = tail * tail_mult
    x_exp = jnp.concatenate([x, tail], axis=1)
    acc_t = jnp.int32 if int_path else jnp.float32
    if not int_path:
        x_exp = x_exp.astype(jnp.float32)
        w = w8.astype(jnp.float32)
    else:
        w = w8
    acc = jax.lax.dot_general(
        x_exp, w, (((1,), (0,)), ((), ())), preferred_element_type=acc_t
    ).astype(jnp.float32)
    if x_scale is not None:
        acc = acc * jnp.asarray(x_scale, jnp.float32).reshape(-1, 1)
    acc = acc * jnp.asarray(w_scale, jnp.float32).reshape(1, -1)
    return acc.astype(out_dtype)


def moe_gmm_ref(x, w8, w_scale, src_tail, mult_tail, group, n_used):
    """XLA oracle of :func:`moe_gmm`: one ragged matmul of the expanded rows
    over the int8 stack, each expert on its own tiles' rows, float32
    accumulation, the expert's column scale after; rows of unused tiles
    read 0."""
    from .moe_gmm import column_scales, expand_rows, group_sizes

    e, ke, n = w8.shape
    bm = x.shape[0] // group.shape[0]
    x_exp = expand_rows(x, src_tail, mult_tail, group).astype(jnp.float32)
    y = jax.lax.ragged_dot(
        x_exp, w8.astype(jnp.float32), group_sizes(group, n_used, e, bm),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    scale = column_scales(w_scale, e, n)[jnp.repeat(group, bm), 0]
    return (y * scale).astype(x.dtype)
