"""Pallas TPU kernel: fused dynamic-quant + OCS-expanded W8A8 matmul.

The W8A8 serving hot path previously paid three XLA passes over the
activations (abs-max reduce, quantize, expanded matmul) plus an HBM
materialization of the OCS-expanded tensor ``x_exp``. This kernel fuses the
whole chain into one ``pallas_call``:

    per [bm, K] row tile (resident in VMEM, K not gridded):
      1. scale[m] = max|x[m, :K]| / qmax           (row abs-max, one VPU pass)
      2. q = clip(floor(x / scale + 1/2))          (int8, stays in VMEM)
      3. q_tail = q @ onehot(src_tail)             (OCS duplicate gather from
                                                    the already-resident rows;
                                                    one-hot int8 MXU matmul —
                                                    Mosaic has no lane gather)
      4. o[i, j] = (q_exp @ w8[:, j]) * scale * w_scale   (int8 MXU, f32 epi)

    x is read from HBM exactly once; neither ``x_exp`` nor ``q`` ever exists
    in HBM. Grid is (M/bm, N/bn) with N innermost: the x block index map is
    constant in j, so Pallas keeps the tile resident and the quantize+gather
    runs only on the first j step (``pl.when(j == 0)``), amortized over N.

**Contract (the layout invariant from repro.core.ocs):** ``w8`` is the
*packed* expanded weight matrix ``[K + S_pad, N]`` — duplicated channels
appended after the K originals, any activation-side multiplier (activation-
OCS halving, Eq. 4) folded into the duplicate rows *before* quantization
(:func:`repro.core.ocs.fold_expansion_mult`), and alignment padding rows
zero. Under that contract the integer duplicate is exact:
``Q(x)[:, src]`` == the reference ``expand -> quantize`` chain, so the kernel
is bit-identical to :func:`repro.kernels.ref.fused_quant_matmul_ref`.

Scale semantics: per-row activation scale is computed over the K *original*
channels only (duplicates share their source's quantized value, not a second
vote in the abs-max).

The wrapper falls back to the XLA composition when the row tile exceeds the
VMEM budget (mirrors :mod:`repro.kernels.dynamic_quant`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .budget import VMEM_BUDGET_BYTES, reroute
from .paged_attention import unpack_nibbles

__all__ = [
    "fused_qmatmul_kernel",
    "fused_quant_matmul",
    "w4a8_qmatmul_kernel",
    "w4a8_quant_matmul",
    "VMEM_BUDGET_BYTES",
]


def _kernel(
    x_ref, src_ref, w_ref, ws_ref, o_ref, q_ref, s_ref,
    *, kdim: int, s_pad: int, qmax: float,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _quantize():
        x = x_ref[...].astype(jnp.float32)  # [bm, K]
        amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        scale = jnp.maximum(amax, 1e-30) / qmax
        q = jnp.clip(jnp.floor(x / scale + 0.5), -qmax, qmax).astype(jnp.int8)
        q_ref[:, :kdim] = q
        s_ref[...] = scale
        if s_pad:
            # Duplicate gather as a one-hot int8 matmul: G[c, t] = 1 iff
            # src_tail[t] == c. q @ G picks exactly one int8 value per tail
            # column -> bit-exact duplication on the MXU.
            ids = jax.lax.broadcasted_iota(jnp.int32, (kdim, s_pad), 0)
            onehot = (ids == src_ref[...]).astype(jnp.int8)
            q_ref[:, kdim:] = jax.lax.dot_general(
                q, onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.int8)

    acc = jax.lax.dot_general(
        q_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    o_ref[...] = (acc.astype(jnp.float32) * (s_ref[...] * ws_ref[...])).astype(
        o_ref.dtype
    )


def fused_qmatmul_kernel(
    x: jnp.ndarray,
    w8: jnp.ndarray,
    src_tail: jnp.ndarray,
    w_scale: jnp.ndarray,
    *,
    bits: int = 8,
    bm: int = 128,
    bn: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call; shapes pre-padded. x: [M, K] float; w8: [K+S_pad, N]
    int8 packed; src_tail: [1, S_pad] int32 (dummy [1, 1] when S_pad == 0);
    w_scale: [1, N] f32."""
    m, kdim = x.shape
    ke, n = w8.shape
    s_pad = ke - kdim
    assert m % bm == 0 and n % bn == 0, (x.shape, w8.shape, (bm, bn))
    assert s_pad >= 0 and (s_pad == 0 or src_tail.shape == (1, s_pad))
    qmax = float((1 << (bits - 1)) - 1)

    return pl.pallas_call(
        functools.partial(_kernel, kdim=kdim, s_pad=s_pad, qmax=qmax),
        grid=(m // bm, n // bn),  # N innermost: x tile + q scratch reused
        in_specs=[
            pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
            pl.BlockSpec(src_tail.shape, lambda i, j: (0, 0)),
            pl.BlockSpec((ke, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, ke), jnp.int8),  # quantized expanded row tile
            pltpu.VMEM((bm, 1), jnp.float32),  # per-row scales
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, src_tail, w8, w_scale)


def _pad_axis(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _xla_fallback(x, w8, src_tail, w_scale, bits, out_dtype):
    """The sharded/dry-run composition: three XLA passes, same numerics."""
    from .ref import fused_quant_matmul_ref

    return fused_quant_matmul_ref(x, w8, w_scale, src_tail, bits, out_dtype)


def fused_quant_matmul(
    x: jnp.ndarray,
    w8: jnp.ndarray,
    w_scale: jnp.ndarray,
    src_tail: jnp.ndarray,
    *,
    bits: int = 8,
    bm: int = 128,
    bn: int = 128,
    lane: int = 128,
    out_dtype=None,
    vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
    interpret: bool = False,
) -> jnp.ndarray:
    """Shape-safe wrapper: one-pass dynamic-quant + OCS matmul.

    x: [M, K] float; w8: [K+S, N] int8 *packed* expanded weights (see module
    docstring); src_tail: [S] int32 source channel per duplicate row;
    w_scale: [N] | scalar. Returns [M, N] ``out_dtype`` (default f32).

    K and S are padded to ``lane`` multiples independently (w8 is split at K
    and each half padded with zero rows, preserving the append-after-K
    layout); M/N pad to the tile sizes. Falls back to the XLA composition
    when the resident [bm, K+S] tiles exceed ``vmem_budget_bytes``.
    """
    m, kdim = x.shape
    ke, n = w8.shape
    s = ke - kdim
    assert s >= 0 and s == src_tail.shape[0], (x.shape, w8.shape, src_tail.shape)
    if out_dtype is None:
        out_dtype = jnp.float32

    kp = kdim + ((-kdim) % lane)
    sp = s + ((-s) % lane) if s else 0
    # Per-program residency: x tile (f32) + q scratch (int8) + w block (int8),
    # times 2 for double buffering of the streamed operands.
    tile_bytes = bm * kp * 4 + bm * (kp + sp) + 2 * (kp + sp) * bn
    if tile_bytes > vmem_budget_bytes:
        reroute("fused_quant_matmul", (m, kdim, ke, n), tile_bytes,
                vmem_budget_bytes)
        return _xla_fallback(x, w8, src_tail, w_scale, bits, out_dtype)

    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(1, -1), (1, n))
    xp = _pad_axis(_pad_axis(x, bm, 0), lane, 1)
    if kp != kdim or sp != s:
        w8 = jnp.concatenate(
            [_pad_axis(w8[:kdim], lane, 0), _pad_axis(w8[kdim:], lane, 0)], axis=0
        )
    wp = _pad_axis(w8, bn, 1)
    wsp = _pad_axis(ws, bn, 1)
    if sp:
        # Padding duplicates point at channel 0; their weight rows are zero,
        # so the gathered value never reaches the output.
        srcp = _pad_axis(src_tail.reshape(1, -1).astype(jnp.int32), lane, 1)
    else:
        srcp = jnp.zeros((1, 1), jnp.int32)

    out = fused_qmatmul_kernel(
        xp, wp, srcp, wsp, bits=bits, bm=bm, bn=bn, out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[:m, :n]


# ---------------------------------------------------------------------------
# W4A8: packed int4 weights + 8-bit outlier channels, one kernel pass


def _w4a8_kernel(
    x_ref, src_ref, oidx_ref, w4_ref, s4_ref, w8_ref, s8_ref, o_ref,
    q_ref, q8_ref, s_ref,
    *, kdim: int, s_pad: int, t_pad: int, qmax: float,
):
    """Fused dynamic-quant + OCS expansion + mixed-width W4A8 matmul.

    Same first stage as :func:`_kernel` (quantize + duplicate gather on the
    first N step), then two accumulations per [bm, bn] tile: the int4 main
    term (weight nibbles unpacked in VMEM — split-half layout, so the dot
    splits into a low-half and a high-half int8 MXU pass) and the int8
    outlier term over the ``t_pad`` separated channels, gathered from the
    resident q tile by the same one-hot-matmul trick. The zeroed outlier
    rows inside ``w4`` make the two integer accumulators an exact partition
    of the full sum — bit-identical to :func:`repro.kernels.ref.w4a8_matmul_ref`.
    """
    j = pl.program_id(1)
    ke = kdim + s_pad

    @pl.when(j == 0)
    def _quantize():
        x = x_ref[...].astype(jnp.float32)
        amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        # Reciprocal-multiply form (paged_attention.quant_rows): immune to
        # XLA's loop-invariant ``amax / const -> amax * (1/const)`` rewrite,
        # so the grid-looped kernel matches the eager ref bit-for-bit.
        scale = jnp.maximum(amax, 1e-30) * (1.0 / qmax)
        q = jnp.clip(
            jnp.floor(x * (1.0 / scale) + 0.5), -qmax, qmax
        ).astype(jnp.int8)
        q_ref[:, :kdim] = q
        s_ref[...] = scale
        if s_pad:
            ids = jax.lax.broadcasted_iota(jnp.int32, (kdim, s_pad), 0)
            onehot = (ids == src_ref[...]).astype(jnp.int8)
            q_ref[:, kdim:] = jax.lax.dot_general(
                q, onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.int8)
        if t_pad:
            ids8 = jax.lax.broadcasted_iota(jnp.int32, (ke, t_pad), 0)
            onehot8 = (ids8 == oidx_ref[...]).astype(jnp.int8)
            q8_ref[...] = jax.lax.dot_general(
                q_ref[...], onehot8, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.int8)

    # Unpack the packed nibble block in VMEM: split-half layout means the
    # low nibbles are K rows [0, ke/2) and the high nibbles [ke/2, ke).
    lo, hi = (n.astype(jnp.int8) for n in unpack_nibbles(w4_ref[...]))
    half = ke // 2
    acc4 = jax.lax.dot_general(
        q_ref[:, :half], lo, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) + jax.lax.dot_general(
        q_ref[:, half:], hi, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc4.astype(jnp.float32) * (s_ref[...] * s4_ref[...])
    if t_pad:
        acc8 = jax.lax.dot_general(
            q8_ref[...], w8_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        out = out + acc8.astype(jnp.float32) * (s_ref[...] * s8_ref[...])
    o_ref[...] = out.astype(o_ref.dtype)


def w4a8_qmatmul_kernel(
    x: jnp.ndarray,
    w4: jnp.ndarray,
    src_tail: jnp.ndarray,
    oidx: jnp.ndarray,
    s4: jnp.ndarray,
    w8: jnp.ndarray,
    s8: jnp.ndarray,
    *,
    t_pad: int,
    bits: int = 8,
    bm: int = 128,
    bn: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call; shapes pre-padded. x: [M, K]; w4: [(K+S)//2, N]
    uint8 packed (outlier rows zero); src_tail: [1, S] int32 (dummy [1, 1]
    when S == 0); oidx: [1, t_pad] int32 (dummy [1, 1] when t_pad == 0);
    w8: [t_pad, N] int8 ([1, N] dummy when t_pad == 0); s4/s8: [1, N] f32."""
    m, kdim = x.shape
    kh, n = w4.shape
    ke = kh * 2
    s_pad = ke - kdim
    qmax = float((1 << (bits - 1)) - 1)
    assert m % bm == 0 and n % bn == 0, (x.shape, w4.shape, (bm, bn))
    assert s_pad >= 0
    assert t_pad == 0 or (oidx.shape == (1, t_pad) and w8.shape[0] == t_pad)

    t_blk = w8.shape[0]
    return pl.pallas_call(
        functools.partial(
            _w4a8_kernel, kdim=kdim, s_pad=s_pad, t_pad=t_pad, qmax=qmax
        ),
        grid=(m // bm, n // bn),  # N innermost: x tile + q scratch reused
        in_specs=[
            pl.BlockSpec((bm, kdim), lambda i, j: (i, 0)),
            pl.BlockSpec(src_tail.shape, lambda i, j: (0, 0)),
            pl.BlockSpec(oidx.shape, lambda i, j: (0, 0)),
            pl.BlockSpec((kh, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((t_blk, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, ke), jnp.int8),  # quantized expanded row tile
            pltpu.VMEM((bm, max(t_pad, 1)), jnp.int8),  # outlier q gather
            pltpu.VMEM((bm, 1), jnp.float32),  # per-row scales
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, src_tail, oidx, w4, s4, w8, s8)


def w4a8_quant_matmul(
    x: jnp.ndarray,
    w4: jnp.ndarray,
    s4: jnp.ndarray,
    w8: jnp.ndarray,
    s8: jnp.ndarray,
    src_tail: jnp.ndarray,
    outlier_idx: jnp.ndarray,
    *,
    bits: int = 8,
    bm: int = 128,
    bn: int = 128,
    lane: int = 128,
    out_dtype=None,
    vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
    interpret: bool = False,
) -> jnp.ndarray:
    """Shape-safe wrapper for the W4A8 outlier-separated matmul.

    Argument layout matches :func:`repro.kernels.ref.w4a8_matmul_ref` /
    :class:`repro.core.ocs.W4A8Linear`: x [M, K] float, w4 [(K+S)//2, N]
    uint8 packed, w8 [T, N] int8 outlier rows, s4/s8 [N] f32, src_tail [S]
    int32, outlier_idx [T] int32 (rows of the expanded K kept at 8-bit).

    The packed contraction axis is unpacked, split at K, each half padded
    to ``lane`` multiples, and repacked — the split-half byte layout is not
    stable under row padding, so the repack keeps the in-kernel unpack a
    pair of contiguous slices. ``outlier_idx`` entries pointing at
    duplicate rows (>= K) shift with the padding. Falls back to the XLA
    composition when the resident tiles exceed ``vmem_budget_bytes``.
    """
    from .paged_attention import pack_int4, unpack_int4
    from .ref import w4a8_matmul_ref

    m, kdim = x.shape
    kh, n = w4.shape
    ke = kh * 2
    s = ke - kdim
    t = outlier_idx.shape[0]
    assert s >= 0 and s == src_tail.shape[0], (x.shape, w4.shape, src_tail.shape)
    assert w8.shape == (t, n), (w8.shape, t, n)
    if out_dtype is None:
        out_dtype = jnp.float32

    kp = kdim + ((-kdim) % lane)
    sp = s + ((-s) % lane) if s else 0
    tp = t + ((-t) % lane) if t else 0
    tile_bytes = (
        bm * kp * 4                      # x tile (f32)
        + bm * (kp + sp)                 # q scratch (int8)
        + bm * max(tp, 1)                # outlier q scratch (int8)
        + 2 * ((kp + sp) // 2 * bn)      # packed w4 blocks (uint8, dbl-buf)
        + 2 * max(tp, 1) * bn            # w8 blocks (int8, dbl-buf)
    )
    if tile_bytes > vmem_budget_bytes:
        reroute("w4a8_quant_matmul", (m, kdim, ke, n), tile_bytes,
                vmem_budget_bytes)
        return w4a8_matmul_ref(
            x, w4, s4, w8, s8, src_tail, outlier_idx, bits, out_dtype
        )

    xp = _pad_axis(_pad_axis(x, bm, 0), lane, 1)
    wq = unpack_int4(w4.T).T  # [ke, n] int8
    if kp != kdim or sp != s:
        wq = jnp.concatenate(
            [_pad_axis(wq[:kdim], lane, 0), _pad_axis(wq[kdim:], lane, 0)],
            axis=0,
        )
    wq = _pad_axis(wq, bn, 1)
    w4p = pack_int4(wq.T).T
    s4p = _pad_axis(jnp.asarray(s4, jnp.float32).reshape(1, -1), bn, 1)
    s8p = _pad_axis(jnp.asarray(s8, jnp.float32).reshape(1, -1), bn, 1)
    if sp:
        srcp = _pad_axis(src_tail.reshape(1, -1).astype(jnp.int32), lane, 1)
    else:
        srcp = jnp.zeros((1, 1), jnp.int32)
    if tp:
        # Duplicate-row outliers (>= K) shift with the K-half padding;
        # padding entries point at channel 0 and carry zero weight rows.
        oidx = jnp.where(outlier_idx < kdim, outlier_idx,
                         outlier_idx + (kp - kdim))
        oidxp = _pad_axis(oidx.reshape(1, -1).astype(jnp.int32), lane, 1)
        w8p = _pad_axis(_pad_axis(w8, lane, 0), bn, 1)
    else:
        oidxp = jnp.zeros((1, 1), jnp.int32)
        w8p = jnp.zeros((1, w4p.shape[1]), jnp.int8)

    out = w4a8_qmatmul_kernel(
        xp, w4p, srcp, oidxp, s4p, w8p, s8p, t_pad=tp,
        bits=bits, bm=bm, bn=bn, out_dtype=out_dtype, interpret=interpret,
    )
    return out[:m, :n]
