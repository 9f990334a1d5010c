"""Pallas TPU kernel: quantized matmul over OCS-expanded weights.

The paper's transformation makes the contraction dim ragged: the expanded
weight ``W_exp`` has ``K + S`` rows (S = split channels, §3.4) and the
activations must be duplicated to match (§3.5 "a custom layer which simply
copies and scales the appropriate channels"). ``repro.core.ocs`` appends the
duplicated channels *after* the K original ones, so the expanded activations
are ``x_exp = [x | mult * x[:, src_tail]]``. The wrapper forms ``x_exp`` in
one XLA fusion — ``M * (K+S)`` activation elements, small beside the
``(K+S) * N`` int8 weight a call reads — and the kernel is one blocked matmul
whose contraction tile only has to divide ``K + S``:

    y = x_exp @ W_exp * (x_scale * w_scale)       (one epilogue, one y write)

Grid ``(M/bm, N/bn, (K+S)/bk)``, K innermost, a ``[bm, bn]`` VMEM
accumulator written once on the last K step.

**Tiles come from the call's shape** (:func:`plan_tiles`, at trace time,
from static shapes and dtypes alone). ``bm`` is the row count rounded up to
the operand's sublane tile (16 rows for bfloat16), split into row tiles of
at most 512. ``bk`` and ``bn`` are drawn from the divisors of the stored
``K + S`` and ``N`` (multiples of 128, or the whole dim), so the weight is
never padded or copied. Of the pairs whose resident tiles fit
:data:`PLAN_BUDGET_BYTES`, the plan takes the one with the least modelled
time: a fixed cost per grid step plus the larger of the HBM bytes and the
MXU operations; at 8 decode rows that gives weight tiles of 1-3.5 MiB.
Each plan is logged once per shape and kept in :func:`tile_plans`.

**The contraction dtype is the activations'.** int8 x int8 -> int32 (W8A8).
bfloat16 x bfloat16 -> float32: the int8 weight tile is converted to
bfloat16 in VMEM and the tail multiplier (0, 1, or activation-OCS's 0.5) is
taken in bfloat16, both exact, so every product is exact in float32 and only
the order of float32 summation differs from a float32 contraction. Any
other float dtype contracts as float32 x float32.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.log import get_logger

from .budget import VMEM_BUDGET_BYTES

__all__ = [
    "ocs_matmul_kernel", "ocs_quant_matmul", "plan_tiles", "tile_plans",
    "clear_tile_plans", "TilePlan", "PLAN_BUDGET_BYTES", "VMEM_LIMIT_BYTES",
]

# The kernel asks Mosaic for this much scoped VMEM and plans its resident
# tiles within half of it, the other half left to Mosaic's own scratch.
VMEM_LIMIT_BYTES = 4 * VMEM_BUDGET_BYTES
PLAN_BUDGET_BYTES = VMEM_LIMIT_BYTES // 2

_LANE = 128
_BM_MAX = 512
_SUBLANES = {1: 32, 2: 16, 4: 8}  # rows of one (sublane, 128) tile, by itemsize
# The plan's time model, TPU v5e: a grid step's fixed cost (about 0.30 us
# measured with 128-wide tiles), HBM bytes/s and bf16 operations/s.
_STEP_S = 0.35e-6
_HBM_BYTES_S = 819e9
_MXU_FLOPS_S = 197e12

_LOG = get_logger("kernels")


class TilePlan(NamedTuple):
    bm: int
    bn: int
    bk: int
    grid: Tuple[int, int, int]
    vmem_bytes: int  # resident tiles, as :func:`_vmem_bytes` counts them

    @property
    def steps(self) -> int:
        return self.grid[0] * self.grid[1] * self.grid[2]


_PLANS: Dict[Tuple, TilePlan] = {}


def tile_plans() -> Dict[Tuple, TilePlan]:
    """``{(M, K, S, N, dtype): TilePlan}`` of every call traced since the
    last clear."""
    return dict(_PLANS)


def clear_tile_plans() -> None:
    _PLANS.clear()


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _splits(dim: int):
    """Tiles that divide ``dim``: multiples of 128, else the whole dim."""
    if dim % _LANE:
        return [dim]
    q = dim // _LANE
    return [_LANE * d for d in range(1, q + 1) if q % d == 0]


def _vmem_bytes(bm: int, bk: int, bn: int, xb: int, ob: int) -> int:
    """Double-buffered x, w and output tiles, the weight tile converted to
    the contraction dtype (none on the int8 path), the float32/int32
    accumulator and the dot's result, and the two scale blocks (padded to
    128 lanes and 8 sublanes)."""
    conv = bk * bn * xb if xb > 1 else 0
    return (2 * (bm * bk * xb + bk * bn + bm * bn * ob) + conv
            + 2 * bm * bn * 4 + 2 * (bm * _LANE * 4 + 8 * bn * 4))


def plan_tiles(
    m: int, ke: int, n: int, x_dtype, out_dtype, *,
    bm: Optional[int] = None, bn: Optional[int] = None, bk: Optional[int] = None,
) -> TilePlan:
    """The tiles for an ``[m, ke] @ [ke, n]`` call (see the module doc).

    A tile given here is taken as is and its dim padded to it; the planned
    tiles divide the stored dims and fit :data:`PLAN_BUDGET_BYTES`.
    """
    xb = jnp.dtype(x_dtype).itemsize
    ob = jnp.dtype(out_dtype).itemsize
    if bm is None:
        rows = _round_up(m, _SUBLANES[xb])
        bm = _round_up(-(-rows // -(-rows // _BM_MAX)), _SUBLANES[xb])
    mp = _round_up(m, bm)
    # Dims that are no multiple of 128 and too large for one tile pad to 128.
    for dk, dn in ((ke, n), (_round_up(ke, _LANE), _round_up(n, _LANE))):
        best = None
        for tk in [bk] if bk else _splits(dk):
            for tn in [bn] if bn else _splits(dn):
                kp, np_ = _round_up(dk, tk), _round_up(dn, tn)
                vmem = _vmem_bytes(bm, tk, tn, xb, ob)
                if vmem > PLAN_BUDGET_BYTES and not (bk and bn):
                    continue
                gi, gj, gk = mp // bm, np_ // tn, kp // tk
                # A block whose index stays put from one step to the next is
                # not fetched again.
                w_reads = 1 if gj == gk == 1 else gi
                x_reads = 1 if gk == 1 else gj
                hbm = w_reads * kp * np_ + x_reads * mp * kp * xb + mp * np_ * ob
                t = gi * gj * gk * _STEP_S + max(
                    hbm / _HBM_BYTES_S, 2 * mp * kp * np_ / _MXU_FLOPS_S)
                cand = (t, -tk * tn, TilePlan(bm, tn, tk, (gi, gj, gk), vmem))
                best = cand if best is None or cand[:2] < best[:2] else best
        if best is not None:
            return best[2]
    raise ValueError(f"no OCS matmul tiles fit {PLAN_BUDGET_BYTES} bytes of "
                     f"VMEM for [{m}, {ke}] @ [{ke}, {n}]")


def _kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    # The int8 weight tile takes the activations' dtype: exact in bfloat16
    # and float32, and int8 x int8 stays integer.
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * (xs_ref[...] * ws_ref[...])).astype(o_ref.dtype)


def ocs_matmul_kernel(
    x_exp: jnp.ndarray,
    w8: jnp.ndarray,
    x_scale: jnp.ndarray,
    w_scale: jnp.ndarray,
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw pallas_call. x_exp: [M, K+S] (int8, bfloat16 or float32);
    w8: [K+S, N] int8; every dim a multiple of its tile.

    ``x_scale``: [M, 1] f32; ``w_scale``: [1, N] f32.
    """
    m, ke = x_exp.shape
    ke2, n = w8.shape
    assert ke == ke2, (x_exp.shape, w8.shape)
    assert all(d % b == 0 for d, b in [(m, bm), (n, bn), (ke, bk)])
    nk = ke // bk
    acc_t = jnp.int32 if x_exp.dtype == jnp.int8 else jnp.float32

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_t)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(x_exp, w8, x_scale, w_scale)


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
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """OCS-expanded matmul: ``[x | mult * x[:, src_tail]] @ w8``, scaled.

    x: [M, K] (int8 + ``x_scale`` or float); w8: [K+S_pad, N] int8 expanded
    weights (rows ``K:`` are the OCS duplicates, zero rows as alignment
    padding); src_tail: [S_pad] int32 source channel per duplicated row;
    ``tail_mult``: optional per-duplicate multiplier (activation-OCS halves;
    weight-OCS leaves None = 1). Padding rows must carry mult 0 via
    ``tail_mult`` or map to a zero weight row. ``bm``/``bn``/``bk`` override
    the planned tiles (the dims are then padded to them).

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
    if not int_path and x.dtype != jnp.bfloat16:
        x = x.astype(jnp.float32)
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
            x_tail = x_tail * tail_mult.astype(x.dtype)
    x_exp = jnp.concatenate([x, x_tail], axis=1) if s else x

    plan = plan_tiles(m, ke, n, x.dtype, out_dtype, bm=bm, bn=bn, bk=bk)
    key = (m, kdim, s, n, jnp.dtype(x.dtype).name)
    if key not in _PLANS:
        _LOG.info("ocs_matmul%s: tiles (bm, bn, bk) %s, grid %s, %d bytes "
                  "of VMEM", key, plan[:3], plan.grid, plan.vmem_bytes)
    _PLANS[key] = plan

    xs = jnp.broadcast_to(jnp.asarray(x_scale, jnp.float32).reshape(-1, 1), (m, 1))
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(1, -1), (1, n))
    # Rows always pad (activations); the weight pads only for tiles given
    # by the caller or dims that are no multiple of 128 and too large for
    # one tile.
    x_exp = _pad_axis(_pad_axis(x_exp, plan.bm, 0), plan.bk, 1)
    w8 = _pad_axis(_pad_axis(w8, plan.bk, 0), plan.bn, 1)
    out = ocs_matmul_kernel(
        x_exp, w8, _pad_axis(xs, plan.bm, 0), _pad_axis(ws, plan.bn, 1),
        bm=plan.bm, bn=plan.bn, bk=plan.bk, out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[:m, :n]
