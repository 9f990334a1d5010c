"""Outlier Channel Splitting (paper §3) — the core contribution.

A linear layer ``y = x @ W`` (``W: [Cin, Cout]``) is expanded by duplicating
input channels that contain outliers:

* **weight OCS** (Eq. 3): duplicate input channel ``m``; the two copies of row
  ``W[m]`` are *halved* (naive) or QA-split; activations are duplicated
  unchanged (``x_exp[c] = x[src[c]]``).
* **activation OCS** (Eq. 4): duplicate input channel ``m``; the weight rows
  are copied unchanged and the two activation copies are halved.

Both are captured by an affine expansion spec applied to activations::

    x_exp[..., c] = x[..., src[c]] * mult[c] + bias[c]

so the expanded layer is ``y = x_exp @ W_exp`` with functional equivalence
``x_exp @ W_exp == x @ W`` in float.

**Quantization-aware (QA) splitting** (§3.3): with grid step ``Δ`` and
``Q(v) = Δ·⌊v/Δ + 1/2⌋`` (round half up), splitting ``w`` into
``((w − Δ/2)/2, (w + Δ/2)/2)`` satisfies ``Q(w) = Q(w₁) + Q(w₂)`` exactly
(Hermite's identity, Eq. 7/8). The step Δ depends on the post-split dynamic
range, so we run a short fixed-point iteration: simulate with naive halving to
estimate Δ, re-split QA-style, re-derive Δ (converges in 1–2 rounds; the
correction is O(Δ/4)).

**Channel selection** (§3.4): split one channel at a time, always the channel
holding the current global max |value|; ``ceil(r·C)`` splits for expansion
ratio ``r``. Activations use calibration stats (99th-percentile exceedance
counts, §5.3) or the per-batch Oracle (Table 4).

Splitting itself is host-side numpy (PTQ is an offline pipeline stage); the
expansion spec + expanded integer weights are consumed by jitted serving code.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .clipping import find_clip
from .histogram import CHUNK, ChannelStats, StreamingHistogram
from .quantizer import QuantParams, qmax, quantize_tensor

__all__ = [
    "OCSSpec",
    "n_splits_for_ratio",
    "split_weights",
    "split_activations_spec",
    "expand_activations",
    "fold_expansion_mult",
    "collapse_expanded",
    "oracle_expand",
    "OCSQuantLinear",
    "W4A8Linear",
    "to_w4a8",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class OCSSpec:
    """Affine channel-expansion spec: x_exp[c] = x[src[c]] * mult[c] + bias[c]."""

    src: jnp.ndarray  # int32 [C_exp]
    mult: jnp.ndarray  # f32   [C_exp]
    bias: jnp.ndarray  # f32   [C_exp]

    @property
    def n_expanded(self) -> int:
        return self.src.shape[0]

    @staticmethod
    def identity(n_channels: int) -> "OCSSpec":
        return OCSSpec(
            src=jnp.arange(n_channels, dtype=jnp.int32),
            mult=jnp.ones(n_channels, dtype=jnp.float32),
            bias=jnp.zeros(n_channels, dtype=jnp.float32),
        )


def n_splits_for_ratio(n_channels: int, ratio: float) -> int:
    """ceil(r * C) splits (paper §3.4); 0 for r == 0."""
    if ratio <= 0:
        return 0
    return int(math.ceil(ratio * n_channels))


def expanded_channels(
    cin: int, ratio: float, *, pad_to: int = 1, groups: int = 1
) -> int:
    """Expanded (and padded) contraction dim after OCS — shape arithmetic only.

    Must stay in lockstep with :func:`make_ocs_quant_linear`; the dry-run
    builds ShapeDtypeStructs from this without running the host-side split.
    """
    n = n_splits_for_ratio(cin, ratio)
    if groups <= 1:
        c = cin + n
        return c + ((-c) % pad_to)
    per = int(math.ceil(n / groups))
    gsz = cin // groups + per
    gsz = gsz + ((-gsz) % pad_to)
    return gsz * groups


def expand_activations(x: jnp.ndarray, spec: OCSSpec) -> jnp.ndarray:
    """Apply the expansion spec along the last axis of x."""
    return jnp.take(x, spec.src, axis=-1) * spec.mult + spec.bias


# ---------------------------------------------------------------------------
# Weight OCS (host-side, offline)


def _row_absmax(w: np.ndarray) -> np.ndarray:
    """max |w| of each row, a block of rows at a time (no |w|-sized copy)."""
    rows = max(1, CHUNK // max(1, math.prod(w.shape[1:])))
    return np.concatenate([np.abs(w[i:i + rows]).max(axis=1)
                           for i in range(0, max(w.shape[0], 1), rows)])


def _run_splits(w: np.ndarray, n_splits: int, delta: float, qa: bool,
                out: Optional[np.ndarray] = None):
    """Split ``n_splits`` times, each time the row holding the current global
    max |value| (§3.4); split rows are appended after the originals.

    Linear in the matrix size: the per-row max |value| is kept as a vector
    and only the two rows a split touches are re-reduced, and the output is
    allocated once (or is ``out``, every row of which is overwritten).
    ``argmax`` over that vector picks the first of tied rows, as it would
    over the whole matrix.
    """
    c = w.shape[0]
    shape = (c + n_splits,) + w.shape[1:]
    if out is None:
        out = np.empty(shape, dtype=w.dtype)
    elif out.shape != shape or out.dtype != w.dtype:
        raise ValueError(f"out is {out.dtype}{out.shape}, want {w.dtype}{shape}")
    out[:c] = w
    src = np.empty(c + n_splits, dtype=np.int32)
    src[:c] = np.arange(c, dtype=np.int32)
    row_max = np.empty(c + n_splits, dtype=w.dtype)
    row_max[:c] = _row_absmax(w)
    for n in range(c, c + n_splits):
        idx = int(np.argmax(row_max[:n]))
        row = out[idx]
        if qa and delta > 0:
            # (w - Δ/2)/2 , (w + Δ/2)/2 — exact quantization preservation
            # (Eq. 6/7).
            r1 = (row - 0.5 * delta) / 2.0
            r2 = (row + 0.5 * delta) / 2.0
        else:
            r1 = row / 2.0
            r2 = row / 2.0
        out[idx] = r1
        out[n] = r2
        src[n] = src[idx]
        row_max[idx] = np.abs(r1).max()
        row_max[n] = np.abs(r2).max()
    return out, src


def _run_splits_grouped(
    w: np.ndarray, n_total: int, delta: float, qa: bool, groups: int,
    out: Optional[np.ndarray] = None,
):
    """Split within ``groups`` contiguous channel groups (TP-shard locality).

    Each group receives ``ceil(n_total / groups)`` splits of *its own* current
    max channel, so duplicated channels stay on the same tensor-parallel shard
    as their source and the expanded dim stays evenly shardable. ``groups=1``
    reproduces the paper's global selection exactly, into ``out`` if given
    (grouped splits allocate their own result).
    """
    if groups <= 1:
        return _run_splits(w, n_total, delta, qa, out=out)
    c = w.shape[0]
    if c % groups:
        raise ValueError(f"channels {c} not divisible by groups {groups}")
    per = int(math.ceil(n_total / groups))
    gsz = c // groups
    outs, srcs = [], []
    for g in range(groups):
        wg, sg = _run_splits(w[g * gsz : (g + 1) * gsz], per, delta, qa)
        outs.append(wg)
        srcs.append(sg + g * gsz)
    return np.concatenate(outs, axis=0), np.concatenate(srcs, axis=0)


def split_weights(
    w: np.ndarray,
    ratio: float,
    bits: int,
    *,
    qa: bool = True,
    clip_method: Optional[str] = None,
    fixed_point_iters: int = 2,
    groups: int = 1,
    n_splits: Optional[int] = None,
) -> Tuple[np.ndarray, OCSSpec, float]:
    """Weight OCS on ``w: [Cin, Cout]``.

    Returns ``(w_expanded, spec, clip_threshold)`` where ``spec`` duplicates
    activations unchanged (mult=1, bias=0) and ``clip_threshold`` is the
    post-split threshold chosen by ``clip_method`` (max|w| when None) — feed it
    to the quantizer as the grid range. ``n_splits`` overrides the per-layer
    ``ceil(r*C)`` count (knapsack allocation, §3.4).
    """
    w = np.asarray(w, dtype=np.float32)
    if w.ndim != 2:
        raise ValueError(f"split_weights expects [Cin, Cout], got {w.shape}")
    n = n_splits_for_ratio(w.shape[0], ratio) if n_splits is None else int(n_splits)
    if n == 0:
        spec = OCSSpec.identity(w.shape[0])
        t = find_clip(w, bits, clip_method)
        return w, spec, float(t)

    # Pass 1: naive halving to estimate the post-split grid step.
    w_est, src_est = _run_splits_grouped(w, n, 0.0, False, groups)
    thresh = find_clip(w_est, bits, clip_method)
    delta = thresh / qmax(bits)
    if qa:
        # Each pass rewrites the previous one's buffer: only its clip
        # threshold is kept.
        w_exp, src = w_est, src_est
        for _ in range(max(1, fixed_point_iters)):
            w_exp, src = _run_splits_grouped(w, n, delta, True, groups, out=w_exp)
            new_thresh = find_clip(w_exp, bits, clip_method)
            new_delta = new_thresh / qmax(bits)
            if abs(new_delta - delta) <= 1e-7 * max(delta, 1e-12):
                thresh, delta = new_thresh, new_delta
                break
            thresh, delta = new_thresh, new_delta
    else:
        w_exp, src = w_est, src_est

    spec = OCSSpec(
        src=jnp.asarray(src, dtype=jnp.int32),
        mult=jnp.ones(len(src), dtype=jnp.float32),
        bias=jnp.zeros(len(src), dtype=jnp.float32),
    )
    return w_exp, spec, float(thresh)


# ---------------------------------------------------------------------------
# Activation OCS (calibration-driven) and Oracle OCS


def split_activations_spec(
    stats: ChannelStats,
    ratio: float,
    *,
    act_delta: float = 0.0,
    qa: bool = False,
) -> OCSSpec:
    """Build an expansion spec that splits the top-outlier activation channels.

    Each selected channel (by 99th-percentile exceedance count, §5.3) is split
    once: both copies carry mult=1/2 (Eq. 4). With ``qa`` and a known
    activation grid step, biases ∓Δ/4 make the split quantization-preserving.
    """
    c = stats.n_channels
    n = n_splits_for_ratio(c, ratio)
    order = stats.split_order()[:n]
    src = list(range(c))
    mult = [1.0] * c
    bias = [0.0] * c
    for ch in order:
        ch = int(ch)
        mult[ch] = 0.5
        bias[ch] = -0.25 * act_delta if qa else 0.0
        src.append(ch)
        mult.append(0.5)
        bias.append(+0.25 * act_delta if qa else 0.0)
    return OCSSpec(
        src=jnp.asarray(src, dtype=jnp.int32),
        mult=jnp.asarray(mult, dtype=jnp.float32),
        bias=jnp.asarray(bias, dtype=jnp.float32),
    )


def duplicate_weight_rows(w: jnp.ndarray, spec: OCSSpec) -> jnp.ndarray:
    """Weight expansion for *activation* OCS: rows are copied unchanged."""
    return jnp.take(w, spec.src, axis=0)


def fold_expansion_mult(
    w_exp: np.ndarray, spec: OCSSpec
) -> Tuple[np.ndarray, OCSSpec]:
    """Fold activation-side multipliers into the expanded weight rows.

    ``x_exp @ W == (x[:, src] * mult) @ W == x[:, src] @ (mult[:, None] * W)``
    — so any expansion whose bias is zero can be *packed*: the returned
    weights carry the multiplier per row (activation-OCS halving, Eq. 4, and
    the zero padding-row masks) and the returned spec is pure duplication
    (mult == 1 everywhere). Packed weights are the contract the integer
    serving kernels rely on: the duplicated activation channel is then
    bit-identical to its source, so already-quantized int8 values can be
    copied instead of requantized (see ``repro.kernels.fused_qmatmul``).

    Fold *before* quantization — the multiplier changes the rows' dynamic
    range, so quantizing first and folding after would change the grid.
    """
    bias = np.asarray(spec.bias)
    if bias.size and np.any(bias != 0.0):
        raise ValueError(
            "fold_expansion_mult requires bias == 0 (QA activation splits "
            "carry a +-delta/4 bias that cannot move into the weights)"
        )
    mult = np.asarray(spec.mult, dtype=np.float32)
    w_folded = np.asarray(w_exp, dtype=np.float32) * mult[:, None]
    packed = OCSSpec(
        src=spec.src,
        mult=jnp.ones_like(spec.mult),
        bias=spec.bias,
    )
    return w_folded, packed


def oracle_expand(
    x: jnp.ndarray, n_split: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle OCS (Table 4): per-batch dynamic channel selection.

    Picks the ``n_split`` channels with the largest |value| *in this batch*,
    returns ``(x_expanded, src)`` with the selected channels halved (both
    copies). ``src`` must be used to gather weight rows. Fully traceable
    (static n_split, dynamic indices).
    """
    c = x.shape[-1]
    ch_max = jnp.max(jnp.abs(x.reshape(-1, c)), axis=0)
    _, top = jax.lax.top_k(ch_max, n_split)
    halve = jnp.zeros((c,), jnp.float32).at[top].set(1.0)
    mult = jnp.where(halve > 0, 0.5, 1.0)
    x_main = x * mult
    x_dup = jnp.take(x, top, axis=-1) * 0.5
    src = jnp.concatenate([jnp.arange(c, dtype=jnp.int32), top.astype(jnp.int32)])
    return jnp.concatenate([x_main, x_dup], axis=-1), src


# ---------------------------------------------------------------------------
# Collapse (for fast equivalence checks / fake-quant evaluation)


def collapse_expanded(
    w_exp: np.ndarray, spec: OCSSpec, n_orig: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Fold an expanded layer back to original shape.

    Returns ``(w_eff [n_orig, Cout], y_bias [Cout])`` such that
    ``x_exp @ w_exp == x @ w_eff + y_bias`` for every x.
    """
    w_exp = np.asarray(w_exp, dtype=np.float64)
    src = np.asarray(spec.src)
    mult = np.asarray(spec.mult, dtype=np.float64)
    bias = np.asarray(spec.bias, dtype=np.float64)
    w_eff = np.zeros((n_orig, w_exp.shape[1]), dtype=np.float64)
    np.add.at(w_eff, src, mult[:, None] * w_exp)
    y_bias = bias @ w_exp
    return w_eff.astype(np.float32), y_bias.astype(np.float32)


# ---------------------------------------------------------------------------
# Fused state for a quantized linear layer


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class OCSQuantLinear:
    """Serving-ready quantized linear: expanded int weights + expansion spec.

    ``y = (expand_activations(x, spec) [quantized to a_bits at serve time])
          @ dequant(weight)``
    """

    weight: QuantParams  # int values [C_exp(+pad), Cout]
    spec: OCSSpec
    n_orig: int = dataclasses.field(metadata=dict(static=True), default=0)
    a_bits: Optional[int] = dataclasses.field(metadata=dict(static=True), default=None)
    a_scale: Optional[jnp.ndarray] = None  # activation scale from calibration

    def dequant_weight(self, dtype=jnp.float32) -> jnp.ndarray:
        return self.weight.dequant(dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class W4A8Linear:
    """Sub-8-bit serving tier: packed int4 weights + 8-bit outlier channels.

    The W4A8 failure mode is exactly the paper's outlier problem one tier
    down: a handful of input channels dominate ``max|W[k, :]|`` and stretch
    the 4-bit grid until every other channel quantizes to a couple of
    levels. Instead of *splitting* those channels (which doubles their
    footprint), this tier *separates* them — the OCS ranking criterion
    (§3.4: channels holding the global max |value|) selects the rows that
    stay at 8-bit, and everything else drops to int4:

    ``y = q_a @ deq4(w4)  +  q_a[:, outlier_idx] @ deq8(w8)``

    with ``q_a`` the per-row dynamically int8-quantized (OCS-expanded)
    activations. ``w4`` stores two nibbles per byte along the contraction
    axis using the split-half convention of
    :func:`repro.kernels.paged_attention.pack_int4` (byte row ``j`` holds
    rows ``j`` and ``j + K/2``); outlier rows are **zeroed** inside ``w4``
    so the two integer accumulators partition the sum exactly.
    """

    w4: jnp.ndarray  # uint8 [K_exp//2, Cout] packed nibbles, outlier rows zero
    s4: jnp.ndarray  # f32 [Cout] per-output-column int4 grid scale
    w8: jnp.ndarray  # int8 [S, Cout] outlier rows at 8-bit
    s8: jnp.ndarray  # f32 [Cout] per-output-column int8 grid scale
    outlier_idx: jnp.ndarray  # int32 [S] row indices into the expanded K
    spec: OCSSpec
    n_orig: int = dataclasses.field(metadata=dict(static=True), default=0)
    a_bits: int = dataclasses.field(metadata=dict(static=True), default=8)

    @property
    def n_outliers(self) -> int:
        return self.w8.shape[0]

    @property
    def k_expanded(self) -> int:
        return self.w4.shape[0] * 2

    def dequant_weight(self, dtype=jnp.float32) -> jnp.ndarray:
        """Reconstruct the full expanded float weight [K_exp, Cout]."""
        from repro.kernels.paged_attention import unpack_int4

        wq = unpack_int4(self.w4.T).T  # int8 [K_exp, Cout]
        w = wq.astype(jnp.float32) * self.s4[None, :]
        if self.n_outliers:
            w = w.at[self.outlier_idx].add(
                self.w8.astype(jnp.float32) * self.s8[None, :]
            )
        return w.astype(dtype)


def _w4a8_split(w: np.ndarray, ratio: float):
    """Separate + quantize one [K_exp, Cout] float matrix for the W4A8 tier.

    Returns numpy ``(w4 packed uint8, s4, q8, s8, outlier_idx)``.
    """
    from repro.kernels.paged_attention import pack_int4

    k_exp, n = w.shape
    if k_exp % 2:
        raise ValueError(
            f"w4a8 split-half packing needs an even contraction dim, got {k_exp}"
        )
    s_out = n_splits_for_ratio(k_exp, ratio)
    if s_out:
        order = np.argsort(-np.abs(w).max(axis=1), kind="stable")
        outlier_idx = np.sort(order[:s_out]).astype(np.int32)
    else:
        outlier_idx = np.zeros((0,), np.int32)

    w_lo = w.copy()
    w_lo[outlier_idx] = 0.0
    s4 = (np.maximum(np.abs(w_lo).max(axis=0), 1e-30) / 7.0).astype(np.float32)
    q4 = np.clip(np.floor(w_lo / s4[None, :] + 0.5), -7, 7).astype(np.int8)
    w4 = np.asarray(pack_int4(jnp.asarray(q4.T))).T

    w_out = w[outlier_idx]  # [S, N]
    if s_out:
        s8 = (np.maximum(np.abs(w_out).max(axis=0), 1e-30) / 127.0).astype(
            np.float32
        )
    else:
        s8 = np.ones((n,), np.float32)
    q8 = np.clip(np.floor(w_out / s8[None, :] + 0.5), -127, 127).astype(np.int8)
    return w4, s4, q8, s8, outlier_idx


def to_w4a8(lin: OCSQuantLinear, ratio: float) -> "W4A8Linear":
    """Convert an int8-tier :class:`OCSQuantLinear` to the W4A8 tier.

    ``ratio`` is the outlier fraction: ``ceil(ratio * K_exp)`` expanded
    input channels — ranked by ``max|W[k, :]|``, the OCS §3.4 criterion —
    keep 8-bit rows; the rest drop to packed int4. ``ratio == 0`` is the
    naive-W4A8 ablation arm (no outlier separation). Host-side numpy, like
    the rest of the offline PTQ pipeline. Stacked (scan-sliced) leaves keep
    their leading layer dims; the outlier count is shape-static so per-layer
    index sets stack cleanly.
    """
    if not 0.0 <= ratio <= 1.0:
        raise ValueError(f"outlier ratio must be in [0, 1], got {ratio}")
    w = np.asarray(lin.weight.dequant(jnp.float32), dtype=np.float32)
    spec = lin.spec
    if w.shape[-2] % 2:
        # Split-half packing needs an even contraction dim: append one zero
        # weight row plus a dead spec entry (src 0, mult 0 — the duplicated
        # activation hits a zero row, contributing nothing).
        def _pad1(a, v):
            return jnp.concatenate(
                [a, jnp.full(a.shape[:-1] + (1,), v, a.dtype)], axis=-1
            )

        spec = OCSSpec(
            src=_pad1(spec.src, 0),
            mult=_pad1(spec.mult, 0.0),
            bias=_pad1(spec.bias, 0.0),
        )
        w = np.concatenate(
            [w, np.zeros(w.shape[:-2] + (1, w.shape[-1]), w.dtype)], axis=-2
        )
    lead = w.shape[:-2]
    flat = w.reshape((-1,) + w.shape[-2:])
    parts = [_w4a8_split(flat[i], ratio) for i in range(flat.shape[0])]
    if lead:
        def stk(i):
            return np.stack([p[i] for p in parts]).reshape(
                lead + parts[0][i].shape
            )
        w4, s4, q8, s8, oidx = (stk(i) for i in range(5))
    else:
        w4, s4, q8, s8, oidx = parts[0]

    return W4A8Linear(
        w4=jnp.asarray(w4, jnp.uint8),
        s4=jnp.asarray(s4, jnp.float32),
        w8=jnp.asarray(q8, jnp.int8),
        s8=jnp.asarray(s8, jnp.float32),
        outlier_idx=jnp.asarray(oidx, jnp.int32),
        spec=spec,
        n_orig=lin.n_orig,
        a_bits=lin.a_bits if lin.a_bits is not None else 8,
    )


def _pad_expanded(w_exp: np.ndarray, spec: OCSSpec, pad: int):
    if pad == 0:
        return w_exp, spec
    w_exp = np.concatenate(
        [w_exp, np.zeros((pad, w_exp.shape[1]), w_exp.dtype)], axis=0
    )
    spec = OCSSpec(
        src=jnp.concatenate([spec.src, jnp.zeros(pad, jnp.int32)]),
        mult=jnp.concatenate([spec.mult, jnp.zeros(pad, jnp.float32)]),
        bias=jnp.concatenate([spec.bias, jnp.zeros(pad, jnp.float32)]),
    )
    return w_exp, spec


def make_ocs_quant_linear(
    w: np.ndarray,
    ratio: float,
    bits: int,
    *,
    qa: bool = True,
    clip_method: Optional[str] = None,
    per_channel: bool = False,
    pad_to: int = 1,
    groups: int = 1,
) -> OCSQuantLinear:
    """Full offline weight pipeline: OCS split -> (clip) -> integer quantize.

    ``pad_to`` zero-pads the expanded contraction dim to a multiple (MXU tile
    alignment); zero rows quantize exactly to 0 and the spec maps them to
    channel 0 with mult 0. With ``groups > 1`` (tensor-parallel shards) the
    split is shard-local and each group is padded independently so the
    expanded dim remains evenly shardable.
    """
    w_exp, spec, thresh = split_weights(
        w, ratio, bits, qa=qa, clip_method=clip_method, groups=groups
    )
    if groups > 1:
        gsz = w_exp.shape[0] // groups
        pad = (-gsz) % pad_to
        if pad:
            parts_w, parts_s = [], []
            for g in range(groups):
                wg, sg = _pad_expanded(
                    w_exp[g * gsz : (g + 1) * gsz],
                    OCSSpec(
                        src=spec.src[g * gsz : (g + 1) * gsz],
                        mult=spec.mult[g * gsz : (g + 1) * gsz],
                        bias=spec.bias[g * gsz : (g + 1) * gsz],
                    ),
                    pad,
                )
                parts_w.append(wg)
                parts_s.append(sg)
            w_exp = np.concatenate(parts_w, axis=0)
            spec = OCSSpec(
                src=jnp.concatenate([s.src for s in parts_s]),
                mult=jnp.concatenate([s.mult for s in parts_s]),
                bias=jnp.concatenate([s.bias for s in parts_s]),
            )
    else:
        w_exp, spec = _pad_expanded(w_exp, spec, (-w_exp.shape[0]) % pad_to)
    clip = None if per_channel else thresh
    qp = quantize_tensor(
        jnp.asarray(w_exp),
        bits,
        channel_axis=1 if per_channel else None,
        clip=clip,
    )
    return OCSQuantLinear(weight=qp, spec=spec, n_orig=int(w.shape[0]))
