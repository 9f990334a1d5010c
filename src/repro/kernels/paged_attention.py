"""Pallas TPU kernel: paged-attention decode over the KV page pool.

The paged decode path previously re-materialized the whole gathered cache
every step and every layer: ``gather_pages(pool, table)`` wrote a dense
``[B, KV, T*page_size, hd]`` HBM tensor (plus its scale gathers), attention
read it back, and the current token's K/V needed a *separate* scatter into
the pool first — three HBM round trips whose cost scales with the table
extent (max context), not with the tokens actually attended. This module
replaces that with one flash-decode-style dispatch that consumes the pool
*in place*:

* **fused append** — the current Q tokens' K/V rows are quantized (int8
  pools) and DMA'd into their pages inside the kernel, so decode is one
  dispatch instead of scatter + gather + attention;
* **block-table page loads** — each grid program ``(lane b, kv head g)``
  walks its lane's block-table row and DMAs one ``[page_size, hd]`` page
  tile at a time into VMEM; nothing per-lane is ever materialized in HBM;
* **in-VMEM dequant** — int8 page rows are dequantized with their per-token
  scales right after the load (``x * scale``, the paper's linear grid);
* **online softmax** — the flash recurrence accumulates across pages, so
  the loop stops after ``(pos + Q - 1) // page_size + 1`` pages: work scales
  with the tokens attended, not the table extent;
* **position masking** — per-lane causal masks (query ``j`` sees positions
  ``<= pos + j``) *and* an explicit trash-page mask: page-0 loads are
  select-zeroed before the dots, so a poisoned (even NaN) trash page can
  never reach an output (see ``tests/test_paged_attention.py``).

``Q > 1`` queries run the speculative ``verify_step`` through the same
kernel: rows are laid out ``(query j, rep r)`` row-major, so row ``qr``
masks against ``pos + qr // rep``.

Numerics: the kernel computes attention in f32 after dequant. Float pages
match the gather oracle to float tolerance (online vs one-shot softmax);
int8 pages additionally differ from the *legacy* gather path, which
re-quantizes q and the softmax weights for s8 x s8 dots. The legacy path
stays the production fallback wherever the kernel doesn't run, so the
engine-level bit-exactness contracts (float-page parity vs the dense cache;
spec-decode greedy output identity) are preserved there unchanged.

Fallbacks (see ``ops.paged_attention``): non-TPU backends and page tiles
past the VMEM budget run :func:`paged_attention_xla` — the same fused
append + online-softmax loop expressed as a ``fori_loop`` over page *blocks*
with a dynamic trip count. It never materializes the full gather either,
which is what the ``benchmarks/paged_attention_bench.py`` kernel arm
measures on CPU. :func:`paged_attention_gather_ref` keeps the old
gather-everything formulation as the reference oracle.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .budget import VMEM_BUDGET_BYTES, reroute

__all__ = [
    "TRASH_PAGE",
    "KV4_QMAX",
    "quant_rows",
    "pack_int4",
    "unpack_nibbles",
    "unpack_int4",
    "pool_kind",
    "page_size",
    "append_rows",
    "paged_attention_gather_ref",
    "paged_attention_xla",
    "paged_attention_kernel",
    "paged_attention",
    "VMEM_BUDGET_BYTES",
]

NEG_INF = -1e30  # finite: exp(NEG_INF - NEG_INF) == 1, never NaN
TRASH_PAGE = 0  # reserved pool page (serving.kv_cache.TRASH_PAGE): never read
KV4_QMAX = 7.0  # symmetric int4 grid: quantized values live in [-7, 7]


def quant_rows(x: jnp.ndarray, qmax: float = 127.0):
    """Symmetric absmax quantization over the last axis -> (int8, f32 scale).

    The single source of truth for KV-cache-row quantization: the dense int8
    cache, the int8 page pool, and this kernel's fused append all call (or
    mirror bit-for-bit) this function, so pools written by any path agree
    bitwise. ``models.attention._quant_rows`` is an alias of this. The int4
    tier reuses the same formula at ``qmax=KV4_QMAX`` — one grid family for
    every precision tier.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    # Reciprocal-multiply, not division: XLA rewrites a loop-invariant
    # ``amax / qmax`` into ``amax * (1/qmax)`` inside compiled loop bodies (a
    # 1-ulp difference), so eager and in-kernel quantization would disagree
    # bitwise. Spelling the reciprocal out makes every context compute the
    # same thing — the cross-path pool bit-exactness contract depends on it.
    scale = jnp.maximum(amax, 1e-30) * (1.0 / qmax)
    q = jnp.clip(jnp.floor(x.astype(jnp.float32) * (1.0 / scale) + 0.5),
                 -qmax, qmax)
    return q.astype(jnp.int8), scale[..., 0]


def pack_int4(q: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Pack int8 nibble values (in [-8, 7]) two-per-byte along ``axis``.

    Split-half convention: byte ``j`` holds element ``j`` in its low nibble
    and element ``j + C/2`` in its high nibble (``C`` = the axis length).
    Pack and unpack are then contiguous half slices + a concat — no strided
    interleave, which keeps the in-kernel (Mosaic) forms trivial. The bit
    operations run in int32 and narrow at the end: Mosaic has no 8-bit
    vector shifts.
    """
    axis = axis % q.ndim
    c = q.shape[axis]
    lo = jax.lax.slice_in_dim(q, 0, c // 2, axis=axis).astype(jnp.int32) & 0xF
    hi = jax.lax.slice_in_dim(q, c // 2, c, axis=axis).astype(jnp.int32) & 0xF
    return (lo | jnp.left_shift(hi, 4)).astype(jnp.uint8)


def unpack_nibbles(b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Packed bytes -> (low, high) nibbles, each sign-extended, as int32.

    Arithmetic shifts in int32 (``(w << 28) >> 28`` for the low nibble,
    ``(w << 24) >> 28`` for the high) — no lookup table, no compare/select,
    and no 8-bit vector shift, which Mosaic cannot lower.
    """
    w = b.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(w, 28), 28)
    hi = jnp.right_shift(jnp.left_shift(w, 24), 28)
    return lo, hi


def unpack_int4(b: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`: uint8 [.., C/2, ..] -> int8 [.., C, ..]."""
    return jnp.concatenate(unpack_nibbles(b), axis=axis).astype(jnp.int8)


def pool_kind(pool) -> str:
    """Precision tier of a page pool, discriminated by the value dtype
    (jit-static): int8 values -> "int8", packed uint8 nibbles -> "int4",
    anything else -> "float"."""
    dt = pool["k"].dtype
    if dt == jnp.int8:
        return "int8"
    if dt == jnp.uint8:
        return "int4"
    return "float"


def page_size(pool) -> int:
    """Tokens per page. Packed int4 pages store two tokens per byte row
    (``[P, KV, ps/2, hd]``: row ``r`` holds token ``r`` in its low nibbles and
    token ``r + ps/2`` in its high ones), so the value tile is lane-dense;
    the scale arrays always have one entry per token."""
    if "k_scale" in pool:
        return pool["k_scale"].shape[2]
    return pool["k"].shape[2]


def append_rows(pool: Dict, k_new, v_new, table, pos) -> Dict:
    """XLA scatter of Q tokens' K/V rows through the block table.

    k_new/v_new: ``[B, Q, KV, hd]`` (post-RoPE); table: ``[B, T]``; pos:
    ``[B]`` first-token position per lane. Bitwise identical to
    ``serving.kv_cache.append_tokens`` (same clamp, same quant grid) minus
    the sharding constraint, which the model layer re-applies.
    """
    ps = page_size(pool)
    t = table.shape[1]
    qn = k_new.shape[1]
    lin = jnp.clip(pos[:, None] + jnp.arange(qn)[None, :], 0, t * ps - 1)
    pidx = jnp.take_along_axis(table, lin // ps, axis=1)  # [B, Q]
    slot = lin % ps
    out = dict(pool)
    kind = pool_kind(pool)
    if kind == "int8":
        k_q, k_s = quant_rows(k_new)
        v_q, v_s = quant_rows(v_new)
        out["k"] = pool["k"].at[pidx, :, slot, :].set(k_q)
        out["v"] = pool["v"].at[pidx, :, slot, :].set(v_q)
        out["k_scale"] = pool["k_scale"].at[pidx, :, slot].set(k_s)
        out["v_scale"] = pool["v_scale"].at[pidx, :, slot].set(v_s)
    elif kind == "int4":
        k_q, k_s = quant_rows(k_new, qmax=KV4_QMAX)
        v_q, v_s = quant_rows(v_new, qmax=KV4_QMAX)
        half = ps // 2
        hi = (slot >= half)[:, :, None, None]
        row = slot % half
        for name, q in (("k", k_q), ("v", v_q)):
            # Token ``slot`` owns one nibble of byte row ``slot % (ps/2)``:
            # merge it into the current byte, one token at a time (two
            # tokens of a verify window may share a row).
            vals = out[name]
            for j in range(qn):
                cur = vals[pidx[:, j], :, row[:, j], :].astype(jnp.int32)
                nib = q[:, j].astype(jnp.int32) & 0xF  # [B, KV, hd]
                new = jnp.where(hi[:, j], (cur & 0x0F) | jnp.left_shift(nib, 4),
                                (cur & 0xF0) | nib)
                vals = vals.at[pidx[:, j], :, row[:, j], :].set(
                    new.astype(jnp.uint8))
            out[name] = vals
        out["k_scale"] = pool["k_scale"].at[pidx, :, slot].set(k_s)
        out["v_scale"] = pool["v_scale"].at[pidx, :, slot].set(v_s)
    else:
        out["k"] = pool["k"].at[pidx, :, slot, :].set(k_new.astype(pool["k"].dtype))
        out["v"] = pool["v"].at[pidx, :, slot, :].set(v_new.astype(pool["v"].dtype))
    return out


def _q_rows(q: jnp.ndarray, kvh: int) -> jnp.ndarray:
    """[B, Q, H, hd] -> [B, KV, Q*rep, hd] f32, scaled by hd^-1/2.

    Row ``qr`` is (query ``qr // rep``, rep ``qr % rep``) — the layout every
    path's causal mask assumes.
    """
    b, qn, h, hd = q.shape
    qf = q.astype(jnp.float32) * (hd ** -0.5)
    qf = qf.reshape(b, qn, kvh, h // kvh, hd)
    return jnp.moveaxis(qf, 1, 2).reshape(b, kvh, qn * (h // kvh), hd)


def _rows_out(out: jnp.ndarray, qn: int) -> jnp.ndarray:
    """[B, KV, Q*rep, hd] -> [B, Q, H, hd] (inverse of :func:`_q_rows`)."""
    b, kvh, qr, hd = out.shape
    out = out.reshape(b, kvh, qn, qr // qn, hd)
    return jnp.moveaxis(out, 2, 1).reshape(b, qn, kvh * (qr // qn), hd)


def _dequant_zero_trash(vals, scale, readable):
    """Page values -> f32, per-row scales applied, non-readable pages
    select-zeroed (a *select*, not a multiply: NaN poison must not survive)."""
    x = vals.astype(jnp.float32)
    if scale is not None:
        x = x * scale[..., None]
    return jnp.where(readable, x, 0.0)


def _int4_flash_step(qv, kf, vf, vis, carry):
    """One page's online-softmax update for the int4 tier.

    The int4 bit-exactness contract: the gather oracle, the XLA fallback,
    and the Pallas kernel all run THIS function (the kernel on per-``(b, g)``
    2-D slices, the XLA paths batched over ``[B, KV]``) against bitwise-equal
    dequantized page tiles, so the three paths' outputs agree *bitwise* — not
    merely to tolerance like the int8 tier, whose fallback requantizes q and
    the softmax weights. ``qv``: [..., QR, hd] f32 pre-scaled; ``kf``/``vf``:
    [..., ps, hd] f32 dequantized; ``vis``: broadcastable to the [..., QR, ps]
    scores. Carry is ``(m [..., QR], l [..., QR], acc [..., QR, hd])``.
    """
    m, l, acc = carry
    s = jnp.einsum("...rd,...sd->...rs", qv, kf,
                   preferred_element_type=jnp.float32)
    s = s + jnp.where(vis, 0.0, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "...rs,...sd->...rd", p, vf, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _int4_finish(m, l, acc):
    """Normalize the int4 flash carry; fully-masked rows (retired lanes'
    all-trash tables) emit exact zeros like every other path."""
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.where(m[..., None] > 0.5 * NEG_INF, out, 0.0)


# ---------------------------------------------------------------------------
# Reference oracle: gather everything, one-shot softmax


def paged_attention_gather_ref(pool, table, pos, q, k_new, v_new) -> Tuple:
    """The demoted formulation: append, gather ``pool[table]`` dense,
    dequantize in full, one-shot softmax. Same f32-after-dequant math as the
    kernel (the legacy ``attention_decode`` int8 path additionally quantizes
    q and the softmax weights — that path lives on in the model layer)."""
    b, qn, h, hd = q.shape
    kvh, ps = pool["k"].shape[1], page_size(pool)
    t = table.shape[1]
    new_pool = append_rows(pool, k_new, v_new, table, pos)
    kind = pool_kind(pool)
    int8 = kind == "int8"

    def flat(x):  # [B, T, KV, ps, ...] -> [B, KV, T*ps, ...]
        return jnp.moveaxis(x, 2, 1).reshape((b, kvh, t * ps) + x.shape[4:])

    if kind == "int4":
        # Independent *data* path (dense gather + flatten, like the int8/
        # float oracle) but the kernel's page-blocked recurrence: the int4
        # tier's oracle is bit-exact against the kernel and XLA fallback.
        rep = h // kvh
        qr = qn * rep
        rd = jnp.repeat(table != TRASH_PAGE, ps, axis=1)[:, None, :, None]
        kf = _dequant_zero_trash(
            flat(unpack_int4(new_pool["k"][table], axis=-2)),
            flat(new_pool["k_scale"][table]), rd)
        vf = _dequant_zero_trash(
            flat(unpack_int4(new_pool["v"][table], axis=-2)),
            flat(new_pool["v_scale"][table]), rd)
        q2 = _q_rows(q, kvh)  # [B, KV, QR, hd]
        bound = pos[:, None] + (jnp.arange(qr) // rep)[None, :]  # [B, QR]
        k5 = kf.reshape(b, kvh, t, ps, hd)
        v5 = vf.reshape(b, kvh, t, ps, hd)
        page_ok = table != TRASH_PAGE  # [B, T]

        def body(i, carry):
            kb = jax.lax.dynamic_index_in_dim(k5, i, 2, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(v5, i, 2, keepdims=False)
            gpos = i * ps + jnp.arange(ps)
            ok = jax.lax.dynamic_index_in_dim(page_ok, i, 1, keepdims=True)
            vis = (gpos[None, None, :] <= bound[:, :, None]) & ok[:, :, None]
            return _int4_flash_step(q2, kb, vb, vis[:, None], carry)

        m0 = jnp.full((b, kvh, qr), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, qr), jnp.float32)
        acc0 = jnp.zeros((b, kvh, qr, hd), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, t, body, (m0, l0, acc0))
        return _rows_out(_int4_finish(m, l, acc), qn), new_pool

    readable = jnp.repeat(table != TRASH_PAGE, ps, axis=1)[:, None, :, None]
    kf = _dequant_zero_trash(
        flat(new_pool["k"][table]),
        flat(new_pool["k_scale"][table]) if int8 else None,
        readable,
    )
    vf = _dequant_zero_trash(
        flat(new_pool["v"][table]),
        flat(new_pool["v_scale"][table]) if int8 else None,
        readable,
    )
    q2 = _q_rows(q, kvh)  # [B, KV, QR, hd]
    jrow = jnp.arange(q2.shape[2]) // (h // kvh)
    vis = (jnp.arange(t * ps)[None, None, :] <= (pos[:, None] + jrow[None, :])[:, :, None])
    vis = vis & readable[:, 0, :, 0][:, None, :]
    s = jnp.einsum("bgrd,bgsd->bgrs", q2, kf, preferred_element_type=jnp.float32)
    s = s + jnp.where(vis[:, None], 0.0, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bgsd->bgrd", p, vf, preferred_element_type=jnp.float32)
    return _rows_out(out, qn), new_pool


# ---------------------------------------------------------------------------
# XLA fallback: fused append + online softmax over page blocks


def paged_attention_xla(
    pool, table, pos, q, k_new, v_new, *, block_tokens: int = 2048
) -> Tuple:
    """Gather-free paged attention in pure XLA.

    A ``fori_loop`` over blocks of ``block_tokens // page_size`` table
    columns with a *dynamic* trip count — blocks wholly past
    ``max(pos) + Q`` are never executed, so (unlike the gather path) work
    scales with the tokens attended. Per block only a
    ``[B, nb, KV, ps, hd]`` tile is gathered; the einsums contract it in
    page-major flatten and the block temps are reused buffers, so the full
    per-lane cache never exists in memory. Trash-page poison never enters:
    trash table entries are remapped to a real page before the load and
    masked out of every softmax (see the body comment).
    """
    b, qn, h, hd = q.shape
    kvh, ps = pool["k"].shape[1], page_size(pool)
    t = table.shape[1]
    rep = h // kvh
    qr = qn * rep
    kind = pool_kind(pool)
    int8 = kind == "int8"
    new_pool = append_rows(pool, k_new, v_new, table, pos)

    if kind == "int4":
        # One page per block, f32 after in-register dequant, the shared
        # _int4_flash_step recurrence: bit-exact vs the kernel and the
        # gather oracle (no s8 requant of q / softmax weights — the int4
        # tier's fallback IS the oracle). Trash pages are remapped to page 1
        # like the int8 path; their slots are invisible, so p underflows to
        # exact zero against any finite running max and the remapped values
        # never contribute; fully-masked rows are zeroed in _int4_finish.
        q2 = _q_rows(q, kvh)  # [B, KV, QR, hd]
        bound = pos[:, None] + (jnp.arange(qr) // rep)[None, :]  # [B, QR]
        n_active = jnp.minimum(
            t, (jnp.max(pos) + qn - 1) // ps + 1
        ).astype(jnp.int32)

        def body(i, carry):
            cols = jax.lax.dynamic_slice(table, (0, i), (b, 1))  # [B, 1]
            ok = cols != TRASH_PAGE
            safe = jnp.where(ok, cols, 1)
            kf = unpack_int4(new_pool["k"][safe], axis=-2).astype(jnp.float32)
            vf = unpack_int4(new_pool["v"][safe], axis=-2).astype(jnp.float32)
            kf = kf * new_pool["k_scale"][safe][..., None]
            vf = vf * new_pool["v_scale"][safe][..., None]
            # [B, 1, KV, ps, hd] -> [B, KV, ps, hd]
            kf = jnp.moveaxis(kf, 2, 1).reshape(b, kvh, ps, hd)
            vf = jnp.moveaxis(vf, 2, 1).reshape(b, kvh, ps, hd)
            gpos = i * ps + jnp.arange(ps)
            vis = (gpos[None, None, :] <= bound[:, :, None]) & ok[:, :, None]
            return _int4_flash_step(q2, kf, vf, vis[:, None], carry)

        m0 = jnp.full((b, kvh, qr), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, qr), jnp.float32)
        acc0 = jnp.zeros((b, kvh, qr, hd), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, n_active, body, (m0, l0, acc0))
        return _rows_out(_int4_finish(m, l, acc), qn), new_pool

    nb = max(1, min(t, block_tokens // ps))
    n_blocks = -(-t // nb)
    tpad = table
    if n_blocks * nb != t:  # trash-pad the ragged last block (masked anyway)
        tpad = jnp.pad(table, ((0, 0), (0, n_blocks * nb - t)),
                       constant_values=TRASH_PAGE)
    q2 = _q_rows(q, kvh)  # [B, KV, QR, hd]
    bound = pos[:, None] + (jnp.arange(qr) // rep)[None, :]  # [B, QR]
    n_active = jnp.minimum(
        n_blocks, (jnp.max(pos) + qn - 1) // (nb * ps) + 1
    ).astype(jnp.int32)
    if int8:
        # Integer path, like the legacy gather attention: quantize q once,
        # s8 x s8 -> s32 dots against the raw int8 page tiles, scales in the
        # f32 epilogue — the cache is only ever moved at int8 width. (The
        # Pallas kernel instead dequantizes in VMEM, where the f32 tile
        # never touches HBM; re-widening every block to f32 here would
        # triple the fallback's traffic.)
        q8, q_s = quant_rows(q2)  # [B, KV, QR, hd] int8, [B, KV, QR]

    def body(i, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice(tpad, (0, i * nb), (b, nb))  # [B, nb]
        readable = cols != TRASH_PAGE
        # Trash-page exclusion by *remap*, not by zeroing the loaded tiles:
        # page 0 is the only page allowed to hold junk (NaN included — it is
        # never read), so pointing its table entries at page 1 (always a
        # real, finite page: pools have >= 2 pages by construction) makes
        # every load finite, and the tiny [B, nb] visibility mask below
        # keeps the remapped slots out of every softmax — two full-block
        # selects cheaper than scrubbing k and v.
        cols = jnp.where(readable, cols, 1)
        # [B, nb, KV, ps, hd] -> [B, KV, nb*ps, hd] (page-major flatten)
        kf = jnp.moveaxis(new_pool["k"][cols], 2, 1).reshape(b, kvh, nb * ps, hd)
        vf = jnp.moveaxis(new_pool["v"][cols], 2, 1).reshape(b, kvh, nb * ps, hd)
        gpos = ((i * nb + jnp.arange(nb))[:, None] * ps
                + jnp.arange(ps)[None, :]).reshape(nb * ps)
        vis = (gpos[None, None, :] <= bound[:, :, None]) & jnp.repeat(
            readable, ps, axis=1
        )[:, None, :]
        if int8:
            ks = jnp.moveaxis(new_pool["k_scale"][cols], 2, 1)
            ks = ks.reshape(b, kvh, nb * ps)
            s32 = jnp.einsum("bgrd,bgsd->bgrs", q8, kf,
                             preferred_element_type=jnp.int32)
            s = s32.astype(jnp.float32) * q_s[..., None] * ks[:, :, None, :]
        else:
            s = jnp.einsum("bgrd,bgsd->bgrs", q2, kf,
                           preferred_element_type=jnp.float32)
        s = s + jnp.where(vis[:, None], 0.0, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        if int8:
            # p.V like the legacy path: fold the v scales into p, quantize
            # the folded p per row (over this block — a finer grid than the
            # legacy full-row quant, same tolerance class), one s8 x s8 dot.
            vs = jnp.moveaxis(new_pool["v_scale"][cols], 2, 1)
            p8, p_s = quant_rows(p * vs.reshape(b, kvh, 1, nb * ps))
            o32 = jnp.einsum("bgrs,bgsd->bgrd", p8, vf,
                             preferred_element_type=jnp.int32)
            pv = o32.astype(jnp.float32) * p_s[..., None]
        else:
            pv = jnp.einsum("bgrs,bgsd->bgrd", p, vf,
                            preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return m_new, l, acc

    m0 = jnp.full((b, kvh, qr), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, qr), jnp.float32)
    acc0 = jnp.zeros((b, kvh, qr, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_active, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # Fully-masked rows (an inactive lane's all-trash table): every score
    # stayed NEG_INF, so the remapped page-1 rows would average into the
    # output. The gather oracle, the Pallas kernel, and the legacy path all
    # return exact zeros there — match them (m moved iff any slot was
    # visible: real scores are nowhere near NEG_INF).
    out = jnp.where(m[..., None] > 0.5 * NEG_INF, out, 0.0)
    return _rows_out(out, qn), new_pool


# ---------------------------------------------------------------------------
# Pallas TPU kernel


def _paged_attn_kernel(
    # scalar prefetch
    table_ref,  # [B, T] int32
    pos_ref,  # [B] int32
    # inputs
    q_ref,  # [1, 1, QR, hd] f32 block for (b, g)
    kn_ref,  # [1, 1, Q, hd] f32 block
    vn_ref,
    k_in,  # [P, KV, ps, hd] ANY (aliased; unused — reads go through k_out)
    v_in,
    *rest,  # (ks_in, vs_in,) out refs, (scale out refs,) scratch, sems
    ps: int,
    qn: int,
    rep: int,
    t: int,
    kind: str,
):
    scaled = kind in ("int8", "int4")
    if scaled:
        (ks_in, vs_in, out_ref, k_out, v_out, ks_out, vs_out,
         k_scr, v_scr, ks_scr, vs_scr, sems) = rest
    else:
        (out_ref, k_out, v_out, k_scr, v_scr, sems) = rest
    b = pl.program_id(0)
    g = pl.program_id(1)
    pos_b = pos_ref[b]
    qr = qn * rep
    half = ps // 2

    def copy_pages(pid, to_vmem: bool):
        # Page tiles of (page pid, head g), plus the page's scale row. Reads
        # go through the *output* refs (the aliased buffers), so every
        # append is visible to the loads after it.
        pairs = [(k_out.at[pid, g], k_scr), (v_out.at[pid, g], v_scr)]
        if scaled:
            pairs += [(ks_out.at[pl.ds(pid, 1)], ks_scr),
                      (vs_out.at[pl.ds(pid, 1)], vs_scr)]
        dmas = [
            pltpu.make_async_copy(*((h, v) if to_vmem else (v, h)), sems.at[i])
            for i, (h, v) in enumerate(pairs)
        ]
        for d in dmas:
            d.start()
        for d in dmas:
            d.wait()

    def scale_col(s_scr):
        # This head's per-token scales as a [ps, 1] column, picked out of the
        # page's lane-dense scale row by a select + lane sum (exact: one
        # nonzero term per row; scales are positive and finite on real pages).
        lanes = s_scr.shape[-1]
        pick = (jax.lax.broadcasted_iota(jnp.int32, (ps, lanes), 1)
                == g * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, lanes), 0))
        return jnp.sum(jnp.where(pick, s_scr[...], 0.0), axis=1, keepdims=True)

    def page_values(scr):
        # Page tile -> [ps, hd] f32. Packed int4 tiles hold token r in the
        # low nibble and token r + ps/2 in the high nibble of row r; nibbles
        # are sign-extended by 32-bit shifts (see unpack_nibbles).
        if kind == "int4":
            lo, hi = unpack_nibbles(scr[...])
            return jnp.concatenate([lo, hi], axis=0).astype(jnp.float32)
        return scr[...].astype(jnp.float32)

    # ---- fused append: this program owns (lane b, head g)'s Q rows. Pages
    # past the prompt are never shared across lanes, so the only rows this
    # program ever reads back below are its own writes (waited on here).
    # Each row is a read-modify-write of the whole page tile, in a 32-bit
    # type: Mosaic can neither DMA one row of an 8-bit page (it is not
    # aligned to the tiling) nor select on 8-bit vectors.
    for j in range(qn):
        lin = jnp.minimum(jnp.maximum(pos_b + j, 0), t * ps - 1)
        pid = table_ref[b, lin // ps]
        slot = lin % ps
        kr = kn_ref[0, 0, j : j + 1, :].astype(jnp.float32)  # [1, hd]
        vr = vn_ref[0, 0, j : j + 1, :].astype(jnp.float32)
        copy_pages(pid, to_vmem=True)
        if not scaled:
            hit = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0) == slot
            for row, scr in ((kr, k_scr), (vr, v_scr)):
                scr[...] = jnp.where(
                    hit, row, scr[...].astype(jnp.float32)
                ).astype(scr.dtype)
        else:
            # quant_rows, inlined: same grid as every other pool writer
            # (qmax 127 for int8 pages, KV4_QMAX for packed int4 pages).
            qm = 127.0 if kind == "int8" else KV4_QMAX
            lanes = ks_scr.shape[-1]
            s_hit = (jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
                     == g * ps + slot)
            for row, scr, s_scr in ((kr, k_scr, ks_scr), (vr, v_scr, vs_scr)):
                amax = jnp.max(jnp.abs(row), axis=-1, keepdims=True)
                sc = jnp.maximum(amax, 1e-30) * (1.0 / qm)
                qrow = jnp.clip(
                    jnp.floor(row * (1.0 / sc) + 0.5), -qm, qm
                ).astype(jnp.int32)
                cur = scr[...].astype(jnp.int32)
                if kind == "int4":
                    hi = slot >= half
                    nib = qrow & 0xF
                    new = jnp.where(hi, (cur & 0x0F) | jnp.left_shift(nib, 4),
                                    (cur & 0xF0) | nib)
                    r = slot - jnp.where(hi, half, 0)
                    hit = jax.lax.broadcasted_iota(jnp.int32, (half, 1), 0) == r
                else:
                    new = qrow
                    hit = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0) == slot
                scr[...] = jnp.where(hit, new, cur).astype(scr.dtype)
                s_scr[...] = jnp.where(s_hit, sc, s_scr[...])
        copy_pages(pid, to_vmem=False)

    # ---- flash loop over this lane's active pages only.
    qv = q_ref[0, 0]  # [QR, hd] f32, pre-scaled
    bound = pos_b + jax.lax.broadcasted_iota(jnp.int32, (qr, 1), 0) // rep
    n_active = jnp.minimum(t, (pos_b + qn - 1) // ps + 1)

    def load_tiles(ti):
        pid = table_ref[b, ti]
        copy_pages(pid, to_vmem=True)
        readable = pid != TRASH_PAGE
        kf = page_values(k_scr)
        vf = page_values(v_scr)
        if scaled:  # in-VMEM dequant with the per-token scales ([ps, 1])
            kf = kf * scale_col(ks_scr)
            vf = vf * scale_col(vs_scr)
        kf = jnp.where(readable, kf, 0.0)  # select: NaN poison dies here
        vf = jnp.where(readable, vf, 0.0)
        gpos = ti * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        return kf, vf, (gpos <= bound) & readable

    if kind == "int4":
        # int4 tier: the shared _int4_flash_step recurrence on 2-D per-(b, g)
        # slices — the same op sequence the XLA fallback and the gather
        # oracle run batched, so the three paths agree bitwise (the tier's
        # exactness contract).
        def body(ti, carry):
            kf, vf, vis = load_tiles(ti)
            return _int4_flash_step(qv, kf, vf, vis, carry)

        m0 = jnp.full((qr,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((qr,), jnp.float32)
        acc0 = jnp.zeros((qr, q_ref.shape[-1]), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, n_active, body, (m0, l0, acc0))
        out_ref[0, 0] = _int4_finish(m, l, acc)
        return

    def body(ti, carry):
        m, l, acc = carry
        kf, vf, vis = load_tiles(ti)
        s = jax.lax.dot_general(  # [QR, ps]
            qv, kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s + jnp.where(vis, 0.0, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((qr, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((qr, 1), jnp.float32)
    acc0 = jnp.zeros((qr, q_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_active, body, (m0, l0, acc0))
    out_ref[0, 0] = acc / jnp.maximum(l, 1e-30)


def _scale_rows(scale: jnp.ndarray) -> jnp.ndarray:
    """[P, KV, ps] page scales -> [P, L] lane-dense rows (L = KV*ps padded
    to 128 lanes), so one page's scales move as one aligned row DMA."""
    p, kvh, ps = scale.shape
    rows = scale.reshape(p, kvh * ps)
    return jnp.pad(rows, ((0, 0), (0, (-kvh * ps) % 128)))


def paged_attention_kernel(
    pool, table, pos, q, k_new, v_new, *, interpret: bool = False
) -> Tuple:
    """Raw pallas_call. q: [B, Q, H, hd] float (post-RoPE, unscaled);
    k_new/v_new: [B, Q, KV, hd]; table: [B, T] int32; pos: [B] int32.
    Returns (out [B, Q, H, hd] f32, new pool — appended in place via
    input/output aliasing)."""
    b, qn, h, hd = q.shape
    p_pages, kvh, tile_rows, _ = pool["k"].shape  # ps/2 rows when int4
    ps = page_size(pool)
    t = table.shape[1]
    rep = h // kvh
    qr = qn * rep
    kind = pool_kind(pool)
    scaled = kind in ("int8", "int4")

    q2 = _q_rows(q, kvh)  # [B, KV, QR, hd] f32 pre-scaled
    kn2 = jnp.moveaxis(k_new.astype(jnp.float32), 1, 2)  # [B, KV, Q, hd]
    vn2 = jnp.moveaxis(v_new.astype(jnp.float32), 1, 2)
    pdt = pool["k"].dtype

    blk = lambda shape: pl.BlockSpec(shape, lambda i, j, *_: (i, j, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [blk((1, 1, qr, hd)), blk((1, 1, qn, hd)), blk((1, 1, qn, hd)),
                any_spec, any_spec]
    inputs = [q2, kn2, vn2, pool["k"], pool["v"]]
    out_specs = [blk((1, 1, qr, hd)), any_spec, any_spec]
    out_shape = [
        jax.ShapeDtypeStruct((b, kvh, qr, hd), jnp.float32),
        jax.ShapeDtypeStruct(pool["k"].shape, pdt),
        jax.ShapeDtypeStruct(pool["v"].shape, pdt),
    ]
    # Input indices include the 2 scalar-prefetch args (table, pos).
    aliases = {5: 1, 6: 2}
    scratch = [
        pltpu.VMEM((tile_rows, hd), pdt),  # k page tile
        pltpu.VMEM((tile_rows, hd), pdt),  # v page tile
    ]
    if scaled:
        ks2 = _scale_rows(pool["k_scale"])
        vs2 = _scale_rows(pool["v_scale"])
        inputs += [ks2, vs2]
        in_specs += [any_spec, any_spec]
        out_specs += [any_spec, any_spec]
        out_shape += [jax.ShapeDtypeStruct(ks2.shape, jnp.float32)] * 2
        aliases.update({7: 3, 8: 4})
        scratch += [pltpu.VMEM((1, ks2.shape[1]), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((4,))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    res = pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, ps=ps, qn=qn, rep=rep, t=t, kind=kind
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(table, jnp.broadcast_to(pos, (b,)).astype(jnp.int32), *inputs)
    out = res[0]
    new_pool = {"k": res[1], "v": res[2]}
    if scaled:
        new_pool["k_scale"] = res[3][:, : kvh * ps].reshape(p_pages, kvh, ps)
        new_pool["v_scale"] = res[4][:, : kvh * ps].reshape(p_pages, kvh, ps)
    return _rows_out(out, qn), new_pool


def paged_attention(
    pool,
    table,
    pos,
    q,
    k_new,
    v_new,
    *,
    block_tokens: int = 512,
    vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
    interpret: bool = False,
) -> Tuple:
    """Shape-safe wrapper: fused append + paged flash-decode attention.

    Falls back to the gather-free XLA formulation when the per-program page
    tiles would not fit the VMEM budget (double-buffered k/v page tiles plus
    the q/out row blocks). Dispatching between this and the XLA/gather paths
    lives in :func:`repro.kernels.ops.paged_attention`.
    """
    b, qn, h, hd = q.shape
    _, kvh, rows, _ = pool["k"].shape  # rows: ps/2 for packed int4
    itemsize = jnp.dtype(pool["k"].dtype).itemsize
    qr = qn * (h // kvh)
    # k/v page tiles, the two scale rows, and the q/out row blocks.
    scale_row = 4 * (kvh * page_size(pool) + (-kvh * page_size(pool)) % 128)
    tile_bytes = 2 * (2 * rows * hd * itemsize + 2 * scale_row) + 2 * qr * hd * 4
    if tile_bytes > vmem_budget_bytes:
        reroute("paged_attention", pool["k"].shape, tile_bytes,
                vmem_budget_bytes)
        return paged_attention_xla(
            pool, table, pos, q, k_new, v_new, block_tokens=block_tokens
        )
    return paged_attention_kernel(
        pool, table, pos, q, k_new, v_new, interpret=interpret
    )
