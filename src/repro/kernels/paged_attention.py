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
* **page blocks** — each grid program (one lane) walks its lane's
  block-table row in blocks of ``G`` pages (:func:`plan_pages`, from the
  pool's static shapes: at most :data:`BLOCK_TOKENS` tokens, within half
  the VMEM budget; 16 pages at glm4-9b's 2 KV heads, 8 at 16). Each page
  is one DMA per tile for all its KV heads (one ``[KV, page_size, hd]``
  tile of K, one of V, one scale row each); every page of the next block
  is in flight while this one computes (two block slots), and a full
  block is waited on once per array. Pages past the lane's last are never
  loaded; their stale slots, like the trash page, are select-zeroed and
  masked. Nothing per-lane is ever materialized in HBM;
* **in-VMEM dequant** — int8 pages are converted in VMEM and their
  per-token scales (the paper's linear grid) act on the scores and on the
  softmax weights, lane by lane; packed int4 tiles are dequantized row by
  row right after the load;
* **online softmax** — the flash recurrence accumulates across blocks,
  so the loop stops after ``(pos + Q - 1) // page_size + 1`` pages: work
  scales with the tokens attended, not the table extent. All heads and
  pages of a block attend in one pair of matmuls and one softmax update,
  each query row masked to its own head's keys;
* **position masking** — per-lane causal masks (query ``j`` sees positions
  ``<= pos + j``) *and* an explicit trash-page mask: page-0 loads are
  select-zeroed before the dots, so a poisoned (even NaN) trash page can
  never reach an output (see ``tests/test_paged_attention.py``).

``Q > 1`` queries run the speculative ``verify_step`` through the same
kernel: rows are laid out ``(query j, rep r)`` row-major, so row ``qr``
masks against ``pos + qr // rep``. ``G`` does not depend on ``Q``, so the
decode and verify calls of one engine sum a row's keys in the same order.
The int4 tier keeps one page a step: its per-page recurrence
(``_int4_flash_step``) is what makes it bitwise equal to its fallbacks.

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
    "plan_pages",
    "BLOCK_TOKENS",
    "VMEM_BUDGET_BYTES",
]

NEG_INF = -1e30  # finite: exp(NEG_INF - NEG_INF) == 1, never NaN
TRASH_PAGE = 0  # reserved pool page (serving.kv_cache.TRASH_PAGE): never read
KV4_QMAX = 7.0  # symmetric int4 grid: quantized values live in [-7, 7]
BLOCK_TOKENS = 256  # most tokens one step of the kernel's flash loop takes


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
    q_ref,  # [1, KV*QR, hd] f32 block for lane b: head-major rows
    kn_ref,  # [1, KV, Q, hd] f32 block
    vn_ref,
    k_in,  # [P, KV, rows, hd] ANY (aliased; unused — reads go through k_out)
    v_in,
    *rest,  # (ks_in, vs_in,) out refs, (scale out refs,) scratch, sems
    ps: int,
    qn: int,
    rep: int,
    t: int,
    g: int,
    kind: str,
):
    scaled = kind in ("int8", "int4")
    if scaled:
        (ks_in, vs_in, out_ref, k_out, v_out, ks_out, vs_out,
         k_scr, v_scr, ks_scr, vs_scr, sems) = rest
    else:
        (out_ref, k_out, v_out, k_scr, v_scr, sems) = rest
    b = pl.program_id(0)
    pos_b = pos_ref[b]
    qr = qn * rep
    half = ps // 2
    kvh, hd = kn_ref.shape[1], q_ref.shape[-1]
    n_rows, page_keys = kvh * qr, kvh * ps
    n_keys = g * page_keys

    hbm = [k_out, v_out] + ([ks_out, vs_out] if scaled else [])
    vmem = [k_scr, v_scr] + ([ks_scr, vs_scr] if scaled else [])

    def copies(pid, slot, j, to_vmem: bool):
        # One page for all the lane's heads, into page j of a block slot: a
        # [KV, rows, hd] tile of K and of V and the lane-dense scale rows,
        # one DMA each. Reads go through the *output* refs (the aliased
        # buffers), so every append is visible to the loads after it.
        return [
            pltpu.make_async_copy(
                *((h.at[pid], v.at[slot, j]) if to_vmem
                  else (v.at[slot, j], h.at[pid])),
                sems.at[slot, i])
            for i, (h, v) in enumerate(zip(hbm, vmem))
        ]

    def run(dmas):
        for d in dmas:
            d.start()
        for d in dmas:
            d.wait()

    # ---- fused append: this program owns lane b's Q rows.
    # Pages past the prompt are never shared across lanes, so the only rows
    # this program ever reads back below are its own writes (waited on
    # here). Each row is a read-modify-write of the page tile, in a 32-bit
    # type: Mosaic can neither DMA one row of an 8-bit page (it is not
    # aligned to the tiling) nor select on 8-bit vectors.
    for j in range(qn):
        lin = jnp.minimum(jnp.maximum(pos_b + j, 0), t * ps - 1)
        pid = table_ref[b, lin // ps]
        slot = lin % ps
        run(copies(pid, 0, 0, to_vmem=True))
        for h in range(kvh):
            kr = kn_ref[0, h, j : j + 1, :].astype(jnp.float32)  # [1, hd]
            vr = vn_ref[0, h, j : j + 1, :].astype(jnp.float32)
            if not scaled:
                hit = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0) == slot
                for row, scr in ((kr, k_scr), (vr, v_scr)):
                    scr[0, 0, h] = jnp.where(
                        hit, row, scr[0, 0, h].astype(jnp.float32)
                    ).astype(scr.dtype)
                continue
            # quant_rows, inlined: same grid as every other pool writer
            # (qmax 127 for int8 pages, KV4_QMAX for packed int4 pages).
            qm = 127.0 if kind == "int8" else KV4_QMAX
            s_hit = (jax.lax.broadcasted_iota(jnp.int32, ks_scr.shape[2:], 1)
                     == h * ps + slot)
            for row, scr, s_scr in ((kr, k_scr, ks_scr), (vr, v_scr, vs_scr)):
                amax = jnp.max(jnp.abs(row), axis=-1, keepdims=True)
                sc = jnp.maximum(amax, 1e-30) * (1.0 / qm)
                qrow = jnp.clip(
                    jnp.floor(row * (1.0 / sc) + 0.5), -qm, qm
                ).astype(jnp.int32)
                cur = scr[0, 0, h].astype(jnp.int32)
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
                scr[0, 0, h] = jnp.where(hit, new, cur).astype(scr.dtype)
                s_scr[0, 0] = jnp.where(s_hit, sc, s_scr[0, 0])
        run(copies(pid, 0, 0, to_vmem=False))

    # ---- flash loop over this lane's active pages only, in blocks of g
    # pages: every page of the next block in flight while this one
    # computes (two block slots). Pages past the lane's last are never
    # loaded; their stale slots are masked below.
    n_active = jnp.minimum(t, (pos_b + qn - 1) // ps + 1)  # >= 1
    n_blocks = (n_active + g - 1) // g

    def start(blk, slot):
        for j in range(g):
            @pl.when(blk * g + j < n_active)
            def _load():
                for d in copies(table_ref[b, blk * g + j], slot, j, to_vmem=True):
                    d.start()

    def wait(blk, slot):
        """Wait for block ``blk``; per page of it, whether it is readable
        (loaded, and not the trash page)."""
        full = (blk + 1) * g <= n_active

        # A full block's pages all signal one semaphore per array: one
        # wait for the whole slot's bytes. The last block waits page by page.
        @pl.when(full)
        def _whole():
            for i, (h, v) in enumerate(zip(hbm, vmem)):
                pltpu.make_async_copy(h.at[pl.ds(0, g)], v.at[slot],
                                      sems.at[slot, i]).wait()

        ok = []
        for j in range(g):
            idx = blk * g + j
            if g > 1:
                @pl.when(jnp.logical_not(full) & (idx < n_active))
                def _page():
                    for d in copies(0, slot, j, to_vmem=True):
                        d.wait()
            ok.append((idx < n_active)
                      & (table_ref[b, jnp.minimum(idx, t - 1)] != TRASH_PAGE))
        return ok

    start(0, 0)

    if kind == "int4":
        # int4 tier (g == 1): the shared _int4_flash_step recurrence on 2-D
        # per-head slices against the dequantized tiles — the same op
        # sequence the XLA fallback and the gather oracle run batched, so
        # the three paths agree bitwise (the tier's exactness contract).
        bound = pos_b + jax.lax.broadcasted_iota(jnp.int32, (qr, 1), 0) // rep
        lanes = ks_scr.shape[-1]
        pick = lambda h: (jax.lax.broadcasted_iota(jnp.int32, (ps, lanes), 1)
                          == h * ps
                          + jax.lax.broadcasted_iota(jnp.int32, (ps, lanes), 0))

        def tile(scr, s_scr, slot, h):
            # Head h's page tile -> [ps, hd] f32: token r in the low nibble
            # and token r + ps/2 in the high nibble of row r, sign-extended
            # by 32-bit shifts (see unpack_nibbles); its per-token scales
            # picked out of the lane-dense row as a [ps, 1] column by a
            # select + lane sum (exact: one nonzero term per row).
            lo, hi = unpack_nibbles(scr[slot, 0, h])
            col = jnp.sum(jnp.where(pick(h), s_scr[slot, 0], 0.0), axis=1,
                          keepdims=True)
            return jnp.concatenate([lo, hi], axis=0).astype(jnp.float32) * col

        def body(ti, carry):
            slot = ti % 2

            @pl.when(ti + 1 < n_active)
            def _prefetch():
                start(ti + 1, 1 - slot)

            (readable,) = wait(ti, slot)
            gpos = ti * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
            vis = (gpos <= bound) & readable
            out = []
            for h in range(kvh):
                kf = jnp.where(readable, tile(k_scr, ks_scr, slot, h), 0.0)
                vf = jnp.where(readable, tile(v_scr, vs_scr, slot, h), 0.0)
                q = q_ref[0, h * qr:(h + 1) * qr, :]
                out.append(_int4_flash_step(q, kf, vf, vis, carry[h]))
            return tuple(out)

        init = tuple((jnp.full((qr,), NEG_INF, jnp.float32),
                      jnp.zeros((qr,), jnp.float32),
                      jnp.zeros((qr, hd), jnp.float32)) for _ in range(kvh))
        res = jax.lax.fori_loop(0, n_active, body, init)
        out_ref[0] = jnp.concatenate([_int4_finish(*c) for c in res], axis=0)
        return

    # Every head and page of a block in one pair of matmuls: query rows
    # (head-major) against the block's keys, each row then masked to its
    # own head's tokens. Row r is head r // QR, query (r % QR) // rep; key
    # column c is page c // (KV*ps) of the block, head (c // ps) % KV,
    # token c % ps of the page.
    row = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_keys), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_keys), 1)
    own = row // qr == (col // ps) % kvh
    bound = pos_b + (row % qr) // rep
    cpos = (col // page_keys) * ps + col % ps  # token offset in the block
    cpage = jax.lax.broadcasted_iota(jnp.int32, (1, n_keys), 1) // page_keys
    qv = q_ref[0]  # [KV*QR, hd] f32, pre-scaled

    def scale_row(scr, slot, ok_cols):
        # The block's per-token scales as one [1, G*KV*ps] row, columns in
        # key order; an unreadable page's are zeroed (a select: a stale or
        # poisoned slot may hold NaN).
        rows = [scr[slot, j][:, :page_keys] for j in range(g)]
        return jnp.where(ok_cols, jnp.concatenate(rows, axis=1), 0.0)

    def body(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _prefetch():
            start(blk + 1, 1 - slot)

        ok = wait(blk, slot)
        ok_cols = cpage < 0
        for j in range(g):
            ok_cols = ok_cols | ((cpage == j) & ok[j])
        if scaled:
            kf = k_scr[slot].astype(jnp.float32).reshape(n_keys, hd)
            vf = v_scr[slot].astype(jnp.float32).reshape(n_keys, hd)
        else:  # select: NaN poison and stale slots die here
            kf, vf = (jnp.concatenate([
                jnp.where(ok[j], scr[slot, j].astype(jnp.float32), 0.0)
                .reshape(page_keys, hd) for j in range(g)], axis=0)
                for scr in (k_scr, v_scr))
        s = jax.lax.dot_general(  # [KV*QR, G*KV*ps]
            qv, kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if scaled:
            # int8 pages: the per-token scales act on the scores and on the
            # softmax weights, lane by lane.
            s = s * scale_row(ks_scr, slot, ok_cols)
        vis = own & (blk * (g * ps) + cpos <= bound) & ok_cols
        s = s + jnp.where(vis, 0.0, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if scaled:
            p = p * scale_row(vs_scr, slot, ok_cols)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((n_rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_rows, 1), jnp.float32)
    acc0 = jnp.zeros((n_rows, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    out_ref[0] = acc / jnp.maximum(l, 1e-30)


def _scale_rows(scale: jnp.ndarray) -> jnp.ndarray:
    """[P, KV, ps] page scales -> [P, 1, L] lane-dense rows (L = KV*ps padded
    to 128 lanes), so one page's scales move as one DMA of a leading-dim
    slice, aligned in any memory space."""
    p, kvh, ps = scale.shape
    rows = scale.reshape(p, 1, kvh * ps)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, (-kvh * ps) % 128)))


def _block_bytes(kvh: int, rows: int, hd: int, itemsize: int, ps: int,
                 scaled: bool, g: int) -> int:
    """The resident VMEM of a block of ``g`` pages, whatever the query
    rows: two block slots of K and V tiles and scale rows (8 sublanes,
    128-lane padded) and the block's float32 K and V."""
    lanes = -(-kvh * ps // 128) * 128
    page = 2 * kvh * rows * hd * itemsize + (2 * 8 * lanes * 4 if scaled else 0)
    return 2 * g * page + 2 * g * kvh * ps * hd * 4


def _vmem_bytes(kvh: int, rows: int, hd: int, itemsize: int, ps: int,
                qr: int, qn: int, scaled: bool, g: int) -> int:
    """Resident VMEM of one program: the block (:func:`_block_bytes`), the
    scores and softmax weights of a block, and the double-buffered q, out
    and new-row blocks."""
    r8 = lambda r: -(-r // 8) * 8
    keys = -(-g * kvh * ps // 128) * 128
    rows_bytes = r8(kvh * qr) * keys * 4 * 2 + 2 * (
        2 * r8(kvh * qr) * hd * 4 + 2 * kvh * r8(qn) * hd * 4)
    return _block_bytes(kvh, rows, hd, itemsize, ps, scaled, g) + rows_bytes


def plan_pages(pool, t: int, vmem_budget_bytes: int = VMEM_BUDGET_BYTES) -> int:
    """Pages the kernel's flash loop takes per block, for ``pool`` and a
    table of ``t`` columns (see the module doc).

    At most :data:`BLOCK_TOKENS` tokens and ``t`` pages, halved until the
    block (:func:`_block_bytes`) fits half the VMEM budget; the other half
    is left to the query rows. Made from the pool's static shapes alone, so
    the decode (``Q = 1``) and verify (``Q > 1``) calls of one engine walk
    the same blocks and sum in the same order. The int4 tier takes one page
    at a time (its per-page recurrence is the tier's exactness contract).
    """
    if pool_kind(pool) == "int4":
        return 1
    _, kvh, rows, hd = pool["k"].shape
    ps = page_size(pool)
    itemsize = jnp.dtype(pool["k"].dtype).itemsize
    scaled = "k_scale" in pool
    g = max(1, min(t, BLOCK_TOKENS // ps))
    while g > 1 and (_block_bytes(kvh, rows, hd, itemsize, ps, scaled, g)
                     > vmem_budget_bytes // 2):
        g //= 2
    return g


def paged_attention_kernel(
    pool, table, pos, q, k_new, v_new, *, pages_per_block: int = 0,
    interpret: bool = False,
) -> Tuple:
    """Raw pallas_call. q: [B, Q, H, hd] float (post-RoPE, unscaled);
    k_new/v_new: [B, Q, KV, hd]; table: [B, T] int32; pos: [B] int32.
    Returns (out [B, Q, H, hd] f32, new pool — appended in place via
    input/output aliasing).

    One program a lane: it walks the lane's pages in blocks of
    ``pages_per_block`` (0: :func:`plan_pages`), loads each page for all KV
    heads at once, the next block in flight while this one computes, and
    attends all heads and pages of a block in one pair of matmuls."""
    b, qn, h, hd = q.shape
    p_pages, kvh, tile_rows, _ = pool["k"].shape  # ps/2 rows when int4
    ps = page_size(pool)
    t = table.shape[1]
    rep = h // kvh
    qr = qn * rep
    kind = pool_kind(pool)
    scaled = kind in ("int8", "int4")
    # A full block is waited on as one slice of the pool: never past its end.
    g = 1 if kind == "int4" else min(pages_per_block or plan_pages(pool, t),
                                     p_pages)

    # [B, KV*QR, hd] f32 pre-scaled, rows head-major
    q2 = _q_rows(q, kvh).reshape(b, kvh * qr, hd)
    kn2 = jnp.moveaxis(k_new.astype(jnp.float32), 1, 2)  # [B, KV, Q, hd]
    vn2 = jnp.moveaxis(v_new.astype(jnp.float32), 1, 2)
    pdt = pool["k"].dtype

    rows = pl.BlockSpec((1, kvh * qr, hd), lambda i, *_: (i, 0, 0))
    new = pl.BlockSpec((1, kvh, qn, hd), lambda i, *_: (i, 0, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [rows, new, new, any_spec, any_spec]
    inputs = [q2, kn2, vn2, pool["k"], pool["v"]]
    out_specs = [rows, any_spec, any_spec]
    out_shape = [
        jax.ShapeDtypeStruct((b, kvh * qr, hd), jnp.float32),
        jax.ShapeDtypeStruct(pool["k"].shape, pdt),
        jax.ShapeDtypeStruct(pool["v"].shape, pdt),
    ]
    # Input indices include the 2 scalar-prefetch args (table, pos).
    aliases = {5: 1, 6: 2}
    scratch = [
        pltpu.VMEM((2, g, kvh, tile_rows, hd), pdt),  # k tiles, two block slots
        pltpu.VMEM((2, g, kvh, tile_rows, hd), pdt),  # v tiles
    ]
    if scaled:
        ks2 = _scale_rows(pool["k_scale"])
        vs2 = _scale_rows(pool["v_scale"])
        inputs += [ks2, vs2]
        in_specs += [any_spec, any_spec]
        out_specs += [any_spec, any_spec]
        out_shape += [jax.ShapeDtypeStruct(ks2.shape, jnp.float32)] * 2
        aliases.update({7: 3, 8: 4})
        scratch += [pltpu.VMEM((2, g, 1, ks2.shape[-1]), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2, 4))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    res = pl.pallas_call(
        functools.partial(_paged_attn_kernel, ps=ps, qn=qn, rep=rep, t=t,
                          g=g, kind=kind),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(table, jnp.broadcast_to(pos, (b,)).astype(jnp.int32), *inputs)
    new_pool = {"k": res[1], "v": res[2]}
    if scaled:
        new_pool["k_scale"] = res[3][:, 0, : kvh * ps].reshape(p_pages, kvh, ps)
        new_pool["v_scale"] = res[4][:, 0, : kvh * ps].reshape(p_pages, kvh, ps)
    return _rows_out(res[0].reshape(b, kvh, qr, hd), qn), new_pool


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

    Falls back to the gather-free XLA formulation when one program's
    resident tiles (:func:`_vmem_bytes`, a block of pages for all KV heads
    of a lane) would not fit the VMEM budget. Dispatching between this and the
    XLA/gather paths lives in :func:`repro.kernels.ops.paged_attention`.
    """
    b, qn, h, hd = q.shape
    _, kvh, rows, _ = pool["k"].shape  # rows: ps/2 for packed int4
    itemsize = jnp.dtype(pool["k"].dtype).itemsize
    scaled = pool_kind(pool) in ("int8", "int4")
    g = plan_pages(pool, table.shape[1], vmem_budget_bytes)
    need = _vmem_bytes(kvh, rows, hd, itemsize, page_size(pool),
                       qn * (h // kvh), qn, scaled, g)
    if need > vmem_budget_bytes:
        reroute("paged_attention", pool["k"].shape, need, vmem_budget_bytes)
        return paged_attention_xla(
            pool, table, pos, q, k_new, v_new, block_tokens=block_tokens
        )
    return paged_attention_kernel(
        pool, table, pos, q, k_new, v_new, pages_per_block=g,
        interpret=interpret,
    )
