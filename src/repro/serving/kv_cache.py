"""Paged KV-cache subsystem: page pools, block tables, prefix reuse.

The paper's deployment scenario is a provider serving a client's float model
in low precision; at production batch sizes the KV cache — not the weights —
dominates accelerator memory. The old engine pre-allocated a dense
``[max_batch, KV, max_len, hd]`` cache per layer, so capacity was fixed at
construction and a 12-token request paid for ``max_len`` slots. This module
replaces that with a vLLM-style paged layout:

* **page pool** — one ``[n_pages, KV, page_size, hd]`` array per layer
  (int8 values + one f32 scale per token per kv head when ``cfg.kv_bits == 8``
  — the paper's symmetric linear grid applied per cache row — or a float pool
  for parity testing). Page 0 is a reserved *trash* page: inactive decode
  lanes and bucket padding write there, and nothing ever reads it.
* **block tables** — a ``[max_batch, max_pages_per_seq]`` int32 array mapping
  each decode lane's token position ``p`` to pool page ``table[lane, p //
  page_size]``, slot ``p % page_size``. Retired lanes point every entry at
  the trash page.
* **PageAllocator** — host-side alloc/append/free with refcounted prefix
  sharing: full pages of a prompt are content-addressed by a chained hash,
  so a repeated system prompt's pages are reused (refcount bumped) instead
  of re-prefilled. Sharing is copy-on-write at page granularity: a shared
  page is immutable (it was fully written by the prefill that allocated it;
  decode only ever appends to pages past the prompt), so "copy" never
  actually happens — a writer simply gets a fresh page.

**Layout invariant the decode kernels rely on** (see docs/serving.md):
token position ``p`` of a sequence lives at ``(table[p // ps], :, p % ps, :)``
of every layer's pool, with the same page ids across layers; gathering
``pool[table]`` and flattening (page-major, then slot) therefore reconstructs
the contiguous ``[B, KV, L, hd]`` cache bit-for-bit, which is what makes
float-page decode *bit-exact* against the dense cache.

Sharding: page pools shard the KV-head dim on the ``model`` mesh axis via the
``kv_heads`` rule in ``sharding/specs.py`` (the page dim stays replicated —
``kv_pages`` rule), the same placement as the dense decode cache.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict, deque
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.sharding.specs import logical
# Single source of truth for cache-row quantization and the page-pool
# scatter: the contiguous int8 cache, the int8 page pool, and the fused
# paged-attention kernel's in-kernel append must agree bitwise.
from repro.kernels.paged_attention import (
    KV4_QMAX,
    append_rows as _append_rows,
    pack_int4,
    page_size,
    quant_rows as _quant_rows_q,
    unpack_int4,
)
from repro.models.attention import _quant_rows

__all__ = [
    "pages_needed",
    "kv_bytes_per_token",
    "init_page_pool",
    "init_paged_cache",
    "append_token",
    "append_tokens",
    "gather_pages",
    "write_prompt_pages",
    "gather_prefix",
    "rewind_positions",
    "PageAllocator",
]

TRASH_PAGE = 0  # reserved: written by inactive lanes / padding, never read


def pages_needed(n_tokens: int, page_size: int) -> int:
    """Pages required to hold ``n_tokens`` cache rows."""
    if n_tokens <= 0:
        return 0
    return -(-n_tokens // page_size)


def kv_bytes_per_token(cfg: ModelConfig) -> int:
    """Pool bytes one cache row costs across all layers (values + scales).

    The precision-tier capacity lever in one number: at kv_bits=4 the value
    bytes halve versus int8 (two nibbles per byte), so a matched-memory pool
    holds ~2x the tokens. Scales are tier-independent (one f32 per token per
    KV head per side).
    """
    if cfg.kv_bits is None:
        per_row = 2 * cfg.hd * 4  # float32 k + v, no scales
    else:
        per_row = 2 * (cfg.hd * cfg.kv_bits // 8) + 2 * 4
    return cfg.n_layers * cfg.n_kv_heads * per_row


# ---------------------------------------------------------------------------
# Device-side pool ops (traced inside prefill/decode jits)


def _shard_pool(pool: Dict) -> Dict:
    out = dict(pool)
    out["k"] = logical(pool["k"], "kv_pages", "kv_heads", None, None)
    out["v"] = logical(pool["v"], "kv_pages", "kv_heads", None, None)
    if "k_scale" in pool:
        out["k_scale"] = logical(pool["k_scale"], "kv_pages", "kv_heads", None)
        out["v_scale"] = logical(pool["v_scale"], "kv_pages", "kv_heads", None)
    return out


def init_page_pool(
    cfg: ModelConfig, n_pages: int, page_size: int, dtype=jnp.float32
) -> Dict:
    """One layer's pool: ``[n_pages, KV, page_size, hd]`` (+ scales if int8)."""
    shape = (n_pages, cfg.n_kv_heads, page_size, cfg.hd)
    if cfg.kv_bits is not None:
        if cfg.kv_bits == 4:
            # Packed nibbles, two tokens per byte: row r of a page holds
            # token r (low nibble) and token r + page_size/2 (high nibble)
            # of every channel — pack_int4/unpack_int4's split-half layout
            # along the token axis, so a page tile stays hd lanes wide.
            # uint8 dtype is the tier discriminator (int8 pools quantize at
            # qmax=127, packed pools at qmax=7); scales keep the int8 layout.
            if page_size % 2:
                raise ValueError(
                    f"kv_bits=4 needs an even page size, got {page_size}"
                )
            packed = (n_pages, cfg.n_kv_heads, page_size // 2, cfg.hd)
            return {
                "k": jnp.zeros(packed, jnp.uint8),
                "v": jnp.zeros(packed, jnp.uint8),
                "k_scale": jnp.zeros(shape[:3], jnp.float32),
                "v_scale": jnp.zeros(shape[:3], jnp.float32),
            }
        if cfg.kv_bits != 8:
            raise NotImplementedError("kv_bits: only int8/int4 pages implemented")
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:3], jnp.float32),
            "v_scale": jnp.zeros(shape[:3], jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    n_pages: int,
    page_size: int,
    max_pages_per_seq: int,
    dtype=jnp.float32,
) -> Dict:
    """Engine cache tree for the paged layout (attention archs only).

    ``layers[i]["attn"]`` holds layer i's page pool; ``table`` and ``pos``
    are shared across layers (one page id sequence per decode lane).
    """
    if cfg.block not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged KV cache: attention archs only, got {cfg.block}"
        )
    return {
        "layers": [
            {"attn": init_page_pool(cfg, n_pages, page_size, dtype)}
            for _ in range(cfg.n_layers)
        ],
        "table": jnp.zeros((batch, max_pages_per_seq), jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def append_token(pool: Dict, k_new, v_new, table, pos) -> Dict:
    """Write one token's K/V rows through the block table.

    k_new/v_new: ``[B, KV, hd]`` (post-RoPE); table: ``[B, T]``; pos: ``[B]``.
    Position is clamped to the table extent (same overwrite-last semantics as
    the dense cache's ``min(pos, s_cache-1)`` clamp). A single batched
    scatter: duplicate (page, slot) targets can only be trash-page writes
    from inactive lanes, which are never read.
    """
    return append_tokens(pool, k_new[:, None], v_new[:, None], table, pos)


def append_tokens(pool: Dict, k_new, v_new, table, pos) -> Dict:
    """Write Q consecutive tokens' K/V rows through the block table — the
    speculative verify path's batched generalization of :func:`append_token`.

    k_new/v_new: ``[B, Q, KV, hd]`` (post-RoPE); table: ``[B, T]``; pos:
    ``[B]`` — the position of each lane's *first* token (token ``j`` lands at
    ``pos + j``). Per-token positions are clipped to the table extent (the
    single-token overwrite-last semantics); clipped and trash-page targets
    are only ever read by queries past a request's budget, whose logits the
    engine never commits.

    The scatter body is :func:`repro.kernels.paged_attention.append_rows` —
    the same implementation the fused paged-attention dispatch appends with,
    so the pools the two paths write agree bitwise by construction; this
    wrapper only adds the sharding constraint.
    """
    return _shard_pool(_append_rows(pool, k_new, v_new, table, pos))


def rewind_positions(pos_vec, new_pos) -> jnp.ndarray:
    """Roll the per-lane position vector back to the committed positions.

    The paged-KV rollback invariant (docs/serving.md): a speculative verify
    writes K/V for every proposed position, but only positions ``< pos`` are
    visible to the causal mask — so rolling back a rejected tail is *just*
    this rewind. The stale rows past the committed position are invisible to
    every subsequent read and are overwritten in place when decode reaches
    those positions again; no page content needs touching, and prompt pages
    (always at positions below the committed prefix) are never affected, so
    the prefix cache stays consistent.
    """
    return jnp.asarray(new_pos, jnp.int32).reshape(jnp.asarray(pos_vec).shape)


def gather_pages(pool: Dict, table) -> Tuple:
    """Reconstruct per-lane contiguous caches from the pool.

    Returns ``(k [B, KV, L, hd], v, k_scale [B, KV, L] | None, v_scale)``
    with ``L = T * page_size``; gathered position ``j`` is sequence position
    ``j`` (page-major flatten — the layout invariant).

    Trash-page entries (inactive lanes; table padding past a lane's
    allocation) are *select-zeroed*: page 0 holds arbitrary dead writes —
    NaN included — and the decode masks only add ``NEG_INF`` to scores, so a
    NaN leaking through the gather would survive ``exp`` and ``p @ v`` into
    an active lane's output. ``jnp.where`` discards the poisoned value
    outright (a multiply would propagate it). Real-page positions are
    untouched, preserving the bit-exact reconstruction contract.
    """
    b, t = table.shape
    n_kv, hd = pool["k"].shape[1], pool["k"].shape[3]
    ps = page_size(pool)
    packed = pool["k"].dtype == jnp.uint8

    trash = jnp.repeat(table == TRASH_PAGE, ps, axis=1)  # [B, T*ps]

    def flat4(x):  # [B, T, KV, ps, hd] -> [B, KV, T*ps, hd]
        if packed:  # two tokens per byte row; callers see int8 rows
            x = unpack_int4(x, axis=-2)
        x = jnp.moveaxis(x, 2, 1).reshape(b, n_kv, t * ps, hd)
        return jnp.where(trash[:, None, :, None], jnp.zeros((), x.dtype), x)

    def flat3(x):  # [B, T, KV, ps] -> [B, KV, T*ps]
        x = jnp.moveaxis(x, 2, 1).reshape(b, n_kv, t * ps)
        return jnp.where(trash[:, None, :], jnp.zeros((), x.dtype), x)

    k = flat4(pool["k"][table])
    v = flat4(pool["v"][table])
    if "k_scale" not in pool:
        return k, v, None, None
    return k, v, flat3(pool["k_scale"][table]), flat3(pool["v_scale"][table])


def write_prompt_pages(pool: Dict, k, v, page_ids) -> Dict:
    """Write a prefilled prompt's K/V into its pages in one scatter.

    k/v: ``[1, S, KV, hd]`` (post-RoPE, S = jit bucket, ``S % page_size ==
    0``); page_ids: ``[S // page_size]`` — the sequence's pages in order,
    padded with the trash page for bucket positions past the allocation.
    """
    ps = page_size(pool)
    s, n_kv, hd = k.shape[1:]
    nb = s // ps

    def paged(x):  # [1, S, KV, hd] -> [nb, KV, ps, hd]
        return jnp.moveaxis(x[0].reshape(nb, ps, n_kv, hd), 2, 1)

    k_p, v_p = paged(k), paged(v)
    out = dict(pool)
    if pool["k"].dtype == jnp.uint8:
        # Packed int4 tier: same quant_rows as append_rows' in-place append
        # (qmax=7), nibble-packed — prefill-written and decode-appended pages
        # agree bitwise.
        k_q, k_s = _quant_rows_q(k_p, qmax=KV4_QMAX)
        v_q, v_s = _quant_rows_q(v_p, qmax=KV4_QMAX)
        out["k"] = pool["k"].at[page_ids].set(pack_int4(k_q, axis=-2))
        out["v"] = pool["v"].at[page_ids].set(pack_int4(v_q, axis=-2))
        out["k_scale"] = pool["k_scale"].at[page_ids].set(k_s)
        out["v_scale"] = pool["v_scale"].at[page_ids].set(v_s)
    elif pool["k"].dtype == jnp.int8:
        k_q, k_s = _quant_rows(k_p)
        v_q, v_s = _quant_rows(v_p)
        out["k"] = pool["k"].at[page_ids].set(k_q)
        out["v"] = pool["v"].at[page_ids].set(v_q)
        out["k_scale"] = pool["k_scale"].at[page_ids].set(k_s)
        out["v_scale"] = pool["v_scale"].at[page_ids].set(v_s)
    else:
        out["k"] = pool["k"].at[page_ids].set(k_p.astype(pool["k"].dtype))
        out["v"] = pool["v"].at[page_ids].set(v_p.astype(pool["v"].dtype))
    return _shard_pool(out)


def gather_prefix(pool: Dict, prefix_ids) -> Tuple:
    """Dequantized K/V of a shared prompt prefix, for suffix-only prefill.

    prefix_ids: ``[n_hit_pages]``. Returns ``(k, v)`` as ``[1, n_hit, KV,
    hd]`` f32 — the ``kv_prefix`` layout ``models.attention.attention``
    concatenates on the key side (prefix tokens precede every suffix query,
    so the always-visible prefix semantics are exactly causal here).
    """
    n_hit, n_kv, hd = prefix_ids.shape[0], pool["k"].shape[1], pool["k"].shape[3]
    ps = page_size(pool)
    packed = pool["k"].dtype == jnp.uint8

    def flat(vals, scale):  # [H, KV, ps, hd] -> [1, H*ps, KV, hd]
        if packed:
            vals = unpack_int4(vals, axis=-2)
        x = vals.astype(jnp.float32)
        if scale is not None:
            x = x * scale[..., None]
        return jnp.moveaxis(x, 1, 2).reshape(1, n_hit * ps, n_kv, hd)

    quant = pool["k"].dtype != jnp.float32 and "k_scale" in pool
    k = flat(pool["k"][prefix_ids], pool["k_scale"][prefix_ids] if quant else None)
    v = flat(pool["v"][prefix_ids], pool["v_scale"][prefix_ids] if quant else None)
    return k, v


# ---------------------------------------------------------------------------
# Host-side allocation + prefix cache


class PageAllocator:
    """Refcounted page allocator with a content-addressed prefix cache.

    Pages move between three states:

    * **free** — unallocated, on the free list;
    * **referenced** — owned by >= 1 live sequence (``_ref[pid] >= 1``);
    * **cached** — refcount dropped to zero but the page holds a registered
      prompt prefix; it stays hit-able in LRU order and is evicted (back to
      a fresh allocation) only under pool pressure.

    Admission control asks :meth:`available` (free + evictable-cached) before
    admitting; page 0 (the trash page) is never handed out.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the reserved trash page)")
        self.page_size = page_size
        self.n_pages = n_pages
        self.capacity = n_pages - 1  # trash page excluded
        self._free = deque(range(1, n_pages))
        self._ref: Dict[int, int] = {}
        self._key_of: Dict[int, bytes] = {}  # registered pid -> chain key
        self._page_of: Dict[bytes, int] = {}  # chain key -> pid
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # cached, ref==0
        self.peak_in_use = 0
        # Prefix-cache stats are counted by the caller (note_prefix_stats),
        # once per *admitted* request — a failed-admission retry loop calling
        # match_prefix every engine step must not inflate the hit rate.
        self.prefix_hit_pages = 0
        self.prefix_lookup_pages = 0

    # -- state ------------------------------------------------------------

    def in_use(self) -> int:
        return len(self._ref)

    def available(self) -> int:
        return len(self._free) + len(self._lru)

    def cached_pages(self) -> int:
        return len(self._lru)

    def hit_rate(self) -> float:
        if not self.prefix_lookup_pages:
            return 0.0
        return self.prefix_hit_pages / self.prefix_lookup_pages

    def _note_peak(self) -> None:
        if len(self._ref) > self.peak_in_use:
            self.peak_in_use = len(self._ref)

    # -- alloc/free --------------------------------------------------------

    def _evict_one(self) -> int:
        pid, _ = self._lru.popitem(last=False)  # oldest cached prefix first
        del self._page_of[self._key_of.pop(pid)]
        return pid

    def alloc(self, n: int) -> List[int]:
        if self.available() < n:
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {self.available()} "
                f"(capacity {self.capacity})"
            )
        out = []
        for _ in range(n):
            pid = self._free.popleft() if self._free else self._evict_one()
            self._ref[pid] = 1
            out.append(pid)
        self._note_peak()
        return out

    def retain(self, pid: int) -> None:
        if pid in self._ref:
            self._ref[pid] += 1
        else:  # cached page revived by a prefix hit
            del self._lru[pid]
            self._ref[pid] = 1
        self._note_peak()

    def release(self, ids: Sequence[int]) -> None:
        for pid in ids:
            r = self._ref[pid] - 1
            if r:
                self._ref[pid] = r
                continue
            del self._ref[pid]
            if pid in self._key_of:
                self._lru[pid] = None  # keep hit-able until evicted
            else:
                self._free.append(pid)

    def truncate(self, pages: List[int], keep_tokens: int) -> List[int]:
        """Page-aware rollback: release the tail of a lane's ``pages`` not
        needed to hold ``keep_tokens`` committed cache rows, returning the
        kept prefix. ``keep_tokens=0`` is retirement (release everything).

        Prefix-cache consistency: a released page that holds a registered
        prompt prefix drops to the LRU (still hit-able, evicted only under
        pool pressure) exactly like any other release — truncation can never
        orphan or double-free a shared prefix page, because shared prompt
        pages sit at the *front* of a lane's page list (positions below the
        committed prefix) and a commit point can only move past them.
        """
        keep = pages_needed(keep_tokens, self.page_size)
        if keep >= len(pages):
            return list(pages)
        self.release(pages[keep:])
        return list(pages[:keep])

    # -- prefix cache ------------------------------------------------------

    def chain_keys(self, tokens: Sequence[int], n_blocks: int) -> List[bytes]:
        """Content keys of the first ``n_blocks`` full pages: each key hashes
        its block's tokens chained on the previous key, so a key identifies
        the whole prefix up to and including its page."""
        keys = []
        h = b""
        for j in range(n_blocks):
            blk = np.asarray(
                tokens[j * self.page_size : (j + 1) * self.page_size], np.int64
            ).tobytes()
            h = hashlib.sha256(h + blk).digest()
            keys.append(h)
        return keys

    def match_prefix(
        self, tokens: Sequence[int], max_pages: int
    ) -> Tuple[List[int], List[bytes]]:
        """Longest cached prefix of ``tokens``, capped at ``max_pages`` pages.

        Returns ``(hit page ids — already retained, chain keys for *all*
        full pages)``; the caller registers the keys of the pages it writes
        and books stats via :meth:`note_prefix_stats` once it commits.
        """
        full = len(tokens) // self.page_size
        keys = self.chain_keys(tokens, full)
        hits: List[int] = []
        for j in range(min(max_pages, full)):
            pid = self._page_of.get(keys[j])
            if pid is None:
                break
            self.retain(pid)
            hits.append(pid)
        return hits, keys

    def note_prefix_stats(self, hit_pages: int, lookup_pages: int) -> None:
        """Book one admitted request's prefix-cache outcome."""
        self.prefix_hit_pages += hit_pages
        self.prefix_lookup_pages += lookup_pages

    def register(self, key: bytes, pid: int) -> None:
        """Publish a freshly written full prompt page. First writer wins:
        two cold identical prompts admitted back-to-back both write their own
        pages; only the first registration is kept."""
        if key in self._page_of or pid in self._key_of:
            return
        self._page_of[key] = pid
        self._key_of[pid] = key
