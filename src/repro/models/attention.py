"""Grouped-query attention: RoPE / M-RoPE, qk-norm, sliding window, KV cache.

Full-sequence attention uses a memory-efficient online-softmax formulation
(lax.scan over KV chunks, flash-attention recurrence) so the S x S score
matrix is never materialized — required for ``prefill_32k``. Decode attends a
single query against the cache (ring buffer for sliding-window layers).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import quant_rows as _quant_rows
from repro.sharding.specs import activation_rules, logical, logical_guarded
from .layers import dense, rms_norm

__all__ = [
    "attention_params_shape",
    "attention",
    "attention_decode",
    "init_kv_cache",
    "USE_PALLAS_PAGED_ATTN",
]

NEG_INF = -1e30

# DEPRECATED shim (since ISSUE 5). This global is no longer read by
# ``attention_decode`` at dispatch time; it only seeds
# ``EngineConfig.kernels.attn`` when that field is ``KernelChoice.AUTO``
# (resolved once at engine construction by ``repro.serving.config``).
# Select the path explicitly instead, via the per-call ``attn_kernel=``
# argument ("pallas" | "xla" | "gather") threaded from
# ``EngineConfig(kernels=KernelConfig(attn=...))``. The flag-off default
# ("gather") is the legacy scatter + ``gather_pages`` + dense-attention
# chain — the bit-exactness oracle (float pages == dense cache) and what
# GSPMD partitions for multi-device dry-runs.
USE_PALLAS_PAGED_ATTN = False


def _coerce_attn_kernel(choice) -> str:
    """Normalize the paged decode-attention backend selection.

    ``None`` -> "gather" (the legacy default-default); legacy bools map
    True -> "pallas", False -> "gather" (the pre-ISSUE-5 ``paged_attn=``
    vocabulary). Strings must be the ``KernelChoice`` vocabulary.
    """
    if choice is None:
        return "gather"
    if isinstance(choice, bool):
        return "pallas" if choice else "gather"
    choice = getattr(choice, "value", choice)
    if choice not in ("pallas", "xla", "gather"):
        raise ValueError(
            f"attn_kernel must be pallas|xla|gather (or None), got {choice!r}"
        )
    return choice


# ---------------------------------------------------------------------------
# RoPE


def _rope_angles(positions, hd: int, theta: float, sections=None):
    """positions: [..., S] (or [..., S, 3] for M-RoPE). Returns [..., S, hd/2]."""
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if sections is None:
        return positions[..., None].astype(jnp.float32) * freqs
    # M-RoPE (Qwen2-VL): frequency slots are owned by (t, h, w) sections.
    t_sec, h_sec, w_sec = sections
    assert t_sec + h_sec + w_sec == half, "mrope sections must sum to hd/2"
    owner = jnp.concatenate(
        [
            jnp.zeros(t_sec, jnp.int32),
            jnp.ones(h_sec, jnp.int32),
            2 * jnp.ones(w_sec, jnp.int32),
        ]
    )
    # positions [..., S, 3] -> select per-frequency owner position.
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(owner, positions.shape[:-1] + (half,)).astype(jnp.int32),
        axis=-1,
    )
    return pos * freqs


def apply_rope(x, positions, theta: float, sections=None):
    """x: [B, S, H, hd]; positions: [B, S] or [B, S, 3]."""
    hd = x.shape[-1]
    ang = _rope_angles(positions, hd, theta, sections)  # [B, S, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Params


def attention_params_shape(cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.hd
    shapes = {
        "wq": (d, cfg.n_heads * hd),
        "wk": (d, cfg.n_kv_heads * hd),
        "wv": (d, cfg.n_kv_heads * hd),
        "wo": (cfg.n_heads * hd, d),
    }
    if cfg.qk_norm:
        shapes["q_norm"] = (hd,)
        shapes["k_norm"] = (hd,)
    return shapes


# ---------------------------------------------------------------------------
# Online-softmax (flash) attention over KV chunks


def _pick_chunk(sk: int, want: int) -> int:
    """Largest divisor of sk that is <= want (keeps scan chunks uniform)."""
    c = min(want, sk)
    while sk % c:
        c -= 1
    return c


def _window_static(qf, k, v, window, chunk, n_prefix):
    """Statically-skipped sliding-window attention (q and k both chunked).

    Only the k-chunks that can be visible to a q-chunk — those overlapping
    its ``window`` plus chunk 0 (the always-visible meta/prefix tokens) —
    are touched: ~50% of the score FLOPs/bytes at window=1024, chunk~700,
    vs masking all chunks inside the scan. Requires the window/global choice
    to be static (see the segmented hymba layer scan in transformer.py).

    qf: [B,Sq,KV,rep,hd] pre-scaled; k,v: [B,Sk,KV,hd]; Sq == Sk.
    """
    b, sq, kv, rep, hd = qf.shape
    nq = sq // chunk
    outs = []
    for qi in range(nq):
        q_blk = qf[:, qi * chunk : (qi + 1) * chunk]
        q_pos = qi * chunk + jnp.arange(chunk)
        lo = max(0, (qi * chunk - (window - 1)) // chunk)
        kjs = sorted(set([0]) | set(range(lo, qi + 1)))
        acc = jnp.zeros((b, chunk, kv, rep, hd), jnp.float32)
        m = jnp.full((b, chunk, kv, rep), NEG_INF, jnp.float32)
        l = jnp.zeros((b, chunk, kv, rep), jnp.float32)
        for kj in kjs:
            k_blk = k[:, kj * chunk : (kj + 1) * chunk]
            v_blk = v[:, kj * chunk : (kj + 1) * chunk]
            k_pos = kj * chunk + jnp.arange(chunk)
            diff = q_pos[:, None] - k_pos[None, :]
            vis = ((diff >= 0) & (diff < window)) | (k_pos[None, :] < n_prefix)
            s = jnp.einsum("bqgrd,bkgd->bqgrk", q_blk, k_blk,
                           preferred_element_type=jnp.float32)
            s = s + jnp.where(vis, 0.0, NEG_INF)[None, :, None, None, :]
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqgrk,bkgd->bqgrd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            m = m_new
        outs.append(acc / jnp.maximum(l[..., None], 1e-30))
    return jnp.concatenate(outs, axis=1)


def _flash_over_kv(q, k, v, kind, q_pos, window, chunk, n_prefix, is_global=None,
                   prefix_real=None):
    """q: [B,Sq,H,hd]; k,v: [B,Sk,KV,hd] -> [B,Sq,H,hd]. f32 accumulators.

    ``q_pos``/key positions are *mask* positions over the concatenated
    (prefix + sequence) key axis; keys with position < n_prefix (learnable
    prefix / meta tokens) are visible to every query. ``is_global`` (traced
    bool, optional) switches between full-causal and windowed masks at
    runtime — used when heterogeneous layers run under one lax.scan.
    ``prefix_real`` (traced scalar, optional): the prefix's *real* length
    when the first ``n_prefix`` keys are a padded prefix — keys in
    ``[prefix_real, n_prefix)`` are pad rows and masked out entirely (the
    chunked-prefill scheduler pads prefix pages to pow2 buckets so chunk
    calls share jit traces). Pure-static sliding windows (is_global None,
    self-attention shapes) route to :func:`_window_static` which skips
    invisible chunks outright.
    """
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    rep = h // kv
    chunk = _pick_chunk(sk, chunk)
    n_chunks = sk // chunk
    # Keep operands in the compute dtype; accumulate in f32 inside the dots.
    qf = (q.astype(jnp.float32) * (hd ** -0.5)).astype(q.dtype)
    qf = qf.reshape(b, sq, kv, rep, hd)
    if kind == "window" and is_global is None and sq == sk and prefix_real is None:
        out = _window_static(qf, k, v, window, chunk, n_prefix)
        return out.reshape(b, sq, h, hd)

    def mask_for(k_pos):
        if kind == "full":
            vis = jnp.ones((sq, chunk), bool)
        else:
            diff = q_pos[:, None] - k_pos[None, :]
            causal = diff >= 0
            if kind == "window":
                win = causal & (diff < window)
                if is_global is not None:
                    vis = jnp.where(is_global, causal, win)
                else:
                    vis = win
            else:
                vis = causal
            vis = vis | (k_pos[None, :] < n_prefix)  # prefix always visible
        if prefix_real is not None:  # padded prefix: pad rows never visible
            vis = vis & ~(
                (k_pos[None, :] >= prefix_real) & (k_pos[None, :] < n_prefix)
            )
        return jnp.where(vis, 0.0, NEG_INF)

    def body(carry, inp):
        acc, m_run, l_run = carry
        kj, vj, j = inp
        k_pos = j * chunk + jnp.arange(chunk)
        s = jnp.einsum(
            "bqgrd,bkgd->bqgrk", qf, kj, preferred_element_type=jnp.float32
        )
        s = s + mask_for(k_pos)[None, :, None, None, :]
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + p.sum(axis=-1)
        pv = jnp.einsum(
            "bqgrk,bkgd->bqgrd",
            p.astype(vj.dtype),
            vj,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, sq, kv, rep, hd), jnp.float32)
    m0 = jnp.full((b, sq, kv, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kv, rep), jnp.float32)
    ks = jnp.moveaxis(k.reshape(b, n_chunks, chunk, kv, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, n_chunks, chunk, kv, hd), 1, 0)
    # Nested remat: without it the scan stashes the per-chunk f32 score/p
    # tensors ([n_chunks, B, Sq, KV, chunk] stacks) as backward residuals —
    # recomputing them per chunk trades cheap FLOPs (compute term is 30x
    # under the memory term here) for the full stacked-scores traffic.
    (acc, m, l), _ = jax.lax.scan(
        jax.checkpoint(body), (acc0, m0, l0), (ks, vs, jnp.arange(n_chunks))
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, hd)


def attention(
    params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,
    kind: str = "causal",
    window: int = 0,
    kv_prefix: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    is_global=None,
    n_prefix: int = 0,
    prefix_len: Optional[jnp.ndarray] = None,
    return_kv: bool = False,
):
    """Full-sequence attention. x: [B, S, d]; positions: [B, S] (or [B,S,3]).

    ``n_prefix`` marks the first N *sequence* tokens as always-visible
    (Hymba meta tokens flowing through the layers); ``kv_prefix`` is a
    separate learnable KV prefix concatenated on the key side only.
    ``prefix_len`` (traced scalar): real length of a *padded* ``kv_prefix``
    — rows past it are pad and masked invisible (chunked prefill).
    """
    b, s, _ = x.shape
    hd, h, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    # Head-TP only works when the head counts divide the 'model' axis;
    # forcing an indivisible constraint makes GSPMD pad/replicate the
    # [B,S,H,hd] tensors and re-gather them every layer (measured 8 TB/dev
    # of all-gather on qwen3-14b train: 40 q / 8 kv heads vs model=16).
    # Indivisible archs switch to *query-sequence* sharding over 'model'
    # instead: queries are independent given the full K/V, and GQA K/V is
    # small (kv_heads x hd), so one K/V gather per layer replaces the
    # per-layer padded-head re-gathers (EXPERIMENTS §Perf Cell D). Hybrid
    # blocks are excluded (hymba's windowed attention is too cheap to pay
    # any resharding; its SSM dominates and reshards separately).
    tp_ok = True
    active = activation_rules()
    if active is not None and cfg.block != "hymba":
        mesh, rules = active
        model_ax = rules.get("heads")
        if isinstance(model_ax, str):
            msz = mesh.shape[model_ax]
            tp_ok = (h % msz == 0) and (kvh % msz == 0)
    q = dense(params["wq"], x, name="attn_q").reshape(b, s, h, hd)
    k = dense(params["wk"], x, name="attn_k").reshape(b, s, kvh, hd)
    v = dense(params["wv"], x, name="attn_v").reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = rms_norm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    if tp_ok:
        q = logical(q, "batch", "seq", "heads", None)
        k = logical(k, "batch", "seq", "kv_heads", None)
        v = logical(v, "batch", "seq", "kv_heads", None)
    else:
        # Sequence parallelism: q's seq dim over 'model', K/V replicated
        # across it (the one small gather); heads stay whole per shard.
        q = logical_guarded(q, "batch", "seq_attn", None, None)
        k = logical_guarded(k, "batch", None, None, None)
        v = logical_guarded(v, "batch", None, None, None)
    kq, vq = k, v
    q_pos = jnp.arange(s)
    if kv_prefix is not None:
        pk, pv = kv_prefix  # [B, M, KV, hd] (learnable KV prefix)
        n_prefix = max(n_prefix, pk.shape[1])
        kq = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        vq = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        q_pos = q_pos + pk.shape[1]
    out = _flash_over_kv(
        q, kq, vq, kind, q_pos, window, cfg.attn_chunk, n_prefix, is_global,
        prefix_real=(prefix_len if kv_prefix is not None else None),
    )
    out = out.astype(x.dtype).reshape(b, s, h * hd)
    y = dense(params["wo"], out, name="attn_o")
    if not tp_ok:
        y = logical(y, "batch", "seq", "embed")  # reshard back at the boundary
    if return_kv:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------------
# Decode (single token, KV cache)


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_len: int, window: int = 0, dtype=jnp.bfloat16
):
    """Cache for one layer: [B, KV, S_cache, hd] x 2. Ring buffer if window>0.

    Head-major layout: both decode einsums (q.k^T contracting hd, p.v
    contracting S) read the cache without a physical transpose — with a
    [B, S, KV, hd] layout XLA materializes a transposed copy of the multi-GB
    cache every step.

    With ``cfg.kv_bits == 8`` the cache stores int8 values + one f32 scale
    per written token per kv head (symmetric absmax over hd — the paper's
    linear grid applied to the cache). Decode is fully int8: q and the
    softmax weights are dynamically quantized per step and both attention
    contractions run as s8 x s8 -> s32 dots (see ``attention_decode``), so
    the multi-GB cache is read at half the bf16 bytes — the dominant term of
    the decode memory roofline.
    """
    s = min(max_len, window) if window else max_len
    shape = (batch, cfg.n_kv_heads, s, cfg.hd)
    if cfg.kv_bits is not None:
        if cfg.kv_bits != 8:
            raise NotImplementedError("kv_bits: only int8 cache implemented")
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros((batch, cfg.n_kv_heads, s), jnp.float32),
            "v_scale": jnp.zeros((batch, cfg.n_kv_heads, s), jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# _quant_rows (the cache-row quantizer) now lives in
# repro.kernels.paged_attention.quant_rows — one grid for the dense cache,
# the page pool, and the fused in-kernel append — imported above under its
# historical name for the serving layer (serving.kv_cache imports it here).


def attention_decode(
    params,
    x: jnp.ndarray,
    cache,
    pos: jnp.ndarray,
    cfg: ModelConfig,
    *,
    window: int = 0,
    kv_prefix: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    table: Optional[jnp.ndarray] = None,
    attn_kernel=None,
):
    """Decode attention against the KV cache. x: [B, Q, d]; pos: position of
    the *first* query token — a scalar (all slots in lockstep) or a [B]
    vector (per-slot positions, the continuous-batching engine's mixed-length
    admission). Q == 1 is the classic one-token decode step; Q > 1 is the
    speculative *verify* path: the Q tokens occupy positions ``pos ..
    pos + Q - 1``, their K/V rows are written into the cache, and query ``j``
    attends causally over cache slots ``<= pos + j`` — so the Q logits equal
    Q sequential one-token decode steps, in one batched call.

    Returns (y [B,Q,d], new_cache). Sliding-window layers use a ring buffer
    (cache length == window); new keys overwrite slot ``pos % window``
    (Q == 1 only — hymba is never speculated).

    ``table`` switches to the *paged* cache: ``cache`` is then a page pool
    ``[n_pages, KV, page_size, hd]`` (``serving.kv_cache``) and reads/writes
    go through the ``[B, T]`` block table — new tokens are scattered into
    page ``table[b, p // page_size]``, and attention runs over the
    table-gathered ``[B, KV, T*page_size, hd]`` view, which reconstructs the
    contiguous cache positions exactly (bit-exact with the dense float cache).

    ``attn_kernel`` (paged only; ``"pallas" | "xla" | "gather"``, ``None`` =
    ``"gather"``; legacy bools coerce True -> "pallas", False -> "gather")
    selects the paged decode path. ``"pallas"``/``"xla"`` route through the
    fused paged-attention dispatch (``kernels.ops.paged_attention``): one
    dispatch appends the new K/V rows into their pages and runs
    online-softmax attention over block-table-indexed page loads — the
    per-lane gathered cache is never materialized (``"xla"`` pins the
    gather-free XLA formulation even on TPU). Float pages match the gather
    path to float tolerance (online vs one-shot softmax); int8 pages
    dequantize in-kernel to f32 instead of re-quantizing q/softmax weights
    for integer dots, so logits differ within quantization tolerance while
    the *pool* contents stay bitwise identical (same append grid). The
    choice is threaded explicitly from ``EngineConfig.kernels.attn`` — this
    function never reads the deprecated ``USE_PALLAS_PAGED_ATTN`` global.
    """
    b, qn, _ = x.shape
    hd, h, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    pos = jnp.asarray(pos)
    paged = table is not None
    if qn > 1 and (window or kv_prefix is not None):
        raise NotImplementedError(
            "multi-token decode: full-causal dense/moe layers only (no ring "
            "buffer, no learnable kv_prefix) — SSM/hybrid archs can't verify"
        )
    if paged:
        if window:
            raise NotImplementedError(
                "paged KV cache: sliding-window layers keep the contiguous "
                "ring buffer (hymba is served unpaged)"
            )
        if kv_prefix is not None:
            raise NotImplementedError("paged KV cache: no learnable kv_prefix")
        pos = jnp.broadcast_to(pos, (b,))  # block tables are per-lane
    per_slot = pos.ndim > 0
    q = dense(params["wq"], x, name="attn_q").reshape(b, qn, h, hd)
    k = dense(params["wk"], x, name="attn_k").reshape(b, qn, kvh, hd)
    v = dense(params["wv"], x, name="attn_v").reshape(b, qn, kvh, hd)
    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = rms_norm(params["k_norm"], k, cfg.norm_eps)
    # Query positions [B, Q]: pos + 0..Q-1 per lane (Q == 1 reduces to the
    # classic single-position decode).
    qpos = (pos if per_slot else jnp.broadcast_to(pos, (b,)))[:, None] + jnp.arange(qn)
    if cfg.mrope_sections is not None:
        posq = jnp.broadcast_to(qpos[:, :, None], (b, qn, 3))
    else:
        posq = qpos
    q = apply_rope(q, posq, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, posq, cfg.rope_theta, cfg.mrope_sections)

    int8_cache = cache["k"].dtype == jnp.int8
    k_t = jnp.swapaxes(k, 1, 2)  # [B, KV, Q, hd]
    v_t = jnp.swapaxes(v, 1, 2)

    if paged:
        # Runtime import: serving builds on models, not the reverse; the
        # paged branch is only traced by the serving engine / paged tests.
        from repro.serving import kv_cache as _kvc

        kernel = _coerce_attn_kernel(attn_kernel)
        packed4 = cache["k"].dtype == jnp.uint8
        if kernel in ("pallas", "xla") or packed4:
            # Fused dispatch: append + page-indexed flash attention in one
            # call ("pallas" = Mosaic on TPU with the gather-free XLA loop
            # as the off-TPU/VMEM fallback; "xla" pins that loop outright).
            # Packed int4 pools route *every* kernel choice here — including
            # "gather", as the dispatch's gather oracle: the legacy s8 x s8
            # path below has no nibble unpack, and the int4 tier's contract
            # is bit-exact agreement across all three paths anyway.
            from repro.kernels import ops as kops

            force = {"pallas": None, "xla": "ref", "gather": "gather"}[kernel]
            out, new_cache = kops.paged_attention(
                cache, table, pos, q, k, v, force=force,
            )
            new_cache = _kvc._shard_pool(new_cache)
            out = out.astype(x.dtype).reshape(b, qn, h * hd)
            return dense(params["wo"], out, name="attn_o"), new_cache

        if qn == 1:
            new_cache = _kvc.append_token(
                cache, k_t[:, :, 0], v_t[:, :, 0], table, pos
            )
        else:
            new_cache = _kvc.append_tokens(cache, k, v, table, pos)
        ck, cv, cks, cvs = _kvc.gather_pages(new_cache, table)
        s_cache = ck.shape[2]
    else:
        s_cache = cache["k"].shape[2]
        if qn > 1:
            # Multi-token scatter through per-token positions (clipped to the
            # cache extent — the same overwrite-last semantics as Q == 1;
            # clipped writes are only reachable by queries past a request's
            # token budget, whose logits the engine never commits).
            lin = jnp.clip(qpos, 0, s_cache - 1)  # [B, Q]
            bidx = jnp.arange(b)[:, None]
            if int8_cache:
                k_q, k_s = _quant_rows(k)  # [B, Q, KV, hd], [B, Q, KV]
                v_q, v_s = _quant_rows(v)
                ck = cache["k"].at[bidx, :, lin, :].set(k_q)
                cv = cache["v"].at[bidx, :, lin, :].set(v_q)
                cks = cache["k_scale"].at[bidx, :, lin].set(k_s)
                cvs = cache["v_scale"].at[bidx, :, lin].set(v_s)
            else:
                ck = cache["k"].at[bidx, :, lin, :].set(k.astype(cache["k"].dtype))
                cv = cache["v"].at[bidx, :, lin, :].set(v.astype(cache["v"].dtype))
        else:
            slot = (pos % s_cache) if window else jnp.minimum(pos, s_cache - 1)
            if per_slot:
                # Per-slot write positions: one dynamic_update_slice per batch
                # row (vmapped); XLA fuses these into a batched scatter.
                upd4 = jax.vmap(
                    lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (0, p, 0))
                )
                upd3 = jax.vmap(
                    lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (0, p))
                )
            else:
                upd4 = lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (0, 0, p, 0))
                upd3 = lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (0, 0, p))
            if int8_cache:
                k_q, k_s = _quant_rows(k_t)
                v_q, v_s = _quant_rows(v_t)
                ck = upd4(cache["k"], k_q, slot)
                cv = upd4(cache["v"], v_q, slot)
                cks = upd3(cache["k_scale"], k_s, slot)
                cvs = upd3(cache["v_scale"], v_s, slot)
            else:
                ck = upd4(cache["k"], k_t.astype(cache["k"].dtype), slot)
                cv = upd4(cache["v"], v_t.astype(cache["v"].dtype), slot)
    ck = logical(ck, "batch", "kv_heads", None, None)
    cv = logical(cv, "batch", "kv_heads", None, None)

    idx = jnp.arange(s_cache)
    # Causal visibility per query: slot i is visible to query j iff
    # i <= pos + j. Ring buffer (window, Q == 1): every slot is valid once
    # pos >= s_cache (wrapped). [B, Q, S] mask.
    valid = (idx[None, None, :] <= qpos[:, :, None]) | (
        jnp.full((1, 1, s_cache), bool(window), bool)
        & (qpos[:, :, None] >= s_cache)
    )
    bias = jnp.where(valid, 0.0, NEG_INF)

    rep = h // kvh
    # Never cast the cache: einsums read bf16 (or int8) operands and
    # accumulate in f32/s32 (preferred_element_type). An .astype(f32) here
    # would materialize a full-cache temp copy.
    if int8_cache:
        # Fully-int8 QK^T: quantize q per (b, q, kv, rep) row, s8 x s8 -> s32,
        # epilogue scale = q_scale * k_scale (the quant_matmul pattern).
        qf = (q.astype(jnp.float32) * (hd ** -0.5)).reshape(b, qn, kvh, rep, hd)
        q8, q_s = _quant_rows(qf)
        s32 = jnp.einsum("bqgrd,bgsd->bqgrs", q8, ck, preferred_element_type=jnp.int32)
        s = s32.astype(jnp.float32) * q_s[..., None] * cks[:, None, :, None, :]
    else:
        qf = (q.astype(jnp.float32) * (hd ** -0.5)).astype(ck.dtype)
        qf = qf.reshape(b, qn, kvh, rep, hd)
        s = jnp.einsum(
            "bqgrd,bgsd->bqgrs", qf, ck, preferred_element_type=jnp.float32
        )
    s = s + bias[:, :, None, None, :]
    if kv_prefix is not None:
        pk = kv_prefix[0]  # meta prefix keys: [B, M, KV, hd]
        sp = jnp.einsum(
            "bqgrd,bmgd->bqgrm", qf, pk.astype(ck.dtype), preferred_element_type=jnp.float32
        )
        s = jnp.concatenate([sp, s], axis=-1)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)

    def pv(p_seq, v_cache):
        """p.V with an int8 cache: fold the per-token v scales into p, then
        dynamically quantize the folded p per row -> one s8 x s8 dot.
        Exact: out = sum_s p[s] v8[s] vs[s] = (p*vs) @ v8."""
        if not int8_cache:
            # One [B, KV] batch of (Q*rep, S) x (S, hd) matmuls: the (q, r)
            # rows merged next to the batch dims. XLA's CPU backend refuses
            # a bf16 x bf16 -> f32 dot with the rows split around the batch
            # dims; only the small p is transposed, never the cache.
            qn_, rep_, s_ = p_seq.shape[1], p_seq.shape[3], p_seq.shape[4]
            p2 = jnp.moveaxis(p_seq.astype(v_cache.dtype), 1, 2).reshape(
                b, kvh, qn_ * rep_, s_)
            o = jnp.einsum("bgxs,bgsd->bgxd", p2, v_cache,
                           preferred_element_type=jnp.float32)
            return jnp.moveaxis(o.reshape(b, kvh, qn_, rep_, hd), 2, 1)
        p_fold = p_seq * cvs[:, None, :, None, :]
        p8, p_s = _quant_rows(p_fold)
        o32 = jnp.einsum("bqgrs,bgsd->bqgrd", p8, v_cache,
                         preferred_element_type=jnp.int32)
        return o32.astype(jnp.float32) * p_s[..., None]

    if kv_prefix is not None:
        m = kv_prefix[0].shape[1]
        pfx_dtype = kv_prefix[1].dtype
        out = jnp.einsum(
            "bqgrm,bmgd->bqgrd",
            p[..., :m].astype(pfx_dtype),
            kv_prefix[1],
            preferred_element_type=jnp.float32,
        )
        out = out + pv(p[..., m:], cv)
    else:
        out = pv(p, cv)
    out = out.astype(x.dtype).reshape(b, qn, h * hd)
    y = dense(params["wo"], out, name="attn_o")
    if not paged:  # paged: new_cache is the updated page pool, built above
        new_cache = {"k": ck, "v": cv}
        if int8_cache:
            new_cache["k_scale"] = cks
            new_cache["v_scale"] = cvs
    return y, new_cache
