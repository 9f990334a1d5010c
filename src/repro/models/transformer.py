"""TransformerLM — composable LM covering all assigned architecture families.

Block types:
* ``dense``  — GQA attention + (SwiGLU|GELU) MLP   (glm4, minitron, deepseek-7b,
               qwen3 (qk-norm), qwen2-vl (M-RoPE), hubert (encoder, no causal))
* ``moe``    — GQA attention + MoE FFN             (deepseek-moe, phi3.5-moe)
* ``mamba2`` — SSD state-space block, attention-free (mamba2-1.3b)
* ``hymba``  — parallel attention + SSM heads sharing one input, meta tokens,
               sliding-window attention with a few global layers (hymba-1.5b)

Layers run under ``lax.scan`` with stacked parameters (HLO size independent of
depth — critical for the 512-device dry-run) or unrolled (``scan=False``) for
eager calibration taps and heterogeneous decode caches. Forward modes:

* ``forward``      — full-sequence logits (training / encoder).
* ``loss_fn``      — mean token cross-entropy (f32 softmax).
* ``prefill``      — full sequence -> last-token logits + decode caches.
* ``decode_step``  — one token against the caches (the ``serve_step``).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.sharding.specs import logical
from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .attention import (
    attention,
    attention_decode,
    attention_params_shape,
    init_kv_cache,
)
from .layers import dense, embed, rms_norm, layer_norm
from .mlp import mlp, mlp_params_shape
from .moe import moe, moe_params_shape
from .ssm import init_ssm_cache, mamba2, mamba2_decode, ssm_params_shape

__all__ = ["TransformerLM"]


def _norm(cfg: ModelConfig, p, x):
    if cfg.norm == "rms":
        return rms_norm(p["scale"], x, cfg.norm_eps)
    return layer_norm(p["scale"], p["bias"], x, cfg.norm_eps)


def _norm_shape(cfg: ModelConfig, d: int):
    if cfg.norm == "rms":
        return {"scale": (d,)}
    return {"scale": (d,), "bias": (d,)}


def layer_params_shape(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    shapes: Dict[str, Any] = {"norm1": _norm_shape(cfg, d)}
    if cfg.block in ("dense", "moe", "hymba"):
        shapes["attn"] = attention_params_shape(cfg)
    if cfg.block == "dense":
        shapes["norm2"] = _norm_shape(cfg, d)
        shapes["mlp"] = mlp_params_shape(cfg)
    elif cfg.block == "moe":
        shapes["norm2"] = _norm_shape(cfg, d)
        shapes["moe"] = moe_params_shape(cfg)
    elif cfg.block == "mamba2":
        shapes["ssm"] = ssm_params_shape(cfg)
    elif cfg.block == "hymba":
        shapes["ssm"] = ssm_params_shape(cfg)
        shapes["attn_fuse_norm"] = {"scale": (d,)}
        shapes["ssm_fuse_norm"] = {"scale": (d,)}
        shapes["norm2"] = _norm_shape(cfg, d)
        shapes["mlp"] = mlp_params_shape(cfg)
    else:
        raise ValueError(cfg.block)
    return shapes


def model_params_shape(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    shapes: Dict[str, Any] = {
        "embed": (cfg.vocab, d),
        "final_norm": _norm_shape(cfg, d),
        "layers": jax.tree.map(
            lambda s: (cfg.n_layers,) + s,
            layer_params_shape(cfg),
            is_leaf=lambda s: isinstance(s, tuple),
        ),
    }
    if not cfg.tie_embeddings:
        shapes["lm_head"] = (d, cfg.vocab)
    if cfg.block == "hymba":
        shapes["meta_tokens"] = (cfg.hymba.n_meta_tokens, d)
    return shapes


def param_init_rule(cfg: ModelConfig, path: str, shape) -> Tuple[str, float]:
    """How parameter ``path`` (``core.apply.path_str`` form) is initialized:
    ``(kind, std)`` with kind one of ``ones`` / ``zeros`` (in the tree's
    dtype), ``ones_f32`` / ``zeros_f32`` / ``a_log`` (always float32), or
    ``normal`` (``std`` times a standard normal).

    The one rule behind :func:`init_params` (``jax.random``) and the
    host-side seeded loader (``repro.launch.weights``, numpy, one slice at a
    time); only the random source differs.
    """
    p = path.lower()
    vector = len(shape) == 1 or (len(shape) == 2 and shape[0] == cfg.n_layers)
    if "scale" in p or "norm" in p:
        return "ones", 0.0
    if "a_log" in p:
        return "a_log", 0.0
    if "dt_bias" in p or p.endswith("conv_b"):
        return "zeros_f32", 0.0
    if p.endswith("/d") or p.split("/")[-1] == "d":
        return "ones_f32", 0.0
    if vector:
        return "zeros", 0.0
    if "embed" in p or "meta_tokens" in p:
        return "normal", 0.02
    return "normal", 1.0 / math.sqrt(shape[-2])


def init_params(cfg: ModelConfig, key, dtype=jnp.float32):
    from repro.core.apply import path_str

    shapes = model_params_shape(cfg)
    is_shape = lambda s: isinstance(s, tuple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)
    keys = jax.random.split(key, len(flat))

    def init_one(k, path, shape):
        kind, std = param_init_rule(cfg, path_str(path), shape)
        if kind == "normal":
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        if kind == "a_log":
            base = jnp.log(jnp.linspace(1.0, 16.0, shape[-1], dtype=jnp.float32))
            return jnp.broadcast_to(base, shape).astype(jnp.float32)
        fill = jnp.ones if kind.startswith("ones") else jnp.zeros
        return fill(shape, jnp.float32 if kind.endswith("_f32") else dtype)

    leaves = [init_one(k, path, shape) for k, (path, shape) in zip(keys, flat)]
    return treedef.unflatten(leaves)


def per_layer(params):
    """``params`` in the serving layout: ``params["layers"]`` a list of
    per-layer trees.

    Training keeps one tree of stacked ``[L, ...]`` leaves (``forward``'s
    ``lax.scan``). The decode and prefill functions take the per-layer
    list: slicing a stacked leaf that feeds a Pallas call copies the slice
    first (a custom call cannot read a view of a larger buffer), which
    doubles the weight traffic of every decode step. Called once where a
    tree enters serving (``ServingEngine``); a list passes through as is.
    """
    layers = params["layers"]
    if isinstance(layers, list):
        return params
    n = jax.tree.leaves(layers)[0].shape[0]
    return {**params,
            "layers": [jax.tree.map(lambda a: a[i], layers) for i in range(n)]}


# ---------------------------------------------------------------------------
# Block forward (full sequence)


def _block(cfg: ModelConfig, p, x, positions, layer_flag=None, *, return_kv=False,
           kv_prefix=None, prefix_len=None):
    """One layer, full sequence.

    ``layer_flag``: hymba is-global switch — a static bool when layers run
    in homogeneous segments (enables the statically-skipped window path in
    attention), or a traced bool under a mixed scan (decode fallback).
    ``return_kv`` (dense/moe only): also return this layer's post-RoPE K/V
    — the chunked-prefill cache build reuses the exact forward body.
    ``kv_prefix`` (dense/moe only): cached K/V of an already-prefilled
    prompt prefix, concatenated on the key side — suffix-only prefill for
    the paged prefix cache (callers offset ``positions`` by the prefix len).
    ``prefix_len`` (traced scalar, dense/moe only): real length of a padded
    ``kv_prefix`` — pad rows are masked invisible (chunked prefill).
    """
    kind = "full" if not cfg.causal else "causal"
    if cfg.block in ("dense", "moe"):
        h = _norm(cfg, p["norm1"], x)
        a = attention(
            p["attn"], h, cfg, positions=positions, kind=kind,
            return_kv=return_kv, kv_prefix=kv_prefix, prefix_len=prefix_len,
        )
        kv = None
        if return_kv:
            a, kv = a
        x = x + a
        h = _norm(cfg, p["norm2"], x)
        x = x + (
            moe(p["moe"], h, cfg) if cfg.block == "moe" else mlp(p["mlp"], h, cfg)
        )
        return (x, kv) if return_kv else x
    if return_kv or kv_prefix is not None:
        raise NotImplementedError(
            f"return_kv/kv_prefix: attention blocks only, got {cfg.block}"
        )
    if cfg.block == "mamba2":
        h = _norm(cfg, p["norm1"], x)
        x = x + mamba2(p["ssm"], h, cfg)
    elif cfg.block == "hymba":
        h = _norm(cfg, p["norm1"], x)
        if isinstance(layer_flag, (bool, np.bool_)):  # static segment
            a_kind = "causal" if layer_flag else "window"
            a_flag = None
        else:
            a_kind = "window"
            a_flag = layer_flag
        a = attention(
            p["attn"],
            h,
            cfg,
            positions=positions,
            kind=a_kind,
            window=cfg.hymba.swa_window,
            is_global=a_flag,
            n_prefix=cfg.hymba.n_meta_tokens,
        )
        s = mamba2(p["ssm"], h, cfg)
        fused = 0.5 * (
            rms_norm(p["attn_fuse_norm"]["scale"], a, cfg.norm_eps)
            + rms_norm(p["ssm_fuse_norm"]["scale"], s, cfg.norm_eps)
        )
        x = x + fused
        h = _norm(cfg, p["norm2"], x)
        x = x + mlp(p["mlp"], h, cfg)
    else:
        raise ValueError(cfg.block)
    return x


def _hymba_flags(cfg: ModelConfig) -> np.ndarray:
    """Static (host) per-layer is-global flags; jnp-converted only for scan."""
    flags = np.zeros(cfg.n_layers, dtype=bool)
    for i in cfg.hymba.global_layers:
        flags[i] = True
    return flags


def _segments(flags: np.ndarray):
    """Contiguous same-flag runs: [(lo, hi, flag), ...] covering all layers."""
    out = []
    lo = 0
    for i in range(1, len(flags) + 1):
        if i == len(flags) or flags[i] != flags[lo]:
            out.append((lo, i, bool(flags[lo])))
            lo = i
    return out


def _positions(cfg: ModelConfig, b: int, s: int, offset: int = 0):
    pos = jnp.arange(s) + offset
    if cfg.mrope_sections is not None:
        return jnp.broadcast_to(pos[None, :, None], (b, s, 3))
    return jnp.broadcast_to(pos[None, :], (b, s))


def forward(
    params,
    tokens: jnp.ndarray,
    cfg: ModelConfig,
    *,
    scan: bool = True,
    embeds: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-sequence logits. tokens: [B, S] int32 (or embeds [B, S, d])."""
    if embeds is not None:
        x = embeds.astype(jnp.bfloat16)
        b, s = x.shape[0], x.shape[1]
    else:
        b, s = tokens.shape
        x = embed(params["embed"], tokens)
    x = logical(x, "batch", "seq", "embed")

    n_meta = cfg.hymba.n_meta_tokens if cfg.block == "hymba" else 0
    if n_meta:
        meta = jnp.broadcast_to(
            params["meta_tokens"].astype(x.dtype), (b, n_meta, cfg.d_model)
        )
        x = jnp.concatenate([meta, x], axis=1)
    positions = _positions(cfg, b, s + n_meta)

    flags = _hymba_flags(cfg) if cfg.block == "hymba" else None
    if scan and flags is not None:
        # Segmented scan: contiguous runs of same-kind layers (the 3 global
        # layers become their own segments) so the window/global choice is
        # STATIC inside each body — unlocking the skipped-chunk window path.
        # HLO holds one body per segment (~5 for hymba) instead of 1; depth
        # independence within segments is preserved.
        for lo, hi, glob in _segments(flags):
            sub = jax.tree.map(lambda a: a[lo:hi], params["layers"])
            body = lambda carry, p, _g=bool(glob): (
                _block(cfg, p, carry, positions, _g),
                None,
            )
            if cfg.remat:
                body = jax.checkpoint(body)
            x, _ = jax.lax.scan(body, x, sub)
    elif scan:
        body = lambda carry, p: (_block(cfg, p, carry, positions, None), None)
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        for i in range(cfg.n_layers):
            p_i = jax.tree.map(lambda a: a[i], params["layers"])
            f_i = bool(flags[i]) if flags is not None else None
            x = _block(cfg, p_i, x, positions, f_i)

    if n_meta:
        x = x[:, n_meta:]
    x = _norm(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = dense(head, x, name="lm_head")
    return logical(logits, "batch", "seq", "vocab")


def loss_fn(
    params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig, *, scan: bool = True
) -> jnp.ndarray:
    """Mean token cross-entropy (f32). batch: tokens/labels [B, S] (+embeds)."""
    logits = forward(
        params, batch.get("tokens"), cfg, scan=scan, embeds=batch.get("embeds")
    ).astype(jnp.float32)
    labels = batch["labels"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# Decode path


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Per-layer decode caches (+ per-slot position vector [batch]).

    Caches are a *list of per-layer trees*, not stacked [L, ...] arrays:
    decode unrolls the layer loop so every cache tensor is updated by exactly
    one dynamic_update_slice and XLA aliases the donated buffer in place.
    (A scanned [L, ...] cache forces xs/ys double buffering — measured 22 GB
    of temps for deepseek-7b decode_32k before this layout.)
    """
    if not cfg.causal:
        raise ValueError("encoder-only models have no decode step")

    if cfg.block in ("dense", "moe"):
        return {
            "layers": [
                {"attn": init_kv_cache(cfg, batch, max_len, dtype=dtype)}
                for _ in range(cfg.n_layers)
            ],
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    if cfg.block == "mamba2":
        return {
            "layers": [
                {"ssm": init_ssm_cache(cfg, batch, dtype=dtype)}
                for _ in range(cfg.n_layers)
            ],
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    if cfg.block == "hymba":
        flags = np.zeros(cfg.n_layers, bool)
        for i in cfg.hymba.global_layers:
            flags[i] = True
        caches = []
        for i in range(cfg.n_layers):
            window = 0 if flags[i] else cfg.hymba.swa_window
            caches.append(
                {
                    "attn": init_kv_cache(cfg, batch, max_len, window=window, dtype=dtype),
                    "meta_k": jnp.zeros(
                        (batch, cfg.hymba.n_meta_tokens, cfg.n_kv_heads, cfg.hd), dtype
                    ),
                    "meta_v": jnp.zeros(
                        (batch, cfg.hymba.n_meta_tokens, cfg.n_kv_heads, cfg.hd), dtype
                    ),
                    "ssm": init_ssm_cache(cfg, batch, dtype=dtype),
                }
            )
        return {"layers": caches, "pos": jnp.zeros((batch,), jnp.int32)}
    raise ValueError(cfg.block)


def _decode_block(cfg: ModelConfig, p, x, cache, pos, window: int = 0, table=None,
                  attn_kernel=None):
    """One layer, one token. Returns (x, new_cache). ``table`` (dense/moe):
    the paged cache's block table — ``cache`` is then a page pool;
    ``attn_kernel`` ("pallas" | "xla" | "gather") selects the paged decode
    attention path (see ``attention.attention_decode``)."""
    if cfg.block in ("dense", "moe"):
        h = _norm(cfg, p["norm1"], x)
        a, new_attn = attention_decode(p["attn"], h, cache, pos, cfg, table=table,
                                       attn_kernel=attn_kernel)
        x = x + a
        h = _norm(cfg, p["norm2"], x)
        x = x + (moe(p["moe"], h, cfg) if cfg.block == "moe" else mlp(p["mlp"], h, cfg))
        return x, new_attn
    if cfg.block == "mamba2":
        h = _norm(cfg, p["norm1"], x)
        s, new_ssm = mamba2_decode(p["ssm"], h, cache, cfg)
        return x + s, new_ssm
    if cfg.block == "hymba":
        h = _norm(cfg, p["norm1"], x)
        a, new_attn = attention_decode(
            p["attn"],
            h,
            cache["attn"],
            pos,
            cfg,
            window=window,
            kv_prefix=(cache["meta_k"], cache["meta_v"]),
        )
        s, new_ssm = mamba2_decode(p["ssm"], h, cache["ssm"], cfg)
        fused = 0.5 * (
            rms_norm(p["attn_fuse_norm"]["scale"], a, cfg.norm_eps)
            + rms_norm(p["ssm_fuse_norm"]["scale"], s, cfg.norm_eps)
        )
        x = x + fused
        h = _norm(cfg, p["norm2"], x)
        x = x + mlp(p["mlp"], h, cfg)
        new_cache = {
            "attn": new_attn,
            "meta_k": cache["meta_k"],
            "meta_v": cache["meta_v"],
            "ssm": new_ssm,
        }
        return x, new_cache
    raise ValueError(cfg.block)


def decode_tokens(
    params,
    tokens: jnp.ndarray,
    caches,
    cfg: ModelConfig,
    *,
    layers_limit: Optional[int] = None,
    attn_kernel=None,
):
    """Shared decode body: Q tokens [B, Q] -> (logits [B, Q, V], new caches).

    ``Q == 1`` is the classic serve step; ``Q > 1`` is the speculative
    *verify* path (dense/moe only): the Q tokens occupy positions ``pos ..
    pos + Q - 1``, K/V rows for all of them are written through the cache
    (paged or dense), and logit ``j`` attends causally over positions
    ``<= pos + j`` — equal to Q sequential one-token steps, in ONE call.

    The layer loop is unrolled (see ``init_cache``): per-layer cache tensors
    are donated and updated in place. ``params`` is in the serving layout
    (:func:`per_layer`), as for every prefill function below.

    Paged caches (``"table"`` present, see ``serving.kv_cache``): per-layer
    leaves are page pools and reads/writes go through the shared block table;
    ``attn_kernel`` ("pallas" | "xla" | "gather"; ``None`` = "gather")
    selects their decode-attention path — threaded explicitly from
    ``EngineConfig.kernels.attn``, never read from a module global.

    ``layers_limit`` (dense/moe): run only the first L layers and project
    their output through final_norm + lm_head — the early-exit *drafter* of
    the self-speculation subsystem. Caches of skipped layers pass through
    untouched.
    """
    pos = caches["pos"]
    table = caches.get("table")  # paged KV cache (dense/moe serving)
    qn = tokens.shape[1]
    if qn > 1 and cfg.block not in ("dense", "moe"):
        raise NotImplementedError(
            f"multi-token decode: attention archs only, got {cfg.block} "
            "(SSM/hybrid decode states cannot roll back a rejected tail)"
        )
    n_run = cfg.n_layers
    if layers_limit is not None:
        if cfg.block not in ("dense", "moe"):
            raise NotImplementedError("layers_limit: dense/moe drafters only")
        n_run = max(1, min(layers_limit, cfg.n_layers))
    x = embed(params["embed"], tokens)
    x = logical(x, "batch", "seq", "embed")

    flags = _hymba_flags(cfg) if cfg.block == "hymba" else None
    new_layers = []
    for i in range(cfg.n_layers):
        if i >= n_run:
            new_layers.append(caches["layers"][i])  # drafter skips the tail
            continue
        p_i = params["layers"][i]
        if cfg.block == "hymba":
            window = 0 if bool(flags[i]) else cfg.hymba.swa_window
            x, nc = _decode_block(cfg, p_i, x, caches["layers"][i], pos, window)
        elif cfg.block in ("dense", "moe"):
            x, nc_attn = _decode_block(
                cfg, p_i, x, caches["layers"][i]["attn"], pos, table=table,
                attn_kernel=attn_kernel,
            )
            nc = {"attn": nc_attn}
        elif cfg.block == "mamba2":
            x, nc_ssm = _decode_block(cfg, p_i, x, caches["layers"][i]["ssm"], pos)
            nc = {"ssm": nc_ssm}
        else:
            raise ValueError(cfg.block)
        new_layers.append(nc)
    new_caches = {"layers": new_layers, "pos": pos + qn}
    if table is not None:
        new_caches["table"] = table

    x = _norm(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = dense(head, x, name="lm_head")
    return logical(logits, "batch", "seq", "vocab"), new_caches


def decode_step(
    params,
    token: jnp.ndarray,
    caches,
    cfg: ModelConfig,
    *,
    layers_limit: Optional[int] = None,
    attn_kernel=None,
):
    """serve_step: one new token [B, 1] -> (logits [B, V], new caches).

    ``layers_limit`` truncates to the first L layers (the speculative
    drafter); ``attn_kernel`` selects the paged decode-attention path; see
    :func:`decode_tokens`.
    """
    logits, new_caches = decode_tokens(
        params, token, caches, cfg, layers_limit=layers_limit,
        attn_kernel=attn_kernel,
    )
    return logical(logits[:, 0, :], "batch", "vocab"), new_caches


def verify_step(params, tokens: jnp.ndarray, caches, cfg: ModelConfig, *,
                attn_kernel=None):
    """Speculative verify: score Q proposed tokens in ONE batched step.

    tokens: ``[B, Q]`` — each lane's current token followed by its Q-1 draft
    proposals. Returns (logits ``[B, Q, V]``, new caches with ``pos``
    advanced by Q): ``logits[:, j]`` is exactly the distribution a plain
    decode loop would produce after consuming ``tokens[:, :j+1]``, so greedy
    acceptance (`argmax(logits[:, j]) == tokens[:, j+1]`) commits precisely
    the tokens plain greedy decode would emit. The caller rolls back the
    rejected tail by rewinding ``pos`` (``serving.kv_cache.rewind_positions``)
    — K/V written past the committed position is invisible to the causal
    mask and overwritten in place later. Dense/moe archs only.
    """
    return decode_tokens(params, tokens, caches, cfg, attn_kernel=attn_kernel)


def prefill(params, tokens: jnp.ndarray, cfg: ModelConfig, max_len: int):
    """Run the full prompt, return last-token logits (no cache build).

    For the dry-run shapes only ``forward`` (prefill compute) matters; the
    serving engine uses :func:`prefill_with_cache`.
    """
    logits = forward(params, tokens, cfg)
    return logits[:, -1, :]


def _write_kv(cache, k, v):
    """Write full-sequence K/V [B, S, KV, hd] into the first S slots of a
    decode cache layout [B, KV, S_cache, hd] (int8-quantizing per token when
    the cache is int8). Positions beyond the real prompt length hold
    pad-token K/V — invisible to decode, which masks on the per-slot
    position."""
    from .attention import _quant_rows

    k_t = jnp.swapaxes(k, 1, 2)  # [B, KV, S, hd]
    v_t = jnp.swapaxes(v, 1, 2)
    s = k_t.shape[2]
    if cache["k"].dtype == jnp.int8:
        k_q, k_s = _quant_rows(k_t)
        v_q, v_s = _quant_rows(v_t)
        return {
            "k": cache["k"].at[:, :, :s, :].set(k_q),
            "v": cache["v"].at[:, :, :s, :].set(v_q),
            "k_scale": cache["k_scale"].at[:, :, :s].set(k_s),
            "v_scale": cache["v_scale"].at[:, :, :s].set(v_s),
        }
    return {
        "k": cache["k"].at[:, :, :s, :].set(k_t.astype(cache["k"].dtype)),
        "v": cache["v"].at[:, :, :s, :].set(v_t.astype(cache["v"].dtype)),
    }


def prefill_with_cache(
    params,
    tokens: jnp.ndarray,
    cfg: ModelConfig,
    max_len: int,
    *,
    length: Optional[jnp.ndarray] = None,
    cache_dtype=jnp.float32,
):
    """True chunked prefill: one full-sequence forward that also materializes
    decode-ready KV caches — O(1) jitted calls per prompt instead of the
    O(prompt_len) decode-step replay.

    tokens: [B, S_pad] int32, zero-padded to the jit bucket; ``length``
    (scalar or [B]) is the real prompt length — logits are taken at
    ``length - 1`` and the returned cache's per-slot ``pos`` starts there.
    Attention blocks only (dense/moe): SSM and hybrid blocks carry conv/SSD
    states that the full-sequence scan does not expose in cache layout; the
    engine keeps the decode-replay fallback for those.
    """
    if cfg.block not in ("dense", "moe"):
        raise NotImplementedError(f"chunked prefill: attention archs only, got {cfg.block}")
    b, s = tokens.shape
    if length is None:
        length = jnp.full((b,), s, jnp.int32)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))

    x = embed(params["embed"], tokens)
    x = logical(x, "batch", "seq", "embed")
    positions = _positions(cfg, b, s)
    caches = init_cache(cfg, b, max_len, dtype=cache_dtype)

    for i in range(cfg.n_layers):
        p = params["layers"][i]
        # The exact forward body (_block) — chunked prefill cannot drift
        # from forward/decode_step structure.
        x, (k, v) = _block(cfg, p, x, positions, return_kv=True)
        caches["layers"][i]["attn"] = _write_kv(caches["layers"][i]["attn"], k, v)

    caches["pos"] = length
    x = _norm(cfg, params["final_norm"], x)
    # Project only the last real token through the lm_head: the vocab dim is
    # the widest output in the model, so a full [B, S, V] projection would
    # waste (S-1)/S of the prefill's largest matmul.
    last_h = jnp.take_along_axis(
        x, (length - 1)[:, None, None].astype(jnp.int32), axis=1
    )
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    last = dense(head, last_h, name="lm_head")[:, 0, :]
    return logical(last, "batch", "vocab"), caches


def prefill_chunk_with_cache(
    params,
    tokens: jnp.ndarray,
    cfg: ModelConfig,
    caches,
    *,
    start: jnp.ndarray,
    length: jnp.ndarray,
    prefix_pad: int,
):
    """One budgeted prefill chunk against an unpaged decode cache (b=1).

    tokens: ``[1, S_bucket]`` — this chunk's prompt tokens, zero-padded to
    the jit bucket; ``start`` (traced scalar): tokens already committed to
    ``caches`` (the chunk's absolute offset); ``length``: ``[1]`` real chunk
    length; ``prefix_pad`` (static): cache rows ``[0, prefix_pad)`` are
    attended as the chunk's prefix, with rows past ``start`` masked
    invisible and zero-selected — so every chunk whose committed prefix
    rounds into the same pow2 bucket shares one jit trace (the unpaged twin
    of :func:`prefill_into_pages` with padded ``prefix_ids``).

    Returns (last-real-token logits ``[1, V]``, updated caches with ``pos``
    advanced to ``start + length``). K/V rows land at absolute positions
    ``[start, start + S_bucket)`` via a drop-mode scatter; bucket-pad rows
    past the real length hold garbage that the next chunk (or decode)
    overwrites before any masked read can see it — exactly the
    :func:`prefill_with_cache` pad contract.
    """
    from .attention import _quant_rows

    if cfg.block not in ("dense", "moe"):
        raise NotImplementedError(
            f"chunked prefill: attention archs only, got {cfg.block}"
        )
    b, s = tokens.shape
    if b != 1:
        raise ValueError("chunked prefill is per-request (b=1 scratch cache)")
    st = jnp.asarray(start, jnp.int32).reshape(())
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))

    x = embed(params["embed"], tokens)
    x = logical(x, "batch", "seq", "embed")
    positions = _positions(cfg, b, s, offset=st)
    idx = st + jnp.arange(s)

    new_layers = []
    for i in range(cfg.n_layers):
        p = params["layers"][i]
        cache = caches["layers"][i]["attn"]
        kv_prefix = None
        if prefix_pad:
            pk = jnp.swapaxes(cache["k"][:, :, :prefix_pad, :], 1, 2)
            pv = jnp.swapaxes(cache["v"][:, :, :prefix_pad, :], 1, 2)
            if cache["k"].dtype == jnp.int8:
                ks = jnp.swapaxes(cache["k_scale"][:, :, :prefix_pad], 1, 2)
                vs = jnp.swapaxes(cache["v_scale"][:, :, :prefix_pad], 1, 2)
                pk = pk.astype(jnp.float32) * ks[..., None]
                pv = pv.astype(jnp.float32) * vs[..., None]
            # Rows past the commit point are stale (earlier bucket pads) —
            # zero-select so the masked softmax sees finite scores.
            row_ok = (jnp.arange(prefix_pad) < st)[None, :, None, None]
            kv_prefix = (jnp.where(row_ok, pk, 0.0), jnp.where(row_ok, pv, 0.0))
        # The exact forward body (_block) — chunked prefill cannot drift
        # from forward/decode_step structure.
        x, (k, v) = _block(cfg, p, x, positions, return_kv=True,
                           kv_prefix=kv_prefix,
                           prefix_len=(st if prefix_pad else None))
        k_t = jnp.swapaxes(k, 1, 2)
        v_t = jnp.swapaxes(v, 1, 2)
        if cache["k"].dtype == jnp.int8:
            k_q, k_s = _quant_rows(k_t)
            v_q, v_s = _quant_rows(v_t)
            new = {
                "k": cache["k"].at[:, :, idx, :].set(k_q, mode="drop"),
                "v": cache["v"].at[:, :, idx, :].set(v_q, mode="drop"),
                "k_scale": cache["k_scale"].at[:, :, idx].set(k_s, mode="drop"),
                "v_scale": cache["v_scale"].at[:, :, idx].set(v_s, mode="drop"),
            }
        else:
            new = {
                "k": cache["k"].at[:, :, idx, :].set(
                    k_t.astype(cache["k"].dtype), mode="drop"
                ),
                "v": cache["v"].at[:, :, idx, :].set(
                    v_t.astype(cache["v"].dtype), mode="drop"
                ),
            }
        new_layers.append({"attn": new})

    x = _norm(cfg, params["final_norm"], x)
    last_h = jnp.take_along_axis(
        x, (length - 1)[:, None, None].astype(jnp.int32), axis=1
    )
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    last = dense(head, last_h, name="lm_head")[:, 0, :]
    new_caches = {"layers": new_layers, "pos": st + length}
    return logical(last, "batch", "vocab"), new_caches


def prefill_into_pages(
    params,
    tokens: jnp.ndarray,
    cfg: ModelConfig,
    pools,
    page_ids: jnp.ndarray,
    *,
    length: jnp.ndarray,
    prefix_ids: jnp.ndarray,
    prefix_len: Optional[jnp.ndarray] = None,
):
    """Chunked prefill straight into the paged KV cache (one request).

    tokens: ``[1, S_bucket]`` — the prompt *suffix* (tokens past the shared
    prefix), zero-padded to the jit bucket (``S_bucket % page_size == 0``);
    ``length``: ``[1]`` real suffix length; ``page_ids``: ``[S_bucket //
    page_size]`` pool pages receiving the suffix K/V (trash-padded past the
    allocation); ``prefix_ids``: ``[n_hit_pages]`` pages of the shared,
    already-prefilled prefix — gathered read-only and attended via the
    ``kv_prefix`` key-side concat (every suffix query is causally after the
    whole prefix, so "always visible" is exact). ``pools``: list of per-layer
    page pools. Returns (last-token logits ``[1, V]``, updated pools).

    ``prefix_len`` (``[1]`` traced, optional): the real prefix length when
    ``prefix_ids`` is *padded* with trash pages to a pow2 page bucket — the
    budgeted chunk scheduler pads so successive chunks of one prompt share
    jit traces instead of compiling one trace per prefix size. Pad rows are
    zero-selected after the gather and masked invisible in attention, so
    they contribute exact zeros to the online softmax.

    Prefix reuse is what makes a repeated system prompt prefill once: the
    suffix forward is the only model compute this function runs.
    """
    from repro.serving import kv_cache as _kvc  # serving builds on models

    if cfg.block not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged prefill: attention archs only, got {cfg.block}"
        )
    b, s = tokens.shape
    if b != 1:
        raise ValueError("paged prefill is per-request (page_ids are per-seq)")
    n_hit = prefix_ids.shape[0] * _kvc.page_size(pools[0])
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    p_len = None
    if prefix_len is not None and n_hit:
        p_len = jnp.asarray(prefix_len, jnp.int32).reshape(())

    x = embed(params["embed"], tokens)
    x = logical(x, "batch", "seq", "embed")
    positions = _positions(cfg, b, s, offset=(n_hit if p_len is None else p_len))

    new_pools = []
    for i in range(cfg.n_layers):
        p = params["layers"][i]
        kv_prefix = _kvc.gather_prefix(pools[i], prefix_ids) if n_hit else None
        if kv_prefix is not None and p_len is not None:
            # Trash-page pad rows may hold arbitrary stale K/V (even NaN from
            # a quarantined lane) — zero-select them so the masked softmax
            # sees finite scores.
            pk, pv = kv_prefix
            row_ok = (jnp.arange(n_hit) < p_len)[None, :, None, None]
            kv_prefix = (jnp.where(row_ok, pk, 0.0), jnp.where(row_ok, pv, 0.0))
        # The exact forward body (_block) — paged prefill cannot drift from
        # forward/decode_step structure.
        x, (k, v) = _block(cfg, p, x, positions, return_kv=True,
                           kv_prefix=kv_prefix, prefix_len=p_len)
        new_pools.append(_kvc.write_prompt_pages(pools[i], k, v, page_ids))

    x = _norm(cfg, params["final_norm"], x)
    # Project only the last real suffix token through the lm_head (the vocab
    # dim is the widest output in the model — see prefill_with_cache).
    last_h = jnp.take_along_axis(
        x, (length - 1)[:, None, None].astype(jnp.int32), axis=1
    )
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    last = dense(head, last_h, name="lm_head")[:, 0, :]
    return logical(last, "batch", "vocab"), new_pools


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    """Thin, stateless facade bundling the functional API."""

    cfg: ModelConfig

    def init(self, key, dtype=jnp.float32):
        return init_params(self.cfg, key, dtype)

    def forward(self, params, tokens, **kw):
        return forward(params, tokens, self.cfg, **kw)

    def loss(self, params, batch, **kw):
        return loss_fn(params, batch, self.cfg, **kw)

    def init_cache(self, batch, max_len, dtype=jnp.bfloat16):
        return init_cache(self.cfg, batch, max_len, dtype)

    def decode_step(self, params, token, caches):
        return decode_step(per_layer(params), token, caches, self.cfg)

    def verify_step(self, params, tokens, caches):
        return verify_step(per_layer(params), tokens, caches, self.cfg)

    def prefill(self, params, tokens, max_len: int):
        return prefill(params, tokens, self.cfg, max_len)
