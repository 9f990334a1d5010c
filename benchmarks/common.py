"""Shared benchmark infrastructure: trained model cache, eval loops, tables.

Every paper table needs a *trained* float model (PTQ on random weights is
meaningless — no outliers, no signal). The three subjects are trained once
per process tree and cached under ``.bench_cache/`` so table runs are
incremental:

* **convnet** — ResNet-20-shaped CNN on synthetic class-template images
  (stands in for the paper's ImageNet CNNs / CIFAR ResNet-20; Tables 1-5);
* **lstm** — 2-layer LSTM LM on the synthetic token stream (Table 6);
* **lm** — small dense transformer LM (the framework's own model zoo code
  path; Tables 2-3 LM columns).

Accuracy evals are jitted once per (model, context) and reused across all
quantization cells, since fake-quant keeps every shape identical.
"""
from __future__ import annotations

import os
import time
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.data import SyntheticLM
from repro.models import transformer as T
from repro.models.convnet import (
    ConvNetConfig,
    convnet_forward,
    convnet_loss,
    init_convnet,
    make_synthetic_images,
)
from repro.models.lstm import LSTMConfig, init_lstm, lstm_forward, lstm_loss
from repro.optim import adamw_init, adamw_update, cosine_schedule

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".bench_cache")


# ---------------------------------------------------------------------------
# Param-tree <-> npz cache


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def save_tree(name: str, tree) -> None:
    os.makedirs(CACHE_DIR, exist_ok=True)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    np.savez(
        os.path.join(CACHE_DIR, name + ".npz"),
        **{_path_str(p): np.asarray(x) for p, x in flat},
    )


def load_tree(name: str, template):
    f = os.path.join(CACHE_DIR, name + ".npz")
    if not os.path.exists(f):
        return None
    z = np.load(f)
    try:
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: jnp.asarray(z[_path_str(p)]), template
        )
    except KeyError:
        return None  # stale cache from an older layout


# ---------------------------------------------------------------------------
# Generic AdamW train loop (host data -> jitted step)


def train_loop(params, loss_fn, batches, *, lr=3e-3, log_name="", total=None):
    opt = adamw_init(params)
    total = total or len(batches)

    @jax.jit
    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        lr_t = cosine_schedule(opt.count, lr, max(total // 20, 5), total)
        params, opt = adamw_update(grads, opt, params, lr=lr_t,
                                   weight_decay=0.01, clip_norm=1.0)
        return params, opt, loss

    t0 = time.time()
    loss = None
    for i, b in enumerate(batches):
        params, opt, loss = step(params, opt, b)
        if log_name and (i % max(total // 5, 1) == 0 or i == total - 1):
            print(f"  [{log_name}] step {i}: loss {float(loss):.3f} "
                  f"({time.time() - t0:.0f}s)")
    return params


# ---------------------------------------------------------------------------
# Subject 1: convnet


CONV_CFG = ConvNetConfig(n_classes=16, width=16, n_blocks=3, img=16)


def conv_batches(n_steps: int, batch: int = 64, seed: int = 0):
    out = []
    for i in range(n_steps):
        d = make_synthetic_images(batch, CONV_CFG, seed=seed * 100_000 + i)
        out.append({"images": jnp.asarray(d["images"]),
                    "labels": jnp.asarray(d["labels"])})
    return out


def get_convnet(steps: int = 400) -> Tuple[Dict, ConvNetConfig]:
    template = init_convnet(CONV_CFG, jax.random.PRNGKey(0))
    cached = load_tree("convnet", template)
    if cached is not None:
        return cached, CONV_CFG
    print("[common] training convnet (cache miss)...")
    params = train_loop(
        template, partial(convnet_loss, cfg=CONV_CFG),
        conv_batches(steps), lr=2e-3, log_name="convnet",
    )
    save_tree("convnet", params)
    return params, CONV_CFG


_CONV_EVAL = None


def convnet_accuracy(params, n: int = 2048, seed: int = 777,
                     forward: Optional[Callable] = None) -> float:
    """Top-1 accuracy on a held-out synthetic split (seed disjoint from train)."""
    global _CONV_EVAL
    fwd = forward or (lambda p, x: convnet_forward(p, x, CONV_CFG))
    if forward is None:
        if _CONV_EVAL is None:
            _CONV_EVAL = jax.jit(fwd)
        fwd = _CONV_EVAL
    d = make_synthetic_images(n, CONV_CFG, seed=seed)
    correct = 0
    bs = 256
    for i in range(0, n, bs):
        logits = fwd(params, jnp.asarray(d["images"][i : i + bs]))
        correct += int((np.argmax(np.asarray(logits), -1)
                        == d["labels"][i : i + bs]).sum())
    return 100.0 * correct / n


# ---------------------------------------------------------------------------
# Subject 2: LSTM LM


LSTM_CFG = LSTMConfig(vocab=512, hidden=160, n_layers=2)
_LSTM_DS = SyntheticLM(LSTM_CFG.vocab, 64, 16, seed=11)


def get_lstm(steps: int = 400) -> Tuple[Dict, LSTMConfig]:
    template = init_lstm(LSTM_CFG, jax.random.PRNGKey(1))
    cached = load_tree("lstm", template)
    if cached is not None:
        return cached, LSTM_CFG
    print("[common] training lstm (cache miss)...")
    batches = [
        {k: jnp.asarray(v) for k, v in _LSTM_DS.batch_at(i).items()}
        for i in range(steps)
    ]
    params = train_loop(
        template, partial(lstm_loss, cfg=LSTM_CFG), batches,
        lr=4e-3, log_name="lstm",
    )
    save_tree("lstm", params)
    return params, LSTM_CFG


_LSTM_EVAL = None


def lstm_ppl(params, n_batches: int = 8) -> float:
    global _LSTM_EVAL
    if _LSTM_EVAL is None:
        _LSTM_EVAL = jax.jit(partial(lstm_loss, cfg=LSTM_CFG))
    losses = []
    for i in range(n_batches):
        b = {k: jnp.asarray(v) for k, v in _LSTM_DS.batch_at(50_000 + i).items()}
        losses.append(float(_LSTM_EVAL(params, b)))
    return float(np.exp(np.mean(losses)))


# ---------------------------------------------------------------------------
# Subject 3: small transformer LM (model-zoo code path)


LM_CFG = ModelConfig(
    name="bench-lm", block="dense", n_layers=4, d_model=128, n_heads=4,
    n_kv_heads=2, d_ff=256, vocab=512, attn_chunk=64, remat=False,
)
_LM_DS = SyntheticLM(LM_CFG.vocab, 64, 16, seed=7)


def get_lm(steps: int = 400) -> Tuple[Dict, ModelConfig]:
    template = T.init_params(LM_CFG, jax.random.PRNGKey(2))
    cached = load_tree("lm", template)
    if cached is not None:
        return cached, LM_CFG
    print("[common] training transformer lm (cache miss)...")
    batches = [
        {k: jnp.asarray(v) for k, v in _LM_DS.batch_at(i).items()}
        for i in range(steps)
    ]
    params = train_loop(
        template, partial(T.loss_fn, cfg=LM_CFG), batches,
        lr=3e-3, log_name="lm",
    )
    save_tree("lm", params)
    return params, LM_CFG


def lm_ppl(params, n_batches: int = 8, forward_scan: bool = True,
           eval_fn: Optional[Callable] = None) -> float:
    fn = eval_fn or jax.jit(partial(T.loss_fn, cfg=LM_CFG, scan=forward_scan))
    losses = []
    for i in range(n_batches):
        b = {k: jnp.asarray(v) for k, v in _LM_DS.batch_at(50_000 + i).items()}
        losses.append(float(fn(params, b)))
    return float(np.exp(np.mean(losses)))


# ---------------------------------------------------------------------------
# Conv-aware weight fake-quantization (matricized per §3.2)


def fake_quant_convnet(params: Dict, recipe) -> Dict:
    """OCS+clip+quantize convnet weights (stem excluded, paper §5)."""
    from repro.core.apply import _fake_quant_2d  # shared 2-D pipeline
    from repro.models.convnet import conv_w_from_2d, conv_w_to_2d

    def visit(path, leaf):
        p = _path_str(path)
        if "stem" in p:
            return leaf  # first layer unquantized
        w = np.asarray(leaf, np.float32)
        if w.ndim == 4:  # HWIO conv
            h, ww, cin, cout = w.shape
            w2d = conv_w_to_2d(w)
            wq = _fake_quant_2d(w2d, recipe)
            return jnp.asarray(conv_w_from_2d(wq, (h, ww), cout))
        if w.ndim == 2:
            return jnp.asarray(_fake_quant_2d(w, recipe))
        return leaf

    return jax.tree_util.tree_map_with_path(visit, params)


# ---------------------------------------------------------------------------
# Table rendering


def render_table(title: str, rows: List[str], cols: List[str],
                 cells: Dict[Tuple[str, str], float], fmt: str = "{:.1f}") -> str:
    widths = [max(len(c), 7) for c in cols]
    rw = max(len(r) for r in rows) + 2
    out = [title, "-" * len(title)]
    out.append(" " * rw + " | " + " | ".join(c.rjust(w) for c, w in zip(cols, widths)))
    out.append("-" * (rw + 3 + sum(w + 3 for w in widths)))
    for r in rows:
        line = r.ljust(rw) + " | "
        vals = []
        for c, w in zip(cols, widths):
            v = cells.get((r, c))
            vals.append(("-" if v is None else fmt.format(v)).rjust(w))
        out.append(line + " | ".join(vals))
    return "\n".join(out)


def save_json(name: str, obj) -> None:
    import json

    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, name + ".json"), "w") as f:
        json.dump(obj, f, indent=1, default=float)


# v2: serving bench gained the paged-KV metrics (kv_pool_peak_occupancy,
# prefix_hit_rate, kv_pages_*) and the page-exhaustion backpressure check.
# v3: the speculative-decoding arm (BENCH_serving_spec.json: acceptance rate,
# tokens/target-step, spec-vs-baseline decode throughput) and the spec_*
# zeros in the baseline serving metrics.
# v4: the paged-attention microbench (BENCH_paged_attention.json: kernel vs
# gather-oracle decode latency/throughput over context x Q x page dtype) and
# the attn_step_ms / attn_kernel decode-path accounting in BENCH_serving.
# v5: the EngineConfig API cut — BENCH_serving adds TTFT/ITL p50+p95 from
# the per-token event stream (ttft_p50_s/ttft_p95_s/itl_p50_s/itl_p95_s),
# meta gains matmul_kernel / attn_kernel_cfg, and attn_kernel now speaks the
# full KernelChoice vocabulary ("gather" for the legacy oracle path that v4
# reported as "xla").
# v6: the overload-safety layer — engine stats gain the preempted / shed /
# timed_out / errors / kernel_fallbacks counters and the watchdog
# step_p50_ms / step_p95_ms / step_stalled, surfaced in BENCH_serving, and
# the oversubscribed arm lands as BENCH_serving_overload.json (optimistic
# admission at ~50% of worst-case page demand, preemption bit-exactness
# asserted against the uncontended oracle).
# v7: the continuous-batching step scheduler — engine stats gain
# queue_wait_p50_s / queue_wait_p95_s and the sched_* counters (chunks,
# budget-limited steps, aging promotions, peak step prefill tokens),
# BENCH_serving adds compile_cache cold/warm prefill+decode compile seconds
# (the persistent compilation cache), and the oversubscribed mixed-prompt
# chunked-prefill arm lands as BENCH_serving_sched.json (token identity vs
# the monolithic oracle, itl_p95 <= 2x itl_p50 tail bound, ttft_p95
# improvement).
# v8: the serving observability layer — engine stats gain trace_* and
# drift_* (span ring + quant-drift monitor), BENCH_serving adds the
# obs_overhead_* fractions from the tracing+metrics-on rerun (gated
# absolutely at 5% by tools/compare_bench.py), and the obs arm exports
# results/TRACE_serving.json (Chrome trace) + METRICS_serving.prom
# (Prometheus text) + METRICS_serving.jsonl (registry snapshots).
# v9: the fault-tolerant replica router — router stats add the router_*
# counters / replica_health gauges / migrate-latency percentiles on top of
# the per-replica v8 engine schema, and the chaos arm lands as
# BENCH_serving_chaos.json (scripted kill/NaN/stall/retry faults; CI gates
# the kill arm absolutely: migrated > 0, lost == 0, oracle_exact == 1).
# v10: the sub-8-bit precision tiers — engine stats gain kv_bits /
# kv_bytes_per_token / kv_pool_capacity_tokens, router stats gain
# router_tier_rejected (cross-tier migration is rejected, never resumed),
# the int4-vs-int8 matched-memory arm lands as BENCH_kv_precision.json
# (CI gates the lane-capacity ratio >= 1.9 and the greedy-agreement floor
# absolutely via tools/compare_bench.py --kv), and the tier quality gate
# exports QUALITY_tiers.json (tools/quality_eval.py: logit MSE / top-1
# agreement / pseudo-ppl of int8, w4a8_ocs, w4a8_naive vs the float
# oracle; outlier separation must beat naive W4A8).
BENCH_SCHEMA_VERSION = 10


def save_bench_json(bench: str, metrics: Dict, meta: Optional[Dict] = None) -> str:
    """Write ``results/BENCH_<bench>.json`` in the stable cross-PR schema.

    Schema (version 4, consumed by future PRs' trend tooling — append keys,
    never rename):

        {"schema": 4, "bench": str, "created_unix": float,
         "metrics": {flat name -> number}, "meta": {free-form context}}
    """
    name = f"BENCH_{bench}"
    save_json(
        name,
        {
            "schema": BENCH_SCHEMA_VERSION,
            "bench": bench,
            "created_unix": time.time(),
            "metrics": metrics,
            "meta": meta or {},
        },
    )
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    return os.path.join(d, name + ".json")
