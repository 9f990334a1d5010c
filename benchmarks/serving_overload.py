"""Oversubscribed serving benchmark -> results/BENCH_serving_overload.json.

    PYTHONPATH=src python -m benchmarks.serving_overload [--quick]
        [--arch glm4-9b] [--n-requests N]

The overload arm of the serving trajectory (ISSUE 6, ROADMAP items 2/5):
drive the engine with a mixed long/short prompt queue against a page pool
sized at ~50% of the workload's worst-case demand under **optimistic
admission**, so mid-decode page exhaustion and preemption-and-recompute are
guaranteed to fire. Three sub-arms:

* **oversubscribed** — the headline arm. Asserts zero deadlocks (every
  request reaches a terminal ``finish_reason``), ``preempted > 0`` (the pool
  genuinely ran dry), and — the paper-grade contract — every greedy output
  is **token-identical to the uncontended oracle** (same requests, full
  pool, reserve admission);
* **deadline** — the same workload with a tight per-request ``deadline_s``:
  some requests must time out, none may hang, and every completion is still
  oracle-exact;
* **shed** — a bounded queue (``max_queue``) absorbing a burst: the
  overflow must be rejected as typed ``EngineOverloaded`` sheds while every
  admitted request completes.

Reported metrics (schema v6): throughput under contention, the overload
counters (preempted / shed / timed_out), recompute overhead (decode steps
vs oracle), and the watchdog step-time percentiles. CPU smoke numbers are
not TPU numbers — the value is the trend and the exactness/termination
invariants, which are machine-independent.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import smoke_config
from repro.core.apply import quantize_params
from repro.core.recipe import QuantRecipe
from repro.models import transformer as T
from repro.obs.log import add_log_level_arg, get_logger, setup_logging
from repro.serving import (
    EngineConfig,
    EngineOverloaded,
    Request,
    ServingEngine,
    pages_needed,
)

from .common import save_bench_json

log = get_logger("bench.overload")


def _mk_requests(rng, vocab, lengths, max_new, deadline_s=None):
    return [
        Request(
            uid=i,
            prompt=rng.integers(0, vocab, n).tolist(),
            max_new_tokens=max_new,
            deadline_s=deadline_s,
        )
        for i, n in enumerate(lengths)
    ]


def _drive(cfg, params, ecfg, reqs, *, max_steps=50_000):
    """Submit everything, run to drain, and assert termination: every
    request left the engine with a terminal finish_reason (zero deadlocks —
    the oversubscribed acceptance bar)."""
    eng = ServingEngine(cfg, params, ecfg)
    shed = 0
    for r in reqs:
        try:
            eng.submit(r)
        except EngineOverloaded:
            shed += 1
    t0 = time.perf_counter()
    eng.run(max_steps=max_steps)
    wall = time.perf_counter() - t0
    for r in reqs:
        assert r.finish_reason is not None, (
            f"request {r.uid} never reached a terminal state (deadlock)"
        )
        assert r.t_done > 0.0, r.uid
    s = eng.stats()
    assert s["kv_pages_in_use"] == 0.0, "drained engine must hold no pages"
    s["wall_s"] = wall
    s["shed_at_submit"] = float(shed)
    return eng, s


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--n-requests", type=int, default=0, help="0 = preset")
    ap.add_argument("--max-new", type=int, default=0, help="0 = preset")
    ap.add_argument("--float-weights", action="store_true",
                    help="skip PTQ, serve the float tree")
    ap.add_argument("--ocs-ratio", type=float, default=0.02)
    ap.add_argument("--seed", type=int, default=0)
    add_log_level_arg(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    setup_logging(args.log_level)

    n_req = args.n_requests or (6 if args.quick else 12)
    # max_new must outgrow the optimistic install grant (prompt pages +
    # headroom) or decode never requests growth and preemption cannot fire.
    max_new = args.max_new or 16
    cfg = smoke_config(args.arch)
    if cfg.block not in ("dense", "moe"):
        raise SystemExit(
            f"overload bench needs a paged (dense/moe) arch, got {cfg.block}"
        )
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    if not args.float_weights:
        recipe = QuantRecipe(
            w_bits=8, ocs_ratio=args.ocs_ratio, per_channel=True, pad_to=1
        )
        t0 = time.perf_counter()
        params = quantize_params(params, recipe)
        get_logger("bench.ptq").info(
            "OCS+int8 in %.1fs", time.perf_counter() - t0)

    rng = np.random.default_rng(args.seed + 1)
    max_batch, max_len, page_size = 4, 128, 8
    # Mixed workload: alternate long and short prompts so lanes of very
    # different page appetites cohabit (the preemption-interesting case).
    lengths = [
        int(rng.integers(24, 48)) if i % 2 == 0 else int(rng.integers(3, 10))
        for i in range(n_req)
    ]
    # Pool at ~50% of the worst-case demand of a full batch of the hungriest
    # requests: optimistic admission overcommits, decode growth runs dry,
    # preemption must fire.
    worst = max(
        min(pages_needed(n + max_new, page_size), max_len // page_size)
        for n in lengths
    )
    n_pages = max(worst + 2, (max_batch * worst) // 2) + 1
    log.info(
        "arch=%s requests=%d lengths=%s pool=%d pages (~50%% of "
        "worst-case %d)", cfg.name, n_req, lengths, n_pages - 1,
        max_batch * worst,
    )

    oracle_conf = EngineConfig(max_batch=max_batch, max_len=max_len,
                               page_size=page_size)
    over_conf = oracle_conf.replace(n_pages=n_pages, admission="optimistic")

    # --- oracle: uncontended, reserve admission -------------------------
    # Every later arm clones its prompts from oracle_reqs, so all arms
    # serve the identical request stream.
    oracle_reqs = _mk_requests(rng, cfg.vocab, lengths, max_new)
    _, oracle_stats = _drive(cfg, params, oracle_conf, oracle_reqs)
    oracle_out = {r.uid: list(r.output) for r in oracle_reqs}

    # --- arm 1: oversubscribed pool, preemption-and-recompute -----------
    reqs = [Request(uid=r.uid, prompt=list(r.prompt), max_new_tokens=max_new)
            for r in oracle_reqs]
    eng, s = _drive(cfg, params, over_conf, reqs)
    assert s["preempted"] > 0, (
        "pool was sized to force preemption but none happened — "
        "the arm is not testing overload"
    )
    for r in reqs:
        assert r.finish_reason in ("eos", "length"), (r.uid, r.finish_reason)
        assert r.output == oracle_out[r.uid], (
            f"request {r.uid}: preempted-and-recomputed output diverged "
            "from the uncontended oracle"
        )
    log.info(
        "[check] oversubscribed: %d completed, %d preemptions, outputs "
        "oracle-exact; recompute cost %s steps (oracle %s)",
        int(s["completed"]), int(s["preempted"]), s["decode_steps"],
        oracle_stats["decode_steps"],
    )

    # --- arm 2: deadlines under the same contention ---------------------
    dl_reqs = [
        Request(uid=r.uid, prompt=list(r.prompt), max_new_tokens=max_new,
                deadline_s=0.001 if r.uid % 3 == 2 else 60.0)
        for r in oracle_reqs
    ]
    time.sleep(0.005)  # let the tight deadlines lapse before the first step
    _, dl_stats = _drive(cfg, params, over_conf, dl_reqs)
    assert dl_stats["timed_out"] > 0, "tight deadlines must shed something"
    for r in dl_reqs:
        if r.finish_reason in ("eos", "length"):
            assert r.output == oracle_out[r.uid], r.uid
        else:
            assert r.finish_reason == "timeout", (r.uid, r.finish_reason)
    log.info(
        "[check] deadline: %d timed out, %d completed oracle-exact",
        int(dl_stats["timed_out"]), int(dl_stats["completed"]),
    )

    # --- arm 3: bounded queue sheds the burst ---------------------------
    shed_conf = over_conf.replace(max_queue=2)
    burst = [Request(uid=r.uid, prompt=list(r.prompt), max_new_tokens=max_new)
             for r in oracle_reqs]
    _, shed_stats = _drive(cfg, params, shed_conf, burst)
    assert shed_stats["shed"] > 0, "burst must overflow the bounded queue"
    for r in burst:
        if r.finish_reason == "shed":
            assert r.output == []  # never took a lane
        else:
            assert r.output == oracle_out[r.uid], r.uid
    log.info(
        "[check] shed: %d rejected typed, %d admitted all completed",
        int(shed_stats["shed"]), int(shed_stats["completed"]),
    )

    log.info(
        "contended decode %.1f tok/s (oracle %.1f) | step p50/p95 "
        "%.1f/%.1f ms | wall %.1fs", s["decode_tok_per_s"],
        oracle_stats["decode_tok_per_s"], s["step_p50_ms"],
        s["step_p95_ms"], s["wall_s"],
    )
    path = save_bench_json(
        "serving_overload",
        metrics={
            # headline oversubscribed arm (oracle_exact records the
            # in-process bit-exactness assertion for artifact consumers)
            "oracle_exact": 1.0,
            "preempted": s["preempted"],
            "completed": s["completed"],
            "decode_tok_per_s": s["decode_tok_per_s"],
            "decode_steps": float(s["decode_steps"]),
            "oracle_decode_steps": float(oracle_stats["decode_steps"]),
            "oracle_decode_tok_per_s": oracle_stats["decode_tok_per_s"],
            "recompute_step_overhead": (
                s["decode_steps"] / oracle_stats["decode_steps"]
                if oracle_stats["decode_steps"]
                else 0.0
            ),
            "kv_pool_peak_occupancy": s["kv_pool_peak_occupancy"],
            "prefix_hit_rate": s["prefix_hit_rate"],
            "mean_latency_s": s["mean_latency_s"],
            "ttft_p95_s": s["ttft_p95_s"],
            "itl_p95_s": s["itl_p95_s"],
            "step_p50_ms": s["step_p50_ms"],
            "step_p95_ms": s["step_p95_ms"],
            "step_stalled": s["step_stalled"],
            "wall_s": s["wall_s"],
            # deadline arm
            "deadline_timed_out": dl_stats["timed_out"],
            "deadline_completed": dl_stats["completed"],
            # shed arm
            "shed": shed_stats["shed"],
            "shed_completed": shed_stats["completed"],
        },
        meta={
            "arch": cfg.name,
            "admission": "optimistic",
            "n_pages": n_pages,
            "worst_case_pages": max_batch * worst,
            "page_size": page_size,
            "max_batch": max_batch,
            "max_len": max_len,
            "backend": jax.default_backend(),
            "quantized": not args.float_weights,
            "n_requests": n_req,
            "max_new": max_new,
            "quick": bool(args.quick),
        },
    )
    log.info("wrote %s", path)
    return s


if __name__ == "__main__":
    main()
