"""Serving throughput benchmark -> results/BENCH_serving.json.

    PYTHONPATH=src python -m benchmarks.serving_throughput [--quick]
        [--arch glm4-9b] [--matmul-mode dequant|w8a8] [--n-requests N]

Drives :class:`repro.serving.ServingEngine` on a smoke config with a
mixed-length request queue and reports the serving numbers the perf
trajectory tracks:

* **prefill tok/s** — prompt tokens through the chunked prefill path;
* **decode tok/s** — generated tokens through the batched decode step;
* **TTFT / ITL** (schema v5) — submit-to-first-token and inter-token
  latencies from the engine's per-token event stream, p50 + p95 — the same
  timestamps a ``generate()`` streaming client observes;
* **KV pool accounting** — peak page occupancy and prefix-cache hit rate of
  the paged KV cache (``serving/kv_cache.py``);
* **speculative decoding** (``BENCH_serving_spec.json``) — the
  self-speculation arm (``serving/spec_decode.py``: quantized w8a8 draft,
  serving-precision multi-token verify) reruns the same workload and reports
  acceptance rate, tokens/target-step, and decode tok/s vs the baseline —
  after asserting the committed streams are token-identical and rollback
  left the page pool exactly as the baseline did;
* **step scheduler** (``BENCH_serving_sched.json``, schema v7) — an
  oversubscribed mixed-prompt workload (two ~384-token prompts arriving
  while short requests decode, more shorts queued behind) through the
  chunked-prefill scheduler (``prefill_budget > 0``) vs the monolithic
  oracle: greedy token identity asserted, ``itl_p95 <= 2 x itl_p50``
  (one chunk bounds any decode stall), short-class ``ttft_p95`` strictly
  improved;
* **compile cache** — cold-vs-warm prefill/decode compile seconds through
  the JAX persistent compilation cache (``repro.launch.compile_cache``),
  reported in ``BENCH_serving``;
* **observability overhead** (schema v8) — the same workload rerun with
  the span ring + metrics registry live (``trace=True``), exporting
  ``results/TRACE_serving.json`` (Chrome trace, Perfetto-loadable),
  ``METRICS_serving.prom`` (Prometheus text) and ``METRICS_serving.jsonl``
  (registry snapshots); the ``obs_overhead_*`` fractions vs the untraced
  arm are gated at 5% absolute by ``tools/compare_bench.py``.

Engine knobs come from the auto-generated :class:`EngineConfig` flags
(``--matmul-kernel``/``--attn-kernel`` speak the shared ``KernelChoice``
vocabulary).

It also *asserts* the chunked-prefill compile story via the engine's trace
counters: O(1) jitted calls per request (the dead-``_prefill_cache`` era
cost O(prompt_len)), at most one compile per pow2 prompt bucket — and that
page exhaustion *queues* (backpressure) rather than crashes: a second pass
reruns the workload against a pool several times smaller than the fixed-slot
footprint and must still complete every request via page recycling.

CPU smoke numbers are not TPU numbers — the value is the trend across PRs
(the stable BENCH schema) and the O(1)-calls invariant, which is
machine-independent.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import smoke_config
from repro.core.apply import quantize_params
from repro.core.recipe import QuantRecipe
from repro.models import transformer as T
from repro.obs.log import add_log_level_arg, get_logger, setup_logging
from repro.obs.trace import validate_chrome_trace
from repro.serving import (
    EngineConfig,
    Request,
    ServingEngine,
    add_engine_config_args,
    engine_config_from_args,
    pages_needed,
)

from .common import save_bench_json

log = get_logger("bench.serving")


def run_engine(cfg, params, ecfg: EngineConfig, *, lengths, max_new):
    eng = ServingEngine(cfg, params, ecfg)
    rng = np.random.default_rng(0)
    for i, n in enumerate(lengths):
        eng.submit(
            Request(
                uid=i,
                prompt=rng.integers(0, cfg.vocab, n).tolist(),
                max_new_tokens=max_new,
            )
        )
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    assert len(done) == len(lengths), (len(done), len(lengths))
    s = eng.stats()
    s["wall_s"] = wall
    return eng, s


def check_backpressure(cfg, params, ecfg, *, lengths, max_new):
    """Page exhaustion must queue, never crash: rerun the workload against a
    pool sized for only ~2 concurrent requests (far below the fixed-slot
    footprint) and require every request to complete via page recycling."""
    zeros = {
        "backpressure_pool_tokens": 0.0,
        "backpressure_total_tokens": 0.0,
        "backpressure_peak_occupancy": 0.0,
    }
    if cfg.block not in ("dense", "moe"):
        log.info("[check] backpressure: skipped (unpaged %s engine)", cfg.block)
        return zeros  # schema v2: unpaged engines report zeros, not gaps
    page_size = 16
    need = [
        min(pages_needed(n + max_new, page_size), ecfg.max_len // page_size)
        for n in lengths
    ]
    n_pages = 2 * max(need) + 1  # ~2 requests resident; the rest queue
    eng, s = run_engine(
        cfg, params,
        ecfg.replace(page_size=page_size, n_pages=n_pages, attn_probe=False),
        lengths=lengths, max_new=max_new,
    )
    assert s["completed"] == len(lengths), s["completed"]
    assert s["kv_pages_peak"] <= s["kv_pages_capacity"], s
    total_tokens = sum(lengths) + max_new * len(lengths)
    pool_tokens = int(s["kv_pages_capacity"] * s["kv_page_size"])
    assert total_tokens > pool_tokens, "workload must oversubscribe the pool"
    log.info(
        "[check] backpressure: %s requests (%d prompt+decode tokens) "
        "through a %d-token pool; peak %.0f/%.0f pages",
        s["completed"], total_tokens, pool_tokens, s["kv_pages_peak"],
        s["kv_pages_capacity"],
    )
    return {
        "backpressure_pool_tokens": pool_tokens,
        "backpressure_total_tokens": total_tokens,
        "backpressure_peak_occupancy": s["kv_pool_peak_occupancy"],
    }


def run_spec_arm(cfg, params, base_eng, base_stats, ecfg, *, lengths, max_new,
                 spec_k, draft_layers):
    """Speculative-decoding arm: rerun the workload with the self-speculative
    engine (quantized draft, serving-precision verify) and report acceptance
    rate, tokens/target-step, and end-to-end decode throughput vs the
    non-speculative baseline.

    Asserts the subsystem's two contracts on the way: the committed token
    streams are identical to the baseline's, and rollback leaves the page
    pool exactly as the baseline left it (zero referenced pages).
    """
    if cfg.block not in ("dense", "moe") or spec_k <= 0:
        log.info("[check] spec-decode: skipped (%s engine / spec_k=0)", cfg.block)
        return None
    from repro.serving import SpecConfig

    spec = SpecConfig(k=spec_k, draft_layers=draft_layers or None)
    # Same kernel selection as the baseline arm: the output-identity
    # assertion below compares the two engines token for token.
    eng, s = run_engine(
        cfg, params, ecfg.replace(spec=spec, attn_probe=False),
        lengths=lengths, max_new=max_new,
    )
    base_out = {r.uid: r.output for r in base_eng.done}
    spec_out = {r.uid: r.output for r in eng.done}
    assert spec_out == base_out, "spec-decode broke greedy output identity"
    assert s["spec_acceptance_rate"] > 0, s
    # An accepted draft means some verify event committed >1 token, so the
    # per-target-step yield must be strictly above the plain-decode 1.0.
    assert s["spec_tokens_per_target_step"] > 1.0, s
    assert s["kv_pages_in_use"] == base_stats["kv_pages_in_use"] == 0.0, (
        "rollback must leave pool occupancy identical to the baseline"
    )
    log.info(
        "[check] spec-decode: outputs identical; acceptance %.0f%%, "
        "%.2f tokens/target-step (%.0f target steps vs %.0f baseline)",
        s["spec_acceptance_rate"] * 100, s["spec_tokens_per_target_step"],
        s["decode_steps"], base_stats["decode_steps"],
    )
    return {
        "spec_k": float(spec_k),
        "spec_rounds": s["spec_rounds"],
        "spec_acceptance_rate": s["spec_acceptance_rate"],
        "spec_tokens_per_target_step": s["spec_tokens_per_target_step"],
        "spec_decode_tok_per_s": s["decode_tok_per_s"],
        "baseline_decode_tok_per_s": base_stats["decode_tok_per_s"],
        "spec_decode_steps": float(s["decode_steps"]),
        "baseline_decode_steps": float(base_stats["decode_steps"]),
        "spec_draft_time_s": s["spec_draft_time_s"],
        "spec_verify_time_s": s["spec_verify_time_s"],
        "spec_compile_s": s["spec_compile_s"],
        "wall_s": s["wall_s"],
        "baseline_wall_s": base_stats["wall_s"],
    }


def run_sched_arm(cfg, params, ecfg, *, quick, seed):
    """Continuous-batching scheduler arm (schema v7): the head-of-line
    pathology reproduced and fixed. Three short requests start decoding;
    two ~384-token prompts then arrive mid-stream with more shorts behind
    them. The monolithic oracle (``prefill_budget=0``) stalls every live
    decode lane for a whole long prefill and makes the trailing shorts
    wait behind both; the chunked step scheduler (sjf, ``prefill_budget``
    tokens/step) bounds any stall to one chunk.

    Asserts the PR-7 contracts:

    * **token identity** — every request's greedy output under the
      interleaved schedule equals the oracle's, token for token (both
      passes, all 8 requests x 2);
    * **decode tail** — ``itl_p95 <= 2 * itl_p50`` (+ a small absolute
      floor for CPU timer noise); the oracle's tail is a whole long
      prefill;
    * **budget** — no step ran more than ``prefill_budget`` prefill
      tokens;
    * **ttft tail (interactive class)** — ``ttft_p95`` over the *short*
      requests strictly below the oracle's. The short class is what
      head-of-line blocking punishes; the longs' own TTFT is the price
      sjf + chunking deliberately pays, so overall ``ttft_p95`` (which a
      2-longs-in-8 population pins to a long) is reported but not gated.

    The workload geometry is fixed (max_batch=4, max_len=512, page_size
    16) regardless of the CLI engine flags: the contracts above are about
    the scheduler, not the flag surface. Warmup pass and measured pass
    share the same ``lengths`` list, so every chunk-jit key the measured
    pass can hit is compiled by the warmup pass by construction.
    """
    if cfg.block not in ("dense", "moe"):
        log.info("[check] sched arm: skipped (replay-prefill %s)", cfg.block)
        return None
    rng = np.random.default_rng(seed + 7)
    n_long, n_short = (2, 6) if quick else (2, 8)
    lengths = []
    for i in range(max(n_long, n_short)):  # interleave long into the shorts
        if i < n_long:
            lengths.append(384 + int(rng.integers(0, 16)))
        if i < n_short:
            # One pow2 bucket (8): the measured pass must not hit a fresh
            # prefill compile the warmup pass didn't.
            lengths.append(int(rng.integers(4, 9)))
    max_new = 6 if quick else 12
    budget, chunk = 32, 16
    geom = dict(max_batch=4, max_len=512, page_size=16, n_pages=None,
                attn_probe=False)
    base_cfg = ecfg.replace(prefill_budget=0, **geom)
    sched_cfg = ecfg.replace(
        prefill_budget=budget, chunk_size=chunk, sched_policy="sjf", **geom,
    )

    # Two passes per engine: pass 1 warms every jit bucket (compile stalls
    # would otherwise dominate the latency tail on CPU), pass 2 is the
    # measurement — same lengths, *different* tokens (identical prompts
    # would prefix-cache-hit and serve no prefill work at all). Output
    # identity is asserted on both passes.
    def two_pass(arm_cfg):
        eng = ServingEngine(cfg, params, arm_cfg)
        # Chunked-vs-monolithic identity is empirical, not bitwise (see
        # docs/serving.md): the random-weight smoke model has argmax
        # knife-edges where fp accumulation-order noise flips a token.
        # The prompt seed is pinned to a region where both passes match
        # the oracle token for token (same convention as the overload
        # bench / test_overload seed pinning).
        prng = np.random.default_rng(seed + 12)
        for p in range(2):
            reqs = [Request(
                uid=100 * p + i,
                prompt=prng.integers(0, cfg.vocab, n).tolist(),
                max_new_tokens=max_new,
            ) for i, n in enumerate(lengths)]
            shorts = [r for r in reqs if len(r.prompt) < 64]
            longs = [r for r in reqs if len(r.prompt) >= 64]
            # Staggered arrivals: the first shorts must already be
            # decoding when the longs land, or the oracle's monolithic
            # prefill has nothing to stall and the pathology vanishes.
            for r in shorts[:3]:
                eng.submit(r)
            for _ in range(2):
                eng.step()
            for r in longs + shorts[3:]:
                eng.submit(r)
            t0 = time.perf_counter()
            eng.run()
            wall = time.perf_counter() - t0
        meas = [r for r in eng.done if r.uid >= 100]
        assert len(meas) == len(lengths), (len(meas), len(lengths))
        ttft = [r.t_first_token - r.t_submit for r in meas]
        short_ttft = [r.t_first_token - r.t_submit for r in meas
                      if len(r.prompt) < 64]
        itl = [b - a for r in meas
               for a, b in zip(r.t_tokens[:-1], r.t_tokens[1:])]
        out = {r.uid: r.output for r in eng.done}
        pct = lambda v, q: float(np.percentile(np.asarray(v), q))
        return eng, out, {
            "ttft_p50_s": pct(ttft, 50), "ttft_p95_s": pct(ttft, 95),
            "ttft_p95_short_s": pct(short_ttft, 95),
            "itl_p50_s": pct(itl, 50), "itl_p95_s": pct(itl, 95),
            "wall_s": wall,
        }

    base_eng, base_out, base = two_pass(base_cfg)
    sched_eng, sched_out, lat = two_pass(sched_cfg)
    sched = sched_eng.stats()
    assert sched_out == base_out, (
        "chunked-prefill interleave broke greedy output identity"
    )
    assert sched["sched_peak_step_prefill_tokens"] <= budget, sched
    assert sched["sched_chunks"] > 0, sched
    itl_bound = 2.0 * lat["itl_p50_s"] + 0.05
    assert lat["itl_p95_s"] <= itl_bound, (
        f"decode tail past the chunk bound: itl p95 {lat['itl_p95_s']:.3f}s"
        f" > {itl_bound:.3f}s (p50 {lat['itl_p50_s']:.3f}s)"
    )
    assert lat["ttft_p95_short_s"] < base["ttft_p95_short_s"], (
        f"scheduler must improve the short-class TTFT tail: "
        f"{lat['ttft_p95_short_s']:.3f}s vs oracle "
        f"{base['ttft_p95_short_s']:.3f}s"
    )
    base_ratio = base["itl_p95_s"] / max(base["itl_p50_s"], 1e-9)
    sched_ratio = lat["itl_p95_s"] / max(lat["itl_p50_s"], 1e-9)
    log.info(
        "[check] sched arm: outputs identical | itl p95/p50 %.1fx "
        "(oracle %.1fx) | short ttft p95 %.0f ms (oracle %.0f ms) | "
        "peak step prefill %.0f/%d tok",
        sched_ratio, base_ratio, lat["ttft_p95_short_s"] * 1e3,
        base["ttft_p95_short_s"] * 1e3,
        sched["sched_peak_step_prefill_tokens"], budget,
    )
    return {
        "prefill_budget": float(budget),
        "chunk_size": float(chunk),
        "n_requests": float(len(lengths)),
        "itl_p50_s": lat["itl_p50_s"],
        "itl_p95_s": lat["itl_p95_s"],
        "itl_tail_ratio": sched_ratio,
        "baseline_itl_p50_s": base["itl_p50_s"],
        "baseline_itl_p95_s": base["itl_p95_s"],
        "baseline_itl_tail_ratio": base_ratio,
        "ttft_p50_s": lat["ttft_p50_s"],
        "ttft_p95_s": lat["ttft_p95_s"],
        "ttft_p95_short_s": lat["ttft_p95_short_s"],
        "baseline_ttft_p50_s": base["ttft_p50_s"],
        "baseline_ttft_p95_s": base["ttft_p95_s"],
        "baseline_ttft_p95_short_s": base["ttft_p95_short_s"],
        "queue_wait_p50_s": sched["queue_wait_p50_s"],
        "queue_wait_p95_s": sched["queue_wait_p95_s"],
        "sched_chunks": sched["sched_chunks"],
        "sched_budget_limited_steps": sched["sched_budget_limited_steps"],
        "sched_aging_promotions": sched["sched_aging_promotions"],
        "sched_peak_step_prefill_tokens":
            sched["sched_peak_step_prefill_tokens"],
        "oracle_exact": 1.0,
        "decode_tok_per_s": sched["decode_tok_per_s"],
        "baseline_decode_tok_per_s": base_eng.stats()["decode_tok_per_s"],
        "wall_s": lat["wall_s"],
        "baseline_wall_s": base["wall_s"],
    }


def run_compile_cache_arm(cfg, params, ecfg, *, lengths, max_new):
    """Cold-vs-warm compile seconds through the JAX persistent compilation
    cache: the warm engine re-traces its jits (fresh python wrappers) but
    deserializes the executables the cold engine persisted, so its compile
    seconds collapse to trace time. The arm owns a fixed subdirectory of the
    cache and empties it first, so its cold engine starts cold; the process
    keeps that directory afterwards."""
    import shutil

    from repro.launch.compile_cache import cache_root, enable_compile_cache

    shutil.rmtree(cache_root() / "compile_cache_arm", ignore_errors=True)
    cache_dir = enable_compile_cache("compile_cache_arm")
    arm = ecfg.replace(attn_probe=False)
    _, cold = run_engine(cfg, params, arm, lengths=lengths, max_new=max_new)
    _, warm = run_engine(cfg, params, arm, lengths=lengths, max_new=max_new)
    log.info(
        "[check] compile cache: prefill compile %.2fs cold -> %.2fs warm | "
        "decode compile %.2fs cold -> %.2fs warm (%s)",
        cold["prefill_compile_s"], warm["prefill_compile_s"],
        cold["decode_compile_s"], warm["decode_compile_s"], cache_dir,
    )
    return {
        "compile_cache_cold_prefill_s": cold["prefill_compile_s"],
        "compile_cache_warm_prefill_s": warm["prefill_compile_s"],
        "compile_cache_cold_decode_s": cold["decode_compile_s"],
        "compile_cache_warm_decode_s": warm["decode_compile_s"],
    }


def run_obs_arm(cfg, params, ecfg, *, lengths, max_new):
    """Observability-overhead arm (schema v8): run the workload with the
    span ring live against a *paired* untraced reference and report the
    overhead fractions on the warm-path numbers. Exports the span ring as
    a validated Chrome trace plus the Prometheus exposition and a registry
    snapshot into ``results/``.

    The pairing matters: the main baseline arm runs cold at process start
    while this arm runs last, after five other arms have churned the
    process (compile floods, allocator state, CPU thermal/frequency
    drift) — compared against that arm's stats the measured "overhead"
    is dominated by run-order bias, not tracing. So both sides of the
    fraction are measured here, as adjacent (ref, traced) pairs, and the
    reported overhead is the MINIMUM over pairs. That estimator is a
    deliberate tripwire, not an average: per-run wall-clock noise on a
    loaded CPU box is ~10-15% — symmetric, far above the microseconds
    tracing actually costs — so any mean-like estimate flakes against an
    absolute 5% gate. A *real* regression (a sync, an eager hop, an
    O(events) scan on the hot path) slows every traced run and survives
    the min; symmetric noise shows the truth in at least one pair with
    probability ~1 - p^N.

    The quant-drift monitor stays OFF here: it runs *eager* sampled
    forwards, orders of magnitude slower than the jitted step — its cost
    is bounded by ``drift_every``, not by this gate (its behavior is
    validated functionally in tests/test_obs.py)."""
    ref_cfg = ecfg.replace(attn_probe=False)
    obs_cfg = ref_cfg.replace(trace=True)
    # A --quick decode phase is ~6 steps of ~1ms — far below CPU timer
    # jitter. Stretch the decode phase (identically on both sides, so the
    # fraction stays apples-to-apples) to get a measurable denominator.
    max_new = max(max_new, 24)
    eng = None
    pairs = []
    for _ in range(4):
        _, ref = run_engine(cfg, params, ref_cfg, lengths=lengths,
                            max_new=max_new)
        eng, obs = run_engine(cfg, params, obs_cfg, lengths=lengths,
                              max_new=max_new)
        pairs.append((ref, obs))
    s = pairs[-1][1]  # the last traced run backs the exports/counters
    doc = eng.trace.chrome_trace()
    err = validate_chrome_trace(doc)
    assert err is None, f"obs arm produced an invalid Chrome trace: {err}"
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    eng.trace.export(os.path.join(d, "TRACE_serving.json"))
    with open(os.path.join(d, "METRICS_serving.prom"), "w") as f:
        f.write(eng.metrics_text())
    with open(os.path.join(d, "METRICS_serving.jsonl"), "w") as f:
        f.write(json.dumps({"step": int(s["decode_steps"]),
                            "time": time.time(),
                            "metrics": eng.metrics_snapshot()}) + "\n")

    def tput_loss(base, obs):
        """Fraction of baseline throughput lost with tracing on."""
        return (base - obs) / base if base > 0 else 0.0

    def lat_gain(base, obs):
        """Fractional latency increase with tracing on."""
        return (obs - base) / base if base > 0 else 0.0

    metrics = {
        # positive = the traced arm was slower / higher-latency than its
        # adjacent untraced reference in EVERY pair (min-over-pairs)
        "obs_overhead_decode_frac": min(
            tput_loss(r["decode_tok_per_s"], o["decode_tok_per_s"])
            for r, o in pairs),
        "obs_overhead_prefill_frac": min(
            tput_loss(r["prefill_tok_per_s"], o["prefill_tok_per_s"])
            for r, o in pairs),
        "obs_overhead_itl_p50_frac": min(
            lat_gain(r["itl_p50_s"], o["itl_p50_s"]) for r, o in pairs),
        "obs_trace_events": s["trace_events"],
        "obs_trace_dropped": s["trace_dropped"],
    }
    log.info(
        "[check] obs arm: trace valid (%.0f events, %.0f dropped) | "
        "overhead decode %+.1f%% prefill %+.1f%% itl_p50 %+.1f%%",
        s["trace_events"], s["trace_dropped"],
        100 * metrics["obs_overhead_decode_frac"],
        100 * metrics["obs_overhead_prefill_frac"],
        100 * metrics["obs_overhead_itl_p50_frac"],
    )
    return metrics


def check_o1_prefill(eng, stats, lengths) -> None:
    """The acceptance invariant: chunked prefill is O(1) jitted calls per
    request for attention archs (SSM/hybrid archs replay by design)."""
    cfg = eng.cfg
    if cfg.block in ("dense", "moe"):
        assert stats["prefill_calls_per_request"] == 1.0, stats
        # Derive the bucket set from the engine's own policy, not a re-
        # implementation of it.
        buckets = {eng._prefill_bucket(int(n)) for n in lengths}
        assert stats["prefill_traces"] <= len(buckets), (stats, buckets)
        log.info(
            "[check] chunked prefill O(1): %s calls / %s requests, "
            "%s bucket compiles", stats["prefill_calls"],
            stats["prefill_requests"], stats["prefill_traces"],
        )
    else:
        log.info(
            "[check] replay fallback (%s): %s calls for %d prompt tokens",
            cfg.block, stats["prefill_calls"], sum(lengths),
        )


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--n-requests", type=int, default=0, help="0 = preset")
    ap.add_argument("--max-new", type=int, default=0, help="0 = preset")
    ap.add_argument("--float-weights", action="store_true",
                    help="skip PTQ, serve the float tree")
    ap.add_argument("--spec-arm-k", type=int, default=3,
                    help="speculative-decoding arm draft window (0 = off)")
    ap.add_argument("--spec-arm-draft-layers", type=int, default=0,
                    help="truncate the spec arm's drafter to L layers (0 = all)")
    ap.add_argument("--ocs-ratio", type=float, default=0.02)
    ap.add_argument("--seed", type=int, default=0)
    add_log_level_arg(ap)
    # The bench manages speculation (its own --spec-arm-* flags drive the
    # spec arm), the probe (always on for attention archs), and the obs arm
    # (which flips `trace` itself): those fields get no flags here rather
    # than flags that would be silently overridden.
    add_engine_config_args(ap, defaults=EngineConfig(max_batch=4, max_len=128),
                           skip=("spec", "attn_probe", "trace"))
    args = ap.parse_args(argv)
    enable_compile_cache()
    setup_logging(args.log_level)

    n_req = args.n_requests or (6 if args.quick else 16)
    max_new = args.max_new or (4 if args.quick else 12)
    cfg = smoke_config(args.arch)
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
    max_len = args.max_len
    lengths = [int(rng.integers(3, min(48, max_len // 2))) for _ in range(n_req)]
    log.info(
        "arch=%s mode=%s requests=%d lengths=%s",
        cfg.name, args.matmul_mode, n_req, lengths,
    )
    ecfg = engine_config_from_args(
        args, attn_probe=cfg.block in ("dense", "moe")
    )
    eng, stats = run_engine(cfg, params, ecfg, lengths=lengths, max_new=max_new)
    check_o1_prefill(eng, stats, lengths)
    spec_metrics = run_spec_arm(
        cfg, params, eng, stats, ecfg, lengths=lengths, max_new=max_new,
        spec_k=args.spec_arm_k, draft_layers=args.spec_arm_draft_layers,
    )
    bp_metrics = check_backpressure(
        cfg, params, ecfg, lengths=lengths, max_new=max_new
    )
    cc_metrics = run_compile_cache_arm(
        cfg, params, ecfg, lengths=lengths, max_new=max_new
    )
    sched_metrics = run_sched_arm(cfg, params, ecfg, quick=args.quick,
                                  seed=args.seed)
    obs_metrics = run_obs_arm(
        cfg, params, ecfg, lengths=lengths, max_new=max_new
    )

    log.info(
        "prefill %.1f tok/s | decode %.1f tok/s | ttft %.0f ms | "
        "wall %.1f s", stats["prefill_tok_per_s"],
        stats["decode_tok_per_s"], stats["mean_ttft_s"] * 1e3,
        stats["wall_s"],
    )
    log.info(
        "latency: ttft p50/p95 %.0f/%.0f ms | itl p50/p95 %.1f/%.1f ms",
        stats["ttft_p50_s"] * 1e3, stats["ttft_p95_s"] * 1e3,
        stats["itl_p50_s"] * 1e3, stats["itl_p95_s"] * 1e3,
    )
    if stats["kv_page_size"]:
        log.info(
            "kv pool: peak %.0f/%.0f pages (%.0f%%) | prefix hit rate "
            "%.0f%%", stats["kv_pages_peak"], stats["kv_pages_capacity"],
            stats["kv_pool_peak_occupancy"] * 100,
            stats["prefix_hit_rate"] * 100,
        )
        log.info(
            "decode attention: kernel=%s | probed step %.2f ms/layer",
            stats["attn_kernel"], stats["attn_step_ms"],
        )
    path = save_bench_json(
        "serving",
        metrics={
            "prefill_tok_per_s": stats["prefill_tok_per_s"],
            "decode_tok_per_s": stats["decode_tok_per_s"],
            "mean_ttft_s": stats["mean_ttft_s"],
            "mean_latency_s": stats["mean_latency_s"],
            # TTFT/ITL percentiles from the token event stream (schema v5)
            "ttft_p50_s": stats["ttft_p50_s"],
            "ttft_p95_s": stats["ttft_p95_s"],
            "itl_p50_s": stats["itl_p50_s"],
            "itl_p95_s": stats["itl_p95_s"],
            "prefill_compile_s": stats["prefill_compile_s"],
            "decode_compile_s": stats["decode_compile_s"],
            "prefill_calls_per_request": stats["prefill_calls_per_request"],
            "prefill_traces": stats["prefill_traces"],
            "decode_traces": stats["decode_traces"],
            "decoded_tokens": stats["decoded_tokens"],
            "prefill_tokens": stats["prefill_tokens"],
            "wall_s": stats["wall_s"],
            # paged KV-pool accounting (schema v2; zeros on unpaged engines)
            "kv_page_size": stats["kv_page_size"],
            "kv_pages_capacity": stats["kv_pages_capacity"],
            "kv_pages_peak": stats["kv_pages_peak"],
            "kv_pool_peak_occupancy": stats["kv_pool_peak_occupancy"],
            "prefix_hit_rate": stats["prefix_hit_rate"],
            "prefix_hit_pages": stats["prefix_hit_pages"],
            # decode-attention path accounting (schema v4)
            "attn_step_ms": stats["attn_step_ms"],
            # overload counters + watchdog step-time percentiles (schema v6;
            # all-zero on this uncontended arm — the oversubscribed numbers
            # live in BENCH_serving_overload.json)
            "preempted": stats["preempted"],
            "shed": stats["shed"],
            "timed_out": stats["timed_out"],
            "errors": stats["errors"],
            "kernel_fallbacks": stats["kernel_fallbacks"],
            "step_p50_ms": stats["step_p50_ms"],
            "step_p95_ms": stats["step_p95_ms"],
            "step_stalled": stats["step_stalled"],
            # scheduler + queue-wait accounting (schema v7; budget 0 on this
            # arm — the chunked numbers live in BENCH_serving_sched.json)
            "queue_wait_p50_s": stats["queue_wait_p50_s"],
            "queue_wait_p95_s": stats["queue_wait_p95_s"],
            "sched_prefill_budget": stats["sched_prefill_budget"],
            "sched_chunks": stats["sched_chunks"],
            "sched_budget_limited_steps": stats["sched_budget_limited_steps"],
            "sched_aging_promotions": stats["sched_aging_promotions"],
            "sched_peak_step_prefill_tokens":
                stats["sched_peak_step_prefill_tokens"],
            **cc_metrics,
            **bp_metrics,
            # tracing+metrics overhead arm (schema v8; compare_bench gates
            # the obs_overhead_* fractions at 5% absolute)
            **obs_metrics,
        },
        meta={
            "arch": cfg.name,
            "matmul_mode": ecfg.matmul_mode,
            "matmul_kernel": stats["matmul_kernel"],
            "attn_kernel": stats["attn_kernel"],
            "attn_kernel_cfg": ecfg.kernels.attn.value,
            "backend": jax.default_backend(),
            "quantized": not args.float_weights,
            "n_requests": n_req,
            "max_new": max_new,
            "max_batch": ecfg.max_batch,
            "max_len": ecfg.max_len,
            "quick": bool(args.quick),
        },
    )
    log.info("wrote %s", path)
    if spec_metrics is not None:
        log.info(
            "spec-decode: acceptance %.0f%% | %.2f tok/target-step | "
            "decode %.1f tok/s (baseline %.1f)",
            spec_metrics["spec_acceptance_rate"] * 100,
            spec_metrics["spec_tokens_per_target_step"],
            spec_metrics["spec_decode_tok_per_s"],
            spec_metrics["baseline_decode_tok_per_s"],
        )
        spath = save_bench_json(
            "serving_spec",
            metrics=spec_metrics,
            meta={
                "arch": cfg.name,
                "matmul_mode": ecfg.matmul_mode,
                "draft_mode": "w8a8",
                "draft_layers": args.spec_arm_draft_layers,
                "backend": jax.default_backend(),
                "quantized": not args.float_weights,
                "n_requests": n_req,
                "max_new": max_new,
                "quick": bool(args.quick),
            },
        )
        log.info("wrote %s", spath)
    if sched_metrics is not None:
        gpath = save_bench_json(
            "serving_sched",
            metrics=sched_metrics,
            meta={
                "arch": cfg.name,
                "matmul_mode": ecfg.matmul_mode,
                "sched_policy": "sjf",
                "backend": jax.default_backend(),
                "quantized": not args.float_weights,
                "quick": bool(args.quick),
            },
        )
        log.info("wrote %s", gpath)
    return stats


if __name__ == "__main__":
    main()
