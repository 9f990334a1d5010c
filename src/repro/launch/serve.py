"""Serving launcher: checkpoint -> OCS PTQ -> batched quantized serving.

The deployment half of the paper's scenario. Loads a float checkpoint (or a
freshly initialized model), runs the offline PTQ pipeline (weight OCS +
clipping + integer quantization — zero training data needed, §3.4), then
serves batched requests through :class:`repro.serving.ServingEngine` with
the int8 parameter tree.

Engine flags are **auto-generated from the EngineConfig dataclass**
(:func:`repro.serving.add_engine_config_args`) — the CLI cannot drift from
the config surface: adding a field to ``EngineConfig`` adds the flag here.
``--temperature/--top-k/--top-p`` exercise the per-request
:class:`SamplingParams` lifecycle (greedy by default).

``--compare-float`` serves the same requests with the float weights and
reports the token-level agreement — the serving-side analogue of the
paper's accuracy tables.

Observability (PR 8): ``--trace-out`` exports the engine's span ring as a
Perfetto-loadable Chrome trace (requires ``--trace``), ``--metrics-out``
writes a Prometheus text exposition after the run, and ``--metrics-jsonl``
streams periodic registry snapshots (one JSON line every
``--metrics-every`` engine steps) while the engine drains. Progress goes
through :mod:`repro.obs.log` (``--log-level``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, list_archs, smoke_config
from repro.core.apply import quantize_params
from repro.core.recipe import QuantRecipe
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.weights import load_quantized
from repro.models import transformer as T
from repro.obs.log import add_log_level_arg, get_logger, setup_logging
from repro.optim import adamw_init
from repro.serving import (
    EngineConfig,
    KernelChoice,
    ReplicaSet,
    Request,
    Router,
    RouterConfig,
    SamplingParams,
    ServingEngine,
    add_engine_config_args,
    engine_config_from_args,
)

log = get_logger("launch.serve")

# Legacy --paged-attn vocabulary -> the shared KernelChoice vocabulary.
_PAGED_ATTN_ALIAS = {"auto": "auto", "on": "pallas", "off": "gather"}


def build_parser():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="deepseek-7b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--bits", type=int, default=8)
    # --kv-bits is auto-generated from EngineConfig.kv_bits below.
    ap.add_argument("--ocs-ratio", type=float, default=0.02)
    ap.add_argument("--clip", default="mse")
    ap.add_argument("--float-serve", action="store_true",
                    help="skip PTQ, serve float weights")
    ap.add_argument("--compare-float", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="request top-k restriction (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="request nucleus restriction (1 = off)")
    ap.add_argument("--paged-attn", default=None,
                    choices=sorted(_PAGED_ATTN_ALIAS),
                    help="DEPRECATED alias for --attn-kernel "
                         "(on = pallas, off = gather)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through N data-parallel engine replicas "
                         "behind the fault-tolerant router (1 = the plain "
                         "single-engine path)")
    ap.add_argument("--placement", default="least_loaded",
                    choices=["least_loaded", "round_robin"],
                    help="router placement policy (only with --replicas > 1)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default="",
                    help="export the span ring as Chrome trace JSON "
                         "(requires --trace)")
    ap.add_argument("--metrics-out", default="",
                    help="write Prometheus text exposition after the run")
    ap.add_argument("--metrics-jsonl", default="",
                    help="stream periodic registry snapshots (JSONL)")
    ap.add_argument("--metrics-every", type=int, default=50,
                    help="engine steps between --metrics-jsonl snapshots")
    add_log_level_arg(ap)
    # Engine flags, generated from the EngineConfig fields themselves.
    add_engine_config_args(ap, defaults=EngineConfig(max_batch=4, max_len=128))
    return ap


def _engine_config(args, cfg) -> EngineConfig:
    ecfg = engine_config_from_args(args)
    if args.paged_attn is not None:
        if args.attn_kernel != "auto":
            raise SystemExit(
                "serve.py: --paged-attn (deprecated) conflicts with an "
                "explicit --attn-kernel; drop --paged-attn"
            )
        warnings.warn(
            "--paged-attn is deprecated; use --attn-kernel "
            f"{_PAGED_ATTN_ALIAS[args.paged_attn]}",
            DeprecationWarning,
        )
        ecfg = ecfg.replace(
            kernels=dataclasses.replace(
                ecfg.kernels,
                attn=KernelChoice.coerce(_PAGED_ATTN_ALIAS[args.paged_attn]),
            )
        )
    if cfg.block in ("dense", "moe") and not ecfg.attn_probe:
        ecfg = ecfg.replace(attn_probe=True)  # probed attn time in the report
    return ecfg


def _make_requests(n, vocab, rng, max_new, sampling=None):
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, 12))
        prompt = rng.integers(0, vocab, plen).tolist()
        reqs.append(
            Request(uid=i, prompt=prompt, max_new_tokens=max_new,
                    sampling=sampling)
        )
    return reqs


# Additive per-replica counters the replicated report sums; point-in-time
# percentiles report the worst replica instead (summing a p95 is nonsense).
_SUM_STATS = (
    "completed", "cancelled", "decoded_tokens", "decode_steps", "preempted",
    "shed", "timed_out", "errors", "kernel_fallbacks", "prefill_tokens",
    "prefill_calls", "prefill_requests", "kv_pages_capacity",
    "kv_pages_in_use", "sched_chunks", "sched_budget_limited_steps",
    "sched_aging_promotions",
)
_MAX_STATS = (
    "ttft_p50_s", "ttft_p95_s", "itl_p50_s", "itl_p95_s", "mean_latency_s",
    "step_p50_ms", "step_p95_ms", "step_stalled", "queue_wait_p50_s",
    "queue_wait_p95_s", "kv_pool_peak_occupancy",
)


def serve_replicated(cfg, params, reqs, ecfg: EngineConfig, n: int,
                     placement: str):
    """Serve through the fault-tolerant router (`--replicas N`): stats are
    replica 0's view with additive counters summed (and percentiles taken
    from the worst replica) plus the router's ``router_*`` layer."""
    router = Router(ReplicaSet.build(cfg, params, ecfg, n),
                    RouterConfig(placement=placement))
    for r in reqs:
        router.submit(r)
    t0 = time.time()
    router.run(max_steps=100_000)
    wall = time.time() - t0
    per = [rep.engine.stats() for rep in router.replicas]
    s = dict(per[0])
    for key in _SUM_STATS:
        s[key] = sum(p[key] for p in per)
    for key in _MAX_STATS:
        s[key] = max(p[key] for p in per)
    s.update(router.stats())
    s["wall_s"] = round(wall, 2)
    s["tokens_per_s"] = round(s["decoded_tokens"] / max(wall, 1e-9), 1)
    return reqs, s, router


def serve_once(cfg, params, reqs, ecfg: EngineConfig, *,
               metrics_jsonl: str = "", metrics_every: int = 50):
    eng = ServingEngine(cfg, params, ecfg)
    for r in reqs:
        eng.submit(r)
    t0 = time.time()
    if metrics_jsonl:
        # Drive step-by-step so registry snapshots stream while serving
        # (eng.run() is the same loop without the snapshot hook).
        eng.start_profile()
        try:
            with open(metrics_jsonl, "w") as f:
                for _ in range(10_000):
                    busy = eng.step()
                    if eng.steps % max(metrics_every, 1) == 0:
                        f.write(json.dumps(
                            {"step": eng.steps, "time": time.time(),
                             "metrics": eng.metrics_snapshot()}) + "\n")
                    if not busy and not eng.queue:
                        break
                f.write(json.dumps(
                    {"step": eng.steps, "time": time.time(),
                     "metrics": eng.metrics_snapshot()}) + "\n")
        finally:
            eng.stop_profile()
        done = eng.done
    else:
        done = eng.run()
    wall = time.time() - t0
    s = eng.stats()
    s["wall_s"] = round(wall, 2)
    s["tokens_per_s"] = round(s["decoded_tokens"] / max(wall, 1e-9), 1)
    return done, s, eng


def main(argv=None):
    args = build_parser().parse_args(argv)
    setup_logging(args.log_level)
    if args.trace_out and not args.trace:
        raise SystemExit("serve.py: --trace-out requires --trace")
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = np.random.default_rng(args.seed)
    enable_compile_cache()

    recipe = QuantRecipe(
        w_bits=args.bits, w_clip=args.clip, ocs_ratio=args.ocs_ratio,
        per_channel=True, pad_to=128,
    )
    params = None
    t0 = time.time()
    if not args.smoke and not args.ckpt_dir:
        # Full width: the float tree never exists whole. Seeded slices are
        # quantized on the host one at a time; only int8 reaches the device.
        if args.float_serve or args.compare_float:
            raise SystemExit(
                "serve.py: --float-serve/--compare-float need the whole float "
                f"tree, which does not fit one device at {cfg.name}'s full "
                "width; use --smoke"
            )
        qparams = load_quantized(cfg, args.seed, recipe)
    else:
        params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
        if args.ckpt_dir:
            ckpt = CheckpointManager(args.ckpt_dir, async_write=False)
            (params, _opt), meta = ckpt.restore((params, adamw_init(params)))
            params = jax.tree.map(jnp.asarray, params)
            log.info("restored %s step %s", meta.get("arch"), ckpt.latest_step())
        qparams = params if args.float_serve else quantize_params(params, recipe)
    if not args.float_serve:
        get_logger("launch.ptq").info(
            "quantized in %.1fs (w%d, ocs r=%s, clip=%s)",
            time.time() - t0, args.bits, args.ocs_ratio, args.clip)

    ecfg = _engine_config(args, cfg)
    if args.float_serve and ecfg.matmul_mode != "dequant":
        ecfg = ecfg.replace(matmul_mode="dequant")
    sampling = None
    if args.temperature > 0:
        sampling = SamplingParams(
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
            seed=args.seed,
        )
    elif args.top_k or args.top_p < 1.0:
        # temperature == 0 is exact greedy; silently dropping the
        # restriction flags would masquerade as sampled decode.
        raise SystemExit(
            "serve.py: --top-k/--top-p only apply to sampled decode; "
            "set --temperature > 0"
        )
    reqs = _make_requests(args.n_requests, cfg.vocab, rng, args.max_new,
                          sampling=sampling)
    if args.replicas > 1:
        if args.trace_out or args.metrics_jsonl:
            raise SystemExit(
                "serve.py: --trace-out/--metrics-jsonl export one engine's "
                "telemetry; with --replicas > 1 use --metrics-out (router "
                "registry) instead"
            )
        done, stats, router = serve_replicated(
            cfg, qparams, reqs, ecfg, args.replicas, args.placement)
        eng = router.replicas[0].engine
    else:
        done, stats, eng = serve_once(
            cfg, qparams, reqs, ecfg,
            metrics_jsonl=args.metrics_jsonl,
            metrics_every=args.metrics_every,
        )
    log.info("%s", stats)
    reasons = {}
    for r in done:
        reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
    log.info(
        "finish reasons: %s",
        " ".join(f"{k}={v}" for k, v in sorted(reasons.items(),
                                               key=lambda kv: str(kv[0]))),
    )
    log.info(
        "latency: ttft p50 %.0f ms / p95 %.0f ms | itl p50 %.1f ms / "
        "p95 %.1f ms",
        stats["ttft_p50_s"] * 1e3, stats["ttft_p95_s"] * 1e3,
        stats["itl_p50_s"] * 1e3, stats["itl_p95_s"] * 1e3,
    )
    if stats.get("kv_page_size"):
        log.info(
            "paged attention: kernel=%s (cfg %s), probed attn step "
            "%.2f ms/layer",
            stats["attn_kernel"], ecfg.kernels.attn.value,
            stats["attn_step_ms"],
        )
    if ecfg.spec is not None:
        log.info(
            "spec-decode: acceptance %.1f%%, %.2f tokens/target-step over "
            "%.0f rounds (adaptive k -> %.0f)",
            stats["spec_acceptance_rate"] * 100.0,
            stats["spec_tokens_per_target_step"], stats["spec_rounds"],
            stats["spec_k"],
        )
    log.info(
        "overload: preempted %.0f | shed %.0f | timed out %.0f | errors "
        "%.0f | kernel fallbacks %.0f",
        stats["preempted"], stats["shed"], stats["timed_out"],
        stats["errors"], stats["kernel_fallbacks"],
    )
    log.info(
        "watchdog: step p50 %.1f ms / p95 %.1f ms%s",
        stats["step_p50_ms"], stats["step_p95_ms"],
        " | STALLED" if stats["step_stalled"] else "",
    )
    log.info(
        "queue wait: p50 %.0f ms / p95 %.0f ms",
        stats["queue_wait_p50_s"] * 1e3, stats["queue_wait_p95_s"] * 1e3,
    )
    if args.replicas > 1:
        log.info(
            "router: %d replicas (%d healthy) | placed %.0f | retried %.0f "
            "| migrated %.0f | drained %.0f | dead %.0f | migrate p50 "
            "%.1f ms",
            args.replicas, int(stats["router_healthy_replicas"]),
            stats["router_placed"], stats["router_retried"],
            stats["router_migrated"], stats["router_drained"],
            stats["router_dead_replicas"], stats["router_migrate_p50_ms"],
        )
    if stats.get("sched_prefill_budget"):
        log.info(
            "scheduler: %s | budget %.0f tok/step | chunks %.0f | "
            "budget-limited steps %.0f | aging promotions %.0f | "
            "peak step prefill %.0f tok",
            stats["sched_policy"], stats["sched_prefill_budget"],
            stats["sched_chunks"], stats["sched_budget_limited_steps"],
            stats["sched_aging_promotions"],
            stats["sched_peak_step_prefill_tokens"],
        )
    if stats.get("drift_enabled"):
        log.info(
            "quant drift: %.0f samples over %.0f sites | flagged %.0f | "
            "max live/calib ratio %.2f",
            stats["drift_samples"], stats["drift_sites"],
            stats["drift_flagged_sites"], stats["drift_max_ratio"],
        )
        for site, info in sorted(eng.drift_report().items()):
            if info["ratio"] > 1.0:
                log.warning(
                    "drift site %s: live rate %.2e vs calib %.2e "
                    "(ratio %.1f, clip %.3g)", site, info["live_rate"],
                    info["calib_rate"], info["ratio"], info["clip"],
                )
    if args.trace_out:
        eng.trace.export(args.trace_out)
        log.info(
            "trace: %d events (%d dropped) -> %s",
            len(eng.trace), eng.trace.dropped, args.trace_out,
        )
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            if args.replicas > 1:
                f.write(router.metrics_text())  # router_* / replica_health_*
            f.write(eng.metrics_text())
        log.info("metrics: Prometheus exposition -> %s", args.metrics_out)
    if args.metrics_jsonl:
        log.info("metrics: JSONL snapshots -> %s", args.metrics_jsonl)

    if args.compare_float and not args.float_serve:
        freqs = _make_requests(args.n_requests, cfg.vocab,
                               np.random.default_rng(args.seed), args.max_new,
                               sampling=sampling)
        fdone, _, _ = serve_once(cfg, params, freqs,
                                 ecfg.replace(matmul_mode="dequant", spec=None))
        by_uid = {r.uid: r.output for r in fdone}
        agree = total = 0
        for r in done:
            ref = by_uid.get(r.uid, [])
            for a, b in zip(r.output, ref):
                agree += int(a == b)
                total += 1
        log.info("int8-vs-float token agreement: %d/%d (%.1f%%)",
                 agree, total, 100.0 * agree / max(total, 1))
    return stats


if __name__ == "__main__":
    main()
