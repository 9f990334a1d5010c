"""Replica-router chaos benchmark -> results/BENCH_serving_chaos.json.

    PYTHONPATH=src python -m benchmarks.serving_chaos [--quick]
        [--arch glm4-9b] [--n-requests N] [--replicas N]

The fault-tolerance arm of the serving trajectory (ISSUE 9, ROADMAP open
item #1): drive a :class:`repro.serving.Router` over N replicas through
scripted :class:`repro.serving.FaultPlan` failures and hold the recovery
contracts that make replication worth having. Five sub-arms:

* **oracle** — every request through ONE uncontended engine: the
  token-identity reference every other arm is compared against;
* **kill** — the headline arm. A replica is killed mid-decode; its
  in-flight requests (committed tokens intact) must migrate and finish on
  the survivors with ``lost == 0`` and every greedy output **token-exact
  to the oracle** (the ``_resume_paged`` replay contract, cross-replica);
* **nan** — a scripted nonfinite fault poisons one request on one
  replica; with a hair-trigger breaker the replica must degrade, the
  poisoned request errors typed, and everything else completes exact;
* **stall** — the replica's ``step`` sleeps for a few calls: the
  router-side watchdog must degrade it to draining and then *heal* it
  once the stall passes, with zero effect on outputs;
* **retry** — a burst against replicas with ``max_queue=1``: overload
  sheds convert to informed backoff retries and every request completes.

Reported metrics (schema v9): migrated / lost / oracle_exact for the kill
arm (CI gates these absolutely), breaker transitions for nan/stall, retry
counters, and migrate-latency percentiles. CPU smoke numbers are not TPU
numbers — the value is the recovery invariants, which are
machine-independent.
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
    ChaosHarness,
    EngineConfig,
    FaultPlan,
    InjectNaN,
    KillReplica,
    ReplicaSet,
    Request,
    Router,
    RouterConfig,
    ServingEngine,
    StallSteps,
)

from .common import save_bench_json

log = get_logger("bench.chaos")


def _mk_requests(rng, vocab, lengths, max_new):
    return [
        Request(uid=i, prompt=rng.integers(0, vocab, n).tolist(),
                max_new_tokens=max_new)
        for i, n in enumerate(lengths)
    ]


def _clone(oracle_reqs, max_new):
    return [
        Request(uid=r.uid, prompt=list(r.prompt), max_new_tokens=max_new)
        for r in oracle_reqs
    ]


def _mk_router(cfg, params, econf, n, rconf):
    return Router(ReplicaSet.build(cfg, params, econf, n), rconf)


def _losses(reqs, oracle_out, *, allow=()):
    """Requests that did not come home: no terminal state, or a normal
    completion whose tokens diverge from the oracle. ``allow`` lists
    finish_reasons the arm expects for specific casualties."""
    lost = []
    for r in reqs:
        if r.finish_reason in ("eos", "length"):
            if r.output != oracle_out[r.uid]:
                lost.append((r.uid, "diverged"))
        elif r.finish_reason in allow:
            continue
        else:
            lost.append((r.uid, r.finish_reason))
    return lost


def _assert_drained(router, reqs):
    for r in reqs:
        assert r.t_done > 0.0, f"request {r.uid} never terminal (deadlock)"
    for rep in router.replicas:
        alloc = rep.engine.allocator
        assert alloc.in_use() + alloc.available() == alloc.capacity, (
            f"replica {rep.rid} leaked pages ({rep.state})"
        )


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--n-requests", type=int, default=0, help="0 = preset")
    ap.add_argument("--max-new", type=int, default=0, help="0 = preset")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--float-weights", action="store_true",
                    help="skip PTQ, serve the float tree")
    ap.add_argument("--ocs-ratio", type=float, default=0.02)
    ap.add_argument("--seed", type=int, default=0)
    add_log_level_arg(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    setup_logging(args.log_level)

    n_req = args.n_requests or (6 if args.quick else 10)
    max_new = args.max_new or (8 if args.quick else 16)
    cfg = smoke_config(args.arch)
    if cfg.block not in ("dense", "moe"):
        raise SystemExit(
            f"chaos bench needs a paged (dense/moe) arch, got {cfg.block}"
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
    lengths = [int(rng.integers(4, 24)) for _ in range(n_req)]
    econf = EngineConfig(max_batch=max_batch, max_len=max_len,
                         page_size=page_size)
    log.info("arch=%s replicas=%d requests=%d lengths=%s",
             cfg.name, args.replicas, n_req, lengths)

    # --- oracle: one uncontended engine, no faults ----------------------
    oracle_reqs = _mk_requests(rng, cfg.vocab, lengths, max_new)
    eng = ServingEngine(cfg, params, econf)
    for r in oracle_reqs:
        eng.submit(r)
    eng.run(max_steps=50_000)
    for r in oracle_reqs:
        assert r.finish_reason in ("eos", "length"), (r.uid, r.finish_reason)
    oracle_out = {r.uid: list(r.output) for r in oracle_reqs}

    # --- arm 1: kill a replica mid-decode (the headline) ----------------
    # round_robin so the doomed replica deterministically owns lanes;
    # step 4 lands after prefill, mid-decode, so harvested requests carry
    # committed tokens into the cross-replica resume.
    router = _mk_router(cfg, params, econf, args.replicas,
                        RouterConfig(placement="round_robin"))
    reqs = _clone(oracle_reqs, max_new)
    t0 = time.perf_counter()
    for r in reqs:
        router.submit(r)
    harness = ChaosHarness(router, FaultPlan((KillReplica(step=4, replica=0),)))
    harness.run()
    kill_wall = time.perf_counter() - t0
    _assert_drained(router, reqs)
    lost = _losses(reqs, oracle_out)
    kill_stats = router.stats()
    assert not lost, f"kill arm lost requests: {lost}"
    assert kill_stats["router_migrated"] > 0, (
        "kill fired before any in-flight work existed — the arm is not "
        "testing crash-and-migrate"
    )
    assert kill_stats["router_dead_replicas"] == 1.0, kill_stats
    log.info(
        "[check] kill: replica 0 dead at step 4, %d migrated, 0 lost, "
        "all %d outputs oracle-exact (migrate p50 %.1f ms)",
        int(kill_stats["router_migrated"]), n_req,
        kill_stats["router_migrate_p50_ms"],
    )

    # --- arm 2: nonfinite fault trips the breaker -----------------------
    # Hair-trigger breaker (degraded_after=1): the first quarantine on
    # replica 1 must open it. uid 1 sits on replica 1 under round_robin.
    router = _mk_router(
        cfg, params, econf, args.replicas,
        RouterConfig(placement="round_robin", degraded_after=1, dead_after=3),
    )
    reqs = _clone(oracle_reqs, max_new)
    for r in reqs:
        router.submit(r)
    harness = ChaosHarness(
        router, FaultPlan((InjectNaN(step=0, replica=1, uid=1,
                                     at_output_index=1),))
    )
    harness.run()
    _assert_drained(router, reqs)
    nan_stats = router.stats()
    poisoned = next(r for r in reqs if r.uid == 1)
    assert poisoned.finish_reason == "error", poisoned.finish_reason
    lost = _losses(reqs, oracle_out, allow=("error",))
    assert not lost, f"nan arm lost requests: {lost}"
    assert nan_stats["router_drained"] >= 1.0, nan_stats
    log.info(
        "[check] nan: poisoned uid 1 errored typed, breaker opened "
        "(%d drain transitions), %d bystanders oracle-exact",
        int(nan_stats["router_drained"]), n_req - 1,
    )

    # --- arm 3: stall -> draining -> heal -------------------------------
    # Warm the router first (jit compiles would otherwise dominate the
    # watchdog window), snapshot the breaker counter, then stall replica 0
    # hard enough that the router-side StepTimer must flag it.
    router = _mk_router(
        cfg, params, econf, args.replicas,
        RouterConfig(placement="round_robin", straggle_factor=3.0,
                     straggle_patience=2),
    )
    warm = _clone(oracle_reqs, max_new)
    for r in warm:
        router.submit(r)
    router.run(max_steps=50_000)
    assert not _losses(warm, oracle_out)
    drained_before = router.stats()["router_drained"]
    reqs = _clone(oracle_reqs, max_new)
    for r in reqs:
        router.submit(r)
    harness = ChaosHarness(
        router, FaultPlan((StallSteps(step=3, replica=0, steps=4,
                                      seconds=0.3),))
    )
    harness.run()
    _assert_drained(router, reqs)
    stall_stats = router.stats()
    stall_drains = stall_stats["router_drained"] - drained_before
    assert stall_drains >= 1.0, (
        f"stalled replica never degraded (drains {stall_drains})"
    )
    assert stall_stats["replica0_health"] == 1.0, (
        "stalled replica did not heal after the stall passed: "
        f"health {stall_stats['replica0_health']}"
    )
    lost = _losses(reqs, oracle_out)
    assert not lost, f"stall arm lost requests: {lost}"
    log.info(
        "[check] stall: replica 0 degraded (%d transitions) and healed, "
        "all outputs oracle-exact", int(stall_drains),
    )

    # --- arm 4: overload burst -> informed retries ----------------------
    # max_queue=1 per replica: most of the burst sheds at submit and must
    # come back through capped backoff (hint = step_p50 x queue depth).
    router = _mk_router(
        cfg, params, econf.replace(max_queue=1), args.replicas,
        RouterConfig(max_retries=8, backoff_base_s=0.05, backoff_cap_s=0.5),
    )
    reqs = _clone(oracle_reqs, max_new)
    for r in reqs:
        router.submit(r)
    router.run(max_steps=100_000)
    _assert_drained(router, reqs)
    retry_stats = router.stats()
    assert retry_stats["router_retried"] > 0, (
        "bounded queues never shed — the arm is not testing retry"
    )
    assert retry_stats["router_shed"] == 0.0, retry_stats
    lost = _losses(reqs, oracle_out)
    assert not lost, f"retry arm lost requests: {lost}"
    log.info(
        "[check] retry: %d backoff retries, 0 terminal sheds, all %d "
        "completed oracle-exact",
        int(retry_stats["router_retried"]), n_req,
    )

    path = save_bench_json(
        "serving_chaos",
        metrics={
            # headline kill arm (absolute CI gates: lost == 0,
            # oracle_exact == 1, migrated > 0)
            "oracle_exact": 1.0,
            "lost": 0.0,
            "migrated": kill_stats["router_migrated"],
            "kill_completed": float(n_req),
            "kill_placed": kill_stats["router_placed"],
            "kill_dead_replicas": kill_stats["router_dead_replicas"],
            "migrate_p50_ms": kill_stats["router_migrate_p50_ms"],
            "migrate_p95_ms": kill_stats["router_migrate_p95_ms"],
            "kill_wall_s": kill_wall,
            # nan arm: breaker + typed casualty
            "nan_drained": nan_stats["router_drained"],
            "nan_errors": 1.0,
            # stall arm: degrade + heal
            "stall_drained": stall_drains,
            "stall_healed": stall_stats["replica0_health"],
            # retry arm
            "retried": retry_stats["router_retried"],
            "retry_shed": retry_stats["router_shed"],
        },
        meta={
            "arch": cfg.name,
            "replicas": args.replicas,
            "placement": "round_robin",
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
    return kill_stats


if __name__ == "__main__":
    main()
