"""Continuous-batching step scheduler (ISSUE 7): chunked prefill / decode
interleave under a per-step token budget.

The acceptance bar: with ``prefill_budget > 0`` the engine splits every
prompt into chunks and interleaves them with live decode lanes, and the
greedy output stream is token-for-token identical to the uninterleaved
monolithic oracle — paged and unpaged, dense and MoE, speculation on and
off, and through a mid-prefill preemption-and-resume. ``prefill_budget=0``
(the default) must keep the legacy monolithic prefill path byte for byte.

Identity is empirical, not bitwise (docs/serving.md): chunked prefill
changes fp accumulation order, and the random-weight smoke models have
argmax knife-edges where that noise flips a token. Prompt seeds below are
pinned to regions where chunked == monolithic holds, the same convention
test_overload uses for its preemption-exactness seeds.
"""
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import smoke_config
from repro.models import transformer as T
from repro.serving import (
    EngineConfig,
    EngineOverloaded,
    Request,
    ServingEngine,
    SpecConfig,
)
from repro.serving.scheduler import StepScheduler


@pytest.fixture(scope="module")
def dense_setup():
    cfg = smoke_config("glm4-9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


_PARAM_CACHE = {}


def _setup(arch):
    if arch not in _PARAM_CACHE:
        cfg = smoke_config(arch)
        _PARAM_CACHE[arch] = (cfg, T.init_params(cfg, jax.random.PRNGKey(0)))
    return _PARAM_CACHE[arch]


def _serve(cfg, params, reqs, **conf):
    eng = ServingEngine(cfg, params, EngineConfig(**conf))
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, {r.uid: (r.finish_reason, list(r.output)) for r in reqs}


def _req(uid, n):
    return SimpleNamespace(uid=uid, prompt=[0] * n)


# ---------------------------------------------------------------------------
# Tentpole: chunked prefill is output-identical to the monolithic oracle


@pytest.mark.parametrize("arch", ["glm4-9b", "deepseek-moe-16b"])
@pytest.mark.parametrize("spec", [None, SpecConfig(k=3)])
def test_chunked_prefill_exactness_paged(arch, spec):
    """A 40-token prompt runs as 3 chunks interleaved with two short lanes;
    outputs must equal the monolithic oracle's, spec on and off. Prompt
    seeds are pinned off the smoke models' argmax knife-edges (see module
    docstring)."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(7 if arch == "glm4-9b" else 34)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (40, 7, 5)]

    def reqs():
        return [Request(uid=i, prompt=list(p), max_new_tokens=12)
                for i, p in enumerate(prompts)]

    conf = dict(max_batch=3, max_len=96, page_size=8, spec=spec)
    _, oracle = _serve(cfg, params, reqs(), **conf)
    eng, got = _serve(cfg, params, reqs(), prefill_budget=16, chunk_size=16,
                      sched_policy="sjf", **conf)
    assert got == oracle
    s = eng.stats()
    assert s["sched_chunks"] >= 3  # the long prompt alone takes 3 chunks
    assert s["sched_peak_step_prefill_tokens"] <= 16
    assert s["kv_pages_in_use"] == 0.0
    if spec is not None:
        # Speculation pauses while a lane is mid-prefill but must resume
        # once every lane is decoding.
        assert s["spec_rounds"] > 0


def test_chunked_prefill_exactness_unpaged(dense_setup):
    """The unpaged (scratch-cache) chunk path: same identity contract with
    ``paged=False``, where chunk_size need not align to page_size."""
    cfg, params = dense_setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (21, 6, 4)]

    def reqs():
        return [Request(uid=i, prompt=list(p), max_new_tokens=10)
                for i, p in enumerate(prompts)]

    conf = dict(max_batch=3, max_len=64, paged=False)
    _, oracle = _serve(cfg, params, reqs(), **conf)
    eng, got = _serve(cfg, params, reqs(), prefill_budget=12, chunk_size=6,
                      **conf)
    assert got == oracle
    assert eng.stats()["sched_chunks"] >= 4  # 21 tokens / 6-token chunks


def test_budget_zero_keeps_monolithic_prefill(dense_setup):
    """The default config never chunks: one prefill call per request and
    zero scheduler activity (the legacy path is byte-for-byte intact)."""
    cfg, params = dense_setup
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, n).tolist(),
                    max_new_tokens=4) for i, n in enumerate((20, 6))]
    eng, _ = _serve(cfg, params, reqs, max_batch=2, max_len=64)
    s = eng.stats()
    assert s["sched_chunks"] == 0.0
    assert s["sched_prefill_budget"] == 0.0
    assert s["prefill_calls_per_request"] == 1.0


def test_sched_counters_and_queue_wait_stats(dense_setup):
    """Stats schema v7: the sched_* counters and queue-wait percentiles are
    real measurements, not placeholder zeros."""
    cfg, params = dense_setup
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, n).tolist(),
                    max_new_tokens=4) for i, n in enumerate((20, 6, 5))]
    eng, _ = _serve(cfg, params, reqs, max_batch=2, max_len=64, page_size=8,
                    prefill_budget=8, chunk_size=8, sched_policy="sjf")
    s = eng.stats()
    assert s["sched_policy"] == "sjf"
    assert s["sched_prefill_budget"] == 8.0
    assert s["sched_chunks"] >= 3  # the 20-token prompt alone needs 3
    assert 0 < s["sched_peak_step_prefill_tokens"] <= 8
    assert s["queue_wait_p50_s"] >= 0.0
    assert s["queue_wait_p95_s"] >= s["queue_wait_p50_s"]


def test_mid_prefill_preemption_resumes_exactly(dense_setup):
    """A lane preempted halfway through its chunked prefill (optimistic
    admission, tiny pool) re-queues with zero output, resumes off its
    registered prompt pages, and still matches the uncontended monolithic
    oracle token for token."""
    cfg, params = dense_setup
    rng = np.random.default_rng(17)
    # The short fills page 1 exactly, so its 2-page optimistic grant runs
    # dry after 8 decode tokens (~step 9) — while the 88-token long is
    # still mid-prefill (11 chunks of 8). With zero free pages left, the
    # short's growth must evict the younger, half-prefilled long.
    short = rng.integers(0, cfg.vocab, 8).tolist()
    long = rng.integers(0, cfg.vocab, 88).tolist()

    def reqs():
        return [Request(uid=0, prompt=list(short), max_new_tokens=24),
                Request(uid=1, prompt=list(long), max_new_tokens=6)]

    _, oracle = _serve(cfg, params, reqs(), max_batch=2, max_len=96,
                       page_size=8)
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=96, page_size=8, n_pages=15,
        admission="optimistic", admission_headroom=1,
        prefill_budget=8, chunk_size=8, sched_policy="fifo"))
    rs = reqs()
    for r in rs:
        eng.submit(r)
    saw_mid_prefill_victim = False
    while eng.queue or any(s.req for s in eng.slots):
        eng.step()
        if eng.preempted and any(
                r.uid == 1 and not r.output for r in eng.queue):
            saw_mid_prefill_victim = True
    assert eng.preempted > 0
    assert saw_mid_prefill_victim, (
        "pool was meant to evict the long lane mid-prefill")
    got = {r.uid: (r.finish_reason, list(r.output)) for r in rs}
    assert got == oracle
    assert eng.stats()["kv_pages_in_use"] == 0.0


# ---------------------------------------------------------------------------
# Satellite: persistent compilation cache


def test_compile_cache_dir_populates(dense_setup, tmp_path, monkeypatch):
    """The entry points' compile-cache helper turns on the jax persistent
    compilation cache under JAX_COMPILATION_CACHE_DIR: a fresh directory
    gains entries after one request."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

    cfg, params = dense_setup
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        cache = enable_compile_cache("cc")
        assert cache == tmp_path / "cc"
        eng = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=32))
        eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=3))
        eng.run()
        assert cache.exists() and any(cache.iterdir()), (
            "persistent compilation cache wrote nothing")
    finally:
        compilation_cache.reset_cache()
        for k, v in saved.items():
            jax.config.update(k, v)


def test_compile_cache_placement(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing (JAX reads
    the variable itself); without it the cache is .jax_cache/ in the
    checkout, a fixed path."""
    from pathlib import Path

    from repro.launch import compile_cache as cc

    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(cc.ENV_VAR)
    root = Path(__file__).resolve().parents[1]
    assert cc.cache_root() == root / ".jax_cache"


# ---------------------------------------------------------------------------
# Scheduler policy unit tests (pure bookkeeping, no engine)


def test_order_queue_fifo_matches_arrival_order():
    sched = StepScheduler(policy="fifo", aging_steps=4)
    q = [_req(i, n) for i, n in enumerate((9, 1, 5))]
    assert sched.order_queue(q, 0, lambda r: False) == q
    # Resumes outrank policy order regardless of policy.
    assert sched.order_queue(q, 0, lambda r: r.uid == 2)[0] is q[2]


def test_order_queue_sjf_shortest_first_then_aged_fifo():
    sched = StepScheduler(policy="sjf", aging_steps=3)
    q = [_req(i, n) for i, n in enumerate((9, 1, 5))]
    assert [r.uid for r in sched.order_queue(q, 0, lambda r: False)] \
        == [1, 2, 0]
    # Once everything ages, order falls back to FIFO among the aged.
    assert [r.uid for r in sched.order_queue(q, 3, lambda r: False)] \
        == [0, 1, 2]


def test_plan_chunks_drains_head_first():
    sched = StepScheduler(policy="fifo", prefill_budget=32, chunk_size=8)
    plan = sched.plan_chunks([(0, 20, 0), (1, 20, 1)])
    # Head-first: lane 0 finishes its prefill before lane 1 starts.
    assert plan == [(0, 8), (0, 8), (0, 4), (1, 8)]
    assert sched.budget_limited_steps == 1
    assert sched.peak_step_tokens == 28


# ---------------------------------------------------------------------------
# Satellite: property tests (hypothesis stub)


@settings(max_examples=40)
@given(st.lists(st.integers(min_value=1, max_value=200), min_size=0,
                max_size=10),
       st.integers(min_value=1, max_value=64),
       st.integers(min_value=1, max_value=256),
       st.integers(min_value=0, max_value=1))
def test_property_plan_never_exceeds_budget(remainings, chunk, budget,
                                            policy_idx):
    """Per-step invariant: total granted tokens <= prefill_budget, every
    grant <= chunk_size, no lane granted past its remaining prefill, and
    progress is always made when any lane has work."""
    budget = max(budget, chunk)  # config guarantees budget >= chunk_size
    sched = StepScheduler(policy=("fifo", "sjf")[policy_idx],
                          prefill_budget=budget, chunk_size=chunk)
    plan = sched.plan_chunks([(i, r, i) for i, r in enumerate(remainings)])
    assert sum(g for _, g in plan) <= budget
    assert all(0 < g <= chunk for _, g in plan)
    granted = {}
    for s, g in plan:
        granted[s] = granted.get(s, 0) + g
    for i, r in enumerate(remainings):
        assert granted.get(i, 0) <= r
    assert sched.peak_step_tokens <= budget
    if remainings:
        assert plan, "budget >= chunk_size guarantees progress"


@settings(max_examples=20)
@given(st.integers(min_value=1, max_value=30),
       st.integers(min_value=2, max_value=50))
def test_property_aging_bounds_starvation(aging, long_len):
    """Adversarial sjf starvation: a long prompt with a fresh shorter rival
    arriving every step is still admitted within aging_steps + 1 (without
    aging it would wait forever), and the promotion is counted."""
    sched = StepScheduler(policy="sjf", aging_steps=aging,
                          prefill_budget=8, chunk_size=8)
    long_req = _req(-1, long_len)
    queue = [long_req]
    admitted = None
    for step in range(aging + 10):
        queue.append(_req(step, 1))
        head = sched.order_queue(list(queue), step, lambda r: False)[0]
        queue.remove(head)
        sched.note_admitted(head.uid)
        if head is long_req:
            admitted = step
            break
    assert admitted is not None and admitted <= aging + 1
    assert sched.aging_promotions >= 1


@settings(max_examples=8)
@given(st.lists(st.integers(min_value=0, max_value=5), min_size=1,
                max_size=18))
def test_property_chunked_lifecycle_never_leaks_pages(ops):
    """The overload lifecycle fuzz with chunking on: random submit / step /
    cancel / deadline interleavings — now with lanes that can be preempted
    mid-prefill — keep ``in_use + available == capacity`` at every point
    and drain to zero."""
    cfg, params = _setup("glm4-9b")
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, page_size=8, n_pages=7,
        admission="optimistic", max_queue=4,
        prefill_budget=8, chunk_size=8, sched_policy="sjf",
        sched_aging_steps=4))
    rng = np.random.default_rng(sum(ops) + len(ops))
    uid = 0
    live = []
    for op in ops:
        if op in (0, 1):  # submit (short / long-enough-to-chunk)
            r = Request(uid=uid,
                        prompt=rng.integers(0, cfg.vocab,
                                            3 + op * 17).tolist(),
                        max_new_tokens=4 + op * 12)
            uid += 1
            try:
                eng.submit(r)
                live.append(r)
            except EngineOverloaded:
                assert r.finish_reason == "shed"
        elif op == 2 and live:  # cancel a random live request
            eng.cancel(live[rng.integers(0, len(live))].uid)
        elif op == 3 and live:  # force a deadline expiry
            live[rng.integers(0, len(live))].deadline_s = 0.0
        else:
            eng.step()
        a = eng.allocator
        assert a.in_use() + a.available() == a.capacity
        live = [r for r in live if r.t_done == 0.0]
    eng.run()
    a = eng.allocator
    assert a.in_use() == 0
    assert a.in_use() + a.available() == a.capacity
