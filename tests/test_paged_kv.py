"""Paged KV-cache subsystem (ISSUE 2): page pools, block tables, prefix reuse.

The acceptance bar: paged decode is *bit-exact* against the contiguous f32
cache in float-page mode (the gather reconstructs the dense layout), page
admission control recycles pages so workloads larger than the pool complete
(impossible with fixed-slot caches), and refcounted prefix sharing serves a
repeated system prompt without re-prefilling it — with identical outputs.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models import transformer as T
from repro.serving import (EngineConfig, PageAllocator, Request,
                           ServingEngine, pages_needed)
from repro.serving import kv_cache as kvc


def _mk_requests(rng, vocab, lengths, max_new=5, eos=None):
    return [
        Request(
            uid=i,
            prompt=rng.integers(0, vocab, n).tolist(),
            max_new_tokens=max_new,
            eos_id=eos,
        )
        for i, n in enumerate(lengths)
    ]


@pytest.fixture(scope="module")
def dense_setup():
    cfg = smoke_config("glm4-9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# Allocator (host-side, no jax)


def test_pages_needed():
    assert pages_needed(0, 16) == 0
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2


def test_allocator_alloc_free_refcount():
    a = PageAllocator(n_pages=5, page_size=4)  # capacity 4 (page 0 is trash)
    assert a.capacity == 4 and a.available() == 4
    ids = a.alloc(3)
    assert len(ids) == 3 and 0 not in ids and a.in_use() == 3
    a.retain(ids[0])
    a.release(ids)  # ids[0] still referenced once
    assert a.in_use() == 1 and a.available() == 3
    a.release([ids[0]])
    assert a.in_use() == 0 and a.available() == 4
    with pytest.raises(RuntimeError):
        a.alloc(5)
    assert a.peak_in_use == 3


def test_allocator_prefix_match_register_evict():
    a = PageAllocator(n_pages=4, page_size=2)  # capacity 3
    toks = [1, 2, 3, 4, 5]
    hits, keys = a.match_prefix(toks, max_pages=2)
    assert hits == [] and len(keys) == 2  # 2 full pages of 5 tokens
    ids = a.alloc(2)
    a.register(keys[0], ids[0])
    a.register(keys[1], ids[1])
    a.release(ids)  # zero-ref but cached: still hit-able, still allocatable
    assert a.in_use() == 0 and a.cached_pages() == 2 and a.available() == 3

    hits2, _ = a.match_prefix(toks, max_pages=2)
    assert hits2 == ids and a.in_use() == 2  # revived from the LRU
    # chained hash: a different second block must not hit past page 0
    hits3, _ = a.match_prefix([1, 2, 9, 9], max_pages=2)
    assert hits3 == [ids[0]]
    a.release(hits2)
    a.release(hits3)

    # pool pressure evicts cached pages (oldest first) back into circulation
    got = a.alloc(3)
    assert sorted(got) == sorted([ids[0], ids[1]] + [a for a in got if a not in ids])
    assert a.cached_pages() == 0
    assert a.match_prefix(toks, max_pages=2)[0] == []  # cache gone after evict


# ---------------------------------------------------------------------------
# Paged decode correctness (model layer)


@pytest.mark.parametrize("kv_bits", [None, 8])
def test_paged_decode_bitexact_vs_contiguous(kv_bits):
    """The layout invariant: gathering pool[table] reconstructs the dense
    cache, so paged decode logits equal contiguous-cache logits *bitwise* —
    float pages and int8 pages alike (same quant grid, same values)."""
    cfg = dataclasses.replace(smoke_config("deepseek-7b"), kv_bits=kv_bits)
    params = T.per_layer(T.init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab, (2, 12))
    B, L, ps = 2, 32, 8

    def decode_all(paged):
        if paged:
            t = L // ps
            caches = kvc.init_paged_cache(cfg, B, B * t + 1, ps, t, dtype=jnp.float32)
            table = np.arange(1, B * t + 1, dtype=np.int32).reshape(B, t)
            caches["table"] = jnp.asarray(table)
        else:
            caches = T.init_cache(cfg, B, L, dtype=jnp.float32)
        outs = []
        for i in range(tokens.shape[1]):
            lg, caches = T.decode_step(
                params, jnp.asarray(tokens[:, i : i + 1]), caches, cfg
            )
            outs.append(np.asarray(lg, np.float32))
        return np.stack(outs)

    np.testing.assert_array_equal(decode_all(False), decode_all(True))


def test_paged_engine_matches_unpaged(dense_setup):
    """End-to-end float-page parity: the paged engine emits exactly the
    tokens of the fixed-slot engine for a mixed-length workload."""
    cfg, params = dense_setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in [3, 11, 6, 21]]

    def run(paged):
        eng = ServingEngine(cfg, params, EngineConfig(max_batch=3, max_len=64, paged=paged))
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=6))
        return {r.uid: r.output for r in eng.run()}

    assert run(True) == run(False)


def test_paged_engine_matches_unpaged_moe():
    """MoE blocks serve through the paged cache too (attention is the only
    cached state; expert routing is stateless)."""
    cfg = smoke_config("deepseek-moe-16b")
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in [4, 13]]

    def run(paged):
        eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=64, paged=paged))
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=4))
        return {r.uid: r.output for r in eng.run()}

    assert run(True) == run(False)


# ---------------------------------------------------------------------------
# Engine: reclamation, recycling, backpressure


def test_page_reclamation_across_retire_admit_cycles(dense_setup):
    """Pages free on retirement and get reused by later admissions: a
    workload whose total footprint is several times the pool completes, and
    the pool drains back to zero referenced pages."""
    cfg, params = dense_setup
    rng = np.random.default_rng(11)
    # capacity 8 pages = 128 cache tokens, far below max_batch * max_len
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_len=64, n_pages=9))
    lengths = [int(rng.integers(4, 30)) for _ in range(8)]
    reqs = _mk_requests(rng, cfg.vocab, lengths, max_new=6)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    s = eng.stats()
    assert len(done) == 8 and all(len(r.output) == 6 for r in done)
    total = sum(n + 6 for n in lengths)
    assert total > s["kv_pages_capacity"] * s["kv_page_size"]  # oversubscribed
    assert s["kv_pages_peak"] <= s["kv_pages_capacity"]
    assert s["kv_pages_in_use"] == 0  # everything reclaimed
    # the drained engine is immediately reusable
    eng.submit(Request(uid=99, prompt=[1, 2, 3], max_new_tokens=3))
    assert len(eng.run()) == 9


def test_page_exhaustion_backpressure_queues(dense_setup):
    """When the pool can't hold another request, admission *waits* (FIFO)
    instead of crashing; a request larger than the whole pool is rejected at
    submit so it can never deadlock the queue."""
    cfg, params = dense_setup
    rng = np.random.default_rng(13)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_len=64, n_pages=4))
    # each request needs 2 pages (17 + 5 tokens @ ps=16); pool holds 1 at once
    reqs = _mk_requests(rng, cfg.vocab, [17, 17, 17, 17], max_new=5)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    s = eng.stats()
    assert len(done) == 4
    assert s["kv_pages_peak"] <= s["kv_pages_capacity"] == 3
    with pytest.raises(ValueError):  # needs 4 pages; capacity is 3
        eng.submit(Request(uid=9, prompt=list(range(60)), max_new_tokens=4))


# ---------------------------------------------------------------------------
# Prefix sharing


def test_shared_prefix_batched_matches_solo(dense_setup):
    """Refcounted prefix sharing: requests sharing a system prompt decode
    batched off shared pages exactly as they decode solo from a cold engine
    (float-page mode — bit-exact pages, greedy argmax, identical tokens)."""
    cfg, params = dense_setup
    rng = np.random.default_rng(3)
    sys_prompt = rng.integers(0, cfg.vocab, 33).tolist()  # 2 full pages @ 16
    tails = [rng.integers(0, cfg.vocab, k).tolist() for k in (5, 9, 2)]

    solo = []
    for t in tails:
        eng = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=64))
        eng.submit(Request(uid=0, prompt=sys_prompt + t, max_new_tokens=5))
        solo.append(eng.run()[0].output)

    eng = ServingEngine(cfg, params, EngineConfig(max_batch=3, max_len=64))
    for i, t in enumerate(tails):
        eng.submit(Request(uid=i, prompt=sys_prompt + t, max_new_tokens=5))
    done = {r.uid: r.output for r in eng.run()}
    for i in range(len(tails)):
        assert done[i] == solo[i], f"uid={i}"
    s = eng.stats()
    # requests 2 and 3 each hit the 2 full prefix pages written by request 1
    assert s["prefix_hit_pages"] == 4 and s["prefix_hit_rate"] > 0


def test_repeated_prompt_prefills_once(dense_setup):
    """A repeated system prompt's shared pages prefill once: a repeat
    prefills only the suffix past its prefix hit. Hits are capped at
    (n-1)//page_size pages so the prefill keeps >= 1 real token: a 33-token
    prompt hits both full pages and reruns a 1-token suffix."""
    cfg, params = dense_setup
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, 33).tolist()  # 2 full pages + 1 tail
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=64))
    for i in range(3):
        eng.submit(Request(uid=i, prompt=list(prompt), max_new_tokens=4))
    done = {r.uid: r.output for r in eng.run()}
    assert done[0] == done[1] == done[2]
    s = eng.stats()
    # cold: 33 tokens; repeats: 1-token suffix each
    assert s["prefill_tokens"] == 33 + 1 + 1, s["prefill_tokens"]
    assert s["prefix_hit_pages"] == 4  # two full pages per repeat
    # cached pages survive retirement and still drain from in_use
    assert s["kv_pages_in_use"] == 0 and s["kv_pages_cached"] > 0


def test_prefix_pages_shared_not_copied(dense_setup):
    """Refcounting, not copying: two live sequences with the same prompt
    hold strictly fewer pages than two independent allocations."""
    cfg, params = dense_setup
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, cfg.vocab, 32).tolist()
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=64))
    # long decode budgets keep both sequences live simultaneously
    for i in range(2):
        eng.submit(Request(uid=i, prompt=list(prompt), max_new_tokens=8))
    eng.step()  # admits both (same _admit pass), decodes one token
    s = eng.stats()
    independent = 2 * pages_needed(32 + 8, 16)
    assert s["kv_pages_in_use"] < independent
    assert s["kv_pages_in_use"] == pages_needed(32 + 8, 16) + 2  # shared + own
    eng.run()


# ---------------------------------------------------------------------------
# Satellites: eos on the prefill token


@pytest.mark.parametrize("paged", [True, False])
def test_eos_on_first_token_retires_immediately(dense_setup, paged):
    """An immediate-eos request must not burn max_new_tokens-1 decode steps
    (or hold pages/a lane): probe the greedy first token, then resubmit with
    it as eos_id."""
    cfg, params = dense_setup
    rng = np.random.default_rng(17)
    prompt = rng.integers(0, cfg.vocab, 9).tolist()
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=64, paged=paged))
    eng.submit(Request(uid=0, prompt=list(prompt), max_new_tokens=8))
    first = eng.run()[0].output[0]

    eng2 = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=64, paged=paged))
    eng2.submit(Request(uid=0, prompt=list(prompt), max_new_tokens=8, eos_id=first))
    done = eng2.run()
    s = eng2.stats()
    assert len(done) == 1 and done[0].output == [first]
    assert done[0].t_done > 0
    assert s["decode_steps"] == 0  # zero decode work
    assert s["kv_pages_in_use"] == 0  # pages reclaimed at once (paged mode)


def test_max_new_tokens_one(dense_setup):
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=64))
    eng.submit(Request(uid=0, prompt=[5, 6, 7], max_new_tokens=1))
    done = eng.run()
    assert len(done) == 1 and len(done[0].output) == 1
    assert eng.stats()["decode_steps"] == 0
