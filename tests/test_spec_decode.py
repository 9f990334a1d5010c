"""Self-speculative decoding (ISSUE 3): draft, multi-token verify, rollback.

The acceptance bar: greedy spec-decode is **token-identical** to plain greedy
decode (dense + MoE, paged + unpaged engines, eos mid-window, budget boundary
inside an accepted window) — every committed token comes from the target's
own argmax, so the draft can only change *how fast* tokens commit, never
*which* tokens. And the paged-KV rollback invariant: speculation leaves the
page allocator (refcounts, pool occupancy, prefix cache) in exactly the state
of never having speculated.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models import transformer as T
from repro.serving import (EngineConfig, PageAllocator, Request,
                           ServingEngine, SpecConfig)
from repro.serving.spec_decode import AdaptiveK, committed_tokens


@pytest.fixture(scope="module")
def dense_setup():
    cfg = smoke_config("glm4-9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def quant_setup(dense_setup):
    from repro.core.apply import quantize_params
    from repro.core.recipe import QuantRecipe

    cfg, params = dense_setup
    qparams = quantize_params(
        params, QuantRecipe(w_bits=8, ocs_ratio=0.02, per_channel=True, pad_to=1)
    )
    return cfg, qparams


def _run(cfg, params, prompts, *, max_new=6, spec=None, paged=None,
         max_batch=3, max_len=64, matmul_mode="dequant", eos=None):
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_batch=max_batch, max_len=max_len, paged=paged,
                     matmul_mode=matmul_mode, spec=spec),
    )
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=max_new, eos_id=eos))
    done = {r.uid: r.output for r in eng.run()}
    return done, eng


# ---------------------------------------------------------------------------
# Model layer: the multi-token verify path


@pytest.mark.parametrize("paged", [False, True])
def test_verify_step_equals_sequential_decode(paged):
    """verify_step's Q logits are bit-identical to Q sequential one-token
    decode steps — the primitive the exactness contract rests on (float
    caches; the paged run addresses the same positions through a table)."""
    cfg = smoke_config("deepseek-7b")
    params = T.per_layer(T.init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab, (2, 5))
    B, L, ps = 2, 32, 8

    def mk_caches():
        if not paged:
            return T.init_cache(cfg, B, L, dtype=jnp.float32)
        from repro.serving import kv_cache as kvc

        t = L // ps
        caches = kvc.init_paged_cache(cfg, B, B * t + 1, ps, t, dtype=jnp.float32)
        table = np.arange(1, B * t + 1, dtype=np.int32).reshape(B, t)
        caches["table"] = jnp.asarray(table)
        return caches

    caches = mk_caches()
    outs = []
    for i in range(tokens.shape[1]):
        lg, caches = T.decode_step(
            params, jnp.asarray(tokens[:, i : i + 1]), caches, cfg
        )
        outs.append(np.asarray(lg, np.float32))
    seq = np.stack(outs, axis=1)

    caches = mk_caches()
    lg, caches = T.verify_step(params, jnp.asarray(tokens), caches, cfg)
    np.testing.assert_array_equal(seq, np.asarray(lg, np.float32))
    assert int(caches["pos"][0]) == tokens.shape[1]


def test_truncated_draft_runs_prefix_only():
    """layers_limit: the drafter runs the first L layers (different logits)
    and leaves the skipped layers' caches untouched."""
    cfg = smoke_config("glm4-9b")
    params = T.per_layer(T.init_params(cfg, jax.random.PRNGKey(0)))
    caches = T.init_cache(cfg, 1, 32, dtype=jnp.float32)
    tok = jnp.asarray([[37]], jnp.int32)
    lg_full, _ = T.decode_step(params, tok, caches, cfg)
    lg_tr, c2 = T.decode_step(params, tok, caches, cfg, layers_limit=1)
    assert float(np.abs(np.asarray(lg_full) - np.asarray(lg_tr)).max()) > 0
    for a, b in zip(
        jax.tree_util.tree_leaves(caches["layers"][-1]),
        jax.tree_util.tree_leaves(c2["layers"][-1]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_multi_token_decode_rejects_ssm():
    cfg = smoke_config("mamba2-1.3b")
    params = T.per_layer(T.init_params(cfg, jax.random.PRNGKey(1)))
    caches = T.init_cache(cfg, 1, 16, dtype=jnp.float32)
    with pytest.raises(NotImplementedError):
        T.verify_step(params, jnp.zeros((1, 3), jnp.int32), caches, cfg)


# ---------------------------------------------------------------------------
# Exactness contract: spec greedy == plain greedy


@pytest.mark.parametrize("paged", [True, False])
def test_spec_matches_plain_greedy_dense_quantized(quant_setup, paged):
    """The contract, on a *real* draft/target split: int8 weights served in
    dequant mode (target) with the w8a8 dynamic-quant path drafting. Drafts
    get rejected (acceptance < 1) yet the output stream is token-identical."""
    cfg, qparams = quant_setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in [3, 11, 6, 21]]
    plain, _ = _run(cfg, qparams, prompts, max_new=8, paged=paged)
    spec, eng = _run(
        cfg, qparams, prompts, max_new=8, paged=paged,
        spec=SpecConfig(k=3, draft_mode="w8a8"),
    )
    assert spec == plain
    s = eng.stats()
    assert s["spec_rounds"] > 0 and s["spec_proposed"] > 0
    assert 0.0 < s["spec_acceptance_rate"] <= 1.0
    # Each target step commits at least its correction token.
    assert s["spec_tokens_per_target_step"] >= 1.0


def test_spec_matches_plain_greedy_moe_paged():
    """MoE target: expert routing is stateless, so verify batches Q tokens
    through the same dispatch — spec must stay token-identical there too."""
    cfg = smoke_config("deepseek-moe-16b")
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in [4, 13]]
    plain, _ = _run(cfg, params, prompts, max_new=6, max_batch=2)
    spec, eng = _run(
        cfg, params, prompts, max_new=6, max_batch=2,
        spec=SpecConfig(k=3, draft_layers=1),
    )
    assert spec == plain
    assert eng.stats()["spec_rounds"] > 0


def test_spec_identical_draft_accepts_everything(dense_setup):
    """Float params: every matmul mode is the float matmul, so the draft IS
    the target — acceptance must be exactly 1.0 (the window clamp keeps
    beyond-budget drafts out of the rate)."""
    cfg, params = dense_setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in [5, 9]]
    plain, _ = _run(cfg, params, prompts, max_new=7, max_batch=2)
    spec, eng = _run(
        cfg, params, prompts, max_new=7, max_batch=2, spec=SpecConfig(k=3)
    )
    assert spec == plain
    s = eng.stats()
    assert s["spec_acceptance_rate"] == 1.0
    # Full acceptance: fewer target steps than tokens generated.
    assert s["decode_steps"] < s["decoded_tokens"]


def test_spec_eos_mid_window(quant_setup):
    """eos landing inside an accepted window retires the lane with the tail
    dropped — same tokens as the plain engine honoring the same eos."""
    cfg, qparams = quant_setup
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab, 9).tolist()]
    # Probe a full greedy run, then pick a mid-stream token as the eos.
    probe, _ = _run(cfg, qparams, prompts, max_new=10, max_batch=1)
    eos = probe[0][len(probe[0]) // 2]
    plain, _ = _run(cfg, qparams, prompts, max_new=10, max_batch=1, eos=eos)
    spec, eng = _run(
        cfg, qparams, prompts, max_new=10, max_batch=1, eos=eos,
        spec=SpecConfig(k=3, draft_mode="w8a8"),
    )
    assert spec == plain
    assert spec[0][-1] == eos and len(spec[0]) < 10
    assert eng.stats()["kv_pages_in_use"] == 0  # retired mid-window: reclaimed


@pytest.mark.parametrize("max_new", [2, 3, 4, 5])
def test_spec_max_new_boundary_inside_window(dense_setup, max_new):
    """The budget boundary lands at every offset inside an accepted window
    (float params, k=3: windows commit up to 4 tokens) — the output must cut
    exactly at max_new_tokens, identical to the plain engine."""
    cfg, params = dense_setup
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab, 6).tolist()]
    plain, _ = _run(cfg, params, prompts, max_new=max_new, max_batch=1)
    spec, _ = _run(
        cfg, params, prompts, max_new=max_new, max_batch=1,
        spec=SpecConfig(k=3, adaptive=False),
    )
    assert spec == plain and len(spec[0]) == max_new


def test_spec_mixed_continuous_batching(quant_setup):
    """Hot-swap admission under speculation: more requests than lanes, mixed
    lengths/budgets — all complete, all token-identical to plain serving."""
    cfg, qparams = quant_setup
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, cfg.vocab, int(n)).tolist()
               for n in rng.integers(3, 24, size=6)]
    plain, _ = _run(cfg, qparams, prompts, max_new=5, max_batch=2)
    spec, eng = _run(
        cfg, qparams, prompts, max_new=5, max_batch=2,
        spec=SpecConfig(k=3, draft_mode="w8a8"),
    )
    assert spec == plain and len(spec) == 6


# ---------------------------------------------------------------------------
# Rollback invariant: the allocator can't tell speculation ever happened


def test_spec_rollback_allocator_state_matches_plain(quant_setup):
    """After draining the same workload, the speculative engine's page pool
    is indistinguishable from the plain engine's: zero referenced pages, the
    same free+cached accounting, the same request footprints — rollback
    releases nothing it shouldn't and leaks nothing it wrote."""
    cfg, qparams = quant_setup
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in [17, 5, 33, 12]]
    _, eng_p = _run(cfg, qparams, prompts, max_new=6)
    _, eng_s = _run(
        cfg, qparams, prompts, max_new=6, spec=SpecConfig(k=3, draft_mode="w8a8")
    )
    a_p, a_s = eng_p.allocator, eng_s.allocator
    assert a_s.in_use() == a_p.in_use() == 0
    assert a_s._ref == a_p._ref == {}  # no stray refcounts
    assert a_s.available() == a_p.available() == a_s.capacity
    assert a_s.cached_pages() == a_p.cached_pages()
    assert a_s.peak_in_use == a_p.peak_in_use  # same footprint per request


def test_allocator_truncate():
    """Page-aware truncate: releases exactly the tail past the committed
    token count; registered (prefix-cache) pages drop to the LRU and stay
    hit-able — truncation keeps the prefix cache consistent."""
    a = PageAllocator(n_pages=8, page_size=4)
    ids = a.alloc(5)  # covers 20 tokens
    kept = a.truncate(ids, 10)  # 10 tokens -> 3 pages
    assert kept == ids[:3] and a.in_use() == 3 and a.available() == 4
    assert a.truncate(kept, 12) == kept  # nothing past the committed point
    # Registered prompt page released by truncate stays hit-able.
    key = a.chain_keys([1, 2, 3, 4], 1)[0]
    a.register(key, kept[0])
    assert a.truncate(kept, 0) == []
    assert a.in_use() == 0 and a.cached_pages() == 1
    hits, _ = a.match_prefix([1, 2, 3, 4], max_pages=1)
    assert hits == [kept[0]]


def test_spec_requires_attention_arch():
    cfg = smoke_config("mamba2-1.3b")
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    with pytest.raises(ValueError):
        ServingEngine(cfg, params,
                      EngineConfig(max_batch=1, max_len=32, spec=SpecConfig(k=3)))


def test_spec_submit_rejects_overlong_budget(dense_setup):
    """Spec engines require prompt + max_new_tokens <= max_len: committed
    positions must live in real cache slots for the exactness contract."""
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params,
                        EngineConfig(max_batch=1, max_len=32, spec=SpecConfig(k=2)))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=list(range(20)), max_new_tokens=20))


# ---------------------------------------------------------------------------
# Adaptive window controller + bookkeeping units


def test_committed_tokens_accept_prefix():
    # full accept: all drafts match the target chain -> k + 1 commits
    toks, acc = committed_tokens([7, 8, 9], [7, 8, 9, 4], k=3)
    assert toks == [7, 8, 9, 4] and acc == 3
    # first miss at j=1: commit the match + the target's correction
    toks, acc = committed_tokens([7, 5, 9], [7, 8, 9, 4], k=3)
    assert toks == [7, 8] and acc == 1
    # immediate miss: the round still commits the target's token
    toks, acc = committed_tokens([5, 5, 5], [7, 8, 9, 4], k=3)
    assert toks == [7] and acc == 0
    # k == 0: a plain decode step through the verify path
    toks, acc = committed_tokens([], [7], k=0)
    assert toks == [7] and acc == 0


def test_adaptive_k_grows_and_shrinks():
    spec = SpecConfig(k=5, k_min=1, grow_at=0.8, shrink_at=0.4, ema=0.5)
    ctl = AdaptiveK(spec)
    k0 = ctl.k
    for _ in range(10):
        ctl.update(accepted=10, proposed=10)  # perfect drafts
    assert ctl.k == 5 > k0
    for _ in range(20):
        ctl.update(accepted=0, proposed=10)  # hopeless drafts
    assert ctl.k == 1
    ctl.update(accepted=0, proposed=0)  # no usable proposals: k unchanged
    assert ctl.k == 1
    fixed = AdaptiveK(SpecConfig(k=4, adaptive=False))
    assert fixed.k == 4
    fixed.update(accepted=0, proposed=10)
    assert fixed.k == 4  # non-adaptive: pinned


def test_spec_stats_schema(dense_setup):
    cfg, params = dense_setup
    done, eng = _run(
        cfg, params, [[1, 2, 3], [4, 5, 6, 7]], max_new=5, max_batch=2,
        spec=SpecConfig(k=2),
    )
    s = eng.stats()
    for key in (
        "spec_rounds", "spec_k", "spec_proposed",
        "spec_acceptance_rate", "spec_tokens_per_target_step",
        "spec_draft_time_s", "spec_verify_time_s", "spec_compile_s",
    ):
        assert key in s, key
    assert s["spec_rounds"] > 0
    # 2 requests x (max_new - 1) decode-committed tokens (first from prefill)
    assert s["decoded_tokens"] == 8
    assert all(len(o) == 5 for o in done.values())
    # decode_steps now counts target steps: fewer than decoded tokens.
    assert s["decode_steps"] <= s["decoded_tokens"]
