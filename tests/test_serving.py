"""Serving engine: chunked prefill, per-slot positions, continuous batching.

The acceptance bar (ISSUE 1): chunked prefill issues O(1) jitted calls per
request (vs O(prompt_len) decode replay), and mixed-length admission decodes
correctly — a request served in a mixed batch must emit exactly the tokens
it emits when served alone (per-slot positions make this exact; the old
global-``max`` position hack broke it).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models import transformer as T
from repro.serving import EngineConfig, Request, ServingEngine


def _mk_requests(rng, vocab, lengths, max_new=5):
    return [
        Request(uid=i, prompt=rng.integers(0, vocab, n).tolist(), max_new_tokens=max_new)
        for i, n in enumerate(lengths)
    ]


@pytest.fixture(scope="module")
def dense_setup():
    cfg = smoke_config("glm4-9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_prefill_with_cache_matches_replay(dense_setup):
    """One-shot prefill == token-by-token replay: same pos, same first token,
    K/V rows equal to bf16 accumulation noise (layer 0 exactly)."""
    cfg, params = dense_setup
    params = T.per_layer(params)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, 9).tolist()

    toks = np.zeros((1, 16), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, c_chunk = T.prefill_with_cache(
        params, jnp.asarray(toks), cfg, 32, length=jnp.asarray([len(prompt)])
    )
    c_rep = T.init_cache(cfg, 1, 32, dtype=jnp.float32)
    lg = None
    for t in prompt:
        lg, c_rep = T.decode_step(params, jnp.asarray([[t]], jnp.int32), c_rep, cfg)

    assert int(c_chunk["pos"][0]) == int(c_rep["pos"][0]) == len(prompt)
    assert int(jnp.argmax(logits[0])) == int(jnp.argmax(lg[0]))
    n = len(prompt)
    a0 = c_chunk["layers"][0]["attn"]
    b0 = c_rep["layers"][0]["attn"]
    # Layer 0 K/V depend only on the embeddings: bit-equal.
    np.testing.assert_array_equal(
        np.asarray(a0["k"][:, :, :n]), np.asarray(b0["k"][:, :, :n])
    )
    for li in range(cfg.n_layers):
        a = c_chunk["layers"][li]["attn"]
        b = c_rep["layers"][li]["attn"]
        for key in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(a[key][:, :, :n], np.float32),
                np.asarray(b[key][:, :, :n], np.float32),
                atol=0.1,  # bf16 compute: flash-prefill vs decode accumulation
            )


def test_engine_o1_prefill_calls(dense_setup):
    """Chunked prefill: exactly ONE jitted call per admitted request, and one
    compile per pow2 bucket — the compile/trace counters are the evidence."""
    cfg, params = dense_setup
    rng = np.random.default_rng(0)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=3, max_len=64))
    reqs = _mk_requests(rng, cfg.vocab, [3, 9, 12, 4, 30], max_new=4)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    s = eng.stats()
    assert s["completed"] == 5
    assert all(len(r.output) == 4 for r in done)
    assert s["prefill_calls_per_request"] == 1.0
    # Buckets hit: 8 (3, 4), 16 (9, 12), 32 (30) -> <= 3 compiles.
    assert s["prefill_traces"] <= 3
    assert len(eng._prefill_cache) == s["prefill_traces"]


def test_mixed_length_batch_matches_solo(dense_setup):
    """Per-slot positions: a request decodes identically whether it shares
    the batch with different-length neighbours or runs alone."""
    cfg, params = dense_setup
    rng = np.random.default_rng(7)
    lengths = [3, 11, 6]
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in lengths]

    solo_outputs = []
    for p in prompts:
        eng = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=64))
        eng.submit(Request(uid=0, prompt=p, max_new_tokens=6))
        done = eng.run()
        solo_outputs.append(done[0].output)

    eng = ServingEngine(cfg, params, EngineConfig(max_batch=3, max_len=64))
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
    done = {r.uid: r.output for r in eng.run()}
    for i in range(3):
        assert done[i] == solo_outputs[i], (
            f"uid={i}: batched {done[i]} != solo {solo_outputs[i]}"
        )


def test_continuous_batching_hotswap(dense_setup):
    """More requests than slots: freed slots admit from the queue mid-run."""
    cfg, params = dense_setup
    rng = np.random.default_rng(11)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=64))
    reqs = _mk_requests(rng, cfg.vocab, [4, 7, 5, 9, 6], max_new=3)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.output) == 3 for r in done)
    assert eng.stats()["prefill_calls_per_request"] == 1.0


def test_ssm_replay_fallback():
    """SSM blocks keep the decode-replay prefill (states not cache-exposed);
    the engine still serves correctly, just at O(prompt_len) calls."""
    cfg = smoke_config("mamba2-1.3b")
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=32))
    reqs = _mk_requests(rng, cfg.vocab, [4, 6], max_new=3)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 2
    assert eng.stats()["prefill_calls"] == 10  # 4 + 6: one per prompt token


def test_engine_w8a8_serving(dense_setup):
    """The engine serves an OCS-quantized tree in dynamic-W8A8 mode."""
    from repro.core.apply import quantize_params
    from repro.core.recipe import QuantRecipe

    cfg, params = dense_setup
    recipe = QuantRecipe(w_bits=8, ocs_ratio=0.02, per_channel=True, pad_to=1)
    qparams = quantize_params(params, recipe)
    rng = np.random.default_rng(2)
    eng = ServingEngine(
        cfg, qparams, EngineConfig(max_batch=2, max_len=64, matmul_mode="w8a8")
    )
    reqs = _mk_requests(rng, cfg.vocab, [5, 8], max_new=4)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 2 and all(len(r.output) == 4 for r in done)
    # w8a8 must stay close to dequant serving: token agreement, not identity.
    eng2 = ServingEngine(cfg, qparams, EngineConfig(max_batch=2, max_len=64))
    for i, r in enumerate(reqs):
        eng2.submit(Request(uid=i, prompt=r.prompt, max_new_tokens=4))
    done2 = {r.uid: r.output for r in eng2.run()}
    agree = sum(
        a == b for r in done for a, b in zip(r.output, done2[r.uid])
    )
    assert agree >= 4  # half the tokens (random-weight smoke model: noisy)


def test_stats_schema(dense_setup):
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=32))
    # Two same-bucket requests: the second prefill and the later decode
    # steps run warm, so the compile-excluded throughputs are nonzero.
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
    eng.submit(Request(uid=1, prompt=[4, 5, 6], max_new_tokens=4))
    eng.run()
    s = eng.stats()
    for key in (
        "completed", "decode_steps", "decoded_tokens", "mean_latency_s",
        "mean_ttft_s", "prefill_tokens", "prefill_time_s", "prefill_tok_per_s",
        "prefill_compile_s", "decode_time_s", "decode_compile_s",
        "decode_tok_per_s", "prefill_calls", "prefill_requests",
        "prefill_calls_per_request", "prefill_traces", "decode_traces",
        # paged KV-pool accounting (zeros on unpaged SSM/hybrid engines)
        "kv_page_size", "kv_pages_capacity", "kv_pages_in_use",
        "kv_pages_cached", "kv_pages_peak", "kv_pool_occupancy",
        "kv_pool_peak_occupancy", "prefix_hit_rate", "prefix_hit_pages",
        # speculative decoding (zeros when speculation is off)
        "spec_enabled", "spec_rounds", "spec_k", "spec_acceptance_rate",
        "spec_tokens_per_target_step", "spec_draft_time_s",
        "spec_verify_time_s", "spec_compile_s",
        # decode-attention path ("pallas"/"xla"; probed step time, 0.0
        # unless the engine was built with attn_probe=True)
        "attn_kernel", "attn_step_ms",
        # overload safety + watchdog (stats schema v6)
        "preempted", "shed", "timed_out", "errors", "kernel_fallbacks",
        "step_p50_ms", "step_p95_ms", "step_stalled",
        # step scheduler + queue-wait percentiles (stats schema v7)
        "queue_wait_p50_s", "queue_wait_p95_s", "sched_policy",
        "sched_prefill_budget", "sched_chunks", "sched_budget_limited_steps",
        "sched_aging_promotions", "sched_peak_step_prefill_tokens",
    ):
        assert key in s, key
    assert s["spec_enabled"] == 0.0
    assert s["prefill_tok_per_s"] > 0 and s["decode_tok_per_s"] > 0
    # Compile time was actually carved out of the warm buckets.
    assert s["prefill_compile_s"] > 0 and s["decode_compile_s"] > 0
