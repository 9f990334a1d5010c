"""Paged-attention kernel (ISSUE 4): fused append + in-pool flash decode.

The contracts under test:

* **parity** — the Pallas kernel (interpret mode) and the gather-free XLA
  fallback match the gather-everything oracle across page sizes, ragged
  per-lane positions, and Q > 1 verify masks (float pages to float
  tolerance — online vs one-shot softmax ordering — int8 pages to
  quantization tolerance);
* **append fusion** — the pool returned by the fused dispatch is *bitwise*
  the pool `kv_cache.append_tokens` would have produced (one quant grid for
  every pool writer);
* **trash-page invariant** — page 0 poisoned with NaN changes no active
  lane's output, for the legacy gather path (the new `gather_pages` mask),
  the XLA fallback, and the interpreted kernel;
* **engine integration** — `EngineConfig.kernels.attn` selections produce
  token-identical greedy output, spec-decode output identity holds with the
  kernel enabled, `stats()` reports the attention path in the shared
  `KernelChoice` vocabulary, and the deprecated `USE_PALLAS_PAGED_ATTN`
  module flag seeds the `auto` default at engine construction only.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import attention as attn_mod
from repro.models import transformer as T
from repro.serving import (EngineConfig, KernelConfig, Request,
                           ServingEngine, SpecConfig)
from repro.serving import kv_cache as kvc


def _mk_pool(rng, int8, P, KV, ps, hd):
    if int8:
        return {
            "k": jnp.asarray(rng.randint(-127, 128, (P, KV, ps, hd)), jnp.int8),
            "v": jnp.asarray(rng.randint(-127, 128, (P, KV, ps, hd)), jnp.int8),
            "k_scale": jnp.asarray(rng.rand(P, KV, ps) * 0.1 + 0.01, jnp.float32),
            "v_scale": jnp.asarray(rng.rand(P, KV, ps) * 0.1 + 0.01, jnp.float32),
        }
    return {
        "k": jnp.asarray(rng.randn(P, KV, ps, hd), jnp.float32),
        "v": jnp.asarray(rng.randn(P, KV, ps, hd), jnp.float32),
    }


def _mk_case(rng, int8, qn, ps, B=3, T=4, KV=2, rep=2, hd=16):
    """Ragged lanes: lane b owns b+2 pages (capped at T), the rest trash."""
    P = B * T + 1
    H = KV * rep
    pool = _mk_pool(rng, int8, P, KV, ps, hd)
    table = np.full((B, T), kvc.TRASH_PAGE, np.int32)
    pages = iter(range(1, P))
    pos = []
    for b in range(B):
        npg = min(T, b + 2)
        for t in range(npg):
            table[b, t] = next(pages)
        pos.append(max((npg - 1) * ps - qn - b, 0))
    args = (
        pool,
        jnp.asarray(table),
        jnp.asarray(pos, jnp.int32),
        jnp.asarray(rng.randn(B, qn, H, hd), jnp.float32),  # q
        jnp.asarray(rng.randn(B, qn, KV, hd), jnp.float32),  # k_new
        jnp.asarray(rng.randn(B, qn, KV, hd), jnp.float32),  # v_new
    )
    return args


# ---------------------------------------------------------------------------
# Kernel / fallback vs the gather oracle (op level)


@pytest.mark.parametrize("ps", [8, 16, 64])
@pytest.mark.parametrize("qn", [1, 4])
@pytest.mark.parametrize("int8", [False, True])
def test_kernel_and_xla_match_gather_oracle(ps, qn, int8):
    rng = np.random.RandomState(hash((ps, qn, int8)) % (2**31))
    args = _mk_case(rng, int8, qn, ps)
    o_ref, p_ref = ops.paged_attention(*args, force="gather")
    o_xla, p_xla = ops.paged_attention(*args, force="ref")
    o_krn, p_krn = ops.paged_attention(*args, force="interpret")
    # Float pages: same f32 math, online vs one-shot softmax ordering only.
    # Int8: the kernel dequantizes in VMEM (f32 dots, tight vs the oracle);
    # the XLA fallback runs the legacy integer path (q and softmax weights
    # requantized), so it carries the int8 cache's quantization-noise
    # tolerance (same class as tests/test_kv_cache_quant.py).
    if not int8:
        np.testing.assert_allclose(np.asarray(o_krn), np.asarray(o_ref),
                                   atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_ref),
                                   atol=2e-6, rtol=2e-6)
    else:
        ref = np.asarray(o_ref)
        scale = np.abs(ref).max()
        assert np.abs(np.asarray(o_krn) - ref).max() / scale < 0.02
        assert np.abs(np.asarray(o_xla) - ref).max() / scale < 0.15
    # The appended pools must agree BITWISE across all three paths.
    for key in p_ref:
        assert (np.asarray(p_ref[key]) == np.asarray(p_xla[key])).all(), key
        assert (np.asarray(p_ref[key]) == np.asarray(p_krn[key])).all(), key


@pytest.mark.parametrize("int8", [False, True])
def test_append_fusion_matches_append_tokens(int8):
    """The fused dispatch's pool == kv_cache.append_tokens' pool, bitwise:
    one quantization grid for every pool writer."""
    rng = np.random.RandomState(7)
    pool, table, pos, q, k_new, v_new = _mk_case(rng, int8, 4, 16)
    _, p_fused = ops.paged_attention(pool, table, pos, q, k_new, v_new,
                                     force="ref")
    # append_tokens takes [B, Q, KV, hd] and the same clamp semantics. Jit
    # it like the dispatch is: eager XLA may order the absmax reduction
    # differently and flip last-ulp scale bits on ties.
    p_ref = jax.jit(kvc.append_tokens)(pool, k_new, v_new, table, pos)
    for key in p_ref:
        assert (np.asarray(p_fused[key]) == np.asarray(p_ref[key])).all(), key


@pytest.mark.parametrize("qn", [1, 4])
def test_ragged_lanes_match_solo(qn):
    """Each lane of a ragged batch gets exactly its solo-run output (the
    per-lane position bounds in the kernel are per-lane, not batch-max)."""
    rng = np.random.RandomState(3)
    pool, table, pos, q, k_new, v_new = _mk_case(rng, False, qn, 8)
    out, _ = ops.paged_attention(pool, table, pos, q, k_new, v_new,
                                 force="interpret")
    for b in range(table.shape[0]):
        solo, _ = ops.paged_attention(
            pool, table[b : b + 1], pos[b : b + 1], q[b : b + 1],
            k_new[b : b + 1], v_new[b : b + 1], force="interpret",
        )
        np.testing.assert_allclose(
            np.asarray(out[b]), np.asarray(solo[0]), atol=1e-6, rtol=1e-6
        )


@pytest.mark.parametrize("int8", [False, True])
def test_inactive_lane_outputs_zero_on_every_path(int8):
    """A retired lane (all-trash table row, pos 0) must emit exact zeros on
    all three paths — the engine never commits it, but op-level parity (and
    any batch-wide comparison) relies on the agreement."""
    rng = np.random.RandomState(13)
    pool, table, pos, q, k_new, v_new = _mk_case(rng, int8, 2, 8)
    table = table.at[1].set(kvc.TRASH_PAGE)  # retire lane 1
    pos = pos.at[1].set(0)
    for force in ("gather", "ref", "interpret"):
        out, _ = ops.paged_attention(pool, table, pos, q, k_new, v_new,
                                     force=force)
        assert (np.asarray(out[1]) == 0).all(), force


def test_q4_rows_equal_sequential_q1():
    """Per-token causal masks: the Q=4 verify shape reproduces 4 sequential
    Q=1 appends+attends (the spec-decode verify contract, at op level)."""
    rng = np.random.RandomState(11)
    pool, table, pos, q, k_new, v_new = _mk_case(rng, False, 4, 16)
    out4, pool4 = ops.paged_attention(pool, table, pos, q, k_new, v_new,
                                      force="interpret")
    cur = pool
    for j in range(4):
        oj, cur = ops.paged_attention(
            cur, table, pos + j, q[:, j : j + 1], k_new[:, j : j + 1],
            v_new[:, j : j + 1], force="interpret",
        )
        np.testing.assert_allclose(
            np.asarray(out4[:, j]), np.asarray(oj[:, 0]), atol=1e-5, rtol=1e-5
        )
    for key in cur:
        assert (np.asarray(cur[key]) == np.asarray(pool4[key])).all(), key


# ---------------------------------------------------------------------------
# Trash-page invariant: page 0 poisoned with NaN changes nothing


def _poison(pool):
    out = dict(pool)
    if pool["k"].dtype == jnp.int8:
        # int8 values can't be NaN; poison the scales instead.
        out["k_scale"] = pool["k_scale"].at[kvc.TRASH_PAGE].set(jnp.nan)
        out["v_scale"] = pool["v_scale"].at[kvc.TRASH_PAGE].set(jnp.nan)
    else:
        out["k"] = pool["k"].at[kvc.TRASH_PAGE].set(jnp.nan)
        out["v"] = pool["v"].at[kvc.TRASH_PAGE].set(jnp.nan)
    return out


@pytest.mark.parametrize("int8", [False, True])
def test_gather_pages_masks_trash(int8):
    rng = np.random.RandomState(5)
    pool, table, pos, *_ = _mk_case(rng, int8, 1, 8)
    k, v, ks, vs = kvc.gather_pages(_poison(pool), table)
    trash = np.repeat(np.asarray(table) == kvc.TRASH_PAGE, 8, axis=1)
    for arr in (k, v) + ((ks, vs) if int8 else ()):
        a = np.asarray(arr, np.float32)
        assert np.isfinite(a).all()
        # trash positions read as exact zeros, real positions untouched
        sl = a[:, :, :, 0] if arr.ndim == 4 else a
        assert (sl[np.nonzero(trash)[0], :, np.nonzero(trash)[1]] == 0).all()


@pytest.mark.parametrize("force", ["gather", "ref", "interpret"])
@pytest.mark.parametrize("int8", [False, True])
def test_nan_poisoned_trash_page_does_not_reach_outputs(force, int8):
    rng = np.random.RandomState(9)
    pool, table, pos, q, k_new, v_new = _mk_case(rng, int8, 2, 8)
    clean, _ = ops.paged_attention(pool, table, pos, q, k_new, v_new,
                                   force=force)
    dirty, _ = ops.paged_attention(_poison(pool), table, pos, q, k_new,
                                   v_new, force=force)
    # Every lane in _mk_case is active (owns real pages): outputs must be
    # finite and unchanged by the poison.
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def test_legacy_decode_path_survives_poisoned_trash_page():
    """End to end through attention_decode's *gather* path: an active lane
    decodes next to a retired (all-trash) lane whose page 0 holds NaN."""
    cfg = smoke_config("deepseek-7b")
    params = T.per_layer(T.init_params(cfg, jax.random.PRNGKey(0)))
    B, L, ps = 2, 32, 8
    t = L // ps
    caches = kvc.init_paged_cache(cfg, B, B * t + 1, ps, t, dtype=jnp.float32)
    table = np.full((B, t), kvc.TRASH_PAGE, np.int32)
    table[0] = np.arange(1, t + 1)  # lane 0 active, lane 1 retired
    caches["table"] = jnp.asarray(table)
    tok = jnp.asarray([[3], [0]], jnp.int32)

    def run(poison):
        c = jax.tree.map(lambda a: a, caches)
        if poison:
            c["layers"] = [
                {"attn": _poison(layer["attn"])} for layer in c["layers"]
            ]
        outs = []
        for _ in range(3):
            lg, c = T.decode_step(params, tok, c, cfg)
            outs.append(np.asarray(lg[0]))
        return np.stack(outs)

    clean, dirty = run(False), run(True)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(clean, dirty)


# ---------------------------------------------------------------------------
# Engine integration


@pytest.fixture(scope="module")
def dense_setup():
    cfg = smoke_config("glm4-9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run_engine(cfg, params, *, seed=0, max_new=6, attn="gather", spec_k=0,
                attn_probe=False):
    rng = np.random.default_rng(seed)
    ecfg = EngineConfig(
        max_batch=2, max_len=64, kernels=KernelConfig(attn=attn),
        spec=SpecConfig(k=spec_k) if spec_k else None, attn_probe=attn_probe,
    )
    eng = ServingEngine(cfg, params, ecfg)
    for i, n in enumerate([5, 11, 3, 17]):
        eng.submit(Request(uid=i, prompt=rng.integers(0, cfg.vocab, n).tolist(),
                           max_new_tokens=max_new))
    eng.run()
    return eng, {r.uid: r.output for r in eng.done}


def test_engine_outputs_identical_with_kernel_enabled(dense_setup):
    cfg, params = dense_setup
    _, base = _run_engine(cfg, params, attn="gather")
    eng, fused = _run_engine(cfg, params, attn="pallas")
    assert fused == base
    assert eng.paged_attn is True  # legacy view of the kernel selection


@pytest.mark.parametrize("kv_bits", [None, 8])
def test_spec_decode_output_identity_with_kernel_enabled(kv_bits):
    """The spec-decode greedy exactness contract, re-run with the paged-
    attention kernel path enabled: spec == plain, both through the kernel."""
    cfg = dataclasses.replace(smoke_config("deepseek-7b"), kv_bits=kv_bits)
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    _, plain = _run_engine(cfg, params, attn="pallas")
    eng, spec = _run_engine(cfg, params, attn="pallas", spec_k=3)
    assert spec == plain
    assert eng.stats()["spec_rounds"] > 0


def test_module_flag_seeds_engine_config_default(dense_setup):
    """The deprecated USE_PALLAS_PAGED_ATTN shim seeds KernelChoice.AUTO at
    engine construction — and ONLY there: an engine built while the flag was
    set keeps its resolved kernel after the flag is restored."""
    cfg, params = dense_setup
    old = attn_mod.USE_PALLAS_PAGED_ATTN
    attn_mod.USE_PALLAS_PAGED_ATTN = True
    try:
        eng = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=32))
        assert eng.attn_kernel == "pallas" and eng.paged_attn is True
    finally:
        attn_mod.USE_PALLAS_PAGED_ATTN = old
    # Construction-time seeding only: the engine keeps "pallas" ...
    assert eng.attn_kernel == "pallas"
    # ... and a fresh default engine resolves the restored flag to "gather".
    eng2 = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=32))
    assert eng2.attn_kernel == "gather" and eng2.paged_attn is False


def test_stats_report_attention_path(dense_setup):
    cfg, params = dense_setup
    eng, _ = _run_engine(cfg, params, attn="pallas", attn_probe=True)
    s = eng.stats()
    assert s["attn_kernel"] in ("pallas", "xla")
    if jax.default_backend() != "tpu":
        assert s["attn_kernel"] == "xla"  # kernel can't compile off-TPU
    assert s["attn_step_ms"] > 0.0  # probe enabled
    eng2, _ = _run_engine(cfg, params)
    assert eng2.stats()["attn_step_ms"] == 0.0  # probe off by default
    assert eng2.stats()["attn_kernel"] == "gather"  # KernelChoice vocabulary
