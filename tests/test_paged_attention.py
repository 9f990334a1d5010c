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


@pytest.mark.parametrize("qn", [1, 4])
@pytest.mark.parametrize("int8", [False, True])
def test_kernel_matches_gather_oracle_with_one_query_head_per_kv_head(qn, int8):
    """MHA (16 KV heads in deepseek-moe-16b, 4 here): one query row a head
    at Q=1, four verify rows at Q=4, every head of the lane in one
    program."""
    rng = np.random.RandomState(17 + qn + 2 * int8)
    args = _mk_case(rng, int8, qn, 16, KV=4, rep=1)
    o_ref, p_ref = ops.paged_attention(*args, force="gather")
    o_krn, p_krn = ops.paged_attention(*args, force="interpret")
    ref = np.asarray(o_ref)
    # Same f32 math as the oracle, online softmax and another summation
    # order (the test above's tolerances).
    tol = 0.02 * np.abs(ref).max() if int8 else 2e-6
    np.testing.assert_allclose(np.asarray(o_krn), ref, atol=tol, rtol=0 if int8 else tol)
    for key in p_ref:
        assert (np.asarray(p_ref[key]) == np.asarray(p_krn[key])).all(), key


@pytest.mark.parametrize("int8", [False, True])
def test_past_the_vmem_budget_reroutes_to_xla(int8):
    """Where one program's tiles for all 16 KV heads of a lane would not
    fit the budget, the wrapper runs the XLA formulation and books the
    reroute (the chip run refuses one), with the XLA path's result."""
    from repro.kernels import budget
    rng = np.random.RandomState(21)
    args = _mk_case(rng, int8, 2, 16, KV=16, rep=1)
    budget.clear_reroutes()
    o_small, p_small = pa.paged_attention(*args, vmem_budget_bytes=1 << 16,
                                          interpret=True)
    assert list(budget.reroutes()) == [f"paged_attention{args[0]['k'].shape}"]
    budget.clear_reroutes()
    o_xla, p_xla = pa.paged_attention_xla(*args)
    np.testing.assert_array_equal(np.asarray(o_small), np.asarray(o_xla))
    for key in p_xla:
        assert (np.asarray(p_small[key]) == np.asarray(p_xla[key])).all(), key
    pa.paged_attention(*args, interpret=True)
    assert budget.reroutes() == {}


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
# Page blocks: the kernel's flash loop takes G pages a step

G = 3  # pages per block in these cases: several blocks in an 8-page table
BLOCK_LANES = (1, G - 1, G, G + 1, 2 * G + 1)  # active pages per lane
TRASH_AT = (4, 1)  # (lane, column): a trash page inside the lane's first block


def _mk_block_case(rng, int8, qn, kv, rep, ps=8, hd=16, t=8):
    """One lane per entry of BLOCK_LANES, each owning that many pages, its
    last page partly filled, the rest of its table trash. The last lane's
    column 1 is the trash page: inside its first block, whose slot its
    third block (one page past its end) finds stale."""
    b = len(BLOCK_LANES)
    p = b * t + 1
    pool = _mk_pool(rng, int8, p, kv, ps, hd)
    table = np.full((b, t), kvc.TRASH_PAGE, np.int32)
    pages = iter(range(1, p))
    pos = []
    for lane, n in enumerate(BLOCK_LANES):
        for col in range(n):
            table[lane, col] = next(pages)
        pos.append(max(n * ps - qn - lane % 3, 0))
    table[TRASH_AT] = kvc.TRASH_PAGE
    h = kv * rep
    return (
        pool, jnp.asarray(table), jnp.asarray(pos, jnp.int32),
        jnp.asarray(rng.randn(b, qn, h, hd), jnp.float32),  # q
        jnp.asarray(rng.randn(b, qn, kv, hd), jnp.float32),  # k_new
        jnp.asarray(rng.randn(b, qn, kv, hd), jnp.float32),  # v_new
    )


@pytest.mark.parametrize("kv,rep", [(2, 16), (16, 1)], ids=["gqa", "mha"])
@pytest.mark.parametrize("qn", [1, 4])
@pytest.mark.parametrize("int8", [False, True])
def test_page_blocks_match_gather_oracle_and_xla(kv, rep, qn, int8):
    """Lanes of 1, G-1, G, G+1 and 2G+1 pages, a trash page inside a
    block, last blocks partly past their lanes' ends: the blocked kernel
    agrees with the gather oracle and the XLA fallback, and appends the
    same pool bitwise."""
    rng = np.random.RandomState(31 + kv + qn + 2 * int8)
    args = _mk_block_case(rng, int8, qn, kv, rep)
    o_ref, p_ref = ops.paged_attention(*args, force="gather")
    o_xla, p_xla = ops.paged_attention(*args, force="ref")
    o_krn, p_krn = pa.paged_attention_kernel(*args, pages_per_block=G,
                                             interpret=True)
    ref = np.asarray(o_ref)
    # The tolerances of test_kernel_and_xla_match_gather_oracle.
    if not int8:
        np.testing.assert_allclose(np.asarray(o_krn), ref, atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(np.asarray(o_xla), ref, atol=2e-6, rtol=2e-6)
    else:
        scale = np.abs(ref).max()
        assert np.abs(np.asarray(o_krn) - ref).max() / scale < 0.02
        assert np.abs(np.asarray(o_xla) - ref).max() / scale < 0.15
    for key in p_ref:
        assert (np.asarray(p_ref[key]) == np.asarray(p_krn[key])).all(), key


@pytest.mark.parametrize("int8", [False, True])
def test_nan_poisoned_trash_page_inside_a_block_does_not_reach_outputs(int8):
    """The trash page loaded in the middle of a block, poisoned with NaN,
    and left stale in its slot for a later partial block: no output moves."""
    rng = np.random.RandomState(37)
    pool, *rest = _mk_block_case(rng, int8, 1, 2, 2)
    clean, _ = pa.paged_attention_kernel(pool, *rest, pages_per_block=G,
                                         interpret=True)
    dirty, _ = pa.paged_attention_kernel(_poison(pool), *rest,
                                         pages_per_block=G, interpret=True)
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.parametrize("g", [1, 2, G, 8])
def test_every_block_size_gives_one_answer(g):
    """One page a block up to the whole table: the same outputs to float
    tolerance (only the order of summation moves)."""
    rng = np.random.RandomState(41)
    args = _mk_block_case(rng, False, 2, 2, 2)
    o_ref, _ = ops.paged_attention(*args, force="gather")
    o_krn, _ = pa.paged_attention_kernel(*args, pages_per_block=g,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(o_krn), np.asarray(o_ref),
                               atol=2e-6, rtol=2e-6)


def _pool_shapes(kv, t, lanes, kind="int8", ps=16, hd=128):
    p = lanes * t + 1
    if kind == "float":
        return {"k": jax.ShapeDtypeStruct((p, kv, ps, hd), jnp.float32),
                "v": jax.ShapeDtypeStruct((p, kv, ps, hd), jnp.float32)}
    dt, rows = (jnp.int8, ps) if kind == "int8" else (jnp.uint8, ps // 2)
    return {"k": jax.ShapeDtypeStruct((p, kv, rows, hd), dt),
            "v": jax.ShapeDtypeStruct((p, kv, rows, hd), dt),
            "k_scale": jax.ShapeDtypeStruct((p, kv, ps), jnp.float32),
            "v_scale": jax.ShapeDtypeStruct((p, kv, ps), jnp.float32)}


@pytest.mark.parametrize("kv,t,lanes,heads,want", [(2, 256, 8, 32, 16),
                                                   (16, 32, 32, 16, 8)],
                         ids=["glm4-9b", "deepseek-moe-16b"])
def test_plan_pages_fits_the_budget_whatever_q(kv, t, lanes, heads, want):
    """The block comes from the pool's shapes and the table alone, so decode
    (Q = 1) and verify (Q = 4) walk the same blocks; with any Q the
    program's resident bytes stay within the VMEM budget."""
    pool = _pool_shapes(kv, t, lanes)
    g = pa.plan_pages(pool, t)
    assert g == want
    assert pa.plan_pages(_pool_shapes(kv, t, lanes, "int4"), t) == 1
    assert pa.plan_pages(pool, 4) == 4  # never more pages than the table
    for qn in (1, 4):
        need = pa._vmem_bytes(kv, 16, 128, 1, 16, qn * heads // kv, qn, True, g)
        assert need <= pa.VMEM_BUDGET_BYTES, (qn, need)
    # A tighter budget takes smaller blocks before it reroutes.
    tight = pa._block_bytes(kv, 16, 128, 1, 16, True, g) * 2 - 1
    assert pa.plan_pages(pool, t, tight) == g // 2


def test_decode_and_verify_calls_take_the_same_blocks(monkeypatch):
    """The wrapper hands the kernel one block size for Q = 1 and Q = 4."""
    seen = []
    real = pa.paged_attention_kernel

    def spy(*args, pages_per_block=0, **kw):
        seen.append(pages_per_block)
        return real(*args, pages_per_block=pages_per_block, **kw)

    monkeypatch.setattr(pa, "paged_attention_kernel", spy)
    for qn in (1, 4):
        rng = np.random.RandomState(43)
        pa.paged_attention(*_mk_block_case(rng, True, qn, 2, 2), interpret=True)
    assert seen == [8, 8]  # the whole 8-page table: within BLOCK_TOKENS


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


def test_stats_report_pages_per_block(dense_setup, monkeypatch):
    """Where the Pallas kernel is the live path, stats() names its planned
    pages per block; the gather and XLA paths read 0."""
    cfg, params = dense_setup
    eng, _ = _run_engine(cfg, params, attn="pallas")
    if jax.default_backend() != "tpu":
        assert eng.stats()["attn_pages_per_block"] == 0.0  # the XLA loop
        monkeypatch.setattr(eng, "_attn_kernel_stat", lambda: "pallas")
    pool = eng.caches["layers"][0]["attn"]
    want = pa.plan_pages(pool, eng.caches["table"].shape[1])
    assert want >= 1
    assert eng.stats()["attn_pages_per_block"] == float(want)
    eng2, _ = _run_engine(cfg, params)
    assert eng2.stats()["attn_pages_per_block"] == 0.0  # gather path
