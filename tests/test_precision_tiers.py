"""Sub-8-bit precision tier (ISSUE 10): int4 KV pages + W4A8 matmul.

The contracts under test:

* **nibble packing** — ``pack_int4``/``unpack_int4`` round-trip the full
  signed int4 range ``[-8, 7]`` for arbitrary even channel counts (the
  split-half byte layout: byte ``j`` holds channels ``j`` and ``j + C/2``);
  ``quant_rows`` at ``KV4_QMAX`` stays on the 15-level grid with the usual
  half-step reconstruction bound;
* **kv4 parity** — the three paged-attention paths (gather oracle, XLA
  online-softmax fallback, Pallas kernel in interpret mode) are *bitwise*
  identical on packed int4 pools — outputs AND appended pools — across page
  sizes and Q > 1 verify windows; trash-page poison changes nothing;
* **W4A8 matmul** — the Pallas kernel (interpret mode) is bit-exact against
  ``ref.w4a8_matmul_ref`` through the jitted ``ops.w4a8_matmul`` dispatch,
  including OCS-duplicated outlier channels and odd expanded contraction
  dims, and both match the float composition to int8-activation tolerance;
* **to_w4a8** — the outlier separator keeps exactly the ranked rows at
  8-bit, zeroes them inside ``w4`` (exact partition), pads odd expanded
  dims with a dead spec entry, preserves stacked (scan) layer dims, and
  separation strictly improves weight reconstruction on outlier-planted
  matrices (the acceptance criterion, weight-space edition);
* **config** — the precision-tier knobs reject invalid combinations at
  construction time (kv_bits vocabulary, int4-needs-paged, outlier-ratio
  range, w4a8 + incompatible spec drafter);
* **engine** — int4-KV serving agrees with int8-KV serving on a pinned
  knife-edge seed, W4A8 serving agrees with dequant serving (same bar as
  ``test_engine_w8a8_serving``), the combined sub-8-bit tier (int4 pages +
  W4A8 matmuls) serves to completion, and the v10 stats gauges report the
  tier.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.configs import smoke_config
from repro.core import ocs
from repro.core.apply import quantize_params
from repro.core.recipe import QuantRecipe
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import transformer as T
from repro.serving import EngineConfig, Request, ServingEngine, SpecConfig
from repro.serving import kv_cache as kvc
from repro.serving.config import ConfigError


# ---------------------------------------------------------------------------
# Nibble packing


def test_pack_unpack_roundtrip_full_range():
    """Every signed int4 value survives the split-half byte layout."""
    q = jnp.asarray(np.arange(-8, 8, dtype=np.int8).reshape(1, 16))
    b = pa.pack_int4(q)
    assert b.dtype == jnp.uint8 and b.shape == (1, 8)
    np.testing.assert_array_equal(np.asarray(pa.unpack_int4(b)), np.asarray(q))


@settings(max_examples=25, deadline=None)
@given(
    rows=st.integers(1, 6),
    half=st.integers(1, 40),
    seed=st.integers(0, 10_000),
)
def test_pack_unpack_roundtrip_property(rows, half, seed):
    rng = np.random.RandomState(rows * 7919 + half * 131 + seed)
    q = rng.randint(-8, 8, (rows, 2 * half)).astype(np.int8)
    b = pa.pack_int4(jnp.asarray(q))
    assert b.dtype == jnp.uint8 and b.shape == (rows, half)
    np.testing.assert_array_equal(np.asarray(pa.unpack_int4(b)), q)


def test_pack_unpack_split_half_layout():
    """Byte j holds channel j in the low nibble, channel j + C/2 in the high."""
    q = jnp.asarray([[1, 2, 3, 4]], jnp.int8)
    b = np.asarray(pa.pack_int4(q))
    np.testing.assert_array_equal(b, [[(3 << 4) | 1, (4 << 4) | 2]])


def test_quant_rows_int4_grid():
    """qmax=KV4_QMAX stays on the 15-level grid; reconstruction within s/2."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(6, 4, 32) * 3.0, jnp.float32)
    q, s = pa.quant_rows(x, qmax=pa.KV4_QMAX)
    qn = np.asarray(q, np.int32)
    assert qn.min() >= -7 and qn.max() <= 7
    err = np.abs(qn * np.asarray(s)[..., None] - np.asarray(x))
    assert (err <= np.asarray(s)[..., None] * 0.5 + 1e-6).all()


# ---------------------------------------------------------------------------
# kv4 three-way parity (gather oracle / XLA fallback / interpreted kernel)


def _mk_int4_pool(rng, P, KV, ps, hd):
    """Random packed int4 pages: arbitrary bytes unpack to nibbles in [-8, 7]."""
    return {
        "k": jnp.asarray(rng.randint(0, 256, (P, KV, ps // 2, hd)), jnp.uint8),
        "v": jnp.asarray(rng.randint(0, 256, (P, KV, ps // 2, hd)), jnp.uint8),
        "k_scale": jnp.asarray(rng.rand(P, KV, ps) * 0.1 + 0.01, jnp.float32),
        "v_scale": jnp.asarray(rng.rand(P, KV, ps) * 0.1 + 0.01, jnp.float32),
    }


def _mk_int4_case(rng, qn, ps, B=3, Tp=4, KV=2, rep=2, hd=16):
    """Ragged lanes: lane b owns b+2 pages (capped at Tp), the rest trash."""
    P = B * Tp + 1
    H = KV * rep
    pool = _mk_int4_pool(rng, P, KV, ps, hd)
    table = np.full((B, Tp), kvc.TRASH_PAGE, np.int32)
    pages = iter(range(1, P))
    pos = []
    for b in range(B):
        npg = min(Tp, b + 2)
        for t in range(npg):
            table[b, t] = next(pages)
        pos.append(max((npg - 1) * ps - qn - b, 0))
    return (
        pool,
        jnp.asarray(table),
        jnp.asarray(pos, jnp.int32),
        jnp.asarray(rng.randn(B, qn, H, hd), jnp.float32),
        jnp.asarray(rng.randn(B, qn, KV, hd), jnp.float32),
        jnp.asarray(rng.randn(B, qn, KV, hd), jnp.float32),
    )


_POOL_KEYS = ("k", "v", "k_scale", "v_scale")


@pytest.mark.parametrize("ps", [8, 16, 64])
@pytest.mark.parametrize("qn", [1, 4])
def test_int4_three_way_bitwise_parity(ps, qn):
    """int4 pages: all three paths share the dequant + online-softmax
    recurrence, so outputs AND appended pools are bitwise equal (the int8
    tier is only tolerance-equal here — its gather path requantizes)."""
    rng = np.random.RandomState(ps * 131 + qn)
    args = _mk_int4_case(rng, qn, ps)
    assert pa.pool_kind(args[0]) == "int4"
    o_g, p_g = ops.paged_attention(*args, force="gather")
    o_x, p_x = ops.paged_attention(*args, force="ref")
    o_k, p_k = ops.paged_attention(*args, force="interpret")
    np.testing.assert_array_equal(np.asarray(o_g), np.asarray(o_x))
    np.testing.assert_array_equal(np.asarray(o_g), np.asarray(o_k))
    for key in _POOL_KEYS:
        np.testing.assert_array_equal(np.asarray(p_g[key]), np.asarray(p_x[key]))
        np.testing.assert_array_equal(np.asarray(p_g[key]), np.asarray(p_k[key]))


@pytest.mark.parametrize("force", ["gather", "ref", "interpret"])
def test_int4_trash_page_invariant(force):
    """Poisoning page 0 (0xFF bytes, NaN scales) changes no lane's output."""
    rng = np.random.RandomState(99)
    args = _mk_int4_case(rng, 2, 16)
    clean, _ = ops.paged_attention(*args, force=force)
    pool = dict(args[0])
    pool["k"] = pool["k"].at[kvc.TRASH_PAGE].set(255)
    pool["v"] = pool["v"].at[kvc.TRASH_PAGE].set(255)
    pool["k_scale"] = pool["k_scale"].at[kvc.TRASH_PAGE].set(jnp.nan)
    pool["v_scale"] = pool["v_scale"].at[kvc.TRASH_PAGE].set(jnp.nan)
    dirty, _ = ops.paged_attention(pool, *args[1:], force=force)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def test_int4_pool_init_layout():
    """kv_bits=4 pools pack two tokens per byte; scales keep int8 layout."""
    cfg = dataclasses.replace(smoke_config("deepseek-7b"), kv_bits=4)
    pool = kvc.init_page_pool(cfg, 5, 8)
    assert pool["k"].dtype == jnp.uint8
    assert pool["k"].shape == (5, cfg.n_kv_heads, 4, cfg.hd)  # 2 tokens/row
    assert pool["k_scale"].shape == (5, cfg.n_kv_heads, 8)
    assert pa.pool_kind(pool) == "int4"
    # bytes/token halves the value payload vs int8; scales are unchanged.
    c4 = kvc.kv_bytes_per_token(cfg)
    c8 = kvc.kv_bytes_per_token(dataclasses.replace(cfg, kv_bits=8))
    per_row8 = 2 * cfg.hd + 2 * 4
    assert c8 - c4 == cfg.n_layers * cfg.n_kv_heads * (per_row8 - (cfg.hd + 8))


# ---------------------------------------------------------------------------
# W4A8 matmul: kernel vs ref bit-exactness through the jitted dispatch


def _mk_w4a8(rng, k, n, ratio, ocs_ratio):
    w = rng.randn(k, n).astype(np.float32)
    w[rng.choice(k, 3, replace=False)] *= 10.0  # plant outlier input channels
    lin = ocs.make_ocs_quant_linear(w, ocs_ratio, 8, per_channel=True, pad_to=1)
    lin4 = ocs.to_w4a8(lin, ratio)
    return w, lin4, lin4.spec.src[lin4.n_orig:]


@pytest.mark.parametrize(
    "k,n,ratio,ocs_ratio",
    [
        (128, 128, 0.0, 0.0),
        (128, 128, 0.1, 0.0),
        (96, 80, 0.0, 0.05),  # odd expanded dim: 96 + 5 -> padded to 102
        (96, 80, 0.1, 0.05),
        (200, 144, 0.25, 0.1),
    ],
)
def test_w4a8_kernel_bitexact_vs_ref(k, n, ratio, ocs_ratio):
    """force="ref" and force="interpret" agree bit for bit under jit (both
    share the reciprocal-multiply activation quant and the grouped
    ``acc*(a_s*s)`` epilogue)."""
    rng = np.random.RandomState(k * 7919 + n * 131 + int(ratio * 100) + int(ocs_ratio * 1000))
    _, lin4, src_tail = _mk_w4a8(rng, k, n, ratio, ocs_ratio)
    x = jnp.asarray(rng.randn(24, k), jnp.float32)
    a = (x, lin4.w4, lin4.s4, lin4.w8, lin4.s8, src_tail, lin4.outlier_idx)
    y_ref = ops.w4a8_matmul(*a, force="ref")
    y_krn = ops.w4a8_matmul(*a, force="interpret")
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_krn))


def test_w4a8_matches_float_composition():
    """The two-accumulator partition equals q_exp @ dequant_weight."""
    rng = np.random.RandomState(17)
    _, lin4, src_tail = _mk_w4a8(rng, 128, 64, 0.1, 0.05)
    x = jnp.asarray(rng.randn(16, 128), jnp.float32)
    y = np.asarray(ops.w4a8_matmul(
        x, lin4.w4, lin4.s4, lin4.w8, lin4.s8, src_tail, lin4.outlier_idx,
        force="ref",
    ))
    q, a_s = pa.quant_rows(x, qmax=127.0)
    q_exp = jnp.concatenate([q, jnp.take(q, src_tail, axis=1)], axis=1)
    xf = np.asarray(q_exp, np.float32) * np.asarray(a_s)[:, None]
    want = xf @ np.asarray(lin4.dequant_weight())
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# to_w4a8: outlier separation


def _plain_lin(rng, k=64, n=32, planted=()):
    w = rng.randn(k, n).astype(np.float32)
    for ch, mag in planted:
        w[ch] *= mag
    return w, ocs.make_ocs_quant_linear(w, 0.0, 8, per_channel=True, pad_to=1)


def test_to_w4a8_outlier_count_ranking_and_partition():
    rng = np.random.RandomState(3)
    w, lin = _plain_lin(rng, planted=[(5, 20.0), (17, 15.0)])
    lin4 = ocs.to_w4a8(lin, 0.1)
    assert lin4.n_outliers == ocs.n_splits_for_ratio(64, 0.1)
    assert lin4.k_expanded == 64
    oi = np.asarray(lin4.outlier_idx)
    assert {5, 17} <= set(oi.tolist())  # max|W| ranking catches the plants
    assert (np.diff(oi) > 0).all()  # sorted, unique
    # Outlier rows are zeroed inside w4: the accumulators partition the sum.
    wq = np.asarray(pa.unpack_int4(lin4.w4.T).T)
    assert (wq[oi] == 0).all()
    assert np.abs(wq).max() <= 7


def test_to_w4a8_separation_improves_reconstruction():
    """The acceptance criterion in weight space: separating the planted
    outlier channels shrinks the int4 grid for everything else."""
    rng = np.random.RandomState(8)
    w, lin = _plain_lin(rng, planted=[(2, 25.0), (9, 25.0), (33, 25.0)])
    def err(ratio):
        d = np.asarray(ocs.to_w4a8(lin, ratio).dequant_weight())
        return float(np.linalg.norm(d - w))
    assert err(0.1) < 0.5 * err(0.0)


def test_to_w4a8_odd_expanded_dim_pads_with_dead_spec_entry():
    rng = np.random.RandomState(12)
    w = rng.randn(63, 16).astype(np.float32)
    lin = ocs.make_ocs_quant_linear(w, 0.0, 8, per_channel=True, pad_to=1)
    lin4 = ocs.to_w4a8(lin, 0.0)
    assert lin4.k_expanded == 64
    assert lin4.spec.src.shape[-1] == 64
    assert float(lin4.spec.mult[-1]) == 0.0  # dead duplicate: contributes 0
    wq = np.asarray(pa.unpack_int4(lin4.w4.T).T)
    assert (wq[63] == 0).all()  # the pad row quantizes exactly to zero


def test_to_w4a8_stacked_leaves_keep_layer_dim():
    """Scan-sliced (stacked) leaves convert per layer with the lead dim kept."""
    from repro.core.apply import _quant_linear_stacked

    rng = np.random.RandomState(21)
    wa, _ = _plain_lin(rng, planted=[(4, 12.0)])
    wb, _ = _plain_lin(rng, planted=[(40, 12.0)])
    recipe = QuantRecipe(w_bits=8, ocs_ratio=0.0, per_channel=True, pad_to=1)
    stacked = _quant_linear_stacked(np.stack([wa, wb]), recipe)
    la = _quant_linear_stacked(wa, recipe)
    lb = _quant_linear_stacked(wb, recipe)
    l4 = ocs.to_w4a8(stacked, 0.1)
    assert l4.w4.shape == (2, 32, 32)
    assert l4.w8.shape[0] == 2
    pa_, pb_ = ocs.to_w4a8(la, 0.1), ocs.to_w4a8(lb, 0.1)
    np.testing.assert_array_equal(np.asarray(l4.w4[0]), np.asarray(pa_.w4))
    np.testing.assert_array_equal(np.asarray(l4.w4[1]), np.asarray(pb_.w4))
    np.testing.assert_array_equal(
        np.asarray(l4.outlier_idx[1]), np.asarray(pb_.outlier_idx)
    )


def test_to_w4a8_ratio_validation():
    rng = np.random.RandomState(1)
    _, lin = _plain_lin(rng)
    with pytest.raises(ValueError, match="ratio"):
        ocs.to_w4a8(lin, 1.5)


# ---------------------------------------------------------------------------
# Config validation


def test_engine_config_precision_validation():
    with pytest.raises(ValueError, match="kv_bits"):
        EngineConfig(kv_bits=5)
    with pytest.raises(ConfigError, match="int4"):
        EngineConfig(kv_bits=4, paged=False)
    with pytest.raises(ValueError, match="w4a8_outlier_ratio"):
        EngineConfig(w4a8_outlier_ratio=1.5)
    with pytest.raises(ConfigError, match="draft_mode"):
        EngineConfig(matmul_mode="w4a8", spec=SpecConfig())
    # The valid combinations construct fine.
    EngineConfig(kv_bits=4)
    EngineConfig(matmul_mode="w4a8", spec=SpecConfig(draft_mode="w4a8"))


# ---------------------------------------------------------------------------
# Engine integration


@pytest.fixture(scope="module")
def dense_setup():
    cfg = smoke_config("glm4-9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def quant_setup(dense_setup):
    cfg, params = dense_setup
    recipe = QuantRecipe(w_bits=8, ocs_ratio=0.02, per_channel=True, pad_to=1)
    return cfg, quantize_params(params, recipe)


def _serve(cfg, params, seed, max_new=8, **conf):
    eng = ServingEngine(
        cfg, params, EngineConfig(max_batch=2, max_len=64, **conf)
    )
    rng = np.random.default_rng(seed)
    reqs = [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab, n).tolist(),
                max_new_tokens=max_new)
        for i, n in enumerate([5, 11, 3, 17])
    ]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.finish_reason in ("eos", "length") for r in reqs)
    return eng, {r.uid: list(r.output) for r in reqs}


def _agreement(a, b):
    tot = match = 0
    for uid in a:
        for x, y in zip(a[uid], b[uid]):
            tot += 1
            match += int(x == y)
    return match, tot


def test_engine_int4_vs_int8_token_agreement(dense_setup):
    """Pinned knife-edge seed: the random-weight smoke model flips argmax
    easily under 4-bit KV error, so assert majority agreement, not identity
    (seed 7 observed 22/32)."""
    cfg, params = dense_setup
    eng8, o8 = _serve(cfg, params, 7, kv_bits=8)
    eng4, o4 = _serve(cfg, params, 7, kv_bits=4)
    match, tot = _agreement(o8, o4)
    assert tot == 32 and match >= 16, (match, tot)
    s8, s4 = eng8.stats(), eng4.stats()
    assert s8["kv_bits"] == 8.0 and s4["kv_bits"] == 4.0
    assert 0 < s4["kv_bytes_per_token"] < s8["kv_bytes_per_token"]
    assert s4["kv_pool_capacity_tokens"] > 0


def test_engine_w4a8_serving_agreement(quant_setup):
    """W4A8 must stay close to dequant serving on the same quantized tree —
    the same bar as test_engine_w8a8_serving, one tier down (seed 2
    observed 15/32 on the random-weight smoke model)."""
    cfg, qparams = quant_setup
    _, od = _serve(cfg, qparams, 2)
    engw, ow = _serve(
        cfg, qparams, 2, matmul_mode="w4a8", w4a8_outlier_ratio=0.25
    )
    match, tot = _agreement(od, ow)
    assert tot == 32 and match >= 8, (match, tot)
    assert engw.stats()["completed"] == 4


def test_engine_combined_sub8_tier_serves(quant_setup):
    """The full sub-8-bit tier — int4 KV pages AND W4A8 matmuls — serves
    every request to completion with the v10 gauges reporting the tier."""
    cfg, qparams = quant_setup
    eng, out = _serve(
        cfg, qparams, 5,
        kv_bits=4, matmul_mode="w4a8", w4a8_outlier_ratio=0.25,
    )
    assert all(len(v) == 8 for v in out.values())
    s = eng.stats()
    assert s["kv_bits"] == 4.0 and s["completed"] == 4
