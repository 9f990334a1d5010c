"""int8 KV cache: decode equivalence vs the bf16/f32 cache within quant error."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models import transformer as T


@pytest.mark.parametrize("arch", ["deepseek-7b", "qwen3-14b"])
def test_int8_cache_matches_float_decode(arch):
    cfg = smoke_config(arch)
    cfg8 = dataclasses.replace(cfg, kv_bits=8)
    params = T.per_layer(T.init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab, (2, 12))

    def decode_all(c):
        caches = T.init_cache(c, 2, 32, dtype=jnp.float32)
        outs = []
        for t in range(tokens.shape[1]):
            logits, caches = T.decode_step(
                params, jnp.asarray(tokens[:, t : t + 1]), caches, c)
            outs.append(np.asarray(logits, np.float32))
        return np.stack(outs)

    ref = decode_all(cfg)
    got = decode_all(cfg8)
    assert np.isfinite(got).all()
    # int8 cache: logits agree to quantization noise; argmax almost always.
    denom = np.maximum(np.abs(ref).max(), 1e-6)
    rel = np.abs(got - ref).max() / denom
    assert rel < 0.08, rel
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.9, agree


@pytest.mark.parametrize("arch", ["deepseek-7b", "qwen3-14b"])
def test_int8_paged_cache_matches_float_decode(arch):
    """Paged int8 cache: same tolerances as the contiguous int8 cache vs the
    float decode — pages reuse the identical per-row linear quant grid, so
    the paged/contiguous int8 paths are bitwise equal and both sit within
    quantization noise of the float reference."""
    from repro.serving import kv_cache as kvc

    cfg = smoke_config(arch)
    cfg8 = dataclasses.replace(cfg, kv_bits=8)
    params = T.per_layer(T.init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab, (2, 12))
    B, L, ps = 2, 32, 8

    def decode_all(c, paged):
        if paged:
            t = L // ps
            caches = kvc.init_paged_cache(c, B, B * t + 1, ps, t, dtype=jnp.float32)
            caches["table"] = jnp.asarray(
                np.arange(1, B * t + 1, dtype=np.int32).reshape(B, t)
            )
        else:
            caches = T.init_cache(c, B, L, dtype=jnp.float32)
        outs = []
        for i in range(tokens.shape[1]):
            logits, caches = T.decode_step(
                params, jnp.asarray(tokens[:, i : i + 1]), caches, c)
            outs.append(np.asarray(logits, np.float32))
        return np.stack(outs)

    ref = decode_all(cfg, paged=False)
    got = decode_all(cfg8, paged=True)
    np.testing.assert_array_equal(got, decode_all(cfg8, paged=False))
    assert np.isfinite(got).all()
    denom = np.maximum(np.abs(ref).max(), 1e-6)
    rel = np.abs(got - ref).max() / denom
    assert rel < 0.08, rel
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.9, agree


def test_int8_page_pool_structure():
    from repro.serving import kv_cache as kvc

    cfg = dataclasses.replace(smoke_config("deepseek-7b"), kv_bits=8)
    pool = kvc.init_page_pool(cfg, n_pages=8, page_size=4)
    assert pool["k"].dtype == jnp.int8
    assert pool["k"].shape == (8, cfg.n_kv_heads, 4, cfg.hd)
    assert pool["k_scale"].shape == (8, cfg.n_kv_heads, 4)
    int8_bytes = pool["k"].size + 4 * pool["k_scale"].size
    bf16_bytes = 2 * pool["k"].size
    assert int8_bytes < 0.78 * bf16_bytes


def test_int8_cache_structure():
    cfg = dataclasses.replace(smoke_config("deepseek-7b"), kv_bits=8)
    caches = T.init_cache(cfg, 2, 16)
    layer0 = caches["layers"][0]["attn"]
    assert layer0["k"].dtype == jnp.int8
    assert layer0["k_scale"].shape == (2, cfg.n_kv_heads, 16)
    # Bytes: int8 values + f32 scales ~= 0.5x the bf16 cache + small overhead.
    int8_bytes = layer0["k"].size + 4 * layer0["k_scale"].size
    bf16_bytes = 2 * layer0["k"].size
    assert int8_bytes < 0.78 * bf16_bytes
