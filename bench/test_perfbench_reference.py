"""The float32 reference against the program at smoke size, on the CPU."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import dense as RM
from bench.reference import weights as RW

SMOKE = json.loads((Path(__file__).parent / "testdata" / "smoke.json").read_text())
MODEL = SMOKE["model"]


def _cfg():
    from repro.configs.base import ModelConfig

    return ModelConfig(**MODEL)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_weights_are_the_loaders_float_weights(seed):
    from repro.launch.weights import init_params_host

    p = init_params_host(_cfg(), seed)
    np.testing.assert_array_equal(np.asarray(p["embed"]), RW.embed(MODEL, seed))
    np.testing.assert_array_equal(np.asarray(p["lm_head"]), RW.lm_head(MODEL, seed))
    for layer in range(MODEL["n_layers"]):
        for name in RW.MATRICES:
            group = "mlp" if name.startswith("w_") else "attn"
            np.testing.assert_array_equal(
                np.asarray(p["layers"][group][name][layer]),
                RW.layer_matrix(MODEL, seed, layer, name))
    for leaf in ("final_norm",):
        assert np.all(np.asarray(p[leaf]["scale"]) == 1.0)


@pytest.mark.parametrize("arch", ["glm4-9b", "deepseek-moe-16b"])
def test_draw_is_the_loaders_rule_for_every_block(arch):
    """Given its block's sorted leaf list, ``weights.draw`` gives each slice
    the loader draws: a layer of a per-layer matrix, an expert of a layer
    (row-major over the stack dims), or the whole leaf."""
    from repro.configs import smoke_config
    from repro.core.apply import path_str
    from repro.launch.weights import init_params_host

    cfg = smoke_config(arch)
    seed = 2**31 + 11
    flat = jax.tree_util.tree_flatten_with_path(init_params_host(cfg, seed))[0]
    leaves = [path_str(p) for p, _ in flat]
    assert leaves == sorted(leaves)
    drawn = 0
    for path, a in flat:
        a = np.asarray(a)
        name = path_str(path)
        if "norm" in name or "scale" in name:
            continue
        std = 0.02 if name == "embed" else 1.0 / np.sqrt(a.shape[-2])
        lead = a.shape[:-2]
        for i, idx in enumerate(np.ndindex(*lead)):
            np.testing.assert_array_equal(
                a[idx], RW.draw(seed, leaves, name, i, a.shape[-2:], std))
            drawn += 1
    assert drawn > (20 if cfg.moe else 10)


def _float_forward(seed, tokens):
    from repro.launch.weights import init_params_host
    from repro.models import transformer as T

    cfg = dataclasses.replace(_cfg(), remat=False)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), init_params_host(cfg, seed))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(p["embed"], jnp.asarray(tokens)[None], axis=0)
        return np.asarray(T.forward(p, None, cfg, embeds=x.astype(jnp.float32))[0],
                          np.float32)


def test_reference_gaps_of_the_float_models_own_tokens_are_zero():
    """Served tokens that are the float model's argmax read a gap near 0."""
    seed = 3
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, MODEL["vocab"], 40).astype(np.int32)
    toks = list(prompt)
    served = []
    for _ in range(6):  # greedy decode by the program's float forward
        nxt = int(np.argmax(_float_forward(seed, np.asarray(toks))[-1]))
        served.append(nxt)
        toks.append(nxt)
    gaps = RM.served_gaps(MODEL, seed, [(prompt, served)], control=True)
    assert gaps["served"][0].shape == (6,)
    # The program's forward carries bfloat16 activations: near-ties may
    # flip, by a small fraction of the logits' spread.
    assert np.max(gaps["served"][0]) < 0.05
    assert gaps["control"][0].shape == (6,)


def test_reference_logits_match_the_programs_float_forward():
    """The reference pass and the program's own float pass agree."""
    seed = 4
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, MODEL["vocab"], 30).astype(np.int32)
    served = list(rng.integers(0, MODEL["vocab"], 5))
    want = _float_forward(seed, np.concatenate([prompt, served[:-1]]))[29:34]
    gaps = RM.served_gaps(MODEL, seed, [(prompt, served)])["served"][0]
    best, std = want.max(axis=1), want.std(axis=1)
    ref = (best - want[np.arange(5), served]) / std
    # Equal up to the program's bfloat16 activations (about 2^-9 of each
    # value); a wrong rotation, head grouping or norm moves a gap by the
    # order of the logits' spread.
    np.testing.assert_allclose(gaps, ref, atol=0.05)


def test_int4_control_rounds_each_column_to_fifteen_levels():
    w = jnp.asarray(np.random.default_rng(2).standard_normal((64, 8)), jnp.float32)
    q = np.asarray(RM._int4(w))
    for j in range(8):
        step = np.abs(np.asarray(w)[:, j]).max() / 7
        levels = np.unique(np.round(q[:, j] / step))
        assert len(levels) <= 15 and np.abs(levels).max() <= 7
