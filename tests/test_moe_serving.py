"""The held-expert MoE layer against the plain float32 reference
(``bench/reference/moe.py``), at smoke size on the CPU.

The smoke model (``bench/testdata/smoke-moe.json``) keeps the served
configuration's structure: a dense layer 0, then a layer of 8 routed
experts (top-3, gates used as they are) and a shared expert, 4 of the 8
experts held. Weights are the seeded draws both sides make.
"""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import moe as RM
from bench.run import model_config
from repro.core.apply import quantize_params
from repro.kernels import ops as kops
from repro.launch.weights import SERVE_RECIPE, init_params_host, load_quantized
from repro.models import layers
from repro.models import moe as M
from repro.models import transformer as T
from repro.serving import kv_cache as kvc

SMOKE = json.loads((Path(__file__).parents[1] / "bench" / "testdata"
                    / "smoke-moe.json").read_text())
MODEL = SMOKE["model"]
SEED = 2**31 + 17


def _cfg(**moe):
    cfg = model_config(MODEL)
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe))


def _moe_tree(cfg, seed=SEED):
    """The float32 expert layer of the seeded model (its layer 1)."""
    return T.split_layers(init_params_host(cfg, seed)["layers"])[1]["moe"]


def _x(n, d, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, n, d), jnp.float32)


def _shared(w, x):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def test_held_layer_matches_the_reference_layer():
    """The program's layer holding 4 of 8 experts, float32 throughout, is
    the reference's layer: routing over all 8, the 4 held experts' part of
    the routed output and the shared expert."""
    cfg = _cfg()
    w = _moe_tree(cfg)
    x = _x(40, cfg.d_model)
    # The block normalises the layer's input; the reference layer does so
    # inside.
    got = M.moe(w, RM.D._rms(x, cfg.norm_eps), cfg)[0]
    with jax.default_matmul_precision("highest"):
        want = RM._moe(x[0], w, k=cfg.moe.top_k, norm=cfg.moe.norm_topk,
                       eps=cfg.norm_eps) - x[0]
    # Both float32; the program normalises before the layer, the
    # reference inside it: sums in another order, a few ulps.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * float(jnp.abs(want).max()))


def test_four_shares_add_up_to_the_uncut_layer():
    """Four chips holding experts [0,2), [2,4), [4,6), [6,8): their routed
    parts, and the shared expert counted once, add up to the uncut
    reference layer. A share other than [0, n) is held here by relabelling
    the router's columns, which routes every token alike."""
    whole = _cfg(n_held=0)
    share = _cfg(n_held=2)
    w = _moe_tree(whole)
    x = _x(24, whole.d_model, seed=1)[0]
    h = RM.D._rms(x, whole.norm_eps)
    routed = 0.0
    for s in range(4):
        ids = (2 * s + np.arange(8)) % 8
        ws = {"router": w["router"][:, ids],
              "experts": jax.tree.map(lambda a: a[ids[:2]], w["experts"])}
        routed = routed + M._moe_held(ws, h, share)
    with jax.default_matmul_precision("highest"):
        want = RM._moe(x, w, k=whole.moe.top_k, norm=whole.moe.norm_topk,
                       eps=whole.norm_eps) - x
        got = routed + _shared(w["shared"], h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_a_token_alone_equals_it_in_a_batch_of_64(kernel):
    """Dropless and combined per token in a fixed order: a token's output
    does not depend on the other tokens of its batch, bit for bit, on the
    batched path (xla) and the grouped kernel's path (pallas; its XLA
    oracle on the CPU)."""
    cfg = _cfg()
    q = quantize_params({"moe": _moe_tree(cfg)}, SERVE_RECIPE)["moe"]
    x = _x(64, cfg.d_model, seed=2).astype(jnp.bfloat16)
    with layers.serving_mode("dequant", kernel=kernel):
        batch = np.asarray(M.moe(q, x, cfg), np.float32)
        alone = [np.asarray(M.moe(q, x[:, i:i + 1], cfg), np.float32)
                 for i in range(64)]
    np.testing.assert_array_equal(batch, np.concatenate(alone, axis=1))


def test_grouped_kernel_and_batched_path_agree():
    """The same quantized layer through the grouped kernel's oracle and the
    batched per-tile path: the kernel scales after the float32 sum, the
    batched path multiplies bfloat16-dequantized weights, so they differ by
    bfloat16 rounding only."""
    cfg = _cfg()
    q = quantize_params({"moe": _moe_tree(cfg)}, SERVE_RECIPE)["moe"]
    x = _x(32, cfg.d_model, seed=3).astype(jnp.bfloat16)
    out = {}
    for kernel in ("xla", "pallas"):
        with layers.serving_mode("dequant", kernel=kernel):
            out[kernel] = np.asarray(M.moe(q, x, cfg), np.float32)
    scale = np.abs(out["xla"]).max()
    assert np.abs(out["pallas"] - out["xla"]).max() < 0.02 * scale


def test_counters_are_rows_experts_and_no_drops():
    cfg = _cfg()
    w = _moe_tree(cfg)
    x = _x(50, cfg.d_model, seed=4)[0]
    with M.counting() as counts:
        M._moe_held(w, x, cfg)
    rows, touched, dropped = (int(v) for v in counts[0])
    probs = jax.nn.softmax(x @ w["router"], axis=-1)
    _, idx = jax.lax.top_k(probs, cfg.moe.top_k)
    held = np.asarray(idx) < cfg.moe.n_held
    assert rows == held.sum() and dropped == 0
    assert touched == len(np.unique(np.asarray(idx)[held]))


def _greedy(cfg, params, prompt, steps, max_len=64, ps=8):
    """Prefill into int8 pages, then paged decode steps: the functions the
    engine runs, each position's logits and the greedy tokens."""
    pages = max_len // ps
    caches = kvc.init_paged_cache(cfg, 1, pages + 1, ps, pages, dtype=jnp.float32)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    caches["table"] = table
    n = len(prompt)
    bucket = -(-n // ps) * ps
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = prompt
    pools = [layer["attn"] for layer in caches["layers"]]
    with layers.serving_mode("dequant", kernel="pallas"):
        lg, pools = T.prefill_into_pages(
            params, jnp.asarray(toks), cfg, pools, table[0, : bucket // ps],
            length=jnp.asarray([n], jnp.int32),
            prefix_ids=jnp.zeros((0,), jnp.int32))
        caches["layers"] = [{"attn": p} for p in pools]
        caches["pos"] = jnp.asarray([n], jnp.int32)
        out, served = [np.asarray(lg[0], np.float32)], []
        for _ in range(steps):
            served.append(int(np.argmax(out[-1])))
            lg, caches = T.decode_step(params, jnp.asarray([[served[-1]]]),
                                       caches, cfg, attn_kernel="pallas")
            out.append(np.asarray(lg[0], np.float32))
    return np.stack(out), served


# Logit error as a share of the row's spread: bfloat16 activations and int8
# pages read 0.05-0.29 on float and int8 OCS weights (3 seeds x 2 prompts;
# the widest where a near-tied route flips), int4 weights 1.6-2.1.
LOGIT_TOL = 0.5


@pytest.mark.parametrize("bits", [None, 8, 4], ids=["float", "int8", "int4"])
def test_prefill_then_paged_decode_match_the_reference(bits):
    """Prefill, then six paged decode steps through int8 pages, on logits
    against the float32 reference fed the same tokens: within
    ``LOGIT_TOL`` of each row's spread on float and int8 OCS weights, past
    it on int4 weights (the precision below the served one). A wrong
    route, expert or gate moves logits by the order of the spread."""
    cfg = dataclasses.replace(_cfg(), kv_bits=8)
    params = (T.per_layer(init_params_host(cfg, SEED)) if bits is None else
              load_quantized(cfg, SEED, dataclasses.replace(SERVE_RECIPE, w_bits=bits)))
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, 21).tolist()
    got, served = _greedy(cfg, params, prompt, 6)
    want = RM.logits(MODEL, SEED, prompt + served)[len(prompt) - 1:]
    err = np.abs(got - want).max(axis=1) / want.std(axis=1)
    assert bool(err.max() < LOGIT_TOL) == (bits != 4), err


def test_ops_dispatch_runs_the_oracle_off_the_chip():
    """Off the TPU the grouped matmul's dispatch runs its XLA oracle."""
    assert kops.backend_mode() == "ref"
