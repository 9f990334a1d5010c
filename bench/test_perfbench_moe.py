"""The moe block's pieces of the benchmark at smoke size on the CPU: its
reference's weights and leaf list against the program's loader, a whole
run of a smoke moe cell (sound: correct; the int4 control in the
program's place: not correct), the grouped matmul's costs and the three
expert-layer metric readers.

The smoke moe model (``testdata/smoke-moe.json``: a dense layer, then 4 of
8 experts held) reads a widest gap of 0.048-0.067 and a mean of
0.0011-0.0017 on three seeds, the int4 control 1.37-2.21 and 0.21-0.30;
the smoke limits (0.4, 0.03) lie between.
"""
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import costs_moe
from bench import run as R
from bench.loadgen import load_mix
from bench.reference import moe as RM

HERE = Path(__file__).parent
SPEC = json.loads((HERE.parent / "BENCHMARK.json").read_text())
SMOKE = json.loads((HERE / "testdata" / "smoke-moe.json").read_text())
MODEL = SMOKE["model"]
CELL = "deepseek-moe-16b.short-chat"
CONFIG = json.loads((HERE / "configs" / "deepseek-moe-16b.json").read_text())


@pytest.fixture(autouse=True)
def _own_compile_cache(monkeypatch):
    """A run turns JAX's persistent cache on; give the settings back."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_leaf_list_is_the_programs():
    from repro.core.apply import path_str
    from repro.models import transformer as T

    shapes = T.model_params_shape(R.model_config(MODEL))
    flat = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]
    assert tuple(path_str(p) for p, _ in flat) == RM.MOE_LEAVES


def test_reference_draws_are_the_loaders_weights():
    """Every matrix the reference draws for a layer equals the loader's
    float slice: the held experts at ``l * n_experts + e``."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.launch.weights import init_params_host
    from repro.models import transformer as T

    seed = 2**31 + 3
    per = T.split_layers(init_params_host(R.model_config(MODEL), seed)["layers"])
    with ThreadPoolExecutor(2) as pool:
        for i, layer in enumerate(per):
            got = RM._nest({n: (np.stack([f.result() for f in v]) if isinstance(v, list)
                                else v.result())
                            for n, v in RM.draw_layer(MODEL, seed, i, pool).items()})
            want = {**layer["attn"], **layer.get("mlp", {}), **layer.get("moe", {})}
            assert set(got) == set(want)
            for n, w in got.items():
                for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(want[n])):
                    np.testing.assert_array_equal(a, np.asarray(b))


def _run(seed=123, control=False):
    mix = load_mix(HERE / "testdata" / "smoke-chat.json")
    return R.run_cell({"name": CELL}, SMOKE, mix, SPEC, seed, 1.0, False,
                      jax.devices("cpu"), control=control, log=lambda *a: None)


def test_sound_moe_run_is_correct():
    res = _run()
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tokens_per_s", "setup_s"}


def test_int4_control_of_the_moe_block_is_not_correct():
    res = _run(seed=7, control=True)
    assert not res["correct"]
    assert res["compared"]["served_gap_max"]["value"] > SMOKE["check"]["limits"]["served_gap_max"]


def test_costs_at_published_widths():
    """One held expert touched at decode reads its three int8 projections
    (K padded to 2176 and 1536 by OCS) and their float32 scales."""
    model, recipe = CONFIG["model"], CONFIG["recipe"]
    flops, nbytes = costs_moe.layer_calls(model, recipe, rows=0, touched=1)
    assert flops == 0
    assert nbytes == 2 * 2176 * 1408 + 1536 * 2048 + 4 * (2 * 1408 + 2048)
    flops, nbytes = costs_moe.layer_calls(model, recipe, rows=3, touched=0)
    assert flops == 2 * 3 * (2 * 2176 * 1408 + 1536 * 2048)
    assert nbytes == 2 * 3 * ((2048 + 1408) * 2 + (1408 + 2048))


def _run_ns(op_s=None, counters=None, program_s=None, program_runs=None):
    trace = None if op_s is None else {"op_s": op_s, "program_s": program_s or {},
                                       "program_runs": program_runs or {}}
    return types.SimpleNamespace(
        trace=trace, counters=counters or {}, config=CONFIG,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})


COUNTERS = {"moe_rows_decode": 300.0, "moe_experts_touched_decode": 100.0,
            "moe_rows_prefill": 0.0, "moe_experts_touched_prefill": 0.0}


def test_readers_read_the_kernel_and_the_counters():
    """``%moe_gmm`` and the unnumbered call's long label both count; other
    programs' and other kernels' labels do not."""
    least = costs_moe.least_seconds(CONFIG["model"], CONFIG["recipe"],
                                    COUNTERS, _run_ns().peaks)
    op_s = {"decode:%moe_gmm": 2 * least, "decode:%moe_gmm = bf16[432,1408]": least,
            "decode:%paged_attention": 5.0, "prefill:%fusion": 1.0}
    run = _run_ns(op_s, COUNTERS, {"decode": 6 * least})
    read = lambda name: R.metric_reader(name)(run)
    assert read("moe_gmm_roofline") == pytest.approx(100.0 / 3)
    assert read("moe.decode_share") == pytest.approx(50.0)
    assert read("moe.rows_per_expert") == pytest.approx(3.0)


@pytest.mark.parametrize("held", [4, 3, 0])
def test_roofline_counts_the_calls_the_trace_holds(held):
    """The counters cover 4 decode steps; a device trace that holds 3 of
    their programs (its capture started late) holds their share of the
    least time. A trace that names no runs, or all of them, takes all."""
    least = costs_moe.least_seconds(CONFIG["model"], CONFIG["recipe"],
                                    COUNTERS, _run_ns().peaks)
    counters = dict(COUNTERS, steps=4, prefill_calls=0)
    run = _run_ns({"decode:%moe_gmm": least}, counters, {"decode": 2 * least},
                  {"decode": held} if held else {})
    share = held / 4 if held else 1.0
    assert R.metric_reader("moe_gmm_roofline")(run) == pytest.approx(100.0 * share)


@pytest.mark.parametrize("name", ["moe_gmm_roofline", "moe.rows_per_expert",
                                  "moe.decode_share"])
def test_readers_read_nothing_from_a_program_without_the_layer(name):
    """A program without the held-expert layer (the parent commit, a dense
    model) has no ``moe_*`` counters and no ``%moe_gmm`` ops: each reader
    returns None and does not raise."""
    run = _run_ns({"decode:%ocs_quant_matmul": 1.0}, {"steps": 5},
                  {"decode": 2.0})
    assert R.metric_reader(name)(run) is None
    assert R.metric_reader(name)(_run_ns(None, {"steps": 5})) is None
