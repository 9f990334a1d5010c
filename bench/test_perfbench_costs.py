"""``bench/costs.py`` at sizes small enough to count by hand."""
import math

import numpy as np
import pytest

from bench import costs

RECIPE = {"w_bits": 8, "ocs_ratio": 0.0, "pad_to": 1}
TINY = {"d_model": 4, "d_ff": 6, "n_heads": 2, "n_kv_heads": 1, "head_dim": 4,
        "vocab": 10, "n_layers": 3}


@pytest.mark.parametrize("k,ratio,pad,want", [
    (100, 0.0, 1, 100),
    (100, 0.02, 1, 102),
    (100, 0.02, 128, 128),
    (4096, 0.02, 128, 4224),
    (13696, 0.02, 128, 14080),
])
def test_expanded(k, ratio, pad, want):
    assert costs.expanded(k, ratio, pad) == want


def test_ocs_matmul_by_hand():
    # 2 rows x 4 in x 3 out: 2*2*4*3 flops; 12 int8 weight bytes, 3 f32
    # scales, 2 rows of 4 bf16 in and 3 bf16 out.
    assert costs.ocs_matmul(2, 4, 3, RECIPE) == (48.0, 12 + 12 + 2 * 2 * 7)


def test_ocs_matmul_counts_the_expansion():
    r = {"w_bits": 8, "ocs_ratio": 0.02, "pad_to": 128}
    f, b = costs.ocs_matmul(1, 100, 1, r)
    assert f == 2 * 128 and b == 128 + 4 + 2 * 101


def test_decode_matmuls_cover_every_linear_and_the_head():
    calls = costs.decode_matmuls(TINY, RECIPE, rows=1)
    assert len(calls) == 7 * TINY["n_layers"] + 1
    # Per layer: q 4x8, k 4x4, v 4x4, o 8x4, gate 4x6, up 4x6, down 6x4.
    per_layer = 2 * (32 + 16 + 16 + 32 + 24 + 24 + 24)
    head = 2 * 4 * 10
    assert sum(f for f, _ in calls) == 3 * per_layer + head


def test_paged_attention_by_hand():
    # hd 4, 2 heads over 1 KV head, int8 pages of 2 tokens: a token's K and V
    # are 2 * (4 + 4 scale bytes) = 16 bytes.
    assert costs.kv_bytes_per_token(TINY, 8) == 16
    f, b = costs.paged_attention(TINY, 8, 2, [0, 3])
    # Lane at 0 attends 1 token over 1 page; lane at 3 attends 4 over 2.
    assert f == 4 * 2 * 4 * (1 + 4)
    rows = 4 * 4 * (2 * 2 + 2 * 1)  # q, out, new k, new v in float32
    assert b == (1 * 2 * 16 + rows + 16) + (2 * 2 * 16 + rows + 16)


def test_least_time_is_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_time(50.0, 1.0, peaks) == 0.5
    assert costs.least_time(1.0, 50.0, peaks) == 5.0


def test_decode_step_parts_add_up():
    parts = costs.decode_step(TINY, RECIPE, 8, 2, [0, 3])
    mm = costs.decode_matmuls(TINY, RECIPE, 2)
    att = costs.paged_attention(TINY, 8, 2, [0, 3])
    assert parts["matmul"] == (sum(f for f, _ in mm), sum(b for _, b in mm))
    assert parts["attention"] == (3 * att[0], 3 * att[1])


def test_expanded_matches_the_quantized_weights():
    """The expansion the costs assume is the one the loader builds."""
    from repro.core.apply import quant_slice
    from repro.core.recipe import QuantRecipe

    recipe = QuantRecipe(w_bits=8, w_clip="mse", ocs_ratio=0.02,
                         per_channel=True, pad_to=128)
    for k in (64, 200):
        w = np.random.default_rng(k).standard_normal((k, 32), dtype=np.float32)
        lin = quant_slice(w, recipe)
        assert lin.weight.values.shape[0] == costs.expanded(k, 0.02, 128)
        assert math.ceil(0.02 * k) == lin.weight.values.shape[0] - k - (
            -(k + math.ceil(0.02 * k)) % 128)
