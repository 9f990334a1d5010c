"""The host-side load path: the linear OCS split loop and the seeded
slice-at-a-time loader must reproduce the whole-tree pipeline bit for bit.

* **split loop** — :func:`repro.core.ocs._run_splits` keeps a row-max vector
  instead of re-reducing the matrix per split; its output is compared with
  a straightforward copy of the previous quadratic loop (kept below as the
  oracle) over seeded matrices, including rows whose max |value| ties so
  ``argmax``'s first-index rule decides.
* **host passes** — the histogram and the split loop's row max run a block
  at a time, and the quantizer's range reduction and rounding run compiled;
  each is compared with a straightforward copy of the whole-array form.
* **loader** — :func:`repro.launch.weights.load_quantized`, its per-layer
  trees stacked again, equals ``quantize_params`` over the whole float tree
  built from the same seeded slices, leaf by leaf and bit for bit, at a
  small model size.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import histogram, ocs, quantizer
from repro.core.apply import quantize_params
from repro.launch import weights
from repro.launch.weights import SERVE_RECIPE, init_params_host, load_quantized


def _run_splits_oracle(w, n_splits, delta, qa):
    """The split loop as it was: recompute every row max per split and
    append with a full concatenate (O(splits x |W|))."""
    src = np.arange(w.shape[0], dtype=np.int32)
    w = w.copy()
    for _ in range(n_splits):
        idx = int(np.argmax(np.abs(w).max(axis=1)))
        row = w[idx]
        if qa and delta > 0:
            r1 = (row - 0.5 * delta) / 2.0
            r2 = (row + 0.5 * delta) / 2.0
        else:
            r1 = row / 2.0
            r2 = row / 2.0
        w = np.concatenate([w, r2[None]], axis=0)
        w[idx] = r1
        src = np.concatenate([src, src[idx : idx + 1]], axis=0)
    return w, src


def _tied(rng, rows, cols):
    """Rows whose max |value| ties: several rows share the peak, with both
    signs, so splits walk through the ties in first-index order."""
    w = rng.standard_normal((rows, cols)).astype(np.float32)
    peak = np.float32(4.0)
    for r in rng.choice(rows, size=min(rows, 5), replace=False):
        w[r, rng.integers(cols)] = peak if r % 2 else -peak
    return w


@pytest.mark.parametrize("qa,delta", [(True, 0.05), (False, 0.0), (True, 0.0)])
@pytest.mark.parametrize("case", ["random", "tied", "constant"])
def test_run_splits_bit_identical_to_quadratic_loop(case, qa, delta):
    rng = np.random.default_rng(7)
    if case == "random":
        w = rng.standard_normal((37, 19)).astype(np.float32)
    elif case == "tied":
        w = _tied(rng, 40, 16)
    else:  # every row ties at every split
        w = np.full((12, 8), 0.5, np.float32)
    for n in (0, 1, 9, 25):
        got_w, got_src = ocs._run_splits(w, n, delta, qa)
        want_w, want_src = _run_splits_oracle(w, n, delta, qa)
        assert got_w.dtype == want_w.dtype and got_src.dtype == want_src.dtype
        np.testing.assert_array_equal(got_w, want_w)
        np.testing.assert_array_equal(got_src, want_src)


def test_split_weights_grouped_matches_oracle_loop(monkeypatch):
    """The grouped (TP-local) splitter and the QA fixed point run the new
    loop; the whole split_weights result is unchanged."""
    rng = np.random.default_rng(3)
    w = _tied(rng, 64, 24)
    got = ocs.split_weights(w, 0.1, 8, clip_method="mse", groups=2)
    monkeypatch.setattr(ocs, "_run_splits", _run_splits_oracle)
    want = ocs.split_weights(w, 0.1, 8, clip_method="mse", groups=2)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(np.asarray(got[1].src), np.asarray(want[1].src))
    assert got[2] == want[2]


def _hist_update_oracle(h, x):
    """StreamingHistogram.update as it was: whole-array temporaries."""
    ax = np.abs(np.asarray(x, dtype=np.float32)).ravel()
    m = float(ax.max())
    h.max_seen = max(h.max_seen, m)
    if h.range == 0.0:
        h.range = m if m > 0 else 1.0
    while m > h.range:
        h._double_range()
    idx = np.minimum((ax * (h.n_bins / h.range)).astype(np.int64), h.n_bins - 1)
    np.add.at(h.counts, idx, 1)
    h.total += ax.size


def test_chunked_histogram_matches_whole_array(monkeypatch):
    """Blocks of 1000 elements (ragged tail), over updates that double the
    range: the same counts, range and max as one pass over the array."""
    monkeypatch.setattr(histogram, "CHUNK", 1000)
    rng = np.random.default_rng(5)
    got, want = histogram.StreamingHistogram(64), histogram.StreamingHistogram(64)
    for scale in (1.0, 3.0, 0.5, 40.0):
        x = (rng.standard_normal((77, 61)) * scale).astype(np.float32)
        x[3, 4] = 0.0
        got.update(x)
        _hist_update_oracle(want, x)
        np.testing.assert_array_equal(got.counts, want.counts)
        assert (got.range, got.max_seen, got.total) == (
            want.range, want.max_seen, want.total)


def test_row_absmax_in_blocks(monkeypatch):
    monkeypatch.setattr(ocs, "CHUNK", 50)  # 3 rows of 19 per block
    w = np.random.default_rng(1).standard_normal((37, 19)).astype(np.float32)
    np.testing.assert_array_equal(ocs._row_absmax(w), np.abs(w).max(axis=1))
    got_w, got_src = ocs._run_splits(w, 9, 0.05, True)
    want_w, want_src = _run_splits_oracle(w, 9, 0.05, True)
    np.testing.assert_array_equal(got_w, want_w)
    np.testing.assert_array_equal(got_src, want_src)


@pytest.mark.parametrize("channel_axis,clip", [(None, None), (1, None), (0, None),
                                               (None, 0.7)])
@pytest.mark.parametrize("bits", [4, 8])
def test_quantize_tensor_compiled_matches_eager(bits, channel_axis, clip):
    """The compiled range reduction and rounding equal the op-by-op eager
    forms bit for bit, grid-midpoint ties included."""
    rng = np.random.default_rng(bits)
    x = rng.standard_normal((96, 80)).astype(np.float32)
    x[::3] = np.round(x[::3] * 8) / 8 + 1 / 16  # ties at the grid midpoints
    xj = jnp.asarray(x)
    got = quantizer.quantize_tensor(xj, bits, channel_axis=channel_axis, clip=clip)
    if clip is not None:
        rng_ = jnp.asarray(clip, jnp.float32)
    elif channel_axis is None:
        rng_ = jnp.max(jnp.abs(xj))
    else:
        axes = tuple(i for i in range(2) if i != channel_axis)
        rng_ = jnp.max(jnp.abs(xj), axis=axes)
    q = quantizer.qmax(bits)
    scale = jnp.maximum(rng_, jnp.finfo(jnp.float32).tiny * q) / q
    s = scale if channel_axis is None else scale.reshape(
        [-1 if i == channel_axis else 1 for i in range(2)])
    vals = jnp.clip(jnp.floor(xj / s + 0.5), -q, q).astype(
        quantizer.storage_dtype(bits))
    np.testing.assert_array_equal(np.asarray(got.scale), np.asarray(scale))
    np.testing.assert_array_equal(np.asarray(got.values), np.asarray(vals))


@pytest.mark.parametrize("arch,layers,serial", [
    ("glm4-9b", 0, False),
    ("glm4-9b", 1, True),  # one layer; no memory for a second slice at once
    ("deepseek-moe-16b", 0, False),
])
def test_load_quantized_matches_whole_tree_quantize_params(
        arch, layers, serial, monkeypatch):
    cfg = smoke_config(arch)
    if arch == "glm4-9b":  # odd widths exercise the 128-lane padding
        cfg = dataclasses.replace(cfg, d_ff=200)
    if layers:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    want = quantize_params(init_params_host(cfg, seed=11), SERVE_RECIPE)
    monkeypatch.setattr(weights, "MAX_WORKERS", 3)
    if serial:
        monkeypatch.setattr(weights, "HOST_RESERVE_BYTES", 2**62)
        monkeypatch.setattr(weights, "_host_available", lambda: 2**40)
    got = load_quantized(cfg, seed=11)
    assert isinstance(got["layers"], list) and len(got["layers"]) == cfg.n_layers
    got["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *got["layers"])
    got_l, got_t = jax.tree_util.tree_flatten(got)
    want_l, want_t = jax.tree_util.tree_flatten(want)
    assert got_t == want_t
    for g, w in zip(got_l, want_l):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_init_params_host_is_seeded_per_slice():
    """Each slice has its own generator: same seed, same tree; another seed
    changes the random leaves but not the constant ones."""
    cfg = smoke_config("glm4-9b")
    a = init_params_host(cfg, seed=1)
    b = init_params_host(cfg, seed=1)
    c = init_params_host(cfg, seed=2)
    np.testing.assert_array_equal(a["layers"]["mlp"]["w_up"],
                                  b["layers"]["mlp"]["w_up"])
    assert not np.array_equal(a["layers"]["mlp"]["w_up"],
                              c["layers"]["mlp"]["w_up"])
    np.testing.assert_array_equal(a["final_norm"]["scale"],
                                  c["final_norm"]["scale"])
    w = np.asarray(a["layers"]["mlp"]["w_up"])
    assert not np.array_equal(w[0], w[1])  # layers draw distinct slices
