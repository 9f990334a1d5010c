"""The grouped int8 OCS matmul over held experts (``kernels/moe_gmm.py``).

Its XLA oracle against a per-expert loop, experts with no rows included;
the Pallas kernel (interpret mode) against the oracle; and the static tile
plan: row tiles sized to an expert's expected rows, a tile count no
routing can overflow.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import moe_gmm as G
from repro.kernels import ops


def _case(counts, bm=16, k=256, s=128, n=256, spare=3, seed=0):
    """Rows grouped by expert, each expert's rows padded to the row tile,
    ``spare`` padding tiles past the used ones (they repeat the last used
    expert, as the layer lays them out)."""
    rng = np.random.default_rng(seed)
    e = len(counts)
    tiles = -(-np.asarray(counts) // bm)
    group = np.repeat(np.arange(e), tiles)
    used = len(group)
    last = group[-1] if used else 0
    group = np.concatenate([group, np.full(spare, last)]).astype(np.int32)
    x = np.zeros((len(group) * bm, k), np.float32)
    t = 0
    for c, nt in zip(counts, tiles):
        for j in range(nt):
            r = min(bm, c - j * bm)
            x[t * bm:t * bm + r] = rng.standard_normal((r, k))
            t += 1
    args = (jnp.asarray(x, jnp.bfloat16),
            jnp.asarray(rng.integers(-127, 128, (e, k + s, n)), jnp.int8),
            jnp.asarray(rng.random((e, 1, n)) * 0.01 + 1e-3, jnp.float32),
            jnp.asarray(rng.integers(0, k, (e, s)), jnp.int32),
            jnp.asarray(rng.integers(0, 2, (e, s)), jnp.float32),
            jnp.asarray(group), jnp.asarray([used], jnp.int32))
    return args, used * bm


def _loop(x, w8, ws, src, mult, group, used):
    """Each used tile's rows through its expert alone, float64."""
    bm = x.shape[0] // group.shape[0]
    x = np.asarray(x, np.float64)
    out = np.zeros((x.shape[0], w8.shape[-1]))
    for t in range(int(used[0])):
        g = int(group[t])
        rows = x[t * bm:(t + 1) * bm]
        full = np.concatenate(
            [rows, rows[:, np.asarray(src[g])] * np.asarray(mult[g], np.float64)], 1)
        out[t * bm:(t + 1) * bm] = (full @ np.asarray(w8[g], np.float64)
                                    * np.asarray(ws[g], np.float64))
    return out


def test_expanded_rows_are_each_rows_experts_split_columns():
    """Each row gets its own expert's split columns times its multipliers,
    bit for bit, whatever the other rows' experts."""
    args, _ = _case([3, 0, 17, 0, 1, 40], seed=2)
    x, _, _, src, mult, group, _ = args
    got = np.asarray(G.expand_rows(x, src, mult, group).astype(jnp.float32))
    xf = np.asarray(x.astype(jnp.float32))
    g = np.repeat(np.asarray(group), x.shape[0] // group.shape[0])
    tail = np.stack([xf[r, np.asarray(src)[g[r]]] for r in range(len(g))])
    want = np.concatenate(
        [xf, np.asarray(jnp.asarray(tail, jnp.bfloat16)
                        * jnp.asarray(np.asarray(mult)[g], jnp.bfloat16),
                        np.float32)], axis=1)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("counts", [[3, 0, 17, 0, 1, 40], [0, 0, 5], [16, 16]],
                         ids=["ragged", "leading_empty", "full_tiles"])
def test_oracle_matches_a_per_expert_loop(counts):
    args, m = _case(counts)
    got = np.asarray(ops.moe_gmm(*args, force="ref"), np.float64)
    want = _loop(*args)
    # bfloat16 output: each value rounds to 8 significant bits.
    np.testing.assert_allclose(got[:m], want[:m], rtol=2 ** -8,
                               atol=2 ** -8 * np.abs(want).max())
    assert (got[m:] == 0).all()  # padding tiles read 0 in the oracle


@pytest.mark.parametrize("counts", [[3, 0, 17, 0, 1, 40], [0, 0, 5]],
                         ids=["ragged", "leading_empty"])
def test_kernel_matches_oracle(counts):
    args, m = _case(counts, seed=1)
    want = np.asarray(ops.moe_gmm(*args, force="ref"), np.float32)
    got = np.asarray(ops.moe_gmm(*args, force="interpret"), np.float32)
    # Same bfloat16 products, float32 sums in another order, one bfloat16
    # rounding of the output: a last-bit flip at most.
    np.testing.assert_allclose(got[:m], want[:m], rtol=2 ** -7,
                               atol=2 ** -7 * np.abs(want).max())


def test_no_used_tile_writes_nothing_it_is_asked_for():
    """A step whose tokens all route past the held experts: no tile is
    used, and the kernel runs (every block index stays at tile 0)."""
    args, m = _case([0, 0, 0], spare=2)
    assert m == 0
    out = ops.moe_gmm(*args, force="interpret")
    assert out.shape == (2 * 16, 256)


@pytest.mark.parametrize("n,k,e", [(1, 6, 16), (32, 6, 16), (512, 6, 16),
                                   (7, 3, 8), (64, 3, 4)])
def test_tile_count_holds_every_routing(n, k, e):
    """``max_tiles`` bounds the tiles of the worst routing: every expert
    as full as top-k allows, each with a partial last tile."""
    bm = G.row_tile(n * k / (4 * e), jnp.bfloat16)
    assert bm % 16 == 0 and bm <= 512
    worst = 0
    rng = np.random.default_rng(n)
    for _ in range(200):
        counts = np.zeros(e, np.int64)
        for _ in range(n):  # each token: k distinct experts of 4e, some held
            picks = rng.choice(4 * e, size=k, replace=False)
            for p in picks[picks < e]:
                counts[p] += 1
        worst = max(worst, int((-(-counts // bm)).sum()))
    full = min(e, k) * -(-n // bm)  # k experts each taking every token
    assert max(worst, full) <= G.max_tiles(n, k, e, bm)


def test_plan_takes_the_whole_contraction_at_real_widths():
    """deepseek-moe-16b's projections at decode: one weight tile per
    expert, within the VMEM plan budget."""
    for ke, n in ((2176, 1408), (1536, 2048)):
        p = G.plan_tiles(16, ke, n, jnp.bfloat16, jnp.bfloat16)
        assert (p.bk, p.bn) == (ke, n)
        assert p.vmem_bytes <= G.PLAN_BUDGET_BYTES
