"""Pallas kernels vs pure-jnp oracles (interpret mode = kernel body on CPU).

Every kernel is swept over shapes (aligned and ragged), dtypes, and scale
granularities, and asserted allclose against repro.kernels.ref. Integer paths
must match bit-exactly; float paths allow accumulation-order tolerance.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.dynamic_quant import dynamic_quant
from repro.kernels.ocs_matmul import ocs_quant_matmul
from repro.kernels.quant_matmul import quant_matmul

RNG = np.random.RandomState(0)


def _i8(*shape):
    return jnp.asarray(RNG.randint(-127, 128, shape), jnp.int8)


def _f(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.randn(*shape), dtype)


# ---------------------------------------------------------------------------
# quant_matmul


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128), (8, 128, 72), (200, 260, 130)])
@pytest.mark.parametrize("per_channel", [False, True])
def test_quant_matmul_w8a8(m, k, n, per_channel):
    x8, w8 = _i8(m, k), _i8(k, n)
    xs = jnp.asarray(RNG.rand(m) + 0.1, jnp.float32)
    ws = jnp.asarray(RNG.rand(n) + 0.1, jnp.float32) if per_channel \
        else jnp.asarray(0.37, jnp.float32)
    got = quant_matmul(x8, w8, ws, xs, interpret=True)
    want = ref.quant_matmul_ref(x8, w8, xs, jnp.broadcast_to(ws, (n,)))
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (64, 256, 192)])
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_weight_only(m, k, n, xdtype):
    x = _f(m, k, dtype=xdtype)
    w8 = _i8(k, n)
    ws = jnp.asarray(RNG.rand(n) + 0.1, jnp.float32)
    got = quant_matmul(x, w8, ws, interpret=True, out_dtype=jnp.float32)
    want = (
        x.astype(jnp.float32) @ w8.astype(jnp.float32) * ws[None, :]
    )
    # Blocked-K accumulation reassociates float sums: tolerance, not exactness.
    np.testing.assert_allclose(got, want, rtol=2e-2 if xdtype == jnp.bfloat16 else 2e-3)


@settings(max_examples=15, deadline=None)
@given(
    m=st.integers(1, 70),
    k=st.integers(1, 80),
    n=st.integers(1, 70),
)
def test_quant_matmul_w8a8_property(m, k, n):
    """Bit-exactness for arbitrary ragged shapes (padding correctness)."""
    rng = np.random.RandomState(m * 7919 + k * 131 + n)
    x8 = jnp.asarray(rng.randint(-127, 128, (m, k)), jnp.int8)
    w8 = jnp.asarray(rng.randint(-127, 128, (k, n)), jnp.int8)
    xs = jnp.asarray(rng.rand(m) + 0.05, jnp.float32)
    ws = jnp.asarray(rng.rand(n) + 0.05, jnp.float32)
    got = quant_matmul(x8, w8, ws, xs, interpret=True, bm=32, bn=32, bk=32)
    want = ref.quant_matmul_ref(x8, w8, xs, ws)
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ---------------------------------------------------------------------------
# dynamic_quant


@pytest.mark.parametrize("m,k", [(128, 512), (130, 96), (1, 2048), (256, 1600)])
@pytest.mark.parametrize("bits", [8, 6, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dynamic_quant(m, k, bits, dtype):
    x = _f(m, k, dtype=dtype) * 3.0
    q, s = dynamic_quant(x, bits=bits, interpret=True)
    # Jit the oracle: interpret mode jits the kernel body, and XLA's
    # divide->reciprocal rewrite flips exact .5 midpoints by one ulp if the
    # two sides are compiled differently.
    q_ref, s_ref = jax.jit(ref.dynamic_quant_ref, static_argnums=1)(x, bits)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(s, s_ref, rtol=1e-6)


def test_dynamic_quant_roundtrip_error_bound():
    """|x - dequant(q)| <= scale/2 per element (the linear-grid guarantee)."""
    x = _f(64, 300) * 10.0
    q, s = dynamic_quant(x, bits=8, interpret=True)
    err = np.abs(np.asarray(x) - np.asarray(q, np.float32) * np.asarray(s)[:, None])
    assert (err <= np.asarray(s)[:, None] * 0.5 + 1e-6).all()


# ---------------------------------------------------------------------------
# ocs_matmul (fused OCS expansion)


def _split_setup(rng, m, k, n, s):
    """Expanded weights [k+s, n] + tail sources, mimicking repro.core.ocs."""
    w8 = jnp.asarray(rng.randint(-127, 128, (k + s, n)), jnp.int8)
    src = jnp.asarray(rng.randint(0, k, (s,)), jnp.int32)
    ws = jnp.asarray(rng.rand(n) + 0.05, jnp.float32)
    return w8, src, ws


@pytest.mark.parametrize("m,k,n,s", [
    (128, 256, 128, 128),   # aligned, one tail block
    (64, 300, 130, 7),      # ragged everything
    (32, 128, 64, 0),       # no splits -> plain kernel fallback
    (256, 512, 256, 256),   # two tail blocks
])
def test_ocs_matmul_w8a8(m, k, n, s):
    rng = np.random.RandomState(42 + m + k + n + s)
    x8 = jnp.asarray(rng.randint(-127, 128, (m, k)), jnp.int8)
    w8, src, ws = _split_setup(rng, m, k, n, s)
    xs = jnp.asarray(rng.rand(m) + 0.05, jnp.float32)
    got = ocs_quant_matmul(x8, w8, ws, src, xs, interpret=True)
    want = ref.ocs_quant_matmul_ref(x8, w8, ws, src, xs)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ocs_matmul_weight_only(dtype):
    rng = np.random.RandomState(7)
    m, k, n, s = 64, 256, 128, 16
    x = jnp.asarray(rng.randn(m, k), dtype)
    w8, src, ws = _split_setup(rng, m, k, n, s)
    mult = jnp.asarray(rng.choice([0.5, 1.0], s), jnp.float32)
    got = ocs_quant_matmul(
        x, w8, ws, src, tail_mult=mult, interpret=True, out_dtype=jnp.float32
    )
    want = ref.ocs_quant_matmul_ref(x, w8, ws, src, None, mult, jnp.float32)
    # Blocked-K accumulation reassociates float sums.
    np.testing.assert_allclose(
        got, want, rtol=2e-2 if dtype == jnp.bfloat16 else 2e-3
    )


def test_ocs_matmul_equals_materialized_dense():
    """The fused kernel == naive expand-then-matmul for a real OCS split."""
    from repro.core.ocs import make_ocs_quant_linear
    from repro.core.quantizer import dequantize

    rng = np.random.RandomState(3)
    k, n, m = 96, 80, 24
    w = rng.randn(k, n).astype(np.float32)
    w[rng.randint(0, k, 5), rng.randint(0, n, 5)] *= 8.0  # outliers
    lin = make_ocs_quant_linear(w, 0.05, 8, pad_to=32)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)

    # Naive: materialize expanded activations (ref path used by layers.dense).
    from repro.core.ocs import expand_activations
    xe = expand_activations(x, lin.spec)
    want = xe @ lin.weight.dequant(jnp.float32)

    # Fused kernel: tail = spec entries beyond the original K channels.
    src_tail = lin.spec.src[k:]
    mult_tail = lin.spec.mult[k:]
    got = ocs_quant_matmul(
        x, lin.weight.values, lin.weight.scale, src_tail,
        tail_mult=mult_tail, interpret=True, out_dtype=jnp.float32,
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    m=st.integers(1, 40),
    k=st.integers(8, 90),
    n=st.integers(1, 50),
    s=st.integers(0, 40),
)
def test_ocs_matmul_property(m, k, n, s):
    rng = np.random.RandomState(m + 100 * k + 7 * n + 13 * s)
    x8 = jnp.asarray(rng.randint(-127, 128, (m, k)), jnp.int8)
    w8, src, ws = _split_setup(rng, m, k, n, s)
    got = ocs_quant_matmul(x8, w8, ws, src, interpret=True, bm=32, bn=32, bk=32)
    want = ref.ocs_quant_matmul_ref(x8, w8, ws, src)
    np.testing.assert_allclose(got, want, rtol=1e-6)


# The OCS matmul at decode (8 rows) and prefill (256 rows) shapes, with the
# 128-wide tail of the serving recipe; tiles as planned, or 128-wide so the
# contraction runs over several grid steps.
OCS_SHAPES = [(8, 512, 128, 384), (256, 512, 128, 384)]
OCS_TILES = {"planned": {}, "k-steps": {"bn": 128, "bk": 128}}


@pytest.mark.parametrize("m,k,s,n", OCS_SHAPES)
@pytest.mark.parametrize("mult", [(0.0, 1.0), (0.5, 1.0)])
@pytest.mark.parametrize("tiles", list(OCS_TILES))
def test_ocs_matmul_bf16_contraction(m, k, s, n, mult, tiles):
    """bfloat16 activations against the float32 oracle: every product is
    exact, so the two differ only by the order of float32 summation
    (bounded by K+S roundings of the absolute sum), before any bf16 cast."""
    rng = np.random.RandomState(m + n + int(10 * mult[0]))
    x = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
    w8, src, ws = _split_setup(rng, m, k, n, s)
    tm = jnp.asarray(rng.choice(mult, s), jnp.float32)
    got = ocs_quant_matmul(x, w8, ws, src, tail_mult=tm, interpret=True,
                           out_dtype=jnp.float32, **OCS_TILES[tiles])
    want = ref.ocs_quant_matmul_ref(x, w8, ws, src, None, tm, jnp.float32)
    mag = ref.ocs_quant_matmul_ref(jnp.abs(x), jnp.abs(w8), ws, src, None, tm,
                                   jnp.float32)
    err = np.abs(np.asarray(got) - np.asarray(want))
    assert (err <= (k + s) * 2.0 ** -24 * np.asarray(mag)).all(), err.max()


def _kernel_dots(fn, *args):
    """(lhs, rhs, result) dtypes of every dot inside the traced kernels."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found.append(tuple(jnp.dtype(v.aval.dtype).name
                                   for v in (*eqn.invars, *eqn.outvars)))
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("xdtype,dots", [
    (jnp.int8, ("int8", "int8", "int32")),
    (jnp.bfloat16, ("bfloat16", "bfloat16", "float32")),
    (jnp.float32, ("float32", "float32", "float32")),
])
def test_ocs_matmul_contraction_dtype(xdtype, dots):
    """The MXU takes the activations' dtype: W8A8 stays integer, bfloat16
    activations meet a bfloat16 weight tile, float32 keeps float32."""
    rng = np.random.RandomState(5)
    w8, src, ws = _split_setup(rng, 8, 256, 128, 128)
    x = jnp.asarray(rng.randint(-127, 128, (8, 256)), xdtype)
    fn = lambda x: ocs_quant_matmul(x, w8, ws, src, interpret=True)
    assert _kernel_dots(fn, x) == [dots]


@pytest.mark.parametrize("m,k,s,n", OCS_SHAPES)
def test_ocs_matmul_w8a8_bit_exact(m, k, s, n):
    """The W8A8 path: exact int32 accumulation, then one float32 epilogue
    ``acc * (x_scale * w_scale)``, bit for bit."""
    rng = np.random.RandomState(m + k)
    x8 = rng.randint(-127, 128, (m, k)).astype(np.int8)
    w8, src, ws = _split_setup(rng, m, k, n, s)
    xs = (rng.rand(m) + 0.05).astype(np.float32)
    mask = rng.choice([0.0, 1.0], s).astype(np.float32)
    got = ocs_quant_matmul(jnp.asarray(x8), w8, ws, src, jnp.asarray(xs),
                           tail_mult=jnp.asarray(mask), interpret=True)
    x_exp = np.concatenate([x8, x8[:, np.asarray(src)] * mask.astype(np.int8)], 1)
    acc = (x_exp.astype(np.int64) @ np.asarray(w8, np.int64)).astype(np.float32)
    want = acc * (xs[:, None] * np.asarray(ws)[None, :])
    np.testing.assert_array_equal(np.asarray(got), want)


# glm4-9b's OCS linears (ratio 0.02, tails padded to 128) as (K, S, N, calls
# per decode step): q and o, k and v, gate and up, down, each in 40 layers,
# and the head.
GLM4_LINEARS = [(4096, 128, 4096, 80), (4096, 128, 256, 80),
                (4096, 128, 13696, 80), (13696, 384, 4096, 40),
                (4096, 128, 151552, 1)]


def test_ocs_matmul_decode_plan_glm4():
    """One glm4-9b decode step (8 rows) plans a few thousand grid steps, not
    the 552,112 of 128-wide tiles; every plan fits its VMEM budget, and its
    tiles divide the stored weight."""
    from repro.kernels import ocs_matmul as om

    om.clear_tile_plans()
    for k, s, n, _ in GLM4_LINEARS:
        sds = jax.ShapeDtypeStruct
        jax.eval_shape(
            lambda *a: ocs_quant_matmul(*a, interpret=True),
            sds((8, k), jnp.bfloat16), sds((k + s, n), jnp.int8),
            sds((n,), jnp.float32), sds((s,), jnp.int32))
    plans = om.tile_plans()
    om.clear_tile_plans()
    steps = sum(plans[(8, k, s, n, "bfloat16")].steps * calls
                for k, s, n, calls in GLM4_LINEARS)
    assert steps <= 20_000, steps
    for (m, k, s, n, _), p in plans.items():
        assert p.vmem_bytes <= om.PLAN_BUDGET_BYTES < om.VMEM_LIMIT_BYTES
        assert (k + s) % p.bk == 0 and n % p.bn == 0 and p.bm == 16
        assert p.grid == (1, n // p.bn, (k + s) // p.bk)


# ---------------------------------------------------------------------------
# ops dispatch


def test_dense_pallas_serving_wiring():
    """layers.dense with kernel="pallas" matches the XLA dequant path — via
    the explicit argument and via the serving_mode(kernel=) ambient, which
    replaced dispatch-time reads of the USE_PALLAS_SERVING module global."""
    from repro.core.ocs import make_ocs_quant_linear
    from repro.models import layers

    rng = np.random.RandomState(11)
    w = rng.randn(96, 64).astype(np.float32)
    w[3, 5] = 9.0
    lin = make_ocs_quant_linear(w, 0.03, 8, pad_to=32)
    x = jnp.asarray(rng.randn(4, 96), jnp.float32)
    y_xla = layers.dense(lin, x)
    y_kernel = layers.dense(lin, x, kernel="pallas")
    with layers.serving_mode("dequant", kernel="pallas"):
        y_ambient = layers.dense(lin, x)
    np.testing.assert_allclose(y_xla, y_kernel, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(y_kernel), np.asarray(y_ambient))


def test_ops_dispatch_cpu_ref():
    from repro.kernels import ops

    assert ops.backend_mode() == "ref"  # CPU container
    x8, w8 = _i8(16, 64), _i8(64, 32)
    ws = jnp.asarray(0.5, jnp.float32)
    xs = jnp.ones(16, jnp.float32)
    y = ops.quant_matmul(x8, w8, ws, xs)
    np.testing.assert_allclose(
        y, ref.quant_matmul_ref(x8, w8, xs, jnp.broadcast_to(ws, (32,))), rtol=1e-6
    )
    q, s = ops.dynamic_quant(_f(8, 128))
    assert q.dtype == jnp.int8 and s.shape == (8,)
