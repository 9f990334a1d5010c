"""Compile the serving path's Pallas kernels for a TPU v5e chip that is
described, not attached.

Interpret mode (the other kernel tests) cannot see what Mosaic refuses:
slices not aligned to the tiling, 8-bit vector shifts, tiles past the VMEM
budget. These tests hand each kernel real widths — glm4-9b's (d_model 4096,
d_ff 13696, 32 heads over 2 KV heads of 128, 16-token pages) — and compile it
for one chip of a described ``v5e:2x2`` topology, the way the chip would.
Each asserts the Mosaic kernel is in the compiled HLO (``tpu_custom_call``);
one asserts that a kernel past the VMEM budget is rerouted to XLA *visibly*.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler. The persistent compilation cache is off
around these compiles: an entry written for a described chip cannot be read
back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import budget
from repro.kernels.dynamic_quant import dynamic_quant
from repro.kernels import fused_qmatmul as fq
from repro.kernels import ocs_matmul as om
from repro.kernels import paged_attention as pa

D, FF, H, KV, HD, PS = 4096, 13696, 32, 2, 128, 16
B, T, P = 8, 32, 257  # lanes, table columns (max_len 512), pool pages


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hlo(fn, *args, **static):
    return jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile().as_text()


def _pool(sds, kind):
    if kind == "float":
        return {"k": sds((P, KV, PS, HD), jnp.float32),
                "v": sds((P, KV, PS, HD), jnp.float32)}
    dt, rows = (jnp.int8, PS) if kind == "int8" else (jnp.uint8, PS // 2)
    return {"k": sds((P, KV, rows, HD), dt), "v": sds((P, KV, rows, HD), dt),
            "k_scale": sds((P, KV, PS), jnp.float32),
            "v_scale": sds((P, KV, PS), jnp.float32)}


@pytest.fixture(scope="module")
def sds(one_chip):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)


@pytest.mark.parametrize("qn", [1, 4])
@pytest.mark.parametrize("kind", ["float", "int8", "int4"])
def test_paged_attention_compiles(sds, kind, qn):
    args = (_pool(sds, kind), sds((B, T), jnp.int32), sds((B,), jnp.int32),
            sds((B, qn, H, HD), jnp.bfloat16), sds((B, qn, KV, HD), jnp.bfloat16),
            sds((B, qn, KV, HD), jnp.bfloat16))
    assert "tpu_custom_call" in _hlo(pa.paged_attention, *args)


def test_ocs_quant_matmul_compiles(sds):
    """The default (dequant) serving matmul at the MLP's up projection, with
    the 128-lane OCS tail of the serving recipe (2% of 4096 rows, padded)."""
    args = (sds((8, D), jnp.bfloat16), sds((D + 128, FF), jnp.int8),
            sds((FF,), jnp.float32), sds((128,), jnp.int32))
    assert "tpu_custom_call" in _hlo(om.ocs_quant_matmul, *args)


def test_fused_w8a8_compiles(sds):
    args = (sds((8, D), jnp.bfloat16), sds((D + 128, D), jnp.int8),
            sds((D,), jnp.float32), sds((128,), jnp.int32))
    assert "tpu_custom_call" in _hlo(fq.fused_quant_matmul, *args)


def test_w4a8_compiles(sds):
    t = 128  # outlier rows kept at int8
    args = (sds((8, D), jnp.bfloat16), sds(((D + 128) // 2, D), jnp.uint8),
            sds((D,), jnp.float32), sds((t, D), jnp.int8),
            sds((D,), jnp.float32), sds((128,), jnp.int32),
            sds((t,), jnp.int32))
    assert "tpu_custom_call" in _hlo(fq.w4a8_quant_matmul, *args)


def test_dynamic_quant_compiles(sds):
    assert "tpu_custom_call" in _hlo(dynamic_quant, sds((8, D), jnp.bfloat16))


def test_fused_w8a8_past_budget_reroutes_visibly(sds):
    """The down projection (K = d_ff = 13696) keeps a [128, K] row tile
    resident: past the VMEM budget, so the wrapper runs the XLA composition —
    and says so, in the reroute record the chip run refuses."""
    budget.clear_reroutes()
    args = (sds((8, FF), jnp.bfloat16), sds((FF + 384, D), jnp.int8),
            sds((D,), jnp.float32), sds((384,), jnp.int32))
    assert "tpu_custom_call" not in _hlo(fq.fused_quant_matmul, *args)
    assert budget.reroutes() == {f"fused_quant_matmul(8, {FF}, {FF + 384}, {D})": 1}
    budget.clear_reroutes()


def test_unpaged_gqa_decode_reads_bf16_cache(sds):
    """The unpaged GQA decode's p.V contraction merges the (query, rep) rows
    so XLA's CPU backend accepts it; on the chip it must still read the
    bfloat16 cache as is — no float32 buffer the size of the cache."""
    import math
    import re

    from repro.configs import get_config
    from repro.models.attention import attention_decode

    cfg = get_config("glm4-9b")
    seq = 4096
    p = {"wq": sds((D, H * HD), jnp.bfloat16), "wk": sds((D, KV * HD), jnp.bfloat16),
         "wv": sds((D, KV * HD), jnp.bfloat16), "wo": sds((H * HD, D), jnp.bfloat16)}
    cache = {"k": sds((B, KV, seq, HD), jnp.bfloat16),
             "v": sds((B, KV, seq, HD), jnp.bfloat16)}
    hlo = _hlo(lambda p, x, c, pos: attention_decode(p, x, c, pos, cfg),
               p, sds((B, 1, D), jnp.bfloat16), cache, sds((B,), jnp.int32))
    cache_elems = B * KV * seq * HD
    sizes = [math.prod(int(d) for d in m.group(1).split(","))
             for m in re.finditer(r"f32\[([\d,]+)\]", hlo)]
    assert max(sizes) < cache_elems, "a float32 copy of the KV cache"
