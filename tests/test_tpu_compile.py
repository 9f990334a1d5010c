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
from repro.kernels import moe_gmm as mg
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


@pytest.mark.parametrize("qn", [1, 4])
@pytest.mark.parametrize("kv,lanes", [(16, 32), (32, 8)],
                         ids=["deepseek-moe-16b", "deepseek-7b"])
def test_paged_attention_compiles_at_mha_head_counts(sds, kv, lanes, qn):
    """int8 pages of 16 tokens at 16 KV heads of 128 (MHA, 32 lanes of 512
    tokens) and at 32 (8 lanes): one program per lane takes all its heads
    (no reroute), each page one DMA per tile."""
    budget.clear_reroutes()
    p = lanes * T + 1
    pool = {"k": sds((p, kv, PS, HD), jnp.int8),
            "v": sds((p, kv, PS, HD), jnp.int8),
            "k_scale": sds((p, kv, PS), jnp.float32),
            "v_scale": sds((p, kv, PS), jnp.float32)}
    args = (pool, sds((lanes, T), jnp.int32), sds((lanes,), jnp.int32),
            sds((lanes, qn, kv, HD), jnp.bfloat16),
            sds((lanes, qn, kv, HD), jnp.bfloat16),
            sds((lanes, qn, kv, HD), jnp.bfloat16))
    assert "tpu_custom_call" in _hlo(pa.paged_attention, *args)
    assert budget.reroutes() == {}


@pytest.mark.parametrize("qn", [1, 4])
@pytest.mark.parametrize("kv,heads,lanes,t,g", [(2, 32, 8, 256, 16),
                                                (16, 16, 32, 32, 8)],
                         ids=["glm4-9b.chat", "deepseek-moe-16b.short-chat"])
def test_paged_attention_blocks_compile_at_cell_shapes(sds, kv, heads, lanes,
                                                       t, g, qn):
    """The blocked kernel at the two cells' shapes, int8 pages of 16: 8
    lanes of 256 pages at 2 KV heads and 32 lanes of 32 pages at 16, the
    planned blocks (16 and 8 pages) within the budget, no reroute."""
    budget.clear_reroutes()
    p = lanes * t + 1
    pool = {"k": sds((p, kv, PS, HD), jnp.int8),
            "v": sds((p, kv, PS, HD), jnp.int8),
            "k_scale": sds((p, kv, PS), jnp.float32),
            "v_scale": sds((p, kv, PS), jnp.float32)}
    assert pa.plan_pages(pool, t) == g
    args = (pool, sds((lanes, t), jnp.int32), sds((lanes,), jnp.int32),
            sds((lanes, qn, heads, HD), jnp.bfloat16),
            sds((lanes, qn, kv, HD), jnp.bfloat16),
            sds((lanes, qn, kv, HD), jnp.bfloat16))
    assert "tpu_custom_call" in _hlo(pa.paged_attention, *args)
    assert budget.reroutes() == {}


@pytest.mark.parametrize("tokens", [32, 128, 512])
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_moe_gmm_compiles(sds, tokens, k, n):
    """deepseek-moe-16b's expert projections over its 16 held experts: a
    32-lane decode step (about 3 rows an expert) and prefills of 128 and
    512 tokens, each at the layer's row tile and tile count."""
    e, topk, n_experts = 16, 6, 64
    bm = mg.row_tile(tokens * topk / n_experts, jnp.bfloat16)
    t = mg.max_tiles(tokens, topk, e, bm)
    ke = k + 128  # the serving recipe's OCS tail, padded to 128
    args = (sds((t * bm, k), jnp.bfloat16), sds((e, ke, n), jnp.int8),
            sds((e, 1, n), jnp.float32), sds((e, 128), jnp.int32),
            sds((e, 128), jnp.float32), sds((t,), jnp.int32),
            sds((1,), jnp.int32))
    hlo = _hlo(mg.moe_gmm, *args)
    assert "tpu_custom_call" in hlo and "%moe_gmm" in hlo


def test_ocs_quant_matmul_compiles(sds):
    """The default (dequant) serving matmul at the MLP's up projection, with
    the 128-lane OCS tail of the serving recipe (2% of 4096 rows, padded)."""
    args = (sds((8, D), jnp.bfloat16), sds((D + 128, FF), jnp.int8),
            sds((FF,), jnp.float32), sds((128,), jnp.int32))
    assert "tpu_custom_call" in _hlo(om.ocs_quant_matmul, *args)


# glm4-9b's stored OCS weights (K, S, N): q and o, k and v, gate and up,
# down, and the head.
OCS_WEIGHTS = [(D, 128, D), (D, 128, KV * HD), (D, 128, FF), (FF, 384, D),
               (D, 128, 151552)]


@pytest.mark.parametrize("k,s,n", OCS_WEIGHTS)
@pytest.mark.parametrize("m", [8, 1024])
def test_ocs_quant_matmul_plan_compiles(sds, m, k, s, n):
    """Every glm4-9b OCS matmul, at 8 decode rows and a 1024-token prefill,
    compiles to the Mosaic kernel with its planned tiles: no reroute, and
    tiles that divide the stored weight, so no int8 copy of it is padded or
    concatenated."""
    import re

    budget.clear_reroutes()
    om.clear_tile_plans()
    args = (sds((m, k), jnp.bfloat16), sds((k + s, n), jnp.int8),
            sds((n,), jnp.float32), sds((s,), jnp.int32))
    hlo = _hlo(om.ocs_quant_matmul, *args, tail_mult=jnp.ones((s,), jnp.float32))
    assert "tpu_custom_call" in hlo
    assert budget.reroutes() == {}
    plan = om.tile_plans()[(m, k, s, n, "bfloat16")]
    assert (k + s) % plan.bk == 0 and n % plan.bn == 0
    assert not re.search(r"= s8\[[\d,]+\]\S* (pad|concatenate|fusion)\(", hlo)
    om.clear_tile_plans()


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
