"""Operations and bytes that a call needs, computed from its shapes.

These are the least work the algorithm asks of the chip, not what a kernel
happens to do: padding rows, dead DMAs and re-reads are not counted, so a
kernel's time against them is its share of the roofline.

Linear layers hold OCS-expanded int8 weights: the contraction dim ``k``
grows by ``ceil(ratio * k)`` split channels and is padded to ``pad_to``
(:func:`expanded`). A call reads the int8 weight once, its float32
per-column scales, the bfloat16 activations and writes bfloat16 outputs.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Tuple

ACT_BYTES = 2  # bfloat16 activations in and out


def expanded(k: int, ratio: float, pad_to: int) -> int:
    """The OCS-expanded contraction dim: ``k + ceil(ratio * k)``, padded."""
    c = k + (int(math.ceil(ratio * k)) if ratio > 0 else 0)
    return c + (-c) % pad_to


def linears(model: Dict) -> List[Tuple[str, int, int]]:
    """``(name, k, n)`` of each quantized linear of one dense layer."""
    d, f = model["d_model"], model["d_ff"]
    hd = model.get("head_dim") or d // model["n_heads"]
    q, kv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("w_gate", d, f), ("w_up", d, f), ("w_down", f, d)]


def ocs_matmul(rows: int, k: int, n: int, recipe: Dict) -> Tuple[float, float]:
    """One OCS matmul over ``rows`` activation rows: (flops, bytes)."""
    ke = expanded(k, recipe["ocs_ratio"], recipe["pad_to"])
    flops = 2.0 * rows * ke * n
    nbytes = ke * n * recipe["w_bits"] / 8 + 4 * n + ACT_BYTES * rows * (k + n)
    return flops, nbytes


def decode_matmuls(model: Dict, recipe: Dict, rows: int) -> List[Tuple[float, float]]:
    """(flops, bytes) of every quantized matmul of one decode step: each
    layer's seven linears and the ``lm_head``."""
    per_layer = [ocs_matmul(rows, k, n, recipe) for _, k, n in linears(model)]
    head = ocs_matmul(rows, model["d_model"], model["vocab"], recipe)
    return per_layer * model["n_layers"] + [head]


def kv_bytes_per_token(model: Dict, kv_bits: int) -> float:
    """K and V of one token in one layer, values and per-token scales."""
    hd = model.get("head_dim") or model["d_model"] // model["n_heads"]
    kvh = model["n_kv_heads"]
    if kv_bits in (8, 4):
        return 2 * kvh * (hd * kv_bits / 8 + 4)
    return 2 * kvh * hd * 4


def paged_attention(model: Dict, kv_bits: int, page_size: int,
                    positions: Iterable[int]) -> Tuple[float, float]:
    """One layer's paged-attention call for lanes at ``positions`` (the
    position each lane's new token is written to): (flops, bytes).

    Each lane reads the K, V and scales of every page up to its position
    (the page being appended to included), reads its query and new K/V rows
    in float32, writes its new rows into the page and its output."""
    hd = model.get("head_dim") or model["d_model"] // model["n_heads"]
    h, kvh = model["n_heads"], model["n_kv_heads"]
    per_tok = kv_bytes_per_token(model, kv_bits)
    flops = nbytes = 0.0
    for p in positions:
        attended = p + 1
        pages = p // page_size + 1
        flops += 4.0 * h * hd * attended
        nbytes += pages * page_size * per_tok  # pages read
        nbytes += 4 * hd * (2 * h + 2 * kvh)  # q and out, new k and v (f32)
        nbytes += per_tok  # the appended row
    return flops, nbytes


def decode_step(model: Dict, recipe: Dict, kv_bits: int, page_size: int,
                positions: List[int]) -> Dict[str, Tuple[float, float]]:
    """(flops, bytes) of one decode step at the live lanes' positions, by
    part: ``matmul`` (every quantized linear and the head), ``attention``
    (every layer's paged attention) and ``other`` (the embedding rows)."""
    rows = len(positions)
    mm = decode_matmuls(model, recipe, rows)
    att = paged_attention(model, kv_bits, page_size, positions)
    layers = model["n_layers"]
    return {
        "matmul": (sum(f for f, _ in mm), sum(b for _, b in mm)),
        "attention": (att[0] * layers, att[1] * layers),
        "other": (0.0, 4.0 * rows * model["d_model"]),
    }


def least_time(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def decode_matmul_least_time(model: Dict, recipe: Dict, rows: int,
                             peaks: Dict) -> float:
    """Least time of one decode step's quantized matmuls, call by call."""
    return sum(least_time(f, b, peaks)
               for f, b in decode_matmuls(model, recipe, rows))
