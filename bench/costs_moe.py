"""Operations and bytes of the held-expert layer's grouped matmul
(``kernels/moe_gmm.py``), from shapes and the engine's counters.

One ``moe_gmm`` call computes one projection (gate, up or down) of every
held expert that has rows, on exactly those rows. Its least work: ``2 *
rows * K_exp * N`` operations, where ``K_exp`` is the OCS-expanded
contraction dim (:func:`bench.costs.expanded`); and the bytes of the
touched experts' int8 weights and float32 per-column scales, plus the
bfloat16 rows in and out. Row padding to the tile, and the tiles that pad
the static tile count, are not counted.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.costs import ACT_BYTES, expanded, least_time

KERNEL = "moe_gmm"  # the Pallas call's name: its ops read %moe_gmm(.N)


def gmm_seconds(trace: Dict, program: str) -> float:
    """Device seconds of the ``moe_gmm`` calls in ``program`` ("decode",
    "prefill"), from the reduced trace's ``op_s``: the labels
    ``<program>:%moe_gmm`` (a numbered call) and ``<program>:%moe_gmm =
    ...`` (the call without a number, whose label runs to the first dot)."""
    head = f"{program}:%{KERNEL}"
    return sum(s for label, s in trace["op_s"].items()
               if label == head or label.startswith(head + " "))


def projections(model: Dict) -> List[Tuple[str, int, int]]:
    """``(name, k, n)`` of an expert's three projections."""
    d, f = model["d_model"], model["moe"]["expert_ff"]
    return [("w_gate", d, f), ("w_up", d, f), ("w_down", f, d)]


def moe_gmm(rows: float, touched: float, k: int, n: int,
            recipe: Dict) -> Tuple[float, float]:
    """One call's (flops, bytes): ``rows`` routed rows over ``touched``
    experts with at least one row, a ``[k, n]`` projection each."""
    ke = expanded(k, recipe["ocs_ratio"], recipe["pad_to"])
    flops = 2.0 * rows * ke * n
    nbytes = (touched * (ke * n * recipe["w_bits"] / 8 + 4 * n)
              + ACT_BYTES * rows * (k + n))
    return flops, nbytes


def layer_calls(model: Dict, recipe: Dict, rows: float,
                touched: float) -> Tuple[float, float]:
    """(flops, bytes) of the three calls of held-expert layers that
    computed ``rows`` routed rows on ``touched`` (layer, call, expert)
    triples, summed."""
    parts = [moe_gmm(rows, touched, k, n, recipe) for _, k, n in projections(model)]
    return sum(f for f, _ in parts), sum(b for _, b in parts)


def least_seconds(model: Dict, recipe: Dict, counters: Dict, peaks: Dict,
                  kinds: Tuple[str, ...] = ("decode", "prefill")) -> float:
    """Least time of the grouped matmuls behind the engine's counters
    (``moe_rows_<kind>``, ``moe_experts_touched_<kind>``), each program kind
    of ``kinds`` bounded on its own."""
    return sum(
        least_time(*layer_calls(model, recipe, counters[f"moe_rows_{kind}"],
                                counters[f"moe_experts_touched_{kind}"]), peaks)
        for kind in kinds)
