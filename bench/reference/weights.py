"""The float32 weights a seed stands for, drawn here without the program.

The rule the served weights are drawn by (:func:`draw`): the parameter
tree's leaves in sorted-key order; slice ``i`` of leaf ``j`` (row-major over
the leaf's stack dims: layer ``i`` of a per-layer matrix, expert ``e`` of
layer ``l`` at ``l * n_experts + e``, else the whole leaf) is numpy's
``default_rng((seed, j, i)).standard_normal(shape, float32)`` times a
standard deviation: 0.02 for the embedding, ``1 / sqrt(fan_in)`` for every
other matrix. Norm scales are ones. The program quantizes these slices; the
reference uses them as drawn.

The rule is the same for every block; only the leaf list differs. The dense
block's is :data:`DENSE_LEAVES`, with its matrices below; another block's
reference module passes its own sorted list to :func:`draw`.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import numpy as np

# The dense block's leaves in the order the tree flattens them.
DENSE_LEAVES = (
    "embed", "final_norm/scale",
    "layers/attn/wk", "layers/attn/wo", "layers/attn/wq", "layers/attn/wv",
    "layers/mlp/w_down", "layers/mlp/w_gate", "layers/mlp/w_up",
    "layers/norm1/scale", "layers/norm2/scale",
    "lm_head",
)
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _check(model: Dict) -> None:
    if (model["block"], model["act"], model["norm"]) != ("dense", "swiglu", "rms"):
        raise ValueError("the reference covers dense SwiGLU RMSNorm blocks only")
    if model.get("tie_embeddings") or model.get("qk_norm"):
        raise ValueError("tied embeddings and qk-norm are not in the reference")


def matrix_shape(model: Dict, name: str) -> Tuple[int, int]:
    d, f = model["d_model"], model["d_ff"]
    hd = model.get("head_dim") or d // model["n_heads"]
    q, kv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
            "embed": (model["vocab"], d), "lm_head": (d, model["vocab"])}[name]


def draw(seed: int, leaves: Sequence[str], leaf: str, i: int, shape,
         std: float) -> np.ndarray:
    """Slice ``i`` of ``leaf`` as the loader draws it, float32; ``leaves``:
    the block's leaf paths in sorted order."""
    w = np.random.default_rng((seed, leaves.index(leaf), i)).standard_normal(
        shape, dtype=np.float32)
    w *= np.float32(std)
    return w


def layer_matrix(model: Dict, seed: int, layer: int, name: str) -> np.ndarray:
    """One layer's float32 matrix ``name`` (``wq`` ... ``w_down``)."""
    _check(model)
    shape = matrix_shape(model, name)
    group = "mlp" if name.startswith("w_") else "attn"
    return draw(seed, DENSE_LEAVES, f"layers/{group}/{name}", layer, shape,
                1.0 / math.sqrt(shape[0]))


def embed(model: Dict, seed: int) -> np.ndarray:
    _check(model)
    return draw(seed, DENSE_LEAVES, "embed", 0, matrix_shape(model, "embed"), 0.02)


def lm_head(model: Dict, seed: int) -> np.ndarray:
    _check(model)
    shape = matrix_shape(model, "lm_head")
    return draw(seed, DENSE_LEAVES, "lm_head", 0, shape, 1.0 / math.sqrt(shape[0]))
