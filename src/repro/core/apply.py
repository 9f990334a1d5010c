"""Apply a :class:`QuantRecipe` to a whole model parameter tree.

Two paths:

* :func:`fake_quantize_params` — every quantizable weight is replaced *in
  place* (same shape/dtype) by its OCS+clip+quantize-dequantize "effective"
  float equivalent (the expanded layer collapsed back via
  :func:`collapse_expanded`). Model code runs unchanged; outputs are
  *bit-identical* to running the expanded integer network in float math.
  Used for accuracy evaluation (paper Tables 1–3, 6).

* :func:`quantize_params` — quantizable weights become
  :class:`OCSQuantLinear` leaves (expanded int8/int4 storage + scales +
  expansion spec). Model code dispatches through ``layers.dense`` and the
  serving kernels consume the integer values directly. Used for serving.

Weights with leading stack dims (``[L, Cin, Cout]`` from scanned layers,
``[L, E, Cin, Cout]`` for MoE experts) are quantized per-slice: each layer /
expert gets its own split table and scale, then slices are restacked so that
``lax.scan`` keeps slicing them per step.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .clipping import find_clip
from .histogram import StreamingHistogram
from .ocs import (
    OCSQuantLinear,
    OCSSpec,
    collapse_expanded,
    make_ocs_quant_linear,
    split_weights,
)
from .quantizer import QuantParams, fake_quant, qmax
from .recipe import QuantRecipe

__all__ = [
    "fake_quantize_params",
    "quantize_params",
    "quant_slice",
    "slice_form",
    "stack_linears",
    "path_str",
    "act_scales_from_collector",
]


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _is_quantizable(path: str, leaf, recipe: QuantRecipe) -> bool:
    if not isinstance(leaf, (np.ndarray, jnp.ndarray)) or leaf.ndim < 2:
        return False
    # jnp.issubdtype, NOT np.issubdtype: bfloat16 is an ml_dtypes extension
    # type that numpy does not classify as floating (a silent skip-everything
    # bug for bf16 trees otherwise).
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    return not recipe.should_skip(path)


def _fake_quant_2d(
    w: np.ndarray, recipe: QuantRecipe, n_splits: Optional[int] = None
) -> np.ndarray:
    """OCS split -> clip -> quantize -> dequantize -> collapse, [Cin, Cout]."""
    w_exp, spec, thresh = split_weights(
        w,
        recipe.ocs_ratio,
        recipe.w_bits,
        qa=recipe.qa_split,
        clip_method=recipe.w_clip,
        n_splits=n_splits,
    )
    if recipe.per_channel:
        wq = np.stack(
            [
                np.asarray(fake_quant(jnp.asarray(w_exp[:, j]), recipe.w_bits))
                for j in range(w_exp.shape[1])
            ],
            axis=1,
        )
    else:
        wq = np.asarray(fake_quant(jnp.asarray(w_exp), recipe.w_bits, clip=thresh))
    w_eff, _ = collapse_expanded(wq, spec, w.shape[0])
    return w_eff


def _map_stacked(w, fn: Callable[[np.ndarray], np.ndarray]):
    """Apply fn over all leading stack dims of [..., Cin, Cout]."""
    w = np.asarray(w, dtype=np.float32)
    lead = w.shape[:-2]
    flat = w.reshape((-1,) + w.shape[-2:])
    out = np.stack([fn(flat[i]) for i in range(flat.shape[0])], axis=0)
    return out.reshape(lead + out.shape[1:])


def knapsack_splits(params, recipe: QuantRecipe) -> Dict[str, int]:
    """Global split allocation (§3.4 knapsack variant): path#slice -> count."""
    from .allocate import knapsack_allocate

    layers = []

    def collect(path, leaf):
        p = path_str(path)
        if not _is_quantizable(p, leaf, recipe):
            return
        w = np.asarray(leaf, np.float32)
        flat = w.reshape((-1,) + w.shape[-2:])
        for i in range(flat.shape[0]):
            layers.append((f"{p}#{i}", flat[i]))

    jax.tree_util.tree_map_with_path(lambda p, l: collect(p, l), params)
    return knapsack_allocate(layers, recipe.ocs_ratio)


def fake_quantize_params(params, recipe: QuantRecipe):
    """Replace quantizable weights with their PTQ'd float equivalents.

    ``recipe.alloc == 'knapsack'`` swaps the per-layer ``ceil(r*C)`` split
    count for the globally-budgeted allocation (same total overhead).
    """
    if not recipe.wants_weight_quant():
        return params
    alloc = knapsack_splits(params, recipe) if recipe.alloc == "knapsack" else None

    def visit(path, leaf):
        p = path_str(path)
        if not _is_quantizable(p, leaf, recipe):
            return leaf
        if alloc is None:
            out = _map_stacked(leaf, lambda w2d: _fake_quant_2d(w2d, recipe))
        else:
            w = np.asarray(leaf, np.float32)
            lead = w.shape[:-2]
            flat = w.reshape((-1,) + w.shape[-2:])
            out = np.stack(
                [
                    _fake_quant_2d(flat[i], recipe, n_splits=alloc[f"{p}#{i}"])
                    for i in range(flat.shape[0])
                ]
            ).reshape(w.shape)
        return jnp.asarray(out, dtype=jnp.asarray(leaf).dtype)

    return jax.tree_util.tree_map_with_path(visit, params)


def quant_slice(w: np.ndarray, recipe: QuantRecipe) -> OCSQuantLinear:
    """One [Cin, Cout] slice through the recipe's OCS + quantize pipeline."""
    return make_ocs_quant_linear(
        w,
        recipe.ocs_ratio,
        recipe.w_bits,
        qa=recipe.qa_split,
        clip_method=recipe.w_clip,
        per_channel=recipe.per_channel,
        pad_to=recipe.pad_to,
    )


def slice_form(lin: OCSQuantLinear, recipe: QuantRecipe) -> OCSQuantLinear:
    """One slice as it sits inside a stacked leaf: scales broadcast-ready
    against the values (per-channel ``[1, Cout]``, per-tensor ``[1, 1]``)."""
    ws = lin.weight.scale
    scale = ws[None, :] if lin.weight.channel_axis == 1 else ws[None, None]
    qp = QuantParams(values=lin.weight.values, scale=scale, bits=recipe.w_bits,
                     channel_axis=None)
    return OCSQuantLinear(
        weight=qp, spec=lin.spec, n_orig=lin.n_orig, a_bits=recipe.a_bits
    )


def stack_linears(lins, lead: Tuple[int, ...], recipe: QuantRecipe) -> OCSQuantLinear:
    """Restack per-slice linears (row-major over the ``lead`` stack dims)
    into one OCSQuantLinear whose leaves carry ``lead`` again, so
    ``lax.scan`` or a per-layer index keeps slicing them; index ``i`` of the
    result is :func:`slice_form` of slice ``i``. ``lead == ()`` returns the
    single slice as is."""
    if not lead:
        return lins[0]
    forms = [slice_form(l, recipe) for l in lins]
    return jax.tree.map(
        lambda *xs: jnp.stack(xs).reshape(lead + xs[0].shape), *forms
    )


def _quant_linear_stacked(w, recipe: QuantRecipe) -> OCSQuantLinear:
    """Build a (possibly stacked) OCSQuantLinear from [..., Cin, Cout]."""
    w = np.asarray(w, dtype=np.float32)
    flat = w.reshape((-1,) + w.shape[-2:])
    lins = [quant_slice(flat[i], recipe) for i in range(flat.shape[0])]
    return stack_linears(lins, w.shape[:-2], recipe)


def quantize_params(params, recipe: QuantRecipe):
    """Replace quantizable weights with OCSQuantLinear integer leaves."""
    if not recipe.wants_weight_quant():
        return params

    def visit(path, leaf):
        p = path_str(path)
        if not _is_quantizable(p, leaf, recipe):
            return leaf
        return _quant_linear_stacked(leaf, recipe)

    return jax.tree_util.tree_map_with_path(visit, params, is_leaf=None)


def abstract_quantize_params(sds_params, recipe: QuantRecipe):
    """ShapeDtypeStruct version of :func:`quantize_params` (no host compute).

    Input: a pytree of ``jax.ShapeDtypeStruct`` float params. Output: the same
    tree with quantizable leaves replaced by OCSQuantLinear whose components
    are ShapeDtypeStructs with the *exact* shapes ``quantize_params`` would
    produce — used to lower/compile the serving step in the dry-run without
    materializing a single weight.
    """
    from .ocs import expanded_channels

    if not recipe.wants_weight_quant():
        return sds_params

    sds = jax.ShapeDtypeStruct

    def visit(path, leaf):
        p = path_str(path)
        if not isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        if (
            leaf.ndim < 2
            or not jnp.issubdtype(leaf.dtype, jnp.floating)
            or recipe.should_skip(p)
        ):
            return leaf
        lead = leaf.shape[:-2]
        cin, cout = leaf.shape[-2:]
        cexp = expanded_channels(cin, recipe.ocs_ratio, pad_to=recipe.pad_to)
        from .quantizer import storage_dtype

        vdtype = storage_dtype(recipe.w_bits)
        if lead:
            scale_shape = lead + ((1, cout) if recipe.per_channel else (1, 1))
            ch_axis = None
        else:
            scale_shape = (cout,) if recipe.per_channel else ()
            ch_axis = 1 if recipe.per_channel else None
        qp = QuantParams(
            values=sds(lead + (cexp, cout), vdtype),
            scale=sds(scale_shape, jnp.float32),
            bits=recipe.w_bits,
            channel_axis=ch_axis,
        )
        spec = OCSSpec(
            src=sds(lead + (cexp,), jnp.int32),
            mult=sds(lead + (cexp,), jnp.float32),
            bias=sds(lead + (cexp,), jnp.float32),
        )
        a_scale = (
            sds((), jnp.float32) if recipe.wants_act_quant() else None
        )
        return OCSQuantLinear(
            weight=qp,
            spec=spec,
            n_orig=cin,
            a_bits=recipe.a_bits,
            a_scale=a_scale,
        )

    return jax.tree_util.tree_map_with_path(
        visit, sds_params, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
    )


def act_scales_from_collector(
    collector, recipe: QuantRecipe
) -> Dict[str, float]:
    """Per-site activation clip thresholds from calibration stats (§5.3)."""
    if not recipe.wants_act_quant():
        return {}
    out: Dict[str, float] = {}
    for name, stats in collector.sites.items():
        out[name] = find_clip(stats.hist, recipe.a_bits, recipe.a_clip)
    return out
