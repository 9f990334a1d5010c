"""Jitted dispatch wrappers over the Pallas kernels.

On TPU the Pallas kernels run natively (compiled by Mosaic); on any other
backend the wrappers either run the kernels in interpret mode (``force=
"interpret"``, used by the correctness tests) or fall back to the pure-jnp
oracles in :mod:`repro.kernels.ref`, which XLA compiles efficiently on CPU.
Production code calls these wrappers and never touches the kernels directly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from . import paged_attention as _pa
from .dynamic_quant import dynamic_quant as _dynamic_quant_pallas
from .fused_qmatmul import fused_quant_matmul as _fused_qmatmul_pallas
from .fused_qmatmul import w4a8_quant_matmul as _w4a8_qmatmul_pallas
from .moe_gmm import moe_gmm as _moe_gmm_pallas
from .ocs_matmul import ocs_quant_matmul as _ocs_matmul_pallas
from .quant_matmul import quant_matmul as _quant_matmul_pallas

__all__ = [
    "quant_matmul",
    "dynamic_quant",
    "ocs_quant_matmul",
    "fused_quant_matmul",
    "w4a8_matmul",
    "paged_attention",
    "moe_gmm",
    "backend_mode",
]


def backend_mode(force: Optional[str] = None) -> str:
    """'pallas' on TPU, 'ref' elsewhere; ``force`` overrides ('interpret')."""
    if force in ("pallas", "ref", "interpret"):
        return force
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@functools.partial(jax.jit, static_argnames=("force", "out_dtype"))
def quant_matmul(
    x, w8, w_scale, x_scale=None, *, force: Optional[str] = None, out_dtype=None
):
    """y = dequant(x?) @ dequant(w8). See quant_matmul.py for modes."""
    mode = backend_mode(force)
    if mode == "ref":
        xs = jnp.ones((), jnp.float32) if x_scale is None else x_scale
        return ref.quant_matmul_ref(x, w8, xs, w_scale, out_dtype or jnp.float32) \
            if x.dtype == jnp.int8 else _weight_only_ref(x, w8, w_scale, out_dtype)
    return _quant_matmul_pallas(
        x, w8, w_scale, x_scale, out_dtype=out_dtype,
        interpret=(mode == "interpret"),
    )


def _weight_only_ref(x, w8, w_scale, out_dtype=None):
    acc = jax.lax.dot_general(
        x.astype(jnp.float32),
        w8.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc = acc * jnp.asarray(w_scale, jnp.float32).reshape(1, -1)
    return acc.astype(out_dtype or x.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "force"))
def dynamic_quant(x, *, bits: int = 8, force: Optional[str] = None):
    """Per-row dynamic quantization: x [M, K] -> (q int8, scale [M])."""
    mode = backend_mode(force)
    if mode == "ref":
        return ref.dynamic_quant_ref(x, bits)
    return _dynamic_quant_pallas(x, bits=bits, interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("bits", "force", "out_dtype"))
def fused_quant_matmul(
    x, w8, w_scale, src_tail, *, bits: int = 8,
    force: Optional[str] = None, out_dtype=None,
):
    """One-pass dynamic-quant + OCS-expanded W8A8 matmul (fused_qmatmul.py).

    ``w8`` must be the *packed* expanded weights (see
    ``repro.core.ocs.fold_expansion_mult``); the ref backend runs the same
    numerics as three XLA passes.
    """
    mode = backend_mode(force)
    if mode == "ref":
        return ref.fused_quant_matmul_ref(x, w8, w_scale, src_tail, bits, out_dtype)
    return _fused_qmatmul_pallas(
        x, w8, w_scale, src_tail, bits=bits, out_dtype=out_dtype,
        interpret=(mode == "interpret"),
    )


@functools.partial(jax.jit, static_argnames=("bits", "force", "out_dtype"))
def w4a8_matmul(
    x, w4, s4, w8, s8, src_tail, outlier_idx, *, bits: int = 8,
    force: Optional[str] = None, out_dtype=None,
):
    """W4A8 matmul with OCS-separated 8-bit outlier channels.

    ``w4``: [(K+S)//2, N] uint8 split-half packed int4 weights with the
    outlier rows zeroed (:class:`repro.core.ocs.W4A8Linear` layout);
    ``w8``: [T, N] int8 outlier rows; ``outlier_idx``: [T] int32 expanded-K
    row indices. The ref backend runs the same numerics as the pure-jnp
    composition (bit-exact with the kernel).
    """
    mode = backend_mode(force)
    if mode == "ref":
        return ref.w4a8_matmul_ref(
            x, w4, s4, w8, s8, src_tail, outlier_idx, bits, out_dtype
        )
    return _w4a8_qmatmul_pallas(
        x, w4, s4, w8, s8, src_tail, outlier_idx, bits=bits,
        out_dtype=out_dtype, interpret=(mode == "interpret"),
    )


@functools.partial(jax.jit, static_argnames=("force",))
def paged_attention(pool, table, pos, q, k_new, v_new, *, force: Optional[str] = None):
    """Fused append + paged flash-decode attention over the KV page pool.

    pool: page-pool dict (``serving.kv_cache`` layout); table: ``[B, T]``
    int32; pos: ``[B]`` int32; q: ``[B, Q, H, hd]`` post-RoPE (unscaled);
    k_new/v_new: ``[B, Q, KV, hd]`` post-RoPE. Returns
    ``(out [B, Q, H, hd] f32, appended pool)``.

    Dispatch: the Pallas kernel on TPU (page tiles within the VMEM budget),
    the gather-free XLA online-softmax loop elsewhere — neither materializes
    the per-lane gathered cache. ``force="gather"`` runs the demoted
    gather-everything oracle; ``force="interpret"`` the kernel interpreted.

    This is where the serving API's ``KernelChoice`` attention selections
    land (threaded from ``EngineConfig.kernels.attn`` through
    ``attention_decode(attn_kernel=)``): ``"pallas"`` -> ``force=None``
    (backend auto), ``"xla"`` -> ``force="ref"`` (pin the XLA loop even on
    TPU); the ``"gather"`` choice takes the legacy path inside
    ``attention_decode`` and never reaches this dispatch.
    """
    if force == "gather":
        return _pa.paged_attention_gather_ref(pool, table, pos, q, k_new, v_new)
    mode = backend_mode(force)
    if mode == "ref":
        return _pa.paged_attention_xla(pool, table, pos, q, k_new, v_new)
    return _pa.paged_attention(
        pool, table, pos, q, k_new, v_new, interpret=(mode == "interpret")
    )


@functools.partial(
    jax.jit, static_argnames=("tail_is_mask", "force", "out_dtype")
)
def ocs_quant_matmul(
    x, w8, w_scale, src_tail, x_scale=None, tail_mult=None,
    *, tail_is_mask: bool = False, force: Optional[str] = None, out_dtype=None,
):
    """Fused OCS-expansion matmul (see ocs_matmul.py).

    ``tail_is_mask`` (static) declares a traced ``tail_mult`` to be a 0/1
    mask — required to use masks on the int8 path through this jitted
    dispatch, where values cannot be inspected.
    """
    mode = backend_mode(force)
    if mode == "ref":
        return ref.ocs_quant_matmul_ref(
            x, w8, w_scale, src_tail, x_scale, tail_mult, out_dtype
        )
    return _ocs_matmul_pallas(
        x, w8, w_scale, src_tail, x_scale, tail_mult=tail_mult,
        tail_is_mask=tail_is_mask, out_dtype=out_dtype,
        interpret=(mode == "interpret"),
    )


@functools.partial(jax.jit, static_argnames=("force",))
def moe_gmm(x, w8, w_scale, src_tail, mult_tail, group, n_used, *,
            force: Optional[str] = None):
    """Grouped OCS matmul over held experts (moe_gmm.py): row tile ``t`` of
    ``x`` times expert ``group[t]``'s expanded int8 weight; tiles from
    ``n_used`` on are padding."""
    mode = backend_mode(force)
    if mode == "ref":
        return ref.moe_gmm_ref(x, w8, w_scale, src_tail, mult_tail, group,
                               n_used)
    return _moe_gmm_pallas(x, w8, w_scale, src_tail, mult_tail, group, n_used,
                           interpret=(mode == "interpret"))
