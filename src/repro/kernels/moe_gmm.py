"""Pallas TPU kernel: grouped int8 OCS matmul over the experts held here.

The held-expert layer (``models/moe.py``) sorts a step's assignments by
expert and pads each expert's rows to the row tile, so every row tile
belongs to one expert. One call computes one projection (gate, up or down)
of every held expert on exactly its rows:

    y[t] = [x[t] | mult_g * x[t][:, src_g]] @ W_g * w_scale_g,   g = group[t]

``W`` is the stack ``[E, K+S, N]`` of the held experts' OCS-expanded int8
weights, each expert with its own split table (``src_g``, ``mult_g``: the
``S`` duplicated rows after the ``K`` originals, as in ``ocs_matmul``). The
wrapper forms each row's expanded activations from its tile's expert, in
one XLA gather; the kernel is a blocked matmul over the grid ``(N/bn,
tiles, (K+S)/bk)``, contraction innermost:

* **group table** — ``group[t]`` (scalar-prefetched) picks tile ``t``'s
  expert in the weight's index map, so a tile reads only its expert;
* **no rows, no DMA** — only experts with rows have tiles. Tiles from
  ``n_used`` on are padding of the static tile count: they compute nothing,
  and every block index stays where the last used tile left it, so they
  start no DMA;
* **bfloat16 x bfloat16 -> float32** — the int8 weight tile is converted
  in VMEM (exact), as ``ocs_matmul`` does; the per-column scale is applied
  once, on the last contraction step.

Tiles come from static shapes: :func:`row_tile` sizes ``bm`` to the rows an
expert expects (the layer pads to it), :func:`plan_tiles` takes the whole
contraction and the widest output tile that fit the VMEM plan budget, so a
run of tiles of one expert reads its weight once.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ocs_matmul import (PLAN_BUDGET_BYTES, VMEM_LIMIT_BYTES, _SUBLANES,
                         _splits, _vmem_bytes)

__all__ = ["row_tile", "max_tiles", "group_sizes", "plan_tiles", "moe_gmm",
           "GmmPlan"]

_BM_MAX = 512


class GmmPlan(NamedTuple):
    bn: int
    bk: int
    vmem_bytes: int


def row_tile(rows_per_expert: float, dtype) -> int:
    """Rows per tile for experts that expect ``rows_per_expert`` rows each:
    that many rounded up to the dtype's sublane tile, at most 512."""
    sub = _SUBLANES[jnp.dtype(dtype).itemsize]
    want = min(_BM_MAX, max(1, math.ceil(rows_per_expert)))
    return -(-want // sub) * sub


def max_tiles(n_tokens: int, k: int, n_held: int, bm: int) -> int:
    """The most row tiles ``n_tokens`` tokens routed to ``k`` distinct
    experts each can fill on ``n_held`` experts: an expert takes at most
    ``n_tokens`` rows, and each of them wastes less than a tile."""
    rows = min(n_tokens * k, n_held * n_tokens)
    return max(1, min(n_held * -(-n_tokens // bm), (rows + n_held * (bm - 1)) // bm))


def group_sizes(group, n_used, n_groups: int, bm: int):
    """Rows of each expert, padded to whole tiles: ``bm`` for every used
    tile of it (the ``group_sizes`` of ``jax.lax.ragged_dot`` over the
    tiled rows)."""
    used = jnp.arange(group.shape[0]) < n_used[0]
    return jnp.zeros((n_groups,), jnp.int32).at[group].add(
        jnp.where(used, bm, 0).astype(jnp.int32))


def plan_tiles(bm: int, ke: int, n: int, x_dtype, out_dtype) -> GmmPlan:
    """``(bn, bk)`` for ``[bm, ke] @ [ke, n]`` tiles: of the tiles that
    divide the stored dims (multiples of 128, or the whole dim) and fit
    :data:`PLAN_BUDGET_BYTES`, the fewest grid steps per row tile, the
    larger contraction tile first."""
    xb, ob = jnp.dtype(x_dtype).itemsize, jnp.dtype(out_dtype).itemsize
    best = None
    for bk in _splits(ke):
        for bn in _splits(n):
            vmem = _vmem_bytes(bm, bk, bn, xb, ob)
            if vmem > PLAN_BUDGET_BYTES:
                continue
            cand = ((ke // bk) * (n // bn), -bk, GmmPlan(bn, bk, vmem))
            best = cand if best is None or cand[:2] < best[:2] else best
    if best is None:
        raise ValueError(f"no grouped matmul tiles fit {PLAN_BUDGET_BYTES} "
                         f"bytes of VMEM for [{bm}, {ke}] @ [{ke}, {n}]")
    return best[2]


def _kernel(group_ref, used_ref, x_ref, w_ref, ws_ref, o_ref, acc_ref, *,
            nk: int):
    t, k = pl.program_id(1), pl.program_id(2)

    @pl.when(t < used_ref[0])
    def _tile():
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        acc_ref[...] += jax.lax.dot_general(
            x, w_ref[...].astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(k == nk - 1)
        def _epilogue():
            o_ref[...] = (acc_ref[...] * ws_ref[...]).astype(o_ref.dtype)


def moe_gmm_kernel(x_exp, w8, w_scale, group, n_used, *, bn: int, bk: int,
                   out_dtype, interpret: bool = False) -> jnp.ndarray:
    """Raw pallas_call. x_exp: [T*bm, K+S] (rows of tile ``t`` belong to
    expert ``group[t]``); w8: [E, K+S, N] int8; w_scale: [E, 1, N] f32;
    group: [T] int32; n_used: [1] int32. Rows of tiles from ``n_used`` on
    are not written."""
    m, ke = x_exp.shape
    _, _, n = w8.shape
    n_tiles = group.shape[0]
    bm = m // n_tiles
    nk = ke // bk

    def live(t, used):
        return t < used[0]

    def last(used):  # the last used tile (0 when none is)
        return jnp.maximum(used[0] - 1, 0)

    def x_map(j, t, k, group, used):
        on = live(t, used)
        return jnp.where(on, t, last(used)), jnp.where(on, k, nk - 1)

    def w_map(j, t, k, group, used):
        return group[t], jnp.where(live(t, used), k, nk - 1), j

    def s_map(j, t, k, group, used):
        return group[t], 0, j

    def o_map(j, t, k, group, used):
        return jnp.where(live(t, used), t, last(used)), j

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // bn, n_tiles, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), x_map),
                pl.BlockSpec((None, bk, bn), w_map),
                pl.BlockSpec((None, 1, bn), s_map),
            ],
            out_specs=pl.BlockSpec((bm, bn), o_map),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            # A padding tile keeps the last used tile's output block: the
            # tile axis must run in order.
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        name="moe_gmm",
        interpret=interpret,
    )(group, n_used, x_exp, w8, w_scale)


def column_scales(w_scale, e: int, n: int):
    """Each expert's output-column scales, [E, 1, N] float32."""
    ws = jnp.asarray(w_scale, jnp.float32).reshape(e, 1, -1)
    return jnp.broadcast_to(ws, (e, 1, n))


def expand_rows(x, src_tail, mult_tail, group):
    """``[x | mult_g * x[:, src_g]]`` row by row, ``g`` the row's expert:
    each expert's own split table.

    Every expert's split columns are taken for all rows at once, as whole
    rows of ``x``'s transpose, and each row keeps its own expert's by a
    select: a gather of one element per row and column costs the TPU a
    scalar step per element (most of a decode step's time at 16 experts)."""
    e, s = src_tail.shape
    m = x.shape[0]
    g = jnp.repeat(group, m // group.shape[0])
    cols = x.T[src_tail.reshape(-1)].reshape(e, s, m)
    cols = cols * mult_tail.astype(x.dtype)[:, :, None]
    mine = g[None, None, :] == jnp.arange(e, dtype=g.dtype)[:, None, None]
    tail = jnp.sum(jnp.where(mine, cols, jnp.zeros((), x.dtype)), axis=0,
                   dtype=x.dtype)
    return jnp.concatenate([x, tail.T], axis=1)


def moe_gmm(x, w8, w_scale, src_tail, mult_tail, group, n_used, *,
            interpret: bool = False) -> jnp.ndarray:
    """Grouped OCS matmul (see the module doc). x: [T*bm, K] bfloat16 rows
    grouped by expert; w8: [E, K+S, N] int8; w_scale: per column [E, 1, N]
    or [E, N], or per expert [E, 1, 1];
    src_tail / mult_tail: [E, S]; group: [T] int32; n_used: [1] int32.
    Returns [T*bm, N] in x's dtype; rows of unused tiles are undefined."""
    e, ke, n = w8.shape
    x_exp = expand_rows(x, src_tail, mult_tail, group)
    assert x_exp.shape[1] == ke, (x.shape, w8.shape, src_tail.shape)
    bm = x.shape[0] // group.shape[0]
    plan = plan_tiles(bm, ke, n, x.dtype, x.dtype)
    ws = column_scales(w_scale, e, n)
    return moe_gmm_kernel(x_exp, w8, ws, group, n_used, bn=plan.bn,
                          bk=plan.bk, out_dtype=x.dtype, interpret=interpret)
