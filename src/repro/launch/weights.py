"""Seeded random weights, quantized one slice at a time on the host.

A full-width model does not fit one chip in float32 (glm4-9b: about 9.4 B
parameters, 37.6 GB), and its float tree need not exist whole on the host
either. :func:`load_quantized` draws each parameter slice — one layer (one
expert) of one leaf — from its own numpy generator seeded by ``(seed, leaf,
slice)``, runs the recipe's OCS + quantize pipeline on that slice alone on
the host CPU, and puts each finished layer of a leaf on the device. Only the
quantized tree ever reaches the device. Slices are independent, so a thread
pool quantizes them in parallel (numpy and XLA's CPU ops release the GIL),
as many at a time as the host's memory allows; the result does not depend
on the order they finish in.

:func:`init_params_host` builds the whole float tree from the same slices:
for small models, the float comparison, and the check that
``load_quantized`` equals ``quantize_params(init_params_host(...))`` bit for
bit.
"""
from __future__ import annotations

import contextlib
import ctypes
import math
import os
from collections import deque
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.apply import path_str, quant_slice, slice_form, stack_linears
from repro.core.recipe import QuantRecipe
from repro.models import transformer as T

__all__ = ["SERVE_RECIPE", "init_params_host", "load_quantized"]

# The serving recipe: int8 weights, OCS r=0.02 with MSE clipping (§3.4/§4),
# per-output-channel scales, and the expanded contraction dim padded to the
# 128-lane tile the kernels take without a per-call pad copy.
SERVE_RECIPE = QuantRecipe(
    w_bits=8, w_clip="mse", ocs_ratio=0.02, per_channel=True, a_bits=None,
    pad_to=128,
)

# Threads that quantize slices at once, at most; fewer where the process may
# use fewer CPUs.
MAX_WORKERS = 8
# Host memory a new slice must leave free. The host's free memory shrinks by
# more than the slices hold, and recovers only tens of seconds after they
# end, so a slice starts only while the host reports memory for it and this
# reserve; one that cannot fit runs alone. A one-chip TPU v5e host reports
# 46.8 GB available at the start and ends a process at 40 GiB in use; with
# 8 GiB here the glm4-9b load peaked at 37.8 GiB.
HOST_RESERVE_BYTES = 10 * 2**30


def _host_available() -> Optional[int]:
    """``MemAvailable`` of the host in bytes, or None where not reported."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


@contextlib.contextmanager
def _retain_freed_memory():
    """For the load only, keep the memory slices free inside the process for
    the next slice to reuse (glibc: no mmap-backed blocks, no heap
    trimming), then return it once. A no-op without glibc's ``mallopt``.

    With the admission rule of :func:`load_quantized` alone, the host's free
    memory fell about 1 GB/s while eight threads quantized slices and
    recovered only in bursts: the glm4-9b load on a one-chip TPU v5e host
    (40 GiB) was killed at the limit 145 s in.

    glibc cannot report these settings, so they return to its defaults,
    not to values read beforehand. Setting them also stops glibc from
    moving its mmap threshold for the rest of the process: the threshold
    stays where it was when the load began.
    """
    try:
        libc = ctypes.CDLL(None)
        mallopt, trim = libc.mallopt, libc.malloc_trim
    except (OSError, AttributeError):
        yield
        return
    m_trim_threshold, m_mmap_max = -1, -4
    mallopt(m_trim_threshold, 2**31 - 1)
    mallopt(m_mmap_max, 0)
    try:
        yield
    finally:
        mallopt(m_mmap_max, 65536)  # glibc's defaults
        mallopt(m_trim_threshold, 128 * 1024)
        trim(0)


def _param_leaves(cfg: ModelConfig):
    """[(path, shape)] in tree order, and the tree structure."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        T.model_params_shape(cfg), is_leaf=lambda s: isinstance(s, tuple)
    )
    return [(path_str(p), tuple(shape)) for p, shape in flat], treedef


def _lead(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """Stack dims sliced over: everything before a matrix's last two."""
    return shape[:-2] if len(shape) >= 2 else ()


def _float_slice(cfg, seed: int, leaf: int, path: str, shape, i: int):
    """Slice ``i`` (row-major over the stack dims) of one leaf, float32,
    drawn as the uncut model's slice ``T.param_draw_index``."""
    kind, std = T.param_init_rule(cfg, path, shape)
    sl = shape[len(_lead(shape)):]
    if kind == "normal":
        w = np.random.default_rng(
            (seed, leaf, T.param_draw_index(cfg, path, i))
        ).standard_normal(sl, dtype=np.float32)
        w *= np.float32(std)  # in place: a vocab-sized slice is 2.3 GiB
        return w
    if kind == "a_log":
        base = np.log(np.linspace(1.0, 16.0, sl[-1], dtype=np.float32))
        return np.broadcast_to(base, sl).copy()
    return (np.ones if kind.startswith("ones") else np.zeros)(sl, np.float32)


def _quantized(path: str, shape, recipe: Optional[QuantRecipe]) -> bool:
    return (recipe is not None and recipe.wants_weight_quant()
            and len(shape) >= 2 and not recipe.should_skip(path))


def _host_cost(leaf: Tuple[str, Tuple[int, ...]], recipe) -> int:
    """Host bytes one slice of ``leaf`` holds while it is built: the float
    slice, and for a quantized one the pipeline's buffers (the split result,
    its padded copy and the quantizer's input: 2.5x the slice at peak,
    measured at 4096x13696 on a CPU; 4x with the slice itself and some
    margin)."""
    path, shape = leaf
    nbytes = 4 * math.prod(shape[len(_lead(shape)):])
    return nbytes * (4 if _quantized(path, shape, recipe) else 1)


def init_params_host(cfg: ModelConfig, seed: int):
    """The whole float32 tree, stacked from the loader's seeded slices."""
    leaves, treedef = _param_leaves(cfg)
    out = []
    for leaf, (path, shape) in enumerate(leaves):
        n = math.prod(_lead(shape))
        slices = [_float_slice(cfg, seed, leaf, path, shape, i)
                  for i in range(n)]
        out.append(jnp.asarray(np.stack(slices).reshape(shape)))
    return treedef.unflatten(out)


def load_quantized(
    cfg: ModelConfig,
    seed: int,
    recipe: QuantRecipe = SERVE_RECIPE,
    *,
    device=None,
):
    """The quantized tree of :func:`init_params_host`'s weights, built one
    slice at a time on the host and placed on ``device`` (default: the first
    device) as soon as a layer of a leaf is complete. The tree is in the
    serving layout, ``params["layers"]`` a list of per-layer trees
    (``models.transformer.per_layer``); stacked again, it is equal bit for
    bit to ``quantize_params(init_params_host(cfg, seed), recipe)``.

    Up to ``MAX_WORKERS`` threads quantize slices at once. A slice starts
    only while the host reports the memory its work needs
    (:func:`_host_cost`) available beyond ``HOST_RESERVE_BYTES``; a slice
    that cannot fit runs alone. Freed memory stays in the process until
    the load ends (:func:`_retain_freed_memory`).
    """
    device = device if device is not None else jax.devices()[0]
    cpu = jax.devices("cpu")[0]
    leaves, treedef = _param_leaves(cfg)
    counts = [math.prod(_lead(shape)) for _, shape in leaves]

    def build(leaf: int, i: int):
        path, shape = leaves[leaf]
        w = _float_slice(cfg, seed, leaf, path, shape, i)
        if not _quantized(path, shape, recipe):
            return w
        with jax.default_device(cpu):
            return jax.block_until_ready(quant_slice(w, recipe))

    def layer_leaf(leaf: int) -> bool:
        return leaves[leaf][0].startswith("layers/")

    # Slices are placed in groups: one layer (all its experts) of a stacked
    # per-layer matrix, else the whole leaf (a per-layer [L, d] vector is
    # one slice and is split into layers when placed).
    by_layer = [layer_leaf(leaf) and bool(_lead(shape))
                for leaf, (_, shape) in enumerate(leaves)]
    n_groups = [shape[0] if by_layer[leaf] else 1
                for leaf, (_, shape) in enumerate(leaves)]
    size = [n // g for n, g in zip(counts, n_groups)]

    def to_device(tree):
        # numpy in, and waited on: no transfer keeps a host copy alive.
        return jax.block_until_ready(
            jax.device_put(jax.tree.map(np.asarray, tree), device))

    def place(leaf: int, parts: List):
        """A complete group of slices -> its device form."""
        path, shape = leaves[leaf]
        lead = _lead(shape)
        inner = lead[1:] if by_layer[leaf] else lead
        if not _quantized(path, shape, recipe):
            full = np.stack(parts).reshape(inner + shape[len(lead):])
            if layer_leaf(leaf) and not lead:
                return [to_device(row) for row in full]
            return to_device(full)
        with jax.default_device(cpu):
            if inner:
                return to_device(stack_linears(parts, inner, recipe))
            if layer_leaf(leaf):
                return to_device(slice_form(parts[0], recipe))
            return to_device(parts[0])

    # Largest slices first, so the single-slice matrices (lm_head, embed)
    # do not finish alone after the many per-layer slices.
    jobs = deque(sorted(
        ((leaf, i) for leaf in range(len(leaves)) for i in range(counts[leaf])),
        key=lambda j: -_host_cost(leaves[j[0]], recipe),
    ))
    parts: List[List] = [[None] * n for n in counts]
    out: List[List] = [[None] * g for g in n_groups]
    missing = [[k] * g for k, g in zip(size, n_groups)]
    workers = min(len(os.sched_getaffinity(0)), MAX_WORKERS)
    running = {}
    with _retain_freed_memory(), ThreadPoolExecutor(workers) as pool:
        while jobs or running:
            avail, admitted = _host_available(), 0
            while jobs and len(running) < workers:
                cost = _host_cost(leaves[jobs[0][0]], recipe)
                if running and avail is not None and (
                        avail - admitted < cost + HOST_RESERVE_BYTES):
                    break
                job = jobs.popleft()
                running[pool.submit(build, *job)] = job
                admitted += cost  # not allocated yet: avail cannot show it
            done, _ = wait(running, return_when=FIRST_COMPLETED)
            for fut in done:
                leaf, i = running.pop(fut)
                parts[leaf][i] = fut.result()
                g, k = i // size[leaf], size[leaf]
                missing[leaf][g] -= 1
                if missing[leaf][g] == 0:  # group complete: place, free host copy
                    out[leaf][g] = place(leaf, parts[leaf][g * k:(g + 1) * k])
                    parts[leaf][g * k:(g + 1) * k] = [None] * k
    params = treedef.unflatten([
        o if by_layer[leaf] else o[0] for leaf, o in enumerate(out)
    ])
    # The serving layout: see models.transformer.per_layer.
    is_list = lambda x: isinstance(x, list)
    params["layers"] = T.split_layers(
        params["layers"],
        take=lambda sub, j: jax.tree.map(lambda per: per[j], sub, is_leaf=is_list),
        count=lambda sub: len(jax.tree.leaves(sub, is_leaf=is_list)[0]),
    )
    return params
