"""Mixture-of-Experts block: top-k routing, shared experts, EP sharding.

Dispatch is sort-based with per-expert capacity (no [N, E, C] one-hot tensor):
tokens are ranked within their assigned expert via a stable argsort + segment
offsets, dropped past capacity, gathered into an [E, C, d] buffer, processed
by a vmapped expert MLP, and combined back with the routing gates.

Two execution paths share that algorithm:

* **shard-local dispatch under shard_map** (:func:`_moe_sharded`) — the
  production training path. Data-dependent scatter/gather cannot be
  partitioned by GSPMD: left to the automatic partitioner it replicates the
  [E*C, d] buffers and all-reduces them (measured 10.4 TB/device/step on
  deepseek-moe-16b train_4k — 60x the model's own traffic). Under shard_map
  every device keeps only its own tokens (batch-sharded) and its own experts
  (expert axis on 'model'): routing, sorting and the capacity scatter are
  purely local, expert weights' FSDP dim is all-gathered explicitly, and the
  only cross-device traffic is one psum of the [N_local, d] output partials
  over the expert axis. Capacity is enforced per (data-shard, expert) rather
  than globally — the standard GShard-style approximation.
* **single-device / GSPMD fallback** (:func:`_moe_local`) — identical math
  on one shard; also the serving path for quantized (OCSQuantLinear) expert
  weights, whose pytree leaves keep their own sharding story.

Supports DeepSeek-MoE fine-grained experts (64 routed, top-6, 2 shared) and
Phi-3.5-MoE (16 routed, top-2). Shared experts are fused into one wide SwiGLU
(mathematically identical to summing independent always-on experts).

The router stays in full precision and is excluded from PTQ (recipe skip
pattern 'router') — it is tiny and routing decisions are brittle under
quantization; expert weights are quantized per-expert (per-slice OCS split
tables).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.sharding.specs import activation_rules, logical
from .layers import dense

__all__ = ["moe_params_shape", "moe"]


def moe_params_shape(cfg: ModelConfig) -> Dict:
    d, m = cfg.d_model, cfg.moe
    shapes = {
        "router": (d, m.n_experts),
        "experts": {
            "w_gate": (m.n_experts, d, m.expert_ff),
            "w_up": (m.n_experts, d, m.expert_ff),
            "w_down": (m.n_experts, m.expert_ff, d),
        },
    }
    if m.n_shared:
        f = m.n_shared * m.expert_ff
        shapes["shared"] = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return shapes


def _as_weight(w):
    """Rebuild a dense()-compatible weight from a packed component dict.

    The shard_map dispatch passes expert weights as plain array pytrees
    (shard_map in_specs are per-array); quantized experts travel as their
    {values, scale, src, mult, bias} components and are reassembled into an
    OCSQuantLinear here (static metadata is re-attached; ``bits`` is not
    used on the dequant path).
    """
    if isinstance(w, dict) and "values" in w:
        from repro.core.ocs import OCSQuantLinear, OCSSpec
        from repro.core.quantizer import QuantParams

        return OCSQuantLinear(
            weight=QuantParams(values=w["values"], scale=w["scale"]),
            spec=OCSSpec(src=w["src"], mult=w["mult"], bias=w["bias"]),
        )
    return w


def _expert_mlp(w, x):
    """One expert's SwiGLU on its capacity slice. x: [C, d]."""
    g = dense(_as_weight(w["w_gate"]), x, name="moe_gate")
    u = dense(_as_weight(w["w_up"]), x, name="moe_up")
    return dense(_as_weight(w["w_down"]), jax.nn.silu(g) * u, name="moe_down")


def _route(router_w, xf: jnp.ndarray, k: int):
    """Top-k routing with renormalized gates (f32 softmax)."""
    logits = dense(router_w, xf.astype(jnp.float32), name="router")
    probs = jax.nn.softmax(logits, axis=-1)
    gate, top_idx = jax.lax.top_k(probs, k)  # [N, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate, top_idx


def _dispatch_mlp_combine(experts, xf, gate, top_idx, *, n_experts: int,
                          e0, cap: int, dtype) -> jnp.ndarray:
    """Shard-local sort-based dispatch -> expert MLP -> gated combine.

    xf: [N, d] tokens held by this shard; experts: stacked weights for the
    ``n_experts`` experts owned by this shard, whose global ids start at
    ``e0`` (0 on the single-device path). Assignments to other shards'
    experts fall into the drop slot. Returns this shard's output partial.
    """
    n, d = xf.shape
    k = top_idx.shape[-1]
    flat_e = top_idx.reshape(-1) - e0  # local expert id (may be out of range)
    flat_t = jnp.repeat(jnp.arange(n), k)
    flat_g = gate.reshape(-1)
    mine = (flat_e >= 0) & (flat_e < n_experts)
    key = jnp.where(mine, flat_e, n_experts)  # foreign -> sort to the end
    order = jnp.argsort(key, stable=True)
    sorted_e = key[order]
    sorted_t = flat_t[order]
    sorted_g = flat_g[order]
    counts = jnp.bincount(key, length=n_experts)
    starts = jnp.cumsum(counts) - counts  # exclusive prefix
    pos_in_e = jnp.arange(n * k) - starts[jnp.minimum(sorted_e, n_experts - 1)]
    keep = (sorted_e < n_experts) & (pos_in_e < cap)
    dest = jnp.where(keep, sorted_e * cap + pos_in_e, n_experts * cap)

    buf = jnp.zeros((n_experts * cap + 1, d), dtype).at[dest].set(xf[sorted_t])
    xd = buf[: n_experts * cap].reshape(n_experts, cap, d)
    yd = jax.vmap(_expert_mlp)(experts, xd)  # [E_local, C, d]

    y_flat = yd.reshape(n_experts * cap, d)
    contrib = jnp.where(
        keep[:, None], y_flat[jnp.minimum(dest, n_experts * cap - 1)], 0.0
    )
    return jnp.zeros((n, d), dtype).at[sorted_t].add(
        (contrib * sorted_g[:, None]).astype(dtype)
    )


def _capacity(n_tokens: int, k: int, cf: float, e: int) -> int:
    cap = int(-(-(n_tokens * k) * cf // e))  # ceil
    return max(8, -(-cap // 8) * 8)  # pad to a multiple of 8 lanes


def _moe_local(params, xf, cfg: ModelConfig) -> jnp.ndarray:
    """Single-shard path (also GSPMD fallback for quantized expert trees)."""
    m = cfg.moe
    gate, top_idx = _route(params["router"], xf, m.top_k)
    cap = _capacity(xf.shape[0], m.top_k, m.capacity_factor, m.n_experts)
    return _dispatch_mlp_combine(
        params["experts"], xf, gate, top_idx,
        n_experts=m.n_experts, e0=0, cap=cap, dtype=xf.dtype,
    )


def _shardmap_axes(mesh, rules) -> Optional[Tuple[Tuple[str, ...], str]]:
    """(batch_axes, expert_axis) when the active mesh supports EP dispatch."""
    model_ax = rules.get("expert")
    batch_ax = rules.get("batch")
    if model_ax is None or batch_ax is None:
        return None
    batch_axes = batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)
    if isinstance(model_ax, tuple) or model_ax in batch_axes:
        return None
    return batch_axes, model_ax


def _pack_experts(experts):
    """Expert weights -> plain array pytrees (shard_map specs are per-array).

    Float matrices pass through; OCSQuantLinear stacks decompose into their
    {values, scale, src, mult, bias} components (reassembled per expert by
    ``_as_weight`` inside the manual region).
    """
    from repro.core.ocs import OCSQuantLinear

    def pack(w):
        if isinstance(w, OCSQuantLinear):
            return {"values": w.weight.values, "scale": w.weight.scale,
                    "src": w.spec.src, "mult": w.spec.mult, "bias": w.spec.bias}
        return w

    return {k: pack(experts[k]) for k in ("w_gate", "w_up", "w_down")}


def _moe_sharded(params, xf, cfg: ModelConfig, mesh, batch_axes, model_ax,
                 fsdp_ax: Optional[str]) -> jnp.ndarray:
    """Shard-local dispatch under shard_map (see module docstring).

    Works for float expert weights (training) and quantized OCS trees
    (serving prefill): the big matrices keep their FSDP dim sharded in
    transit (int8 on the wire for quantized values) and are all-gathered
    inside the manual region; component metadata (scales, split tables)
    rides replicated-over-data.
    """
    m = cfg.moe
    e = m.n_experts
    model_size = mesh.shape[model_ax]
    e_local = e // model_size
    dsize = 1
    for a in batch_axes:
        dsize *= mesh.shape[a]
    n_local = xf.shape[0] // dsize
    cap = _capacity(n_local, m.top_k, m.capacity_factor, e)

    gate_full, idx_full = _route(params["router"], xf, m.top_k)

    fsdp_size = mesh.shape[fsdp_ax] if fsdp_ax else 1
    pack = _pack_experts(params["experts"])

    def wt_axis(name):  # FSDP dim of the big matrix (matches param rules)
        return 1 if name != "w_down" else 2

    specs, gathers = {}, {}
    for name, leaf in pack.items():
        ax = wt_axis(name)
        if isinstance(leaf, dict):
            s, g = {}, {}
            for comp, arr in leaf.items():
                if comp == "values" and fsdp_ax and arr.shape[ax] % fsdp_size == 0:
                    parts = [model_ax] + [None] * (arr.ndim - 1)
                    parts[ax] = fsdp_ax
                    s[comp], g[comp] = P(*parts), ax
                else:
                    s[comp] = P(*([model_ax] + [None] * (arr.ndim - 1)))
                    g[comp] = -1  # -1 = no gather (None is a pytree node)
            specs[name], gathers[name] = s, g
        else:
            if fsdp_ax and leaf.shape[ax] % fsdp_size == 0:
                parts = [model_ax] + [None] * (leaf.ndim - 1)
                parts[ax] = fsdp_ax
                specs[name], gathers[name] = P(*parts), ax
            else:
                specs[name] = P(*([model_ax] + [None] * (leaf.ndim - 1)))
                gathers[name] = -1

    def inner(xf_l, gate_l, idx_l, pack_l):
        # Gather the FSDP dim back (explicit in the manual region; the
        # backward pass reduce-scatters the corresponding weight grads).
        def gather(leaf, g):
            if g < 0:
                return leaf
            return jax.lax.all_gather(leaf, fsdp_ax, axis=g, tiled=True)

        experts = jax.tree.map(
            gather, pack_l, gathers,
            is_leaf=lambda x: not isinstance(x, dict),
        )
        e0 = jax.lax.axis_index(model_ax) * e_local
        y_part = _dispatch_mlp_combine(
            experts, xf_l, gate_l, idx_l,
            n_experts=e_local, e0=e0, cap=cap, dtype=xf_l.dtype,
        )
        return jax.lax.psum(y_part, model_ax)

    batch_spec = tuple(batch_axes) if len(batch_axes) > 1 else batch_axes[0]
    axis_names = set(batch_axes) | {model_ax} | (
        {fsdp_ax} if fsdp_ax else set()
    )
    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(batch_spec, None), P(batch_spec, None), P(batch_spec, None),
                  specs),
        out_specs=P(batch_spec, None),
        axis_names=axis_names,
        check_vma=False,
    )(xf, gate_full, idx_full, pack)


def moe(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    b, s, d = x.shape
    m = cfg.moe
    xf = x.reshape(b * s, d)

    from repro.core.ocs import OCSQuantLinear

    active = activation_rules()
    use_shardmap = False
    w_gate_leaf = params["experts"]["w_gate"]
    if active is not None and isinstance(
        w_gate_leaf, (jnp.ndarray, OCSQuantLinear)
    ):
        mesh, rules = active
        axes = _shardmap_axes(mesh, rules)
        if axes is not None and m.n_experts % mesh.shape[axes[1]] == 0:
            batch_axes, model_ax = axes
            dsize = 1
            for a in batch_axes:
                dsize *= mesh.shape[a]
            if (b * s) % max(dsize, 1) == 0:
                use_shardmap = True

    if use_shardmap:
        # fsdp='data' shards the weights' d dim; it is also a batch axis for
        # xf — different tensors, coherent specs. Only an fsdp==expert-axis
        # collision (never produced by the rule tables) would be unsound.
        fsdp_ax = rules.get("fsdp")
        if isinstance(fsdp_ax, tuple) or fsdp_ax == model_ax:
            fsdp_ax = None
        y = _moe_sharded(params, xf, cfg, mesh, batch_axes, model_ax, fsdp_ax)
    else:
        y = _moe_local(params, xf, cfg)

    # --- Shared (always-on) experts (dense GSPMD tensor-parallel matmuls).
    if "shared" in params:
        sh = params["shared"]
        g = dense(sh["w_gate"], xf, name="moe_shared_gate")
        u = dense(sh["w_up"], xf, name="moe_shared_up")
        y = y + dense(sh["w_down"], jax.nn.silu(g) * u, name="moe_shared_down")
    return y.reshape(b, s, d)
