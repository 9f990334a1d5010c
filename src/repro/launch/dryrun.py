"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the placeholder device count before ANY other import (jax locks the
device count at first init) — hence the first two lines.

For each cell this driver:
  1. builds ShapeDtypeStruct stand-ins for every input (params, optimizer
     state, KV/SSM caches, token batches) — zero device allocation;
  2. jits the step with explicit in/out shardings from the logical rules;
  3. ``.lower()`` + ``.compile()`` on the production mesh;
  4. prints ``compiled.memory_analysis()`` (proves it fits) and
     ``compiled.cost_analysis()`` (FLOPs/bytes for the roofline);
  5. parses the HLO for collective bytes (all-gather / all-reduce /
     reduce-scatter / all-to-all / collective-permute);
  6. writes a JSON record consumed by ``benchmarks/roofline.py``.

Shapes follow the assignment: ``train_4k`` lowers ``train_step``;
``prefill_32k`` lowers the prefill forward; ``decode_32k`` / ``long_500k``
lower ``serve_step`` (one token against a seq_len cache). Serving runs with
the OCS-quantized int8 parameter tree (the paper's deployment scenario);
``--float-serve`` switches to bf16 weights for the baseline comparison.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from functools import partial  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_config, list_archs  # noqa: E402
from repro.core.apply import abstract_quantize_params, path_str  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.weights import SERVE_RECIPE  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    TrainHyper,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models import transformer as T  # noqa: E402
from repro.optim import adamw_init  # noqa: E402
from repro.sharding.specs import (  # noqa: E402
    LogicalRules,
    MULTI_POD_RULES,
    SINGLE_POD_RULES,
    param_sharding,
    param_spec_tree,
    use_rules,
)

# TPU v5e hardware constants (per chip).
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s/link


# Assignment skip rules (see DESIGN.md §6).
FULL_ATTN_ARCHS = {
    "deepseek-moe-16b",
    "phi3.5-moe-42b-a6.6b",
    "glm4-9b",
    "minitron-8b",
    "deepseek-7b",
    "qwen3-14b",
    "qwen2-vl-7b",
}


def cell_skip_reason(arch: str, shape: str):
    cfg = get_config(arch)
    if not cfg.causal and SHAPES[shape].kind == "decode":
        return "encoder-only: no decode step"
    if shape == "long_500k" and arch in FULL_ATTN_ARCHS:
        return "pure full-attention arch: long_500k needs sub-quadratic attention"
    return None


def serve_rules(multi_pod: bool) -> LogicalRules:
    base = MULTI_POD_RULES if multi_pod else SINGLE_POD_RULES
    return LogicalRules({**base.table, "fsdp": None})


# ---------------------------------------------------------------------------
# Abstract inputs


def abstract_params(cfg, dtype=jnp.float32):
    shapes = T.model_params_shape(cfg)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, dtype),
        shapes,
        is_leaf=lambda s: isinstance(s, tuple),
    )


def _cfg(arch: str, overrides=None):
    import dataclasses as _dc

    cfg = get_config(arch)
    return _dc.replace(cfg, **overrides) if overrides else cfg


def input_specs(arch: str, shape_name: str, *, serve_quant: bool = True,
                cfg_overrides=None):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    cfg = _cfg(arch, cfg_overrides)
    sh = SHAPES[shape_name]
    b, s = sh.global_batch, sh.seq_len
    sds = jax.ShapeDtypeStruct
    if sh.kind == "train":
        if cfg.frontend == "audio":
            batch = {
                "embeds": sds((b, s, cfg.d_model), jnp.float32),
                "labels": sds((b, s), jnp.int32),
            }
        else:
            batch = {
                "tokens": sds((b, s), jnp.int32),
                "labels": sds((b, s), jnp.int32),
            }
        params = abstract_params(cfg, jnp.float32)
        opt = jax.eval_shape(adamw_init, params)
        return {"params": params, "opt_state": opt, "batch": batch}
    if sh.kind == "prefill":
        params = abstract_params(cfg, jnp.bfloat16)
        if serve_quant:
            params = abstract_quantize_params(params, SERVE_RECIPE)
        if cfg.frontend == "audio":
            batch = {"embeds": sds((b, s, cfg.d_model), jnp.float32)}
        else:
            batch = {"tokens": sds((b, s), jnp.int32)}
        return {"params": params, "batch": batch}
    # decode
    params = abstract_params(cfg, jnp.bfloat16)
    if serve_quant:
        params = abstract_quantize_params(params, SERVE_RECIPE)
    caches = jax.eval_shape(partial(T.init_cache, cfg, b, s, dtype=jnp.bfloat16))
    token = sds((b, 1), jnp.int32)
    return {"params": params, "caches": caches, "token": token}


# ---------------------------------------------------------------------------
# Sharding of batches and caches


def _guard(mesh, shape, names, rules):
    """Logical names -> PartitionSpec with divisibility + axis-reuse fallback."""
    axes = []
    used = set()
    for dim, name in zip(shape, names):
        ax = rules.get(name)
        if ax is None:
            axes.append(None)
            continue
        mesh_axes = ax if isinstance(ax, tuple) else (ax,)
        if any(a in used for a in mesh_axes):
            axes.append(None)
            continue
        total = int(np.prod([mesh.shape[a] for a in mesh_axes]))
        if dim % total == 0 and dim >= total:
            axes.append(ax)
            used.update(mesh_axes)
        else:
            axes.append(None)
    return P(*axes)


def batch_sharding(batch_sds, mesh, rules):
    def visit(path, leaf):
        names = ["batch"] + [None] * (len(leaf.shape) - 1)
        return NamedSharding(mesh, _guard(mesh, leaf.shape, names, rules))

    return jax.tree_util.tree_map_with_path(visit, batch_sds)


def cache_sharding(cache_sds, cfg, mesh, rules):
    """KV caches: batch->data, kv-heads->model (seq->model when kv undivisible);
    SSM states: batch->data, heads->model."""

    def visit(path, leaf):
        p = path_str(path).lower()
        shape = leaf.shape
        n = len(shape)
        names = [None] * n
        if n == 0:
            return NamedSharding(mesh, P())
        if "meta_" in p:
            # [B, M, KV, hd]
            names[0] = "batch"
            if shape[-2] % mesh.shape["model"] == 0:
                names[-2] = "kv_heads"
            return NamedSharding(mesh, _guard(mesh, shape, names, rules))
        if re.search(r"(^|/)(k|v)$", p):
            # [B, KV, S, hd] (head-major decode layout)
            names[0] = "batch"
            model = mesh.shape["model"]
            if shape[-3] % model == 0:
                names[-3] = "kv_heads"
            elif shape[-2] % model == 0:
                names[-2] = "heads"  # shard the sequence dim over 'model'
            return NamedSharding(mesh, _guard(mesh, shape, names, rules))
        if re.search(r"(^|/)(k|v)_scale$", p):
            # int8-cache scales [B, KV, S]: shard like the cache values.
            names[0] = "batch"
            model = mesh.shape["model"]
            if shape[-2] % model == 0:
                names[-2] = "kv_heads"
            elif shape[-1] % model == 0:
                names[-1] = "heads"
            return NamedSharding(mesh, _guard(mesh, shape, names, rules))
        if "state" in p:
            # [L,B,g,r,p,n] | [B,g,r,p,n]
            bdim = n - 5
            names[bdim] = "batch"
            names[bdim + 2] = "ssm_heads"
            return NamedSharding(mesh, _guard(mesh, shape, names, rules))
        if "conv" in p:
            # [L,B,W-1,conv_dim] | [B,W-1,conv_dim]
            bdim = n - 3
            names[bdim] = "batch"
            names[-1] = "conv_dim"
            return NamedSharding(mesh, _guard(mesh, shape, names, rules))
        # pos and anything else: replicate.
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(visit, cache_sds)


# ---------------------------------------------------------------------------
# HLO collective parsing


_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9_\[\],{}\s]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(",
)
_ARR_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _ARR_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total = max(total, n * _DTYPE_BYTES[dt])
    return total


def collective_bytes(hlo_text: str):
    """Sum result-tensor bytes per collective kind (wire-traffic proxy)."""
    out = {}
    count = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind, start = m.group(1), m.group(2), m.group(3)
        if start is None and (kind + "-start(") in hlo_text and False:
            pass
        b = _type_bytes(type_str)
        out[kind] = out.get(kind, 0) + b
        count[kind] = count.get(kind, 0) + 1
    # '-done' ops share the '-start' result; the regex only matches lines with
    # '(' directly after the op name, and '-done' lines also match. To avoid
    # double counting async pairs, halve kinds that appear as start/done.
    for kind in list(out):
        n_start = hlo_text.count(f"{kind}-start(")
        n_done = hlo_text.count(f"{kind}-done(")
        if n_start and n_done:
            out[kind] = out[kind] // 2
            count[kind] = count[kind] // 2
    return out, count


# ---------------------------------------------------------------------------
# Cell runner


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    serve_quant: bool = True,
    n_micro: int = 8,
    hlo_out: str = "",
    verbose: bool = True,
    cfg_overrides=None,
):
    cfg = _cfg(arch, cfg_overrides)
    sh = SHAPES[shape_name]
    reason = cell_skip_reason(arch, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name, "skip": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = MULTI_POD_RULES if multi_pod else SINGLE_POD_RULES
    if sh.kind != "train":
        rules = serve_rules(multi_pod)
    spec = input_specs(arch, shape_name, serve_quant=serve_quant,
                       cfg_overrides=cfg_overrides)

    t0 = time.time()
    with use_rules(mesh, rules):
        if sh.kind == "train":
            hyper = TrainHyper(n_micro=n_micro)
            step = make_train_step(cfg, hyper)
            p_sh = param_spec_tree(spec["params"], mesh, rules)
            o_sh = param_spec_tree(spec["opt_state"], mesh, rules)
            b_sh = batch_sharding(spec["batch"], mesh, rules)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(spec["params"], spec["opt_state"], spec["batch"])
        elif sh.kind == "prefill":
            step = make_prefill_step(cfg)
            p_sh = param_spec_tree(spec["params"], mesh, rules)
            b_sh = batch_sharding(spec["batch"], mesh, rules)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh), out_shardings=None)
            lowered = jitted.lower(spec["params"], spec["batch"])
        else:
            step = make_serve_step(cfg)
            p_sh = param_spec_tree(spec["params"], mesh, rules)
            c_sh = cache_sharding(spec["caches"], cfg, mesh, rules)
            t_sh = batch_sharding({"t": spec["token"]}, mesh, rules)["t"]
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, c_sh, t_sh),
                out_shardings=(t_sh, None, c_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(spec["params"], spec["caches"], spec["token"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(hlo)

    # Trip-count-aware cost model (XLA's cost_analysis visits loop bodies
    # once, under-reporting scanned-layer steps by orders of magnitude).
    from repro.launch.hlo_cost import analyze_hlo

    hc = analyze_hlo(hlo)
    n_chips = int(np.prod(list(mesh.shape.values())))
    flops = hc.flops
    bytes_acc = hc.bytes
    coll = {k: float(v) for k, v in hc.collective_bytes.items()}
    coll_count = {k: int(v) for k, v in hc.collective_counts.items()}
    coll_total = hc.collective_total

    # Model FLOPs (6ND train / 2ND inference; N = active params).
    n_active = cfg.active_param_count()
    tokens = sh.global_batch * (sh.seq_len if sh.kind != "decode" else 1)
    mult = 6 if sh.kind == "train" else 2
    model_flops_global = mult * n_active * tokens

    # Analytic memory floor (bytes/device a perfectly-fused step must touch):
    # CPU-backend HLO fuses less than TPU, inflating measured bytes; the floor
    # bounds the achievable memory term from below (see EXPERIMENTS.md).
    if sh.kind == "train":
        p_bytes = 4 * cfg.param_count() / n_chips  # f32 master, FSDP+TP sharded
        mem_floor = (n_micro + 2) * p_bytes + 12 * p_bytes / 4
    elif sh.kind == "decode":
        mem_floor = float(mem.argument_size_in_bytes) * 2  # params + cache r/w
    else:
        mem_floor = float(mem.argument_size_in_bytes) * 1.5

    result = {
        "arch": arch,
        "shape": shape_name,
        "kind": sh.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "serve_quant": bool(serve_quant and sh.kind != "train"),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device": {
            "flops": flops,
            "bytes_accessed": bytes_acc,
            "xla_flops_unscaled": float(cost.get("flops", 0.0)),
            "xla_bytes_unscaled": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes": coll_total,
            "collectives": coll,
            "collective_counts": coll_count,
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
            ),
        },
        "roofline_s": {
            "compute": flops / PEAK_FLOPS,
            "memory": bytes_acc / HBM_BW,
            "collective": coll_total / ICI_BW,
        },
        "memory_floor_s": mem_floor / HBM_BW,
        "model_flops_global": model_flops_global,
        "model_flops_per_chip": model_flops_global / n_chips,
        "useful_flops_ratio": (model_flops_global / n_chips) / max(flops, 1.0),
    }
    dom = max(result["roofline_s"], key=result["roofline_s"].get)
    result["bottleneck"] = dom
    if verbose:
        print(f"== {arch} x {shape_name} ({result['mesh']}) ==")
        print("memory_analysis:", mem)
        print("cost_analysis flops:", flops, "bytes:", bytes_acc)
        print("collectives:", coll, coll_count)
        print("roofline(s):", result["roofline_s"], "->", dom)
        print(
            "useful/total flops:",
            round(result["useful_flops_ratio"], 3),
            "compile:",
            t_compile,
            "s",
        )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--float-serve", action="store_true")
    ap.add_argument("--kv-bits", type=int, default=0, choices=[0, 4, 8],
                    help="quantized KV cache for decode cells (8 = int8, "
                    "4 = packed int4 pages; 0 = float)")
    ap.add_argument("--n-micro", type=int, default=8)
    ap.add_argument("--out", default="")
    ap.add_argument("--hlo-out", default="")
    ap.add_argument("--list-cells", action="store_true")
    args = ap.parse_args()

    if args.list_cells:
        for a in list_archs():
            for s in SHAPES:
                r = cell_skip_reason(a, s)
                print(f"{a}\t{s}\t{'skip: ' + r if r else 'run'}")
        return

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    results = []
    for a in archs:
        for s in shapes:
            try:
                r = run_cell(
                    a,
                    s,
                    multi_pod=args.multi_pod,
                    serve_quant=not args.float_serve,
                    n_micro=args.n_micro,
                    hlo_out=args.hlo_out,
                    cfg_overrides=(
                        {"kv_bits": args.kv_bits} if args.kv_bits else None
                    ),
                )
            except Exception as e:  # noqa: BLE001 — record failures, keep going
                r = {"arch": a, "shape": s, "error": f"{type(e).__name__}: {e}"}
                print(f"== {a} x {s} FAILED: {r['error']}", file=sys.stderr)
            results.append(r)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    ok = sum(1 for r in results if "error" not in r)
    print(f"\n{ok}/{len(results)} cells OK")
    if ok != len(results):
        sys.exit(1)


if __name__ == "__main__":
    main()
