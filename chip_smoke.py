"""Serve glm4-9b at full width on one TPU chip through the normal entry points,
and check what comes out.

    python chip_smoke.py                 # one chip: the serving path
    python chip_smoke.py --four-chips    # four chips: replicas behind the router
    python chip_smoke.py --smoke         # CPU rehearsal at the smoke size

The one-chip run:

1. **load** — glm4-9b at its published widths and all 40 layers, random
   weights from ``--seed``, OCS-quantized one slice at a time on the host
   (``repro.launch.weights.load_quantized``); only the int8 tree reaches the
   chip.
2. **serve** — two ``ServingEngine``s with kernels resolved by AUTO (the
   Pallas kernels on a TPU): the default ``EngineConfig`` (float32 pages)
   and ``kv_bits=8`` (int8 pages). Each answers the same seeded greedy
   requests (prompts 16-480 tokens, 16 new tokens, ``max_len`` 512).
3. **check** — each engine's requests run again at the served batch of 8,
   on an interleaved block table, fed the engine's own tokens, through the
   XLA formulations (``kernels=xla/xla``) of the same quantized tree: every
   served token must sit within a stated margin of the XLA argmax. The
   prefill and first-decode logits of that batch through the Pallas kernels
   must agree with the XLA ones. One paged-attention call on an int4 pool
   at the model's widths, kernel against XLA, covers the int4 page layout,
   which no engine here serves. The run fails on a gap past its tolerance,
   on any kernel rerouted to XLA, on ``kernel_fallbacks`` or ``errors`` in
   ``stats()``, or on a finish other than ``eos``/``length``.

``--four-chips`` runs only the replica path: four replicas behind the
router, one per chip, against one replica on the same greedy requests.
Outputs must be token-identical and every chip must hold its own copy of
the weights. It serves ``FOUR_CHIP_LAYERS`` layers at full width.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU (and without ``--smoke``) the script exits non-zero and prints
no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# Logit tolerances, as a fraction of the reference's max |logit|. Both paths
# carry bfloat16 activations (8 mantissa bits: one rounding is up to 2^-9
# relative), and the XLA formulation also rounds every dequantized weight to
# bfloat16 where the Pallas matmul dequantizes in float32; 40 layers
# compound these roundings. Int8 pages add the XLA attention's int8
# requantization of q and of the softmax weights (up to 1/254 of each row's
# max), which the Pallas kernel does not do.
TOL = {"f32 pages": 0.05, "int8 pages": 0.10}
# The engines take each token as the argmax of their Pallas logits. Where
# the two paths' logits differ by at most TOL of the scale, that token's
# reference logit is at most 2 TOL below the reference's max. Every served
# token is held to this, at every step, against the XLA reference fed the
# engine's own tokens.
MARGIN = {k: 2 * v for k, v in TOL.items()}
# Int4 pages, one paged-attention call: kernel against the XLA formulation
# on the same int4 pool, so both carry the same quantization error and only
# their arithmetic differs (float32 dots that the chip may run in bfloat16
# passes: about 2^-9 relative per product). A layout fault (a wrong page,
# lane, nibble or scale row) moves an output by the order of its own size.
TOL_INT4_ATTN = 0.05
N_REQUESTS, MAX_NEW = 8, 16
# --four-chips checks placement, not the model: the depth it serves.
FOUR_CHIP_LAYERS = 4


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _requests(n, vocab, rng, max_new, lo=16, hi=480):
    from repro.serving import Request

    lens = rng.integers(lo, hi + 1, size=n)
    return [
        Request(uid=i, prompt=rng.integers(0, vocab, int(m)).tolist(),
                max_new_tokens=max_new)
        for i, m in enumerate(lens)
    ]


def _tree_bytes(tree) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree))


def _serve(cfg, params, ecfg, reqs, label):
    """One engine answers ``reqs``; returns (outputs, stats, compile s, serve s)."""
    from repro.serving import ServingEngine

    eng = ServingEngine(cfg, params, ecfg)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    s = eng.stats()
    compile_s = s["prefill_compile_s"] + s["decode_compile_s"]
    reasons = sorted({r.finish_reason for r in done})
    print(f"[{label}] attn_kernel={s['attn_kernel']} "
          f"matmul_kernel={s['matmul_kernel']} completed={s['completed']} "
          f"errors={s['errors']} kernel_fallbacks={s['kernel_fallbacks']} "
          f"finish={reasons} decoded={s['decoded_tokens']} "
          f"traces={s['prefill_traces']}+{s['decode_traces']} "
          f"compile_s={compile_s:.1f} serve_s={wall - compile_s:.1f}")
    if len(done) != len(reqs):
        _fail(f"{label}: {len(done)} of {len(reqs)} requests finished")
    if s["errors"] or s["kernel_fallbacks"]:
        _fail(f"{label}: errors={s['errors']} "
              f"kernel_fallbacks={s['kernel_fallbacks']}")
    if set(reasons) - {"eos", "length"}:
        _fail(f"{label}: finish reasons {reasons}")
    return {r.uid: list(r.output) for r in done}, s, compile_s, wall - compile_s


def _table(b, per):
    """Block table of ``b`` lanes of ``per`` pages, interleaved: lane i's page
    j is ``1 + j*b + i``. No lane's pages are contiguous, so a lane that
    reads another lane's row reads the wrong K/V."""
    import numpy as np

    return (1 + np.arange(per)[None, :] * b
            + np.arange(b)[:, None]).astype(np.int32)


def _forced_logits(cfg, params, reqs, outs, kv_bits, kernel, steps):
    """Logits ``[B, steps, V]`` of the served requests, batch ``B`` =
    ``len(reqs)``, through one kernel family (``pallas`` or ``xla``) for
    matmuls and paged attention, fed the engine's own tokens: row ``t`` of
    lane ``b`` is the prediction for ``outs[uid][t]`` — ``t = 0`` from the
    prefill, ``t >= 1`` from the decode step fed ``outs[uid][t - 1]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import layers
    from repro.models import transformer as T
    from repro.serving import kv_cache as kvc

    c = dataclasses.replace(cfg, kv_bits=kv_bits)
    b, ps, max_len = len(reqs), 16, 512
    per = max_len // ps
    table = _table(b, per)
    caches = kvc.init_paged_cache(c, b, b * per + 1, ps, per)

    @jax.jit
    def prefill(params, tokens, length, page_ids, pools):
        with layers.serving_mode("dequant", kernel=kernel):
            return T.prefill_into_pages(
                params, tokens, c, pools, page_ids, length=length,
                prefix_ids=jnp.zeros((0,), jnp.int32))

    @jax.jit
    def decode(params, tok, caches):
        with layers.serving_mode("dequant", kernel=kernel):
            return T.decode_step(params, tok, caches, c, attn_kernel=kernel)

    pools = [layer["attn"] for layer in caches["layers"]]
    rows = [[] for _ in range(steps)]
    for i, r in enumerate(reqs):
        n = len(r.prompt)
        tokens = np.zeros((1, max_len), np.int32)
        tokens[0, :n] = r.prompt
        lg, pools = prefill(params, tokens, np.array([n], np.int32),
                            table[i], pools)
        rows[0].append(np.asarray(lg[0], np.float32))
    caches = {"layers": [{"attn": p} for p in pools],
              "table": jnp.asarray(table),
              "pos": jnp.asarray([len(r.prompt) for r in reqs], jnp.int32)}
    for t in range(1, steps):
        tok = np.array([[(outs[r.uid][t - 1:t] or [0])[0]] for r in reqs],
                       np.int32)
        lg, caches = decode(params, tok, caches)
        rows[t] = list(np.asarray(lg, np.float32))
    return np.stack([np.stack(r) for r in rows], axis=1)


def _check_engine(cfg, params, reqs, outs, label, kv_bits):
    """Hold one engine's served tokens, and the Pallas logits at its batch,
    to the XLA formulation of the same quantized tree."""
    import numpy as np

    t0 = time.perf_counter()
    got = _forced_logits(cfg, params, reqs, outs, kv_bits, "pallas", 2)
    ref = _forced_logits(cfg, params, reqs, outs, kv_bits, "xla", MAX_NEW)
    secs = time.perf_counter() - t0
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(ref))):
        _fail(f"{label}: nonfinite logits")
    gaps = {}
    for name, t in (("prefill", 0), ("decode", 1)):
        a, b = got[:, t], ref[:, t]
        diff = float(np.max(np.abs(a - b)))
        scale = float(np.max(np.abs(b)))
        gaps[name] = rel = diff / scale
        top1 = float(np.mean(np.argmax(a, -1) == np.argmax(b, -1)))
        print(f"[check] {label} {name}, batch {len(reqs)}: "
              f"max|pallas-xla|={diff:.6g} max|xla|={scale:.6g} rel={rel:.6g} "
              f"(tol {TOL[label]}) top1_agreement={top1}")
        if rel > TOL[label]:
            _fail(f"{label} {name}: logit gap {rel:.4g} > {TOL[label]}")
    margins, agree = [], 0
    for i, r in enumerate(reqs):
        for t, tok in enumerate(outs[r.uid]):
            row = ref[i, t]
            margins.append(float((row.max() - row[tok]) / np.abs(row).max()))
            agree += int(np.argmax(row) == tok)
    worst = max(margins)
    print(f"[check] {label} served tokens: {agree} of {len(margins)} are the "
          f"XLA argmax; worst margin below the XLA max {worst:.6g} of max|xla| "
          f"(tol {MARGIN[label]}) ({secs:.1f}s with compiles)")
    if worst > MARGIN[label]:
        _fail(f"{label}: a served token sits {worst:.4g} below the XLA max")
    gaps["served margin"] = worst
    return gaps


def _check_int4_attention(cfg, seed):
    """One paged-attention call on an int4 pool at the model's widths, batch
    8 at ragged positions: the kernel against the XLA formulation."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.serving import kv_cache as kvc

    c = dataclasses.replace(cfg, kv_bits=4, n_layers=1)
    b, ps, per = N_REQUESTS, 16, 32
    pool = kvc.init_paged_cache(c, b, b * per + 1, ps, per)["layers"][0]["attn"]
    rng = np.random.default_rng(seed)
    pool = {k: jnp.asarray(rng.integers(0, 256, v.shape), v.dtype)
            if v.dtype == jnp.uint8 else
            jnp.asarray(rng.uniform(0.01, 0.1, v.shape), v.dtype)
            for k, v in pool.items()}
    hd, kv = cfg.head_dim, cfg.n_kv_heads
    q, k, v = (jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.bfloat16)
               for h in (cfg.n_heads, kv, kv))
    pos = jnp.asarray(rng.integers(1, per * ps - 1, b), jnp.int32)
    table = jnp.asarray(_table(b, per))
    got, _ = ops.paged_attention(pool, table, pos, q, k, v)
    ref, _ = ops.paged_attention(pool, table, pos, q, k, v, force="ref")
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(ref))):
        _fail("int4 attention: nonfinite outputs")
    rel = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    print(f"[check] int4 pages, one paged-attention call, batch {b}: "
          f"max|pallas-xla|/max|xla|={rel:.6g} (tol {TOL_INT4_ATTN})")
    if rel > TOL_INT4_ATTN:
        _fail(f"int4 attention: gap {rel:.4g} > {TOL_INT4_ATTN}")
    return rel


def _device_line(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def one_chip(cfg, args, dev) -> None:
    import jax
    import numpy as np

    from repro.kernels.budget import reroutes
    from repro.launch.weights import SERVE_RECIPE, load_quantized
    from repro.serving import EngineConfig

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        load_quantized(cfg, args.seed, SERVE_RECIPE, device=dev))
    load_s = time.perf_counter() - t0
    stats = dev.memory_stats() or {}
    print(f"[load] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}: {_tree_bytes(params)} bytes "
          f"of params in {load_s:.1f}s; peak_bytes_in_use after load: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")

    rng = np.random.default_rng(args.seed)
    reqs = _requests(N_REQUESTS, cfg.vocab, rng, MAX_NEW)
    print(f"[serve] {len(reqs)} requests, prompt lengths "
          f"{sorted(len(r.prompt) for r in reqs)}, max_new {MAX_NEW}")
    on_tpu = dev.platform == "tpu"
    compile_s = serve_s = check_s = 0.0
    gaps = {}
    for label, ecfg in (("f32 pages", EngineConfig()),
                        ("int8 pages", EngineConfig(kv_bits=8))):
        batch = [dataclasses.replace(r, output=[], t_tokens=[]) for r in reqs]
        outs, s, c_s, s_s = _serve(cfg, params, ecfg, batch, label)
        compile_s += c_s
        serve_s += s_s
        if on_tpu and (s["attn_kernel"], s["matmul_kernel"]) != ("pallas", "pallas"):
            _fail(f"{label}: kernels resolved to attn={s['attn_kernel']} "
                  f"matmul={s['matmul_kernel']}, not pallas")
        t0 = time.perf_counter()
        for name, g in _check_engine(
                cfg, params, reqs, outs, label, ecfg.kv_bits).items():
            gaps[f"{label} {name}"] = g
        check_s += time.perf_counter() - t0

    t0 = time.perf_counter()
    gaps["int4 pages attention"] = _check_int4_attention(cfg, args.seed)
    check_s += time.perf_counter() - t0
    rr = reroutes()
    print(f"[reroutes] {rr or 'none'}")
    if rr:
        _fail(f"kernels rerouted to XLA: {rr}")
    print(f"[phases] load_s={load_s:.1f} compile_s={compile_s:.1f} "
          f"serve_s={serve_s:.1f} check_s={check_s:.1f}")
    print(f"[logits] max relative gaps: {json.dumps(gaps)}")


def four_chips(cfg, args, devs) -> None:
    import jax
    import numpy as np

    from repro.launch.weights import SERVE_RECIPE, load_quantized
    from repro.serving import EngineConfig, ReplicaSet, Router

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        load_quantized(cfg, args.seed, SERVE_RECIPE, device=devs[0]))
    w_bytes = _tree_bytes(params)
    print(f"[load] {cfg.name} cut to {cfg.n_layers} layers at full width: "
          f"{w_bytes} bytes of params in {time.perf_counter() - t0:.1f}s")
    rng = np.random.default_rng(args.seed)
    # Short prompts: placement is under test here, not long prefill, and
    # every bucket compiles once per replica.
    reqs = _requests(N_REQUESTS, cfg.vocab, rng, MAX_NEW, hi=64)

    outs = {}
    for n in (4, 1):
        rs = ReplicaSet.build(cfg, params, EngineConfig(), n)
        for i, rep in enumerate(rs):
            held = {d for x in jax.tree.leaves(rep.engine.params)
                    for d in x.devices()}
            if held != {devs[i]}:
                _fail(f"replica {i} params on {held}, want {devs[i]}")
        router = Router(rs)
        batch = [dataclasses.replace(r, output=[], t_tokens=[]) for r in reqs]
        for r in batch:
            router.submit(r)
        t0 = time.perf_counter()
        router.run(max_steps=100_000)
        st = router.stats()
        per = [rep.engine.stats() for rep in rs]
        print(f"[replicas={n}] {time.perf_counter() - t0:.1f}s placed="
              f"{st['router_placed']} per-replica completed="
              f"{[p['completed'] for p in per]} attn_kernel="
              f"{per[0]['attn_kernel']} errors={sum(p['errors'] for p in per)}")
        if any(p["errors"] or p["kernel_fallbacks"] for p in per):
            _fail(f"replicas={n}: errors or kernel fallbacks")
        if set(r.finish_reason for r in batch) - {"eos", "length"}:
            _fail(f"replicas={n}: finish reasons "
                  f"{sorted({r.finish_reason for r in batch})}")
        outs[n] = {r.uid: list(r.output) for r in batch}
        if n == 4:
            used = sum(1 for p in per if p["completed"])
            for d in devs[:4]:
                m = d.memory_stats() or {}
                b = m.get("bytes_in_use")
                print(f"[memory] {d}: bytes_in_use={b} "
                      f"peak_bytes_in_use={m.get('peak_bytes_in_use')}")
                if b is None and d.platform == "tpu":
                    _fail(f"{d} reports no bytes_in_use")
                if b is not None and b < w_bytes:
                    _fail(f"{d} holds {b} bytes < one copy of the weights "
                          f"({w_bytes})")
            if used < 2:
                _fail(f"the router placed work on {used} replica(s)")
        del router, rs
    if outs[4] != outs[1]:
        bad = [u for u in outs[1] if outs[4].get(u) != outs[1][u]]
        _fail(f"4 replicas disagree with 1 replica on requests {bad}")
    print(f"[four-chips] outputs token-identical over {len(outs[1])} requests")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal at the smoke size (never on the chip)")
    ap.add_argument("--four-chips", action="store_true",
                    help="only the four-replica router path, one chip each")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(_SRC, "repro")):
        _fail(f"no repro package under {_SRC}: run from a checkout")
    sys.path.insert(0, _SRC)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.smoke:
        _fail(f"no TPU: JAX found {devs[0].platform} devices only")
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        _fail(f"--four-chips needs 4 devices, JAX found {len(devs)}")

    from repro.configs import get_config, smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs.log import setup_logging

    setup_logging("WARNING")
    enable_compile_cache()
    cfg = smoke_config("glm4-9b") if args.smoke else get_config("glm4-9b")
    if args.four_chips:
        cfg = dataclasses.replace(
            cfg, n_layers=min(FOUR_CHIP_LAYERS, cfg.n_layers))
    print(f"[device] platform={devs[0].platform} "
          f"device_kind={devs[0].device_kind} count={len(devs)}")
    if args.four_chips:
        four_chips(cfg, args, devs[:4])
    else:
        one_chip(cfg, args, devs[0])
    print(json.dumps({"ok": True, "device": _device_line(devs[:want])}))


if __name__ == "__main__":
    main()
