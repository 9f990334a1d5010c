#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>
.json``: model sizes, quantization recipe, engine settings, replicas, the
limits of the output check) and a traffic mix (``bench/traffic/<name>.json``,
read by ``bench/loadgen.py``). One run:

1. **finds the chip**: without a TPU, or with fewer chips than the cell
   asks for, it exits 1 and prints no result;
2. **sets up**: JAX's compilation cache in ``.jax_cache/`` of the checkout;
   the configuration's weights drawn from ``--seed`` and quantized by the
   program's own loader; the serving front (one engine, or replicas behind
   the program's router); on every engine one prefill per prompt-length
   class the mix can send and one decode step (every program the window
   runs); then the first requests (closed loop) or the schedule's lead
   time (open loop), so the window opens at steady load;
3. **measures** for ``--seconds``: the loop drives ``submit`` and ``step``
   and stamps each token on the host clock as ``step`` returns it. With
   ``--trace 1`` the window's first ``TRACE_SECONDS`` run under the
   profiler, each ``step`` in a ``bench_step`` span;
4. **checks** a sample, drawn from the seed, of the requests the window
   finished (the longest among them) against the float32 reference of the
   configuration's block, ``bench/reference/<block>.py`` (looked up before
   anything loads: a block without one ends the run at once), after the
   engines and their weights are freed.
   With ``--control 1`` the int4 control's tokens stand in the program's
   place, and the same limits must fail them;
5. prints, as the last line of standard output, one JSON object: the
   cell's end-to-end metrics (``--trace 0``) or per-layer metrics
   (``--trace 1``), each read by ``bench/metrics/<metric>.py``, and the
   numbers the check compared, each beside its limit, under ``compared``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import types
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
# With --trace 1 the profiler records this much of the window: enough steps
# and prefills to read each layer, a trace the run can write and read back
# well inside its time limit.
TRACE_SECONDS = 15.0


def _fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(1)


def _json(path: Path) -> Dict:
    return json.loads(path.read_text())


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(run)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: Dict, cell: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def model_config(d: Dict):
    """The configuration file's ``model`` mapping as a ``ModelConfig``: its
    ``moe``, ``ssm`` and ``hymba`` mappings as their sections, JSON lists as
    tuples."""
    from repro.configs.base import HymbaConfig, ModelConfig, MoEConfig, SSMConfig

    def tup(v):
        return tuple(tup(x) for x in v) if isinstance(v, list) else v

    kw = {k: tup(v) for k, v in d.items()}
    for key, section in (("moe", MoEConfig), ("ssm", SSMConfig),
                         ("hymba", HymbaConfig)):
        if kw.get(key) is not None:
            kw[key] = section(**{k: tup(v) for k, v in kw[key].items()})
    return ModelConfig(**kw)


def reference(block: str):
    """``bench/reference/<block>.py``, the plain reference a configuration of
    this block is checked against; without one the run ends here."""
    name = f"bench.reference.{block}"
    try:
        mod = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        mod = None
    if not hasattr(mod, "served_gaps"):
        _fail(f"no reference for block {block!r}")
    return mod


def engine_config(d: Dict):
    """The configuration file's ``engine`` settings as an ``EngineConfig``."""
    from repro.serving import EngineConfig, KernelConfig

    d = dict(d)
    if "kernels" in d:
        d["kernels"] = KernelConfig(**d["kernels"])
    return EngineConfig(**d)


# ----------------------------------------------------------------- traffic


def _suffix_class(n: int, page: int, max_len: int) -> int:
    """The prefill program a suffix of ``n`` tokens runs in: the engine
    pads a suffix to a power of two, at least a page, at most ``max_len``."""
    b = max(8, 1 << max(0, math.ceil(math.log2(max(n, 1)))))
    return min(max(b, page), max_len)


def warmup_prompts(traffic, engine_cfg: Dict, seed: int):
    """One prompt per prefill program the mix can run, in batches to send
    one after the other: per shared prefix (or once, without prefixes) its
    plain prefill, then per suffix class the longest own part of that class
    after the prefix's cached pages."""
    import numpy as np

    page, max_len = engine_cfg["page_size"], engine_cfg["max_len"]
    own = sorted({int(x) for x in traffic.prompt_lens.ravel()})
    batches = []
    for c in range(max(1, min(len(traffic.prefix_lens), traffic.clients))):
        head = traffic.prefix(c)
        best: Dict[int, int] = {}
        for s in own:
            cls = _suffix_class(len(head) % page + s, page, max_len)
            best[cls] = max(best.get(cls, 0), s)
        rng = np.random.default_rng((int(seed) % (1 << 63), 9, c))
        fresh = lambda n: rng.integers(0, traffic.vocab, n, dtype=np.int32)
        group = [np.concatenate([head, fresh(best[cls])]) for cls in sorted(best)]
        if len(head):  # the prefix's own prefill first: later ones hit it
            batches.append([np.concatenate([head, fresh(max(own))])])
        batches.append(group)
    return batches


def build_front(cfg, params, config: Dict, devices):
    """The serving front the window drives, and its engines: one engine, or
    with ``"replicas": n`` in the configuration ``n`` engines, replica ``i``
    on ``devices[i % len(devices)]``, behind the program's router
    (``"router"``: its settings)."""
    import jax

    from repro.serving import ReplicaSet, Router, RouterConfig, ServingEngine

    ecfg = engine_config(config["engine"])
    engines = []
    for i in range(int(config.get("replicas", 1))):
        d = devices[i % len(devices)]
        with jax.default_device(d):
            engines.append(ServingEngine(
                cfg, params if i == 0 else jax.device_put(params, d), ecfg))
    if len(engines) == 1:
        return engines[0], engines
    rcfg = RouterConfig(**config.get("router", {}))
    return Router(ReplicaSet(engines, rcfg), rcfg), engines


# --------------------------------------------------------------------- run


def run_cell(cell: Dict, config: Dict, mix: Dict, spec: Dict, seed: int,
             seconds: float, trace: bool, devices, *, control: bool = False,
             t_process: Optional[float] = None, log=print) -> Dict:
    """Set up, measure and check one cell on ``devices``; the result object."""
    import jax

    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

    t_process = T_PROCESS if t_process is None else t_process
    os.environ.pop(ENV_VAR, None)  # the cache lives in this checkout
    enable_compile_cache()
    # No eviction: a size limit from the environment (192 MiB on the chip's
    # host) is smaller than one cell's programs, and evicting failed there.
    jax.config.update("jax_compilation_cache_max_size", -1)
    compiles = {"n": 0, "on": False}

    def count(event, duration, **kw):
        if compiles["on"] and ("compile" in event or "jaxpr_trace" in event):
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        return _run(cell, config, mix, spec, seed, seconds, trace, devices,
                    control, t_process, log, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(count)


def _run(cell, config, mix, spec, seed, seconds, trace, devices, control,
         t_process, log, compiles) -> Dict:
    served_gaps = reference(config["model"]["block"]).served_gaps

    import jax
    import numpy as np

    from repro.core.recipe import QuantRecipe
    from repro.kernels.budget import reroutes
    from repro.launch.weights import load_quantized
    from repro.serving import EngineOverloaded, Request

    from bench.loadgen import Traffic

    dev = devices[0]
    rr0 = reroutes()  # the process may have rerouted kernels before this run

    cfg = model_config(config["model"])
    recipe = QuantRecipe(**config["recipe"])
    t0 = time.perf_counter()
    params = jax.block_until_ready(load_quantized(cfg, seed, recipe, device=dev))
    load_s = time.perf_counter() - t0
    log(f"[load] {cfg.name}: {load_s:.1f}s")
    front, engines = build_front(cfg, params, config, devices)
    del params
    traffic = Traffic(mix, seed, cfg.vocab)
    uid = [0]

    def request(prompt, max_new):
        r = Request(uid=uid[0], prompt=[int(t) for t in prompt],
                    max_new_tokens=int(max_new))
        uid[0] += 1
        return r

    # Warm-up, on every engine: each prefill program the mix can run,
    # decode, retirement.
    t0 = time.perf_counter()
    for e in engines:
        for batch in warmup_prompts(traffic, config["engine"], seed):
            for p in batch:
                e.submit(request(p, 2))
            while e.step() or e.queue:
                pass
    warm_s = time.perf_counter() - t0
    log(f"[warmup] {warm_s:.1f}s prefill_traces="
        f"{[e.prefill_traces for e in engines]} decode_traces="
        f"{[e.decode_traces for e in engines]}")

    records: List[Dict] = []  # every request sent, with its host stamps
    reqs: Dict[int, object] = {}
    live: List = []  # (request, record) in flight
    steps: List[Dict] = []
    where: Dict[int, int] = {}  # request uid -> the engine that holds it
    sched = {"t0": 0.0, "next": 0}  # open loop: schedule start, next request

    def send(c: int, k: int, due: Optional[float] = None):
        s = traffic.request(c, k)
        r = request(s.prompt, s.max_new)
        now = time.perf_counter()
        rec = {"uid": r.uid, "client": c, "k": k, "n": len(s.prompt),
               "due": now if due is None else due, "submit": now,
               "stamps": [], "done": None, "reason": None}
        records.append(rec)
        reqs[r.uid] = r
        try:
            front.submit(r)
        except EngineOverloaded:
            rec["done"], rec["reason"] = now, "rejected"
            return
        live.append((r, rec))

    def pump():
        """One step of the front: the open loop's due arrivals first, then
        the step, each new token's stamp and decode position, and the closed
        loop's next requests."""
        t_a = time.perf_counter()
        while traffic.open:
            i = sched["next"]
            due = sched["t0"] + traffic.arrival(i)
            if due > t_a:
                break
            send(i % traffic.clients, i // traffic.clients, due)
            sched["next"] = i + 1
        before = [len(r.output) for r, _ in live]
        pre0 = [e.prefill_tokens for e in engines]
        busy = front.step()
        now = time.perf_counter()
        for i, e in enumerate(engines):
            for s in e.slots:
                if s.req is not None:
                    where[s.req.uid] = i
        positions: List[List[int]] = [[] for _ in engines]
        still, ended = [], []
        for (r, rec), b in zip(live, before):
            got = len(r.output) - b
            rec["stamps"].extend([now] * got)
            if got - (1 if b == 0 and got else 0):  # a decode step's token
                positions[where.get(r.uid, 0)].append(rec["n"] + len(r.output) - 2)
            if r.finish_reason is None:
                still.append((r, rec))
            else:
                rec["done"], rec["reason"] = now, r.finish_reason
                ended.append(rec)
        live[:] = still
        if not traffic.open:
            for rec in sorted(ended, key=lambda x: x["client"]):
                send(rec["client"], rec["k"] + 1)
        for i, e in enumerate(engines):
            steps.append({"t0": t_a, "t1": now, "engine": i,
                          "positions": positions[i],
                          "prefill_tokens": e.prefill_tokens - pre0[i]})
        if traffic.open and not busy:  # idle until the next arrival
            nxt = sched["t0"] + traffic.arrival(sched["next"])
            time.sleep(min(max(0.0, nxt - time.perf_counter()), 0.01))
        return now

    # Fill: a closed loop's first requests, until every client has its
    # first token; an open loop's schedule for its lead time.
    t0 = time.perf_counter()
    if traffic.open:
        sched["t0"] = t0
        while time.perf_counter() < t0 + float(mix.get("lead_s", 0.0)):
            pump()
    else:
        for c in range(traffic.clients):
            send(c, 0)
        while any(not rec["stamps"] for r, rec in live):
            pump()
    fill_s = time.perf_counter() - t0
    log(f"[fill] {len(records)} requests in {fill_s:.1f}s")

    def counters():
        """Every number of the engines' ``stats()``, summed over engines,
        and their decode steps."""
        out: Dict[str, float] = {}
        for e in engines:
            for k, v in dict(e.stats(), steps=e.steps).items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[k] = out.get(k, 0) + v
        return out

    c_start = counters()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=_profile_options())
    n_steps0 = len(steps)
    compiles["on"] = True
    t_start = time.perf_counter()
    setup_s = t_start - t_process
    deadline = t_start + seconds
    now = t_start
    if trace:  # the profiler sees the window's first TRACE_SECONDS
        with _span("bench_window", True):
            while now < min(deadline, t_start + TRACE_SECONDS):
                with _span("bench_step", True):
                    now = pump()
        t_traced, n_traced, c_traced = now, len(steps), counters()
        jax.profiler.stop_trace()
    while now < deadline:
        now = pump()
    t_end = now
    compiles["on"] = False
    c_end = counters()
    log(f"[window] {t_end - t_start:.2f}s, {len(steps) - n_steps0} engine "
        f"steps, compiles in window: {compiles['n']}; "
        f"{json.dumps(_window_stats(records, t_start, t_end))}")

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    stats = [e.stats() for e in engines]
    st = {k: sum(s[k] for s in stats) for k in ("errors", "kernel_fallbacks")}
    rr = {k: n - rr0.get(k, 0) for k, n in reroutes().items()
          if n > rr0.get(k, 0)}
    log(f"[engine] attn_kernel={stats[0]['attn_kernel']} matmul_kernel="
        f"{stats[0]['matmul_kernel']} errors={st['errors']} kernel_fallbacks="
        f"{st['kernel_fallbacks']} reroutes={rr or 'none'} prefix_hit_rate="
        f"{[round(s['prefix_hit_rate'], 3) for s in stats]} "
        f"peak_bytes_in_use={peak}")

    in_window = [r for r in records
                 if r["submit"] < t_end and (r["done"] is None or r["done"] > t_start)]
    finished = [r for r in in_window if r["done"] is not None]
    failed = sum(1 for r in finished if r["reason"] not in ("eos", "length"))
    ok = [r for r in finished if r["reason"] in ("eos", "length")]
    sample = _sample(ok, config["check"]["sample"], seed)
    seqs = [(np.asarray(reqs[r["uid"]].prompt, np.int32),
             list(reqs[r["uid"]].output)) for r in sample]
    del front, engines, reqs, live
    gc.collect()
    log(f"[check] {len(seqs)} of {len(ok)} finished requests, "
        f"{sum(len(s) for _, s in seqs)} served tokens; bytes_in_use after "
        f"freeing the engine: {(dev.memory_stats() or {}).get('bytes_in_use')}")

    t0 = time.perf_counter()
    gaps = served_gaps(config["model"], seed, seqs, control=control,
                       device=dev) if seqs else {"served": [], "control": []}
    check_s = time.perf_counter() - t0
    if control:  # the control's tokens stand in the program's place
        program = _compare(gaps["served"], {}, st, rr)
        log("[check] the program's own: " + ", ".join(
            f"{k} {program[k]['value']!r}" for k in ("served_gap_max", "served_gap_mean")))
    limits = config["check"]["limits"]
    compared = _compare(gaps["control" if control else "served"], limits, st, rr)
    correct = bool(seqs) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    log(f"[check] {check_s:.1f}s")

    # What a metric's reader gets, over the measured window or with tracing
    # its traced part: the configuration and the chip's peaks; set-up times;
    # the window's host-clock bounds; every request sent, with its host
    # stamps (``due``, ``submit``, one per token, ``done``) and ``reason``;
    # one record per engine and step (live decode positions, prefill
    # tokens); the change of each engine counter (``counters``) and each
    # engine's ``stats()`` at the window's end; the reduced trace.
    t_to, n_to, c_to = (t_traced, n_traced, c_traced) if trace else (
        t_end, len(steps), c_end)
    run = types.SimpleNamespace(
        cell=cell["name"], config=config, peaks=_peaks(dev),
        setup={"setup_s": setup_s, "load_s": load_s, "warmup_s": warm_s,
               "fill_s": fill_s},
        window={"t0": t_start, "t1": t_to}, requests=records,
        steps=steps[n_steps0:n_to],
        counters={k: c_to[k] - c_start[k] for k in c_start}, stats=stats,
        trace=None)
    result: Dict = {"correct": correct, "attempted": len(in_window),
                    "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    kind = "per_layer" if trace else "end_to_end"
    if trace:
        from bench import reduce as R

        t0 = time.perf_counter()
        run.trace = R.reduce(R.load(str(TRACE_DIR)))
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        log(f"[trace] reduced in {time.perf_counter() - t0:.1f}s: "
            f"{json.dumps({k: run.trace[k] for k in ('program_s', 'program_runs', 'kernel_s')})}")
    metrics = {}
    for m in cell_metrics(spec, cell["name"], kind):
        v = metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["compared"] = compared
    return result


def _window_stats(records: List[Dict], t0: float, t1: float) -> Dict:
    """What the window's requests read beside the end-to-end metrics: first
    tokens timed from when each request was due, gaps between tokens, and
    how late the generator submitted."""
    import numpy as np

    ttft = [r["stamps"][0] - r["due"] for r in records
            if r["stamps"] and t0 < r["stamps"][0] <= t1]
    itl = [b - a for r in records
           for a, b in zip(r["stamps"], r["stamps"][1:]) if a > t0 and b <= t1]
    late = [r["submit"] - r["due"] for r in records if t0 < r["submit"] <= t1]
    q = lambda xs, p, scale: round(float(np.percentile(xs, p)) * scale, 4) if xs else None
    return {"first_tokens": len(ttft), "ttft_p50_s": q(ttft, 50, 1),
            "ttft_p95_s": q(ttft, 95, 1), "itl_p95_ms": q(itl, 95, 1e3),
            "itl_p99_ms": q(itl, 99, 1e3),
            "submit_late_max_ms": round(max(late) * 1e3, 3) if late else None}


def _compare(gaps, limits, st, rr) -> Dict:
    import numpy as np

    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    out = {
        "served_gap_max": float(allg.max()) if allg.size else float("inf"),
        "served_gap_mean": float(allg.mean()) if allg.size else float("inf"),
    }
    res = {k: {"value": v, "limit": limits.get(k)} for k, v in out.items()}
    for k, v in (("errors", st["errors"]),
                 ("kernel_fallbacks", st["kernel_fallbacks"]),
                 ("reroutes", sum(rr.values()))):
        res[k] = {"value": int(v), "limit": 0}
    return res


def _sample(finished: List[Dict], n: int, seed: int) -> List[Dict]:
    """``n`` finished requests: the longest, and others drawn from the seed."""
    import numpy as np

    if not finished:
        return []
    order = sorted(finished, key=lambda r: r["uid"])
    longest = max(order, key=lambda r: (r["n"] + len(r["stamps"]), -r["uid"]))
    rest = [r for r in order if r is not longest]
    rng = np.random.default_rng((int(seed) % (1 << 63), 7))
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def _peaks(dev) -> Dict:
    table = _json(BENCH / "peaks.json")["devices"]
    if dev.device_kind not in table:
        if dev.platform == "tpu":
            _fail(f"no peaks for device kind {dev.device_kind!r} in bench/peaks.json")
        return {"bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")}
    return table[dev.device_kind]


def _profile_options():
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0  # no Python call events: they slow the host
    o.host_tracer_level = 1  # the benchmark's own spans
    return o


def _span(name: str, on: bool):
    """A host span on the profiler's clock while tracing."""
    import jax

    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def main(argv=None) -> None:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the int4 control in the program's place (not a benchmark run)")
    args = ap.parse_args(argv)

    spec_path = ROOT / "BENCHMARK.json"
    if not spec_path.is_file():
        _fail("no BENCHMARK.json beside bench/")
    spec = _json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        _fail(f"no workload {args.workload!r}; have {sorted(cells)}")
    cell = cells[args.workload]
    if not (ROOT / "src" / "repro").is_dir():
        _fail("no program (src/repro) in this checkout")
    config = _json(BENCH / "configs" / f"{cell['config']}.json")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    reference(config["model"]["block"])
    from bench.loadgen import load_mix

    mix = load_mix(BENCH / "traffic" / f"{cell['traffic']}.json")

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU: JAX found {devs[0].platform} devices only")
    if len(devs) < cell["chips"]:
        _fail(f"{args.workload} needs {cell['chips']} chips, JAX found {len(devs)}")
    d = devs[0]
    print(f"[device] platform={d.platform} device_kind={d.device_kind} "
          f"count={len(devs)}", file=sys.stderr)
    from repro.obs.log import setup_logging

    setup_logging("WARNING")
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    result = run_cell(cell, config, mix, spec, args.seed, args.seconds,
                      bool(args.trace), devs[:cell["chips"]],
                      control=bool(args.control), log=log)
    for name, c in result["compared"].items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
