"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, so that the second can be checked on a small trace kept in the
repository:

1. :func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
   plain lists: device operations with the program (XLA module) they ran
   in, program runs, and the host spans the benchmark and the engine opened
   (``jax.profiler.TraceAnnotation``: ``bench_*`` and ``engine.*``), all on
   the profiler's clock in nanoseconds.
2. :func:`reduce` turns those lists into device seconds per program kind,
   per kernel inside the decode programs, per ``program:kernel-or-op``
   label (``op_s``: every label, so that a metric can read a kernel that
   :data:`KERNELS` does not name), the device's busy time over the measured
   window, and the breakdown of the longest operations and idle gaps, each
   gap named by the innermost host span over it (``bench/spans.py``).

Programs are told apart by their names: the engine's named scopes
(``serving_decode_step``, ``serving_prefill``) where the trace keeps them,
else the XLA module names of the jitted functions, which is all a TPU trace
of JAX 0.9 shows (``jit__decode_impl(...)``; the paged prefill is the
engine's nested ``impl``, ``jit_impl(...)``). Kernels are told apart by the
names of their custom calls (``%ocs_quant_matmul.N``, the paged-attention
call).
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import spans as S

# What marks each program kind and kernel, searched in lower case.
PROGRAMS = {"decode": ("serving_decode_step", "jit__decode_impl("),
            "prefill": ("serving_prefill", "jit_impl(")}
KERNELS = {"ocs_matmul": ("ocs_matmul", "ocs_quant_matmul"),
           "paged_attn": ("paged_attn", "paged_attention")}
WINDOW_SPAN = "bench_window"  # the measured window; each step is a "bench_step"
HOST_SPANS = ("bench_", "engine.")  # the host spans kept: the benchmark's, the engine's


def _text(stats) -> str:
    return " ".join(str(v) for v in stats.values() if isinstance(v, str))


def load(path: str) -> Dict:
    """The trace under ``path`` (a directory holding one ``*.xplane.pb``, or
    the file) as ``{"ops": [[name, start_ns, dur_ns, module_index, text,
    device]], "modules": [[name, start_ns, dur_ns]], "host": [[name,
    start_ns, dur_ns]], "devices": n}``. ``text`` holds the op's string
    stats, where the named scopes and kernel names appear; ``device``
    counts the device planes from 0."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                               recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    ops, modules, host, devices = [], [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            dev = devices
            devices += 1
            lines = {line.name: line for line in plane.lines}
            mods = sorted(
                (ev.start_ns, ev.duration_ns, ev.name)
                for ev in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else []))
            base = len(modules)
            modules.extend([name, s, d] for s, d, name in mods)
            starts = [s for s, _, _ in mods]
            if "XLA Ops" not in lines:
                continue
            for ev in lines["XLA Ops"].events:
                stats = dict(ev.stats)
                j = bisect.bisect_right(starts, ev.start_ns) - 1
                m = base + j if j >= 0 and ev.start_ns < starts[j] + mods[j][1] else -1
                ops.append([ev.name[:160], ev.start_ns, ev.duration_ns, m,
                            _text(stats)[:400], dev])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPANS):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"ops": ops, "modules": modules, "host": sorted(host, key=lambda h: h[1]),
            "devices": max(devices, 1)}


def _kind(text: str, table: Dict[str, Tuple[str, ...]]) -> Optional[str]:
    t = text.lower()
    for kind, marks in table.items():
        if any(m in t for m in marks):
            return kind
    return None


def _union(intervals: List[Tuple[int, int]]) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def window(trace: Dict) -> Tuple[int, int]:
    spans = [h for h in trace["host"] if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    _, s, d = spans[-1]
    return s, s + d


def reduce(trace: Dict, top: int = 10) -> Dict:
    """Device seconds by program kind, by kernel in the decode programs and
    by ``program:kernel-or-op`` label over the measured window, busy seconds
    (each device's, averaged over the devices), and the breakdown. An op
    without a device index ran on device 0."""
    w0, w1 = window(trace)
    clip = lambda s, d: (max(s, w0), min(s + d, w1))
    mod_kind = []
    for name, _, _ in trace["modules"]:
        mod_kind.append(_kind(name, PROGRAMS))
    # A module's kind may only show in its ops' scopes.
    for name, s, d, m, text, *_ in trace["ops"]:
        if m >= 0 and mod_kind[m] is None:
            mod_kind[m] = _kind(text, PROGRAMS)
    program_s: Dict[str, float] = defaultdict(float)
    program_runs: Dict[str, int] = defaultdict(int)
    for (name, s, d), kind in zip(trace["modules"], mod_kind):
        a, b = clip(s, d)
        if b > a and kind:
            program_s[kind] += (b - a) / 1e9
            program_runs[kind] += 1
    kernel_s: Dict[str, float] = defaultdict(float)
    busy, by_label = [], defaultdict(float)
    per_device: Dict[int, List[Tuple[int, int]]] = defaultdict(list)
    for name, s, d, m, text, *dev in trace["ops"]:
        a, b = clip(s, d)
        if b <= a:
            continue
        busy.append((a, b))
        per_device[dev[0] if dev else 0].append((a, b))
        kern = _kind(name + " " + text, KERNELS)
        prog = mod_kind[m] if m >= 0 else None
        if kern and prog == "decode":
            kernel_s[kern] += (b - a) / 1e9
        label = f"{prog or 'other'}:{kern or name.split('.')[0]}"
        by_label[label] += (b - a) / 1e9
    busy_ns = sum(_union(iv) for iv in per_device.values())
    gaps = S.gaps({"spans": [[n, s, d, {}] for n, s, d in trace["host"]],
                   "busy": [busy], "window": [w0, w1]}, top)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9 / trace["devices"],
        "program_s": dict(program_s),
        "program_runs": dict(program_runs),
        "kernel_s": dict(kernel_s),
        "op_s": dict(by_label),
        "device_ops": sorted(([k, v] for k, v in by_label.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": gaps,
    }


def save(trace: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
