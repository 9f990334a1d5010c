"""The engine's phase spans in a profiler trace, beside the device's idle time.

The engine opens a span (``repro.obs.trace.span``) at each phase of a step:
``engine.step`` around the whole ``ServingEngine.step``, and inside it
``engine.admit``, ``engine.prefill``, ``engine.grow``, ``engine.inputs``,
``engine.dispatch`` (arg ``program``: ``decode``, ``prefill``, ...; arg
``traced`` when the call traced), ``engine.wait`` and ``engine.book``. Each is
a ``jax.profiler.TraceAnnotation``: a host event on the profiler's clock,
carrying the engine's ``step`` (and a request's ``uid``). This module reads
them, and each device's busy intervals, from the ``.xplane.pb`` of a traced
run:

- :func:`load`: the trace as plain lists, read once per file for all the
  metric readers of a run (:func:`of`);
- :func:`host_ms`, :func:`dispatch_ms`, :func:`idle_ms`: what the
  ``engine.*`` per-layer metrics read;
- :func:`phases` and :func:`gaps`: each phase's host seconds, and the
  longest device-idle gaps, each named by the innermost span (the shortest)
  over its midpoint.

A trace of a program that opens no such spans gives None from the three
readers, and gaps named by the benchmark's own spans alone.

    python3 -m bench.spans [trace dir]   # all of it, as JSON
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import sys
import warnings
from typing import Dict, List, Optional, Tuple

ENGINE = "engine."
WINDOW_SPAN = "bench_window"
STEP_SPAN = "engine.step"


def load(path: str) -> Dict:
    """The trace under ``path`` (a directory holding ``*.xplane.pb``, the
    newest read, or the file) as ``{"spans": [[name, start_ns, dur_ns,
    args]], "busy": [[[start_ns, end_ns], ...] per device], "window":
    [start_ns, end_ns] or None}``: every host span whose name starts with
    ``engine.`` or ``bench_``, sorted by start, and each device's merged
    operation intervals. ``window`` is the last ``bench_window`` span."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return _load_file(os.path.abspath(path), os.stat(path).st_mtime_ns)


@functools.lru_cache(maxsize=1)
def _load_file(path: str, mtime_ns: int) -> Dict:
    from jax.profiler import ProfileData

    spans, busy = [], []
    pd = ProfileData.from_file(path)
    with warnings.catch_warnings():
        # The stats' binding type warns as it is first built.
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
                ivs = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                       for line in plane.lines if line.name == "XLA Ops"
                       for ev in line.events]
                busy.append(_merge(ivs))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        name = ev.name
                        if name.startswith(ENGINE):
                            spans.append([name, ev.start_ns, ev.duration_ns,
                                          dict(ev.stats)])
                        elif name.startswith("bench_"):
                            spans.append([name, ev.start_ns, ev.duration_ns, {}])
    spans.sort(key=lambda x: (x[1], -x[2]))
    windows = [x for x in spans if x[0] == WINDOW_SPAN]
    window = [windows[-1][1], windows[-1][1] + windows[-1][2]] if windows else None
    return {"spans": spans, "busy": busy, "window": window}


def of(run) -> Optional[Dict]:
    """The trace of a traced benchmark run (``bench/run.py`` keeps it in
    its ``TRACE_DIR`` until the next traced run); None for an untraced one."""
    if run.trace is None:
        return None
    from bench.run import TRACE_DIR

    return load(str(TRACE_DIR))


def _merge(ivs) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(merged: List[List[float]], s: float, e: float,
             starts: Optional[List[float]] = None) -> float:
    """How much of [s, e) the merged intervals cover (``starts``: their
    starts, where the caller asks many times)."""
    if starts is None:
        starts = [m[0] for m in merged]
    i = max(0, bisect.bisect_right(starts, s) - 1)
    total = 0.0
    for a, b in merged[i:]:
        if a >= e:
            break
        total += max(0.0, min(b, e) - max(a, s))
    return total


def _window(t: Dict) -> Tuple[float, float]:
    if t["window"]:
        return tuple(t["window"])
    eng = [x for x in t["spans"] if x[0].startswith(ENGINE)]
    if not eng:
        return 0.0, 0.0
    return eng[0][1], max(x[1] + x[2] for x in eng)


def _engine(t: Dict) -> List[List]:
    """The engine spans wholly inside the window."""
    w0, w1 = _window(t)
    return [x for x in t["spans"]
            if x[0].startswith(ENGINE) and x[1] >= w0 and x[1] + x[2] <= w1]


def _decode_steps(t: Dict) -> List[Tuple[List, List[List]]]:
    """Each ``engine.step`` in the window that dispatched a decode program,
    with the engine spans nested in it."""
    eng = _engine(t)
    starts = [x[1] for x in eng]
    out = []
    for st in eng:
        if st[0] != STEP_SPAN:
            continue
        s, e = st[1], st[1] + st[2]
        kids = [x for x in eng[bisect.bisect_left(starts, s):
                               bisect.bisect_right(starts, e)]
                if x is not st and x[1] + x[2] <= e]
        if any(k[0] == "engine.dispatch" and k[3].get("program") == "decode"
               for k in kids):
            out.append((st, kids))
    return out


def host_ms(t: Dict) -> Optional[float]:
    """Mean over decode steps of ``engine.step``'s duration less the part
    its ``engine.wait`` spans cover: the host work of a step."""
    steps = _decode_steps(t)
    if not steps:
        return None
    total = 0.0
    for st, kids in steps:
        waits = _merge((k[1], k[1] + k[2]) for k in kids if k[0] == "engine.wait")
        total += st[2] - _covered(waits, st[1], st[1] + st[2])
    return total / len(steps) / 1e6


def dispatch_ms(t: Dict) -> Optional[float]:
    """Mean duration of the decode ``engine.dispatch`` spans that did not
    trace: flattening the arguments, transfers and the enqueue."""
    d = [x[2] for x in _engine(t) if x[0] == "engine.dispatch"
         and x[3].get("program") == "decode" and not x[3].get("traced")]
    return sum(d) / len(d) / 1e6 if d else None


def idle_ms(t: Dict) -> Optional[float]:
    """Device-idle milliseconds inside ``engine.step`` spans, per decode
    step, averaged over the devices."""
    steps = _decode_steps(t)
    if not steps or not t["busy"]:
        return None
    spans = _merge((x[1], x[1] + x[2]) for x in _engine(t) if x[0] == STEP_SPAN)
    idle = 0.0
    for busy in t["busy"]:
        starts = [m[0] for m in busy]
        idle += sum((e - s) - _covered(busy, s, e, starts) for s, e in spans)
    return idle / len(t["busy"]) / len(steps) / 1e6


def phases(t: Dict) -> Dict[str, List[float]]:
    """Each engine phase's host seconds and span count over the window
    (nested phases count in their own and in their parent's)."""
    out: Dict[str, List[float]] = {}
    for name, _, dur, _ in _engine(t):
        acc = out.setdefault(name, [0.0, 0])
        acc[0] += dur / 1e9
        acc[1] += 1
    return out


def gaps(t: Dict, top: int = 10) -> List[List]:
    """The ``top`` longest stretches of the window in which no device ran an
    operation, as ``[label, seconds]``: the innermost engine or
    ``bench_step`` span over the gap's midpoint, else "outside any bench
    span"."""
    w0, w1 = _window(t)
    busy = _merge(iv for dev in t["busy"] for iv in dev)
    out, end = [], w0
    for s, e in busy:
        if s > end:
            out.append((end, min(s, w1)))
        end = max(end, e)
        if end >= w1:
            break
    if w1 > end:
        out.append((end, w1))
    named = [x for x in t["spans"] if x[0] != WINDOW_SPAN]
    result = []
    for s, e in sorted(out, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        over = [x for x in named if x[1] <= mid < x[1] + x[2]]
        label = min(over, key=lambda x: x[2])[0] if over else "outside any bench span"
        result.append([label, (e - s) / 1e9])
    return result


def main(argv=None) -> None:
    from bench.run import TRACE_DIR

    args = sys.argv[1:] if argv is None else argv
    t = load(args[0] if args else str(TRACE_DIR))
    print(json.dumps({"host_ms": host_ms(t), "dispatch_ms": dispatch_ms(t),
                      "idle_ms": idle_ms(t), "decode_steps": len(_decode_steps(t)),
                      "phases": phases(t), "gaps": gaps(t)}))


if __name__ == "__main__":
    main()
