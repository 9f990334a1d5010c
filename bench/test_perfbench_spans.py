"""The engine's phase spans as the benchmark reads them: written by a
smoke-size engine under a CPU profiler window, joined with the engine's
trace ring, and reduced by ``bench/spans.py`` on a hand-built trace whose
answers can be counted and on the trace recorded on the chip."""
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import reduce as R
from bench import spans as S
from bench.run import metric_reader

RECORDED = Path(__file__).parent / "testdata" / "trace_glm4-9b.chat.json.gz"
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def smoke():
    from repro.configs import smoke_config
    from repro.models import transformer as T

    cfg = smoke_config("glm4-9b")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _engine(smoke, **kw):
    from repro.serving import EngineConfig, Request, ServingEngine

    cfg, params = smoke
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_len=64, **kw))
    rng = np.random.default_rng(0)
    for i, n in enumerate([5, 9]):
        eng.submit(Request(uid=i, prompt=rng.integers(0, cfg.vocab, n).tolist(),
                           max_new_tokens=6))
    return eng


def _profiled(path, steps):
    """Run ``steps()`` under a CPU profiler window; the spans it recorded."""
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=o)
    try:
        steps()
    finally:
        jax.profiler.stop_trace()
    return S.load(str(path))


def _nested(t, parent):
    s, e = parent[1], parent[1] + parent[2]
    return [x for x in t["spans"] if x is not parent and x[0].startswith("engine.")
            and s <= x[1] and x[1] + x[2] <= e]


def test_one_step_nests_its_phases(smoke, tmp_path):
    """One ``ServingEngine.step``: an ``engine.step`` span with the admit
    (and the prefill it installs), the inputs, the dispatch, the wait and
    the booking inside it, every one stamped with the step."""
    eng = _engine(smoke)
    t = _profiled(tmp_path, eng.step)
    (step,) = [x for x in t["spans"] if x[0] == "engine.step"]
    kids = _nested(t, step)
    names = {k[0] for k in kids}
    assert {"engine.admit", "engine.prefill", "engine.inputs", "engine.dispatch",
            "engine.wait", "engine.book"} <= names
    assert all(k[3]["step"] == step[3]["step"] == 0 for k in kids)
    decode = [k for k in kids if k[0] == "engine.dispatch"
              and k[3]["program"] == "decode"]
    assert len(decode) == 1 and decode[0][3]["traced"] == 1
    prefill = [k for k in kids if k[0] == "engine.prefill"]
    assert sorted(k[3]["uid"] for k in prefill) == [0, 1]
    assert [k[3]["tokens"] for k in prefill] == [5, 9]


def test_ring_joins_profiler_on_step(smoke, tmp_path):
    """With ``trace=True`` the ring's ``step`` and ``decode_step`` spans are
    the profiler's ``engine.step`` and decode ``engine.dispatch`` spans:
    they join on ``step`` and their durations agree within 1 ms."""
    eng = _engine(smoke, trace=True)

    def steps():
        for _ in range(4):
            eng.step()

    t = _profiled(tmp_path, steps)
    prof = {("step", x[3]["step"]): x[2] for x in t["spans"] if x[0] == "engine.step"}
    prof.update({("decode_step", x[3]["step"]): x[2] for x in t["spans"]
                 if x[0] == "engine.dispatch" and x[3]["program"] == "decode"})
    ring = [(e.kind, e.step, e.dur) for e in eng.trace.events()
            if e.kind in ("step", "decode_step")]
    assert sorted((k, n) for k, n, _ in ring) == sorted(prof) and len(ring) == 8
    for kind, n, dur in ring:
        assert abs(prof[(kind, n)] / 1e9 - dur) < 1e-3, (kind, n)


def test_no_span_per_token(smoke, tmp_path):
    """A decode step opens the same spans with one lane decoding as with
    four: spans are per step, never per token."""
    counts = []
    for lanes in (1, 4):
        from repro.serving import EngineConfig, Request, ServingEngine

        cfg, params = smoke
        eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_len=64))
        for i in range(lanes):
            eng.submit(Request(uid=i, prompt=[1 + i, 2, 3], max_new_tokens=8))
        eng.step()  # admits and prefills every lane
        t = _profiled(tmp_path / str(lanes), lambda: [eng.step() for _ in range(3)])
        steps = [x for x in t["spans"] if x[0] == "engine.step"]
        counts.append(sorted(len(_nested(t, st)) for st in steps))
    assert counts[0] == counts[1] == [6, 6, 6]


def test_reduce_keeps_the_engine_spans(smoke, tmp_path):
    """``bench/reduce.py`` reads the engine's spans beside the benchmark's
    from a profiled window, and names its gaps as ``bench/spans.py`` does."""
    eng = _engine(smoke)

    def steps():
        with jax.profiler.TraceAnnotation("bench_window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench_step"):
                    eng.step()

    spans = _profiled(tmp_path, steps)
    t = R.load(str(tmp_path))
    names = {h[0] for h in t["host"]}
    assert {"bench_window", "bench_step", "engine.step", "engine.dispatch",
            "engine.wait"} <= names
    assert R.reduce(t)["idle_gaps"] == S.gaps(spans)


def _hand_trace():
    """Two decode steps in a 100 ms window (ms):

    - step 0 (1-45): admit 1-3, inputs 3-4, a decode dispatch that traced
      4-9, wait 9-40, book 40-44;
    - step 1 (51-95): admit 51-70 holding a prefill of request 3 (52-69:
      dispatch 53-55, wait 55-68), inputs 70-71, decode dispatch 71-73,
      wait 73-90, book 90-94;
    - the benchmark's steps 0-50 and 50-92; the device busy 5-40, 54-68
      and 72-90.
    """
    def sp(name, a, b, **args):
        return [name, a * MS, (b - a) * MS, args]

    spans = [
        sp("bench_window", 0, 100), sp("bench_step", 0, 50),
        sp("engine.step", 1, 45, step=0), sp("engine.admit", 1, 3, step=0),
        sp("engine.inputs", 3, 4, step=0),
        sp("engine.dispatch", 4, 9, step=0, program="decode", traced=1),
        sp("engine.wait", 9, 40, step=0), sp("engine.book", 40, 44, step=0),
        sp("bench_step", 50, 92), sp("engine.step", 51, 95, step=1),
        sp("engine.admit", 51, 70, step=1),
        sp("engine.prefill", 52, 69, step=1, uid=3, tokens=9),
        sp("engine.dispatch", 53, 55, step=1, uid=3, program="prefill"),
        sp("engine.wait", 55, 68, step=1, uid=3),
        sp("engine.inputs", 70, 71, step=1),
        sp("engine.dispatch", 71, 73, step=1, program="decode", traced=0),
        sp("engine.wait", 73, 90, step=1), sp("engine.book", 90, 94, step=1),
    ]
    spans.sort(key=lambda x: (x[1], -x[2]))
    busy = [[[5 * MS, 40 * MS], [54 * MS, 68 * MS], [72 * MS, 90 * MS]]]
    return {"spans": spans, "busy": busy, "window": [0, 100 * MS]}


def test_gaps_are_named_by_the_innermost_span():
    """Idle 0-5 lies in the first admit, 40-54 after step 0 in the
    benchmark's step, 68-72 in step 1's inputs, 90-100 past the
    benchmark's step."""
    got = S.gaps(_hand_trace())
    assert got == [["bench_step", pytest.approx(0.014)],
                   ["outside any bench span", pytest.approx(0.010)],
                   ["engine.admit", pytest.approx(0.005)],
                   ["engine.inputs", pytest.approx(0.004)]]
    ph = S.phases(_hand_trace())
    assert ph["engine.wait"] == [pytest.approx(0.061), 3]
    assert ph["engine.step"] == [pytest.approx(0.088), 2]


@pytest.mark.parametrize("metric,want", [
    # (44 - 31) and (44 - 13 - 17) ms of host work
    ("engine.host_ms", 13.5),
    # the one decode dispatch that did not trace
    ("engine.dispatch_ms", 2.0),
    # (44 - 35) + (44 - 14 - 18) idle ms over two decode steps
    ("engine.idle_ms", 10.5),
])
def test_readers_on_a_hand_trace(monkeypatch, metric, want):
    monkeypatch.setattr(S, "load", lambda path: _hand_trace())
    run = types.SimpleNamespace(trace={})
    assert metric_reader(metric)(run) == pytest.approx(want)
    assert metric_reader(metric)(types.SimpleNamespace(trace=None)) is None


def test_recorded_trace_reduces_as_before():
    """The chip's trace sample (no engine spans: recorded before the
    engine had them) reduces to the same numbers as ever, its gaps keep
    the benchmark's labels, and the engine readers find nothing there."""
    t = R.read(str(RECORDED))
    r = R.reduce(t)
    assert r["window_s"] == pytest.approx(0.5)
    assert r["busy_s"] == pytest.approx(0.4609839785)
    assert r["program_runs"] == {"decode": 4}
    assert r["program_s"] == pytest.approx({"decode": 0.461316024})
    assert r["kernel_s"] == pytest.approx({"ocs_matmul": 0.4021676805,
                                           "paged_attn": 0.052364094})
    as_spans = {
        "spans": sorted(([n, s, d, {}] for n, s, d in t["host"]),
                        key=lambda x: (x[1], -x[2])),
        "busy": [S._merge((s, s + d) for _, s, d, *_ in t["ops"])],
        "window": list(R.window(t)),
    }
    assert S.gaps(as_spans) == [[label, pytest.approx(sec)]
                                for label, sec in r["idle_gaps"]]
    assert all(label == "bench_step" for label, _ in S.gaps(as_spans)[:3])
    assert S.host_ms(as_spans) is None
    assert S.dispatch_ms(as_spans) is None
    assert S.idle_ms(as_spans) is None
