"""Observability layer (PR 8): metrics registry, span tracing, drift.

Three unit families (registry semantics, trace ring + Chrome export,
quant-drift monitor) plus the engine integration: a scripted serving run
with tracing and metrics on must export a structurally valid,
Perfetto-loadable Chrome trace whose request tracks tell the request's
life story (admit -> prefill -> first_token -> retire, preempt -> resume),
and the v8 stats surface must be derivable from the registry alone.
"""
import json

import numpy as np
import pytest

import jax

from repro.configs import smoke_config
from repro.models import transformer as T
from repro.obs.drift import QuantDriftMonitor, clips_from_params
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import ENGINE_TRACK, TraceRing, span, validate_chrome_trace
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving.spec_decode import SpecConfig


# ---------------------------------------------------------------------------
# metrics registry


def test_counter_monotonic():
    c = Counter("requests_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    c.set_(10.0)  # facade path: increases allowed
    assert c.value == 10.0
    with pytest.raises(ValueError):
        c.inc(-1.0)
    with pytest.raises(ValueError):
        c.set_(5.0)  # decreasing a nonzero counter is a bug


def test_gauge_free_move():
    g = Gauge("queue_depth", "help")
    g.set(5.0)
    g.inc(-2.0)
    assert g.value == 3.0


def test_histogram_percentile_exact_under_window():
    h = Histogram("lat", "help", buckets=(0.1, 1.0, 10.0))
    for v in [0.05, 0.2, 0.3, 5.0]:
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(5.55)
    # nearest-rank: exact order statistics while the reservoir holds all
    assert h.percentile(50) == 0.2
    assert h.percentile(100) == 5.0
    assert h.percentile(0) == 0.05
    assert h.mean == pytest.approx(5.55 / 4)


def test_histogram_window_bounded():
    h = Histogram("lat", "help", window=8)
    for i in range(100):
        h.observe(float(i))
    assert h.count == 100  # cumulative count is exact
    assert h.percentile(0) == 92.0  # reservoir kept the newest 8


def test_registry_get_or_create_and_clashes():
    m = MetricsRegistry()
    c1 = m.counter("steps_total", "h")
    assert m.counter("steps_total") is c1
    with pytest.raises(TypeError):
        m.gauge("steps_total")  # same name, different kind
    with pytest.raises(ValueError):
        m.counter("bad name!")
    # labelled series are distinct children under one name
    a = m.gauge("site_rate", "h", labels={"site": "a"})
    b = m.gauge("site_rate", "h", labels={"site": "b"})
    assert a is not b
    assert m.gauge("site_rate", labels={"site": "a"}) is a


def test_prometheus_text_exposition():
    m = MetricsRegistry()
    m.counter("steps_total", "engine steps").inc(3)
    m.gauge("depth", "queue depth").set(2)
    h = m.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    m.gauge("site_rate", "per site", labels={"site": "a"}).set(0.25)
    text = m.prometheus_text()
    lines = text.splitlines()
    # exactly one HELP/TYPE pair per metric name
    assert lines.count("# TYPE steps_total counter") == 1
    assert "steps_total 3" in lines
    assert "depth 2" in lines
    assert 'site_rate{site="a"} 0.25' in lines
    # histogram: cumulative buckets + +Inf == count
    assert 'lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{le="1"} 2' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 2' in lines
    assert "lat_seconds_count 2" in lines


def test_registry_snapshot_json_safe():
    m = MetricsRegistry()
    m.counter("a_total", "h").inc()
    m.histogram("b", "h").observe(1.0)
    snap = m.snapshot()
    json.dumps(snap)  # must round-trip
    assert snap["a_total"]["value"] == 1.0
    assert snap["b"]["count"] == 1


# ---------------------------------------------------------------------------
# trace ring


def test_trace_ring_bound_and_dropped():
    tr = TraceRing(capacity=4)
    for i in range(10):
        tr.emit("step", step=i)
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [e.step for e in tr.events()] == [6, 7, 8, 9]
    doc = tr.chrome_trace()
    assert doc["otherData"]["dropped_events"] == 6


def test_trace_ph_assignment():
    tr = TraceRing()
    tr.emit("step", ts=1.0, dur=0.5)
    tr.emit("admit", track=3)
    evs = tr.events()
    assert evs[0].ph == "X" and evs[1].ph == "i"


def test_chrome_trace_valid_and_nested():
    tr = TraceRing()
    # engine step span enclosing a decode_step span, plus request events
    tr.emit("step", ts=1.0, dur=0.10, step=1)
    tr.emit("decode_step", ts=1.02, dur=0.05, step=1)
    tr.emit("admit", track=7, ts=1.01, step=1)
    tr.emit("prefill", track=7, ts=1.03, dur=0.02, step=1, tokens=9)
    doc = tr.chrome_trace()
    assert validate_chrome_trace(doc) is None
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    # sorted by ts; the enclosing span comes before the enclosed one
    names = [e["name"] for e in evs]
    assert names == ["step", "admit", "decode_step", "prefill"]
    step, decode = evs[0], evs[2]
    assert step["tid"] == decode["tid"]  # same engine lane
    # nesting: decode_step lies inside the step span
    assert step["ts"] <= decode["ts"]
    assert decode["ts"] + decode["dur"] <= step["ts"] + step["dur"]
    # thread_name metadata names both tracks
    meta = {e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert {"engine", "req 7"} <= meta


def test_chrome_trace_non_int_uids():
    tr = TraceRing()
    tr.emit("admit", track="req-abc")
    tr.emit("step")
    doc = tr.chrome_trace()
    assert validate_chrome_trace(doc) is None
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] != "M"}
    assert len(tids) == 2  # engine lane + the string-uid track


def test_span_emits_its_ring_kind_with_late_args():
    """A span with a ring kind lands in the ring once, on exit, with the
    args given at entry and through ``set``; the request's uid is its track."""
    tr = TraceRing()
    with span("engine.prefill", tr, kind="prefill", step=4, uid=7,
              tokens=9) as sp:
        assert sp.on
        sp.set(traced=True)
    (e,) = tr.events()
    assert (e.kind, e.ph, e.track, e.step) == ("prefill", "X", 7, 4)
    assert e.args == {"tokens": 9, "traced": True} and e.dur > 0
    with span("engine.step", tr, kind="step", step=5):
        pass
    assert tr.events()[-1].track == ENGINE_TRACK


@pytest.mark.parametrize("ring,kind", [(None, "step"), ("ring", None)],
                         ids=["no_ring", "no_kind"])
def test_span_without_ring_or_kind_emits_nothing(ring, kind):
    """Phases that are not ring kinds, and every span of an engine without
    a ring, reach the profiler only."""
    tr = TraceRing()
    with span("engine.wait", tr if ring else None, kind=kind, step=1) as sp:
        assert sp.on == (ring is not None and kind is not None)
        sp.set(traced=False)
    assert len(tr) == 0


def test_trace_request_timeline():
    tr = TraceRing()
    tr.emit("admit", track=1, ts=1.0)
    tr.emit("admit", track=2, ts=1.1)
    tr.emit("retire", track=1, ts=2.0, finish_reason="eos")
    tl = tr.trace_request(1)
    assert [e["kind"] for e in tl] == ["admit", "retire"]
    assert tl[1]["args"]["finish_reason"] == "eos"
    assert tr.summary() == {"admit": 2, "retire": 1}


# ---------------------------------------------------------------------------
# quant-drift monitor


def _feed(mon, site, rng, scale, batches, n=1024):
    for _ in range(batches):
        mon.observe(site, (rng.standard_normal(n) * scale).astype(np.float32))


def test_drift_silent_on_in_profile_traffic():
    mon = QuantDriftMonitor(calib_samples=4, min_values=512)
    rng = np.random.default_rng(0)
    _feed(mon, "mlp_in#0", rng, 1.0, 4)   # calibration window
    _feed(mon, "mlp_in#0", rng, 1.0, 8)   # live, same distribution
    assert mon.flagged() == {}
    s = mon.stats()
    assert s["drift_sites"] == 1 and s["drift_flagged_sites"] == 0


def test_drift_flags_injected_shift():
    mon = QuantDriftMonitor(calib_samples=4, min_values=512, factor=4.0)
    rng = np.random.default_rng(0)
    _feed(mon, "mlp_in#0", rng, 1.0, 4)
    _feed(mon, "mlp_in#0", rng, 8.0, 8)   # 8x activation blow-up
    flagged = mon.flagged()
    assert "mlp_in#0" in flagged
    assert flagged["mlp_in#0"] > 4.0
    assert mon.stats()["drift_max_ratio"] == pytest.approx(
        flagged["mlp_in#0"])


def test_drift_fixed_clip_from_grid():
    mon = QuantDriftMonitor(clips={"attn_q#0": 2.0}, calib_samples=2,
                            min_values=128)
    rng = np.random.default_rng(1)
    _feed(mon, "attn_q#0", rng, 1.0, 2, n=256)
    st = mon.sites["attn_q#0"]
    assert st.fixed_clip and st.clip == 2.0  # grid clip wins over quantile
    rep = mon.report()["attn_q#0"]
    assert rep["calibrated"] and rep["grid_clip"]


def test_drift_publish_gauges():
    mon = QuantDriftMonitor(calib_samples=2, min_values=128)
    rng = np.random.default_rng(2)
    _feed(mon, "mlp_in#0", rng, 1.0, 4, n=256)
    m = MetricsRegistry()
    mon.publish(m)
    assert m.gauge("quant_drift_sites").value == 1.0
    assert m.gauge("quant_drift_saturation_rate",
                   labels={"site": "mlp_in#0"}).value >= 0.0


# ---------------------------------------------------------------------------
# engine integration


@pytest.fixture(scope="module")
def dense_setup():
    cfg = smoke_config("glm4-9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _reqs(cfg, lengths, max_new=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, n).tolist(),
                    max_new_tokens=max_new)
            for i, n in enumerate(lengths)]


def test_engine_trace_export(dense_setup, tmp_path):
    """Scripted run with tracing on: the export is valid Chrome trace JSON
    and each request's track tells its life story in order."""
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, trace=True, trace_capacity=512))
    for r in _reqs(cfg, [4, 6, 9]):
        eng.submit(r)
    eng.run()
    doc = eng.trace.chrome_trace()
    assert validate_chrome_trace(doc) is None
    path = tmp_path / "trace.json"
    eng.trace.export(str(path))
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) is None
    # engine-lane spans exist and step spans carry their step index
    kinds = eng.trace.summary()
    assert kinds["step"] >= 1 and kinds["decode_step"] >= 1
    for uid in (0, 1, 2):
        tl = [e["kind"] for e in eng.trace.trace_request(uid)]
        assert tl[0] == "admit" and tl[-1] == "retire"
        assert tl.index("prefill") < tl.index("first_token")
    s = eng.stats()
    assert s["trace_enabled"] == 1.0
    assert s["trace_events"] == float(len(eng.trace))


def test_engine_trace_ring_bound(dense_setup):
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, trace=True, trace_capacity=8))
    for r in _reqs(cfg, [4, 6], max_new=8):
        eng.submit(r)
    eng.run()
    assert len(eng.trace) == 8
    assert eng.stats()["trace_dropped"] > 0


def test_engine_trace_preempt_resume(dense_setup):
    """A preempted request's track shows preempt -> resume, in order."""
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=96, page_size=8, n_pages=6,
        admission="optimistic", admission_headroom=1,
        trace=True, trace_capacity=4096))
    for r in _reqs(cfg, [8, 8], max_new=30, seed=11):
        eng.submit(r)
    eng.run()
    assert eng.preempted > 0
    victims = [e.track for e in eng.trace.events() if e.kind == "preempt"]
    assert victims
    tl = [e["kind"] for e in eng.trace.trace_request(victims[0])]
    assert "preempt" in tl and "resume" in tl
    assert tl.index("preempt") < tl.index("resume")
    assert tl[-1] == "retire"


def test_engine_without_trace_emits_into_no_ring(dense_setup, monkeypatch):
    """``trace=False``: a full run, speculative rounds and chunked prefill
    included, puts nothing into any ring."""
    cfg, params = dense_setup
    emitted = []
    monkeypatch.setattr(TraceRing, "emit", lambda self, *a, **k: emitted.append(a))
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, spec=SpecConfig(k=2), prefill_budget=16,
        chunk_size=16))
    for r in _reqs(cfg, [4, 20], max_new=6):
        eng.submit(r)
    eng.run()
    assert eng.trace is None and eng.completed == 2
    assert emitted == []


@pytest.fixture(scope="module")
def lowered_programs(dense_setup):
    """Each serving program, lowered with the arguments of its first call:
    a plain engine runs prefill and decode; a chunked engine on a pool small
    enough to preempt runs chunks and the resume's replay."""
    cfg, params = dense_setup
    first = {}

    def spy(eng, attr, name):
        orig = getattr(eng, attr)

        def make(key):
            fn = orig(key)

            def call(*a):
                first.setdefault(name, (fn, a, {}))
                return fn(*a)
            return call
        setattr(eng, attr, make)

    plain = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=64))
    spy(plain, "_prefill_fn", "prefill")
    decode = plain._decode

    def decode_call(*a, **k):
        first.setdefault("decode", (decode, a, k))
        return decode(*a, **k)
    plain._decode = decode_call
    for r in _reqs(cfg, [4, 6]):
        plain.submit(r)
    plain.run()
    chunked = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=96, page_size=8, n_pages=6,
        admission="optimistic", admission_headroom=1, prefill_budget=8,
        chunk_size=8))
    spy(chunked, "_prefill_chunk_fn", "prefill_chunk")
    spy(chunked, "_replay_fn", "replay")
    for r in _reqs(cfg, [8, 8], max_new=30, seed=11):
        chunked.submit(r)
    chunked.run()
    return {name: fn.lower(*a, **k).as_text().splitlines()[0]
            for name, (fn, a, k) in first.items()}


@pytest.mark.parametrize("program,module", [
    ("decode", "jit_serving_decode_step"),
    ("prefill", "jit_serving_prefill"),
    ("prefill_chunk", "jit_serving_prefill_chunk"),
    ("replay", "jit_serving_replay"),
])
def test_program_module_names(lowered_programs, program, module):
    """The XLA module names a profiler trace shows: stable ``serving_*``
    names, which the benchmark's reduction finds programs by."""
    head = lowered_programs[program]
    assert head.startswith(f"module @{module} "), head
    assert "jit_impl" not in head and "_decode_impl" not in head


def test_engine_stats_v8_from_registry(dense_setup):
    """The flat stats dict carries the v8 keys and agrees with the
    registry's own view of the counters."""
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=64))
    for r in _reqs(cfg, [4, 6]):
        eng.submit(r)
    eng.run()
    s = eng.stats()
    for k in ("trace_enabled", "trace_events", "trace_dropped",
              "drift_enabled", "drift_samples", "drift_sites",
              "drift_flagged_sites", "drift_max_ratio"):
        assert k in s, k
    assert s["trace_enabled"] == 0.0 and s["drift_enabled"] == 0.0
    # facade: the legacy attributes ARE the registry counters
    assert eng.steps == eng.metrics.counter("engine_steps_total").value
    assert eng.completed == 2
    assert (eng.metrics.counter("engine_completed_total").value
            == float(s["completed"]))
    text = eng.metrics_text()
    assert "# TYPE engine_steps_total counter" in text
    assert "request_ttft_seconds_count 2" in text
    json.dumps(eng.metrics_snapshot())


def test_engine_drift_monitor_samples(dense_setup):
    """drift_every=1 samples an eager forward per productive step and
    populates tap sites; in-profile traffic stays unflagged."""
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, drift_every=1))
    for r in _reqs(cfg, [4, 6], max_new=6):
        eng.submit(r)
    eng.run()
    s = eng.stats()
    assert s["drift_enabled"] == 1.0
    assert s["drift_samples"] > 0
    assert s["drift_sites"] > 0
    assert s["drift_flagged_sites"] == 0.0  # self-calibrated, same traffic
    text = eng.metrics_text()
    assert "quant_drift_sites" in text


def test_clips_from_params_quantized_tree():
    """A PTQ'd tree with a static activation grid yields per-site clips."""
    from repro.core.apply import quantize_params
    from repro.core.recipe import QuantRecipe

    cfg = smoke_config("glm4-9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    recipe = QuantRecipe(w_bits=8, a_bits=8, ocs_ratio=0.0, per_channel=True,
                         pad_to=1)
    try:
        qparams = quantize_params(params, recipe)
    except TypeError:
        pytest.skip("recipe surface has no static activation grid")
    clips = clips_from_params(qparams)
    if clips:  # static-grid leaves present
        assert all(v > 0 for v in clips.values())
        assert any(k.startswith("attn_q") or k.startswith("mlp")
                   for k in clips)
    # weight-only trees legitimately produce {} — must not raise
    assert clips_from_params(params) == {}
