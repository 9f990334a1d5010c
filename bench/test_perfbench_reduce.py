"""The reduction from trace to metrics: on a hand-built trace whose answers
can be counted, and on a trimmed trace recorded on the chip."""
from pathlib import Path

import pytest

from bench import reduce as R

RECORDED = Path(__file__).parent / "testdata" / "trace_glm4-9b.chat.json.gz"


def _hand_trace():
    # ns: decode program 0-100 (matmul 0-40, attention 40-60, other 60-100),
    # prefill program 150-250, window 0-300; steps 0-120 and 140-300.
    return {
        "modules": [["jit__decode_impl(1)", 0, 100], ["jit_impl(2)", 150, 100]],
        "ops": [
            ["fusion.1", 0, 40, 0, "jit(_decode_impl)/serving_decode_step/ocs_quant_matmul/pallas_call"],
            ["custom-call.2", 40, 20, 0, "serving_decode_step/paged_attention/pallas_call"],
            ["fusion.3", 60, 40, 0, "serving_decode_step/add"],
            ["fusion.4", 150, 100, 1, "jit(impl)/serving_prefill/dot"],
        ],
        "host": [["bench_window", 0, 300], ["bench_step", 0, 120],
                 ["bench_step", 140, 160]],
        "devices": 1,
    }


def test_hand_trace():
    r = R.reduce(_hand_trace())
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["program_s"] == pytest.approx({"decode": 100e-9, "prefill": 100e-9})
    assert r["program_runs"] == {"decode": 1, "prefill": 1}
    assert r["kernel_s"] == pytest.approx({"ocs_matmul": 40e-9, "paged_attn": 20e-9})
    assert r["idle_gaps"] == [["outside any bench span", pytest.approx(50e-9)],
                              ["bench_step", pytest.approx(50e-9)]]
    labels = dict(r["device_ops"])
    assert labels["prefill:fusion"] == pytest.approx(100e-9)
    assert labels["decode:ocs_matmul"] == pytest.approx(40e-9)


def test_window_clips_work_outside_it():
    t = _hand_trace()
    t["host"][0] = ["bench_window", 50, 150]  # 50-200
    r = R.reduce(t)
    assert r["busy_s"] == pytest.approx((50 + 50) * 1e-9)
    assert r["program_s"] == pytest.approx({"decode": 50e-9, "prefill": 50e-9})
    assert r["kernel_s"] == pytest.approx({"paged_attn": 10e-9})


def test_busy_is_each_devices_averaged():
    """Two devices busy at the same time each count: 2 x 50 ns over 2."""
    t = _hand_trace()
    t["ops"] = [op + [0] for op in t["ops"]]
    t["ops"].append(["fusion.5", 0, 50, -1, "other", 1])
    t["devices"] = 2
    r = R.reduce(t)
    assert r["busy_s"] == pytest.approx((200 + 50) / 2 * 1e-9)


def test_overlapping_ops_count_once():
    assert R._union([(0, 10), (5, 20), (30, 40), (35, 36)]) == 30


def test_recorded_trace():
    """Half a second of a glm4-9b.chat window recorded on one TPU v5e chip
    (8 decode lanes): four decode programs, their OCS matmul and
    paged-attention calls, and the host's step spans between them."""
    t = R.read(str(RECORDED))
    r = R.reduce(t)
    assert r["window_s"] == pytest.approx(0.5)
    assert r["busy_s"] == pytest.approx(0.4609839785)
    assert r["program_runs"] == {"decode": 4}
    assert r["program_s"] == pytest.approx({"decode": 0.461316024})
    assert r["kernel_s"] == pytest.approx({"ocs_matmul": 0.4021676805,
                                           "paged_attn": 0.052364094})
    assert r["device_ops"][0][0] == "decode:ocs_matmul"
    assert all(label == "bench_step" for label, _ in r["idle_gaps"][:3])


def test_op_s_holds_every_label():
    r = R.reduce(_hand_trace())
    assert r["op_s"] == pytest.approx({
        "decode:ocs_matmul": 40e-9, "decode:paged_attn": 20e-9,
        "decode:fusion": 40e-9, "prefill:fusion": 100e-9})


def test_recorded_trace_op_s():
    """Every label of the recorded trace, with the seconds ``device_ops``
    gives its ten longest; a named kernel's label reads its ``kernel_s``."""
    r = R.reduce(R.read(str(RECORDED)))
    assert len(r["op_s"]) > len(r["device_ops"]) == 10
    for label, sec in r["device_ops"]:
        assert r["op_s"][label] == sec
    for kern, sec in r["kernel_s"].items():
        assert r["op_s"]["decode:" + kern] == pytest.approx(sec)
    assert sum(r["op_s"].values()) >= r["busy_s"]


def test_gaps_are_named_by_the_innermost_host_span():
    """An engine span inside the benchmark's step names the gap under it:
    idle 250-300 lies in step 140-300, engine.step 140-290 and its
    engine.book 260-285."""
    t = _hand_trace()
    t["host"] += [["engine.step", 140, 150], ["engine.book", 260, 25]]
    t["host"].sort(key=lambda h: h[1])
    assert R.reduce(t)["idle_gaps"] == [
        ["outside any bench span", pytest.approx(50e-9)],
        ["engine.book", pytest.approx(50e-9)]]
