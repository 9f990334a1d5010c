"""``BENCHMARK.json`` and the files it names."""
import json
import re
from pathlib import Path

import pytest

HERE = Path(__file__).parent
ROOT = HERE.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_files_exist_and_report(cell):
    cfg = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert (ROOT / cfg["file"]).is_file()
    assert (HERE / "traffic" / f"{cell['traffic']}.json").is_file()
    e2e = [m for m in SPEC["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])]
    pl = [m for m in SPEC["per_layer"] if cell["name"] in m.get("workloads", [cell["name"]])]
    assert any(m["name"] == "setup_s" for m in e2e) and len(e2e) >= 2 and pl
    for m in pl:
        moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
        assert cell["name"] in moved.get("workloads", [cell["name"]])


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    from bench.run import metric_reader

    assert callable(metric_reader(metric["name"]))


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files_hold_what_runs(cfg):
    from repro.configs.base import ModelConfig
    from repro.configs.registry import get_config
    from bench.run import engine_config

    f = json.loads((ROOT / cfg["file"]).read_text())
    assert f["source"] == cfg["source"] and f["reduced"] == cfg["reduced"] == []
    model = ModelConfig(**f["model"])
    # Published widths and full depth, as the program's registry has them.
    reg = get_config(cfg["name"])
    for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "block", "act", "norm"):
        assert getattr(model, k) == getattr(reg, k), k
    engine_config(f["engine"])
    for k in ("assumed", "deployment", "check"):
        assert f[k]


def test_engine_settings_may_choose_kernels():
    from repro.serving import KernelChoice

    from bench.run import engine_config

    e = engine_config({"max_batch": 2, "kernels": {"attn": "xla"}})
    assert e.kernels.attn == KernelChoice.XLA and e.max_batch == 2
