"""``BENCHMARK.json`` and the files it names."""
import copy
import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List

import pytest

HERE = Path(__file__).parent
ROOT = HERE.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# The model keys compared with the program's registry, besides every field
# of each section (``moe``, ``ssm``, ``hymba``) the registry's entry has.
COMPARED = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "block", "act", "norm")
SECTIONS = ("moe", "ssm", "hymba")
# Widths: never cut, named in ``reduced`` or not (also any key ending in
# ``_dim`` or ``_rank``).
WIDTHS = {"d_model", "d_ff", "moe.expert_ff", "moe.top_k", "ssm.d_state",
          "ssm.expansion", "ssm.conv_width", "hymba.swa_window"}


def _is_width(key: str) -> bool:
    return key in WIDTHS or key.endswith(("_dim", "_rank"))


def _compared(model, reg) -> Dict[str, tuple]:
    """``key path -> (file's value, registry's value)`` for every compared
    key; a section the file lacks reads None in each of its fields."""
    out = {k: (getattr(model, k), getattr(reg, k)) for k in COMPARED}
    for sec in SECTIONS:
        want = getattr(reg, sec)
        if want is None:
            continue
        got = getattr(model, sec)
        for f in dataclasses.fields(want):
            out[f"{sec}.{f.name}"] = (getattr(got, f.name, None),
                                      getattr(want, f.name))
    return out


def cut_errors(f: Dict, entry: Dict) -> List[str]:
    """What keeps configuration file ``f`` from standing for its
    ``BENCHMARK.json`` entry: the source, and ``reduced`` the same in both;
    each key of ``reduced`` a key path (``moe.n_experts``) or a section
    (``moe``) that differs from the registry, with its reason under
    ``reduced_why``; no other compared key different; no width cut."""
    from repro.configs.registry import get_config
    from bench.run import model_config

    errors = []
    if f["source"] != entry["source"]:
        errors.append("source differs from BENCHMARK.json")
    if f["reduced"] != entry["reduced"]:
        errors.append("reduced differs from BENCHMARK.json")
    cut = set(f["reduced"])
    if set(f.get("reduced_why", {})) != cut:
        errors.append("reduced_why does not give one reason per key of reduced")
    pairs = _compared(model_config(f["model"]), get_config(entry["name"]))
    differs = {k for k, (got, want) in pairs.items() if got != want}
    for k in sorted(cut):
        if not NAME.match(k):
            errors.append(f"reduced key {k!r} is not a name")
        if not any(d == k or d.startswith(k + ".") for d in differs):
            errors.append(f"reduced names {k!r}, which is not cut")
    for k in sorted(differs):
        if _is_width(k.split(".")[-1]) or _is_width(k):
            errors.append(f"width {k} is cut: {pairs[k][0]!r} for {pairs[k][1]!r}")
        elif k not in cut and k.split(".")[0] not in cut:
            errors.append(f"{k} is {pairs[k][0]!r} for {pairs[k][1]!r}, not in reduced")
    return errors


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
    from bench.run import engine_config

    f = json.loads((ROOT / cfg["file"]).read_text())
    # Published widths and depth, as the program's registry has them, but
    # for the keys the cut names.
    assert cut_errors(f, cfg) == []
    engine_config(f["engine"])
    for k in ("assumed", "deployment", "check"):
        assert f[k]


def _moe_file(**model):
    """A configuration file of deepseek-moe-16b as the registry has it, with
    ``model`` keys changed (``moe`` a mapping of the section's changes)."""
    from repro.configs.registry import get_config

    m = json.loads(json.dumps(dataclasses.asdict(get_config("deepseek-moe-16b"))))
    m["moe"].update(model.pop("moe", {}))
    m.update(model)
    return {"name": "deepseek-moe-16b",
            "source": "https://huggingface.co/deepseek-ai/deepseek-moe-16b-base",
            "reduced": [], "reduced_why": {}, "model": m}


def _entry(f):
    return {"name": f["name"], "source": f["source"], "file": "x.json",
            "reduced": copy.deepcopy(f["reduced"]), "why": "a test"}


def _cut(f, *keys):
    f["reduced"] = list(keys)
    f["reduced_why"] = {k: "one chip's share of a 4-chip deployment" for k in keys}
    return f


@pytest.mark.parametrize("make,ok", [
    (lambda: _moe_file(), True),
    (lambda: _cut(_moe_file(moe={"n_experts": 16}), "moe.n_experts"), True),
    (lambda: _cut(_moe_file(moe={"n_experts": 16}), "moe"), True),
    (lambda: _cut(_moe_file(moe={"n_experts": 16}, n_layers=4), "moe.n_experts", "n_layers"), True),
    (lambda: _moe_file(moe={"n_experts": 16}), False),
    (lambda: _moe_file(d_model=1024), False),
    (lambda: _cut(_moe_file(d_model=1024), "d_model"), False),
    (lambda: _cut(_moe_file(moe={"expert_ff": 704}), "moe"), False),
    (lambda: _cut(_moe_file(moe={"top_k": 2}), "moe.top_k"), False),
    (lambda: _cut(_moe_file(), "n_layers"), False),
    (lambda: dict(_cut(_moe_file(moe={"n_experts": 16}), "moe.n_experts"),
                  reduced_why={}), False),
], ids=["as_published", "experts_held", "experts_held_by_section",
        "experts_and_depth", "experts_unnamed", "d_model_unnamed", "d_model_named",
        "expert_ff_in_named_section", "top_k_named", "named_but_not_cut", "no_reason"])
def test_a_cut_is_named_in_reduced(make, ok):
    f = make()
    assert (cut_errors(f, _entry(f)) == []) is ok, cut_errors(f, _entry(f))


def test_reduced_must_match_benchmark_json():
    f = _cut(_moe_file(moe={"n_experts": 16}), "moe.n_experts")
    entry = dict(_entry(f), reduced=[])
    assert cut_errors(f, entry) == ["reduced differs from BENCHMARK.json"]


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "hymba-1.5b", "mamba2-1.3b",
                                  "qwen2-vl-7b", "glm4-9b"])
def test_model_config_builds_the_sections(arch):
    """A model mapping as a JSON file holds it (sections as mappings, tuples
    as lists) becomes the registry's ``ModelConfig`` field for field."""
    from repro.configs import smoke_config
    from repro.configs.base import MoEConfig
    from bench.run import model_config

    want = smoke_config(arch)
    got = model_config(json.loads(json.dumps(dataclasses.asdict(want))))
    assert got == want
    for f in dataclasses.fields(want):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
    if arch == "deepseek-moe-16b":
        assert isinstance(got.moe, MoEConfig) and got.moe.n_experts == 8


def test_engine_settings_may_choose_kernels():
    from repro.serving import KernelChoice

    from bench.run import engine_config

    e = engine_config({"max_batch": 2, "kernels": {"attn": "xla"}})
    assert e.kernels.attn == KernelChoice.XLA and e.max_batch == 2
