"""A whole run at smoke size on the CPU, past the look for a chip: sound, it
reads correct, in a closed loop, with prefixes, in an open loop and with
replicas behind the router; with the timed path broken underneath, or with
the int4 control in the program's place, it does not.

The smoke limits (``testdata/smoke.json``: widest gap 0.4 of a logit row's
spread, mean 0.03) sit between what sound smoke runs read on 12 seeds
(widest at most 0.11, mean at most 0.005) and what the int4 control read
on the same seeds (widest at least 1.04, mean at least 0.13).
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import run as R
from bench.loadgen import load_mix

HERE = Path(__file__).parent
SPEC = json.loads((HERE.parent / "BENCHMARK.json").read_text())
SMOKE = json.loads((HERE / "testdata" / "smoke.json").read_text())
CELL = "glm4-9b.chat"  # the cell whose end-to-end metrics a smoke run reports


@pytest.fixture(autouse=True)
def _own_compile_cache(monkeypatch):
    """A run turns JAX's persistent cache on; give the settings back."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _run(mix, seed=123, control=False, config=SMOKE):
    m = load_mix(HERE / "testdata" / f"smoke-{mix}.json")
    return R.run_cell({"name": CELL}, config, m, SPEC, seed, 1.0, False,
                      jax.devices("cpu"), control=control, log=lambda *a: None)


def _is_sound(res):
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in SPEC["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("mix", ["chat", "docqa", "open"])
def test_sound_run_is_correct(mix):
    _is_sound(_run(mix))


def test_replicas_behind_the_router_are_correct(monkeypatch):
    """``"replicas": 2`` in the configuration: two engines behind the
    program's router (sharing the one CPU device), both given work."""
    from repro.serving import Router

    built = []
    orig = R.build_front
    monkeypatch.setattr(R, "build_front",
                        lambda *a, **kw: built.append(orig(*a, **kw)) or built[-1])
    _is_sound(_run("chat", config=dict(SMOKE, replicas=2)))
    front, engines = built[0]
    assert isinstance(front, Router) and len(engines) == 2
    assert all(e.completed > 0 for e in engines)


def test_int4_control_is_not_correct():
    """With the control's tokens in the program's place, the run's own
    verdict reads not correct."""
    res = _run("chat", control=True)
    assert not res["correct"], res["compared"]
    assert any(c["value"] > c["limit"] for c in res["compared"].values())


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: the decode step's argmax + 1."""
    from repro.serving import engine as E

    orig = E.ServingEngine._decode_impl

    def broken(self, *a, **kw):
        nxt, finite, caches = orig(self, *a, **kw)
        return (nxt + 1) % self.cfg.vocab, finite, caches

    monkeypatch.setattr(E.ServingEngine, "_decode_impl", broken)


def _keep_state(monkeypatch):
    """A step that returns its state unchanged: decode writes no K/V and
    moves no position."""
    from repro.serving import engine as E

    orig = E.T.decode_step

    def broken(params, token, caches, cfg, **kw):
        logits, _ = orig(params, token, caches, cfg, **kw)
        return logits, caches

    monkeypatch.setattr(E.T, "decode_step", broken)


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_state],
                         ids=["token_altered", "state_unchanged"])
@pytest.mark.parametrize("mix", ["chat", "docqa"])
def test_broken_step_is_not_correct(monkeypatch, fault, mix):
    fault(monkeypatch)
    res = _run(mix)
    assert not res["correct"], res["compared"]


def test_no_chip_no_result(tmp_path):
    """On the CPU the command exits non-zero and prints nothing to stdout."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "glm4-9b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    """In a directory with only BENCHMARK.json and bench/, no program: exit
    non-zero, print nothing to stdout."""
    import os
    import shutil
    import subprocess
    import sys

    shutil.copy(HERE.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "glm4-9b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


# A block kind no reference module will ever be named after.
NO_REFERENCE = "no-such-block"


def test_a_block_without_a_reference_ends_before_the_load(monkeypatch, capsys):
    """A configuration whose block has no ``bench/reference/<block>.py``
    exits 1 with the reason, and loads no weight."""
    import repro.launch.weights as W

    def load(*a, **kw):
        raise AssertionError("weights loaded")

    monkeypatch.setattr(W, "load_quantized", load)
    config = dict(SMOKE, model=dict(SMOKE["model"], block=NO_REFERENCE))
    with pytest.raises(SystemExit) as e:
        _run("chat", config=config)
    assert e.value.code == 1
    assert f"bench: no reference for block '{NO_REFERENCE}'" in capsys.readouterr().err


def test_a_block_without_a_reference_exits_1(tmp_path):
    """From the command line, before the look for a chip: exit 1, the
    reason on standard error, nothing on standard output."""
    import os
    import shutil
    import subprocess
    import sys

    spec = dict(SPEC, configs=SPEC["configs"] + [dict(
        SPEC["configs"][0], name="noref", file="bench/configs/noref.json")],
        workloads=SPEC["workloads"] + [dict(
            SPEC["workloads"][0], name="noref.chat", config="noref")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(HERE.parent / "src")
    config = dict(SMOKE, model=dict(SMOKE["model"], block=NO_REFERENCE))
    (tmp_path / "bench" / "configs" / "noref.json").write_text(json.dumps(config))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "noref.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 1 and p.stdout.strip() == ""
    assert f"bench: no reference for block '{NO_REFERENCE}'" in p.stderr
    assert "no TPU" not in p.stderr
