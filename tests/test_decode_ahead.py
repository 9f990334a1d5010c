"""``EngineConfig.decode_ahead``: the engine keeps one decode step in flight,
dispatching the next on the device-resident tokens and caches of the one
running before it books that one. Whatever the overlap, every request gets
the tokens the plain synchronous path gives it, and the page pool ends as
full as it started.
"""
import numpy as np
import pytest

import jax

from repro.configs import smoke_config
from repro.models import transformer as T
from repro.serving import (
    EngineConfig, Request, SamplingParams, ServingEngine, SpecConfig,
)
from repro.serving.config import ConfigError

ARCHS = ("glm4-9b", "deepseek-moe-16b")


@pytest.fixture(scope="module", params=ARCHS)
def model(request):
    cfg = smoke_config(request.param)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _requests(vocab, n=9, eos_id=None, sampled=False, seed=1):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        sp = (SamplingParams(temperature=0.8, top_k=20, seed=i)
              if sampled and i % 2 else None)
        out.append(Request(
            uid=i, prompt=rng.integers(0, vocab, int(rng.integers(2, 30))).tolist(),
            max_new_tokens=int(rng.integers(1, 12)), eos_id=eos_id, sampling=sp))
    return out


def _serve(cfg, params, ahead, reqs, **kw):
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=3, max_len=64, decode_ahead=ahead, **kw))
    for r in reqs:
        eng.submit(r)
    while eng.step() or eng.queue:
        pass
    return eng


def _outputs(reqs):
    return [(list(r.output), r.finish_reason) for r in reqs]


@pytest.mark.parametrize("case", ["greedy", "eos", "sampled"])
def test_decode_ahead_serves_the_plain_paths_tokens(model, case):
    """More requests than lanes, answers of 1 to 11 tokens: the same tokens
    and finish reasons as the synchronous path, every page back in the pool
    and no step left in flight. With an eos id that one request meets, the
    lane that ends by eos decodes one dropped token more; sampled lanes draw
    from (seed, position) keys, so they agree too."""
    cfg, params = model
    kw = {"sampled": case == "sampled"}
    if case == "eos":
        probe = _requests(cfg.vocab)
        _serve(cfg, params, False, probe)
        kw["eos_id"] = probe[3].output[2]
    plain = _requests(cfg.vocab, **kw)
    ahead = _requests(cfg.vocab, **kw)
    e0 = _serve(cfg, params, False, plain)
    e1 = _serve(cfg, params, True, ahead)
    assert _outputs(ahead) == _outputs(plain)
    if case == "eos":
        assert "eos" in {r.finish_reason for r in ahead}
    assert e1.allocator.available() == e0.allocator.available() == e1.allocator.capacity
    assert e1._inflight is None and not any(s.req for s in e1.slots)
    assert e1.decoded_tokens == e0.decoded_tokens


def test_decode_ahead_books_one_step_behind_the_device(model):
    """A step() call dispatches the next decode step before it books the
    one in flight: the first call after admission books no decode token,
    and each later call books the step the call before dispatched."""
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, decode_ahead=True))
    r = Request(uid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=6)
    eng.submit(r)
    eng.step()
    assert len(r.output) == 1 and eng._inflight is not None and eng.steps == 0
    eng.step()
    assert len(r.output) == 2 and eng._inflight is not None and eng.steps == 1
    while eng.step():
        pass
    assert len(r.output) == 6 and r.finish_reason == "length"
    assert eng._inflight is None and eng.steps == 5


def test_decode_ahead_cancel_while_in_flight(model):
    """A request cancelled while its step is in flight books nothing more;
    its pages come back, and the other lanes serve the tokens they serve
    alone."""
    cfg, params = model
    reqs = _requests(cfg.vocab, n=3, seed=4)
    for r in reqs:
        r.max_new_tokens = 8
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=3, max_len=64, decode_ahead=True))
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None
    n = len(reqs[1].output)
    assert eng.cancel(reqs[1].uid)
    while eng.step() or eng.queue:
        pass
    assert reqs[1].finish_reason == "cancelled" and len(reqs[1].output) == n
    assert eng.allocator.available() == eng.allocator.capacity
    for r in (reqs[0], reqs[2]):
        solo = Request(uid=9, prompt=list(r.prompt), max_new_tokens=8)
        _serve(cfg, params, False, [solo])
        assert r.output == solo.output


def test_decode_ahead_quarantines_an_injected_fault_as_the_plain_path(model):
    """A pending injected fault books the step in flight and runs the plain
    step, so the poisoned output index is the one named: the request ends
    ``error`` with the same tokens as on the plain path, the others are
    untouched, and the engine goes back to keeping a step in flight."""
    cfg, params = model
    got = []
    for ahead in (False, True):
        reqs = _requests(cfg.vocab, n=5, seed=7)
        for r in reqs:
            r.max_new_tokens = 9
        eng = ServingEngine(cfg, params, EngineConfig(
            max_batch=3, max_len=64, decode_ahead=ahead))
        for r in reqs:
            eng.submit(r)
        eng.inject_fault(reqs[1].uid, 4)
        while eng.step() or eng.queue:
            pass
        assert reqs[1].finish_reason == "error" and eng.errors == 1
        assert eng.allocator.available() == eng.allocator.capacity
        got.append(_outputs(reqs))
    assert got[0] == got[1]


@pytest.mark.parametrize("clash", [
    {"paged": False}, {"admission": "optimistic"},
    {"prefill_budget": 32, "chunk_size": 16}, {"spec": SpecConfig(k=2)},
    {"drift_every": 4},
])
def test_decode_ahead_refuses_lanes_that_change_mid_decode(clash):
    """Growth, chunks, speculative rollback and drift sampling each read or
    change a lane between its dispatch and its booking: refused up front."""
    with pytest.raises(ConfigError, match="decode_ahead"):
        EngineConfig(decode_ahead=True, **clash)


def test_decode_ahead_refuses_an_unpaged_block():
    cfg = smoke_config("mamba2-1.3b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ConfigError, match="paged"):
        ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=32,
                                                decode_ahead=True))
