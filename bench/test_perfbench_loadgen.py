"""The traffic generator: seeds reorder one multiset of lengths and gaps."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.loadgen import Traffic, load_mix, strata

HERE = Path(__file__).parent
# The benchmark's mixes, and the smoke mixes with prefixes and an open loop.
MIXES = {"chat": HERE / "traffic" / "chat.json",
         "docqa": HERE / "testdata" / "smoke-docqa.json",
         "open": HERE / "testdata" / "smoke-open.json"}


@pytest.mark.parametrize("mix", list(MIXES))
def test_every_seed_brings_the_same_work(mix):
    m = load_mix(MIXES[mix])
    a, b = Traffic(m, 3, 1000), Traffic(m, 2**31 + 12345, 1000)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.output_lens, b.output_lens)
    assert np.array_equal(a.prefix_lens, b.prefix_lens)
    if a.open:
        assert [a.arrival(i) for i in range(40)] == [b.arrival(i) for i in range(40)]
    # ... and other tokens.
    assert not np.array_equal(a.request(0, 0).prompt, b.request(0, 0).prompt)


@pytest.mark.parametrize("mix", list(MIXES))
def test_lengths_keep_to_the_mix(mix):
    m = load_mix(MIXES[mix])
    t = Traffic(m, 7, 1000)
    for key, lens in (("prompt", t.prompt_lens), ("output", t.output_lens)):
        assert lens.min() >= m[key]["min"] and lens.max() <= m[key]["max"]
    for c in range(t.clients):
        for k in range(3):
            s = t.request(c, k)
            assert s.prompt.dtype == np.int32 and s.prompt.max() < 1000


def test_same_seed_same_requests():
    m = load_mix(MIXES["docqa"])
    a, b = Traffic(m, 11, 500), Traffic(m, 11, 500)
    for c in range(a.clients):
        ra, rb = a.request(c, 2), b.request(c, 2)
        assert np.array_equal(ra.prompt, rb.prompt) and ra.max_new == rb.max_new


def test_documents_prefix_every_request_of_their_client():
    m = load_mix(MIXES["docqa"])
    t = Traffic(m, 5, 500)
    docs = [t.prefix(c) for c in range(t.clients)]
    assert not np.array_equal(docs[0][:64], docs[1][:64])  # one each
    for c, doc in enumerate(docs):
        assert 64 <= len(doc) <= 128
        for k in range(3):
            p = t.request(c, k).prompt
            assert np.array_equal(p[:len(doc)], doc)
            assert 8 <= len(p) - len(doc) <= 24


def test_sessions_share_a_system_prompt():
    m = load_mix(MIXES["open"])
    t = Traffic(m, 5, 500)
    head = t.prefix(0)
    assert len(head) == 40
    for c in range(t.clients):
        assert np.array_equal(t.request(c, 1).prompt[:40], head)


def test_open_loop_arrives_at_its_rate_in_bursts():
    m = load_mix(MIXES["open"])
    t = Traffic(m, 5, 500)
    n = t.clients * t.per_client
    due = [t.arrival(i) for i in range(3 * n)]
    assert due == sorted(due) and due[0] == 0.0
    assert all(due[i] == due[i + 1] for i in range(0, 3 * n, m["burst"]))
    # The gaps cycle, and their mean is the rate's.
    assert due[n] == pytest.approx(n / m["rate"], rel=0.05)
    assert due[2 * n] == pytest.approx(2 * due[n])


@pytest.mark.parametrize("bad", [{"loop": "sometimes"}, {"loop": "open", "rate": 0}])
def test_mixes_that_cannot_run_are_refused(tmp_path, bad):
    f = tmp_path / "mix.json"
    f.write_text(json.dumps(dict(bad, clients=1, requests_per_client=1)))
    with pytest.raises(ValueError):
        load_mix(f)


def test_first_answers_are_staggered():
    m = load_mix(MIXES["chat"])
    t = Traffic(m, 5, 500)
    for c in range(t.clients):
        full = int(t.output_lens[c, 0])
        want = max(m["output"]["min"], round(full * (c + 1) / t.clients))
        assert t.request(c, 0).max_new == want
        assert t.request(c, 1).max_new == int(t.output_lens[c, 1])


def test_strata_are_the_middles():
    assert strata({"dist": "uniform", "min": 0, "max": 100}, 4) == [12, 38, 62, 88]
    med = strata({"dist": "lognormal", "median": 100, "sigma": 1.0,
                  "min": 1, "max": 10**6}, 3)
    assert med[1] == 100 and med[0] < 100 < med[2]
