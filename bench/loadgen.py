"""The one traffic generator: a mix's data file and a seed in, every request
and its arrival out.

A mix (``bench/traffic/<mix>.json``) gives:

* ``loop``: ``"closed"``, ``clients`` clients, each sending its next request
  when its last reply is done; or ``"open"``, requests that arrive on a
  fixed schedule whatever the replies, ``clients`` sessions taking turns:
  request ``i`` is session ``i % clients``'s ``i // clients``-th;
* ``prompt`` and ``output``: the length distributions of each request's own
  prompt tokens and of its answer;
* ``prefix`` (optional): ``{"count": n, ...a length distribution}``, prompt
  heads that sessions share: session ``c``'s prompts start with prefix
  ``c % n``. ``n == clients`` gives each session its own document; fewer
  give system prompts shared among sessions;
* open loop only: ``rate`` (requests per second), ``arrivals``
  (``"poisson"`` or ``"uniform"`` gaps), ``burst`` (requests that arrive
  together, default 1) and ``lead_s`` (seconds the schedule runs before the
  window opens, so the window starts at steady load);
* closed loop only: ``stagger_first``: the first answer of client ``i`` is
  cut to ``(i + 1) / clients`` of its length, so the first completions
  spread out instead of arriving together.

Every seed brings the same work, so that runs differ by the system's noise
and not by their traffic:

* each distribution is cut into ``clients * requests_per_client`` strata
  (prefixes: ``count``) and each length is the middle of its stratum; so
  are the open loop's gaps, ``requests_per_client * clients / burst`` of
  them, cycled;
* one fixed shuffle, the same for every seed, deals the lengths and gaps;
* the seed draws the token ids, per request from ``(seed, kind, session,
  k)``, so a seed gives the same tokens however far a run gets.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import Dict, List

import numpy as np

_SEED_MOD = 1 << 63
LOOPS = ("closed", "open")


@dataclasses.dataclass(frozen=True)
class Spec:
    client: int
    k: int
    prompt: np.ndarray  # int32 token ids
    max_new: int


def load_mix(path) -> Dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}")
    if mix["loop"] == "open" and not mix.get("rate", 0) > 0:
        raise ValueError(f"{path}: an open loop needs a rate above 0")
    return mix


def _quantile(dist: Dict, q: float) -> float:
    if dist["dist"] == "uniform":
        return dist["min"] + (dist["max"] - dist["min"]) * q
    if dist["dist"] == "lognormal":
        return dist["median"] * math.exp(
            dist["sigma"] * statistics.NormalDist().inv_cdf(q))
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def strata(dist: Dict, n: int) -> List[int]:
    """``n`` lengths, the midpoints of ``n`` equal-probability strata."""
    lo, hi = int(dist["min"]), int(dist["max"])
    return [int(min(hi, max(lo, round(_quantile(dist, (j + 0.5) / n)))))
            for j in range(n)]


def gaps(mix: Dict, n: int) -> np.ndarray:
    """``n`` gaps in seconds between the open loop's arrivals (of a burst
    each), the midpoints of ``n`` strata of their distribution, scaled so
    that the schedule offers exactly ``rate``."""
    mean = mix.get("burst", 1) / mix["rate"]
    q = (np.arange(n) + 0.5) / n
    if mix.get("arrivals", "poisson") == "poisson":
        g = -np.log1p(-q)
        return g * (mean / g.mean())
    if mix["arrivals"] == "uniform":
        return np.full(n, mean)
    raise ValueError(f"unknown arrivals {mix['arrivals']!r}")


class Traffic:
    """The requests of one mix under one seed: ``request(c, k)`` is session
    ``c``'s ``k``-th request; in an open loop ``arrival(i)`` is when request
    ``i`` (``request(i % clients, i // clients)``) is due."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.open = mix["loop"] == "open"
        self.seed = int(seed) % _SEED_MOD
        self.vocab = vocab
        self.clients = int(mix["clients"])
        self.per_client = int(mix["requests_per_client"])
        n = self.clients * self.per_client
        rng = np.random.default_rng(0)  # the same lengths for every seed
        self.prompt_lens = rng.permutation(strata(mix["prompt"], n)).reshape(
            self.clients, self.per_client)
        self.output_lens = rng.permutation(strata(mix["output"], n)).reshape(
            self.clients, self.per_client)
        pre = mix.get("prefix")
        self.prefix_lens = (rng.permutation(strata(pre, int(pre["count"])))
                            if pre else np.zeros((0,), np.int64))
        if self.open:
            self.burst = int(mix.get("burst", 1))
            g = rng.permutation(gaps(mix, max(1, n // self.burst)))
            self._due = np.concatenate([[0.0], np.cumsum(g)])
        self._prefixes: Dict[int, np.ndarray] = {}

    def _tokens(self, kind: int, c: int, k: int, n: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, kind, c, k))
        return rng.integers(0, self.vocab, int(n), dtype=np.int32)

    def prefix(self, c: int) -> np.ndarray:
        """The prompt head session ``c`` shares (empty without prefixes)."""
        if not len(self.prefix_lens):
            return np.zeros((0,), np.int32)
        p = c % len(self.prefix_lens)
        if p not in self._prefixes:
            self._prefixes[p] = self._tokens(2, p, 0, self.prefix_lens[p])
        return self._prefixes[p]

    def request(self, c: int, k: int) -> Spec:
        j = k % self.per_client
        own = self._tokens(1, c, k, self.prompt_lens[c, j])
        max_new = int(self.output_lens[c, j])
        if k == 0 and not self.open and self.mix.get("stagger_first"):
            max_new = max(int(self.mix["output"]["min"]),
                          round(max_new * (c + 1) / self.clients))
        return Spec(c, k, np.concatenate([self.prefix(c), own]), max_new)

    def arrival(self, i: int) -> float:
        """Seconds from the schedule's start to open-loop request ``i``."""
        g, cycle = divmod(i // self.burst, len(self._due) - 1)
        return g * self._due[-1] + self._due[cycle]
