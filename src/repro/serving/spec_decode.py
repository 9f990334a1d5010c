"""Self-speculative decoding: the OCS-quantized model is its own free draft.

The paper's premise — OCS yields a faithful low-precision model *without
retraining* — means every served model ships with an already-calibrated draft
model: its own quantized form. This subsystem exploits that for decode
latency:

* **draft** — the quantized fast path (``w8a8`` dynamic activation quant,
  optionally truncated to the first ``draft_layers`` layers as an early-exit
  drafter) proposes ``k`` greedy tokens per decode lane, one cheap
  single-token step at a time;
* **verify** — the serving-precision target scores all ``k + 1`` positions
  (current token + k proposals) in **one** batched multi-token step
  (:func:`repro.models.transformer.verify_step`) against the same KV cache
  (paged or dense);
* **commit / rollback** — per lane, the longest prefix of proposals that
  matches the target's own greedy argmax chain is committed, plus the
  target's next token (the correction on a miss, the bonus token on a full
  accept) — so every committed token comes from the *target's* argmax and
  greedy spec-decode is **output-identical to plain greedy decode** (the
  subsystem's correctness contract and test oracle). The rejected tail is
  rolled back by rewinding the per-lane position vector
  (``serving.kv_cache.rewind_positions``): stale K/V past the committed
  position is invisible to the causal mask and overwritten in place later.

Draft KV hygiene: the drafter writes its (approximate) K/V rows into the
shared cache while proposing, but the verify step *re-writes every proposed
position* with target-precision K/V — so the cache below the committed
position is always bit-identical to what plain greedy decode would have
written, regardless of draft quality. Draft quality only moves the
acceptance rate, never the output.

Adaptivity: a per-engine :class:`AdaptiveK` controller shrinks/grows the
draft window from the observed per-lane acceptance rate (EMA) — long windows
are wasted draft work when acceptance is low, short windows cap the speedup
when acceptance is high. ``k`` is bounded by ``SpecConfig.k`` so the verify
step compiles at most ``k`` distinct shapes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models import transformer as T
from repro.obs.trace import span

__all__ = ["SpecConfig", "AdaptiveK", "SpecDecoder", "committed_tokens"]


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculation knobs (engine ``spec=`` argument; ``spec_k=`` shorthand).

    ``k`` is the *maximum* draft window; the adaptive controller moves the
    live window within ``[k_min, k]``. ``draft_mode`` is the matmul mode the
    drafter traces under (``w8a8`` = the fused dynamic-quant serving fast
    path; on a float parameter tree every mode is the float matmul, so pair
    it with ``draft_layers`` to get a genuinely cheaper drafter there).
    """

    k: int = 4
    k_min: int = 1
    draft_mode: str = "w8a8"
    draft_layers: Optional[int] = None  # None = all layers
    adaptive: bool = True
    grow_at: float = 0.8  # acceptance EMA above this: k += 1
    shrink_at: float = 0.4  # acceptance EMA below this: k -= 1
    ema: float = 0.8  # EMA decay for the observed acceptance rate

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"spec window k must be >= 1, got {self.k}")
        if not 1 <= self.k_min <= self.k:
            raise ValueError(f"need 1 <= k_min <= k, got {self.k_min}/{self.k}")
        if self.draft_layers is not None and self.draft_layers < 1:
            raise ValueError("draft_layers must be >= 1")


class AdaptiveK:
    """Shrink/grow the draft window from the observed acceptance rate.

    Tracks an EMA of the per-round fraction of accepted draft tokens
    (accepted / proposed, aggregated over the active lanes). High acceptance
    means the draft is trustworthy — longer windows amortize more target
    steps; low acceptance means draft work is being thrown away — shrink.
    """

    def __init__(self, cfg: SpecConfig):
        self.cfg = cfg
        self.k = cfg.k if not cfg.adaptive else max(cfg.k_min, min(2, cfg.k))
        self.acc_ema: Optional[float] = None

    def update(self, accepted: int, proposed: int) -> int:
        if not self.cfg.adaptive or proposed <= 0:
            return self.k
        rate = accepted / proposed
        self.acc_ema = (
            rate
            if self.acc_ema is None
            else self.cfg.ema * self.acc_ema + (1.0 - self.cfg.ema) * rate
        )
        if self.acc_ema > self.cfg.grow_at and self.k < self.cfg.k:
            self.k += 1
        elif self.acc_ema < self.cfg.shrink_at and self.k > self.cfg.k_min:
            self.k -= 1
        return self.k


def committed_tokens(draft_row, greedy_row, k: int) -> Tuple[List[int], int]:
    """Greedy accept for one lane: longest matching proposal prefix + the
    target's next token.

    ``greedy_row[j]`` is the target's argmax after consuming the current
    token and proposals ``< j``; it is the token plain greedy decode emits
    next, *valid only while every earlier proposal matched*. Returns
    ``(tokens to commit, n_accepted)`` with ``len(tokens) == n_accepted + 1``
    (>= 1: a full miss still commits the target's correction — the round can
    never stall).
    """
    out: List[int] = []
    for j in range(k):
        tgt = int(greedy_row[j])
        out.append(tgt)  # always the target's token — exactness by construction
        if int(draft_row[j]) != tgt:
            return out, j
    out.append(int(greedy_row[k]))  # bonus: target's token after a full accept
    return out, k


class SpecDecoder:
    """Jitted draft/verify pair + acceptance bookkeeping for one engine.

    Owns two traced callables over the engine's cache tree: ``_draft`` (one
    cheap single-token step under ``draft_mode`` / ``draft_layers``) and
    ``_verify`` (one target multi-token step under the engine's serving
    mode). Timing is booked warm/compile-separated like the engine's own
    counters so BENCH numbers track kernels, not jit noise.
    """

    def __init__(self, cfg: ModelConfig, spec: SpecConfig, matmul_mode: str,
                 *, matmul_kernel: str = "xla", attn_kernel: str = "gather"):
        if cfg.block not in ("dense", "moe"):
            raise ValueError(
                f"speculative decoding: dense/moe archs only, got {cfg.block} "
                "(SSM/hybrid decode states cannot roll back a rejected tail)"
            )
        self.cfg = cfg
        self.spec = spec
        self.controller = AdaptiveK(spec)
        # Counters (the engine's stats() surfaces these).
        self.rounds = 0  # spec rounds (== target verify steps)
        self.lane_rounds = 0  # per-lane verify events
        self.proposed = 0  # draft tokens proposed (active lanes)
        self.accepted = 0  # draft tokens accepted
        self.committed = 0  # tokens committed (accepted + corrections/bonus)
        self.draft_time_s = 0.0  # warm draft wall time
        self.verify_time_s = 0.0  # warm verify wall time
        self.compile_s = 0.0  # draft+verify calls that triggered a trace
        self.draft_traces = 0
        self.verify_traces = 0
        # Span tracing: the engine attaches its TraceRing (or None) and
        # stamps trace_step before each round, so the round's
        # engine.dispatch / engine.wait spans (ring kinds spec_draft /
        # spec_verify) carry the engine's step.
        self.trace = None
        self.trace_step = 0

        # Draft and verify trace the same kernel selection as the engine's
        # plain decode (``attn_kernel`` / ``matmul_kernel`` from the
        # resolved ``EngineConfig.kernels``): the exactness contract
        # compares verify logits against that path's own decode steps, so
        # the two must go through one attention implementation. XLA module
        # names: jit_serving_spec_draft, jit_serving_spec_verify.
        def serving_spec_draft(params, caches, token):
            self.draft_traces += 1  # python side effect: bumps only tracing
            with layers.serving_mode(spec.draft_mode, kernel=matmul_kernel):
                logits, new_caches = T.decode_step(
                    params, token, caches, cfg, layers_limit=spec.draft_layers,
                    attn_kernel=attn_kernel,
                )
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return nxt, new_caches

        def serving_spec_verify(params, caches, tokens, fault):
            self.verify_traces += 1
            with layers.serving_mode(matmul_mode, kernel=matmul_kernel):
                logits, new_caches = T.verify_step(
                    params, tokens, caches, cfg, attn_kernel=attn_kernel
                )
            # Nonfinite guard (engine fault injection enters through the
            # same add): a lane whose verify logits contain NaN/Inf at any
            # position is flagged — the engine commits nothing for it.
            logits = logits + fault[:, None, None]
            finite = jnp.all(jnp.isfinite(logits), axis=(1, 2))  # [B]
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, Q]
            return greedy, finite, new_caches

        self._draft = jax.jit(serving_spec_draft)
        self._verify = jax.jit(serving_spec_verify)  # one compile per k

    # ------------------------------------------------------------------ round

    def propose_and_verify(self, params, caches, tokens, k: Optional[int] = None,
                           fault=None):
        """One speculation round over the whole decode batch.

        tokens: ``[B, 1]`` current per-lane tokens. Drafts ``k`` proposals
        per lane (default: the adaptive controller's current window; the
        engine clamps it to the largest remaining lane budget — drafting past
        every budget is pure waste), rewinds ``pos`` to the round start, then
        runs ONE target verify step over ``[B, k+1]``. ``k == 0`` degenerates
        to a plain decode step through the verify jit. ``fault`` is an
        optional ``[B]`` float32 row added to every lane's verify logits
        (zeros when ``None``) — the engine's fault-injection hook. Returns
        ``(greedy [B, k+1] np.int32, drafts [B, k] np.int32, finite [B]
        np.bool_, caches, k)`` — caches hold target-written K/V for every
        proposed position with ``pos`` advanced past the window; the engine
        commits per lane and rewinds ``pos`` to the committed positions,
        committing nothing for a lane whose ``finite`` flag is False.
        """
        if k is None:
            k = self.controller.k
        if fault is None:
            fault = jnp.zeros((tokens.shape[0],), jnp.float32)
        pos0 = caches["pos"]
        step = self.trace_step
        traces0 = self.draft_traces + self.verify_traces
        t0 = time.perf_counter()
        with span("engine.dispatch", self.trace, kind="spec_draft", step=step,
                  program="spec_draft", k=k) as d:
            cur, drafts = tokens, []
            for _ in range(k):
                cur, caches = self._draft(params, caches, cur)
                drafts.append(cur)
            if drafts:
                draft_toks = jnp.concatenate(drafts, axis=1)  # [B, k]
            else:
                draft_toks = jnp.zeros((tokens.shape[0], 0), jnp.int32)
            d.set(traced=self.draft_traces + self.verify_traces > traces0)
        with span("engine.wait", step=step):
            np_drafts = np.asarray(draft_toks)  # sync: draft chain retired
        t1 = time.perf_counter()
        # Rewind to the round start: verify re-scores (and re-writes, at
        # target precision) every drafted position.
        caches["pos"] = pos0
        verify0 = self.verify_traces
        with span("engine.dispatch", self.trace, kind="spec_verify", step=step,
                  program="spec_verify", lanes=int(tokens.shape[0])) as d:
            greedy, finite, caches = self._verify(
                params, caches, jnp.concatenate([tokens, draft_toks], axis=1),
                fault,
            )
            d.set(traced=self.verify_traces > verify0)
        with span("engine.wait", step=step):
            np_greedy = np.asarray(greedy)  # sync: verify step fully retired
            np_finite = np.asarray(finite)
        t2 = time.perf_counter()
        if self.draft_traces + self.verify_traces > traces0:
            self.compile_s += t2 - t0
        else:
            self.draft_time_s += t1 - t0
            self.verify_time_s += t2 - t1
        self.rounds += 1
        return np_greedy, np_drafts, np_finite, caches, k

    def book_lane(self, n_accepted: int, n_committed: int, n_proposed: int) -> None:
        """Book one active lane's outcome for this round. ``n_proposed`` is
        the lane's *usable* window (drafts that could possibly commit given
        its remaining budget) — acceptance measures draft quality, so window
        tails past the budget don't count against it."""
        self.lane_rounds += 1
        self.proposed += n_proposed
        self.accepted += n_accepted
        self.committed += n_committed

    def end_round(self, accepted: int, proposed: int) -> None:
        self.controller.update(accepted, proposed)

    # ------------------------------------------------------------------ stats

    def acceptance_rate(self) -> float:
        return self.accepted / self.proposed if self.proposed else 0.0

    def tokens_per_target_step(self) -> float:
        return self.committed / self.lane_rounds if self.lane_rounds else 0.0

    def stats(self) -> dict:
        return {
            "spec_rounds": float(self.rounds),
            "spec_k": float(self.controller.k),
            "spec_proposed": float(self.proposed),
            "spec_acceptance_rate": self.acceptance_rate(),
            "spec_tokens_per_target_step": self.tokens_per_target_step(),
            "spec_draft_time_s": self.draft_time_s,
            "spec_verify_time_s": self.verify_time_s,
            "spec_compile_s": self.compile_s,
        }
