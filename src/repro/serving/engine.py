"""Batched serving engine over the OCS-quantized model (continuous batching).

The paper's deployment scenario is an ML service provider running a client's
float model in low precision. This engine is that provider's serving loop:

* **configuration** — one validated, hashable :class:`EngineConfig`
  (``serving.config``) owns every engine-level knob: batching, paging,
  matmul mode, kernel backend selection (:class:`KernelConfig` — threaded
  explicitly through ``layers.dense`` / ``attention_decode``; the old
  ``USE_PALLAS_*`` module globals survive only as deprecated shims that seed
  ``auto``), speculation, and probes. Legacy constructor kwargs
  (``max_batch=`` etc.) keep working one release behind a
  ``DeprecationWarning``;
* **weights** — the OCS+clip+int8 parameter tree from
  :func:`repro.core.apply.quantize_params` (float trees also accepted: the
  model layer dispatches on leaf type), held in the serving layout of one
  tree per layer (``models.transformer.per_layer``, applied at
  construction; ``launch.weights.load_quantized`` builds it directly);
* **request lifecycle** — ``submit(Request)`` queues; per-request
  :class:`SamplingParams` select greedy (default — the mode every
  bit-exactness contract is stated over) or temperature/top-k/top-p
  sampling with a per-lane PRNG key derived from ``(seed, position)``
  (``serving.sampling``), folded into the jitted decode/prefill steps;
  :meth:`ServingEngine.generate` is a streaming facade yielding
  :class:`TokenEvent` s as tokens land (first tokens stream before the
  batch completes); :meth:`ServingEngine.cancel` retires a request
  mid-flight, reclaiming its lane and releasing its pages through
  ``PageAllocator.truncate``;
* **decode lanes** — a fixed decode batch of ``max_batch`` sequences sharing
  one jitted ``decode_step``; finished sequences free their lane immediately
  and the next queued request is *hot-swapped in* (continuous batching);
* **paged KV cache** (attention archs, the default) — KV lives in a global
  page pool (``serving.kv_cache``) addressed per lane through a block table;
  admission is page-based (see PR 2) with FIFO backpressure, prefix reuse,
  and page reclamation at retirement;
* **prefill** — *chunked*: the prompt suffix (zero-padded to a pow2 bucket)
  runs through one jitted call — O(1) jitted calls per request. SSM/hybrid
  blocks fall back to decode-step replay;
* **step scheduler** (``EngineConfig.prefill_budget > 0``, PR 7) — prefill
  is *budgeted*: prompts split into ``chunk_size``-token chunks fed through
  the step loop, each step packing all live decode lanes plus at most
  ``prefill_budget`` prefill tokens, so no decode token waits behind a
  whole prompt (``serving.scheduler.StepScheduler`` owns the policy:
  ``sched_policy`` fifo/sjf with a ``sched_aging_steps`` anti-starvation
  bound). Mid-prefill lanes are invisible to decode (trash table row),
  pause speculation rounds, and are first-class preemption victims (their
  full prefilled pages are registered, so re-admission resumes from the
  prefix cache). Interleaved greedy output is token-identical to the
  uninterleaved (``prefill_budget=0``) oracle — paged + unpaged, dense +
  MoE, spec on/off;
* **self-speculative decoding** (``EngineConfig.spec``, dense/moe) — the
  quantized model drafts ``k`` greedy tokens per lane, the target verifies
  all ``k+1`` positions in one step (``serving.spec_decode``). Greedy
  spec-decode is *output-identical* to plain greedy decode; lanes with
  non-greedy ``SamplingParams`` fall back to plain decode steps for the
  rounds they are active (greedy lanes keep their exact token streams —
  plain decode and spec-decode commit the same argmax chain);
* **overload safety** (PR 6) — ``EngineConfig.admission`` selects between
  *reserve* (worst-case pages up front, the PR-2 behavior) and *optimistic*
  admission (prompt pages + headroom; pages grow per decode step, and on
  pool exhaustion the **youngest lane is preempted**: its full pages are
  registered in the prefix cache, its pages released, and the request
  re-enters the queue head carrying its committed tokens — recompute reuses
  the registered pages and replays the committed output through the decode
  path, so greedy output is **bit-identical** to the uninterrupted run);
  per-request ``deadline_s`` sheds queued/active requests past their
  deadline (``finish_reason="timeout"``), ``EngineConfig.max_queue`` bounds
  the queue with a typed :class:`EngineOverloaded` rejection
  (``finish_reason="shed"``), ``isfinite`` guards folded into the jitted
  decode/prefill steps quarantine numerically faulted lanes
  (``finish_reason="error"``, with a fault-injection hook and an automatic
  pallas->xla attention fallback after repeated faults), and a watchdog
  (``runtime.health.StepTimer`` / ``HeartbeatMonitor``) surfaces step-time
  p50/p95 and a stall flag;
* **observability** (PR 8) — a per-engine :class:`~repro.obs.metrics.
  MetricsRegistry` owns every counter/histogram the engine books (the
  legacy counter attributes are registry-backed properties, so the hot
  path is unchanged); ``EngineConfig.trace`` turns on a bounded
  :class:`~repro.obs.trace.TraceRing` of typed span events (admit /
  prefill_chunk / decode_step / spec rounds / preempt / shed / ... —
  exportable as Perfetto-loadable Chrome trace JSON);
  ``EngineConfig.drift_every`` samples a
  :class:`~repro.obs.drift.QuantDriftMonitor` eager forward every N steps,
  tracking live activation saturation against the calibrated OCS/clip
  grid; ``EngineConfig.profile_dir`` wraps :meth:`ServingEngine.run` in a
  ``jax.profiler`` trace window (``jax.named_scope`` labels the jitted
  prefill/decode/verify dispatches);
* **stats** — a typed :class:`EngineStats` (schema v10: v8 plus the
  precision-tier fields), *derived from the metrics registry* —
  percentiles come from registry histograms, counts from registry
  counters; ``stats()`` keeps returning the flat dict view and
  :meth:`ServingEngine.metrics_text` renders the same registry as
  Prometheus text exposition.

Trace counters (``prefill_traces`` / ``decode_traces`` bump only while jit
is tracing) let benchmarks assert the compile story: a request must cost
O(1) jitted calls, not O(prompt_len).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Callable, Deque, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers
from repro.models import transformer as T
from repro.obs.drift import QuantDriftMonitor, clips_from_params
from repro.obs.log import get_logger
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import TraceRing
from repro.runtime.health import HeartbeatMonitor, StepTimer
from . import kv_cache as kvc
from . import sampling as sampling_mod
from . import spec_decode as spec_mod
from .config import (
    ConfigError,
    EngineConfig,
    KernelChoice,
    KernelConfig,
    SamplingParams,
)
from .scheduler import StepScheduler

__all__ = [
    "Request",
    "TokenEvent",
    "EngineStats",
    "EngineOverloaded",
    "ServingEngine",
    "FINISH_REASONS",
]

_LOG = get_logger("serving.engine")

# The one documented finish_reason vocabulary (docs/serving.md §Overload
# behavior). Every request that leaves the engine carries exactly one:
#   eos       — emitted the request's eos_id
#   length    — exhausted max_new_tokens
#   cancelled — cancel(uid) mid-flight
#   timeout   — deadline_s expired (queued or active)
#   error     — nonfinite logits quarantined the lane
#   shed      — rejected at submit (bounded queue full)
FINISH_REASONS = ("eos", "length", "cancelled", "timeout", "error", "shed")

# Terminal reasons that never booked a final token themselves: stream()
# emits a synthetic finished=True TokenEvent so streaming callers can't
# hang on a request that silently left the queue. "cancelled" is excluded
# (the documented v5 contract: a cancel simply ends the stream).
_SENTINEL_REASONS = ("timeout", "error", "shed")


class EngineOverloaded(RuntimeError):
    """Typed rejection: the bounded submit queue (EngineConfig.max_queue)
    is full. The request was never queued; its ``finish_reason`` is
    ``"shed"`` and ``t_done`` is set, so ``stream()``/``generate()`` yield
    the single shed sentinel event instead of hanging.

    Carries enough context for an *informed* retry (the replica router's
    backoff policy, docs/serving.md §Replicated serving): ``queue_depth``
    is the depth of the queue that rejected the request, and
    ``retry_after_hint_s`` estimates when a slot may free up — the
    engine's rolling median step time times the queue depth (0.0 on a
    cold engine that has never stepped: no information, not advice to
    retry immediately at all costs).
    """

    def __init__(self, msg: str = "", *, queue_depth: int = 0,
                 retry_after_hint_s: float = 0.0):
        super().__init__(msg)
        self.queue_depth = queue_depth
        self.retry_after_hint_s = retry_after_hint_s

_GREEDY = SamplingParams()
_UNSET = object()  # legacy-kwarg sentinel: None is a meaningful value


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    sampling: Optional[SamplingParams] = None  # None = greedy
    deadline_s: Optional[float] = None  # seconds after submit; None = none
    # Filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0  # first admission into a lane (queue-wait stats)
    t_first_token: float = 0.0
    t_done: float = 0.0
    t_tokens: List[float] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None  # one of FINISH_REASONS


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One streamed token of one request (the ``generate`` facade's unit).

    ``t`` is the ``time.perf_counter`` stamp the engine booked the token at
    — TTFT and inter-token latencies derive from these, so the benchmark
    numbers and the stream a client observes are the same measurement.
    """

    uid: int
    token: int
    index: int  # 0-based position in the request's output stream
    t: float
    finished: bool = False
    finish_reason: Optional[str] = None  # set on the final event


@dataclasses.dataclass
class EngineStats:
    """Typed serving counters (stats schema v10, frozen).

    The dict view (:meth:`as_dict`, what ``ServingEngine.stats()`` returns)
    is the stable cross-PR schema consumed by benchmarks — append fields,
    never rename. v10 additions over v8 (v9 was the router schema rev —
    ``serving.router`` — no engine fields changed): the precision-tier
    fields ``kv_bits`` (0 = float KV, 8 / 4 = quantized page tiers; pairs
    with ``matmul_mode``, which gains the ``"w4a8"`` vocabulary) and the
    capacity gauges ``kv_bytes_per_token`` (per-token KV footprint across
    all layers, scales + nibble packing included) and
    ``kv_pool_capacity_tokens`` (pool capacity expressed in tokens —
    ``kv_pages_capacity * page_size``; the int4 tier doubles this at
    matched pool memory). docs/serving.md §Precision tiers has the v9->v10
    migration table. v8 additions over v7 (the observability layer —
    docs/serving.md §Observability has the migration table): the span-ring
    telemetry ``trace_enabled`` / ``trace_events`` / ``trace_dropped`` and
    the quant-drift telemetry ``drift_enabled`` / ``drift_samples`` /
    ``drift_sites`` / ``drift_flagged_sites`` / ``drift_max_ratio``. v8
    also re-derives every numeric field from the engine's metrics
    registry: latency percentiles come from bounded-reservoir registry
    histograms booked live at the event sites (nearest-rank, matching
    ``runtime.health.StepTimer``) instead of an O(done) post-hoc
    ``np.percentile`` scan — same numbers for runs shorter than the
    reservoir window (4096 observations). v7 added the scheduler counters
    (``sched_*``) and ``queue_wait_p50_s`` / ``queue_wait_p95_s`` (submit
    -> first lane admission). v6 added the overload counters ``preempted``
    / ``shed`` / ``timed_out`` / ``errors`` / ``kernel_fallbacks``, the
    watchdog ``step_p50_ms`` / ``step_p95_ms`` / ``step_stalled``, and
    narrowed ``completed`` to *successful* terminals only (eos/length).
    Mean/percentile latencies are booked over successful terminals only.
    """

    completed: int = 0
    cancelled: int = 0
    preempted: int = 0
    shed: int = 0
    timed_out: int = 0
    errors: int = 0
    kernel_fallbacks: int = 0
    step_p50_ms: float = 0.0
    step_p95_ms: float = 0.0
    step_stalled: float = 0.0
    decode_steps: int = 0
    decoded_tokens: int = 0
    mean_latency_s: float = 0.0
    mean_ttft_s: float = 0.0
    ttft_p50_s: float = 0.0
    ttft_p95_s: float = 0.0
    itl_p50_s: float = 0.0
    itl_p95_s: float = 0.0
    prefill_tokens: int = 0
    prefill_time_s: float = 0.0
    prefill_compile_s: float = 0.0
    prefill_tok_per_s: float = 0.0
    decode_time_s: float = 0.0
    decode_compile_s: float = 0.0
    decode_tok_per_s: float = 0.0
    prefill_calls: int = 0
    prefill_requests: int = 0
    prefill_calls_per_request: float = 0.0
    prefill_traces: int = 0
    decode_traces: int = 0
    kv_page_size: float = 0.0
    kv_pages_capacity: float = 0.0
    kv_pages_in_use: float = 0.0
    kv_pages_cached: float = 0.0
    kv_pages_peak: float = 0.0
    kv_pool_occupancy: float = 0.0
    kv_pool_peak_occupancy: float = 0.0
    prefix_hit_rate: float = 0.0
    prefix_hit_pages: float = 0.0
    attn_kernel: str = "xla"
    matmul_kernel: str = "xla"
    matmul_mode: str = "dequant"
    kv_bits: float = 0.0
    kv_bytes_per_token: float = 0.0
    kv_pool_capacity_tokens: float = 0.0
    attn_step_ms: float = 0.0
    spec_enabled: float = 0.0
    spec_rounds: float = 0.0
    spec_k: float = 0.0
    spec_proposed: float = 0.0
    spec_accepted: float = 0.0
    spec_acceptance_rate: float = 0.0
    spec_tokens_per_target_step: float = 0.0
    spec_draft_time_s: float = 0.0
    spec_verify_time_s: float = 0.0
    spec_compile_s: float = 0.0
    queue_wait_p50_s: float = 0.0
    queue_wait_p95_s: float = 0.0
    sched_policy: str = "fifo"
    sched_prefill_budget: float = 0.0
    sched_chunks: float = 0.0
    sched_budget_limited_steps: float = 0.0
    sched_aging_promotions: float = 0.0
    sched_peak_step_prefill_tokens: float = 0.0
    trace_enabled: float = 0.0
    trace_events: float = 0.0
    trace_dropped: float = 0.0
    drift_enabled: float = 0.0
    drift_samples: float = 0.0
    drift_sites: float = 0.0
    drift_flagged_sites: float = 0.0
    drift_max_ratio: float = 0.0

    def as_dict(self) -> Dict:
        return {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    remaining: int = 0
    pages: List[int] = dataclasses.field(default_factory=list)
    seq: int = 0  # install order: preemption always evicts the youngest
    # Budgeted-prefill phase (EngineConfig.prefill_budget > 0): prompt
    # tokens already prefilled, or -1 once the lane is decoding. Mid-prefill
    # lanes are decode-invisible (trash table row, pos 0, greedy sampling).
    prefill_pos: int = -1
    keys: List[bytes] = dataclasses.field(default_factory=list)  # prompt
    # chain keys (paged): full pages register as their chunk completes
    scratch: Optional[Dict] = None  # unpaged chunking: b=1 prefill cache

    @property
    def prefilling(self) -> bool:
        return self.req is not None and self.prefill_pos >= 0


def _fold_legacy_kwargs(config: Optional[EngineConfig], legacy: Dict) -> EngineConfig:
    """One release of backwards compatibility: map deprecated ``ServingEngine``
    kwargs onto :class:`EngineConfig` fields behind a ``DeprecationWarning``."""
    present = {k: v for k, v in legacy.items() if v is not _UNSET}
    config = config if config is not None else EngineConfig()
    if not present:
        return config
    warnings.warn(
        f"ServingEngine kwargs {sorted(present)} are deprecated; pass "
        "EngineConfig(...) (repro.serving.EngineConfig) instead",
        DeprecationWarning,
        stacklevel=3,
    )
    upa = present.pop("use_pallas_paged_attn", None)
    if upa is not None:  # legacy bool vocabulary -> KernelChoice
        config = config.replace(
            kernels=dataclasses.replace(
                config.kernels,
                attn=KernelChoice.PALLAS if upa else KernelChoice.GATHER,
            )
        )
    spec_k = present.pop("spec_k", 0)
    if spec_k and present.get("spec") is None:
        present["spec"] = spec_mod.SpecConfig(k=spec_k)
    return config.replace(**present)


# Legacy counter attribute -> (registry metric name, integer-valued, help).
# Each attribute is installed as a ServingEngine property over a registered
# Counter (see _install_counter_properties), so the ad-hoc `self.steps += 1`
# bookkeeping all over the engine *is* the metric update and the stats-v8
# view derives from the registry instead of shadow state.
_COUNTER_METRICS = {
    "steps": ("engine_steps_total", True, "engine step iterations"),
    "decoded_tokens": ("engine_decoded_tokens_total", True,
                       "decode tokens booked into request outputs"),
    "completed": ("engine_completed_total", True,
                  "successful terminals (eos/length)"),
    "cancelled": ("engine_cancelled_total", True,
                  "requests cancelled mid-flight"),
    "preempted": ("engine_preempted_total", True,
                  "lanes preempted under page-pool pressure"),
    "shed": ("engine_shed_total", True,
             "requests rejected at submit (bounded queue full)"),
    "timed_out": ("engine_timed_out_total", True,
                  "requests shed past their deadline_s"),
    "errors": ("engine_errors_total", True,
               "requests quarantined on nonfinite logits"),
    "kernel_fallbacks": ("engine_kernel_fallbacks_total", True,
                         "automatic pallas -> xla attention demotions"),
    "prefill_calls": ("engine_prefill_calls_total", True,
                      "jitted calls spent on prefill"),
    "prefill_requests": ("engine_prefill_requests_total", True,
                         "requests that entered prefill"),
    "prefill_tokens": ("engine_prefill_tokens_total", True,
                       "prompt tokens run through prefill compute"),
    "prefill_tokens_warm": ("engine_prefill_tokens_warm_total", True,
                            "prefill tokens in warm (non-tracing) calls"),
    "prefill_traces": ("engine_prefill_traces_total", True,
                       "distinct prefill jit compilations"),
    "decode_traces": ("engine_decode_traces_total", True,
                      "distinct decode jit compilations"),
    "decode_tokens_warm": ("engine_decode_tokens_warm_total", True,
                           "decode tokens in warm (non-tracing) steps"),
    "prefill_time_s": ("engine_prefill_warm_seconds_total", False,
                       "warm prefill wall time"),
    "prefill_compile_s": ("engine_prefill_compile_seconds_total", False,
                          "prefill wall time spent tracing/compiling"),
    "decode_time_s": ("engine_decode_warm_seconds_total", False,
                      "warm decode wall time"),
    "decode_compile_s": ("engine_decode_compile_seconds_total", False,
                         "decode wall time spent tracing/compiling"),
}


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        config: Optional[EngineConfig] = None,
        *,
        # Deprecated kwargs (one release behind a DeprecationWarning; the
        # canonical surface is EngineConfig):
        max_batch=_UNSET,
        max_len=_UNSET,
        matmul_mode=_UNSET,
        paged=_UNSET,
        page_size=_UNSET,
        n_pages=_UNSET,
        spec=_UNSET,
        spec_k=_UNSET,
        use_pallas_paged_attn=_UNSET,
        attn_probe=_UNSET,
    ):
        if not cfg.causal:
            raise ValueError("encoder-only arch: no decode serving")
        config = _fold_legacy_kwargs(
            config,
            dict(
                max_batch=max_batch, max_len=max_len, matmul_mode=matmul_mode,
                paged=paged, page_size=page_size, n_pages=n_pages, spec=spec,
                spec_k=spec_k, use_pallas_paged_attn=use_pallas_paged_attn,
                attn_probe=attn_probe,
            ),
        )
        # Precision tier: EngineConfig.kv_bits overrides the model config's
        # cache precision, applied *before* any cache is built so every
        # layer pool (and the drift monitor's tier calibration) sees it.
        if config.kv_bits is not None and config.kv_bits != cfg.kv_bits:
            cfg = dataclasses.replace(cfg, kv_bits=config.kv_bits)
        self.kv_bits = cfg.kv_bits
        self.cfg = cfg
        self.params = params
        self.config = config
        if config.matmul_mode == "w4a8":
            # Sub-8-bit weight tier: rebuild the OCSQuantLinear leaves as
            # packed W4A8Linear (OCS-ranked outlier channels stay int8).
            # Host-side, once, at construction — like PTQ itself.
            from repro.core.ocs import OCSQuantLinear, W4A8Linear, to_w4a8

            def _to_tier(leaf):
                if isinstance(leaf, OCSQuantLinear):
                    return to_w4a8(leaf, config.w4a8_outlier_ratio)
                return leaf

            self.params = jax.tree.map(
                _to_tier,
                self.params,
                is_leaf=lambda x: isinstance(x, (OCSQuantLinear, W4A8Linear)),
            )
            params = self.params
        # The serving layout, once: a per-layer tree per layer, so no decode
        # step slices a stacked weight (see models.transformer.per_layer).
        self.params = T.per_layer(self.params)
        # Observability (PR 8, docs/serving.md §Observability). The metrics
        # registry always exists — every legacy counter attribute below is a
        # registry-backed property (see _COUNTER_METRICS), so booking costs
        # one float add whether anyone is scraping or not. Span tracing and
        # drift sampling are opt-in (EngineConfig.trace / drift_every).
        self.metrics = MetricsRegistry()
        self._metric_counters = {
            attr: self.metrics.counter(name, help_)
            for attr, (name, _integer, help_) in _COUNTER_METRICS.items()
        }
        self._hist_ttft = self.metrics.histogram(
            "request_ttft_seconds", "submit -> first booked token"
        )
        self._hist_itl = self.metrics.histogram(
            "request_itl_seconds", "gap between consecutive booked tokens"
        )
        self._hist_qwait = self.metrics.histogram(
            "request_queue_wait_seconds", "submit -> first lane admission"
        )
        self._hist_latency = self.metrics.histogram(
            "request_latency_seconds",
            "submit -> done over successful terminals (eos/length)",
        )
        self._hist_step = self.metrics.histogram(
            "engine_step_seconds", "one step() call, productive or not"
        )
        self.trace: Optional[TraceRing] = (
            TraceRing(config.trace_capacity) if config.trace else None
        )
        # Quant-drift monitor: clips come from the params tree's calibrated
        # activation grids where present; other sites self-calibrate from
        # early traffic. Sampling happens in step(), outside the watchdog
        # timer; the first sampling failure disables the monitor for good
        # (telemetry must never take the serving loop down). The sub-8-bit
        # tiers calibrate against a wider baseline-saturation floor — a
        # 4-bit grid clips more ordinary-traffic mass by design.
        grid_bits = 4 if (cfg.kv_bits == 4 or config.matmul_mode == "w4a8") else 8
        self._drift: Optional[QuantDriftMonitor] = (
            QuantDriftMonitor(
                clips=clips_from_params(params),
                factor=config.drift_threshold,
                grid_bits=grid_bits,
            )
            if config.drift_every > 0
            else None
        )
        self._drift_broken = False
        self._drift_last_step = -1
        self._profiling = False
        self.max_batch = config.max_batch
        self.max_len = config.max_len
        self.matmul_mode = config.matmul_mode
        # Kernel backends, resolved ONCE (the only reads of the deprecated
        # USE_PALLAS_* shims) and captured per engine: co-resident engines
        # with different KernelConfigs dispatch independently.
        resolved = config.kernels.resolve()
        self.matmul_kernel = resolved.matmul.value  # "pallas" | "xla"
        self.slots = [_Slot() for _ in range(self.max_batch)]
        self.queue: Deque[Request] = deque()  # FIFO; popleft is O(1) on the
        # admission hot loop (a plain list.pop(0) is O(n) for deep queues)
        self.done: List[Request] = []
        # Paged KV cache: attention archs only (SSM/hybrid decode states are
        # O(1) per lane — nothing to page).
        self.paged = (
            cfg.block in ("dense", "moe") if config.paged is None else config.paged
        )
        if cfg.kv_bits == 4 and not self.paged:
            raise ConfigError(
                "kv_bits=4 packs nibbles into page pools; this engine "
                f"resolved to an unpaged cache (block={cfg.block!r}) — the "
                "dense cache has no int4 layout"
            )
        if self.paged:
            if cfg.block not in ("dense", "moe"):
                raise ValueError(f"paged KV cache: dense/moe only, got {cfg.block}")
            page_size = config.page_size
            if self.max_len % page_size:
                raise ValueError(
                    f"max_len {self.max_len} must be a multiple of page_size "
                    f"{page_size}"
                )
            self.page_size = page_size
            self.max_pages_per_seq = self.max_len // page_size
            n_pages = config.n_pages
            if n_pages is None:
                # Default pool = the old fixed-slot memory footprint
                # (+ the reserved trash page); shrink it to oversubscribe.
                n_pages = self.max_batch * self.max_pages_per_seq + 1
            self.allocator = kvc.PageAllocator(n_pages, page_size)
            self.caches = kvc.init_paged_cache(
                cfg, self.max_batch, n_pages, page_size, self.max_pages_per_seq,
                dtype=jnp.float32,
            )
        else:
            self.allocator = None
            self.caches = T.init_cache(cfg, self.max_batch, self.max_len,
                                       dtype=jnp.float32)
        # Paged decode-attention backend (KernelChoice vocabulary); unpaged
        # engines have no paged path and report "xla" (the dense einsums).
        self.attn_kernel = resolved.attn.value if self.paged else "xla"
        # Self-speculative decoding: the quantized model drafts k tokens per
        # lane, the serving-precision target verifies them in one multi-token
        # step. The decoder traces the engine's exact kernel selection.
        self._spec = (
            spec_mod.SpecDecoder(
                cfg, config.spec, self.matmul_mode,
                matmul_kernel=self.matmul_kernel, attn_kernel=self.attn_kernel,
            )
            if config.spec is not None
            else None
        )
        if self._spec is not None:
            self._spec.trace = self.trace  # draft/verify spans, engine lane
        # Per-step attention-time probe (stats()["attn_step_ms"]): off by
        # default — it costs one extra jit compile per engine, which tier-1
        # tests creating dozens of engines must not pay.
        self.attn_probe = config.attn_probe and self.paged
        self._attn_probe_fn: Optional[Callable] = None
        # Overload safety (PR 6). Optimistic admission only means something
        # on a paged engine (unpaged caches are fixed-slot: admission can
        # never oversubscribe, so the mode silently degrades to reserve).
        self.admission = config.admission if self.paged else "reserve"
        self.completed = 0
        self.cancelled = 0
        self.preempted = 0
        self.shed = 0
        self.timed_out = 0
        self.errors = 0
        self.kernel_fallbacks = 0
        self._install_seq = 0  # monotonic install stamp (victim selection)
        # Continuous-batching step scheduler (PR 7): admission ordering for
        # every engine; budgeted chunked prefill when prefill_budget > 0.
        self.chunked = config.prefill_budget > 0
        self._sched = StepScheduler(
            policy=config.sched_policy,
            aging_steps=config.sched_aging_steps,
            prefill_budget=config.prefill_budget,
            chunk_size=config.chunk_size,
        )
        self._sched.trace = self.trace  # budget-limited / promotion instants
        self._preempted_uids: set = set()  # resumes outrank policy order
        self._fault_at: Dict[int, int] = {}  # uid -> output index to poison
        self._fault_streak = 0  # consecutive quarantined requests (no
        # healthy eos/length completion in between) on this kernel
        # Serving watchdog: step-time percentiles + optional heartbeat file
        # (the training-fleet observers from runtime.health, reused as-is).
        self._step_timer = StepTimer(window=200)
        self._heartbeat = (
            HeartbeatMonitor(
                config.heartbeat_path,
                min_interval=config.heartbeat_interval_s,
            )
            if config.heartbeat_path
            else None
        )
        self.tokens = jnp.zeros((self.max_batch, 1), jnp.int32)
        self.steps = 0
        self.decoded_tokens = 0
        # Per-lane sampling state (greedy unless a request says otherwise).
        # The device-array view is rebuilt lazily on admission/retirement;
        # the decode jit's static `sampled` flag follows the live batch, so
        # greedy-only rounds never trace (or pay for) the sampling branch.
        self._sampling: List[SamplingParams] = [_GREEDY] * self.max_batch
        self._samp_cache: Optional[Dict[str, jnp.ndarray]] = None
        self._auto_uid = 0
        # Perf counters (the serving benchmark's raw data). Throughput is
        # computed from *warm* time/tokens only: calls that triggered a jit
        # trace are booked under *_compile_s so BENCH numbers track kernels,
        # not XLA compile noise.
        self.prefill_calls = 0  # jitted calls spent on prefill
        self.prefill_requests = 0
        self.prefill_tokens = 0  # tokens actually run through prefill compute
        self.prefill_tokens_warm = 0
        self.prefill_time_s = 0.0  # warm prefill wall time
        self.prefill_compile_s = 0.0
        self.decode_time_s = 0.0  # warm decode wall time
        self.decode_compile_s = 0.0
        self.decode_tokens_warm = 0
        self.prefill_traces = 0  # distinct prefill compilations (buckets)
        self.decode_traces = 0

        self._decode = jax.jit(self._decode_impl, static_argnames=("sampled",))
        # Prefill jits per shape key: prompt-length bucket (pow2 padding
        # bounds recompiles) + the sampled flag, plus the prefix-hit page
        # count when paged.
        self._prefill_cache: Dict[Tuple, Callable] = {}
        # Preemption-resume replay jits, keyed by token bucket (b=1).
        self._replay_cache: Dict[int, Callable] = {}
        # Budgeted chunk-prefill jits: (token bucket, prefix pad, sampled).
        # The prefix pad is pow2-bucketed and the real prefix length traced,
        # so successive chunks of one prompt share traces.
        self._chunk_cache: Dict[Tuple, Callable] = {}

    # ------------------------------------------------------------- internals

    @property
    def paged_attn(self) -> bool:
        """Legacy view of the attention-kernel selection (True = the fused
        paged-attention dispatch, i.e. ``kernels.attn`` is pallas/xla)."""
        return self.paged and self.attn_kernel in ("pallas", "xla")

    def _decode_impl(self, params, caches, token, samp, fault, *, sampled: bool):
        self.decode_traces += 1  # python side effect: runs only while tracing
        with jax.named_scope("serving_decode_step"), layers.serving_mode(
            self.matmul_mode, kernel=self.matmul_kernel
        ):
            logits, new_caches = T.decode_step(
                params, token, caches, self.cfg, attn_kernel=self.attn_kernel
            )
        # fault: [B] f32, 0.0 everywhere except lanes the injection hook
        # poisons (NaN) — one fused add, free when all-zero. The finite
        # flag is the nonfinite guard: the host quarantines a failed lane
        # instead of streaming garbage (its "token" below is meaningless).
        logits = logits + fault[:, None]
        finite = jnp.all(jnp.isfinite(logits), axis=-1)
        if sampled:
            # Keys derive from (request seed, position): reproducible across
            # runs, batch compositions, and paged/unpaged engines.
            nxt = sampling_mod.sample_tokens(logits, samp, caches["pos"])
        else:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt[:, None], finite, new_caches

    def _samp_device(self) -> Dict[str, jnp.ndarray]:
        if self._samp_cache is None:
            self._samp_cache = sampling_mod.params_to_arrays(self._sampling)
        return self._samp_cache

    @staticmethod
    def _samp_one(sp: SamplingParams) -> Dict[str, jnp.ndarray]:
        """Single-lane sampling arrays (the per-request prefill call)."""
        return sampling_mod.params_to_arrays([sp])

    def _set_lane_sampling(self, slot_idx: int, sp: SamplingParams) -> None:
        self._sampling[slot_idx] = sp
        self._samp_cache = None

    def _active_sampled(self) -> bool:
        return any(
            s.req is not None and not self._sampling[i].greedy
            for i, s in enumerate(self.slots)
        )

    def _prefill_bucket(self, n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        if self.paged:
            b = max(b, self.page_size)  # page-granular writes
        return min(b, self.max_len)

    def _prefill_fn(self, key) -> Callable:
        """key: (bucket, sampled) unpaged / (bucket, n_hit, sampled) paged."""
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        sampled = key[-1]
        if self.paged:

            def impl(params, tokens, length, page_ids, prefix_ids, pools,
                     samp, samp_pos):
                self.prefill_traces += 1
                with jax.named_scope("serving_prefill"), layers.serving_mode(
                    self.matmul_mode, kernel=self.matmul_kernel
                ):
                    logits, new_pools = T.prefill_into_pages(
                        params, tokens, self.cfg, pools, page_ids,
                        length=length, prefix_ids=prefix_ids,
                    )
                finite = jnp.all(jnp.isfinite(logits), axis=-1)
                if sampled:
                    nxt = sampling_mod.sample_tokens(logits, samp, samp_pos)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return nxt, finite, new_pools

        else:

            def impl(params, tokens, length, samp):
                self.prefill_traces += 1
                with jax.named_scope("serving_prefill"), layers.serving_mode(
                    self.matmul_mode, kernel=self.matmul_kernel
                ):
                    logits, scratch = T.prefill_with_cache(
                        params, tokens, self.cfg, self.max_len,
                        length=length, cache_dtype=jnp.float32,
                    )
                finite = jnp.all(jnp.isfinite(logits), axis=-1)
                if sampled:
                    nxt = sampling_mod.sample_tokens(logits, samp, length - 1)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return nxt, finite, scratch

        fn = jax.jit(impl)
        self._prefill_cache[key] = fn
        return fn

    def _prefill_chunk_fn(self, key) -> Callable:
        """Budgeted-chunk prefill jit. key: (token bucket, prefix pad,
        sampled) — the pad (pages when paged, cache rows when not) is the
        pow2-rounded size of the already-prefilled prefix; the *real*
        prefix length is traced, so every chunk whose prefix rounds into
        the same bucket reuses one trace instead of compiling per prefix
        size (the monolithic ``_prefill_fn`` keys on the exact hit count)."""
        fn = self._chunk_cache.get(key)
        if fn is not None:
            return fn
        sampled = key[-1]
        if self.paged:

            def impl(params, tokens, length, page_ids, prefix_ids, prefix_len,
                     pools, samp, samp_pos):
                self.prefill_traces += 1
                with jax.named_scope(
                    "serving_prefill_chunk"
                ), layers.serving_mode(
                    self.matmul_mode, kernel=self.matmul_kernel
                ):
                    logits, new_pools = T.prefill_into_pages(
                        params, tokens, self.cfg, pools, page_ids,
                        length=length, prefix_ids=prefix_ids,
                        prefix_len=prefix_len,
                    )
                finite = jnp.all(jnp.isfinite(logits), axis=-1)
                if sampled:
                    nxt = sampling_mod.sample_tokens(logits, samp, samp_pos)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return nxt, finite, new_pools

        else:
            prefix_pad = key[1]

            def impl(params, tokens, length, start, scratch, samp, samp_pos):
                self.prefill_traces += 1
                with jax.named_scope(
                    "serving_prefill_chunk"
                ), layers.serving_mode(
                    self.matmul_mode, kernel=self.matmul_kernel
                ):
                    logits, new_scratch = T.prefill_chunk_with_cache(
                        params, tokens, self.cfg, scratch,
                        start=start, length=length, prefix_pad=prefix_pad,
                    )
                finite = jnp.all(jnp.isfinite(logits), axis=-1)
                if sampled:
                    nxt = sampling_mod.sample_tokens(logits, samp, samp_pos)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return nxt, finite, new_scratch

        fn = jax.jit(impl)
        self._chunk_cache[key] = fn
        return fn

    def _book_prefill(self, n_tokens: int, elapsed: float, traced: bool,
                      new_request: bool = True):
        if new_request:
            self.prefill_requests += 1
        self.prefill_tokens += n_tokens
        if traced:
            self.prefill_compile_s += elapsed  # first hit of a bucket/shape
        else:
            self.prefill_time_s += elapsed
            self.prefill_tokens_warm += n_tokens

    def _run_prefill(self, prompt: np.ndarray, sp: SamplingParams,
                     uid: int = -1):
        """Prompt -> (first generated token, finite flag, scratch caches).

        Attention archs (unpaged engines): chunked prefill — the padded
        prompt runs in ONE jitted call per request. SSM/hybrid archs:
        decode-step replay (one jitted call per token; exactly consistent
        with the decode path — including the sampled first token, whose key
        position ``n - 1`` matches the chunked path). ``finite`` is the
        nonfinite guard on the first-token logits (the replay path checks
        the final step only — an SSM NaN propagates through the state).
        """
        n = len(prompt)
        self._validate_prompt_len(n)  # backstop; submit() already rejected
        traces0 = self.prefill_traces + self.decode_traces
        t0 = time.perf_counter()
        if self.cfg.block in ("dense", "moe"):
            bucket = self._prefill_bucket(n)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = prompt
            nxt, finite, scratch = self._prefill_fn((bucket, not sp.greedy))(
                self.params, jnp.asarray(toks), jnp.asarray([n], jnp.int32),
                self._samp_one(sp),
            )
            self.prefill_calls += 1
            first = int(nxt[0])
        else:
            scratch = T.init_cache(self.cfg, 1, self.max_len, dtype=jnp.float32)
            tok = jnp.asarray(prompt, jnp.int32)[None, :]
            samp1 = self._samp_one(sp)
            zero_fault = jnp.zeros((1,), jnp.float32)
            nxt = finite = None
            for i in range(tok.shape[1]):
                nxt, finite, scratch = self._decode(
                    self.params, scratch, tok[:, i : i + 1], samp1, zero_fault,
                    sampled=not sp.greedy,
                )
                self.prefill_calls += 1
            first = int(nxt[0, 0])
        elapsed = time.perf_counter() - t0
        traced = self.prefill_traces + self.decode_traces > traces0
        self._book_prefill(n, elapsed, traced)
        if self.trace is not None:
            self.trace.emit("prefill", track=uid, ts=t0, dur=elapsed,
                            step=self.steps, tokens=n, traced=traced)
        return first, bool(finite[0]), scratch

    def _run_prefill_paged(
        self, suffix: np.ndarray, hit_ids: List[int], new_ids: List[int],
        sp: SamplingParams, n_total: int, uid: int = -1,
    ) -> Tuple[int, bool]:
        """Suffix-only prefill, writing K/V straight into the page pool.

        ONE jitted call per request; prefix pages (``hit_ids``) are gathered
        read-only inside the call, so a full-prefix hit prefills only the
        suffix. ``n_total`` is the full prompt length — the sampled first
        token's key position (``n_total - 1``) must not depend on how much
        prefix the cache happened to hit. Returns ``(first generated token,
        finite flag)``.
        """
        m = len(suffix)  # >= 1: admission caps prefix hits at (n-1)//page_size
        bucket = self._prefill_bucket(m)
        nb = bucket // self.page_size
        ids = np.full((nb,), kvc.TRASH_PAGE, np.int32)
        k = min(nb, len(new_ids))
        ids[:k] = new_ids[:k]
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :m] = suffix
        pools = [layer["attn"] for layer in self.caches["layers"]]
        traces0 = self.prefill_traces
        t0 = time.perf_counter()
        nxt, finite, new_pools = self._prefill_fn(
            (bucket, len(hit_ids), not sp.greedy)
        )(
            self.params,
            jnp.asarray(toks),
            jnp.asarray([m], jnp.int32),
            jnp.asarray(ids),
            jnp.asarray(hit_ids, jnp.int32),
            pools,
            self._samp_one(sp),
            jnp.asarray([n_total - 1], jnp.int32),
        )
        self.prefill_calls += 1
        first = int(nxt[0])
        self.caches["layers"] = [{"attn": p} for p in new_pools]
        elapsed = time.perf_counter() - t0
        traced = self.prefill_traces > traces0
        self._book_prefill(m, elapsed, traced)
        if self.trace is not None:
            self.trace.emit("prefill", track=uid, ts=t0, dur=elapsed,
                            step=self.steps, tokens=m, traced=traced)
        return first, bool(finite[0])

    def _replay_fn(self, bucket: int) -> Callable:
        """b=1 multi-token decode over the page pool: the preemption-resume
        recompute path. Runs the committed output tokens through
        ``decode_tokens`` — the *decode-path* numerics — so every K/V row it
        writes is bit-identical to what the uninterrupted run wrote (the
        same invariant the speculative verify step relies on). The logits
        are discarded (DCE'd out of the trace): resume already knows every
        committed token; only the cache rows matter."""
        fn = self._replay_cache.get(bucket)
        if fn is not None:
            return fn

        def impl(params, pools, table1, pos1, tokens):
            self.decode_traces += 1  # python side effect: bumps only tracing
            caches = {
                "layers": [{"attn": p} for p in pools],
                "table": table1,
                "pos": pos1,
            }
            with jax.named_scope("serving_replay"), layers.serving_mode(
                self.matmul_mode, kernel=self.matmul_kernel
            ):
                _, new_caches = T.decode_tokens(
                    params, tokens, caches, self.cfg,
                    attn_kernel=self.attn_kernel,
                )
            return [layer["attn"] for layer in new_caches["layers"]]

        fn = jax.jit(impl)
        self._replay_cache[bucket] = fn
        return fn

    def _run_replay(self, slot_idx: int, tokens: np.ndarray, start: int) -> None:
        """Write decode-path K/V for positions ``start .. start+len(tokens)-1``
        of lane ``slot_idx`` (whose table row must already be set). Padded
        bucket tails write past the committed position — invisible to every
        read and overwritten in place later, exactly like a rejected
        speculative window."""
        if len(tokens) == 0:
            return
        bucket = 8
        while bucket < len(tokens):
            bucket *= 2
        bucket = min(bucket, self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, : len(tokens)] = tokens
        table1 = self.caches["table"][slot_idx : slot_idx + 1]
        pos1 = jnp.asarray([start], jnp.int32)
        pools = [layer["attn"] for layer in self.caches["layers"]]
        traces0 = self.decode_traces
        t0 = time.perf_counter()
        new_pools = self._replay_fn(bucket)(
            self.params, pools, table1, pos1, jnp.asarray(toks)
        )
        jax.block_until_ready(new_pools)
        elapsed = time.perf_counter() - t0
        self.caches["layers"] = [{"attn": p} for p in new_pools]
        if self.decode_traces > traces0:
            self.decode_compile_s += elapsed
        else:
            self.decode_time_s += elapsed

    def _finish_first_token(self, req: Request, first: int) -> bool:
        """Book the prefill-produced token; True if the request is already
        done (immediate eos, or a 1-token budget) and must not take a lane —
        the old engine appended it unchecked, so an immediate-eos request
        still burned ``max_new_tokens - 1`` decode steps (and its pages)."""
        now = time.perf_counter()
        req.t_first_token = now
        req.output.append(first)
        req.t_tokens.append(now)
        self._hist_ttft.observe(now - req.t_submit)
        if self.trace is not None:
            self.trace.emit("first_token", track=req.uid, step=self.steps)
        if req.eos_id is not None and first == req.eos_id:
            req.finish_reason = "eos"
        elif req.max_new_tokens <= 1:
            req.finish_reason = "length"
        else:
            return False
        req.t_done = time.perf_counter()
        self.done.append(req)
        self._book_terminal(req)
        return True

    def _book_terminal(self, req: Request) -> None:
        """Registry/trace booking for one terminal request — called exactly
        once wherever a request leaves the engine with ``t_done`` stamped
        (shed-at-submit excepted: those never entered and emit their own
        ``shed`` instant). Successful terminals book the end-to-end latency
        histogram; every terminal emits a ``retire`` span instant."""
        if req.finish_reason in ("eos", "length"):
            self.completed += 1
            if req.t_done and req.t_submit:
                self._hist_latency.observe(req.t_done - req.t_submit)
        elif req.finish_reason == "cancelled":
            self.cancelled += 1
        if self.trace is not None:
            self.trace.emit("retire", track=req.uid, step=self.steps,
                            finish_reason=req.finish_reason)

    def _install(self, slot_idx: int, req: Request) -> bool:
        """Admit ``req`` into lane ``slot_idx``. Returns False — leaving the
        request queued — only when the page pool can't hold it (backpressure);
        the lane stays free if the request finishes at its first token."""
        if self.chunked and not req.output:
            # Budgeted prefill: reserve resources only — the scheduler's
            # chunk plan runs the prompt through the step loop. Requests
            # carrying committed output (decode-phase preemptees) resume
            # through the replay path below.
            return self._install_chunked(slot_idx, req)
        if self.paged:
            return self._install_paged(slot_idx, req)
        sp = req.sampling or _GREEDY
        first, finite, scratch = self._run_prefill(
            np.asarray(req.prompt, np.int64), sp, uid=req.uid
        )
        if not finite:
            self._quarantine(req)
            return True
        if self._finish_first_token(req, first):
            return True
        self._adopt_scratch(slot_idx, scratch)
        self.tokens = self.tokens.at[slot_idx, 0].set(first)
        self.slots[slot_idx] = _Slot(
            req=req, remaining=req.max_new_tokens - 1, seq=self._install_seq
        )
        self._install_seq += 1
        self._set_lane_sampling(slot_idx, sp)
        return True

    def _adopt_scratch(self, slot_idx: int, scratch) -> None:
        """Copy a b=1 prefill scratch cache into row ``slot_idx`` of the
        engine caches (KV layouts differ per block type; tree_map handles
        every leaf uniformly on the batch axis 0, except scalars). The
        per-slot position resumes exactly at the scratch position; other
        slots are untouched (mixed-length admission is exact)."""

        def put(dst, src):
            if getattr(dst, "ndim", 0) == 0:
                return dst
            return dst.at[slot_idx : slot_idx + 1].set(src)

        eng_layers = self.caches["layers"]
        scr_layers = scratch["layers"]
        for li in range(len(eng_layers)):
            eng_layers[li] = jax.tree.map(put, eng_layers[li], scr_layers[li])
        self.caches["pos"] = self.caches["pos"].at[slot_idx].set(scratch["pos"][0])

    def _need_install(self, n_committed: int, need_total: int) -> int:
        """Pages granted at install time: the full worst-case reservation
        under ``reserve`` admission, or just enough to hold the committed
        context plus headroom under ``optimistic`` (later pages are grown
        per decode step, preempting the youngest lane on exhaustion)."""
        if self.admission != "optimistic":
            return need_total
        return min(
            kvc.pages_needed(n_committed, self.page_size)
            + self.config.admission_headroom,
            need_total,
        )

    def _install_paged(self, slot_idx: int, req: Request) -> bool:
        if req.output:
            return self._resume_paged(slot_idx, req)
        prompt = np.asarray(req.prompt, np.int64)
        n = len(prompt)
        self._validate_prompt_len(n)
        sp = req.sampling or _GREEDY
        ps = self.page_size
        need_total = min(
            kvc.pages_needed(n + req.max_new_tokens, ps), self.max_pages_per_seq
        )
        need_install = self._need_install(n, need_total)
        # Cap prefix hits so the suffix keeps >= 1 token (the prefill must
        # still produce the first-token logits).
        max_hit = (n - 1) // ps
        if self.allocator.available() < need_install - max_hit:
            return False  # can't fit even with a full prefix hit: fail fast
            # before the O(prompt) hash work (a queued request retries every
            # engine step while the pool drains)
        hit_ids, keys = self.allocator.match_prefix(prompt, max_hit)
        need_new = need_install - len(hit_ids)
        if self.allocator.available() < need_new:
            self.allocator.release(hit_ids)  # un-retain; stay queued
            return False
        self.allocator.note_prefix_stats(len(hit_ids), n // ps)
        if self.trace is not None:
            self.trace.emit("prefix_hit" if hit_ids else "prefix_miss",
                            track=req.uid, step=self.steps,
                            pages=len(hit_ids))
        new_ids = self.allocator.alloc(need_new)
        row_ids = hit_ids + new_ids
        n_hit = len(hit_ids) * ps

        first, finite = self._run_prefill_paged(
            prompt[n_hit:], hit_ids, new_ids, sp, n, uid=req.uid
        )
        if not finite:
            self.allocator.release(row_ids)
            self._quarantine(req)
            return True
        # Publish the freshly written *full* prompt pages (decode never
        # touches them — it appends past the prompt — so sharing is safe).
        for j in range(len(hit_ids), n // ps):
            self.allocator.register(keys[j], row_ids[j])

        if self._finish_first_token(req, first):
            self.allocator.release(row_ids)  # registered pages stay hit-able
            return True

        row = np.full((self.max_pages_per_seq,), kvc.TRASH_PAGE, np.int32)
        row[: len(row_ids)] = row_ids
        self.caches["table"] = self.caches["table"].at[slot_idx].set(jnp.asarray(row))
        self.caches["pos"] = self.caches["pos"].at[slot_idx].set(n)
        self.tokens = self.tokens.at[slot_idx, 0].set(first)
        self.slots[slot_idx] = _Slot(
            req=req, remaining=req.max_new_tokens - 1, pages=row_ids,
            seq=self._install_seq,
        )
        self._install_seq += 1
        self._set_lane_sampling(slot_idx, sp)
        return True

    def _resume_paged(self, slot_idx: int, req: Request) -> bool:
        """Re-install a preempted request (``req.output`` holds its committed
        tokens) with bit-exact recompute:

        * full pages of the committed context (prompt + output, registered
          at preemption) come back as prefix hits — their rows are the
          *original* bits, untouched;
        * a prompt remainder past the hits re-runs the same suffix prefill
          path as a fresh install;
        * committed output tokens past the prompt replay through the decode
          path (:meth:`_run_replay`) — decode-path K/V is bit-identical to
          what the uninterrupted run wrote (the speculative-verify
          invariant), so the continuation decodes over an identical cache
          and the greedy stream is token-for-token the uninterrupted one.
        """
        prompt = np.asarray(req.prompt, np.int64)
        n = len(prompt)
        m = len(req.output)
        sp = req.sampling or _GREEDY
        ps = self.page_size
        pos = n + m - 1  # committed position: K/V must exist below it
        ctx = np.concatenate([prompt, np.asarray(req.output, np.int64)])
        need_total = min(
            kvc.pages_needed(n + req.max_new_tokens, ps), self.max_pages_per_seq
        )
        need_install = self._need_install(pos + 1, need_total)
        max_hit = pos // ps  # every full committed page is reusable: resume
        # needs no first-token logits (the committed tokens are known)
        if self.allocator.available() < need_install - max_hit:
            return False
        hit_ids, keys = self.allocator.match_prefix(ctx[:pos], max_hit)
        need_new = need_install - len(hit_ids)
        if self.allocator.available() < need_new:
            self.allocator.release(hit_ids)
            return False
        new_ids = self.allocator.alloc(need_new)
        row_ids = hit_ids + new_ids
        h = len(hit_ids) * ps  # committed tokens covered by hits
        if self.trace is not None:
            self.trace.emit("prefix_hit" if hit_ids else "prefix_miss",
                            track=req.uid, step=self.steps,
                            pages=len(hit_ids))

        if h < n:
            # Hits stopped inside the prompt: re-prefill the remainder the
            # same way a fresh install would (the first token it produces is
            # already committed — discard it; a nonfinite result quarantines
            # exactly like a fresh prefill).
            _, finite = self._run_prefill_paged(
                prompt[h:], hit_ids, new_ids, sp, n, uid=req.uid
            )
            if not finite:
                self.allocator.release(row_ids)
                self._quarantine(req)
                return True

        # Table row first: the replay decodes through it.
        row = np.full((self.max_pages_per_seq,), kvc.TRASH_PAGE, np.int32)
        row[: len(row_ids)] = row_ids
        self.caches["table"] = self.caches["table"].at[slot_idx].set(jnp.asarray(row))
        start = max(h, n)
        self._run_replay(slot_idx, ctx[start:pos], start)
        # (Re-)publish the full committed pages this resume rewrote; pages
        # still registered from preemption win (first-writer-wins no-op).
        for j in range(len(hit_ids), pos // ps):
            self.allocator.register(keys[j], row_ids[j])

        self.caches["pos"] = self.caches["pos"].at[slot_idx].set(pos)
        self.tokens = self.tokens.at[slot_idx, 0].set(int(req.output[-1]))
        self.slots[slot_idx] = _Slot(
            req=req, remaining=req.max_new_tokens - m, pages=row_ids,
            seq=self._install_seq,
        )
        self._install_seq += 1
        self._set_lane_sampling(slot_idx, sp)
        return True

    # ------------------------------------------------- chunked prefill (PR 7)

    def _is_resume(self, req: Request) -> bool:
        """True for requests re-queued by preemption: decode-phase victims
        carry committed output; mid-prefill victims have no output yet, so
        the engine remembers their uids explicitly."""
        return bool(req.output) or req.uid in self._preempted_uids

    def _install_chunked(self, slot_idx: int, req: Request) -> bool:
        """Budgeted admission: reserve the lane (and, when paged, its page
        worst case) *without running any prefill compute* — the scheduler's
        per-step chunk plan (:meth:`_run_chunk_plan`) drains the prompt
        through the engine step loop. The lane is decode-invisible until
        its final chunk: trash table row, position 0, greedy sampling."""
        prompt = np.asarray(req.prompt, np.int64)
        n = len(prompt)
        self._validate_prompt_len(n)
        if self.paged:
            ps = self.page_size
            need_total = min(
                kvc.pages_needed(n + req.max_new_tokens, ps),
                self.max_pages_per_seq,
            )
            need_install = self._need_install(n, need_total)
            max_hit = (n - 1) // ps  # the final chunk must keep >= 1 token
            if self.allocator.available() < need_install - max_hit:
                return False  # fail fast before the O(prompt) hash work
            hit_ids, keys = self.allocator.match_prefix(prompt, max_hit)
            need_new = need_install - len(hit_ids)
            if self.allocator.available() < need_new:
                self.allocator.release(hit_ids)  # un-retain; stay queued
                return False
            self.allocator.note_prefix_stats(len(hit_ids), n // ps)
            if self.trace is not None:
                self.trace.emit("prefix_hit" if hit_ids else "prefix_miss",
                                track=req.uid, step=self.steps,
                                pages=len(hit_ids))
            row_ids = hit_ids + self.allocator.alloc(need_new)
            self.slots[slot_idx] = _Slot(
                req=req, remaining=req.max_new_tokens, pages=row_ids,
                seq=self._install_seq, prefill_pos=len(hit_ids) * ps,
                keys=keys,
            )
        else:
            scratch = T.init_cache(self.cfg, 1, self.max_len, dtype=jnp.float32)
            self.slots[slot_idx] = _Slot(
                req=req, remaining=req.max_new_tokens, seq=self._install_seq,
                prefill_pos=0, scratch=scratch,
            )
        self._install_seq += 1
        self.prefill_requests += 1  # chunks book new_request=False
        return True

    def _run_chunk_plan(self) -> None:
        """Run this step's prefill chunk grants (at most ``prefill_budget``
        tokens total) over every mid-prefill lane."""
        lanes = [
            (i, len(s.req.prompt) - s.prefill_pos, s.seq)
            for i, s in enumerate(self.slots)
            if s.prefilling
        ]
        if not lanes:
            return
        for slot_idx, grant in self._sched.plan_chunks(lanes):
            if not self.slots[slot_idx].prefilling:
                continue  # quarantined by an earlier chunk this step
            if self.paged:
                self._run_chunk_paged(slot_idx, grant)
            elif self.cfg.block in ("dense", "moe"):
                self._run_chunk_unpaged(slot_idx, grant)
            else:
                self._run_chunk_replay(slot_idx, grant)

    def _run_chunk_paged(self, slot_idx: int, grant: int) -> None:
        """One chunk of lane ``slot_idx``'s prompt straight into its pages.

        ``prefill_pos`` is page-aligned for every non-final chunk (install
        starts at a page boundary; intermediate grants are whole chunks and
        ``chunk_size % page_size == 0``), so the chunk's pages are exactly
        ``pages[start/ps : ...]`` and its prefix is exactly ``pages[:start/ps]``
        — padded to a pow2 page count with the real token length traced, so
        chunks share jit traces across prefix sizes."""
        slot = self.slots[slot_idx]
        req = slot.req
        prompt = np.asarray(req.prompt, np.int64)
        n = len(prompt)
        start = slot.prefill_pos
        end = start + grant
        final = end >= n
        sp = req.sampling or _GREEDY
        ps = self.page_size
        bucket = self._prefill_bucket(grant)
        nb = bucket // ps
        p0 = start // ps
        ids = np.full((nb,), kvc.TRASH_PAGE, np.int32)
        have = slot.pages[p0 : p0 + nb]
        ids[: len(have)] = have  # bucket pads past the need write to trash
        pp = 0
        if p0:
            pp = 1
            while pp < p0:
                pp *= 2
        pref = np.full((pp,), kvc.TRASH_PAGE, np.int32)
        pref[:p0] = slot.pages[:p0]
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :grant] = prompt[start:end]
        pools = [layer["attn"] for layer in self.caches["layers"]]
        traces0 = self.prefill_traces
        t0 = time.perf_counter()
        nxt, finite, new_pools = self._prefill_chunk_fn(
            (bucket, pp, not sp.greedy)
        )(
            self.params,
            jnp.asarray(toks),
            jnp.asarray([grant], jnp.int32),
            jnp.asarray(ids),
            jnp.asarray(pref),
            jnp.asarray(start, jnp.int32),
            pools,
            self._samp_one(sp),
            jnp.asarray([n - 1], jnp.int32),
        )
        self.prefill_calls += 1
        finite = bool(finite[0])
        self.caches["layers"] = [{"attn": p} for p in new_pools]
        elapsed = time.perf_counter() - t0
        self._book_prefill(
            grant, elapsed, self.prefill_traces > traces0, new_request=False
        )
        if self.trace is not None:
            self.trace.emit("prefill_chunk", track=req.uid, ts=t0,
                            dur=elapsed, step=self.steps, start=start,
                            grant=grant, final=final)
        if not finite:
            self.allocator.release(slot.pages)
            self.slots[slot_idx] = _Slot()
            self._quarantine(req)
            return
        # Publish the full prompt pages this chunk completed — preemption
        # of a half-prefilled lane then resumes from the prefix cache.
        for j in range(p0, min(end, n) // ps):
            self.allocator.register(slot.keys[j], slot.pages[j])
        slot.prefill_pos = end
        if not final:
            return
        first = int(nxt[0])
        if self._finish_first_token(req, first):
            self.allocator.release(slot.pages)  # registered stay hit-able
            self.slots[slot_idx] = _Slot()
            return
        row = np.full((self.max_pages_per_seq,), kvc.TRASH_PAGE, np.int32)
        row[: len(slot.pages)] = slot.pages
        self.caches["table"] = (
            self.caches["table"].at[slot_idx].set(jnp.asarray(row))
        )
        self.caches["pos"] = self.caches["pos"].at[slot_idx].set(n)
        self.tokens = self.tokens.at[slot_idx, 0].set(first)
        slot.remaining = req.max_new_tokens - 1
        slot.prefill_pos = -1
        slot.keys = []
        self._set_lane_sampling(slot_idx, sp)

    def _run_chunk_unpaged(self, slot_idx: int, grant: int) -> None:
        """Chunk into the lane's b=1 scratch cache (attention archs); the
        finished scratch is adopted into the engine caches at finalize —
        the chunked twin of the monolithic unpaged install."""
        slot = self.slots[slot_idx]
        req = slot.req
        prompt = np.asarray(req.prompt, np.int64)
        n = len(prompt)
        start = slot.prefill_pos
        end = start + grant
        sp = req.sampling or _GREEDY
        bucket = self._prefill_bucket(grant)
        prefix_pad = 0
        if start:
            prefix_pad = 8
            while prefix_pad < start:
                prefix_pad *= 2
            prefix_pad = min(prefix_pad, self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :grant] = prompt[start:end]
        traces0 = self.prefill_traces
        t0 = time.perf_counter()
        nxt, finite, slot.scratch = self._prefill_chunk_fn(
            (bucket, prefix_pad, not sp.greedy)
        )(
            self.params,
            jnp.asarray(toks),
            jnp.asarray([grant], jnp.int32),
            jnp.asarray(start, jnp.int32),
            slot.scratch,
            self._samp_one(sp),
            jnp.asarray([n - 1], jnp.int32),
        )
        self.prefill_calls += 1
        elapsed = time.perf_counter() - t0
        self._book_prefill(
            grant, elapsed, self.prefill_traces > traces0, new_request=False
        )
        if self.trace is not None:
            self.trace.emit("prefill_chunk", track=req.uid, ts=t0,
                            dur=elapsed, step=self.steps, start=start,
                            grant=grant, final=end >= n)
        if end >= n:
            self._finalize_unpaged(slot_idx, int(nxt[0]), bool(finite[0]))
        else:
            if not bool(finite[0]):
                self.slots[slot_idx] = _Slot()
                self._quarantine(req)
                return
            slot.prefill_pos = end

    def _run_chunk_replay(self, slot_idx: int, grant: int) -> None:
        """SSM/hybrid chunk: the monolithic path replays the prompt through
        the decode step one token at a time, so a chunk is just a bounded
        run of the same loop on the lane's scratch — identical calls in
        identical order, only interleaved with decode steps."""
        slot = self.slots[slot_idx]
        req = slot.req
        prompt = np.asarray(req.prompt, np.int64)
        n = len(prompt)
        start = slot.prefill_pos
        end = start + grant
        sp = req.sampling or _GREEDY
        samp1 = self._samp_one(sp)
        zero_fault = jnp.zeros((1,), jnp.float32)
        tok = jnp.asarray(prompt[start:end], jnp.int32)[None, :]
        traces0 = self.prefill_traces + self.decode_traces
        t0 = time.perf_counter()
        nxt = finite = None
        for i in range(grant):
            nxt, finite, slot.scratch = self._decode(
                self.params, slot.scratch, tok[:, i : i + 1], samp1,
                zero_fault, sampled=not sp.greedy,
            )
            self.prefill_calls += 1
        elapsed = time.perf_counter() - t0
        traced = self.prefill_traces + self.decode_traces > traces0
        self._book_prefill(grant, elapsed, traced, new_request=False)
        if self.trace is not None:
            self.trace.emit("prefill_chunk", track=req.uid, ts=t0,
                            dur=elapsed, step=self.steps, start=start,
                            grant=grant, final=end >= n)
        if end >= n:
            # The monolithic replay checks the final step only (an SSM NaN
            # propagates through the state) — keep that contract.
            self._finalize_unpaged(slot_idx, int(nxt[0, 0]), bool(finite[0]))
        else:
            slot.prefill_pos = end

    def _finalize_unpaged(self, slot_idx: int, first: int, finite: bool) -> None:
        """Last chunk done: adopt the scratch into the engine caches and
        flip the lane to decode phase (or finish/quarantine without ever
        occupying a decode lane — same contract as monolithic install)."""
        slot = self.slots[slot_idx]
        req = slot.req
        if not finite:
            self.slots[slot_idx] = _Slot()
            self._quarantine(req)
            return
        if self._finish_first_token(req, first):
            self.slots[slot_idx] = _Slot()
            return
        self._adopt_scratch(slot_idx, slot.scratch)
        self.tokens = self.tokens.at[slot_idx, 0].set(first)
        slot.scratch = None
        slot.remaining = req.max_new_tokens - 1
        slot.prefill_pos = -1
        self._set_lane_sampling(slot_idx, req.sampling or _GREEDY)

    def _retire(self, slot_idx: int) -> None:
        slot = self.slots[slot_idx]
        slot.req.t_done = time.perf_counter()
        if slot.req.finish_reason is None:
            slot.req.finish_reason = "length"
        if slot.req.finish_reason in ("eos", "length"):
            self._fault_streak = 0  # a healthy completion clears the streak
        self.done.append(slot.req)
        self._book_terminal(slot.req)
        if self.paged:
            # Reclaim pages and point the lane at the trash page so its dead
            # writes can never land in a page the allocator hands out again.
            # Retirement is the keep_tokens=0 case of the page-aware truncate
            # (the speculative rollback path — one release policy for both;
            # cancel() rides the same path, so a cancelled lane's pages are
            # reclaimed exactly like a drained one's).
            self.allocator.truncate(slot.pages, 0)
            self.caches["table"] = (
                self.caches["table"].at[slot_idx].set(kvc.TRASH_PAGE)
            )
            self.caches["pos"] = self.caches["pos"].at[slot_idx].set(0)
        self.slots[slot_idx] = _Slot()
        self._set_lane_sampling(slot_idx, _GREEDY)

    # --------------------------------------------------- overload machinery

    def _preempt(self, slot_idx: int) -> None:
        """Evict lane ``slot_idx`` under pool pressure and requeue its
        request at the queue *head* for bit-exact recompute
        (:meth:`_resume_paged`). Every full page of the committed context is
        registered in the prefix cache first, so the released pages drop to
        the LRU still hit-able — the resume usually re-allocates nothing but
        the partial tail page."""
        slot = self.slots[slot_idx]
        req = slot.req
        if slot.prefilling:
            # Half-prefilled victim: every completed full prompt page was
            # already registered by its chunk, so the release keeps them
            # hit-able and the resume re-prefills only what the chunks
            # hadn't finished. The lane never joined decode — its table
            # row is still trash, its position still 0.
            self.allocator.truncate(slot.pages, 0)
            self.slots[slot_idx] = _Slot()
            self._preempted_uids.add(req.uid)
            self.queue.appendleft(req)
            self.preempted += 1
            if self.trace is not None:
                self.trace.emit("preempt", track=req.uid, step=self.steps,
                                prefilling=True)
            return
        pos = len(req.prompt) + len(req.output) - 1
        ctx = list(req.prompt) + req.output
        keys = self.allocator.chain_keys(ctx, pos // self.page_size)
        for j, key in enumerate(keys):
            if j < len(slot.pages):
                self.allocator.register(key, slot.pages[j])
        self.allocator.truncate(slot.pages, 0)
        self.caches["table"] = (
            self.caches["table"].at[slot_idx].set(kvc.TRASH_PAGE)
        )
        self.caches["pos"] = self.caches["pos"].at[slot_idx].set(0)
        self.slots[slot_idx] = _Slot()
        self._set_lane_sampling(slot_idx, _GREEDY)
        self._preempted_uids.add(req.uid)
        self.queue.appendleft(req)
        self.preempted += 1
        if self.trace is not None:
            self.trace.emit("preempt", track=req.uid, step=self.steps,
                            prefilling=False, committed=len(req.output))

    def _grow_lane(self, slot_idx: int, delta: int, touched: Dict) -> None:
        """Grow lane ``slot_idx``'s page list to cover its next ``delta``
        positions, preempting the youngest active lane (possibly itself)
        whenever the pool comes up short. Terminates: each preemption frees
        >= 1 page, the oldest lane is never a victim while others are
        active, and a single lane's need never exceeds pool capacity
        (submit() rejects those outright)."""
        slot = self.slots[slot_idx]
        req = slot.req
        pos = len(req.prompt) + len(req.output) - 1
        need = min(
            kvc.pages_needed(pos + delta, self.page_size),
            self.max_pages_per_seq,
        )
        while self.slots[slot_idx].req is req and len(slot.pages) < need:
            short = need - len(slot.pages)
            if self.allocator.available() < short:
                victim = max(
                    (i for i, s in enumerate(self.slots) if s.req is not None),
                    key=lambda i: self.slots[i].seq,
                )
                self._preempt(victim)
                continue
            slot.pages.extend(self.allocator.alloc(short))
            touched[slot_idx] = slot.pages

    def _ensure_capacity(self, delta: int) -> None:
        """Optimistic admission's growth phase, run before every decode /
        speculation round: each active lane (oldest first — the oldest can
        never be starved by younger arrivals) gets pages for its next
        ``delta`` positions. Reserve admission is a no-op by construction
        (install granted the worst case)."""
        if not self.paged or self.admission != "optimistic":
            return
        touched: Dict[int, List[int]] = {}
        # Mid-prefill lanes don't grow: install reserved their full prompt
        # plus headroom, and they write no decode positions yet. They stay
        # preemption *victims* (youngest-first) in _grow_lane, though.
        order = sorted(
            (
                i for i, s in enumerate(self.slots)
                if s.req is not None and not s.prefilling
            ),
            key=lambda i: self.slots[i].seq,
        )
        for i in order:
            s = self.slots[i]
            if s.req is not None and not s.prefilling:  # not since preempted
                self._grow_lane(i, delta, touched)
        for i, pages in touched.items():
            if self.slots[i].req is None:
                continue  # grew, then lost to an older lane's growth
            row = np.full((self.max_pages_per_seq,), kvc.TRASH_PAGE, np.int32)
            row[: len(pages)] = pages
            self.caches["table"] = (
                self.caches["table"].at[i].set(jnp.asarray(row))
            )

    def _quarantine(self, req: Request) -> None:
        """Terminal-error a request whose logits went nonfinite (before it
        ever took a lane — the active-lane path retires through
        ``_retire`` with the reason pre-set)."""
        req.finish_reason = "error"
        req.t_done = time.perf_counter()
        self.done.append(req)
        self._book_terminal(req)
        self._note_fault(req)

    def _note_fault(self, req: Request) -> None:
        """Book one quarantined request. The streak counts consecutive
        quarantines with no healthy completion in between (``_retire``
        clears it on eos/length): three in a row on the fused pallas
        attention path triggers the automatic XLA fallback."""
        self.errors += 1
        self._fault_at.pop(req.uid, None)
        self._fault_streak += 1
        if self.trace is not None:
            self.trace.emit("quarantine", track=req.uid, step=self.steps,
                            streak=self._fault_streak)
        if self._fault_streak >= 3 and self.attn_kernel == "pallas":
            self._fallback_kernel()

    def _fallback_kernel(self) -> None:
        """Automatic degradation after repeated nonfinite faults on the
        fused pallas attention path: re-trace everything on the XLA
        formulation (bit-different but numerically robust) and keep
        serving. Counted in ``stats()["kernel_fallbacks"]``."""
        self.attn_kernel = "xla"
        self.kernel_fallbacks += 1
        self._fault_streak = 0
        if self.trace is not None:
            self.trace.emit("kernel_fallback", step=self.steps, kernel="xla")
        self._decode = jax.jit(self._decode_impl, static_argnames=("sampled",))
        self._prefill_cache.clear()
        self._replay_cache.clear()
        self._chunk_cache.clear()
        self._attn_probe_fn = None
        if self._spec is not None:
            old = self._spec
            self._spec = spec_mod.SpecDecoder(
                self.cfg, self.config.spec, self.matmul_mode,
                matmul_kernel=self.matmul_kernel, attn_kernel=self.attn_kernel,
            )
            self._spec.controller = old.controller
            self._spec.trace = self.trace
            for attr in (
                "rounds", "lane_rounds", "proposed", "accepted", "committed",
                "draft_time_s", "verify_time_s", "compile_s", "draft_traces",
                "verify_traces", "trace_step",
            ):
                setattr(self._spec, attr, getattr(old, attr))

    def inject_fault(self, uid: int, at_output_index: int) -> None:
        """Test hook: poison (NaN) the jitted step that would produce output
        token ``at_output_index`` (>= 1; index 0 comes from prefill) of
        request ``uid``. The fault flows through the same fused
        ``isfinite`` guard as a real numerical fault, so tests exercise the
        production quarantine path end to end."""
        self._fault_at[uid] = at_output_index

    def _fault_row(self, window: int = 1) -> np.ndarray:
        """Per-lane injection row for the next decode/verify step: NaN for
        lanes whose pending fault falls inside the step's output window
        (``window`` tokens for a speculative round), 0.0 otherwise."""
        fault = np.zeros((self.max_batch,), np.float32)
        for i, slot in enumerate(self.slots):
            r = slot.req
            if r is None or slot.prefilling:
                continue
            at = self._fault_at.get(r.uid)
            if at is not None and at < len(r.output) + window:
                fault[i] = np.nan
        return fault

    def _shed_expired(self) -> None:
        """Deadline policy, applied at the top of every step: queued
        requests past ``deadline_s`` shed before taking a lane; active
        lanes retire mid-decode keeping their partial output. Both end
        ``finish_reason="timeout"``."""
        now = time.perf_counter()

        def expired(r: Request) -> bool:
            return r.deadline_s is not None and now - r.t_submit > r.deadline_s

        for r in [r for r in self.queue if expired(r)]:
            self.queue.remove(r)
            r.finish_reason = "timeout"
            r.t_done = now
            self.done.append(r)
            self._book_terminal(r)
            self.timed_out += 1
            if self.trace is not None:
                self.trace.emit("shed", track=r.uid, step=self.steps,
                                where="queue_deadline")
        for i, slot in enumerate(self.slots):
            if slot.req is not None and expired(slot.req):
                slot.req.finish_reason = "timeout"
                self._retire(i)
                self.timed_out += 1

    # ------------------------------------------------------------------ API

    def _validate_prompt_len(self, n: int) -> None:
        if n == 0:
            raise ValueError("empty prompt: nothing to prefill")
        if n + 1 > self.max_len:
            raise ValueError(
                f"prompt length {n} needs at least one decode slot beyond it; "
                f"engine max_len is {self.max_len}"
            )

    def submit(self, req: Request):
        # Reject here, not at admission: a bad request raised mid-run would
        # abort the engine loop and strand every in-flight sequence — and a
        # request larger than the whole pool would deadlock the queue.
        self._validate_prompt_len(len(req.prompt))
        if req.sampling is not None and not isinstance(req.sampling, SamplingParams):
            raise TypeError(
                f"Request.sampling must be SamplingParams, got {type(req.sampling)}"
            )
        if self._spec is not None and len(req.prompt) + req.max_new_tokens > self.max_len:
            # Speculative windows write up to k positions past the committed
            # point; exactness needs every *committed* position to live in a
            # real cache slot, so the full budget must fit (plain decode
            # merely degrades to overwrite-last beyond max_len).
            raise ValueError(
                f"speculative engine: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) must fit max_len "
                f"({self.max_len})"
            )
        if self.paged:
            need = min(
                kvc.pages_needed(
                    len(req.prompt) + req.max_new_tokens, self.page_size
                ),
                self.max_pages_per_seq,
            )
            if need > self.allocator.capacity:
                raise ValueError(
                    f"request needs {need} pages; pool capacity is "
                    f"{self.allocator.capacity} (raise n_pages)"
                )
        if isinstance(req.uid, int):  # generate()'s auto-uids stay unique
            self._auto_uid = max(self._auto_uid, req.uid + 1)
        req.t_submit = time.perf_counter()
        if self.config.max_queue and len(self.queue) >= self.config.max_queue:
            # Load shedding: reject-at-submit so overload turns into a fast
            # typed error, not an unbounded queue. The request is terminal
            # (finish_reason/t_done set) so stream() yields its sentinel.
            req.finish_reason = "shed"
            req.t_done = req.t_submit
            self.shed += 1
            if self.trace is not None:
                self.trace.emit("shed", track=req.uid, step=self.steps,
                                where="queue_full")
            raise EngineOverloaded(
                f"queue full ({len(self.queue)}/{self.config.max_queue}): "
                f"request {req.uid} shed",
                queue_depth=len(self.queue),
                retry_after_hint_s=(
                    self._step_timer.percentile(50) * len(self.queue)
                ),
            )
        self.queue.append(req)

    def generate(
        self,
        prompt: Sequence[int],
        sampling: Optional[SamplingParams] = None,
        *,
        max_new_tokens: int = 32,
        eos_id: Optional[int] = None,
        uid: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ) -> Iterator[TokenEvent]:
        """Submit one request and stream its tokens as :class:`TokenEvent` s.

        The returned generator *drives the engine* (each ``next()`` runs
        engine steps until the request produces its next token), so tokens
        stream as they land — the first event arrives right after this
        request's prefill, not when the batch drains. Other in-flight
        requests keep decoding in the same steps: interleaving several
        ``generate`` iterators (or a background ``run()``) is the intended
        multi-client shape. ``cancel(uid)`` mid-iteration ends the stream
        with ``finish_reason="cancelled"``.

        A request the bounded queue sheds still streams: its one event is
        the ``finished=True, finish_reason="shed"`` sentinel (callers that
        want the typed :class:`EngineOverloaded` should ``submit()`` +
        ``stream()`` themselves).
        """
        if uid is None:
            uid = self._auto_uid  # submit() bumps past it
        req = Request(
            uid=uid, prompt=list(prompt), max_new_tokens=max_new_tokens,
            eos_id=eos_id, sampling=sampling, deadline_s=deadline_s,
        )
        try:
            self.submit(req)
        except EngineOverloaded:
            pass  # terminal "shed": stream() yields the sentinel and ends
        return self.stream(req)

    def stream(self, req: Request) -> Iterator[TokenEvent]:
        """Yield ``req``'s tokens as they are produced, stepping the engine
        as needed. ``req`` must already be submitted to this engine.

        The final event carries ``finished=True`` + ``finish_reason`` when
        the engine knew the outcome as it booked the token (eos, budget). A
        ``cancel()`` that lands *after* the last token was already yielded
        simply ends the stream — check ``req.finish_reason`` for the
        verdict (a queue-cancelled request yields no events at all).

        Requests that end without booking a final token — shed, timed out,
        or quarantined (``_SENTINEL_REASONS``) — get one synthetic
        ``finished=True`` sentinel event (``token=-1``) so a streaming
        caller can never hang on a request that silently left the queue."""
        seen = 0
        sent_final = False
        while True:
            while seen < len(req.output):
                last = req.t_done > 0.0 and seen == len(req.output) - 1
                sent_final = sent_final or last
                yield TokenEvent(
                    uid=req.uid,
                    token=req.output[seen],
                    index=seen,
                    t=req.t_tokens[seen],
                    finished=last,
                    finish_reason=req.finish_reason if last else None,
                )
                seen += 1
            if req.t_done > 0.0:
                if not sent_final and req.finish_reason in _SENTINEL_REASONS:
                    yield TokenEvent(
                        uid=req.uid, token=-1, index=len(req.output),
                        t=req.t_done, finished=True,
                        finish_reason=req.finish_reason,
                    )
                return  # finished (a queue-cancelled request yields nothing)
            # Re-check t_done before giving up on a drained engine: the step
            # above may itself have finished the request (deadline shed of
            # the last queued request drains the engine AND terminals it —
            # its sentinel must still go out).
            if not self.step() and not self.queue and req.t_done == 0.0:
                return  # engine drained without finishing the request

    def cancel(self, uid: int) -> bool:
        """Cancel a request mid-flight. Returns True if found.

        A queued request is removed before ever taking a lane; an active
        one retires immediately — its lane frees for the next admission and
        its pages are released through ``PageAllocator.truncate`` (the
        retirement path), leaving the allocator exactly as if the request
        had drained. Completed requests are not cancellable.
        """
        for r in self.queue:
            if r.uid == uid:
                self.queue.remove(r)
                r.finish_reason = "cancelled"
                r.t_done = time.perf_counter()
                self.done.append(r)
                self._book_terminal(r)
                return True
        for i, slot in enumerate(self.slots):
            if slot.req is not None and slot.req.uid == uid:
                slot.req.finish_reason = "cancelled"
                self._retire(i)
                return True
        return False

    def _admit(self):
        """Admission in scheduler order — resumes first, then requests past
        the aging bound, then policy order (``fifo`` reproduces the legacy
        submit-order admission exactly). Stops at the first request that
        doesn't fit: no head-of-line bypass — page exhaustion queues, it
        never crashes, and a short late arrival can't drain the pool out
        from under the blocked head."""
        if not self.queue:
            return
        ordered = self._sched.order_queue(
            list(self.queue), self.steps, self._is_resume
        )
        for req in ordered:
            free = next(
                (i for i, s in enumerate(self.slots) if s.req is None), None
            )
            if free is None:
                break
            # Capture before _install: monolithic prefill books the first
            # token into req.output, which would make every fresh admission
            # look like a resume after the fact.
            resumed = self._is_resume(req)
            t_install = time.perf_counter()
            if not self._install(free, req):
                break  # pool full: wait for pages to be reclaimed
            self.queue.remove(req)
            self._sched.note_admitted(req.uid)
            if self.trace is not None:
                # ts = pre-install instant, so the admit sorts ahead of the
                # prefill span _install just emitted.
                self.trace.emit(
                    "resume" if resumed else "admit",
                    track=req.uid, step=self.steps, ts=t_install,
                    queued_s=t_install - req.t_submit,
                )
            self._preempted_uids.discard(req.uid)
            if not req.t_admit:
                req.t_admit = time.perf_counter()
                self._hist_qwait.observe(req.t_admit - req.t_submit)

    def _spec_step(self):
        """One speculative engine iteration: draft k tokens per lane, verify
        all k+1 positions in ONE target step, commit each lane's accepted
        prefix (+ the target's correction/bonus token), roll back the rest.

        Every committed token is the *target's* greedy argmax — the committed
        stream is token-identical to plain greedy decode by construction; the
        draft only decides how many of those tokens one target step yields.
        """
        dec = self._spec
        # Optimistic growth BEFORE the position snapshots: a verify window
        # writes up to k+1 positions past each lane's committed point, and a
        # preemption during growth rewrites lane state the snapshots must
        # already reflect (a stale snapshot would "rewind" a preempted lane
        # back to life at round end).
        self._ensure_capacity(dec.controller.k + 1)
        if not any(s.req for s in self.slots):
            return True  # growth preempted every lane; re-admit next step
        pos0 = np.asarray(self.caches["pos"])
        tok0 = np.asarray(self.tokens)[:, 0]
        warm0 = dec.draft_time_s + dec.verify_time_s
        compile0 = dec.compile_s
        # Clamp the window to the largest remaining lane budget: drafts past
        # every budget can never commit (k == 0 degenerates to a plain decode
        # step through the verify jit when every lane needs exactly 1 token).
        k_want = min(
            dec.controller.k,
            max(0, max(s.remaining for s in self.slots if s.req) - 1),
        )
        fault = self._fault_row(window=k_want + 1)
        dec.trace_step = self.steps  # spec spans land on the engine lane
        greedy, drafts, finite, self.caches, k = dec.propose_and_verify(
            self.params, self.caches, self.tokens, k_want,
            fault=jnp.asarray(fault),
        )
        self.steps += 1
        now = time.perf_counter()
        new_pos = pos0.copy()
        next_tok = tok0.copy()
        round_committed = round_acc = round_prop = 0
        to_retire = []
        faulted: List[Request] = []
        for i, slot in enumerate(self.slots):
            if slot.req is None:
                continue  # idle lanes drafted/verified into their trash rows
            if not bool(finite[i]):
                # Nonfinite verify logits: quarantine the lane, commit
                # nothing (the whole window is suspect), leave its position
                # at the round start. Co-resident lanes are unaffected —
                # the guard is per lane.
                slot.req.finish_reason = "error"
                faulted.append(slot.req)
                to_retire.append(i)
                continue
            usable = min(k, slot.remaining - 1)  # drafts that could commit
            commit, n_acc = spec_mod.committed_tokens(drafts[i], greedy[i], k)
            used = 0
            done = False
            for t in commit:
                if slot.req.t_tokens:  # in-round gaps book as 0.0
                    self._hist_itl.observe(now - slot.req.t_tokens[-1])
                slot.req.output.append(int(t))
                slot.req.t_tokens.append(now)
                self.decoded_tokens += 1
                slot.remaining -= 1
                used += 1
                if slot.req.eos_id is not None and int(t) == slot.req.eos_id:
                    slot.req.finish_reason = "eos"
                    done = True  # eos mid-window: drop the tail
                    break
                if slot.remaining <= 0:
                    slot.req.finish_reason = "length"
                    done = True  # budget mid-window: drop the tail
                    break
            # Acceptance is booked over the drafts that could possibly commit
            # — window tails past a lane's budget measure nothing.
            dec.book_lane(min(n_acc, usable), used, usable)
            round_committed += used
            round_acc += min(n_acc, usable)
            round_prop += usable
            # Page-aware rollback: rewind this lane to its committed position
            # (stale K/V past it is invisible and overwritten in place; the
            # lane's pages all stay owned — only retirement releases them).
            new_pos[i] = pos0[i] + used
            next_tok[i] = commit[used - 1]
            if done:
                to_retire.append(i)
        dec.end_round(round_acc, round_prop)
        self.caches["pos"] = kvc.rewind_positions(self.caches["pos"], new_pos)
        self.tokens = jnp.asarray(next_tok, jnp.int32)[:, None]
        for i in to_retire:
            self._retire(i)
        for r in faulted:
            self._note_fault(r)  # after retirement: may rebuild the decoder
        # Mirror into the engine's warm decode counters so decode_tok_per_s
        # stays the end-to-end generation throughput under speculation.
        warm_delta = (dec.draft_time_s + dec.verify_time_s) - warm0
        if warm_delta > 0:
            self.decode_time_s += warm_delta
            self.decode_tokens_warm += round_committed
        else:
            self.decode_compile_s += dec.compile_s - compile0
        return True

    def step(self):
        """One engine iteration: shed expired deadlines, admit from queue,
        grow optimistic lanes (preempting on exhaustion), decode one token
        for all active slots (or run one speculation round), retire finished
        requests. Wrapped by the serving watchdog: every call is timed into
        the step-time percentiles (and the ``engine_step_seconds``
        histogram) and heartbeats ``heartbeat_path`` (throttled by
        ``heartbeat_interval_s``; the drain's final beat always lands).
        With tracing on, the whole iteration lands as a ``step`` span on
        the engine lane; with ``drift_every`` set, every Nth productive
        step samples the quant-drift monitor *after* the timed window."""
        t0 = time.perf_counter()
        self._step_timer.start()
        try:
            out = self._step_impl()
        finally:
            self._hist_step.observe(self._step_timer.stop())
        if self.trace is not None:
            self.trace.emit(
                "step", ts=t0, dur=time.perf_counter() - t0, step=self.steps,
                active=sum(1 for s in self.slots if s.req is not None),
                queued=len(self.queue),
            )
        if self._heartbeat is not None:
            self._heartbeat.beat(
                self.steps,
                {"active": sum(1 for s in self.slots if s.req is not None),
                 "queued": len(self.queue)},
                force=not out and not self.queue,
            )
        if (
            self._drift is not None
            and out
            and self.steps != self._drift_last_step
            and self.steps % self.config.drift_every == 0
        ):
            self._drift_last_step = self.steps
            self._drift_sample()
        return out

    def _drift_sample(self) -> None:
        """One monitoring forward: re-run the live decode batch *eagerly*
        (no jit) so the ``core.tap`` sites in ``models.layers.dense`` fire
        — ``tap.tag`` is a structural no-op under jit but fires on concrete
        arrays — feeding the drift monitor. Logits and cache writes are
        discarded (the update is functional), so serving state is
        untouched; the cost is one eager forward every ``drift_every``
        steps, entirely outside the watchdog-timed window. The first
        sampling failure disables the monitor for the engine's lifetime:
        telemetry must never take the serving loop down."""
        if self._drift_broken:
            return
        if not any(
            s.req is not None and not s.prefilling for s in self.slots
        ):
            return  # nothing decoding: the batch rows are all garbage

        def forward():
            with layers.serving_mode(self.matmul_mode, kernel="xla"):
                T.decode_step(
                    self.params, self.tokens, self.caches, self.cfg,
                    attn_kernel="gather" if self.paged else self.attn_kernel,
                )

        try:
            self._drift.sample(forward)
        except Exception as e:  # pragma: no cover - defensive
            self._drift_broken = True
            _LOG.warning("quant-drift monitor disabled: %s", e)

    def _step_impl(self):
        self._shed_expired()
        self._admit()
        if self.chunked:
            # Budgeted prefill work first: decode lanes then step below in
            # the same iteration — one chunk's worth of prefill latency is
            # the most any decode token waits (vs a whole prompt before).
            self._run_chunk_plan()
        if not any(s.req for s in self.slots):
            return False
        if not any(s.req is not None and not s.prefilling for s in self.slots):
            return True  # prefill-only step: chunks ran, nothing decodes yet
        # Speculation requires every active lane greedy (the draft/verify
        # accept rule is an argmax-chain comparison); rounds with a sampled
        # lane fall back to plain decode — greedy lanes still emit their
        # exact argmax tokens (the spec output-identity contract), sampled
        # lanes get the ordinary sampled step. Spec rounds resume once the
        # sampled lanes retire — and pause while any lane is mid-prefill
        # (a speculative window would draft through its trash row; plain
        # decode skips it per lane instead).
        if (
            self._spec is not None
            and not self._active_sampled()
            and not any(s.prefilling for s in self.slots)
        ):
            return self._spec_step()
        # Optimistic growth: the next decode writes one position per lane.
        self._ensure_capacity(1)
        if not any(s.req is not None and not s.prefilling for s in self.slots):
            return True  # growth preempted every lane; re-admit next step
        n_active = sum(1 for s in self.slots if s.req and not s.prefilling)
        traces0 = self.decode_traces
        t0 = time.perf_counter()
        # Static per-round flag: greedy-only rounds skip the sampling branch
        # entirely (no sort/softmax over [B, V] per step). Both variants
        # compile at most once, so mixed workloads cannot retrace-thrash.
        nxt, finite, self.caches = self._decode(
            self.params, self.caches, self.tokens, self._samp_device(),
            jnp.asarray(self._fault_row()),
            sampled=self._active_sampled(),
        )
        self.steps += 1
        nxt_np = np.asarray(nxt)  # sync point: decode step fully retired
        finite_np = np.asarray(finite)
        elapsed = time.perf_counter() - t0
        now = time.perf_counter()
        if self.decode_traces > traces0:
            self.decode_compile_s += elapsed
        else:
            self.decode_time_s += elapsed
            self.decode_tokens_warm += n_active
        if self.trace is not None:
            self.trace.emit(
                "decode_step", ts=t0, dur=elapsed, step=self.steps,
                lanes=n_active, traced=self.decode_traces > traces0,
            )
        faulted: List[Request] = []
        for i, slot in enumerate(self.slots):
            if slot.req is None or slot.prefilling:
                continue  # mid-prefill lanes decode into their trash rows
            if not bool(finite_np[i]):
                # Nonfinite logits: the lane's "token" is garbage — book
                # nothing, quarantine the request, free the lane. Neighbour
                # lanes' tokens are unaffected (the guard is per lane).
                slot.req.finish_reason = "error"
                faulted.append(slot.req)
                self._retire(i)
                continue
            tok = int(nxt_np[i, 0])
            if slot.req.t_tokens:
                self._hist_itl.observe(now - slot.req.t_tokens[-1])
            slot.req.output.append(tok)
            slot.req.t_tokens.append(now)
            self.decoded_tokens += 1
            slot.remaining -= 1
            if slot.req.eos_id is not None and tok == slot.req.eos_id:
                slot.req.finish_reason = "eos"
                self._retire(i)
            elif slot.remaining <= 0:
                slot.req.finish_reason = "length"
                self._retire(i)
        self.tokens = nxt
        for r in faulted:
            self._note_fault(r)
        return True

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Drive until queue and slots drain (or the step budget ends).
        ``EngineConfig.profile_dir`` wraps the whole drive in a
        ``jax.profiler`` trace window (the ``jax.named_scope`` labels on
        the prefill/decode/verify dispatches show up there)."""
        self.start_profile()
        try:
            for _ in range(max_steps):
                if not self.step() and not self.queue:
                    break
        finally:
            self.stop_profile()
        return self.done

    def start_profile(self) -> None:
        """Open a ``jax.profiler`` trace window writing to
        ``EngineConfig.profile_dir``; no-op when unset or already open.
        Best-effort: a jaxlib without profiler support must never take the
        serving loop down."""
        if not self.config.profile_dir or self._profiling:
            return
        try:
            jax.profiler.start_trace(self.config.profile_dir)
            self._profiling = True
        except Exception as e:  # pragma: no cover - backend-dependent
            _LOG.warning("jax profiler trace not started: %s", e)

    def stop_profile(self) -> None:
        """Close the profiler window opened by :meth:`start_profile`."""
        if not self._profiling:
            return
        self._profiling = False
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # pragma: no cover - backend-dependent
            _LOG.warning("jax profiler trace not stopped: %s", e)

    def _attn_step_ms(self) -> float:
        """Probe the decode-attention hot path: best-of-3 warm wall time (ms)
        of ONE layer's paged attention dispatch at half-context positions on
        the live page pool. An instrument, not an average over the run —
        attention inside the fused decode jit cannot be timed separately, and
        a fixed probe position makes the number comparable across runs (the
        gather path's cost is position-independent by construction, which is
        exactly what this metric is meant to expose)."""
        if not self.attn_probe:
            return 0.0
        if self._attn_probe_fn is None:
            p0 = self.params["layers"][0]["attn"]

            def impl(p, pool, table, pos, x):
                with layers.serving_mode(
                    self.matmul_mode, kernel=self.matmul_kernel
                ):
                    y, _ = attn_mod.attention_decode(
                        p, x, pool, pos, self.cfg, table=table,
                        attn_kernel=self.attn_kernel,
                    )
                return y

            self._attn_probe_fn = (jax.jit(impl), p0)
        fn, p0 = self._attn_probe_fn
        pool = self.caches["layers"][0]["attn"]
        table = self.caches["table"]
        pos = jnp.full((self.max_batch,), self.max_len // 2, jnp.int32)
        x = jnp.zeros((self.max_batch, 1, self.cfg.d_model), jnp.float32)
        fn(p0, pool, table, pos, x).block_until_ready()  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fn(p0, pool, table, pos, x).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    def _attn_kernel_stat(self) -> str:
        """The compiled decode-attention path, in KernelChoice vocabulary:
        ``"pallas"`` only when the Mosaic kernel actually compiles (paged +
        pallas choice + TPU backend — off TPU the dispatch lowers to the
        gather-free XLA loop, reported as ``"xla"``); ``"gather"`` for the
        legacy oracle path; unpaged engines report ``"xla"`` (dense
        einsums)."""
        if not self.paged or self.attn_kernel == "gather":
            return self.attn_kernel if self.paged else "xla"
        if self.attn_kernel == "pallas" and jax.default_backend() != "tpu":
            return "xla"
        return self.attn_kernel

    def _refresh_gauges(self) -> None:
        """Mirror point-in-time engine state into registry gauges, so the
        Prometheus exposition, the JSONL snapshots, and the v8 stats view
        all read one source. Counters/histograms book live at their event
        sites; everything that is a *reading* of live structures (pool
        occupancy, queue depth, rolling step percentiles, scheduler
        counters owned by the scheduler object) refreshes here, at scrape
        time."""
        m = self.metrics
        alloc = self.allocator
        m.gauge("engine_queue_depth", "requests waiting for a lane").set(
            len(self.queue)
        )
        m.gauge("engine_active_lanes", "lanes holding a request").set(
            sum(1 for s in self.slots if s.req is not None)
        )
        m.gauge("engine_step_p50_ms", "rolling step-time p50").set(
            self._step_timer.percentile(50) * 1e3
        )
        m.gauge("engine_step_p95_ms", "rolling step-time p95").set(
            self._step_timer.percentile(95) * 1e3
        )
        m.gauge("engine_step_stalled", "watchdog straggler flag").set(
            1.0 if self._step_timer.is_straggling else 0.0
        )
        m.gauge("kv_pages_capacity", "page-pool capacity").set(
            float(alloc.capacity) if alloc else 0.0
        )
        m.gauge("kv_pages_in_use", "pages currently owned by lanes").set(
            float(alloc.in_use()) if alloc else 0.0
        )
        m.gauge("kv_pages_cached", "prefix-cache pages (reclaimable)").set(
            float(alloc.cached_pages()) if alloc else 0.0
        )
        m.gauge("kv_pages_peak", "peak pages in use").set(
            float(alloc.peak_in_use) if alloc else 0.0
        )
        m.gauge("kv_pool_occupancy", "in-use fraction of the pool").set(
            alloc.in_use() / alloc.capacity if alloc else 0.0
        )
        m.gauge("kv_pool_peak_occupancy", "peak in-use fraction").set(
            alloc.peak_in_use / alloc.capacity if alloc else 0.0
        )
        m.gauge("prefix_hit_rate", "prefix-cache page hit rate").set(
            alloc.hit_rate() if alloc else 0.0
        )
        m.gauge("prefix_hit_pages", "prefix-cache pages reused").set(
            float(alloc.prefix_hit_pages) if alloc else 0.0
        )
        m.gauge("sched_chunks", "prefill chunk calls planned").set(
            float(self._sched.chunks)
        )
        m.gauge("sched_budget_limited_steps",
                "steps where the prefill budget bound").set(
            float(self._sched.budget_limited_steps)
        )
        m.gauge("sched_aging_promotions",
                "requests promoted by the aging bound").set(
            float(self._sched.aging_promotions)
        )
        m.gauge("sched_peak_step_prefill_tokens",
                "max prefill tokens in one step").set(
            float(self._sched.peak_step_tokens)
        )
        if self._spec is not None:
            m.gauge("spec_acceptance_rate",
                    "draft-token acceptance rate (EMA source)").set(
                self._spec.acceptance_rate()
            )
        if self.trace is not None:
            m.gauge("trace_events", "span events currently in the ring").set(
                float(len(self.trace))
            )
            m.gauge("trace_dropped",
                    "span events aged out of the bounded ring").set(
                float(self.trace.dropped)
            )
        m.gauge("kv_bytes_per_token",
                "per-token KV cache footprint across all layers").set(
            float(kvc.kv_bytes_per_token(self.cfg)) if self.paged else 0.0
        )
        m.gauge("kv_pool_capacity_tokens",
                "page-pool capacity expressed in tokens").set(
            float(alloc.capacity * self.page_size) if alloc else 0.0
        )
        if self._drift is not None:
            self._drift.publish(m)

    def metrics_text(self) -> str:
        """Prometheus text exposition of the engine registry (gauges
        refreshed first)."""
        self._refresh_gauges()
        return self.metrics.prometheus_text()

    def metrics_snapshot(self) -> dict:
        """JSON-safe nested registry snapshot (one JSONL line per call)."""
        self._refresh_gauges()
        return self.metrics.snapshot()

    def drift_report(self) -> dict:
        """Per-site drift diagnostics ({} when ``drift_every`` is off)."""
        return self._drift.report() if self._drift is not None else {}

    def engine_stats(self) -> EngineStats:
        """The typed v10 stats record (``stats()`` is its flat dict view),
        derived from the metrics registry: counts read registry counters
        (through the legacy attribute facade), percentiles read the
        bounded-reservoir registry histograms booked live at the event
        sites, point-in-time readings go through :meth:`_refresh_gauges`."""
        self._refresh_gauges()
        gv = lambda name: self.metrics.gauge(name).value  # noqa: E731
        s = EngineStats(
            completed=self.completed,
            cancelled=self.cancelled,
            preempted=self.preempted,
            shed=self.shed,
            timed_out=self.timed_out,
            errors=self.errors,
            kernel_fallbacks=self.kernel_fallbacks,
            step_p50_ms=gv("engine_step_p50_ms"),
            step_p95_ms=gv("engine_step_p95_ms"),
            step_stalled=gv("engine_step_stalled"),
            decode_steps=self.steps,
            decoded_tokens=self.decoded_tokens,
            mean_latency_s=self._hist_latency.mean,
            mean_ttft_s=self._hist_ttft.mean,
            ttft_p50_s=self._hist_ttft.percentile(50),
            ttft_p95_s=self._hist_ttft.percentile(95),
            itl_p50_s=self._hist_itl.percentile(50),
            itl_p95_s=self._hist_itl.percentile(95),
            prefill_tokens=self.prefill_tokens,
            prefill_time_s=self.prefill_time_s,
            prefill_compile_s=self.prefill_compile_s,
            # Warm-only throughput: compile calls are excluded so the number
            # tracks kernels across PRs, not jit noise. 0.0 when every call
            # hit a fresh bucket (e.g. a single-request run).
            prefill_tok_per_s=(
                self.prefill_tokens_warm / self.prefill_time_s
                if self.prefill_time_s > 0
                else 0.0
            ),
            decode_time_s=self.decode_time_s,
            decode_compile_s=self.decode_compile_s,
            decode_tok_per_s=(
                self.decode_tokens_warm / self.decode_time_s
                if self.decode_time_s > 0
                else 0.0
            ),
            prefill_calls=self.prefill_calls,
            prefill_requests=self.prefill_requests,
            prefill_calls_per_request=(
                self.prefill_calls / self.prefill_requests
                if self.prefill_requests
                else 0.0
            ),
            prefill_traces=self.prefill_traces,
            decode_traces=self.decode_traces,
            # Page-pool accounting (zeros when unpaged, keeping the schema flat).
            kv_page_size=float(self.page_size) if self.paged else 0.0,
            kv_pages_capacity=gv("kv_pages_capacity"),
            kv_pages_in_use=gv("kv_pages_in_use"),
            kv_pages_cached=gv("kv_pages_cached"),
            kv_pages_peak=gv("kv_pages_peak"),
            kv_pool_occupancy=gv("kv_pool_occupancy"),
            kv_pool_peak_occupancy=gv("kv_pool_peak_occupancy"),
            prefix_hit_rate=gv("prefix_hit_rate"),
            prefix_hit_pages=gv("prefix_hit_pages"),
            attn_kernel=self._attn_kernel_stat(),
            matmul_kernel=self.matmul_kernel,
            matmul_mode=self.matmul_mode,
            kv_bits=float(self.kv_bits or 0),
            kv_bytes_per_token=gv("kv_bytes_per_token"),
            kv_pool_capacity_tokens=gv("kv_pool_capacity_tokens"),
            attn_step_ms=self._attn_step_ms(),
            spec_enabled=1.0 if self._spec is not None else 0.0,
            queue_wait_p50_s=self._hist_qwait.percentile(50),
            queue_wait_p95_s=self._hist_qwait.percentile(95),
            sched_policy=self.config.sched_policy,
            sched_prefill_budget=float(self.config.prefill_budget),
            sched_chunks=gv("sched_chunks"),
            sched_budget_limited_steps=gv("sched_budget_limited_steps"),
            sched_aging_promotions=gv("sched_aging_promotions"),
            sched_peak_step_prefill_tokens=gv("sched_peak_step_prefill_tokens"),
            trace_enabled=1.0 if self.trace is not None else 0.0,
            trace_events=(
                float(len(self.trace)) if self.trace is not None else 0.0
            ),
            trace_dropped=(
                float(self.trace.dropped) if self.trace is not None else 0.0
            ),
            drift_enabled=1.0 if self._drift is not None else 0.0,
        )
        if self._spec is not None:
            for k, v in self._spec.stats().items():
                setattr(s, k, v)
        if self._drift is not None:
            for k, v in self._drift.stats().items():
                setattr(s, k, float(v))
        return s

    def stats(self) -> Dict:
        """The flat dict view of :meth:`engine_stats` (stats schema v10)."""
        return self.engine_stats().as_dict()


def _install_counter_properties() -> None:
    """Install the legacy counter attributes as registry-backed properties.

    ``eng.steps`` reads ``Counter.value`` (as int for integer-valued
    counters); ``eng.steps += 1`` goes get -> add -> set through
    ``Counter.set_`` (which refuses to move a counter backwards, so the
    facade keeps Prometheus counter semantics). ``__init__``'s ``= 0``
    assignments hit the same setter before anything has incremented.
    """

    def make(attr: str, integer: bool):
        def fget(self):
            v = self._metric_counters[attr].value
            return int(v) if integer else v

        def fset(self, v):
            self._metric_counters[attr].set_(float(v))

        return property(fget, fset)

    for attr, (_name, integer, _help) in _COUNTER_METRICS.items():
        setattr(ServingEngine, attr, make(attr, integer))


_install_counter_properties()
