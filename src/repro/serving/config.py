"""Typed serving configuration: ``EngineConfig``, ``KernelChoice``,
``SamplingParams``.

The paper's deployment story is an ML provider serving a client's float model
in low precision without retraining. Through PRs 1-4 the provider-side knob
space accreted into three disjoint surfaces — ``ServingEngine`` constructor
kwargs, the ``USE_PALLAS_SERVING`` / ``USE_PALLAS_PAGED_ATTN`` module globals,
and hand-written ``launch/serve.py`` flags — which disagreed on vocabulary
(``--paged-attn {auto,on,off}`` vs ``use_pallas_paged_attn=bool``) and leaked
state across engines (a test flipping a module global changed every engine
traced afterwards). This module makes the knob space one validated, hashable
surface:

* :class:`KernelChoice` — the single kernel-selection vocabulary
  (``auto | pallas | xla | gather``) shared by the config, the CLI, and
  ``stats()["attn_kernel"]``;
* :class:`KernelConfig` — per-engine backend selection for the quantized
  matmuls and the paged decode attention, threaded *explicitly* through
  ``layers.dense`` / ``models.attention.attention_decode`` (the module
  globals survive only as deprecated shims that seed ``auto`` at engine
  construction — nothing reads them at dispatch time);
* :class:`EngineConfig` — every engine-level knob (batching, paging, matmul
  mode, kernels, speculation, probes) as one frozen dataclass.
  ``launch/serve.py`` auto-generates its argparse flags from these fields
  (:func:`add_engine_config_args` / :func:`engine_config_from_args`), so the
  CLI can never drift from the config again;
* :class:`SamplingParams` — per-request decode sampling (greedy by default,
  which is what the spec-decode exactness contract requires; temperature /
  top-k / top-p with a per-request seed otherwise).
"""
from __future__ import annotations

import argparse
import dataclasses
import enum
from typing import Optional

from .spec_decode import SpecConfig

__all__ = [
    "ConfigError",
    "KernelChoice",
    "KernelConfig",
    "EngineConfig",
    "SamplingParams",
    "add_engine_config_args",
    "engine_config_from_args",
]


class ConfigError(ValueError):
    """A structurally valid but *unsupported* knob combination.

    Raised when individually valid fields contradict each other (e.g. a
    precision tier paired with a speculative draft mode it cannot verify
    against, or ``kv_bits=4`` on an unpaged engine). A distinct type so
    launchers and the router can surface "fix your config" separately from
    programming errors — but still a ``ValueError`` for existing handlers.
    """


class KernelChoice(str, enum.Enum):
    """The one kernel-selection vocabulary (config == CLI == stats).

    * ``AUTO``   — ``PALLAS`` for both on a TPU backend; elsewhere defer to
      the deprecated module-global shims (``layers.USE_PALLAS_SERVING`` /
      ``attention.USE_PALLAS_PAGED_ATTN``). Resolved once at engine
      construction, never at dispatch;
    * ``PALLAS`` — the fused Pallas kernels (Mosaic on TPU; off-TPU the
      ``kernels.ops`` dispatch lowers them to their XLA formulations);
    * ``XLA``    — force the pure-XLA formulation even on TPU (what GSPMD
      partitions for multi-device runs);
    * ``GATHER`` — attention only: the legacy gather-everything paged path,
      the bit-exactness oracle (float pages == dense cache).
    """

    AUTO = "auto"
    PALLAS = "pallas"
    XLA = "xla"
    GATHER = "gather"

    @classmethod
    def coerce(cls, v) -> "KernelChoice":
        if isinstance(v, KernelChoice):
            return v
        try:
            return cls(str(v).lower())
        except ValueError:
            raise ValueError(
                f"kernel choice must be one of {[c.value for c in cls]}, "
                f"got {v!r}"
            ) from None


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _default_matmul_kernel() -> KernelChoice:
    """AUTO resolution for the matmul backend: the Pallas kernels on a TPU,
    the deprecated module shim elsewhere."""
    from repro.models import layers

    if _on_tpu() or layers.USE_PALLAS_SERVING:
        return KernelChoice.PALLAS
    return KernelChoice.XLA


def _default_attn_kernel() -> KernelChoice:
    """AUTO resolution for paged decode attention: the Pallas kernel on a
    TPU; elsewhere the deprecated shim, whose flag-off default is the legacy
    *gather* path — the engine-level bit-exactness oracle the CPU tests are
    stated over."""
    from repro.models import attention

    if _on_tpu() or attention.USE_PALLAS_PAGED_ATTN:
        return KernelChoice.PALLAS
    return KernelChoice.GATHER


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Kernel backend selection for one engine (no module-global leakage:
    two co-resident engines with different ``KernelConfig``s dispatch
    independently — the choice is captured per engine at construction and
    threaded through every traced call)."""

    matmul: KernelChoice = KernelChoice.AUTO  # quantized matmuls (dense)
    attn: KernelChoice = KernelChoice.AUTO  # paged decode attention

    def __post_init__(self):
        object.__setattr__(self, "matmul", KernelChoice.coerce(self.matmul))
        object.__setattr__(self, "attn", KernelChoice.coerce(self.attn))
        if self.matmul == KernelChoice.GATHER:
            raise ValueError(
                "kernels.matmul: 'gather' is an attention-only choice "
                "(matmul backends: auto | pallas | xla)"
            )

    def resolve(self) -> "KernelConfig":
        """Pin ``AUTO`` fields to concrete backends (reads the deprecated
        module shims — the only place they are consulted)."""
        return KernelConfig(
            matmul=(
                _default_matmul_kernel()
                if self.matmul == KernelChoice.AUTO
                else self.matmul
            ),
            attn=(
                _default_attn_kernel()
                if self.attn == KernelChoice.AUTO
                else self.attn
            ),
        )


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode sampling.

    The default (``temperature == 0``) is exact greedy argmax — the decode
    semantics every PR-1..4 contract (spec-decode output identity, paged
    bit-exactness) is stated over. Non-greedy requests draw from the
    temperature-scaled distribution restricted by ``top_k`` / ``top_p``,
    with a per-lane PRNG key derived from ``(seed, token position)`` — so a
    fixed seed is bit-reproducible across runs, across batch compositions,
    and across paged/unpaged engines (float pages are bit-exact, hence so
    are the logits the key is applied to).
    """

    temperature: float = 0.0  # 0 = greedy (exact argmax)
    top_k: int = 0  # 0 = no top-k restriction
    top_p: float = 1.0  # 1 = no nucleus restriction
    seed: int = 0  # per-request PRNG seed

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


# Three-state CLI vocabulary for Optional[bool] fields (``paged``): "auto"
# defers to the engine's per-arch default.
_TRI = {"auto": None, "on": True, "off": False}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Every engine-level serving knob, validated and hashable.

    One instance fully determines an engine's serving behavior (given the
    model config and parameters): jit caches and benchmark records can key
    on it, and flipping a module flag can never change an engine that was
    already built (the old leakage hazard). Like every ambient trace
    context here (``layers.SERVING_MODE`` included), the kernel selection
    assumes jit tracing is single-threaded per process.

    CLI metadata: each field's ``metadata`` drives the auto-generated
    ``launch/serve.py`` flags (:func:`add_engine_config_args`) — adding a
    field here *is* adding the flag.
    """

    max_batch: int = dataclasses.field(
        default=8, metadata={"help": "decode lanes (continuous-batching width)"}
    )
    max_len: int = dataclasses.field(
        default=512, metadata={"help": "max prompt+decode positions per lane"}
    )
    matmul_mode: str = dataclasses.field(
        default="dequant",
        metadata={
            "help": "dequant = weight-only int8; w8a8 = dynamic per-row "
            "int8 activations; w4a8 = packed int4 weights with an "
            "OCS-selected outlier-channel set kept at int8",
            "choices": ["dequant", "w8a8", "w4a8"],
        },
    )
    kv_bits: Optional[int] = dataclasses.field(
        default=None,
        metadata={
            "help": "KV-cache precision tier: 8 = int8 rows, 4 = packed "
            "nibble pages (paged engines only; halves KV bytes/token), "
            "0/unset = the model config's default",
            "optional_int": True,
        },
    )
    w4a8_outlier_ratio: float = dataclasses.field(
        default=0.05,
        metadata={
            "help": "w4a8: fraction of input channels kept at int8 "
            "(OCS absmax ranking; 0 = naive all-int4 weights)",
        },
    )
    paged: Optional[bool] = dataclasses.field(
        default=None,
        metadata={
            "help": "paged KV cache (auto = paged on attention archs)",
            "tri_state": True,
        },
    )
    page_size: int = dataclasses.field(
        default=16, metadata={"help": "KV page size in tokens (power of two)"}
    )
    n_pages: Optional[int] = dataclasses.field(
        default=None,
        metadata={
            "help": "KV pool pages (0/unset = the fixed-slot footprint; "
            "smaller oversubscribes via recycling)",
            "optional_int": True,
        },
    )
    kernels: KernelConfig = dataclasses.field(
        default=KernelConfig(),
        metadata={
            "help": "kernel backends",  # expanded to --matmul-kernel/--attn-kernel
            "kernels": True,
        },
    )
    spec: Optional[SpecConfig] = dataclasses.field(
        default=None,
        metadata={
            "help": "self-speculative decoding",  # expanded to --spec-k/--draft-layers
            "spec": True,
        },
    )
    attn_probe: bool = dataclasses.field(
        default=False,
        metadata={
            "help": "probe per-step attention time into stats().attn_step_ms "
            "(costs one extra jit compile)",
            "store_true": True,
        },
    )
    admission: str = dataclasses.field(
        default="reserve",
        metadata={
            "help": "paged admission policy: reserve = worst-case pages up "
            "front (never preempts); optimistic = admit on prompt pages + "
            "headroom, preempt-and-recompute the youngest lane on exhaustion "
            "(greedy output stays bit-identical)",
            "choices": ["reserve", "optimistic"],
        },
    )
    admission_headroom: int = dataclasses.field(
        default=1,
        metadata={
            "help": "optimistic admission: decode pages granted beyond the "
            "prompt at install time (>= 1 so the first decode token always "
            "has a slot)",
        },
    )
    max_queue: int = dataclasses.field(
        default=0,
        metadata={
            "help": "bounded submit queue (0 = unbounded); a full queue "
            "rejects with EngineOverloaded and finish_reason='shed'",
        },
    )
    sched_policy: str = dataclasses.field(
        default="fifo",
        metadata={
            "help": "admission/chunk ordering: fifo = submit order; sjf = "
            "shortest remaining prefill first (aged requests are promoted "
            "ahead after sched_aging_steps engine steps in queue)",
            "choices": ["fifo", "sjf"],
        },
    )
    prefill_budget: int = dataclasses.field(
        default=0,
        metadata={
            "help": "max prefill tokens per engine step (0 = legacy "
            "monolithic prefill); > 0 chunks prompts so decode lanes never "
            "wait behind a whole prompt",
        },
    )
    chunk_size: int = dataclasses.field(
        default=64,
        metadata={
            "help": "prefill chunk length in tokens (multiple of page_size "
            "when paged; only used when prefill_budget > 0)",
        },
    )
    sched_aging_steps: int = dataclasses.field(
        default=64,
        metadata={
            "help": "anti-starvation bound: a queued request older than this "
            "many engine steps is ordered ahead of policy order (sjf cannot "
            "starve long prompts)",
        },
    )
    heartbeat_path: str = dataclasses.field(
        default="",
        metadata={
            "help": "serving heartbeat file, written once per engine step "
            "('' = off); external watchdogs read it for liveness",
        },
    )
    heartbeat_interval_s: float = dataclasses.field(
        default=0.0,
        metadata={
            "help": "min seconds between heartbeat file writes (0 = every "
            "step); throttles the per-step atomic file replace on fast loops",
        },
    )
    trace: bool = dataclasses.field(
        default=False,
        metadata={
            "help": "record typed span events (admit / prefill_chunk / "
            "decode_step / spec / preempt / shed / ...) into a bounded "
            "host-side ring buffer; export Chrome trace JSON via "
            "ServingEngine.trace",
            "store_true": True,
        },
    )
    trace_capacity: int = dataclasses.field(
        default=8192,
        metadata={
            "help": "span-event ring capacity; the oldest events drop once "
            "full (bounded memory no matter how long the engine runs)",
        },
    )
    profile_dir: str = dataclasses.field(
        default="",
        metadata={
            "help": "jax.profiler trace output directory ('' = off); run() "
            "wraps the serving loop in a profiler window, with named_scope "
            "labels on the prefill/decode/verify/attention dispatches",
        },
    )
    drift_every: int = dataclasses.field(
        default=0,
        metadata={
            "help": "sample quantization-drift telemetry every N engine "
            "steps (0 = off): each sample runs one eager tapped forward "
            "over the live decode batch and books per-site activation "
            "saturation against the calibrated clip grid",
        },
    )
    drift_threshold: float = dataclasses.field(
        default=4.0,
        metadata={
            "help": "drift flag: live outlier mass above this multiple of "
            "the calibrated outlier mass marks a site as drifted (> 1)",
        },
    )

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_len < 2:
            raise ValueError(
                f"max_len must leave room for prompt + 1 token, got {self.max_len}"
            )
        if self.matmul_mode not in ("dequant", "w8a8", "w4a8"):
            raise ValueError(
                f"matmul_mode must be dequant|w8a8|w4a8, got {self.matmul_mode!r}"
            )
        if self.kv_bits is not None and self.kv_bits not in (4, 8):
            raise ValueError(
                f"kv_bits must be 4 or 8 (or unset), got {self.kv_bits}"
            )
        if self.kv_bits == 4 and self.paged is False:
            raise ConfigError(
                "kv_bits=4 packs nibbles into page pools; the dense cache "
                "has no int4 layout — drop paged=False or use kv_bits=8"
            )
        if not 0.0 <= self.w4a8_outlier_ratio <= 1.0:
            raise ValueError(
                "w4a8_outlier_ratio must be in [0, 1], got "
                f"{self.w4a8_outlier_ratio}"
            )
        if (
            self.matmul_mode == "w4a8"
            and self.spec is not None
            and getattr(self.spec, "draft_mode", None) != "w4a8"
        ):
            raise ConfigError(
                "matmul_mode='w4a8' serves a W4A8Linear parameter tree; a "
                f"draft_mode={getattr(self.spec, 'draft_mode', None)!r} "
                "drafter cannot trace it (the int8/float matmul modes need "
                "the OCSQuantLinear tree) — set spec.draft_mode='w4a8'"
            )
        if self.page_size < 1 or self.page_size & (self.page_size - 1):
            raise ValueError(
                f"page_size must be a power of two, got {self.page_size}"
            )
        if self.n_pages is not None and self.n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the trash page), got {self.n_pages}"
            )
        if self.admission not in ("reserve", "optimistic"):
            raise ValueError(
                f"admission must be reserve|optimistic, got {self.admission!r}"
            )
        if self.admission_headroom < 1:
            raise ValueError(
                "admission_headroom must be >= 1 (the first decode token "
                f"needs a page slot), got {self.admission_headroom}"
            )
        if self.max_queue < 0:
            raise ValueError(f"max_queue must be >= 0, got {self.max_queue}")
        if self.sched_policy not in ("fifo", "sjf"):
            raise ValueError(
                f"sched_policy must be fifo|sjf, got {self.sched_policy!r}"
            )
        if self.prefill_budget < 0:
            raise ValueError(
                f"prefill_budget must be >= 0, got {self.prefill_budget}"
            )
        if self.prefill_budget:
            if self.chunk_size < 1:
                raise ValueError(
                    "chunk_size must be >= 1 when prefill_budget > 0, "
                    f"got {self.chunk_size}"
                )
            if self.prefill_budget < self.chunk_size:
                raise ValueError(
                    "prefill_budget must be >= chunk_size (each step must "
                    f"fit one chunk), got budget {self.prefill_budget} < "
                    f"chunk {self.chunk_size}"
                )
            if self.paged is not False and self.chunk_size % self.page_size:
                raise ValueError(
                    "chunk_size must be a multiple of page_size for paged "
                    f"engines, got chunk {self.chunk_size} / page "
                    f"{self.page_size}"
                )
        if self.sched_aging_steps < 1:
            raise ValueError(
                f"sched_aging_steps must be >= 1, got {self.sched_aging_steps}"
            )
        if self.heartbeat_interval_s < 0:
            raise ValueError(
                "heartbeat_interval_s must be >= 0, got "
                f"{self.heartbeat_interval_s}"
            )
        if self.trace_capacity < 1:
            raise ValueError(
                f"trace_capacity must be >= 1, got {self.trace_capacity}"
            )
        if self.drift_every < 0:
            raise ValueError(
                f"drift_every must be >= 0, got {self.drift_every}"
            )
        if self.drift_threshold <= 1.0:
            raise ValueError(
                "drift_threshold must be > 1 (a site at its calibrated "
                f"outlier mass is not drifted), got {self.drift_threshold}"
            )
        if self.spec is not None and not isinstance(self.spec, SpecConfig):
            raise TypeError(f"spec must be a SpecConfig, got {type(self.spec)}")
        if not isinstance(self.kernels, KernelConfig):
            if isinstance(self.kernels, dict):
                object.__setattr__(self, "kernels", KernelConfig(**self.kernels))
            elif isinstance(self.kernels, (tuple, list)):
                object.__setattr__(self, "kernels", KernelConfig(*self.kernels))
            else:
                raise TypeError(
                    "kernels must be a KernelConfig (or a dict/tuple of its "
                    f"fields), got {type(self.kernels)}"
                )

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# CLI generation: EngineConfig fields -> argparse flags -> EngineConfig.
# One loop over dataclasses.fields keeps flag names, defaults, help text and
# choices mechanically in sync with the dataclass — the CLI cannot drift.


def _flag(name: str) -> str:
    return "--" + name.replace("_", "-")


def add_engine_config_args(
    ap: argparse.ArgumentParser,
    defaults: Optional[EngineConfig] = None,
    skip: tuple = (),
) -> None:
    """Add one flag per :class:`EngineConfig` field to ``ap``.

    Nested fields expand to their canonical flags: ``kernels`` ->
    ``--matmul-kernel`` / ``--attn-kernel`` (choices = :class:`KernelChoice`),
    ``spec`` -> ``--spec-k`` / ``--draft-layers``. Tri-state fields take
    ``{auto,on,off}``. ``skip`` names fields a tool manages itself — they
    get no flag, and :func:`engine_config_from_args` falls back to the
    defaults for them (never silently discards a flag the user passed).
    """
    d = defaults or EngineConfig()
    g = ap.add_argument_group("engine", "EngineConfig fields (auto-generated)")
    for f in dataclasses.fields(EngineConfig):
        if f.name in skip:
            continue
        meta = f.metadata
        default = getattr(d, f.name)
        if meta.get("kernels"):
            choices = [c.value for c in KernelChoice]
            g.add_argument(
                _flag("matmul_kernel"), default=default.matmul.value,
                choices=[c for c in choices if c != "gather"],
                help="quantized-matmul backend (auto = pallas on a TPU, "
                "else the deprecated layers.USE_PALLAS_SERVING shim)",
            )
            g.add_argument(
                _flag("attn_kernel"), default=default.attn.value, choices=choices,
                help="paged decode-attention backend (auto = pallas on a "
                "TPU, else the deprecated attention.USE_PALLAS_PAGED_ATTN "
                "shim; gather = the legacy bit-exactness oracle)",
            )
        elif meta.get("spec"):
            sd = default if default is not None else SpecConfig()
            g.add_argument(
                _flag("spec_k"), type=int,
                default=(sd.k if default is not None else 0),
                help="self-speculative draft window (0 = off)",
            )
            g.add_argument(
                _flag("draft_layers"), type=int,
                default=(sd.draft_layers or 0),
                help="truncate the drafter to the first L layers (0 = all)",
            )
        elif meta.get("tri_state"):
            g.add_argument(
                _flag(f.name), choices=sorted(_TRI),
                default=next(k for k, v in _TRI.items() if v == default),
                help=meta.get("help"),
            )
        elif meta.get("store_true"):
            g.add_argument(
                _flag(f.name), action="store_true", default=default,
                help=meta.get("help"),
            )
        elif meta.get("optional_int"):
            g.add_argument(
                _flag(f.name), type=int, default=default or 0,
                help=meta.get("help"),
            )
        else:
            g.add_argument(
                _flag(f.name), type=type(default), default=default,
                choices=meta.get("choices"), help=meta.get("help"),
            )


def engine_config_from_args(args: argparse.Namespace, **overrides) -> EngineConfig:
    """Invert :func:`add_engine_config_args`: parsed flags -> EngineConfig.

    Fields whose flags were ``skip``-ped at generation time are absent from
    ``args`` and fall back to the EngineConfig defaults (or ``overrides``).
    """
    kw = {}
    for f in dataclasses.fields(EngineConfig):
        meta = f.metadata
        if meta.get("kernels"):
            if hasattr(args, "matmul_kernel"):
                kw["kernels"] = KernelConfig(
                    matmul=args.matmul_kernel, attn=args.attn_kernel
                )
        elif meta.get("spec"):
            if hasattr(args, "spec_k"):
                kw["spec"] = (
                    SpecConfig(
                        k=args.spec_k, draft_layers=args.draft_layers or None
                    )
                    if args.spec_k
                    else None
                )
        elif not hasattr(args, f.name):
            pass  # skipped at generation time: EngineConfig default applies
        elif meta.get("tri_state"):
            kw[f.name] = _TRI[getattr(args, f.name)]
        elif meta.get("optional_int"):
            kw[f.name] = getattr(args, f.name) or None
        else:
            kw[f.name] = getattr(args, f.name)
    kw.update(overrides)
    return EngineConfig(**kw)
