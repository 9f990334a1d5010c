"""The VMEM budget the Pallas kernel wrappers check, and the record of
reroutes past it.

A wrapper whose resident tiles would not fit the budget runs the kernel's
XLA formulation instead. That choice is made at trace time from static
shapes, so the results alone never show it: each reroute is logged once per
(kernel, shape) and counted in :func:`reroutes`, which a run on the chip
reads to refuse a path that quietly left its kernel.
"""
from __future__ import annotations

from typing import Dict, Sequence

from repro.obs.log import get_logger

__all__ = ["VMEM_BUDGET_BYTES", "reroute", "reroutes", "clear_reroutes"]

# Per-program VMEM budget: ~16 MiB of scoped VMEM per core on v5e; keep half
# for Mosaic scratch and the neighbouring kernels.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

_LOG = get_logger("kernels")
_REROUTES: Dict[str, int] = {}


def reroute(kernel: str, shape: Sequence[int], need: int, budget: int) -> None:
    """Book one trace-time reroute of ``kernel`` at ``shape`` to XLA."""
    key = f"{kernel}{tuple(int(d) for d in shape)}"
    if key not in _REROUTES:
        _LOG.warning(
            "%s: %d bytes of resident tiles exceed the %d-byte VMEM budget; "
            "running the XLA formulation", key, need, budget,
        )
    _REROUTES[key] = _REROUTES.get(key, 0) + 1


def reroutes() -> Dict[str, int]:
    """``{"kernel(shape)": traces rerouted}`` since the last clear."""
    return dict(_REROUTES)


def clear_reroutes() -> None:
    _REROUTES.clear()
