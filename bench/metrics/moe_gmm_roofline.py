"""The grouped expert matmul's share of its roofline: the least time of
the calls behind the engine's held-expert counters over the traced part of
the window (``bench/costs_moe.py``: rows and touched experts' int8 weights,
decode and prefill each bounded on its own), over the device seconds of the
``moe_gmm`` kernel in the decode and prefill programs. The counters hold
every program the traced part ran, but the device trace can hold fewer (its
capture may start late): each kind's least time is taken for the share of
its programs the trace holds, ``program_runs`` over the engine's ``steps``
(decode) or ``prefill_calls``. None where the program has no such kernel or
counters."""
from bench import costs_moe

CALLS = {"decode": "steps", "prefill": "prefill_calls"}


def read(run):
    if run.trace is None or "moe_rows_decode" not in run.counters:
        return None
    least = sec = 0.0
    for kind, calls in CALLS.items():
        s = costs_moe.gmm_seconds(run.trace, kind)
        if not s:
            continue
        n, held = run.counters.get(calls), run.trace["program_runs"].get(kind, 0)
        share = min(1.0, held / n) if n and held else 1.0
        sec += s
        least += share * costs_moe.least_seconds(
            run.config["model"], run.config["recipe"], run.counters,
            run.peaks, (kind,))
    return 100.0 * least / sec if sec else None
