"""Routed rows a held expert computes per decode step and layer where it
computes any: the engine's ``moe_rows_decode`` over
``moe_experts_touched_decode`` in the window (the deployment this cell
stands for gives about 3). None where the program has no such counters."""


def read(run):
    touched = run.counters.get("moe_experts_touched_decode")
    return run.counters["moe_rows_decode"] / touched if touched else None
