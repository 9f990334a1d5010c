"""Share of the decode programs' device time (their XLA modules' seconds
in the traced window) spent in the grouped expert matmul (``moe_gmm``):
whether the held-expert layer does most of a decode step's work. None
where the program has no such kernel."""
from bench import costs_moe


def read(run):
    if run.trace is None:
        return None
    gmm = costs_moe.gmm_seconds(run.trace, "decode")
    total = run.trace["program_s"].get("decode")
    return 100.0 * gmm / total if gmm and total else None
