"""The decode programs' share of their roofline: the least time the chip
could take for the window's decode steps (per step the larger of operations
over the bf16 peak and bytes over HBM bandwidth: weights, KV pages read and
written, from ``bench/costs.py``) over the decode programs' device time."""
from bench import costs


def read(run):
    if run.trace is None or not run.trace["program_s"].get("decode"):
        return None
    c, e = run.config["model"], run.config["engine"]
    least = 0.0
    for s in run.steps:
        if s["positions"]:
            parts = costs.decode_step(c, run.config["recipe"], e["kv_bits"],
                                      e["page_size"], s["positions"])
            least += costs.least_time(sum(f for f, _ in parts.values()),
                                      sum(b for _, b in parts.values()),
                                      run.peaks)
    return 100.0 * least / run.trace["program_s"]["decode"]
