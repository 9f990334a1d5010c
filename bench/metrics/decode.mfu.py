"""The decode programs' share of the chip's bf16 peak: the operations the
window's decode steps need (``bench/costs.py``, at each step's live lanes
and positions) over the decode programs' device time times the peak."""
from bench import costs


def read(run):
    if run.trace is None or not run.trace["program_s"].get("decode"):
        return None
    c, e = run.config["model"], run.config["engine"]
    flops = 0.0
    for s in run.steps:
        if s["positions"]:
            parts = costs.decode_step(c, run.config["recipe"], e["kv_bits"],
                                      e["page_size"], s["positions"])
            flops += sum(f for f, _ in parts.values())
    return 100.0 * flops / (run.trace["program_s"]["decode"]
                            * run.peaks["bf16_flops"])
