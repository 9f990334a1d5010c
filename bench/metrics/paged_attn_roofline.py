"""The paged-attention kernels' share of their roofline inside the decode
programs: per layer and step, the least time for each live lane's pages of
K, V and scales up to its position, its query, output and appended rows
(``bench/costs.py``), over those kernels' device time."""
from bench import costs


def read(run):
    if run.trace is None or not run.trace["kernel_s"].get("paged_attn"):
        return None
    c, e = run.config["model"], run.config["engine"]
    least = 0.0
    for s in run.steps:
        if s["positions"]:
            f, b = costs.paged_attention(c, e["kv_bits"], e["page_size"],
                                         s["positions"])
            least += c["n_layers"] * costs.least_time(f, b, run.peaks)
    return 100.0 * least / run.trace["kernel_s"]["paged_attn"]
