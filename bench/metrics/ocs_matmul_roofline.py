"""The OCS matmul kernels' share of their roofline inside the decode
programs: each call's least time (int8 weight, scales and activations at
the step's live lanes, ``bench/costs.py``) summed over the window, over
those kernels' device time."""
from bench import costs


def read(run):
    if run.trace is None or not run.trace["kernel_s"].get("ocs_matmul"):
        return None
    c = run.config["model"]
    least = sum(costs.decode_matmul_least_time(c, run.config["recipe"],
                                               len(s["positions"]), run.peaks)
                for s in run.steps if s["positions"])
    return 100.0 * least / run.trace["kernel_s"]["ocs_matmul"]
