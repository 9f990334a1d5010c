"""Device milliseconds of the prefill programs per 1,000 prompt tokens they
ran (the engine's prefill token counter, prefix hits excluded)."""


def read(run):
    if run.trace is None:
        return None
    s = run.trace["program_s"].get("prefill", 0.0)
    tok = run.counters["prefill_tokens"]
    return s * 1e3 / (tok / 1e3) if s > 0 and tok else None
