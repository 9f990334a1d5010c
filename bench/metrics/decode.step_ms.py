"""Device milliseconds per run of the decode program in the window."""


def read(run):
    if run.trace is None:
        return None
    n = run.trace["program_runs"].get("decode", 0)
    return run.trace["program_s"]["decode"] * 1e3 / n if n else None
