"""Mean decode lanes per decode step in the window, from the engine's own
counters (tokens decoded and decode steps)."""


def read(run):
    steps = run.counters["steps"]
    return run.counters["decoded_tokens"] / steps if steps else None
