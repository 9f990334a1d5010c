"""Output tokens served in the window per second of the window: every token
a client received, stamped on the host clock as ``step`` returned it."""


def read(run):
    t0, t1 = run.window["t0"], run.window["t1"]
    n = sum(1 for r in run.requests for t in r["stamps"] if t0 < t <= t1)
    return n / (t1 - t0)
