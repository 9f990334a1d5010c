"""Seconds from process start to the window's start: load, compile or cache
load, warm-up and the first requests' prefill."""


def read(run):
    return run.setup["setup_s"]
