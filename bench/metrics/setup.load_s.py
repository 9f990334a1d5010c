"""Seconds in the program's loader (``launch/weights.load_quantized``),
host clock, up to every weight being on the device."""


def read(run):
    return run.setup["load_s"]
