"""Host milliseconds of a decode step that the device waits on: the mean,
over the traced window's ``engine.step`` spans that dispatched a decode
program, of the step's duration less the part its ``engine.wait`` spans
cover (``bench/spans.py``). None where the program opens no engine spans."""
from bench import spans


def read(run):
    t = spans.of(run)
    return None if t is None else spans.host_ms(t)
