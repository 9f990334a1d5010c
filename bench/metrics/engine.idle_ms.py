"""Device-idle milliseconds per decode step that fall inside the traced
window's ``engine.step`` spans: the program's part of ``device.idle`` (the
rest is the harness, or lies between steps; ``bench/spans.py``). None where
the program opens no engine spans."""
from bench import spans


def read(run):
    t = spans.of(run)
    return None if t is None else spans.idle_ms(t)
