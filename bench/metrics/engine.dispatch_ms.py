"""Mean milliseconds of the decode program's ``engine.dispatch`` spans that
did not trace, in the traced window: from the jitted call's entry to its
return, so flattening the per-layer argument tree, transfers and the
enqueue (``bench/spans.py``). None where the program opens no engine
spans."""
from bench import spans


def read(run):
    t = spans.of(run)
    return None if t is None else spans.dispatch_ms(t)
