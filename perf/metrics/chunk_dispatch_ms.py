"""Host loop: median duration of the ``fused.dispatch`` span (the
``run(carry, chunk_iters)`` call in ``train.train``) over the window's chunks,
from the flight ring. See ``_stages.py dispatch_ms``."""
from statistics import median

from perf.metrics import _stages


def read(run, trace):
    values = _stages.dispatch_ms(run)
    return median(values) if values else None
