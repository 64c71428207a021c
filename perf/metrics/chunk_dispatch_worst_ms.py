"""Host loop: longest ``fused.dispatch`` span among the window's chunks (the
median is ``chunk_dispatch_ms``)."""
from perf.metrics import _stages


def read(run, trace):
    values = _stages.dispatch_ms(run)
    return max(values) if values else None
