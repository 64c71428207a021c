"""Insert: device time of the ops under stage ``insert`` (the ring add, with
the slice and flatten that feed it and the layout copies the compiler
inserts for it) inside the iteration loop, per iteration, mean over the
devices traced. See ``_stages.py``."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.ms_per_iter(run, trace, "insert")
