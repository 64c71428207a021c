"""Act: device time of the ops under stage ``act`` (the actor's forward pass
and the epsilon-greedy draw) inside the iteration loop, per iteration, mean
over the devices traced. See ``_stages.py``."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.ms_per_iter(run, trace, "act")
