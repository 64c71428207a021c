"""Env: device time of the ops under stage ``env`` (``env.v_step``) inside
the iteration loop, per iteration, mean over the devices traced. See
``_stages.py``."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.ms_per_iter(run, trace, "env")
