"""Gather: device time of the ops under stage ``gather`` (the window gather,
the n-step fold, the dedup stack rebuild, the batch decode, and the layout
copies the compiler inserts for them), per grad step, mean over the devices
traced. See ``_stages.py``."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.ms_per_grad_step(run, trace, "gather")
