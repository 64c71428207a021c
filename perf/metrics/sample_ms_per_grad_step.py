"""Sample: device time of the ops under stage ``sample`` (the index draw:
uniform, or the priority pass, the CDF and the Mosaic kernel), per grad step,
mean over the devices traced. See ``_stages.py``."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.ms_per_grad_step(run, trace, "sample")
