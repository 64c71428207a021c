"""Loss and gradient: device time of the ops under stage ``loss_grad``
(forward, loss and backward of the train step), per grad step, mean over the
devices traced. See ``_stages.py``."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.ms_per_grad_step(run, trace, "loss_grad")
