"""Train step: device time of the ops of stage ``loss_grad`` under the
recurrent network's ``core`` (the scanned cell: the LSTM today), all three
passes, forward and backward, per grad step. See ``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.ms_per_grad_step(run, trace, "PARTS", "core")
