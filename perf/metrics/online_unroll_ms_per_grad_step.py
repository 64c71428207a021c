"""Train step: device time of the ops of stage ``loss_grad`` under the pass
``online_unroll`` — the online network over the loss + bootstrap steps,
forward and backward — per grad step. See ``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.ms_per_grad_step(run, trace, "PASSES", "online_unroll")
