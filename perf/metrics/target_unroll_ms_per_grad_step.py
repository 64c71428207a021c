"""Train step: device time of the ops of stage ``loss_grad`` under the pass
``target_unroll`` — the target network over the loss + bootstrap steps,
forward only — per grad step. See ``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.ms_per_grad_step(run, trace, "PASSES", "target_unroll")
