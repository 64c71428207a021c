"""Train step: device time of the ops of stage ``loss_grad`` under the pass
``burn_in`` — the stop-gradient refresh of the stored state over the burn-in
steps, online and target network, forward only — per grad step. See
``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.ms_per_grad_step(run, trace, "PASSES", "burn_in")
