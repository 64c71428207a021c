"""Train step: device time of the ops of stage ``loss_grad`` under the dense
gated MLP sublayer (``mlp_dense``: three products and the gate between) of
the hybrid sequence core, all passes, forward, recomputed forward and
backward, per grad step. Left out where the program keeps no such names. See
``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.ms_per_grad_step(run, trace, "CORE_PARTS", "mlp_dense")
