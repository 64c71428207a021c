"""Train step: device time of the ops of stage ``loss_grad`` under the state-
space layers (``ssm``: in-projection, convolution, the chunked scan, gated
norm, out-projection) of the hybrid sequence core, all passes, forward,
recomputed forward and backward, per grad step. Left out where the program
keeps no such names. See ``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.ms_per_grad_step(run, trace, "CORE_PARTS", "ssm")
