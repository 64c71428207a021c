"""Train step: device time of the ops of stage ``loss_grad`` under the
full-attention sublayers (``attention_full``: projections, the YaRN rotary
embedding, the causal triangle of scores by blocks, softmax, the per-head
gate, out-projection) of the hybrid sequence core, all passes, forward,
recomputed forward and backward, per grad step. Left out where the program
keeps no such names. See ``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.ms_per_grad_step(run, trace, "CORE_PARTS",
                                      "attention_full")
