"""Priority write-back: device time of the ops under stage ``writeback``
(``prioritized_ring_update[_batched]``), per grad step, mean over the devices
traced; only a prioritized cell has it. See ``_stages.py``."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.ms_per_grad_step(run, trace, "writeback")
