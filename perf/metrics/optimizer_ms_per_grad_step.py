"""Optimizer: device time of the ops under stages ``optimizer`` (update and
``apply_updates``) and ``target_sync``, which the compiler fuses into one
pass over the parameters, per grad step, mean over the devices traced. See
``_stages.py``."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.ms_per_grad_step(run, trace, "optimizer", "target_sync")
