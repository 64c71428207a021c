"""Train step: device time of the ops of stage ``loss_grad`` under the expert
sublayers of the hybrid sequence core — router, held routed experts and
shared expert (``moe_router`` + ``moe_routed`` + ``moe_shared``) summed — all
passes, forward, recomputed forward and backward, per grad step. Left out
where no op ran under any of the three. See ``_children.py``."""
from perf.metrics import _children

PARTS = ("moe_router", "moe_routed", "moe_shared")


def read(run, trace):
    found = [v for v in (_children.ms_per_grad_step(run, trace, "CORE_PARTS",
                                                    part)
                         for part in PARTS) if v is not None]
    return sum(found) if found else None
