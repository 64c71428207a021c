"""Train step: share of stage ``loss_grad``'s op time that the hybrid core's
part names (``torso``, ``ssm``, ``attention``, ``moe_router``, ``moe_routed``,
``moe_shared``) leave under no child or under ``mixed`` — norms, residual
adds, the heads, the loss, and the health of the names: 100 where they were
lost. Left out where the program keeps no such names. See ``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.unsplit_share(run, trace, "CORE_PARTS")
