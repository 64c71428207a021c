"""Train step: share of stage ``loss_grad``'s op time that the hybrid core's
part names (``torso``, ``attention_window``, ``attention_full``,
``mlp_dense``, ``moe_router``, ``moe_routed``, ``moe_shared``) leave under no
child or under ``mixed`` in a cell of the ``laguna_q`` configuration — norms,
residual adds, the heads, the loss, and the health of the names: 100 where
they were lost. Left out where the program has no ``attention_window`` name
(a program from before the sublayers): there ``core_unsplit_share`` is the
reading. See ``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    names = _children.children(run, "CORE_PARTS")
    if not names or "attention_window" not in set(names.values()):
        return None
    return _children.unsplit_share(run, trace, "CORE_PARTS")
