"""Train step: share of stage ``loss_grad``'s op time that the passes
(``burn_in``, ``online_unroll``, ``target_unroll``) leave under no child or
under ``mixed`` — the loss itself, and the health of the child names: 100
where they were lost. Left out where the program keeps no such names. See
``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.unsplit_share(run, trace, "PASSES")
