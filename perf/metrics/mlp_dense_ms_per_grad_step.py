"""Train step: device time of the ops of stage ``loss_grad`` under the dense
gated MLP sublayers (scope ``mlp_dense``: three products and the gate
between) of the hybrid sequence core, all passes and — in a core that runs
its stack several times — all turns, forward, recomputed forward and
backward, per grad step. The scope's reader under the scope's name, as
``attention_full_ms_per_grad_step`` is: the cell of any configuration with
such sublayers appends itself to its list. (``laguna_q.preset`` reports the
same reading as ``dense_mlp_ms_per_grad_step``, whose list a test of the
benchmark holds to that one cell.) Left out where the program keeps no such
names. See ``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.ms_per_grad_step(run, trace, "CORE_PARTS", "mlp_dense")
