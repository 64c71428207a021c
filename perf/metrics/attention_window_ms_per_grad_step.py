"""Train step: device time of the ops of stage ``loss_grad`` under the
sliding-window attention sublayers (scope ``attention_window``: projections,
rotary embedding, the band of scores, softmax, out-projection; a head gate
where the configuration has one) of the hybrid sequence core, all passes,
forward, recomputed forward and backward, per grad step. The scope's reader
under the scope's name: the cell of any configuration with such sublayers
appends itself to its list. (``laguna_q.preset`` reports the same reading as
``window_attention_ms_per_grad_step``, whose list a test of the benchmark
holds to that one cell: ``PERF.md`` §7.) Left out where the program keeps no
such names. See ``_children.py``."""
from perf.metrics import _children


def read(run, trace):
    return _children.ms_per_grad_step(run, trace, "CORE_PARTS",
                                      "attention_window")
