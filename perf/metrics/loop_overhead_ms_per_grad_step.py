"""Train step: device time of the ops of stage ``loss_grad`` that lie under
the scope ``loops`` — the stack of a core that runs it several times over
shared weights (``HybridQNetwork.turns``; group ``LOOPS`` of
``telemetry/stages.py``) — and under NO name of ``CORE_PARTS`` (none, or
``mixed``), per grad step: the sublayers' norms on input and output, the
residual adds, the norm after each turn, and whatever the loop's form adds
(the turns are written out: the sum of a weight's gradients over its turns;
one scanned body would add its stacked residuals and the slices of its
stacked state here). The mixers' readers
(``attention_full_``, ``mlp_dense_ms_per_grad_step``) and this one share no
instruction. Left out where the program keeps no such group (a program from
before the scope) or runs nothing under it (a core that runs its stack
once). See ``_children.py``."""
from perf.metrics import _children, _stages
from perf.reduce.trace_reduce import NS


def read(run, trace):
    under = _children.children(run, "LOOPS")
    held = _children.children(run, "CORE_PARTS")
    if not under or held is None:
        return None
    own = {inst for inst, child in under.items()
           if child == "loops" and held.get(inst) in _children.UNSPLIT}
    seconds = []
    for device in trace.devices:
        found = _stages._in_loop(device)
        if found is None:
            return None
        seconds.append(NS * sum(op.duration for op in found[0]
                                if op.inst in own))
    grad_steps = run["traced_chunks"] * run["grad_steps_per_chunk"]
    total = sum(seconds) / len(seconds) if seconds else 0.0
    return 1e3 * total / grad_steps if total and grad_steps else None
