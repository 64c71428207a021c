"""Shared arithmetic of the readers of stage ``loss_grad``'s child names
(PR 42).

The program's stage table gives every instruction its stage; a second table
over a group of child names (``dist_dqn_tpu/telemetry/stages.py``:
``PASSES``, the recurrent learner's three network passes, entered as
scopes; ``PARTS``, the recurrent network's two sub-modules, read off the op
paths) splits the instructions of stage ``loss_grad`` and no others. A
child therefore never takes time from ``loss_grad_ms_per_grad_step``: what
a group leaves under no child (or ``mixed``) is the stage's own time.
Everything here returns None where there is nothing to read — a program
from before child names has no such groups — and never raises or guesses.
"""
from perf.metrics import _stages
from perf.reduce.trace_reduce import NS

UNSPLIT = _stages.UNATTRIBUTED


def children(run, group):
    """``{instruction: child}`` over the instructions of stage ``loss_grad``
    for the group of that name (``"PASSES"`` or ``"PARTS"``), built on the
    first call, after the window; None where the program keeps no such
    group. What the group's walk cost is left in the run's record
    (``child_tables``)."""
    try:
        from dist_dqn_tpu.telemetry import stages
    except ImportError:
        return None
    names = getattr(stages, group, None)
    if names is None:
        return None
    found = stages.children(names)
    run.setdefault("child_tables", {})[group] = {
        "seconds": stages.table_seconds(names), "instructions": len(found)}
    return found


def child_seconds(run, trace, group):
    """Per device ``{child: seconds}`` of the leaf ops inside the iteration
    loop whose stage is ``loss_grad``, None and ``mixed`` among the keys;
    None where the program keeps no such group, the device ran no loop or
    no op of the stage. Computed once per trace and group."""
    split = children(run, group)
    cache = vars(trace).setdefault("_child_seconds", {})
    if group in cache and cache[group][0] is split:
        return cache[group][1]
    per_device = []
    for device in trace.devices if split else ():
        found = _stages._in_loop(device)
        if found is None:
            per_device = []
            break
        totals = {}
        for op in found[0]:
            if op.inst in split:
                child = split[op.inst]
                totals[child] = totals.get(child, 0.0) + op.duration * NS
        per_device.append(totals)
    cache[group] = (split, per_device if any(per_device) else None)
    return cache[group][1]


def ms_per_grad_step(run, trace, group, child):
    """Mean over devices of the op time under ``child``, per grad step;
    None where no op ran under it."""
    per_device = child_seconds(run, trace, group)
    if per_device is None or not any(child in d for d in per_device):
        return None
    seconds = sum(d.get(child, 0.0) for d in per_device) / len(per_device)
    grad_steps = run["traced_chunks"] * run["grad_steps_per_chunk"]
    return 1e3 * seconds / grad_steps if seconds and grad_steps else None


def unsplit_share(run, trace, group):
    """Percent of stage ``loss_grad``'s op time that the group leaves under
    no child or ``mixed``, mean over devices: 100 where the names were
    lost."""
    per_device = child_seconds(run, trace, group)
    if per_device is None:
        return None
    shares = [sum(d.get(c, 0.0) for c in UNSPLIT) / sum(d.values())
              for d in per_device if sum(d.values())]
    return 100.0 * sum(shares) / len(shares) if shares else None
