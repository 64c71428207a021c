"""Shared arithmetic of the per-stage readers (PR 26).

The device trace names each op by its HLO instruction; the program's stage
table (``dist_dqn_tpu/telemetry/stages.py table()``: instruction -> stage,
derived from the executable that ran) joins them to the stage names entered
with ``jax.named_scope``. Everything here returns None where there is
nothing to read — a program from before stage names existed has no such
module, no table and no ``fused.dispatch`` span — and never raises or
guesses.
"""
from perf.reduce.trace_reduce import NS, union_seconds

UNATTRIBUTED = (None, "mixed")


def table(run=None):
    """The program's ``{instruction: stage}`` table (built on the first
    call, after the window), or None where the program keeps none. What
    building it cost is left in the run's record (``stage_table``)."""
    try:
        from dist_dqn_tpu.telemetry import stages
    except ImportError:
        return None
    found = stages.table()
    if run is not None:
        run["stage_table"] = {"seconds": stages.table_seconds(),
                              "instructions": len(found)}
    return found


def _in_loop(device):
    """Leaves inside the iteration loop (the outermost ``while``s), and the
    loops' intervals; None where the device ran no such loop."""
    loops = [(o.start, o.end) for o in device.ops
             if o.op == "while" and o.depth == 0]
    if not loops:
        return None
    leaves = [o for o in device.leaves
              if o.depth and any(a <= o.start < b for a, b in loops)]
    return leaves, loops


def stage_seconds(run, trace):
    """Per device ``{stage: seconds}`` of the leaf ops inside the iteration
    loop, None and ``mixed`` among the keys; None where there is no table
    or no loop. Computed once per trace."""
    stage_of = table(run)
    cached = getattr(trace, "_stage_seconds", None)
    if cached is not None and cached[0] is stage_of:
        return cached[1] or None
    per_device = []
    for device in trace.devices if stage_of is not None else ():
        found = _in_loop(device)
        if found is None:
            per_device = []
            break
        totals = {}
        for op in found[0]:
            stage = stage_of.get(op.inst)
            totals[stage] = totals.get(stage, 0.0) + op.duration * NS
        per_device.append(totals)
    trace._stage_seconds = (stage_of, per_device)
    return per_device or None


def _mean_over_devices(run, trace, stages):
    """Mean over devices of the seconds under ``stages``; None where no op
    of any of them ran (the stage does not exist in this cell)."""
    per_device = stage_seconds(run, trace)
    if per_device is None or not any(s in d for d in per_device
                                     for s in stages):
        return None
    return sum(d.get(s, 0.0) for d in per_device
               for s in stages) / len(per_device)


def ms_per_iter(run, trace, *stages):
    seconds = _mean_over_devices(run, trace, stages)
    iterations = run["traced_chunks"] * run["chunk_iters"]
    return 1e3 * seconds / iterations if seconds and iterations else None


def ms_per_grad_step(run, trace, *stages):
    seconds = _mean_over_devices(run, trace, stages)
    grad_steps = run["traced_chunks"] * run["grad_steps_per_chunk"]
    return 1e3 * seconds / grad_steps if seconds and grad_steps else None


def unattributed_share(run, trace):
    """Percent of the loop's op time under no stage or ``mixed``: 100 with
    an empty table (names lost, e.g. to a stale compile cache)."""
    per_device = stage_seconds(run, trace)
    if per_device is None:
        return None
    shares = [sum(d.get(s, 0.0) for s in UNATTRIBUTED) / sum(d.values())
              for d in per_device if sum(d.values())]
    return 100.0 * sum(shares) / len(shares) if shares else None


def loop_gap_share(trace):
    """Percent of the iteration loop's duration in which no op ran, mean
    over devices. Needs no table."""
    shares = []
    for device in trace.devices:
        found = _in_loop(device)
        if found is None:
            return None
        leaves, loops = found
        inside = sum(b - a for a, b in loops) * NS
        busy = union_seconds([(o.start, o.end) for o in leaves])
        shares.append(1.0 - busy / inside)
    return 100.0 * sum(shares) / len(shares) if shares else None


def dispatch_ms(run):
    """``fused.dispatch`` span durations (ms) of the window's chunks, from
    the flight ring: the window's dispatches are the ones before the traced
    chunks'. None where the program records no such span."""
    try:
        from dist_dqn_tpu import telemetry
    except ImportError:
        return None
    spans = [e["dur_s"] for e in telemetry.get_flight().tail()
             if e["kind"] == "span" and e["name"] == "fused.dispatch"]
    n = len(run["series"]["cycle_s"])
    end = len(spans) - run["traced_chunks"]
    if not n or end < n:
        return None
    return [1e3 * s for s in spans[end - n:end]]
