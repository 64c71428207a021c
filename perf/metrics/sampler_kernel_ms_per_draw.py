"""The Mosaic kernel ``per_stratified_sample``: summed device duration of
its custom call over its calls (one call draws one batch)."""
KERNEL = "per_stratified_sample"


def read(run, trace):
    calls = [o for d in trace.devices for o in d.leaves
             if o.op == "custom-call" and o.inst.startswith(KERNEL)]
    if not calls:
        return None
    return 1e-6 * sum(o.duration for o in calls) / len(calls)
