"""Mesh: all-reduce device time during which no other op runs on that
device, per grad step, mean over the devices traced."""


def read(run, trace):
    grad_steps = run["traced_chunks"] * run["grad_steps_per_chunk"]
    if not trace.devices or not grad_steps:
        return None
    exposed = [d.exposed_collective_seconds() for d in trace.devices]
    if None in exposed:
        return None
    return 1e3 * sum(exposed) / len(exposed) / grad_steps
