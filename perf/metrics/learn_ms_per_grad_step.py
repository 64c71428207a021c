"""Sample + gather + train step: device time inside the train
``conditional`` per grad step, mean over the devices traced."""


def read(run, trace):
    grad_steps = run["traced_chunks"] * run["grad_steps_per_chunk"]
    values = []
    for d in trace.devices:
        cond = d.train_conditional()
        if cond is None:
            return None
        values.append(1e3 * cond[0] / grad_steps)
    return sum(values) / len(values) if values and grad_steps else None
