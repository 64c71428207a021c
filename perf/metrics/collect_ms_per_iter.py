"""Act / env / insert: device time of the iteration loop outside the train
``conditional``, per iteration, mean over the devices traced."""


def read(run, trace):
    iterations = run["traced_chunks"] * run["chunk_iters"]
    values = []
    for d in trace.devices:
        loop, cond = d.iteration_loop_seconds(), d.train_conditional()
        if loop is None or cond is None:
            return None
        values.append(1e3 * (loop - cond[0]) / iterations)
    return sum(values) / len(values) if values and iterations else None
