"""Chunk program: device time a chunk spends OUTSIDE its iteration loop —
what the program does once per chunk (the ring's layout copies at entry and
exit), mean over the devices traced."""


def read(run, trace):
    if not trace.devices or not run["traced_chunks"]:
        return None
    values = [d.outside_loop_seconds() for d in trace.devices]
    return 1e3 * sum(values) / len(values) / run["traced_chunks"]
