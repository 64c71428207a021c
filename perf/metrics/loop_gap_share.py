"""Chunk program: share of the iteration loop (the outermost ``while``) in
which no op runs on the device, mean over the devices traced: launch gaps
between the small ops of an iteration."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.loop_gap_share(trace)
