"""Chunk program: share of the loop's op time that the stage table leaves
under no stage or under ``mixed`` — the health of the instrument itself. 100
with an empty table (the names were lost); left out where the program keeps
no table."""
from perf.metrics import _stages


def read(run, trace):
    return _stages.unattributed_share(run, trace)
