"""Persistent-cache misses of the whole run: 0 after a cell's first run in a
checkout. (Compiles inside the window are held to 0 by ``correct``.)"""


def read(run, trace):
    return float(run["compile"]["total"]["cache_misses"])
