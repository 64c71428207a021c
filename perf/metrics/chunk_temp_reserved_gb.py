"""Chunk program: the allocator's peak reservation for a running program's
temporaries on the fullest device (``peak_bytes_reserved``), which
``hbm_peak_gb`` (``peak_bytes_in_use``: live buffers) does not see. In these
cells it is the second copy of the replay ring that every chunk program
makes (PERF.md section 5)."""


def read(run, trace):
    reserved = run["memory_stats"].get("peak_bytes_reserved")
    return None if reserved is None else reserved / 1e9
