"""Host loop: see ``perf/harness/estimator.py host_loop_summary``, which the
harness computes once into the run's record."""


def read(run, trace):
    return run["host_loop"]["chunk_host_gap_ms"]
