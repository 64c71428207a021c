"""The benchmark of dist_dqn_tpu: ``python3 perf/run.py`` (see README.md)."""
