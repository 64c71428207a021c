"""Tier-1's shim of perf/tests/test_perf_child_metrics.py (tests/_perf_shim.py)."""
from _perf_shim import adopt

adopt(globals())
