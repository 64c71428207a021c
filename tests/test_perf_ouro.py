"""Tier-1's shim of perf/tests/test_perf_ouro.py (tests/_perf_shim.py)."""
from _perf_shim import adopt

adopt(globals())
