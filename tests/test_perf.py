"""Tier-1 collects the benchmark's own tests (``perf/tests/test_perf_*.py``:
estimator, manifest, reference, trace reduction, the per-stage readers, the
toy cell on the CPU) by importing their test functions and fixtures here, so
a later PR cannot break the yardstick unseen. A file added under
``perf/tests`` is picked up by name; ``python -m pytest perf/tests -q`` runs
the same cases alone."""
import importlib
import pkgutil

import perf.tests

_collected = {}
for _info in pkgutil.iter_modules(perf.tests.__path__):
    if not _info.name.startswith("test_perf_"):
        continue
    _module = importlib.import_module(f"perf.tests.{_info.name}")
    for _name, _value in vars(_module).items():
        # test functions and fixtures (pytest looks both up by name in the
        # collecting module); helpers stay in their own module's globals
        if not (_name.startswith("test_")
                or type(_value).__name__ == "FixtureFunctionDefinition"):
            continue
        assert _collected.setdefault(_name, _info.name) == _info.name, (
            f"{_name} is defined in both perf/tests/{_collected[_name]}.py "
            f"and perf/tests/{_info.name}.py: one would hide the other")
        globals()[_name] = _value
