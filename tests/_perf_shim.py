"""Tier-1 collects the benchmark's own tests (``perf/tests/test_perf_*.py``:
estimator, manifest, references, trace reduction, the per-stage readers, the
toy cells on the CPU), so a later PR cannot break the yardstick unseen.

One shim a module — ``tests/test_perf_<name>.py`` adopts the test functions
and fixtures of ``perf/tests/test_perf_<name>.py`` — so that the driver's
``--dist loadfile`` spreads them over its workers. ``python
tests/_perf_shim.py`` writes the shims that are missing (a new file under
``perf/tests`` without one fails ``tests/test_perf_shims.py``);
``python -m pytest perf/tests -q`` runs the same cases alone."""
import ast
import functools
import importlib
from pathlib import Path

HERE = Path(__file__).resolve().parent
PERF_TESTS = HERE.parent / "perf" / "tests"
SHIM = '''"""Tier-1's shim of perf/tests/{name}.py (tests/_perf_shim.py)."""
from _perf_shim import adopt

adopt(globals())
'''


def module_names() -> list:
    return sorted(path.stem for path in PERF_TESTS.glob("test_perf_*.py"))


@functools.lru_cache(maxsize=None)
def check_test_names_are_unique() -> None:
    """A test name stands for one case across the directory (read from the
    source: no module is imported for it)."""
    seen = {}
    for name in module_names():
        tree = ast.parse((PERF_TESTS / f"{name}.py").read_text())
        for node in tree.body:
            if isinstance(node, ast.FunctionDef) \
                    and node.name.startswith("test_"):
                assert seen.setdefault(node.name, name) == name, (
                    f"{node.name} is defined in both perf/tests/"
                    f"{seen[node.name]}.py and perf/tests/{name}.py")


def adopt(shim_globals: dict) -> None:
    """Put the test functions and fixtures of the shim's own module among
    the shim's globals (pytest looks both up by name in the collecting
    module); helpers stay in their own module's globals."""
    check_test_names_are_unique()
    name = Path(shim_globals["__file__"]).stem
    module = importlib.import_module(f"perf.tests.{name}")
    shim_globals.update({
        key: value for key, value in vars(module).items()
        if key.startswith("test_")
        or type(value).__name__ == "FixtureFunctionDefinition"})


if __name__ == "__main__":
    for name in module_names():
        shim = HERE / f"{name}.py"
        if not shim.exists():
            shim.write_text(SHIM.format(name=name))
            print(f"wrote {shim.relative_to(HERE.parent)}")
