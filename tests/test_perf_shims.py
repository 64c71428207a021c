"""Every module under ``perf/tests`` is collected by tier-1
(tests/_perf_shim.py)."""
from _perf_shim import HERE, SHIM, module_names


def test_every_perf_test_module_has_its_shim_and_no_shim_is_left_over():
    shims = {path.stem: path.read_text()
             for path in HERE.glob("test_perf_*.py")
             if path.stem != "test_perf_shims"}
    assert sorted(shims) == module_names(), (
        "run `python tests/_perf_shim.py` (and delete a shim whose module "
        "is gone)")
    for name, text in shims.items():
        assert text == SHIM.format(name=name), name
