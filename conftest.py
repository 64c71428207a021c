"""Pytest bootstrap: force tests onto a virtual 8-device CPU mesh.

Multi-chip sharding paths (shard_map/psum over the ICI mesh) are exercised on
CPU with ``--xla_force_host_platform_device_count=8`` per SURVEY.md §4, so
the full test suite runs anywhere, including machines where a real
accelerator is present. XLA_FLAGS must be set before the backend initializes.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Tests compile from scratch: the entry points they call place JAX's
# persistent compilation cache in the checkout (utils/backend.py), and a
# test must neither read entries an earlier run left there nor write any.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# Transport payload checksums on under test (race/corruption detection;
# off by default in production for throughput — actors/transport.py).
os.environ.setdefault("DQN_TRANSPORT_CRC", "1")

import pytest  # noqa: E402


def pytest_collection_finish(session):
    """Fail loudly when mark filtering empties an explicitly named file.

    pyproject's ``addopts = -m 'not slow'`` applies to EVERY invocation,
    so ``pytest tests/test_multihost.py`` (an all-slow file) would
    otherwise pass with zero tests executed — a false green. Runs after
    pytest's own mark deselection (collection
    *finish*, not modifyitems, which conftest hooks enter too early):
    if the user named specific test files/nodes on the command line and
    the final selection contains nothing from one of them, error out.
    """
    config = session.config
    markexpr = config.getoption("-m", default="")
    if not markexpr:
        return
    # Other filters can legitimately empty a file — only the mark
    # expression (which addopts injects into EVERY run) warrants the
    # loud failure, so stand down when -k/--deselect are in play.
    if config.getoption("-k", default="") or \
            config.getoption("--deselect", default=None):
        return
    named = [a for a in config.args if ".py" in a]
    if not named:
        return
    import pathlib

    kept = {str(item.path) for item in session.items}
    for arg in named:
        path = str(pathlib.Path(arg.split("::")[0]).resolve())
        if path not in kept:
            raise pytest.UsageError(
                f"mark expression {markexpr!r} deselected every test in "
                f"explicitly named {arg} — a false green. Re-run with "
                f"-m 'slow or not slow' to override pyproject's default "
                f"'not slow' selection.")
