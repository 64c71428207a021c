"""Direct coverage for the shared flush lifecycle
(telemetry/lifecycle.py) — previously only exercised implicitly through
SpanTracer/snapshot tests (ISSUE 4 satellite): the run-once latch
(double-flush idempotency), callback ordering, and the atexit-after-
SIGTERM leg that must NOT flush a second time.
"""
import signal
import subprocess
import sys
import time

from dist_dqn_tpu.telemetry import lifecycle


def test_run_callbacks_is_once_only_and_ordered():
    """The latch: a SIGTERM flush followed by the atexit leg (or two
    racing flush paths) runs every callback exactly once, in
    registration order."""
    lifecycle._reset_for_tests()
    try:
        calls = []
        lifecycle.on_exit(lambda: calls.append("a"))
        lifecycle.on_exit(lambda: calls.append("b"))
        lifecycle._run_callbacks()
        assert calls == ["a", "b"]
        lifecycle._run_callbacks()  # second leg: latched, no double flush
        assert calls == ["a", "b"]
    finally:
        lifecycle._reset_for_tests()


def test_late_registration_after_flush_does_not_retrigger():
    """A callback registered AFTER the once-latch fired stays unrun (the
    process is already exiting; surprising late side effects are worse
    than a lost flush) — pins the current contract."""
    lifecycle._reset_for_tests()
    try:
        calls = []
        lifecycle._run_callbacks()
        lifecycle.on_exit(lambda: calls.append("late"))
        lifecycle._run_callbacks()
        assert calls == []
    finally:
        lifecycle._reset_for_tests()


def test_off_exit_deregisters():
    lifecycle._reset_for_tests()
    try:
        calls = []
        fn = lambda: calls.append("x")  # noqa: E731
        lifecycle.on_exit(fn)
        lifecycle.off_exit(fn)
        lifecycle._run_callbacks()
        assert calls == []
        lifecycle.off_exit(fn)  # absent: no-op, no raise
    finally:
        lifecycle._reset_for_tests()


def _run_child(code: str, sig=None, timeout=30):
    """Run a child that writes `ready` when set up; optionally signal it;
    return the completed process."""
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, text=True)
    if sig is None:
        proc.wait(timeout=timeout)
        return proc
    deadline = time.time() + timeout
    line = proc.stdout.readline()
    assert line.strip() == "ready", f"child never became ready: {line!r}"
    assert time.time() < deadline
    proc.send_signal(sig)
    proc.wait(timeout=timeout)
    return proc


def test_sigterm_flushes_once_then_exits_128_plus_signum(tmp_path):
    """SIGTERM ordering: the handler runs the callbacks, the chained
    atexit leg must not run them again, and with no pre-existing handler
    the process exits 128+SIGTERM."""
    out = tmp_path / "flushes.txt"
    code = (
        "import sys\n"
        "from dist_dqn_tpu.telemetry import lifecycle\n"
        "lifecycle.on_exit(lambda: open(%r, 'a').write('flush\\n'))\n"
        "print('ready', flush=True)\n"
        "import time; time.sleep(60)\n" % str(out))
    proc = _run_child(code, sig=signal.SIGTERM)
    assert proc.returncode == 128 + signal.SIGTERM
    assert out.read_text() == "flush\n"  # exactly once


def test_sigterm_chains_preexisting_handler_after_flush(tmp_path):
    """A SIGTERM handler installed BEFORE the lifecycle still runs — after
    the flush callbacks, and the flush still happens exactly once."""
    out = tmp_path / "order.txt"
    code = (
        "import os, signal, sys, time\n"
        "def prev(signum, frame):\n"
        "    open(%r, 'a').write('prev\\n')\n"
        "    os._exit(7)\n"
        "signal.signal(signal.SIGTERM, prev)\n"
        "from dist_dqn_tpu.telemetry import lifecycle\n"
        "lifecycle.on_exit(lambda: open(%r, 'a').write('flush\\n'))\n"
        "print('ready', flush=True)\n"
        "time.sleep(60)\n" % (str(out), str(out)))
    proc = _run_child(code, sig=signal.SIGTERM)
    assert proc.returncode == 7  # the chained handler decided the exit
    assert out.read_text() == "flush\nprev\n"


def test_normal_exit_flushes_via_atexit(tmp_path):
    out = tmp_path / "flushes.txt"
    code = (
        "from dist_dqn_tpu.telemetry import lifecycle\n"
        "lifecycle.on_exit(lambda: open(%r, 'a').write('flush\\n'))\n"
        % str(out))
    proc = _run_child(code)
    assert proc.returncode == 0
    assert out.read_text() == "flush\n"
