"""What the host can say about the chip without reading a trace: where a
host runtime's chunk wall went, the allocator's counters, and a profile on
demand.

How busy the chip is, and on what, is read from a device trace through the
program's own names (``telemetry/stages.py``, the ``fused.*`` spans) —
``perf/`` reduces one, ``--profile-dir`` / ``/debug/profile`` write one.
Nothing here prices a program's FLOPs or calls a host wall device time.

``UtilizationLedger``
    For the host runtimes (host_replay_loop.py, actors/service.py), whose
    loops really wait at named seams: each chunk's wall split into the
    train section's wall (``busy``) and the stalls ``sample | evac_fence |
    prefetch_wait | h2d | other``, fed to
    ``dqn_chip_busy_seconds_total{loop}`` /
    ``dqn_chip_idle_seconds_total{loop, cause}``. All host walls.

``sweep_device_memory``
    ``Device.memory_stats()`` -> ``dqn_device_memory_bytes{kind, device}``
    gauges with a host-tracked peak; every loop calls it once a chunk.

``capture_profile`` / ``maybe_trace_first_chunk``
    The ``/debug/profile?seconds=N`` backend (a jax.profiler trace into
    the forensics dir) and the host runtimes' ``--profile-dir`` one-shot.

``memory_stats() is None`` (the CPU) sweeps to nothing, and jax itself is
imported lazily so the module stays importable from jax-free actor
processes.
"""
from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Any, Dict, Optional

from dist_dqn_tpu.telemetry import collectors as tmc
from dist_dqn_tpu.telemetry.registry import Registry, get_registry

#: Fixed idle-cause vocabulary for dqn_chip_idle_seconds_total. Keep in
#: lockstep with the docs/observability.md naming table.
IDLE_CAUSES = ("sample", "evac_fence", "prefetch_wait", "h2d", "other")

#: Hard ceiling on one /debug/profile capture; xprof windows past this
#: are better taken as several correlated short ones.
PROFILE_MAX_SECONDS = 60.0


class UtilizationLedger:
    """Per-chunk wall-time decomposition for one loop.

    ``observe_chunk(wall_s, busy_s, sample=..., evac_fence=...,
    prefetch_wait=..., h2d=...)`` files the measured device-busy time
    under ``dqn_chip_busy_seconds_total{loop}`` and the host-blocked
    remainder under ``dqn_chip_idle_seconds_total{loop, cause}``;
    whatever wall-time the named causes don't explain lands in
    ``other`` (clamped at zero — the buckets are estimates sampled at
    existing fences, never allowed to go negative). All five cause
    series are registered up front so the family is scrapeable at 0
    before the first chunk and dashboards never see a hole.
    """

    def __init__(self, loop: str, reg: Optional[Registry] = None):
        if reg is None:
            reg = get_registry()
        self.loop = loop
        self._busy = reg.counter(
            tmc.CHIP_BUSY_SECONDS,
            "chunk wall-time the device was measured busy",
            {"loop": loop})
        self._idle = {
            cause: reg.counter(
                tmc.CHIP_IDLE_SECONDS,
                "chunk wall-time the device sat idle, by cause",
                {"loop": loop, "cause": cause})
            for cause in IDLE_CAUSES
        }
        self.chunks = 0
        self.totals: Dict[str, float] = {"busy": 0.0}
        self.totals.update({c: 0.0 for c in IDLE_CAUSES})

    def observe_chunk(self, wall_s: float, busy_s: float,
                      sample: float = 0.0, evac_fence: float = 0.0,
                      prefetch_wait: float = 0.0,
                      h2d: float = 0.0) -> Dict[str, float]:
        """File one chunk; returns the breakdown (incl. the derived
        ``other`` residual) for the caller's own log row."""
        wall_s = max(float(wall_s), 0.0)
        busy_s = min(max(float(busy_s), 0.0), wall_s)
        named = {"sample": max(float(sample), 0.0),
                 "evac_fence": max(float(evac_fence), 0.0),
                 "prefetch_wait": max(float(prefetch_wait), 0.0),
                 "h2d": max(float(h2d), 0.0)}
        named["other"] = max(wall_s - busy_s - sum(named.values()), 0.0)
        self._busy.inc(busy_s)
        self.totals["busy"] += busy_s
        for cause, secs in named.items():
            if secs > 0:
                self._idle[cause].inc(secs)
            self.totals[cause] += secs
        self.chunks += 1
        out = {"wall": wall_s, "busy": busy_s}
        out.update(named)
        return out

    def snapshot(self) -> Dict[str, float]:
        return {"chunks": float(self.chunks), **self.totals}


# ---------------------------------------------------------------------------
# Device memory telemetry


_mem_lock = threading.Lock()
_mem_peaks: Dict[str, float] = {}


def sweep_device_memory(reg: Optional[Registry] = None,
                        devices: Any = None) -> Dict[str, Dict[str, float]]:
    """Sweep ``Device.memory_stats()`` into
    ``dqn_device_memory_bytes{kind, device}`` gauges.

    Backends that report nothing (CPU returns ``None``) or partial
    dicts sweep to exactly the keys they report — gauges degrade to
    absent, never crash. ``bytes_in_use`` additionally feeds a
    host-tracked high-water mark published as
    ``kind="peak_bytes_in_use_seen"`` (native ``peak_bytes_in_use``
    resets on some backends). Returns {device_label: {kind: bytes}}
    of what was actually swept (empty dict when nothing reported).
    """
    if reg is None:
        reg = get_registry()
    if devices is None:
        try:
            import jax
            devices = jax.local_devices()
        except Exception:
            return {}
    swept: Dict[str, Dict[str, float]] = {}
    for i, dev in enumerate(devices):
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        label = str(getattr(dev, "id", i))
        kinds: Dict[str, float] = {}
        for kind, value in stats.items():
            try:
                value = float(value)
            except (TypeError, ValueError):
                continue
            kinds[kind] = value
            reg.gauge(tmc.DEVICE_MEMORY_BYTES,
                      "Device.memory_stats() sweep",
                      {"kind": str(kind), "device": label}).set(value)
        in_use = kinds.get("bytes_in_use")
        if in_use is not None:
            with _mem_lock:
                peak = max(_mem_peaks.get(label, 0.0), in_use)
                _mem_peaks[label] = peak
            kinds["peak_bytes_in_use_seen"] = peak
            reg.gauge(tmc.DEVICE_MEMORY_BYTES,
                      "host-tracked high-water mark of bytes_in_use",
                      {"kind": "peak_bytes_in_use_seen",
                       "device": label}).set(peak)
        if kinds:
            swept[label] = kinds
    return swept


# ---------------------------------------------------------------------------
# On-demand profiling (/debug/profile backend)


_profile_lock = threading.Lock()


def _profile_base_dir() -> str:
    """Where captures land: the armed watchdog/sentinel forensics dir,
    else $DQN_FORENSICS_DIR, else a tempdir — same resolution order the
    crash path uses, so traces sit next to the forensics bundles."""
    from dist_dqn_tpu.telemetry import watchdog as wd
    for get in (wd.get_watchdog, getattr(wd, "get_sentinel", None)):
        if get is None:
            continue
        try:
            holder = get()
        except Exception:
            continue
        d = getattr(holder, "forensics_dir", None)
        if d:
            return str(d)
    env = os.environ.get(wd.FORENSICS_ENV)
    if env:
        return env
    return tempfile.mkdtemp(prefix="dqn-profile-")


def capture_profile(seconds: float,
                    base_dir: Optional[str] = None) -> Dict[str, Any]:
    """Capture a ``jax.profiler`` trace for ``seconds`` (clamped to
    [0, PROFILE_MAX_SECONDS]) into a fresh subdirectory of the
    forensics dir. Serialized process-wide: a second caller while a
    capture is running gets ``{"error": "busy"}`` instead of corrupting
    the active trace. ``seconds=0`` opens and immediately closes the
    trace window — cheap smoke-path for tests and endpoint probes.
    """
    try:
        seconds = max(0.0, min(float(seconds), PROFILE_MAX_SECONDS))
    except (TypeError, ValueError):
        return {"error": f"bad seconds value: {seconds!r}"}
    if not _profile_lock.acquire(blocking=False):
        return {"error": "busy", "detail": "a capture is already running"}
    try:
        try:
            import jax.profiler
        except Exception as e:  # jax-free process (actor-side server)
            return {"error": f"jax unavailable: {e}"}
        base = base_dir or _profile_base_dir()
        trace_dir = os.path.join(
            base, f"profile-{os.getpid()}-{int(time.time() * 1000)}")
        os.makedirs(trace_dir, exist_ok=True)
        t0 = time.perf_counter()
        try:
            jax.profiler.start_trace(trace_dir)
            if seconds > 0:
                time.sleep(seconds)
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                return {"error": f"stop_trace failed: {e}",
                        "trace_dir": trace_dir}
        n_files = sum(len(fns) for _, _, fns in os.walk(trace_dir))
        return {"trace_dir": trace_dir,
                "seconds": seconds,
                "capture_wall_s": time.perf_counter() - t0,
                "files": n_files}
    finally:
        _profile_lock.release()


def maybe_trace_first_chunk(profile_dir: Optional[str]):
    """The --profile-dir contract, shared by all three runtimes: a
    context-manager-shaped pair of (start, stop) callables that trace
    exactly one post-warmup chunk into ``profile_dir`` and are no-ops
    when it is unset or jax.profiler is unavailable."""

    class _OneShot:
        def __init__(self, target: Optional[str]):
            self._target = target
            self._armed = bool(target)
            self._active = False

        def start(self) -> None:
            if not self._armed or self._active:
                return
            try:
                import jax.profiler
                jax.profiler.start_trace(self._target)
                self._active = True
            except Exception:
                self._armed = False

        def stop(self) -> Optional[str]:
            if not self._active:
                return None
            try:
                import jax.profiler
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._active = False
            self._armed = False  # one shot
            return self._target

    return _OneShot(profile_dir)
