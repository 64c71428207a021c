"""Chip-time attribution plane (ISSUE 19): per-program device-time
ledger, utilization decomposition, on-demand profiling, HBM telemetry.

Nothing else in the repo can say how busy the chip is or *why* it is
idle: ``dqn_learner_mfu`` was hand-wired per runtime and no metric
attributed chunk wall-time to device-busy vs host-blocked causes. This
module is the shared substrate:

``ProgramRegistry``
    Process-wide table of every jitted entry point (fused chunk,
    collect, train/scan-train step, act dispatch, sampler draw,
    evac split). Each :class:`ProgramRecord` carries FLOPs/bytes from
    the XLA cost analysis (``utils/flops.py``), dispatch counts, and
    device-seconds sampled at fences the loops ALREADY hold — no new
    synchronization on the hot path. Cost is harvested lazily via
    ``jitted.lower(*args)`` at the first dispatch site: its own
    ``cost_analysis()`` on the CPU (trace-only), the compiled
    executable's on a TPU (``_cost_from``; the dispatch reuses that
    executable, so no program is compiled twice).

``UtilizationLedger``
    Decomposes each chunk's wall-time into device-busy plus the named
    host-blocked buckets ``sample | evac_fence | prefetch_wait | h2d |
    other`` and feeds the ``dqn_chip_idle_seconds_total{cause}`` /
    ``dqn_chip_busy_seconds_total`` families.

``set_learner_mfu``
    The registry-derived replacement for the per-loop MFU hand-wirings:
    FLOPs-per-exec x executions / device-seconds over the chip's bf16
    peak.

``sweep_device_memory`` / ``capture_profile``
    ``Device.memory_stats()`` -> ``dqn_device_memory_bytes{kind,device}``
    gauges with host-tracked peak, and the ``/debug/profile?seconds=N``
    backend (jax.profiler trace into the forensics dir).

Everything degrades on CPU: cost analysis that fails leaves FLOPs
``None`` (gauges absent, never a crash), ``memory_stats() is None``
sweeps to nothing, and jax itself is imported lazily so the module
stays importable from jax-free actor processes.
"""
from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional

from dist_dqn_tpu.telemetry import collectors as tmc
from dist_dqn_tpu.telemetry.registry import Registry, get_registry
from dist_dqn_tpu.utils import flops as flops_util

#: Fixed idle-cause vocabulary for dqn_chip_idle_seconds_total. Keep in
#: lockstep with the docs/observability.md naming table.
IDLE_CAUSES = ("sample", "evac_fence", "prefetch_wait", "h2d", "other")

#: Hard ceiling on one /debug/profile capture; xprof windows past this
#: are better taken as several correlated short ones.
PROFILE_MAX_SECONDS = 60.0


def _cost_from(obj: Any) -> Dict[str, Optional[float]]:
    """FLOPs/bytes for one execution of ``obj`` — a Compiled, a Lowered,
    or a zero-arg callable returning either. Any failure (CPU backends
    without a cost model, interpreter mode, tracing errors) degrades to
    ``{"flops": None, "bytes": None}``.

    A Lowered has a cost analysis on the CPU backend only; for a TPU it
    returns None and the census needs the compiled executable. Compiling
    the Lowered here is the compilation the first dispatch would do:
    the jit call that follows reuses the executable from JAX's
    in-memory cache (observed on the chip: a cold first chunk of 0.18 s
    with no persistent-cache hit), so the compile moves ahead of the
    first dispatch, it is not paid twice."""
    try:
        if callable(obj) and not hasattr(obj, "cost_analysis"):
            obj = obj()
        flops = flops_util.compiled_flops(obj)
        nbytes = flops_util.compiled_bytes(obj)
        if flops is None and nbytes is None and hasattr(obj, "compile"):
            obj = obj.compile()
            flops = flops_util.compiled_flops(obj)
            nbytes = flops_util.compiled_bytes(obj)
    except Exception:
        flops = nbytes = None
    return {"flops": flops, "bytes": nbytes}


class ProgramRecord:
    """One jitted entry point: static cost + running dispatch tallies.

    ``flops``/``bytes`` are for ONE execution of the compiled program.
    Caveat inherited from the XLA cost census: a ``lax.scan`` body is
    counted once regardless of trip count, so scan-shaped programs
    should register with ``execs_per_dispatch`` = trip count to keep
    FLOPs x executions honest.
    """

    def __init__(self, registry: "ProgramRegistry", name: str, loop: str,
                 role: Optional[str], execs_per_dispatch: float):
        self._registry = registry
        self.name = name
        self.loop = loop
        self.role = role
        self.execs_per_dispatch = float(execs_per_dispatch)
        self.flops: Optional[float] = None
        self.bytes: Optional[float] = None
        self._cost_done = False
        self._lock = threading.Lock()
        labels = {"program": name, "loop": loop}
        reg = registry.metrics
        self._g_flops = reg.gauge(
            tmc.PROGRAM_FLOPS, "FLOPs per execution (XLA cost analysis)",
            labels)
        self._g_bytes = reg.gauge(
            tmc.PROGRAM_BYTES, "bytes accessed per execution", labels)
        self._c_dispatch = reg.counter(
            tmc.PROGRAM_DISPATCHES, "host-side launches", labels)
        self._c_devsec = reg.counter(
            tmc.PROGRAM_DEVICE_SECONDS,
            "device time attributed at existing fences", labels)
        self.dispatches = 0.0
        self.device_seconds = 0.0

    def attach_cost(self, source: Any) -> "ProgramRecord":
        """Harvest FLOPs/bytes once from ``source`` (Compiled / Lowered /
        zero-arg callable returning either). Idempotent: the first
        successful harvest wins; repeat calls and failures are free, so
        dispatch sites can call this unconditionally."""
        with self._lock:
            if self._cost_done:
                return self
            cost = _cost_from(source)
            if cost["flops"] is None and cost["bytes"] is None:
                # Leave _cost_done False only for *callables* that may
                # succeed later? No: retrying a failing trace every
                # dispatch is hot-path work. One shot, like the fences.
                self._cost_done = True
                return self
            self.flops, self.bytes = cost["flops"], cost["bytes"]
            self._cost_done = True
        if self.flops is not None:
            self._g_flops.set(self.flops)
        if self.bytes is not None:
            self._g_bytes.set(self.bytes)
        return self

    @property
    def cost_attached(self) -> bool:
        return self._cost_done

    def count_dispatch(self, n: float = 1.0) -> None:
        self.dispatches += n
        self._c_dispatch.inc(n)

    def add_device_seconds(self, seconds: float) -> None:
        if seconds <= 0:
            return
        self.device_seconds += seconds
        self._c_devsec.inc(seconds)

    @property
    def executions(self) -> float:
        return self.dispatches * self.execs_per_dispatch

    @property
    def arith_intensity(self) -> Optional[float]:
        if self.flops is None or not self.bytes:
            return None
        return self.flops / self.bytes

    def snapshot(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "program": self.name,
            "loop": self.loop,
            "flops": self.flops,
            "bytes": self.bytes,
            "dispatches": self.dispatches,
            "execs_per_dispatch": self.execs_per_dispatch,
            "device_seconds": self.device_seconds,
        }
        ai = self.arith_intensity
        if ai is not None:
            out["arith_intensity"] = ai
        return out


class ProgramRegistry:
    """Process-wide (name, loop) -> :class:`ProgramRecord` table."""

    def __init__(self, metrics: Optional[Registry] = None):
        self.metrics = metrics if metrics is not None else get_registry()
        self._records: Dict[tuple, ProgramRecord] = {}
        self._lock = threading.RLock()

    def register(self, name: str, loop: str = "default",
                 cost: Any = None, role: Optional[str] = None,
                 execs_per_dispatch: float = 1.0) -> ProgramRecord:
        """Get-or-create the record for ``(name, loop)``. ``cost`` (a
        Compiled/Lowered/zero-arg callable) is attached immediately when
        given; dispatch sites that only have real args later can call
        ``record.attach_cost`` themselves."""
        key = (name, loop)
        with self._lock:
            rec = self._records.get(key)
            if rec is None:
                rec = ProgramRecord(self, name, loop, role,
                                    execs_per_dispatch)
                self._records[key] = rec
            elif role is not None and rec.role is None:
                rec.role = role
        if cost is not None:
            rec.attach_cost(cost)
        return rec

    def records(self, loop: Optional[str] = None):
        with self._lock:
            recs = list(self._records.values())
        if loop is not None:
            recs = [r for r in recs if r.loop == loop]
        return recs

    def get(self, name: str, loop: str = "default"):
        with self._lock:
            return self._records.get((name, loop))

    def snapshot(self, loop: Optional[str] = None) -> Dict[str, Dict]:
        """JSON-able {program: fields} block for BENCH rows."""
        return {r.name: r.snapshot() for r in self.records(loop)}

    def learner_mfu(self, loop: str,
                    device: Any = None) -> Optional[float]:
        """Registry-derived MFU for ``loop``: summed FLOPs x executions
        over summed device-seconds of every record tagged role="train",
        against the chip's bf16 peak. None when no train program has
        both cost and device time, or the chip peak is unknown (CPU)."""
        if device is None:
            device = _default_device()
        if device is None:
            return None
        peak = flops_util.chip_peak_flops(device)
        if not peak:
            return None
        total_flops = 0.0
        total_secs = 0.0
        for rec in self.records(loop):
            if rec.role != "train" or rec.flops is None:
                continue
            total_flops += rec.flops * rec.executions
            total_secs += rec.device_seconds
        if total_secs <= 0 or total_flops <= 0:
            return None
        return (total_flops / total_secs) / peak


_program_registry = ProgramRegistry()


def get_program_registry() -> ProgramRegistry:
    """The process-global program registry (what every loop uses)."""
    return _program_registry


def reset_program_registry(metrics: Optional[Registry] = None
                           ) -> ProgramRegistry:
    """Swap in a fresh registry (tests / multi-leg benchmarks that want
    per-leg dispatch tallies). Returns the new instance."""
    global _program_registry
    _program_registry = ProgramRegistry(metrics)
    return _program_registry


def register_program(name: str, loop: str = "default", cost: Any = None,
                     role: Optional[str] = None,
                     execs_per_dispatch: float = 1.0) -> ProgramRecord:
    """Module-level convenience for the common dispatch-site idiom."""
    return _program_registry.register(
        name, loop=loop, cost=cost, role=role,
        execs_per_dispatch=execs_per_dispatch)


def programs_snapshot(loop: Optional[str] = None) -> Dict[str, Dict]:
    return _program_registry.snapshot(loop)


def _default_device():
    try:
        import jax
        return jax.devices()[0]
    except Exception:
        return None


def set_learner_mfu(loop: str, device: Any = None,
                    reg: Optional[Registry] = None) -> Optional[float]:
    """Publish the registry-derived ``dqn_learner_mfu{loop=...}`` gauge.
    No-op (gauge absent) when the MFU is underivable — unknown chip
    peak, no cost analysis, no device time yet."""
    value = _program_registry.learner_mfu(loop, device=device)
    if value is None:
        return None
    if reg is None:
        reg = get_registry()
    reg.gauge(tmc.LEARNER_MFU, "model FLOPs utilization vs chip peak "
              "(registry-derived)", {"loop": loop}).set(value)
    return value


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
