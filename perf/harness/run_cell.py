"""One run of one cell: load, warm up, measure, check, print the last line.

The harness drives ``dist_dqn_tpu.train.train`` — the normal entry point —
through its own arguments (``log_fn``, ``stop_fn``, ``chunk_iters``,
``num_devices``) and sees nothing else of the program during the window.
Its ``stop_fn`` hook stamps ``time.perf_counter()`` as each chunk row
arrives: one reading is one whole loop cycle, fence to fence.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

from . import estimator
from .compile_meter import CompileMeter
from .manifest import Manifest, ManifestError, resolve_cell

CHECKOUT = Path(__file__).resolve().parents[2]
# More frames than any window reaches; ``stop_fn`` ends the run.
ENDLESS_FRAMES = 1 << 60
# A warm-up that never reaches its rule is a failure, not a long set-up.
MAX_WARMUP_CHUNKS = 400


class RunFailure(RuntimeError):
    """The run cannot report a result: no line is printed, exit code 1."""


class Recorder:
    """The ``stop_fn`` the harness hands to ``train.train``.

    Phases: warm-up (set-up) -> window (``seconds`` long, ends on a chunk
    boundary) -> optionally ``trace_chunks`` whole chunks inside a
    ``jax.profiler`` bracket -> stop. Each chunk row is kept as a dict with
    the harness's stamp: ``t`` (s, perf_counter at the row's arrival),
    ``wall`` (s, the trainer's own dispatch-to-fence wall), ``frames`` and
    ``grad_steps`` done in the chunk, ``loss``.
    """

    def __init__(self, plan: Dict, cfg, seconds: float, trace_dir:
                 Optional[Path], meter: CompileMeter):
        self.plan = plan
        self.seconds = seconds
        # Tests only (CPU): the window is this many chunks, whatever the
        # clock says, so that nothing in them times the CPU.
        self.window_chunks = plan["test_window_chunks"]
        self.trace_dir = trace_dir
        self.meter = meter
        self.lanes = cfg.actor.num_envs
        self.chunk_iters = int(plan["chunk_iters"])
        if self.chunk_iters % cfg.train_every:
            raise ManifestError(
                f"chunk_iters={self.chunk_iters} is not a multiple of "
                f"train_every={cfg.train_every}: chunks would not hold the "
                "same number of grad steps")
        self.frames_per_chunk = self.chunk_iters * self.lanes
        self.grad_steps_per_chunk = (
            self.chunk_iters // cfg.train_every * cfg.updates_per_train
            * cfg.replay.updates_per_chunk)
        warm = plan["warmup"]
        self.warm_full_chunks = int(warm["full_train_chunks"])
        self.warm_frames = {"full": cfg.replay.capacity,
                            "min_fill": cfg.replay.min_fill}[warm["ring"]]
        self.phase = "warmup"
        self.warmup: List[Dict] = []
        self.window: List[Dict] = []
        self.traced: List[Dict] = []
        self.t_window_start = None
        self.compile_at_window_start: Dict = {}
        self.compile_at_window_end: Dict = {}
        self._prev_frames = 0
        self._full_run = 0

    def __call__(self, row: Dict) -> bool:
        t = time.perf_counter()
        chunk = dict(
            t=t, wall=self.frames_per_chunk / row["env_steps_per_sec"],
            frames=int(row["env_frames"]) - self._prev_frames,
            grad_steps=float(row["grad_steps_in_chunk"]),
            loss=float(row["loss"]))
        self._prev_frames = int(row["env_frames"])
        return getattr(self, f"_on_{self.phase}")(chunk)

    def _on_warmup(self, chunk: Dict) -> bool:
        self.warmup.append(chunk)
        full = chunk["grad_steps"] == self.grad_steps_per_chunk
        self._full_run = self._full_run + 1 if full else 0
        if (self._full_run >= self.warm_full_chunks
                and self._prev_frames >= self.warm_frames):
            # Everything allocated so far lives for the whole run: one
            # collection now, then nothing of it is scanned again.
            gc.collect()
            gc.freeze()
            self.compile_at_window_start = self.meter.snapshot()
            self.phase = "window"
            # The window starts here, after the collection, so that the
            # first cycle is a whole cycle and nothing else.
            self.t_window_start = chunk["t"] = time.perf_counter()
        elif len(self.warmup) >= MAX_WARMUP_CHUNKS:
            raise RunFailure(
                f"warm-up did not reach {self.warm_full_chunks} full chunks "
                f"past {self.warm_frames} frames in {MAX_WARMUP_CHUNKS} "
                "chunks")
        return False

    def _on_window(self, chunk: Dict) -> bool:
        self.window.append(chunk)
        if (len(self.window) < self.window_chunks if self.window_chunks
                else chunk["t"] - self.t_window_start < self.seconds):
            return False
        self.compile_at_window_end = self.meter.snapshot()
        if self.trace_dir is None:
            return True
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=options)
        self.phase = "traced"
        return False

    def _on_traced(self, chunk: Dict) -> bool:
        self.traced.append(chunk)
        if len(self.traced) < int(self.plan["trace_chunks"]):
            return False
        import jax

        jax.profiler.stop_trace()
        return True

    # -- what the window says ----------------------------------------------
    def series(self) -> Dict[str, List[float]]:
        """Per-chunk series of the window: the first cycle runs from the
        window's start stamp."""
        stamps = [self.t_window_start] + [c["t"] for c in self.window]
        return {
            "cycle_s": [b - a for a, b in zip(stamps, stamps[1:])],
            "wall_s": [c["wall"] for c in self.window],
            "frames": [c["frames"] for c in self.window],
            "grad_steps": [c["grad_steps"] for c in self.window],
        }

    def failed_chunks(self) -> int:
        """Window and traced chunks with a non-finite loss or a wrong count
        of frames or grad steps."""
        return sum(1 for c in self.window + self.traced
                   if not (math.isfinite(c["loss"])
                           and c["frames"] == self.frames_per_chunk
                           and c["grad_steps"] == self.grad_steps_per_chunk))


def build_config(plan: Dict):
    """The program's ``ExperimentConfig`` for the cell: preset, the
    configuration's overrides, a check against the sizes its file states,
    then the traffic's overrides. Evaluation is off in every cell."""
    from dist_dqn_tpu.config import CONFIGS, apply_overrides

    cfg = apply_overrides(CONFIGS[plan["preset"]], plan["config_overrides"])
    for path, stated in plan["sizes"].items():
        value = cfg
        for key in path.split("."):
            value = getattr(value, key)
        if isinstance(value, tuple):
            value = list(value)
        if value != stated:
            raise ManifestError(
                f"config {plan['config']}: its file states {path}={stated!r} "
                f"but the program runs {value!r}")
    cfg = apply_overrides(cfg, plan["traffic_overrides"])
    return dataclasses.replace(cfg, eval_every_steps=0)


def _fullest_device_stats(devices) -> Dict:
    """Everything the allocator of the fullest device reports: the device
    with the largest ``peak_bytes_in_use``, the peak of live buffers."""
    stats = [{k: v for k, v in (d.memory_stats() or {}).items()
              if isinstance(v, (int, float))} for d in devices]
    return max(stats, key=lambda s: s.get("peak_bytes_in_use", 0),
               default={})


def run(args, t_process_start: float) -> int:
    manifest = Manifest(args.root)
    plan = resolve_cell(manifest, args.workload)

    import jax

    from dist_dqn_tpu.utils import backend

    if args.allow_cpu:
        jax.config.update("jax_platforms", "cpu")
    backend.enable_compile_cache()
    # Keep every program, however quick to compile, so that a second run
    # of a cell finds all of them (JAX's default keeps only >= 1 s).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter = CompileMeter()
    device = (backend.device_summary() if args.allow_cpu
              else backend.require_accelerator())
    if device["count"] < plan["chips"]:
        raise RunFailure(f"cell {plan['cell']} needs {plan['chips']} chips, "
                         f"JAX reports {device['count']}")
    if plan["test_window_chunks"] and not args.allow_cpu:
        raise ManifestError(
            f"cell {plan['cell']}: test_window_chunks is for --allow-cpu "
            "runs; on a chip the window is --seconds long")
    t_backend = time.perf_counter()

    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.train import train

    from . import reference_check

    cfg = build_config(plan)
    env = make_jax_env(cfg.env_name)
    # The reference check: part of set-up, before the trainer takes the
    # chip's memory. Under a mesh each shard's learner sees its own rows of
    # the batch, so that is the size checked (on one device).
    reference = manifest.reference(plan["reference"])
    check = reference_check.make_check(
        reference, cfg, env, build_network(cfg.network, env.num_actions),
        cfg.learner.batch_size // plan["num_devices"])(args.seed)
    t_check = time.perf_counter()

    trace_dir = None
    if args.trace:
        trace_dir = CHECKOUT / ".perf_trace" / plan["cell"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    recorder = Recorder(plan, cfg, args.seconds, trace_dir, meter)
    train(cfg, total_env_steps=ENDLESS_FRAMES, seed=args.seed,
          chunk_iters=recorder.chunk_iters, log_fn=lambda _line: None,
          stop_fn=recorder, num_devices=plan["num_devices"])
    t_train_end = time.perf_counter()
    memory_stats = _fullest_device_stats(jax.devices()[:plan["chips"]])
    memory_peak = int(memory_stats.get("peak_bytes_in_use", 0))

    series = recorder.series()
    # Segments by chunk count alone in a test's window; too few whole chunks
    # for the segment median fails the run (TooFewChunks), whatever it would
    # have printed.
    min_seconds = (0.0 if plan["test_window_chunks"]
                   else estimator.MIN_SEGMENT_SECONDS)
    cycles = series["cycle_s"]
    rates = {
        "env_steps_per_s_chip": estimator.median_rate(
            cycles, series["frames"], min_seconds) / plan["chips"],
        "grad_steps_per_s": estimator.median_rate(
            cycles, series["grad_steps"], min_seconds)}
    in_window = {k: recorder.compile_at_window_end[k]
                 - recorder.compile_at_window_start[k]
                 for k in recorder.compile_at_window_end}
    failed = recorder.failed_chunks()
    correct_parts = {"counts_exact": failed == 0,
                     "compiles_in_window": in_window["compiles"],
                     "reference_ok": check["ok"]}
    setup_s = recorder.t_window_start - t_process_start
    record = {
        "cell": plan["cell"], "config": plan["config"],
        "traffic": plan["traffic"], "chips": plan["chips"],
        "seed": args.seed, "seconds": args.seconds,
        "chunk_iters": recorder.chunk_iters, "lanes": recorder.lanes,
        "batch_size": cfg.learner.batch_size,
        # FLOPs a grad step requires, counted by the configuration's
        # reference module from its own shapes (whole mesh)
        "grad_step_flops": float(reference.grad_step_flops(cfg, env)),
        "grad_steps_per_chunk": recorder.grad_steps_per_chunk,
        "device": device, "series": series, "rates": rates,
        "host_loop": estimator.host_loop_summary(
            cycles, series["wall_s"], series["frames"], min_seconds),
        "warmup_chunks": len(recorder.warmup),
        "traced_chunks": len(recorder.traced),
        "compile": {"setup": recorder.compile_at_window_start,
                    "in_window": in_window, "total": meter.snapshot()},
        "setup_s": setup_s,
        "setup_parts_s": {
            "imports_and_backend": t_backend - t_process_start,
            "reference_check": t_check - t_backend,
            "train_until_window": recorder.t_window_start - t_check},
        # from the window's end to train()'s return: traced chunks and the
        # profiler's own stop, 0 in an untraced run
        "run_tail_s": (t_train_end - recorder.t_window_start
                       - sum(series["cycle_s"])),
        "reference_check": check,
        "correct_parts": correct_parts,
        "memory_peak_bytes": memory_peak,
        "memory_stats": memory_stats,
    }
    line = {"correct": bool(failed == 0 and in_window["compiles"] == 0
                            and check["ok"]),
            "attempted": len(recorder.window) + len(recorder.traced),
            "failed": failed, "metrics": {},
            "device": dict(device, memory_peak_bytes=memory_peak)}
    # A CPU run (tests) prints counts and never a device metric.
    on_accelerator = device["platform"] != "cpu"
    if args.trace:
        _traced_metrics(manifest, args, record, line, trace_dir,
                        on_accelerator)
    elif on_accelerator:
        values = dict(rates, hbm_peak_gb=memory_peak / 1e9, setup_s=setup_s)
        for m in manifest.metrics_of("end_to_end", plan["cell"]):
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    # Every number ``correct`` rests on, beside its limit: last in the line
    # and as the last lines of standard error.
    line["compared"] = dict(
        {name: [value, check["tolerances"][name]]
         for name, value in check["errors"].items()},
        failed_chunks=[failed, 0],
        compiles_in_window=[in_window["compiles"], 0])
    if args.record:
        Path(args.record).parent.mkdir(parents=True, exist_ok=True)
        Path(args.record).write_text(json.dumps(dict(record,
                                                     last_line=line)))
    if args.dump_hlo:
        _dump_chunk_program(Path(args.dump_hlo))
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def _dump_chunk_program(path: Path) -> None:
    """The optimized HLO text of the chunk program ``train.train`` kept for
    its stage table, gzipped: with a dumped trace, what a stage-metric test
    needs (``perf/testdata``)."""
    import gzip

    from dist_dqn_tpu.telemetry import stages

    program = getattr(stages, "_program", None)
    if program is None:
        raise RunFailure("--dump-hlo: the program kept no chunk executable")
    path.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wt") as f:
        f.write(program.as_text())


def _traced_metrics(manifest: Manifest, args, record: Dict, line: Dict,
                    trace_dir: Path, on_accelerator: bool) -> None:
    """Reduce the profiler's trace, keep only the reduction, and fill the
    line's per-layer metrics, ``device.busy_s/window_s`` and ``breakdown``."""
    from perf.reduce import trace_reduce, xplane

    planes = xplane.load_newest(trace_dir)
    if args.dump_trace:
        xplane.dump(planes, args.dump_trace)
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace = trace_reduce.reduce(planes, chips=record["chips"])
    record["trace_summary"] = trace.summary()
    if on_accelerator:
        line["device"]["busy_s"] = trace.busy_s
        line["device"]["window_s"] = trace.window_s
        line["breakdown"] = trace.breakdown()
    for m in manifest.metrics_of("per_layer", record["cell"]):
        if not (on_accelerator or m["source"] == "program_counter"):
            continue
        value = manifest.metric_reader(m["name"])(record, trace)
        if value is not None:
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
