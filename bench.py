"""Headline benchmark: env-steps/sec/chip on the Atari-shaped pipeline.

Runs the fused on-device training loop (act -> PixelPong step -> replay ->
learner update cadence) on whatever single accelerator is present and
reports the driver's north-star metric (BASELINE.json:2,5):
env-steps/sec/chip against the 50k/sec/chip Ape-X target, plus ``mfu`` —
the conventional definition: learner fwd+bwd+optimizer FLOPs over chip
bf16 peak, censused on a standalone compile of the train step (the same
program benchmarks/learner_bench.py times). The census deliberately does
NOT come from the fused chunk: XLA's cost analysis counts a ``lax.scan``
body ONCE regardless of trip count (identical census for
5/20/40-iteration chunks), so a whole-chunk number would
undercount by ~the chunk length; the standalone train step has no scan.

Timing is fenced with ``device_get`` on a chunk metric: only a
host-materialized value proves the chunk ran.

Every exit path — backend failure, no accelerator, any exception — emits
exactly ONE structured JSON line (with an "error" field on failure) and a
nonzero code, so a capture is always parseable:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
Without ``BENCH_SMOKE=1`` a backend that is not an accelerator is an
error: a CPU timing is never written under this metric's name.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time

BASELINE_ENV_STEPS_PER_SEC_PER_CHIP = 50_000.0  # BASELINE.json:5 target
METRIC = "env_steps_per_sec_per_chip"
UNIT = ("env-steps/sec/chip (synthetic 84x84 Atari-shaped pixel env,"
        " Nature CNN, fused on-device actor+learner)")

class ContractEmitter:
    """The emit-once BENCH contract: every exit path of a benchmark —
    success, backend hang, any exception — produces exactly ONE
    structured JSON line (first caller wins), so a driver capture is
    always parseable. Shared with the satellite benchmarks
    (benchmarks/serving_bench.py, benchmarks/scaling_bench.py)."""

    def __init__(self, metric: str, unit: str):
        self.metric, self.unit = metric, unit
        self._lock = threading.Lock()
        self._emitted = False

    def emit_payload(self, payload: dict) -> None:
        with self._lock:
            if self._emitted:
                return
            self._emitted = True
            print(json.dumps(payload), flush=True)

    def error(self, stage: str, err: str) -> None:
        self.emit_payload({"metric": self.metric, "value": None,
                           "unit": self.unit, "vs_baseline": None,
                           "error": f"{stage}: {err}"})


_contract = ContractEmitter(METRIC, UNIT)


def _emit(payload: dict) -> None:
    """Print the single contract JSON line (first caller wins)."""
    _contract.emit_payload(payload)


def _emit_error(stage: str, err: str) -> None:
    _contract.error(stage, err)


def _env_int(name: str, default: int) -> int:
    """Int env override; a malformed value must not be able to break the
    one-JSON-line contract, so it falls back to the default."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _sizes(smoke: bool) -> dict:
    """The run-shaping knobs (BENCH_* env overrides select variants).
    train_every defaults to the atari preset's value so the benchmark
    cannot silently diverge from the config it claims to measure."""
    from dist_dqn_tpu.config import CONFIGS

    # Frame-dedup storage is the default (BENCH_FRAME_DEDUP=0 opts back
    # to full-stack storage). The default ring is sized per mode to the
    # same HBM bytes: 65k deduped == 16k stacked (~0.5 GB).
    frame_dedup = os.environ.get("BENCH_FRAME_DEDUP", "1") == "1"
    default_ring = 65_536 if frame_dedup else 16_384
    return {
        "num_envs": _env_int("BENCH_NUM_ENVS", 8 if smoke else 1024),
        "chunk": _env_int("BENCH_CHUNK", 20 if smoke else 200),
        "measure_chunks": _env_int("BENCH_MEASURE_CHUNKS", 2 if smoke else 25),
        "ring": _env_int("BENCH_RING", 2_048 if smoke else default_ring),
        "batch": _env_int("BENCH_BATCH", 32 if smoke else 512),
        "train_every": _env_int("BENCH_TRAIN_EVERY",
                                CONFIGS["atari"].train_every),
        # BENCH_PRIORITIZED=1 swaps the uniform ring for device PER
        # (ReplayConfig default alpha 0.6 / beta 0.4) — the Ape-X-shaped
        # fused program, measured beside the default Nature-DQN one.
        # Sampler routing follows production: XLA stratified-CDF by
        # default (the small-ring regime), the Pallas kernel with
        # BENCH_PALLAS_SAMPLER=1 (what the apex preset's 1M shard uses).
        "prioritized": os.environ.get("BENCH_PRIORITIZED") == "1",
        "pallas_sampler": os.environ.get("BENCH_PALLAS_SAMPLER") == "1",
        "frame_dedup": frame_dedup,
        # Learner-utilization knobs (ISSUE 6): grad sub-steps per train
        # event (scanned on device), pow2-bucketed train-batch widening
        # (0 = batch as-is), and the actor-inference dtype split. The
        # defaults reproduce the pre-knob program exactly; the BENCH
        # JSON always records all three next to mfu so the trajectory
        # knows WHICH configuration produced each number.
        "replay_ratio": _env_int("BENCH_REPLAY_RATIO", 1),
        "train_batch": _env_int("BENCH_TRAIN_BATCH", 0),
        "actor_dtype": os.environ.get("BENCH_ACTOR_DTYPE", "float32"),
    }


def main() -> int:
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    try:
        import jax

        from dist_dqn_tpu.utils import backend

        if smoke:
            jax.config.update("jax_platforms", "cpu")
        backend.enable_compile_cache()
        if not smoke:
            backend.require_accelerator()
        device = jax.devices()[0]
    except Exception as e:  # noqa: BLE001 — contract: never a raw traceback
        _emit_error("backend-init", repr(e))
        return 2

    try:
        value, extras = _measure(jax, device, smoke)
    except Exception as e:  # noqa: BLE001
        _emit_error("measurement", repr(e))
        return 2

    _emit({"metric": METRIC, "value": round(value, 1), "unit": UNIT,
           "vs_baseline": round(value / BASELINE_ENV_STEPS_PER_SEC_PER_CHIP,
                                6), **extras})
    return 0


def _learner_step_flops(jax, cfg, env, net):
    """Op-census FLOPs of ONE learner grad step, lowered standalone.

    The fused chunk's census also counts env physics, acting and replay
    ops; the conventional MFU definition counts model fwd+bwd+optimizer
    only — so the ``mfu`` field is derived from this
    program, exactly the one benchmarks/learner_bench.py times. The
    census registers as ``fused.train_step`` in the chip-time
    ProgramRegistry (ISSUE 19) so the caller can derive the
    ``dqn_learner_mfu`` gauge the runtimes publish.
    """
    import numpy as np

    from dist_dqn_tpu.agents.dqn import make_learner
    from dist_dqn_tpu.types import Transition
    from dist_dqn_tpu.utils import flops as flops_util

    from dist_dqn_tpu import loop_common

    init, train_step = make_learner(net, cfg.learner)
    obs_shape = env.observation_shape
    obs_dtype = np.dtype(env.observation_dtype)
    state = init(jax.random.PRNGKey(0), jax.numpy.zeros(obs_shape, obs_dtype))
    # The census must price the step the fused program ACTUALLY runs:
    # the bucketed train width, not the nominal batch_size — otherwise
    # a BENCH_TRAIN_BATCH-widened row under-reports mfu by the ratio.
    B = loop_common.resolve_train_batch(cfg)
    r = np.random.default_rng(0)

    def obs():
        if obs_dtype == np.uint8:
            return jax.numpy.asarray(
                r.integers(0, 255, (B,) + obs_shape, np.uint8))
        return jax.numpy.asarray(r.normal(size=(B,) + obs_shape)
                                 .astype(obs_dtype))

    batch = Transition(
        obs=obs(),
        action=jax.numpy.asarray(r.integers(0, env.num_actions, B, np.int32)),
        reward=jax.numpy.asarray(r.normal(size=B).astype(np.float32)),
        discount=jax.numpy.full(B, cfg.learner.gamma ** cfg.learner.n_step,
                                jax.numpy.float32),
        next_obs=obs(),
    )
    from dist_dqn_tpu.telemetry import devtime as _devtime

    jitted = jax.jit(train_step, donate_argnums=0)
    prog = _devtime.register_program(  # cost census of `jitted` above
        "fused.train_step", loop="fused", role="train",
        cost=lambda: jitted.lower(state, batch,
                                  jax.numpy.ones(B, jax.numpy.float32)))
    return prog.flops


def _measure(jax, device, smoke: bool):
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.train_loop import fused_parts, make_fused_train
    from dist_dqn_tpu.utils import flops as flops_util

    # BENCH_SMOKE=1 shrinks every dimension; default sizes target a real
    # TPU chip.
    s = _sizes(smoke)
    num_envs = s["num_envs"]
    chunk = s["chunk"]
    # ~25 chunks x 200 iters x 1024 envs ~= 5M env steps: several seconds
    # of measured work, long enough to average out dispatch/clock jitter.
    measure_chunks = s["measure_chunks"]

    cfg = CONFIGS["atari"]
    cfg = dataclasses.replace(
        cfg,
        actor=dataclasses.replace(cfg.actor, num_envs=num_envs),
        # Production configs size their rings for learning (atari:
        # 200k), not for this contract metric; see _sizes for the ring.
        replay=dataclasses.replace(
            cfg.replay,
            capacity=s["ring"],
            prioritized=s["prioritized"],
            pallas_sampler=s["pallas_sampler"],
            frame_dedup=s["frame_dedup"],
            updates_per_chunk=s["replay_ratio"],
            train_batch=s["train_batch"],
            min_fill=128 if smoke else 4_096),
        learner=dataclasses.replace(
            cfg.learner,
            batch_size=s["batch"]),
        network=dataclasses.replace(
            cfg.network,
            actor_dtype=s["actor_dtype"]),
        train_every=s["train_every"],
    )
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_fused_train(cfg, env, net)
    run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)

    def fence(metrics) -> float:
        return float(jax.device_get(metrics["loss"]))

    carry = init(jax.random.PRNGKey(0))
    compiled = run.lower(carry, chunk).compile()
    # Chip-time attribution (ISSUE 19): the measured program registers
    # with its census so the BENCH row's `programs` block and the
    # registry-derived mfu come from the same plane the runtimes use.
    from dist_dqn_tpu.telemetry import devtime as _devtime
    _prog_chunk = _devtime.register_program(  # census of `run`'s chunk
        "fused.chunk", loop="fused", role="chunk", cost=compiled)
    for _ in range(2):  # warmup + fill past min_fill into steady state
        carry, metrics = compiled(carry)
        fence(metrics)

    t0 = time.perf_counter()
    for _ in range(measure_chunks):
        carry, metrics = compiled(carry)
    fence(metrics)
    dt = time.perf_counter() - t0
    _prog_chunk.count_dispatch(measure_chunks)
    _prog_chunk.add_device_seconds(dt)

    value = measure_chunks * chunk * num_envs / dt
    extras = {"platform": device.platform,
              "device_kind": getattr(device, "device_kind", "unknown")}
    # Telemetry snapshot (ISSUE 1): a perf regression in this line should
    # carry the pipeline internals, not just the headline number — record
    # the measured state into the process registry and embed its JSON
    # snapshot in the contract line's extras.
    from dist_dqn_tpu import telemetry
    from dist_dqn_tpu.telemetry import collectors as tmc

    reg = telemetry.get_registry()
    reg.gauge(tmc.ENV_RATE, "measured env-steps/sec").set(value)
    reg.counter(tmc.ENV_STEPS, "env steps in the measured window") \
        .inc(measure_chunks * chunk * num_envs)
    chunk_hist = reg.histogram("dqn_chunk_seconds", "fused chunk wall")
    chunk_hist.observe(dt / measure_chunks)
    _, ring_slots = tmc.observe_device_ring(
        carry.replay, fused_parts(cfg, env, net)[1].num_slots, num_envs)
    # Experience lineage (ISSUE 16): reconstruct the measured window's
    # collect stamps (the timed loop cannot touch the host per chunk —
    # that would fence it) and age them exactly as train.py does, so
    # the BENCH row carries the fused loop's sample-age distribution.
    gsteps_chunk = float(jax.device_get(metrics["grad_steps_in_chunk"]))
    _lineage = tmc.FusedLineageTable()
    _per_chunk = dt / measure_chunks
    for i in range(measure_chunks):
        _lineage.on_chunk(gsteps_chunk * (i + 1),
                          max(1, ring_slots // chunk),
                          now=t0 + (i + 1) * _per_chunk)
    _age_h, _stale_h = tmc.lineage_histograms("fused")
    extras["sample_age_p50_s"] = round(
        tmc.histogram_quantile(_age_h, 0.5), 6)
    extras["sample_age_p99_s"] = round(
        tmc.histogram_quantile(_age_h, 0.99), 6)
    extras["staleness_versions_p99"] = round(
        tmc.histogram_quantile(_stale_h, 0.99), 2)
    gsteps = float(jax.device_get(metrics["grad_steps_in_chunk"]))
    if gsteps:
        reg.histogram(tmc.GRAD_LATENCY,
                      "per-grad-step share of the chunk wall") \
            .observe(dt / measure_chunks / gsteps)
    # Run manifest (ISSUE 4 satellite): BENCH rows self-describe their
    # provenance — git sha, jax/numpy versions, platform, the exact
    # measured config (hashed), argv, schema_version — the same block
    # train.py logs and forensics bundles embed (telemetry/manifest.py).
    extras["manifest"] = telemetry.build_manifest(cfg)
    if s["prioritized"]:
        extras["prioritized"] = True  # opt-in: default line unchanged
        extras["sampler"] = "pallas" if s["pallas_sampler"] else "xla"
    if s["frame_dedup"]:
        # ON by default: the default contract line carries
        # this field (value/unit/vs_baseline schema unchanged).
        extras["frame_dedup"] = True
    # Learner-utilization config provenance (ISSUE 6): ALWAYS next to
    # mfu, so every BENCH row names the replay ratio / effective train
    # batch / actor dtype that produced its utilization numbers.
    from dist_dqn_tpu import loop_common as _lc
    extras["replay_ratio"] = s["replay_ratio"]
    extras["train_batch"] = _lc.resolve_train_batch(cfg)
    extras["actor_dtype"] = s["actor_dtype"]
    # Conventional MFU: learner fwd+bwd+optimizer FLOPs only. Grad-step
    # count uses the last chunk's census — the cadence is deterministic in
    # steady state, so every measured chunk ran the same number (reading
    # each chunk's metric would insert a host fence into the timed loop).
    # The gauge itself is registry-derived (ISSUE 19): the train-step
    # census program gets the window's dispatches + wall and
    # set_learner_mfu does the same division every runtime publishes.
    grad_steps = float(jax.device_get(metrics["grad_steps_in_chunk"])) \
        * measure_chunks
    train_flops = _learner_step_flops(jax, cfg, env, net)
    _prog_train = _devtime.get_program_registry().get(
        "fused.train_step", "fused")
    if grad_steps:
        _prog_train.count_dispatch(grad_steps)
        _prog_train.add_device_seconds(dt)
    learner = flops_util.mfu_fields(train_flops, grad_steps, dt, device)
    if "model_flops_per_sec" in learner:
        extras["model_flops_per_sec"] = learner["model_flops_per_sec"]
        extras["learner_grad_steps_per_sec"] = round(grad_steps / dt, 2)
    mfu_val = _devtime.set_learner_mfu("fused", device=device, reg=reg)
    if mfu_val is not None:
        extras["mfu"] = round(mfu_val, 4)
    if grad_steps:
        reg.gauge(tmc.LEARNER_GRAD_RATE,
                  "grad steps per second (measured window)",
                  {"loop": "fused"}).set(grad_steps / dt)
    # Per-program chip-time census (ISSUE 19): flops/bytes/dispatches/
    # device-seconds + arithmetic intensity for every registered program.
    extras["programs"] = _devtime.programs_snapshot("fused")
    # Snapshot LAST so the embedded registry block carries the learner-
    # utilization gauges set above.
    extras["telemetry"] = telemetry.snapshot(reg)
    return value, extras


if __name__ == "__main__":
    sys.exit(main())
