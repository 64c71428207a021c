"""Training entrypoint: ``python -m dist_dqn_tpu.train --config cartpole``.

The repo's own training entrypoint in the sense of BASELINE.json:5 — picks a
driver config (BASELINE.json:7-11), builds the env/network/learner, and runs
the fused on-device loop (JAX-native envs) with periodic greedy evaluation
and throughput logging of the north-star metrics (env-steps/sec/chip,
learner grad-steps/sec — BASELINE.json:2).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from dist_dqn_tpu.config import CONFIGS, ExperimentConfig, apply_overrides
from dist_dqn_tpu.envs import make_jax_env
from dist_dqn_tpu.envs.base import held_in_words
from dist_dqn_tpu.models import build_network
from dist_dqn_tpu.train_loop import (fused_parts, make_evaluator,
                                     make_fused_train, twin_obs_checkpoint)


def _pick_mesh_devices(num_devices: int, multiprocess: bool):
    """Device list for the dp mesh. Multi-process meshes must span the
    GLOBAL device list — a prefix slice would leave other processes without
    addressable shards; single-process requests larger than the machine
    fail loudly instead of silently truncating."""
    devs = jax.devices()
    if multiprocess:
        if num_devices not in (0, 1, len(devs)):
            raise ValueError(
                f"multi-process runs use all {len(devs)} global devices; "
                f"--mesh-devices {num_devices} is not meaningful (pass 0)")
        return devs
    if num_devices in (0, None):
        return devs
    if len(devs) < num_devices:
        raise ValueError(f"--mesh-devices {num_devices} requested but only "
                         f"{len(devs)} available")
    return devs[:num_devices]


def _compile_chunk(run, *args) -> None:
    """The chunk program's ONE compilation, ahead of its first dispatch
    (which reuses the executable from JAX's in-memory cache, so the first
    chunk's wall and rate hold no compile), with its stage names in the
    persistent-cache key so that a cache from before a name existed cannot
    serve it. The stage table (telemetry/stages.py) is built from this
    executable on demand, after the run; here only a reference is kept."""
    from dist_dqn_tpu.telemetry import stages
    from dist_dqn_tpu.utils import backend

    with backend.names_in_cache_key():
        compiled = run.lower(*args).compile()
    stages.keep(compiled)


def train(cfg: ExperimentConfig, total_env_steps: int = 0, seed: int = None,
          chunk_iters: int = 2000, log_fn=print,
          checkpoint_dir: str = None, save_every_frames: int = 0,
          profile_dir: str = None, num_devices: int = 1, stop_fn=None,
          checkpoint_replay: bool = False, telemetry_port: int = None,
          telemetry_host: str = "127.0.0.1", trace_path: str = None):
    """Run training; returns (final_carry, history list of metric dicts).

    With ``checkpoint_replay`` the checkpoint holds the WHOLE fused
    carry — replay ring, env states, rng, episode trackers — so a
    resumed run continues BIT-EQUAL to an uninterrupted one (no replay
    refill, no distribution shift). Cost: the ring dominates the
    checkpoint (a 65k-slot pixel ring is ~1.8 GB vs ~7 MB of learner
    state), so saves are proportionally slower — the default
    learner-only mode instead refills replay from live experience in
    ``min_fill / steady-rate`` seconds (sub-second at fused-loop rates;
    see utils/checkpoint.py for the trade-off numbers).

    With ``checkpoint_dir`` set, the learner state is checkpointed every
    ``save_every_frames`` env frames (default: every eval period) and the
    newest checkpoint is restored on startup — actors/replay are stateless
    and refill, per the failure model in SURVEY.md §5. With ``profile_dir``
    set, one steady chunk (the first after a chunk that took the full
    cadence's grad steps; a run that ends before one writes no trace) is
    captured as a ``jax.profiler`` trace for TensorBoard/xprof; with
    ``trace_path``, the host loop's spans (``fused.dispatch`` /
    ``fused.fence`` / ``fused.bookkeeping``) are also written as a Chrome
    trace-event file (utils/trace.py).

    ``num_devices != 1`` selects the mesh trainers (parallel/learner.py):
    env lanes + the replay shard spread over a ``dp`` mesh of that many
    devices (0 = every device) and gradients pmean over the mesh. Under a
    ``jax.distributed`` runtime (parallel/distributed.py) the device list —
    and therefore the mesh — is global, so the same call scales over
    multiple hosts: each process runs this function, process 0 logs, and
    checkpoint/eval work from the replicated learner copy.
    """
    # Population training plane (ISSUE 20): M > 1 routes to the
    # vmap-stacked trainer; M == 1 with a spec applies member 0's
    # overrides STATICALLY and falls through to the plain program —
    # so `--population 1` is bit-identical to today's run by
    # construction (no traced-hyperparameter lanes, no vmap).
    if cfg.population.size > 1:
        return _train_population(
            cfg, total_env_steps=total_env_steps, seed=seed,
            chunk_iters=chunk_iters, log_fn=log_fn,
            checkpoint_dir=checkpoint_dir,
            save_every_frames=save_every_frames,
            profile_dir=profile_dir, num_devices=num_devices,
            stop_fn=stop_fn, checkpoint_replay=checkpoint_replay,
            telemetry_port=telemetry_port,
            telemetry_host=telemetry_host)
    if cfg.population.spec_json:
        from dist_dqn_tpu import population as _pop
        cfg = _pop.member_config(cfg, _pop.resolve_spec(cfg), 0)
    multiprocess = jax.process_count() > 1
    if multiprocess:
        from dist_dqn_tpu.parallel.distributed import main_process_log
        log_fn = main_process_log(log_fn)
    # Telemetry (ISSUE 1): registry instruments for the fused loop, plus
    # the optional /metrics scrape endpoint (--telemetry-port; 0 binds an
    # ephemeral port, reported as a telemetry_port log line). Instruments
    # exist from the first scrape even before the first chunk lands.
    from dist_dqn_tpu import telemetry
    from dist_dqn_tpu.telemetry import collectors as tmc
    from dist_dqn_tpu.telemetry import watchdog as tm_watchdog
    # Crash forensics (ISSUE 4; null-safe no-ops until --forensics-dir /
    # --no-flight-recorder arm or disarm them): a per-chunk stage
    # heartbeat, a per-chunk flight event, and the divergence sentinel
    # on every chunk's loss. Registered WITH startup grace: the first
    # chunk carries the jit compile, whose legitimate wall must not read
    # as a stall — but a compile that outlives grace + deadline trips
    # with its stack on record.
    _flight = telemetry.get_flight()
    _hb_chunk = tm_watchdog.heartbeat(
        "fused.chunk", startup_grace_s=tm_watchdog.STARTUP_GRACE_S)
    _reg = telemetry.get_registry()
    _tm = {
        "env_steps": _reg.counter(tmc.ENV_STEPS, "env frames processed"),
        "env_rate": _reg.gauge(tmc.ENV_RATE, "env-steps/sec (last chunk)"),
        "grad_steps": _reg.counter(tmc.GRAD_STEPS,
                                   "learner grad steps taken"),
        "grad_latency": _reg.histogram(
            tmc.GRAD_LATENCY,
            "per-grad-step share of the fused chunk wall"),
        "staleness": _reg.histogram(
            tmc.PARAM_STALENESS,
            "age of the host-visible params at each chunk boundary "
            "(the fused loop refreshes them once per chunk)"),
        "chunk": _reg.histogram("dqn_chunk_seconds",
                                "fused chunk wall time"),
        "loss": _reg.gauge("dqn_loss", "chunk-mean TD loss"),
        "episodes": _reg.counter("dqn_episodes_completed_total",
                                 "training episodes finished"),
        "ep_return": _reg.gauge("dqn_episode_return",
                                "chunk-mean finished-episode return"),
        "grad_rate": _reg.gauge(tmc.LEARNER_GRAD_RATE,
                                "grad steps per second (last chunk)",
                                {"loop": "fused"}),
    }
    # Experience-lineage accounting (ISSUE 16): host-side chunk stamp
    # table — the fused loop's collect-granular twin of the record
    # stamps the wire-fed runtimes carry.
    _lineage = tmc.FusedLineageTable()
    # Learner-utilization config surface (ISSUE 6): the replay ratio /
    # bucketed batch width / actor dtype this run's rates were shaped by.
    from dist_dqn_tpu import loop_common as _lc
    _fl = {"loop": "fused"}
    _reg.gauge(tmc.LEARNER_REPLAY_RATIO,
               "grad sub-steps per train event",
               _fl).set(_lc.resolve_replay_ratio(cfg))
    _reg.gauge(tmc.LEARNER_TRAIN_BATCH,
               "effective (bucketed) train batch width",
               _fl).set(_lc.resolve_train_batch(cfg))
    _reg.gauge(tmc.LEARNER_ACTOR_DTYPE_INFO,
               "1 for the active actor inference dtype",
               {**_fl, "dtype": cfg.network.actor_dtype
                or "float32"}).set(1)
    telemetry_server = None
    if telemetry_port is not None and (not multiprocess
                                       or jax.process_index() == 0):
        telemetry_server = telemetry.start_server(telemetry_port,
                                                  host=telemetry_host)
        log_fn(json.dumps({"telemetry_port": telemetry_server.port}))
        # Fleet registry (ISSUE 16): after bind, so the descriptor
        # carries the resolved port; no-op without DQN_FLEET_DIR.
        from dist_dqn_tpu.telemetry import fleet as _fleet
        _fleet.register_endpoint("learner", telemetry_server.port,
                                 host=telemetry_host,
                                 labels={"loop": "fused"})
    seed = cfg.seed if seed is None else seed
    total = total_env_steps or cfg.total_env_steps
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)

    use_mesh = num_devices != 1 or multiprocess
    if use_mesh:
        from dist_dqn_tpu.parallel import make_mesh, make_mesh_fused_train
        mesh = make_mesh(devices=_pick_mesh_devices(num_devices,
                                                    multiprocess))
        init, run = make_mesh_fused_train(cfg, env, net, mesh)
    else:
        init, run_chunk = make_fused_train(cfg, env, net)
        run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
    # Whether the ring's merged-row buffer crosses the chunk program's
    # boundary row-major (replay/device_ring.py merged_row_boundary): where
    # it does not, every chunk copies the whole ring in and out.
    device_ring = fused_parts(
        cfg, env, net, num_shards=mesh.shape["dp"] if use_mesh else 1)[1]
    boundary = device_ring.boundary
    _reg.gauge("dqn_ring_boundary_row_major",
               "1: the device ring's merged-row buffer is carried row-major "
               "between chunks (no whole-ring copy in the chunk program)"
               ).set(int(bool(boundary and boundary.row_major)))
    # Whether the loop carries the acting observation once, as 32-bit words
    # (envs/base.py held_in_words: four uint8 frames kept in the env state).
    obs_words = held_in_words(env)
    _reg.gauge("dqn_obs_words",
               "1: the fused loop carries the acting observation once, as "
               "32-bit words (one word = a pixel's four stacked frames)"
               ).set(int(obs_words))
    # What one lane's acting state weighs, by kind of cache, where the
    # network's layers keep several (models/sequence_core.py).
    for cache, nbytes in getattr(net, "state_bytes_a_lane", dict)().items():
        _reg.gauge("dqn_actor_state_bytes_a_lane",
                   "bytes of one lane's acting state, by kind of cache "
                   "(summed over the layers of that kind)",
                   {"cache": cache}).set(nbytes)
    # What one acting step of all lanes reads of those rings, and what the
    # path it takes copies of them on the way (nothing on a TPU).
    for kind, sizes in getattr(net, "attention_ring_bytes", lambda _: {})(
            cfg.actor.num_envs).items():
        for state, nbytes in zip(("read", "copied"), sizes):
            _reg.gauge("dqn_actor_attention_ring_bytes",
                       "bytes of attention rings (keys and values) one "
                       "acting step of all lanes reads, and bytes of "
                       "ring-sized copies it writes on the way, by kind of "
                       "layer", {"kind": kind, "state": state}).set(nbytes)
    # What the learner's attention kernels read and leave out, from their
    # static grids (ops/pallas_attention.py); nothing where none runs.
    for kind, blocks in getattr(net, "attention_key_blocks",
                                lambda *_: {})(
            cfg.learner.batch_size, cfg.replay.burn_in,
            cfg.replay.unroll_length + cfg.learner.n_step).items():
        for state, count in zip(("visited", "skipped"), blocks):
            _reg.gauge("dqn_learner_attention_key_blocks",
                       "key blocks the fused attention kernels' forward "
                       "grids read (visited) and leave out (skipped) in one "
                       "forward pass of a learner's batch, by kind of layer",
                       {"kind": kind, "state": state}).set(count)
    for kind, rows in getattr(net, "rotary_head_rows", lambda *_: {})(
            cfg.learner.batch_size, cfg.replay.burn_in,
            cfg.replay.unroll_length + cfg.learner.n_step).items():
        _reg.gauge("dqn_learner_rotary_head_rows",
                   "query-head rows (windows x steps x heads) one forward "
                   "pass of a learner's batch sends through the rotary "
                   "kernel in front of the attention kernels, by kind of "
                   "layer", {"kind": kind}).set(rows)
    evaluate = jax.jit(make_evaluator(cfg, env, net,
                                      num_episodes=cfg.eval_episodes))
    # Eval-path choice, decided once: multi-process runs eval only on the
    # logging process, from the host copy of the replicated params (the
    # eval program is process-local).
    if not multiprocess:
        run_eval = lambda params, k: float(evaluate(params, k))  # noqa: E731
    elif jax.process_index() == 0:
        from dist_dqn_tpu.parallel.distributed import host_replica
        run_eval = lambda params, k: float(  # noqa: E731
            evaluate(host_replica(params), k))
    else:
        run_eval = None

    rng = jax.random.PRNGKey(seed)
    rng, k_init = jax.random.split(rng)
    # Multi-process: jit inputs must not be process-local committed arrays;
    # plain numpy keys are treated as replicated (identical on every
    # process by construction — same seed).
    carry = init(np.asarray(k_init))
    if boundary is not None:
        # ... and what one shard's scalar-per-step planes (replay/device.py)
        # are stored as, read off the reward plane the carry holds.
        plane = getattr(carry.replay, "ring",
                        carry.replay).reward.addressable_shards[0].data
        log_fn(json.dumps({"ring_boundary": dict(
            boundary._asdict(), row_major=int(boundary.row_major),
            planes={"cells": device_ring.num_slots * device_ring.num_envs,
                    "shape": list(plane.shape), "bytes": plane.nbytes}),
            "obs_words": obs_words}))

    ckpt = None
    frame_offset = 0      # added to the carry's cumulative frame metric
    resumed_frames = 0    # where the loop's cursor actually starts
    if checkpoint_dir:
        from dist_dqn_tpu.utils.checkpoint import (TrainCheckpointer,
                                                   checkpoint_tree,
                                                   record_checkpoint_kind)
        # The cadence chain must never bottom out at 0 (an explicit
        # --eval-every-steps 0 zeroes the eval period): save_every=0
        # would make maybe_save fire on EVERY chunk.
        ckpt = TrainCheckpointer(
            checkpoint_dir,
            save_every_frames=save_every_frames or cfg.eval_every_steps
            or 100_000)
        # Raises with the actual cause if the directory was written with
        # the OTHER --checkpoint-replay setting (the restore would
        # otherwise fail as a misleading structure-mismatch error).
        record_checkpoint_kind(checkpoint_dir,
                               "carry" if checkpoint_replay else "learner")
        saved = checkpoint_tree(carry, checkpoint_replay)
        restored = ckpt.restore_latest(
            saved, older=twin_obs_checkpoint(env, saved)
            if checkpoint_replay else None)
        if restored is not None:
            # Resume continues toward the SAME total: the frame cursor picks
            # up at the checkpoint step so relaunching the identical command
            # finishes the remaining frames (and later saves land at
            # monotonically increasing orbax steps).
            frame_offset, tree = restored
            resumed_frames = frame_offset
            # Mesh path: the restore is templated on the live learner's
            # shardings (utils/checkpoint.py), so global replicated arrays
            # come back as such. Multi-process runs call save/restore on
            # every process (orbax collective IO) against a SHARED
            # checkpoint directory.
            log_fn(json.dumps({"resumed_at_frames": frame_offset,
                               "with_replay": checkpoint_replay}))
            if checkpoint_replay:
                # The carry's own iteration counter came back with it, so
                # the cumulative env_frames metric already continues from
                # the checkpoint — a host-side offset would double-count.
                carry = carry._replace(**tree)
                frame_offset = 0
            else:
                carry = carry._replace(learner=tree)

    # Emergency checkpoint on watchdog abort (ISSUE 8): the abort path
    # saves the NEWEST chunk-boundary state before SIGTERM, so a wedged
    # run loses at most one chunk instead of a whole save period. The
    # holder is refreshed each chunk; device arrays are immutable, so
    # the side-thread save reads a consistent snapshot. Saved to a SIDE
    # location with a one-shot checkpointer — the shared manager may be
    # the very thing the main thread is wedged inside (slow storage),
    # and a concurrent save on it would tear the in-flight commit.
    _emerg = {"frames": resumed_frames, "carry": carry}
    if ckpt is not None:
        from dist_dqn_tpu.utils.checkpoint import save_pytree as _save_pt

        def _emergency_save():
            import os

            _save_pt(os.path.join(checkpoint_dir, "emergency_learner"),
                     {"learner": checkpoint_tree(_emerg["carry"],
                                                 checkpoint_replay)})

        tm_watchdog.register_emergency_hook("fused.checkpoint",
                                            _emergency_save)

    B = cfg.actor.num_envs
    history = []
    frames = resumed_frames
    # 0 disables eval entirely (same convention as the apex runtime's
    # eval_every_steps); otherwise the first chunk gets a baseline eval.
    next_eval = frames if cfg.eval_every_steps else float("inf")
    chunk_index = 0
    # --profile-dir traces a STEADY chunk: the first one after a chunk that
    # reported the full cadence's grad steps (past min_fill, every train
    # event taken), so the trace shows the stages and spans as they repeat.
    full_grad_steps = (chunk_iters // cfg.train_every * cfg.updates_per_train
                       * _lc.resolve_replay_ratio(cfg))
    steady = profiled = False
    # Host spans (utils/trace.py): durations to the flight ring (and, with
    # --trace-path, the Chrome trace + dqn_host_span_seconds); each span is
    # also a profiler TraceAnnotation, so in any device trace a gap between
    # two chunk programs lies under the host span that caused it.
    from dist_dqn_tpu.utils.trace import make_tracer
    tracer = make_tracer(trace_path, process_name="fused-learner")

    try:
        if frames < total:
            _compile_chunk(run, carry, chunk_iters)
        while frames < total:
            profiling = profile_dir is not None and steady and not profiled
            if profiling:
                jax.profiler.start_trace(profile_dir)
            with jax.profiler.StepTraceAnnotation("fused.chunk",
                                                  step_num=chunk_index):
                t0 = time.perf_counter()
                with tracer.span("fused.dispatch"):
                    carry, metrics = run(carry, chunk_iters)
                with tracer.span("fused.fence"):
                    metrics = jax.tree.map(np.asarray,
                                           jax.device_get(metrics))
                dt = time.perf_counter() - t0
            # Everything from the fence to the next dispatch.
            with tracer.span("fused.bookkeeping"):
                chunk_index += 1
                prev_frames = frames
                frames = frame_offset + int(metrics["env_frames"])
                grad_steps_chunk = float(metrics["grad_steps_in_chunk"])
                steady = 0 < full_grad_steps <= grad_steps_chunk
                frames_delta = max(frames - prev_frames, 0)
                _tm["env_steps"].inc(frames_delta)
                _tm["env_rate"].set(frames_delta / dt)
                _tm["grad_steps"].inc(grad_steps_chunk)
                _tm["chunk"].observe(dt)
                # Host-visible params refresh once per chunk boundary, so the
                # chunk wall bounds their staleness; grad-step latency is the
                # per-step share of the fused chunk (the steps run inside one
                # XLA program — there is no finer host-observable boundary).
                _tm["staleness"].observe(dt)
                if grad_steps_chunk:
                    _tm["grad_latency"].observe(dt / grad_steps_chunk)
                _tm["grad_rate"].set(grad_steps_chunk / dt)
                _hb_chunk.beat()
                _loss = float(metrics["loss"])
                _flight.record("chunk", "fused.chunk", frames=frames,
                               loss=_loss, wall_s=round(dt, 4))
                tm_watchdog.observe_divergence(loss=_loss, step=frames)
                _tm["loss"].set(_loss)
                _tm["episodes"].inc(max(float(metrics["episodes"]), 0.0))
                if float(metrics["episodes"]):
                    _tm["ep_return"].set(float(metrics["episode_return"]))
                _, ring_slots = tmc.observe_device_ring(
                    carry.replay, device_ring.num_slots, B)
                # Experience lineage (ISSUE 16): the fused loop stamps at
                # collect — one (birth, version) row per chunk, aged over
                # the live ring window into the same families the apex and
                # host-replay runtimes observe per sampled record.
                _lineage.on_chunk(_tm["grad_steps"].value,
                                  max(1, ring_slots // chunk_iters))
                telemetry.sweep_device_memory(_reg)
                row = {
                    "env_frames": frames,
                    "episode_return": float(metrics["episode_return"]),
                    # Disambiguates episode_return's no-episodes sentinel
                    # (0.0 with episodes == 0) from a genuine 0.0 average
                    # return.
                    "episodes": float(metrics["episodes"]),
                    "loss": float(metrics["loss"]),
                    "env_steps_per_sec": chunk_iters * B / dt,
                    "grad_steps_in_chunk": grad_steps_chunk,
                    "grad_steps_per_sec": grad_steps_chunk / dt,
                }
                if grad_steps_chunk and "routing_held_share" in metrics:
                    # an agent with expert layers (agents/r2d2.py
                    # ROUTING_COUNTERS): the chunk's mean over grad steps
                    row["routing_held_share"] = float(
                        metrics["routing_held_share"])
                    row["routing_busiest_over_mean"] = float(
                        metrics["routing_busiest_over_mean"])
                    _reg.gauge(
                        "dqn_routing_held_share",
                        "share of the tokens' expert choices that fall on "
                        "experts this chip holds (chunk mean)").set(
                            row["routing_held_share"])
                    _reg.gauge(
                        "dqn_routing_busiest_over_mean",
                        "busiest held expert's load over the held "
                        "experts' mean, worst layer (chunk mean)").set(
                            row["routing_busiest_over_mean"])
                if frames >= next_eval:
                    # Every process consumes k_eval so rng streams stay in
                    # lockstep even where run_eval is None (non-logging
                    # processes).
                    rng, k_eval = jax.random.split(rng)
                    if run_eval is not None:
                        row["eval_return"] = run_eval(carry.learner.params,
                                                      k_eval)
                    next_eval = frames + cfg.eval_every_steps
                history.append(row)
                log_fn(json.dumps({k: round(v, 3) if isinstance(v, float)
                                   else v for k, v in row.items()}))
                _emerg["frames"], _emerg["carry"] = frames, carry
                if ckpt is not None:
                    ckpt.maybe_save(
                        frames, checkpoint_tree(carry, checkpoint_replay))
                # Early stop (single-process only: a data-dependent exit
                # would desync multi-process lockstep): stop_fn sees each
                # metric row — solve-detection for tests, target-return
                # stops for users.
                stop = (stop_fn is not None and jax.process_count() == 1
                        and stop_fn(row))
            if profiling:
                # After the span closed, so the trace holds all three.
                jax.profiler.stop_trace()
                profiled = True
                log_fn(json.dumps({"profile_trace": profile_dir}))
            if stop:
                break
    finally:
        # Deregistered even when the loop raises: a leaked
        # heartbeat would read as a permanent stall in a
        # process that caught the exception and lived on.
        _hb_chunk.close()
        tm_watchdog.unregister_emergency_hook("fused.checkpoint")
        tracer.close()
    if ckpt is not None:
        ckpt.save(frames, checkpoint_tree(carry, checkpoint_replay))
        ckpt.close()
    if telemetry_server is not None:
        telemetry_server.close()
    return carry, history


def _train_population(cfg: ExperimentConfig, total_env_steps: int = 0,
                      seed: int = None, chunk_iters: int = 2000,
                      log_fn=print, checkpoint_dir: str = None,
                      save_every_frames: int = 0, profile_dir: str = None,
                      num_devices: int = 1, stop_fn=None,
                      checkpoint_replay: bool = False,
                      telemetry_port: int = None,
                      telemetry_host: str = "127.0.0.1"):
    """The population twin of :func:`train` (ISSUE 20): M vmap-stacked
    policies advance as ONE jitted program, one dispatch per chunk.

    Every carry leaf — params, optimizer state, target params, replay
    ring, env vector, rng — carries a leading member axis; per-member
    hyperparameters (``population.spec_json``) ride as traced [M]
    lanes. Member independence is pinned (tests/test_population.py):
    member k's lane bit-matches an M=1 stacked run configured with
    member k's spec entry and seeded with member k's spawn-key stream
    (``population.member_seeds``), so the population is M independent
    experiments sharing a chip, not a coupled batch.

    Frame accounting: the ``frames`` cursor (and ``total_env_steps``)
    is PER MEMBER — each member trains the same budget a solo run
    would — while telemetry counters and the ``env_steps_per_sec`` /
    ``grad_steps_per_sec`` log columns report the AGGREGATE
    member-steps the chip actually sustained (the north-star the
    population exists to raise). Checkpoints hold the [M]-stacked tree
    (learner-only by default, the whole stacked carry under
    ``checkpoint_replay``) plus a ``POPULATION`` width marker; resume
    at a different ``--population`` is refused with the actual cause,
    and ``restore_params(member=k)`` extracts one member for
    evaluate.py / the serving ModelStore.
    """
    from dist_dqn_tpu import population as pop
    from dist_dqn_tpu import telemetry
    from dist_dqn_tpu.telemetry import collectors as tmc
    from dist_dqn_tpu.telemetry import watchdog as tm_watchdog

    M = cfg.population.size
    if num_devices != 1 or jax.process_count() > 1:
        raise ValueError(
            "--population composes with the single-device fused runtime "
            "only for now: the population fills ONE chip by vmap-stacking "
            "members; run one population process per device instead of "
            "--mesh-devices")
    spec = pop.resolve_spec(cfg)
    hp = pop.member_hp(cfg, spec)
    seed = cfg.seed if seed is None else seed
    total = total_env_steps or cfg.total_env_steps
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    # Built before any heartbeat or server exists: a combination the one
    # chunk program refuses (train_loop.fused_parts) leaves nothing behind.
    init_p, run_population_chunk = pop.make_population_train(cfg, env, net)

    _flight = telemetry.get_flight()
    _hb_chunk = tm_watchdog.heartbeat(
        "population.chunk", startup_grace_s=tm_watchdog.STARTUP_GRACE_S)
    _reg = telemetry.get_registry()
    _fl = {"loop": "fused"}
    _reg.gauge(tmc.POPULATION_SIZE,
               "vmap-stacked members in this run", _fl).set(M)
    _member_loss = [
        _reg.gauge(tmc.POPULATION_LOSS, "chunk-mean TD loss per member",
                   {**_fl, "member": str(k)}) for k in range(M)]
    _member_eval = [
        _reg.gauge(tmc.POPULATION_EVAL_RETURN,
                   "greedy eval return per member",
                   {**_fl, "member": str(k)}) for k in range(M)]
    # The shared fused-loop families count AGGREGATE member-steps: the
    # chip runs M policies, so its env/grad throughput is M-fold.
    _tm = {
        "env_steps": _reg.counter(tmc.ENV_STEPS, "env frames processed"),
        "env_rate": _reg.gauge(tmc.ENV_RATE, "env-steps/sec (last chunk)"),
        "grad_steps": _reg.counter(tmc.GRAD_STEPS,
                                   "learner grad steps taken"),
        "chunk": _reg.histogram("dqn_chunk_seconds",
                                "fused chunk wall time"),
        "loss": _reg.gauge("dqn_loss", "chunk-mean TD loss"),
        "episodes": _reg.counter("dqn_episodes_completed_total",
                                 "training episodes finished"),
        "ep_return": _reg.gauge("dqn_episode_return",
                                "chunk-mean finished-episode return"),
        "grad_rate": _reg.gauge(tmc.LEARNER_GRAD_RATE,
                                "grad steps per second (last chunk)",
                                _fl),
    }
    from dist_dqn_tpu import loop_common as _lc
    _reg.gauge(tmc.LEARNER_REPLAY_RATIO,
               "grad sub-steps per train event",
               _fl).set(_lc.resolve_replay_ratio(cfg))
    _reg.gauge(tmc.LEARNER_TRAIN_BATCH,
               "effective (bucketed) train batch width",
               _fl).set(_lc.resolve_train_batch(cfg))
    telemetry_server = None
    if telemetry_port is not None:
        telemetry_server = telemetry.start_server(telemetry_port,
                                                  host=telemetry_host)
        log_fn(json.dumps({"telemetry_port": telemetry_server.port}))
        from dist_dqn_tpu.telemetry import fleet as _fleet
        _fleet.register_endpoint("learner", telemetry_server.port,
                                 host=telemetry_host,
                                 labels={"loop": "fused"})

    # Per-member host rng streams: member k's stream is EXACTLY the one
    # a solo run seeded with member_seeds(seed, M)[k] would consume —
    # init key and eval keys split in the same order (the PR 5
    # spawn-key discipline; the member-independence pin depends on it).
    seeds = pop.member_seeds(seed, M)
    host_rngs = [jax.random.PRNGKey(s) for s in seeds]
    k_inits = []
    for k in range(M):
        host_rngs[k], k_init = jax.random.split(host_rngs[k])
        k_inits.append(np.asarray(k_init))
    carries = init_p(np.stack(k_inits), hp)
    run = jax.jit(run_population_chunk, static_argnums=2, donate_argnums=0)
    evaluate = jax.jit(jax.vmap(make_evaluator(
        cfg, env, net, num_episodes=cfg.eval_episodes)))
    ckpt = None
    frame_offset = 0
    resumed_frames = 0
    if checkpoint_dir:
        from dist_dqn_tpu.utils.checkpoint import (TrainCheckpointer,
                                                   checkpoint_tree,
                                                   record_checkpoint_kind,
                                                   record_population_size)
        ckpt = TrainCheckpointer(
            checkpoint_dir,
            save_every_frames=save_every_frames or cfg.eval_every_steps
            or 100_000)
        record_checkpoint_kind(checkpoint_dir,
                               "carry" if checkpoint_replay else "learner")
        try:
            record_population_size(checkpoint_dir, M)
        except ValueError:
            # The stacked tree's member axis is structural: resuming a
            # population-M' directory at M would fail as an opaque
            # shape mismatch — refuse with the cause, counted under the
            # same family as the host-replay sidecar pins.
            _reg.counter(tmc.CHECKPOINT_REFUSED,
                         "resume attempts refused at the sidecar pins",
                         {**_fl, "reason": "population"}).inc()
            raise
        saved = checkpoint_tree(carries, checkpoint_replay)
        restored = ckpt.restore_latest(
            saved, older=twin_obs_checkpoint(env, saved)
            if checkpoint_replay else None)
        if restored is not None:
            frame_offset, tree = restored
            resumed_frames = frame_offset
            log_fn(json.dumps({"resumed_at_frames": frame_offset,
                               "with_replay": checkpoint_replay,
                               "population": M}))
            if checkpoint_replay:
                carries = carries._replace(**tree)
                frame_offset = 0
            else:
                carries = carries._replace(learner=tree)

    _emerg = {"frames": resumed_frames, "carry": carries}
    if ckpt is not None:
        from dist_dqn_tpu.utils.checkpoint import save_pytree as _save_pt

        def _emergency_save():
            import os

            _save_pt(os.path.join(checkpoint_dir, "emergency_learner"),
                     {"learner": checkpoint_tree(_emerg["carry"],
                                                 checkpoint_replay)})

        tm_watchdog.register_emergency_hook("population.checkpoint",
                                            _emergency_save)

    B = cfg.actor.num_envs
    history = []
    frames = resumed_frames   # PER-MEMBER cursor (see docstring)
    next_eval = frames if cfg.eval_every_steps else float("inf")
    chunk_index = 0
    profile_chunk = 1 if total > frames + chunk_iters * B else 0
    try:
        if frames < total:
            _compile_chunk(run, carries, hp, chunk_iters)
        while frames < total:
            profiling = (profile_dir is not None
                         and chunk_index == profile_chunk)
            if profiling:
                jax.profiler.start_trace(profile_dir)
            t0 = time.perf_counter()
            carries, metrics = run(carries, hp, chunk_iters)
            # Every metric leaf is [M]; fetch once, fence the chunk.
            metrics = jax.tree.map(np.asarray, jax.device_get(metrics))
            dt = time.perf_counter() - t0
            if profiling:
                jax.profiler.stop_trace()
                log_fn(json.dumps({"profile_trace": profile_dir}))
            chunk_index += 1
            prev_frames = frames
            # Members advance in lockstep (same lane count, same chunk),
            # so member 0's cumulative frame metric IS the cursor.
            frames = frame_offset + int(metrics["env_frames"][0])
            frames_delta = max(frames - prev_frames, 0)
            grad_member = float(np.mean(metrics["grad_steps_in_chunk"]))
            grad_total = float(np.sum(metrics["grad_steps_in_chunk"]))
            _tm["env_steps"].inc(frames_delta * M)
            _tm["env_rate"].set(frames_delta * M / dt)
            _tm["grad_steps"].inc(grad_total)
            _tm["chunk"].observe(dt)
            _tm["grad_rate"].set(grad_total / dt)
            _hb_chunk.beat()
            losses = [float(v) for v in metrics["loss"]]
            _loss = float(np.mean(losses))
            for k in range(M):
                _member_loss[k].set(losses[k])
            _flight.record("chunk", "population.chunk", frames=frames,
                           loss=_loss, wall_s=round(dt, 4))
            # The sentinel watches the population MEAN: one diverged
            # member shifts it enough to trip, and the forensics
            # bundle's registry snapshot carries the per-member gauges
            # to say which.
            tm_watchdog.observe_divergence(loss=_loss, step=frames)
            _tm["loss"].set(_loss)
            episodes = float(np.sum(metrics["episodes"]))
            _tm["episodes"].inc(max(episodes, 0.0))
            ep_members = metrics["episodes"] > 0
            if np.any(ep_members):
                _tm["ep_return"].set(float(np.mean(
                    metrics["episode_return"][ep_members])))
            telemetry.sweep_device_memory(_reg)
            row = {
                "env_frames": frames,
                "population": M,
                "episode_return": (float(np.mean(
                    metrics["episode_return"][ep_members]))
                    if np.any(ep_members) else 0.0),
                "episodes": episodes,
                "loss": _loss,
                "loss_members": losses,
                # Aggregate member-steps/sec — the chip's actual
                # throughput and the bench acceptance column.
                "env_steps_per_sec": M * chunk_iters * B / dt,
                "grad_steps_in_chunk": grad_member,
                "grad_steps_per_sec": grad_total / dt,
                "grad_steps_per_sec_member": grad_member / dt,
            }
            if frames >= next_eval:
                keys = []
                for k in range(M):
                    host_rngs[k], k_eval = jax.random.split(host_rngs[k])
                    keys.append(np.asarray(k_eval))
                rets = np.asarray(jax.device_get(evaluate(
                    carries.learner.params, np.stack(keys))))
                row["eval_return_members"] = [float(r) for r in rets]
                row["eval_return"] = float(np.mean(rets))
                for k in range(M):
                    _member_eval[k].set(float(rets[k]))
                next_eval = frames + cfg.eval_every_steps
            history.append(row)

            def _round(v):
                if isinstance(v, float):
                    return round(v, 3)
                if isinstance(v, list):
                    return [round(x, 3) if isinstance(x, float) else x
                            for x in v]
                return v

            log_fn(json.dumps({k: _round(v) for k, v in row.items()}))
            _emerg["frames"], _emerg["carry"] = frames, carries
            if ckpt is not None:
                ckpt.maybe_save(
                    frames, checkpoint_tree(carries, checkpoint_replay))
            if stop_fn is not None and stop_fn(row):
                break
    finally:
        _hb_chunk.close()
        tm_watchdog.unregister_emergency_hook("population.checkpoint")
    if ckpt is not None:
        ckpt.save(frames, checkpoint_tree(carries, checkpoint_replay))
        ckpt.close()
    if telemetry_server is not None:
        telemetry_server.close()
    return carries, history


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", choices=sorted(CONFIGS), required=True)
    parser.add_argument("--set", dest="overrides", action="append",
                        metavar="PATH=VALUE", default=[],
                        help="override any config field by dotted path, "
                             "repeatable (e.g. --set network.dueling=true "
                             "--set learner.batch_size=64); values are "
                             "coerced to the field's type")
    parser.add_argument("--total-env-steps", type=int, default=0)
    parser.add_argument("--seed", type=int, default=None)
    parser.add_argument("--chunk-iters", type=int, default=2000)
    parser.add_argument("--population", type=int, default=None,
                        metavar="M",
                        help="fused runtime (ISSUE 20): train M "
                             "vmap-stacked policies (population.size) as "
                             "ONE program — every carry leaf gains a "
                             "leading member axis and one dispatch per "
                             "chunk advances all members. Per-member "
                             "seeds spawn from --seed (member k of an "
                             "M-run bit-matches a stacked run with only "
                             "member k); --population 1 is bit-identical "
                             "to the plain program. Mutually exclusive "
                             "with --mesh-devices; see --population-spec "
                             "and docs/performance.md")
    parser.add_argument("--population-spec", default=None, metavar="JSON",
                        help="per-member hyperparameter vectors "
                             "(population.spec_json): a JSON object with "
                             "any of \"epsilon\" (exploration floor "
                             "epsilon_end), \"lr\", \"gamma\" — each a "
                             "length-M array; members without an "
                             "override inherit the config. Example: "
                             "--population 2 --population-spec "
                             "'{\"lr\": [1e-3, 3e-4]}'")
    parser.add_argument("--replay-ratio", type=int, default=None,
                        metavar="N",
                        help="on-device replay ratio "
                             "(replay.updates_per_chunk): N grad "
                             "sub-steps per train event, each drawing "
                             "an independent replay batch, scanned "
                             "inside one jitted program. Supported by "
                             "the fused (feed-forward), host-replay "
                             "and single-learner apex runtimes; 1 is "
                             "bit-identical to the pre-knob program")
    parser.add_argument("--actor-dtype", choices=("float32", "bfloat16"),
                        default=None,
                        help="actor-inference dtype split "
                             "(network.actor_dtype): bfloat16 casts "
                             "the params once per chunk for acting "
                             "while the learner keeps fp32 masters. "
                             "fused + host-replay runtimes; float32 "
                             "(default) is bit-identical to the "
                             "pre-knob program")
    parser.add_argument("--no-double-buffer", action="store_true",
                        help="--runtime host-replay only: disable the "
                             "double-buffered H2D staging path "
                             "(replay/staging.py) and sample->upload->"
                             "train serially — the numerically identical "
                             "A/B reference for a suspected staging "
                             "issue")
    parser.add_argument("--no-pipeline", action="store_true",
                        help="--runtime host-replay only: disable the "
                             "three-stage collect/evacuate/train "
                             "pipeline (streamed sub-chunk D2H + "
                             "background evacuation worker) and "
                             "evacuate each chunk with one blocking "
                             "monolithic fetch — the numerically "
                             "identical serial A/B reference (same "
                             "collect-ahead schedule, zero overlap)")
    parser.add_argument("--evac-slices", type=int, default=4,
                        help="--runtime host-replay only: time slices "
                             "each chunk's D2H evacuation streams "
                             "through (replay/staging.py "
                             "StreamedEvacuator); higher overlaps "
                             "transfers and ring appends at finer "
                             "grain, 1 = one streamed piece. Ignored "
                             "under --no-pipeline")
    parser.add_argument("--no-prefetch", action="store_true",
                        help="--runtime host-replay only: disable the "
                             "background SamplePrefetcher (replay/"
                             "staging.py) and sample train batches on "
                             "the main thread between steps — the "
                             "numerically identical serial A/B "
                             "reference for the sample-side pipeline "
                             "(bit-identical under a fixed seed in "
                             "uniform mode)")
    parser.add_argument("--prefetch-depth", type=int, default=2,
                        help="--runtime host-replay only: device-"
                             "resident batches the SamplePrefetcher "
                             "may stage ahead of the learner (bounds "
                             "host staging memory and sample "
                             "run-ahead). Ignored under --no-prefetch")
    parser.add_argument("--per", action="store_true",
                        help="--runtime host-replay only: force "
                             "prioritized (sum-tree) replay sampling "
                             "with IS weights and batched TD-error "
                             "write-backs; presets with "
                             "replay.prioritized=True enable it by "
                             "default (uniform otherwise)")
    parser.add_argument("--checkpoint-dir", default=None,
                        help="enable checkpoint/resume under this "
                             "directory (orbax; restores newest on "
                             "start). Every runtime configuration that "
                             "trains can checkpoint: host-replay saves "
                             "whole state at any --mesh-devices width "
                             "and under --per (bit-identical resume, "
                             "shard/sampler pins enforced); apex "
                             "--checkpoint-replay snapshots survive "
                             "--ingest-shards changes via the resharding "
                             "migration")
    parser.add_argument("--save-every-frames", type=int, default=0,
                        help="checkpoint period in env frames "
                             "(default: eval_every_steps)")
    parser.add_argument("--checkpoint-replay", action="store_true",
                        help="also checkpoint replay state: the fused "
                             "runtime saves the WHOLE carry (resume is "
                             "bit-equal to an uninterrupted run); the "
                             "apex runtime snapshots the host shard "
                             "beside the learner checkpoint (warm-buffer "
                             "resume). Ring-sized checkpoints (a 65k "
                             "pixel ring is ~1.8 GB vs ~7 MB learner-"
                             "only); default refills from live experience")
    parser.add_argument("--eval-every-steps", type=int, default=None,
                        help="eval period in env steps. Default: config "
                             "value on the fused runtime; DISABLED on the "
                             "apex runtime (its eval steps host envs "
                             "synchronously and stalls the service loop)")
    parser.add_argument("--profile-dir", default=None,
                        help="capture a jax.profiler trace of one chunk "
                             "into this directory (view with TensorBoard "
                             "/ xprof). All three runtimes; the fused "
                             "runtime traces a STEADY chunk (the first "
                             "after one that took the full cadence's grad "
                             "steps), the others their first post-warmup "
                             "chunk. For a window at an arbitrary "
                             "point of a LIVE run, use the telemetry "
                             "server's /debug/profile?seconds=N endpoint "
                             "(or /fleet/profile on the aggregator) "
                             "instead — no restart needed")
    parser.add_argument("--trace-path", default=None,
                        help="apex and fused runtimes: write a Chrome "
                             "trace-event file of the host loop (apex: "
                             "ingest/sample/train spans; fused: "
                             "fused.dispatch/fence/bookkeeping per chunk; "
                             "open in Perfetto) to this path")
    parser.add_argument("--telemetry-port", type=int, default=None,
                        help="serve the process telemetry registry's "
                             "/metrics endpoint (Prometheus text format) "
                             "on this port; 0 binds an ephemeral port "
                             "(reported as a telemetry_port log line). "
                             "Works on both runtimes; see "
                             "docs/observability.md")
    parser.add_argument("--telemetry-host", default="127.0.0.1",
                        help="bind address for --telemetry-port: loopback "
                             "by default (the metric/debug surface is "
                             "unauthenticated); 0.0.0.0 makes /metrics "
                             "and /healthz scrapeable from outside the "
                             "container/VM. All runtimes")
    parser.add_argument("--telemetry-snapshot", default=None,
                        help="dump a JSON snapshot of the telemetry "
                             "registry to this path at exit (offline "
                             "runs; same data as /metrics.json)")
    parser.add_argument("--fleet-dir", default=None,
                        help="fleet registry directory (ISSUE 16): this "
                             "process writes a role-labeled endpoint "
                             "descriptor next to every other member of "
                             "the run so the fleet aggregator (python "
                             "-m dist_dqn_tpu.telemetry.fleet) can "
                             "federate one /metrics pane + /fleet/"
                             "status rollup. Exported as DQN_FLEET_DIR "
                             "so spawned actor/feeder processes "
                             "register their own endpoints. Requires "
                             "--telemetry-port")
    parser.add_argument("--forensics-dir", default=None,
                        help="arm the stall watchdog + divergence "
                             "sentinel (telemetry/watchdog.py): a "
                             "pipeline stage missing its heartbeat "
                             "deadline, or a NaN/Inf loss, dumps a "
                             "forensics bundle (named thread stacks, "
                             "flight-recorder tail, registry snapshot, "
                             "run manifest) under this directory and "
                             "flips /healthz to 503. Exported as "
                             "DQN_FORENSICS_DIR so spawned actor/feeder "
                             "processes arm their own. See the "
                             "'debugging a hang' runbook in "
                             "docs/observability.md")
    parser.add_argument("--watchdog-deadline-s", type=float, default=120.0,
                        help="heartbeat staleness that counts as a stall "
                             "(per stage; requires --forensics-dir)")
    parser.add_argument("--watchdog-abort", action="store_true",
                        help="after dumping the forensics bundle, "
                             "SIGTERM the process (graceful: the "
                             "telemetry flush chains off SIGTERM) "
                             "with a bounded hard-exit "
                             "fallback — for supervisors that restart "
                             "on exit rather than scrape /healthz")
    parser.add_argument("--no-flight-recorder", action="store_true",
                        help="disable the in-memory flight-recorder "
                             "ring (telemetry/flight.py; ~1µs/event "
                             "when on). Forensics bundles and "
                             "/debug/flight then carry no event tail")
    parser.add_argument("--platform", default=None,
                        help="force a JAX platform (e.g. cpu, tpu)")
    parser.add_argument("--mesh-devices", type=int, default=1,
                        help="fused + host-replay runtimes: run over a "
                             "dp mesh of this many devices (0 = all; "
                             "multi-process runs use the GLOBAL device "
                             "list). Fused: env lanes + replay shard "
                             "per device. Host-replay: one COLLECT "
                             "program + env-lane block + host ring / "
                             "evac worker / sample prefetcher per "
                             "device (sharded collect — acting is "
                             "data-parallel too, zero cross-shard "
                             "lane scatter). Gradients pmean over the "
                             "mesh either way; apex uses "
                             "--learner-devices instead")
    parser.add_argument("--coordinator", default=None,
                        help="multi-host: host:port of process 0's "
                             "jax.distributed coordinator. Every host runs "
                             "this same command with its own --process-id; "
                             "checkpoints need a shared directory")
    parser.add_argument("--num-processes", type=int, default=1,
                        help="multi-host: total process count")
    parser.add_argument("--process-id", type=int, default=0,
                        help="multi-host: this process's id (0-based)")
    parser.add_argument("--stop-at-return", type=float, default=None,
                        help="fused runtime, single-process: stop early "
                             "once eval_return reaches this value (e.g. "
                             "475 = CartPole solved)")
    parser.add_argument("--runtime", choices=("fused", "apex",
                                              "host-replay"),
                        default="fused",
                        help="fused: on-device Anakin loop (JAX envs); "
                             "apex: CPU actor processes + learner service "
                             "over the shm/DCN transport (host envs)")
    parser.add_argument("--host-env", default="CartPole-v1",
                        help="apex runtime: host env actors step "
                             "(e.g. CartPole-v1, ale:Pong)")
    parser.add_argument("--num-actors", type=int, default=4)
    parser.add_argument("--envs-per-actor", type=int, default=8)
    parser.add_argument("--num-remote-actors", type=int, default=0,
                        help="apex runtime: remote (TCP) actor slots")
    parser.add_argument("--learner-devices", type=int, default=1,
                        help="apex runtime: shard train batches over this "
                             "many local devices (0 = all; gradients "
                             "pmean over ICI)")
    parser.add_argument("--tcp-port", type=int, default=None,
                        help="apex runtime: listen for remote actors "
                             "(actors/remote.py) on this port; 0 = "
                             "ephemeral")
    parser.add_argument("--device-sampling", action="store_true",
                        help="sample replay priorities ON DEVICE (Pallas "
                             "stratified kernel; items stay in host "
                             "DRAM). Apex runtime: one priority plane "
                             "per --ingest-shards replay shard, each on "
                             "its own chip. Host-replay runtime (with "
                             "--per): one plane per --mesh-devices "
                             "shard, replacing the host sum-trees")
    parser.add_argument("--transport", choices=("zerocopy", "legacy"),
                        default="zerocopy",
                        help="apex runtime experience path (ISSUE 9): "
                             "zerocopy = schema-negotiated raw-array "
                             "frames (shm slot rings locally, zero-copy "
                             "framing on TCP) with actor-shipped "
                             "priorities; legacy = the bit-pinned "
                             "JSON-codec fallback")
    parser.add_argument("--no-actor-priorities", action="store_true",
                        help="apex runtime: keep the learner-side "
                             "priority bootstrap dispatches even on "
                             "--transport zerocopy (A/B baseline; "
                             "re-enables native assembly)")
    parser.add_argument("--ingest-shards", type=int, default=1,
                        help="apex runtime: replay-shard count — the "
                             "store splits into N PrioritizedHostReplay "
                             "shards and every actor's stream lands in "
                             "its sticky crc32 shard (ingest/router.py; "
                             "records_by_shard in the summary proves "
                             "the spread). N > 1 requires the zerocopy "
                             "transport with actor priorities (or a "
                             "recurrent config) for per-actor insert "
                             "attribution; sampling runs on the host "
                             "trees or, with --device-sampling, on one "
                             "per-shard device priority plane each")
    parser.add_argument("--no-wire-dedup", action="store_true",
                        help="apex runtime (ISSUE 14): disable the "
                             "frame-stack dedup wire plane — actors on "
                             "frame-stacked pixel envs then ship full "
                             "stacks on the plain zero-copy layout "
                             "(the dedup-off A/B arm)")
    parser.add_argument("--shm-batch", type=int, default=1,
                        help="apex runtime (ISSUE 14): feeder processes "
                             "coalesce this many step records into one "
                             "seqlock slot publish (amortizes the "
                             "publish/consume handshake for unthrottled "
                             "producers; 1 = bit-pinned per-record "
                             "publishes; rollout actors are lock-step "
                             "and unaffected)")
    parser.add_argument("--shard-sampling", action="store_true",
                        help="apex runtime (ISSUE 14, requires "
                             "--ingest-shards > 1): run the stratified "
                             "draw + gather in per-shard worker threads "
                             "and hand the learner pre-packed batches "
                             "through a bounded queue — train events "
                             "stop paying sample time on the learner "
                             "thread")
    parser.add_argument("--remote-actor-mode", choices=("local", "external"),
                        default="local",
                        help="local: the service spawns its remote actors "
                             "as local processes (single-host DCN "
                             "stand-in); external: slots stay open for "
                             "workers started on other hosts via "
                             "python -m dist_dqn_tpu.actors.remote")
    args = parser.parse_args(argv)
    if args.telemetry_snapshot:
        from dist_dqn_tpu.telemetry import install_snapshot_dump
        install_snapshot_dump(args.telemetry_snapshot)
    import os as _os
    import sys as _sys
    if args.no_flight_recorder:
        # Before any loop wires its recorder reference, and through the
        # environment so spawned actor/feeder processes disable theirs.
        from dist_dqn_tpu.telemetry import flight as _flight_mod
        _os.environ["DQN_FLIGHT_RECORDER"] = "0"
        _flight_mod.configure(enabled=False)
    if args.fleet_dir:
        # Through the environment (like DQN_FORENSICS_DIR) so spawned
        # actor/feeder processes register their own fleet descriptors.
        _os.environ["DQN_FLEET_DIR"] = args.fleet_dir
    if args.forensics_dir:
        from dist_dqn_tpu.telemetry import watchdog as _wd
        _os.environ["DQN_FORENSICS_DIR"] = args.forensics_dir
        _os.environ["DQN_WATCHDOG_DEADLINE_S"] = \
            str(args.watchdog_deadline_s)
        _wd.install_watchdog(forensics_dir=args.forensics_dir,
                             deadline_s=args.watchdog_deadline_s,
                             abort=args.watchdog_abort)
        _wd.install_sentinel(forensics_dir=args.forensics_dir,
                             abort=args.watchdog_abort)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from dist_dqn_tpu.utils import backend as _backend
    _backend.enable_compile_cache()
    if args.coordinator:
        # Must precede the first backend touch; platform choice above feeds
        # the CPU-collectives selection (parallel/distributed.py).
        from dist_dqn_tpu.parallel.distributed import initialize
        initialize(args.coordinator, args.num_processes, args.process_id)
    # First log line: the device as JAX reports it (the manifest below
    # carries the same block).
    _device = _backend.log_device()
    try:
        cfg = apply_overrides(CONFIGS[args.config], args.overrides)
    except ValueError as e:
        parser.error(str(e))
    if args.eval_every_steps is not None:
        # An explicit 0 DISABLES eval (the loop convention) — a plain
        # truthiness test here silently fell back to the config period.
        import dataclasses as _dc
        cfg = _dc.replace(cfg, eval_every_steps=args.eval_every_steps)
    # Learner-utilization knobs (ISSUE 6): applied per runtime, with
    # the standard ignored-flag warnings where a runtime does not
    # support them yet — BEFORE the manifest so provenance records the
    # config actually run.
    import dataclasses as _dc
    _recurrent_fused = args.runtime == "fused" and cfg.network.recurrent
    if args.replay_ratio is not None:
        if _recurrent_fused:
            print("# --replay-ratio is not supported by the recurrent "
                  "(R2D2) fused loop yet (its sequence learner has no "
                  "scan-ratio path); ignored")
        else:
            cfg = _dc.replace(cfg, replay=_dc.replace(
                cfg.replay, updates_per_chunk=args.replay_ratio))
    if args.actor_dtype is not None:
        if args.runtime == "apex":
            print("# --actor-dtype applies to the fused/host-replay "
                  "runtimes only; the apex service acts on the live "
                  "learner params — ignored")
        elif _recurrent_fused:
            print("# --actor-dtype is not supported by the recurrent "
                  "(R2D2) fused loop yet; ignored")
        else:
            cfg = _dc.replace(cfg, network=_dc.replace(
                cfg.network, actor_dtype=args.actor_dtype))
    # Population plane (ISSUE 20): fused-runtime-only, like the knobs
    # above — warn-and-ignore on runtimes without a member axis, but
    # REFUSE the population x mesh cross outright (silently dropping
    # either flag would run a different experiment than asked for).
    if args.population is not None or args.population_spec is not None:
        if args.population is not None and args.population < 1:
            parser.error(f"--population must be >= 1, got "
                         f"{args.population}")
        if args.runtime != "fused":
            print("# --population/--population-spec apply to the fused "
                  "runtime only (the apex/host-replay runtimes have no "
                  "stacked-member plane yet); ignored")
        elif _recurrent_fused:
            print("# --population is not supported by the recurrent "
                  "(R2D2) fused loop yet (its sequence learner has no "
                  "member axis); ignored")
        elif args.mesh_devices != 1 and (args.population or 1) > 1:
            parser.error(
                "--population and --mesh-devices are mutually exclusive: "
                "the population fills ONE chip by vmap-stacking members; "
                "run one population process per device (or drop one "
                "flag)")
        else:
            cfg = _dc.replace(cfg, population=_dc.replace(
                cfg.population,
                size=(args.population if args.population is not None
                      else cfg.population.size),
                spec_json=(args.population_spec
                           if args.population_spec is not None
                           else cfg.population.spec_json)))
            try:
                # Validate at the CLI boundary (spec shape/range + the
                # lr-schedule pin), not as a mid-startup stack trace.
                from dist_dqn_tpu.population import resolve_spec as _rs
                _rs(cfg)
            except ValueError as e:
                parser.error(str(e))
    # Run manifest (ISSUE 4 satellite): one provenance line per run —
    # git sha, versions, config hash, argv — reused verbatim by the
    # forensics bundles and served at /debug/config.
    from dist_dqn_tpu.telemetry import manifest as _manifest
    _man = _manifest.build_manifest(cfg, argv=argv or _sys.argv,
                                    extra={"device": _device})
    _manifest.set_run_manifest(_man)
    print(json.dumps({"manifest": _man}))
    # Chaos (ISSUE 8): game-day runs arm a fault plan via DQN_CHAOS_PLAN
    # — AFTER the manifest is set so the armed plan annotates it (the
    # provenance line above already printed; /debug/config and the
    # forensics bundles read the annotated copy).
    from dist_dqn_tpu import chaos as _chaos
    _chaos.maybe_install_from_env()
    if args.runtime == "host-replay":
        # Hybrid fused loop with the replay window in host DRAM
        # (host_replay_loop.py): device env chunks stream transitions
        # down once, sampled batches stream back double-buffered. The
        # window is DRAM-priced — set replay.capacity accordingly
        # (e.g. --set replay.capacity=8000000 with frame_dedup).
        if args.stop_at_return is not None:
            print("# --stop-at-return is not supported by --runtime "
                  "host-replay (prototype surface); ignored")
        if args.checkpoint_replay:
            print("# --checkpoint-replay is implied by --runtime "
                  "host-replay --checkpoint-dir: its checkpoints are "
                  "always whole-state (per-shard rings + PER sampler "
                  "state + carry + learner) so resume is bit-identical "
                  "at any --mesh-devices width; flag ignored")
        if args.save_every_frames and not args.checkpoint_dir:
            print("# --save-every-frames does nothing without "
                  "--checkpoint-dir; ignored")
        if args.eval_every_steps:
            print("# periodic eval is not supported by --runtime "
                  "host-replay; ignored")
        if args.seed is not None:
            import dataclasses as _dc
            cfg = _dc.replace(cfg, seed=args.seed)
        from dist_dqn_tpu.host_replay_loop import run_host_replay

        if args.telemetry_port is not None:
            # The host ring and chunk loops record into the process
            # registry regardless; this just exposes the scrape surface.
            from dist_dqn_tpu import telemetry as _telemetry
            _srv = _telemetry.start_server(args.telemetry_port,
                                           host=args.telemetry_host)
            print(json.dumps({"telemetry_port": _srv.port}))
            from dist_dqn_tpu.telemetry import fleet as _fleet
            _fleet.register_endpoint("learner", _srv.port,
                                     host=args.telemetry_host,
                                     labels={"loop": "host_replay"})
        out = run_host_replay(
            cfg, total_env_steps=args.total_env_steps or cfg.total_env_steps,
            chunk_iters=args.chunk_iters, log_fn=print,
            double_buffer=not args.no_double_buffer,
            pipeline=not args.no_pipeline,
            evac_slices=args.evac_slices,
            prefetch=not args.no_prefetch,
            prefetch_depth=args.prefetch_depth,
            # None = follow cfg.replay.prioritized; --per forces it on.
            prioritized=True if args.per else None,
            checkpoint_dir=args.checkpoint_dir,
            save_every_frames=args.save_every_frames,
            mesh_devices=args.mesh_devices,
            device_sampling=args.device_sampling,
            profile_dir=args.profile_dir)
        out.pop("history", None)
        print(json.dumps(out))
        return
    if args.runtime == "apex":
        if args.mesh_devices != 1:
            print("# --mesh-devices applies to the fused/host-replay "
                  "runtimes; use --learner-devices for apex batch "
                  "sharding")
        if args.stop_at_return is not None:
            print("# --stop-at-return applies to the fused runtime only; "
                  "ignored under --runtime apex")
        if args.no_double_buffer:
            print("# --no-double-buffer applies to --runtime host-replay "
                  "only; the apex service staging knob is "
                  "ApexRuntimeConfig.stage_depth — ignored")
        if args.no_pipeline \
                or args.evac_slices != parser.get_default("evac_slices"):
            print("# --no-pipeline/--evac-slices apply to --runtime "
                  "host-replay only; ignored under --runtime apex")
        if args.no_prefetch or args.per \
                or args.prefetch_depth != parser.get_default(
                    "prefetch_depth"):
            print("# --no-prefetch/--prefetch-depth/--per apply to "
                  "--runtime host-replay only; the apex service is "
                  "always prioritized and staged via "
                  "ApexRuntimeConfig — ignored")
        import dataclasses

        from dist_dqn_tpu.actors.service import ApexRuntimeConfig, run_apex
        from dist_dqn_tpu.envs.gym_adapter import is_pixel_env
        if not is_pixel_env(args.host_env):
            # Non-pixel host env: the config's Nature-CNN torso can't eat
            # flat observations — swap in the MLP torso, keep the rest.
            print(f"# host env {args.host_env} is non-pixel: using MLP torso")
            cfg = dataclasses.replace(
                cfg, network=dataclasses.replace(
                    cfg.network, torso="mlp", compute_dtype="float32"))
        rt = ApexRuntimeConfig(
            host_env=args.host_env, num_actors=args.num_actors,
            envs_per_actor=args.envs_per_actor,
            total_env_steps=args.total_env_steps or cfg.total_env_steps,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_replay=args.checkpoint_replay,
            save_every_steps=(args.save_every_frames or cfg.eval_every_steps
                              or 100_000),
            eval_every_steps=(args.eval_every_steps
                              if args.eval_every_steps is not None else 0),
            eval_episodes=cfg.eval_episodes,
            tcp_port=args.tcp_port,
            num_remote_actors=args.num_remote_actors,
            spawn_remote_actors=args.remote_actor_mode == "local",
            learner_devices=args.learner_devices,
            trace_path=args.trace_path,
            device_sampling=args.device_sampling,
            transport=args.transport,
            actor_priorities=not args.no_actor_priorities,
            ingest_shards=args.ingest_shards,
            wire_dedup=not args.no_wire_dedup,
            shm_batch=args.shm_batch,
            shard_sampling=args.shard_sampling,
            telemetry_port=args.telemetry_port,
            telemetry_host=args.telemetry_host,
            profile_dir=args.profile_dir)
        print(json.dumps(run_apex(cfg, rt)))
        return
    if args.transport != parser.get_default("transport") \
            or args.no_actor_priorities \
            or args.ingest_shards != parser.get_default("ingest_shards") \
            or args.no_wire_dedup or args.shard_sampling \
            or args.shm_batch != parser.get_default("shm_batch"):
        print("# --transport/--no-actor-priorities/--ingest-shards/"
              "--no-wire-dedup/--shm-batch/--shard-sampling apply "
              "to --runtime apex only (the fused/host-replay runtimes "
              "have no actor transport); ignored")
    if args.no_double_buffer:
        print("# --no-double-buffer applies to --runtime host-replay only; "
              "ignored under the fused runtime (its replay never leaves "
              "the device)")
    if args.no_pipeline \
            or args.evac_slices != parser.get_default("evac_slices"):
        print("# --no-pipeline/--evac-slices apply to --runtime "
              "host-replay only; ignored under the fused runtime (its "
              "replay never leaves the device)")
    if args.no_prefetch or args.per \
            or args.prefetch_depth != parser.get_default("prefetch_depth"):
        print("# --no-prefetch/--prefetch-depth/--per apply to "
              "--runtime host-replay only; ignored under the fused "
              "runtime (its replay samples on device — "
              "replay.prioritized selects the device sampler there)")
    if args.device_sampling:
        print("# --device-sampling applies to the apex/host-replay "
              "runtimes; ignored under the fused runtime (its replay "
              "is device-resident already)")
    stop_fn = None
    if args.stop_at_return is not None:
        target = args.stop_at_return
        stop_fn = lambda row: row.get("eval_return",  # noqa: E731
                                      -float("inf")) >= target
    train(cfg, total_env_steps=args.total_env_steps, seed=args.seed,
          chunk_iters=args.chunk_iters, checkpoint_dir=args.checkpoint_dir,
          save_every_frames=args.save_every_frames,
          profile_dir=args.profile_dir, num_devices=args.mesh_devices,
          stop_fn=stop_fn, checkpoint_replay=args.checkpoint_replay,
          telemetry_port=args.telemetry_port,
          telemetry_host=args.telemetry_host, trace_path=args.trace_path)


if __name__ == "__main__":
    main()
