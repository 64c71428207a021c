"""Hybrid fused loop with HOST-DRAM replay (BASELINE.json:5's north-star
phrase — "replay buffer shards across TPU-VM host DRAM" — applied to the
single-chip fused path, VERDICT round-4 next #2).

The all-on-device loop (train_loop.py) is the throughput king, but its
replay window lives in HBM: ~200k stacked / ~1M deduped pixel
transitions on a 16 GB v5e. This loop splits the program at the replay
boundary instead, and since ISSUE 3 runs the split as a THREE-STAGE
SOFTWARE PIPELINE rather than a serial chunk loop:

  device: [act -> env.step] x chunk_iters  (one jitted scan, no replay)
     |  chunk g+1 is dispatched BEFORE chunk g's train event, so its
     |  device compute overlaps chunk g's evacuation and training
     |  (collect therefore acts on params one train event stale — in
     |  BOTH the pipelined and the serial reference path, so the two
     |  stay bit-identical; Podracer-style off-policy staleness)
  d2h:   chunk records leave as --evac-slices streamed time slices
     |  (replay/staging.py StreamedEvacuator): one split dispatch, all
     |  host copies started async, slice k's ring append overlapping
     |  slice k+1's transfer — drained by a BACKGROUND EVACUATION
     |  WORKER so the main thread keeps dispatching
  host:  HostTimeRing in DRAM — the window is DRAM-sized (hundreds of
     |  GB => hundreds of millions of pixel transitions); slice appends
     |  publish atomically under the ring's generation fence, and the
     |  train event fences on the chunk's completion handle before
     |  sampling, so a batch never sees a half-appended slice
  device: train_step (donated state), exactly the learner the fused
          loop runs; sampled batches H2D double-buffered as before

Since ISSUE 5 the H2D side is pipelined too: a SamplePrefetcher thread
(replay/staging.py — the H2D twin of the EvacuationWorker) runs
sample -> gather -> pin -> upload ahead of the learner, so train steps
pop device-resident batches instead of paying host-side sampling on
the critical path; batch k's RNG is a per-index stream split from the
seed, so the prefetched and serial paths draw bit-identical batches
(``prefetch=False`` / --no-prefetch is the pinned serial reference).
Sampling is also PRIORITIZED now (cfg.replay.prioritized / --per): a
NativeSumTree shard over the ring's slots, kept in lockstep with the
ring by the evacuation worker's appends (new chunks seeded at max
priority, under the generation fence), stratified draws + IS weights,
and TD-error write-backs batched into one vectorized tree update per
``prio_writeback_batch`` train steps (PR 2's semantics: chronological
last-wins + per-slot expected-generation drop).

Throughput model: the link, not HBM, prices the window. Per env step
the D2H cost is one stored frame; per grad step the H2D cost is one
batch (2 x batch x obs bytes). On a TPU-VM host link (~10 GB/s) that
admits ~1.4M deduped env-steps/s of collection (arithmetic, not
measured on the current installation). In the SERIAL chunk loop the
device idles for the whole evacuation and the host for the whole
collect. The pipeline takes the serial sum collect + evac + train to
~max(evac, collect + train): the
per-chunk rows carry the overlap accounting (``evac_s``,
``evac_fence_wait_s``, ``evac_overlap_frac``, ``device_idle_est_s``)
so the win is measured per run, not asserted. ``pipeline=False``
(train.py ``--no-pipeline``) keeps the monolithic blocking evacuation
as the numerically pinned A/B reference, same discipline as PR 2's
``fused_ingest=False``.
"""
from __future__ import annotations

import json
import math
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dist_dqn_tpu import chaos, loop_common
from dist_dqn_tpu.agents.dqn import make_actor_step, make_learner
from dist_dqn_tpu.config import ExperimentConfig
from dist_dqn_tpu.envs.base import JaxEnv
from dist_dqn_tpu.replay.host_ring import HostTimeRing
from dist_dqn_tpu.types import PyTree, Transition

Array = jnp.ndarray


class CollectCarry(NamedTuple):
    env_state: PyTree
    obs: PyTree
    rng: Array
    iteration: Array
    ep_return: Array


class _UniformTag(NamedTuple):
    """Uniform-mode sample bookkeeping: just the ring generation the
    batch was drawn against (the prefetcher's staleness handshake)."""

    generation: int


class _ScanCarry(NamedTuple):
    """Chunk-internal scan carry: the persistent CollectCarry fields plus
    the chunk-local episode accumulators. The accumulators are RETURNED
    as separate chunk outputs rather than carried across chunks, so the
    pipelined loop can hold and fetch them (one fused device_get) after
    the carry itself has been donated into the next chunk's dispatch."""
    env_state: PyTree
    obs: PyTree
    rng: Array
    iteration: Array
    ep_return: Array
    completed_return: Array
    completed_count: Array


def make_collect_chunk(cfg: ExperimentConfig, env: JaxEnv, net,
                       frame_stack: int, lanes: Optional[int] = None,
                       num_shards: int = 1):
    """(init, collect): a device chunk of act -> step that RETURNS its
    transitions (time-major [C, B, ...]) plus the chunk's episode stats
    instead of writing a ring.

    ``lanes``/``num_shards`` (ISSUE 15, sharded collect): build the
    PER-SHARD variant — a chunk program over ``lanes`` env lanes (one
    dp shard's lane block) whose epsilon schedule decays in per-shard
    iteration units (``make_schedules`` divides the decay horizon by
    ``lanes * num_shards``), so N shard programs together walk exactly
    the schedule the whole-B single program walks at the same global
    frame count. Defaults build the whole-B program unchanged."""
    B = cfg.actor.num_envs if lanes is None else int(lanes)
    act = make_actor_step(net)
    epsilon, _ = loop_common.make_schedules(cfg, B, num_shards)
    slice_newest = ((lambda o: o[..., -1:]) if frame_stack
                    else (lambda o: o))

    def init(rng: Array) -> CollectCarry:
        k_env, k_run = jax.random.split(rng)
        env_state, obs = env.v_reset(k_env, B)
        obs = jax.tree.map(jnp.copy, obs)
        return CollectCarry(env_state=env_state, obs=obs, rng=k_run,
                            iteration=jnp.int32(0),
                            ep_return=jnp.zeros((B,), jnp.float32))

    def collect(carry: CollectCarry, params, num_iters: int):
        def one_iteration(sc: _ScanCarry, _):
            rng, k_act = jax.random.split(sc.rng)
            eps = epsilon(sc.iteration)
            actions = act(params, sc.obs, k_act, eps)
            env_state, out = env.v_step(sc.env_state, actions)
            record = dict(obs=slice_newest(sc.obs), action=actions,
                          reward=out.reward, terminated=out.terminated,
                          truncated=out.truncated)
            done = jnp.logical_or(out.terminated, out.truncated)
            ep_return, completed_return, completed_count = \
                loop_common.episode_stats_update(sc, out.reward, done)
            return _ScanCarry(env_state=env_state, obs=out.obs, rng=rng,
                              iteration=sc.iteration + 1,
                              ep_return=ep_return,
                              completed_return=completed_return,
                              completed_count=completed_count), record

        zero = jnp.float32(0.0)
        sc = _ScanCarry(*carry, completed_return=zero,
                        completed_count=zero)
        sc, records = jax.lax.scan(one_iteration, sc, None,
                                   length=num_iters)
        carry = CollectCarry(env_state=sc.env_state, obs=sc.obs,
                             rng=sc.rng, iteration=sc.iteration,
                             ep_return=sc.ep_return)
        stats = (sc.completed_return, sc.completed_count)
        return carry, records, stats

    return init, collect


class _MultiEvacHandle:
    """Fan-in completion handle over per-shard evacuation jobs (dp > 1):
    the train event fences when EVERY shard's lane block is published.
    ``evac_s`` reports the slowest shard (the critical-path wall);
    bytes/slices aggregate."""

    def __init__(self, handles):
        self.handles = handles

    def wait(self, timeout=None) -> bool:
        ok = True
        for h in self.handles:
            ok = h.wait(timeout) and ok
        return ok

    @property
    def done(self) -> bool:
        return all(h.done for h in self.handles)

    @property
    def stats(self) -> dict:
        return {
            "evac_s": max(h.stats["evac_s"] for h in self.handles),
            "bytes": sum(h.stats["bytes"] for h in self.handles),
            "slices": sum(h.stats["slices"] for h in self.handles),
        }

    @property
    def per_shard(self) -> list:
        """[shard] -> that shard's own drained stats (ISSUE 15): the
        per-shard byte-conservation evidence and straggler wall."""
        return [h.stats for h in self.handles]


class _ResumedEvacHandle:
    """Completion-handle stand-in installed on resume: the chunk it
    fences was already appended to the ring INSIDE the checkpoint, so
    the fence is a no-op and the evacuation accounting reads zero."""

    stats = {"evac_s": 0.0, "bytes": 0, "slices": 0}
    per_shard = ()
    done = True

    def wait(self, timeout=None) -> bool:
        return True


def run_host_replay(cfg: ExperimentConfig, total_env_steps: int,
                    chunk_iters: int = 200, log_fn=print,
                    env: Optional[JaxEnv] = None,
                    double_buffer: bool = True,
                    pipeline: bool = True,
                    evac_slices: int = 4,
                    prefetch: bool = True,
                    prefetch_depth: int = 2,
                    prioritized: Optional[bool] = None,
                    prio_writeback_batch: int = 8,
                    checkpoint_dir: Optional[str] = None,
                    save_every_frames: int = 0,
                    mesh_devices: int = 1,
                    sharded_collect: Optional[bool] = None,
                    device_sampling: bool = False,
                    profile_dir: Optional[str] = None):
    """Run the hybrid loop; returns a summary dict.

    Cadence matches the fused loop: one train event every
    ``cfg.train_every`` env iterations, ``cfg.updates_per_train *
    cfg.replay.updates_per_chunk`` grad steps each (the ISSUE 6 replay
    ratio — the prefetcher simply draws that many batches per event),
    batches sampled from the host ring at the pow2-bucketed
    ``replay.train_batch`` width — uniformly, or by sum-tree priority
    when ``prioritized`` (default: ``cfg.replay.prioritized``) is set.

    ``pipeline`` selects the three-stage software pipeline (streamed
    sub-chunk evacuation drained by a background worker, trains fenced
    on the chunk's publication handle); False is the serial reference —
    one monolithic blocking ``device_get`` + one monolithic
    ``add_chunk``, device idle throughout. Both paths share the same
    collect-ahead schedule (chunk g+1 dispatched with the params as
    they stand BEFORE chunk g's train event), so they are numerically
    IDENTICAL — tests/test_host_replay_pipeline.py pins it.

    ``prefetch`` moves the whole sample -> gather -> stage chain onto a
    background SamplePrefetcher thread (replay/staging.py); False keeps
    the sample-in-loop path as the serial reference. Batch RNG streams
    are split from ``cfg.seed`` per batch INDEX, so the two paths draw
    bit-identical batches in uniform mode — the ISSUE 5 equivalence
    pin. PER mode is the one deliberate exception to bit-level
    reproducibility under prefetch: batch k+1's sum-tree draw races
    the batched |TD| write-backs of steps <= k on the fence lock, so
    WHICH priorities a draw sees is timing-dependent (every
    interleaving is a valid PER schedule — write-backs already lag by
    up to ``prio_writeback_batch`` steps by design; ``--no-prefetch``
    PER remains run-to-run deterministic for debugging).
    ``prefetch_depth`` bounds how many device-resident batches may
    be staged ahead. With ``prefetch`` the legacy ``double_buffer``
    knob is moot (the prefetcher owns its own stager); without it,
    ``double_buffer=False`` is the fully serial H2D reference —
    numerically identical, tests/test_ingest_fastpath.py pins it.

    ``prio_writeback_batch`` batches that many train steps' |TD|
    write-backs into one vectorized sum-tree update (PER only; 1 =
    per-step flush), mirroring the apex service's knob.

    ``checkpoint_dir`` (ISSUE 8; sharded + PER since ISSUE 12) enables
    WHOLE-STATE checkpoint/resume every ``save_every_frames`` env
    frames (0 = default cadence: ``max(cfg.eval_every_steps, one
    chunk)`` — each save copies the whole ring window, so the default
    never pays that per chunk): learner state + collect carry (orbax)
    plus the host ring window(s), pending chunk, episode stats and
    every loop cursor (versioned sidecar npz — utils/ckpt_schema.py).
    Saves land at a QUIESCED end-of-chunk boundary (every shard's
    in-flight evacuation is fenced first — idempotent, the next
    chunk's body re-fences for free), so a run killed at chunk k and
    resumed continues BIT-IDENTICALLY to an uninterrupted one — the
    resume pins in tests/test_chaos.py (dp=1 uniform) and
    tests/test_sharded_checkpoint.py (dp>1, PER) hold against mid-run
    kills. At dp > 1 the sidecar carries one ring snapshot PER SHARD
    plus the mesh width; PER mode snapshots each shard's
    RingPrioritySampler (shadow mass, exact sum-tree heap, running
    max, deferred write-backs) so a resumed run's priorities are
    exact, not max-seeded. The sidecar pins ``sidecar_version`` /
    ``chunk_iters`` / ``dp`` / ``per`` and refuses a mismatched resume
    loudly (counted in dqn_checkpoint_refused_resumes_total); a torn
    sidecar falls back to the newest intact step, deleting the
    unusable one. PER + prefetch resume keeps PER's documented
    timing-dependence (above); ``--no-prefetch`` PER resume is
    bit-identical.

    ``mesh_devices`` (ISSUE 10 tentpole) runs the runtime DATA-PARALLEL
    over a ``dp`` mesh of that many devices (0 = all): env lanes split
    into ``dp`` lane blocks, each block's transitions evacuate through
    that shard's own EvacuationWorker into its own host ring
    (replay/sharded.py ShardedHostReplay), each shard's own
    SamplePrefetcher feeds its LOCAL chip, and the train step runs
    under ``shard_map`` with params replicated, batch rows sharded over
    ``dp`` and ONE pmean gradient allreduce per update (the same specs
    the fused and apex learners use — parallel/learner.py).

    Since ISSUE 15 COLLECT is data-parallel too: each dp shard runs its
    OWN collect program over its own ``B/dp`` env-lane block, with its
    own donated ``CollectCarry`` and its own per-shard RNG stream, ON
    ITS OWN DEVICE — the transitions are born on the device whose
    evacuation worker feeds the shard's ring, so no lane-block split
    dispatch and no cross-shard scatter exist anywhere on the path.
    All shard dispatches share ONE params snapshot per chunk (a single
    replicated copy/bf16-cast program; each device materializes its
    replica locally and the shard collects consume zero-copy per-device
    views — parallel/learner.py replicated_device_views), so the bf16
    actor split still costs one cast per chunk, not one per shard. The
    collect-ahead schedule, heartbeats
    (``host_replay.collect.s{N}``), generation fences and evacuation
    workers are all per-shard. ``mesh_devices=1`` is the untouched
    pre-mesh program — bit-identical by construction (same code path);
    ``sharded_collect=True`` at ``mesh_devices=1`` forces the sharded
    machinery through a 1-shard mesh instead — the mechanism pin
    (tests/test_sharded_collect.py holds it bit-identical to the
    single-collect program).
    """
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.telemetry import collectors as tmc, get_registry
    from dist_dqn_tpu.telemetry import flight as tm_flight
    from dist_dqn_tpu.telemetry import watchdog as tm_watchdog

    # Honest-unsupported-surface gate (ADVICE r5): this loop builds the
    # FEED-FORWARD actor/learner; a recurrent config would silently
    # train the wrong program — say so.
    if cfg.network.recurrent:
        raise ValueError(
            "host-replay runs the feed-forward collect/train split; "
            "recurrent (network.lstm_size>0 or network.core.kind) "
            "configs need the sequence learner — use the fused loop (or, "
            "for the LSTM, the apex runtime)")
    if evac_slices < 1:
        raise ValueError(f"--evac-slices must be >= 1, got {evac_slices}")
    if prio_writeback_batch < 1:
        raise ValueError("prio_writeback_batch must be >= 1, got "
                         f"{prio_writeback_batch}")
    per_enabled = (cfg.replay.prioritized if prioritized is None
                   else prioritized)
    if device_sampling and not per_enabled:
        raise ValueError(
            "--device-sampling without --per has nothing to sample on "
            "device: the priority planes hold p^alpha mass (uniform "
            "draws never touch a tree). Add --per or drop "
            "--device-sampling")
    dp = len(jax.devices()) if mesh_devices == 0 else int(mesh_devices)
    if dp < 1:
        raise ValueError(f"mesh_devices must be >= 0, got {mesh_devices}")
    if dp > len(jax.devices()):
        raise ValueError(f"--mesh-devices {dp} requested but only "
                         f"{len(jax.devices())} devices are available")
    if sharded_collect is False and dp > 1:
        raise ValueError(
            "--mesh-devices > 1 always runs the sharded collect path "
            "(ISSUE 15 removed the single-device lane-scatter collect); "
            "sharded_collect=False is only meaningful at mesh width 1")
    # mesh_mode routes the WHOLE sharded machinery (per-shard collect +
    # rings + pipelines + shard_map train). dp > 1 implies it;
    # sharded_collect=True forces it through a 1-shard mesh — the
    # dp=1 mechanism-equivalence pin's knob.
    mesh_mode = dp > 1 or bool(sharded_collect)

    if env is None:
        env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    B = cfg.actor.num_envs
    obs_shape = tuple(env.observation_shape)
    stack = (cfg.replay.frame_dedup
             and getattr(env, "frame_stack", 0)) or 0
    if cfg.replay.frame_dedup and stack < 2:
        raise ValueError(
            "replay.frame_dedup=True but this env declares no rolling "
            "frame stack (envs/base.py JaxEnv.frame_stack)")
    stored_shape = obs_shape[:-1] + (1,) if stack else obs_shape

    # Floor covers the n-step window AND the dedup rebuild context —
    # a smaller ring would be permanently unsampleable (can_sample
    # needs size > n_step + stack - 1).
    num_slots = max(cfg.replay.capacity // B,
                    cfg.learner.n_step + max(stack - 1, 0) + 2)
    # Fail BEFORE the compile, naming the knobs: a chunk larger than the
    # ring would only surface in HostTimeRing.add_chunk after the first
    # device chunk (ADVICE r5 — wasted compile, error points nowhere).
    if chunk_iters > num_slots:
        raise ValueError(
            f"--chunk-iters {chunk_iters} exceeds the host ring's "
            f"{num_slots} slots (replay.capacity={cfg.replay.capacity} "
            f"/ num_envs={B}); lower --chunk-iters or raise "
            "replay.capacity (one chunk == the whole window would make "
            "the ring a FIFO of the last chunk — keep chunk_iters well "
            "below the slot count)")

    if dp > 1 and B % dp:
        raise ValueError(
            f"actor.num_envs={B} not divisible by --mesh-devices {dp}: "
            "each dp shard owns one env-lane block of the collect chunk")

    if mesh_mode:
        # Per-shard collect program (ISSUE 15): one chunk body over a
        # B/dp lane block; ONE jit, dispatched once per shard on that
        # shard's own device (jit re-specializes per device placement,
        # so the mesh pays dp compiles of the same small program).
        init_collect, collect = make_collect_chunk(
            cfg, env, net, stack, lanes=B // dp, num_shards=dp)
    else:
        init_collect, collect = make_collect_chunk(cfg, env, net, stack)
    collect_jit = jax.jit(collect, static_argnums=2, donate_argnums=0)
    init_learner, train_step = make_learner(
        net, cfg.learner, axis_name="dp" if mesh_mode else None)
    from dist_dqn_tpu.telemetry import devtime as _devtime
    mesh = mesh_devs = weights_sharding = None
    if not mesh_mode:
        train_jit = jax.jit(train_step, donate_argnums=0)
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dist_dqn_tpu.parallel import make_mesh
        from dist_dqn_tpu.parallel.learner import (make_sharded_train_step,
                                                   train_step_specs)
        mesh = make_mesh(devices=jax.devices()[:dp])
        mesh_devs = list(mesh.devices.flat)
        data_specs, metric_specs = train_step_specs("dp")
        # Donates the replicated learner state (inside the helper) — the
        # same aliasing contract the single-chip audit pins.
        train_jit = make_sharded_train_step(train_step, mesh,
                                            data_specs, metric_specs)
        weights_sharding = NamedSharding(mesh, P("dp"))
        repl_sharding = NamedSharding(mesh, P())

    # Replay-ratio engine (ISSUE 6): multiplies the grad steps each
    # train event runs — the SamplePrefetcher simply draws that many
    # batches ahead, so the ratio rides the existing sample pipeline.
    replay_ratio = loop_common.resolve_replay_ratio(cfg)
    # Wide bucketed train batches (ISSUE 6): resolved through the same
    # pow2 rule as the fused loop; default = learner.batch_size exactly.
    train_batch = loop_common.resolve_train_batch(cfg)
    if dp > 1 and train_batch % dp:
        raise ValueError(
            f"train batch {train_batch} not divisible by --mesh-devices "
            f"{dp}: each dp shard draws and uploads an equal row block "
            "(widen replay.train_batch or change the mesh size)")
    # Actor-dtype split (ISSUE 6): collect already acts on chunk-stale
    # params by construction (the collect-ahead schedule), so the bf16
    # snapshot costs ONE extra cast dispatch per chunk and no extra
    # staleness. Learner masters stay fp32 untouched.
    _cast_actor, _actor_split = loop_common.make_actor_param_cast(
        cfg.network.actor_dtype)
    cast_jit = jax.jit(_cast_actor) if _actor_split else None

    if not mesh_mode:
        def collect_params(state):
            return cast_jit(state.params) if _actor_split \
                else state.params
    else:
        from dist_dqn_tpu.parallel.learner import replicated_device_views

        # ONE collect-params snapshot per chunk, shared by every shard
        # dispatch (ISSUE 15): a single replicated mesh program — each
        # device casts/copies its own replica locally, replacing PR
        # 10's per-chunk host mirror (one D2H + re-upload) with zero
        # host traffic and exactly one cast even at dp shards. The
        # copy (never an alias of the live params) is what lets the
        # donated train step overwrite its state while the async shard
        # collects are still reading the snapshot.
        # donation: the snapshot must COPY (the learner still owns the
        # params the train step donates).
        @jax.jit
        def snapshot_collect_params(params):
            params = _cast_actor(params) if _actor_split else params
            return jax.tree.map(jnp.copy, params)

        def collect_params_views(state):
            """[shard] -> shard s's zero-copy device view of this
            chunk's one shared snapshot."""
            return replicated_device_views(
                snapshot_collect_params(state.params), mesh_devs)

    if not mesh_mode:
        ring = HostTimeRing(num_slots, B, stored_shape,
                            np.dtype(env.observation_dtype),
                            frame_stack=stack)
        store = None
    else:
        from dist_dqn_tpu.replay.sharded import ShardedHostReplay
        store = ShardedHostReplay(dp, num_slots, B // dp, stored_shape,
                                  np.dtype(env.observation_dtype),
                                  frame_stack=stack)
        ring = None

    rng = jax.random.PRNGKey(cfg.seed)
    k_carry, k_learn = jax.random.split(rng)
    carries = None
    if not mesh_mode:
        carry = init_collect(k_carry)
        obs_example = jax.tree.map(lambda x: x[0], carry.obs)
    else:
        # Per-shard collect carries, each committed to its own device
        # (ISSUE 15). Shard s acts on its own RNG stream; ONE shard
        # keeps the seed's undivided stream, which is what makes the
        # 1-shard sharded-collect path bit-identical to the
        # single-collect program (the dp=1 mechanism pin,
        # tests/test_sharded_collect.py).
        shard_keys = ([k_carry] if dp == 1
                      else list(jax.random.split(k_carry, dp)))
        carries = [jax.device_put(init_collect(shard_keys[s]),
                                  mesh_devs[s]) for s in range(dp)]
        obs_example = jax.tree.map(lambda x: x[0], carries[0].obs)
        carry = None
    state = init_learner(k_learn, obs_example)
    if mesh_mode:
        # Replicate the learner once onto the mesh; the donated sharded
        # train step then updates the replicas in place.
        state = jax.device_put(state, repl_sharding)

    # Prioritized sampling (ISSUE 5): a sum-tree shard over the ring's
    # slots, kept in lockstep with every append (main thread or
    # evacuation worker) through the ring's publish hook — under the
    # same generation fence the samplers hold. dp > 1 attaches ONE
    # sum-tree per shard ring (per-shard fences, per-shard flushes).
    per_sampler = per_samplers = None
    if per_enabled and not mesh_mode:
        if device_sampling:
            from dist_dqn_tpu.replay.host_ring import \
                RingDevicePrioritySampler
            per_sampler = RingDevicePrioritySampler(
                ring, n_step=cfg.learner.n_step,
                alpha=cfg.replay.priority_exponent,
                beta=cfg.replay.importance_exponent,
                eps=cfg.replay.priority_eps,
                device=jax.devices()[0], seed=cfg.seed)
            log_fn("# host-replay sampler: prioritized device plane "
                   f"({jax.devices()[0].platform}, "
                   f"alpha={cfg.replay.priority_exponent}, "
                   f"beta={cfg.replay.importance_exponent}, "
                   f"prio_writeback_batch={prio_writeback_batch})")
        else:
            from dist_dqn_tpu.replay.host_ring import RingPrioritySampler
            per_sampler = RingPrioritySampler(
                ring, n_step=cfg.learner.n_step,
                alpha=cfg.replay.priority_exponent,
                beta=cfg.replay.importance_exponent,
                eps=cfg.replay.priority_eps)
            log_fn("# host-replay sampler: prioritized sum-tree "
                   f"({type(per_sampler.tree).__name__}, "
                   f"alpha={cfg.replay.priority_exponent}, "
                   f"beta={cfg.replay.importance_exponent}, "
                   f"prio_writeback_batch={prio_writeback_batch})")
    elif per_enabled:
        per_samplers = store.attach_priority_samplers(
            n_step=cfg.learner.n_step,
            alpha=cfg.replay.priority_exponent,
            beta=cfg.replay.importance_exponent,
            eps=cfg.replay.priority_eps,
            device_sampling=device_sampling,
            devices=mesh_devs, seed=cfg.seed)
        kind = ("device plane" if device_sampling
                else f"sum-tree ({type(per_samplers[0].tree).__name__})")
        log_fn(f"# host-replay sampler: prioritized {kind} x {dp} "
               f"shards (alpha={cfg.replay.priority_exponent}, "
               f"beta={cfg.replay.importance_exponent}, "
               f"prio_writeback_batch={prio_writeback_batch})")
    else:
        log_fn("# host-replay sampler: uniform"
               + (f" x {dp} shards" if dp > 1 else ""))

    def _batch_rng(k: int, shard: Optional[int] = None
                   ) -> np.random.Generator:
        # Per-batch-index RNG streams split from the seed: batch k's
        # content is a pure function of (k, ring window), never of
        # which thread drew it or when — the property that makes the
        # prefetched and serial paths bit-identical. dp shards extend
        # the spawn key with the shard id: stream (k, s) is shard s's
        # slice of train batch k, identical whether a prefetcher thread
        # or the serial reference draws it.
        key = (k,) if shard is None else (k, shard)
        return np.random.default_rng(
            np.random.SeedSequence(cfg.seed, spawn_key=key))

    def sample_host(k: int):
        """Batch k's host-side sample+gather -> (host pytree, aux)."""
        rng_k = _batch_rng(k)
        if per_sampler is not None:
            hb, aux = per_sampler.sample(rng_k, train_batch,
                                         cfg.learner.gamma)
            tr = Transition(obs=hb.obs, action=hb.action,
                            reward=hb.reward, discount=hb.discount,
                            next_obs=hb.next_obs)
            # IS weights travel WITH the batch through the staging
            # pipeline, so the upload and the bookkeeping stay one unit.
            return (tr, aux.weights), aux
        hs = ring.sample(rng_k, train_batch,
                         cfg.learner.n_step, cfg.learner.gamma)
        hb = hs.batch
        tr = Transition(obs=hb.obs, action=hb.action, reward=hb.reward,
                        discount=hb.discount, next_obs=hb.next_obs)
        return tr, _UniformTag(generation=hs.generation)

    def put_batch(tree):
        return jax.tree.map(jax.device_put, tree)

    def ring_append(tree, lo, hi):
        ring.add_chunk(tree["obs"], tree["action"], tree["reward"],
                       tree["terminated"], tree["truncated"])

    # -- mesh plumbing (ISSUE 10): per-shard sample/upload/assemble ------
    shard_samples = shard_puts = assemble_tree = None
    if mesh_mode:
        lb_shard = train_batch // dp

        def make_shard_sample(s: int):
            ring_s = store.rings[s]
            sampler_s = (per_samplers[s] if per_samplers is not None
                         else None)

            def sample_shard(k: int):
                """Shard s's row block of train batch k. A 1-shard
                mesh keeps the undivided (k,) stream — the dp=1
                mechanism pin's draws are the single-ring draws."""
                rng_k = _batch_rng(k, s if dp > 1 else None)
                if sampler_s is not None:
                    hb, aux = sampler_s.sample(rng_k, lb_shard,
                                               cfg.learner.gamma)
                    tr = Transition(obs=hb.obs, action=hb.action,
                                    reward=hb.reward,
                                    discount=hb.discount,
                                    next_obs=hb.next_obs)
                    return (tr, aux.weights), aux
                hs = ring_s.sample(rng_k, lb_shard, cfg.learner.n_step,
                                   cfg.learner.gamma)
                hb = hs.batch
                tr = Transition(obs=hb.obs, action=hb.action,
                                reward=hb.reward, discount=hb.discount,
                                next_obs=hb.next_obs)
                return tr, _UniformTag(generation=hs.generation)

            return sample_shard

        def _make_shard_put(dev):
            def put(tree):
                # Fresh copy per upload: the staging slot buffers are
                # REUSED while an earlier upload may still alias their
                # pages on CPU PJRT (the ISSUE 5 alias bug) — a per-call
                # copy makes each upload's source immutable for its
                # whole lifetime, and lands the rows on shard s's OWN
                # device so assembly below is zero-copy.
                return jax.tree.map(
                    lambda x: jax.device_put(np.array(x, copy=True),
                                             dev), tree)

            return put

        shard_samples = [make_shard_sample(s) for s in range(dp)]
        shard_puts = [_make_shard_put(mesh_devs[s]) for s in range(dp)]

        def _assemble(*leaves):
            shape = ((sum(lf.shape[0] for lf in leaves),)
                     + tuple(leaves[0].shape[1:]))
            return jax.make_array_from_single_device_arrays(
                shape, weights_sharding, list(leaves))

        def assemble_tree(trees):
            """N per-shard device trees (shard s committed to mesh
            device s) -> one global row-sharded tree, no data motion."""
            return jax.tree.map(lambda *ls: _assemble(*ls), *trees)

    # Sample-side pipeline (ISSUE 5): a background prefetcher runs
    # sample -> gather -> stage ahead of the learner. Without it, the
    # legacy main-thread double-buffered stager (ISSUE 2) or the fully
    # serial put_batch path serve as the pinned references. dp > 1 runs
    # ONE prefetcher per shard, staging onto that shard's local chip.
    prefetcher = stager = prefetchers = None
    if prefetch and mesh_mode:
        from dist_dqn_tpu.replay.staging import SamplePrefetcher
        prefetchers = [
            SamplePrefetcher(shard_samples[s], depth=prefetch_depth,
                             name=f"host_replay_s{s}",
                             wait_generation=store.rings[s]
                             .wait_generation,
                             device_put=shard_puts[s])
            for s in range(dp)
        ]
    elif prefetch:
        from dist_dqn_tpu.replay.staging import SamplePrefetcher
        prefetcher = SamplePrefetcher(sample_host, depth=prefetch_depth,
                                      name="host_replay",
                                      wait_generation=ring.wait_generation)
    elif double_buffer and not mesh_mode:
        from dist_dqn_tpu.replay.staging import DoubleBufferedStager
        stager = DoubleBufferedStager(depth=2, name="host_replay")
    elif double_buffer:
        # Never degrade a requested reference path silently (the
        # train.py ignored-flag discipline): the legacy main-thread
        # stager is single-chip only — the dp serial path samples and
        # uploads per shard on the critical path instead.
        log_fn("# --no-prefetch with --mesh-devices > 1 runs the fully "
               "serial per-shard reference (sample -> per-device upload "
               "-> assemble); the double-buffered stager is single-chip "
               "only — ignored")

    # Streamed D2H + background worker (the pipeline's stages 2 and 3).
    # Mesh mode: one evacuator/worker pair PER SHARD. Since ISSUE 15
    # each shard's records are BORN on that shard's own device (its own
    # collect program), so a worker's whole stream — split dispatch,
    # async host copies, ring appends — runs against its own device and
    # its own generation fence: the lane-block scatter program PR 10
    # dispatched on device 0 no longer exists.
    evacuator = worker = workers = None
    if pipeline and mesh_mode:
        from dist_dqn_tpu.replay.staging import (EvacuationWorker,
                                                 StreamedEvacuator)

        def _make_append(s: int):
            def append(tree, lo, hi):
                store.add_chunk(s, tree["obs"], tree["action"],
                                tree["reward"], tree["terminated"],
                                tree["truncated"])

            return append

        workers = [
            EvacuationWorker(
                StreamedEvacuator(num_slices=evac_slices,
                                  name=f"host_replay_s{s}", shard=s),
                _make_append(s), name=f"host_replay_s{s}", shard=s)
            for s in range(dp)
        ]
    elif pipeline:
        from dist_dqn_tpu.replay.staging import (EvacuationWorker,
                                                 StreamedEvacuator)
        evacuator = StreamedEvacuator(num_slices=evac_slices,
                                      name="host_replay")
        worker = EvacuationWorker(evacuator, ring_append,
                                  name="host_replay")

    def submit_evac(records):
        """Queue one chunk's evacuation; returns the completion handle
        the next train event fences on. Mesh mode takes the per-shard
        records LIST — shard s's block goes straight to shard s's
        worker, no split dispatch in between."""
        if not mesh_mode:
            return worker.submit(records)
        return _MultiEvacHandle([w.submit(r)
                                 for w, r in zip(workers, records)])

    # Crash forensics (ISSUE 4): per-stage heartbeats (the evacuation
    # stage's heartbeat lives inside EvacuationWorker as
    # "evac.host_replay") + per-chunk flight events; the divergence
    # sentinel sees every train event's loss and the end-of-run param
    # checksum. All null-safe no-ops until the CLI arms them
    # (--forensics-dir / --no-flight-recorder, train.py). Startup grace
    # covers the first-chunk jit compile; a compile outliving it trips
    # with its stack on record.
    fr = tm_flight.get_flight()
    # Collect heartbeats are per-shard in mesh mode (ISSUE 15): stage
    # host_replay.collect.s{N} — a wedged shard dispatch names ITS
    # shard in the forensics bundle instead of hiding behind one
    # aggregate stage.
    if mesh_mode:
        hb_collects = [tm_watchdog.heartbeat(
            f"host_replay.collect.s{s}",
            startup_grace_s=tm_watchdog.STARTUP_GRACE_S)
            for s in range(dp)]
    else:
        hb_collects = [tm_watchdog.heartbeat(
            "host_replay.collect",
            startup_grace_s=tm_watchdog.STARTUP_GRACE_S)]
    hb_train = tm_watchdog.heartbeat(
        "host_replay.train", startup_grace_s=tm_watchdog.STARTUP_GRACE_S)

    def _beat_collect():
        for hb in hb_collects:
            hb.beat()

    reg = get_registry()
    _labels = {"loop": "host_replay"}
    g_overlap = reg.gauge(tmc.HOST_REPLAY_OVERLAP,
                          "share of the last chunk's evacuation hidden "
                          "off the training critical path", _labels)
    h_fence = reg.histogram(tmc.HOST_REPLAY_FENCE_WAIT_SECONDS,
                            "main-thread wait on the chunk publication "
                            "fence (evacuation on the critical path)",
                            _labels)
    c_d2h = reg.counter(tmc.HOST_REPLAY_D2H_BYTES,
                        "bytes evacuated device->host by the replay "
                        "pipeline", _labels)
    # Learner-utilization config surface (ISSUE 6): which replay ratio /
    # batch width / actor dtype produced this process's learner numbers.
    reg.gauge(tmc.LEARNER_REPLAY_RATIO,
              "grad sub-steps per train event", _labels).set(replay_ratio)
    reg.gauge(tmc.LEARNER_TRAIN_BATCH,
              "effective (bucketed) train batch width",
              _labels).set(train_batch)
    reg.gauge(tmc.LEARNER_ACTOR_DTYPE_INFO,
              "1 for the active actor inference dtype",
              {**_labels, "dtype": cfg.network.actor_dtype
               or "float32"}).set(1)
    g_grad_rate = reg.gauge(tmc.LEARNER_GRAD_RATE,
                            "grad steps per second (whole loop)",
                            _labels)
    # Utilization ledger (ISSUE 19): per-chunk wall decomposed into
    # device-busy (train section minus its host-blocked share) and the
    # named idle buckets — evac_fence is the publication-fence wait,
    # prefetch_wait/sample the sample-side blocking, everything else
    # (dispatch enqueues, stat fetches, logging) lands in `other`.
    _ledger = _devtime.UtilizationLedger("host_replay", reg)
    # Sharded-collect surface (ISSUE 15): the lane block each shard's
    # own collect acts over, and the per-shard dispatch enqueue wall
    # (async dispatch — growth means that shard's device queue is full,
    # the dqn_mesh_chunk_dispatch_seconds semantic). The per-shard evac
    # gauges live with the workers (replay/staging.py).
    h_collect_disp = c_shard_d2h = None
    collect_dispatch_s_total = 0.0
    if mesh_mode:
        reg.gauge(tmc.HOST_REPLAY_COLLECT_LANE_BLOCK,
                  "env lanes per shard collect dispatch",
                  _labels).set(B // dp)
        h_collect_disp = [reg.histogram(
            tmc.HOST_REPLAY_COLLECT_SECONDS,
            "per-shard collect dispatch enqueue wall",
            {**_labels, "shard": str(s)}) for s in range(dp)]
        # Serial (--no-pipeline) path's half of the per-shard byte
        # family; the pipelined half lives with each shard's
        # StreamedEvacuator (same name+labels => same series).
        c_shard_d2h = [reg.counter(
            tmc.HOST_REPLAY_SHARD_D2H_BYTES,
            "bytes evacuated from this shard's own device into its "
            "own ring (zero cross-shard lane scatter)",
            {**_labels, "shard": str(s)}) for s in range(dp)]

        def dispatch_collect(state):
            """Per-shard collect dispatches (ISSUE 15 tentpole): one
            shared params snapshot, then shard s's donated carry +
            lane block dispatched on ITS OWN device. Dispatches are
            async, so all dp devices collect concurrently; the
            records land where their evac worker and ring live, and
            no byte ever crosses a shard boundary."""
            nonlocal collect_dispatch_s_total
            views = collect_params_views(state)
            recs, sts = [], []
            stalled = False
            for s in range(dp):
                # Chaos seam (ISSUE 15): per-shard crash/stall at the
                # dispatch site. Stall recovery = the completed
                # dispatch pass below; crash recovery = the next
                # process's resume (anchored beside
                # host_replay.chunk's).
                cev = chaos.fire("host_replay.collect")
                if cev is not None:
                    if cev.fault == "crash":
                        raise chaos.ChaosInjectedError(
                            "host_replay.collect", cev.fault)
                    chaos.sleep_for(cev)
                    stalled = True
                t_d = time.perf_counter()
                carries[s], r, st = collect_jit(carries[s], views[s],
                                                chunk_iters)
                dt = time.perf_counter() - t_d
                h_collect_disp[s].observe(dt)
                collect_dispatch_s_total += dt
                hb_collects[s].beat()
                recs.append(r)
                sts.append(st)
            if stalled:
                chaos.mark_recovered("host_replay.collect")
            return recs, sts

    # Train-event cadence carries its remainder across chunks so the
    # average exactly matches the fused loop's one-event-per-train_every
    # iterations (chunk_iters need not divide train_every).
    updates_per_train = max(cfg.updates_per_train, 1) * replay_ratio
    train_debt_iters = 0
    if not mesh_mode:
        weights = jnp.ones((train_batch,), jnp.float32)
    else:
        weights = jax.device_put(np.ones((train_batch,), np.float32),
                                 weights_sharding)

    # Batched priority write-backs (ISSUE 5, PER only): each train
    # step's |TD| plane stays a device array in this pending list (its
    # dispatch is long retired by flush time, so the np.asarray there
    # costs a copy, not a sync) and lands in the sum-tree as ONE
    # vectorized set per prio_writeback_batch steps. Chronological
    # order + the per-slot generation guard preserve last-write-wins.
    # dp > 1: aux is the LIST of per-shard PerSamples and the flush is
    # per shard — the global priority rows materialize in shard-block
    # order (shard s owns rows [s*lb, (s+1)*lb) of every batch), each
    # shard's rows applied as its own vectorized set under its own fence.
    wb_pending = []
    is_w_sum, is_w_count, is_w_min = 0.0, 0, 1.0

    def _wb_add(aux, metrics):
        nonlocal is_w_sum, is_w_count, is_w_min
        if per_sampler is None and per_samplers is None:
            return
        wb_pending.append((aux, metrics["priorities"]))
        for a in (aux if mesh_mode else (aux,)):
            is_w_sum += float(a.weights.sum())
            is_w_count += int(a.weights.shape[0])
            is_w_min = min(is_w_min, float(a.weights.min()))
        if len(wb_pending) >= prio_writeback_batch:
            _wb_flush()

    def _wb_flush():
        if (per_sampler is None and per_samplers is None) \
                or not wb_pending:
            return
        pending, wb_pending[:] = wb_pending[:], []
        if not mesh_mode:
            leaf = np.concatenate([a.leaf for a, _ in pending])
            prios = np.concatenate([np.asarray(p, np.float64)
                                    for _, p in pending])
            gens = np.concatenate([a.slot_gen for a, _ in pending])
            per_sampler.update_priorities(leaf, prios, expected_gen=gens)
            return
        lb = train_batch // dp
        prios_np = [np.asarray(p, np.float64) for _, p in pending]
        for s in range(dp):
            leaf = np.concatenate([aux[s].leaf for aux, _ in pending])
            pr = np.concatenate([p[s * lb:(s + 1) * lb]
                                 for p in prios_np])
            gens = np.concatenate([aux[s].slot_gen
                                   for aux, _ in pending])
            per_samplers[s].update_priorities(leaf, pr,
                                              expected_gen=gens)

    num_chunks = max(0, math.ceil(total_env_steps / (chunk_iters * B)))
    env_steps = 0
    grad_steps = 0
    sample_k = 0          # global batch index — the RNG-stream cursor

    # -- whole-state checkpoint/resume (ISSUE 8; sharded + PER: ISSUE 12) --
    ckpt = None
    next_save = float("inf")
    start_chunk = 0
    resumed = False
    resume_stats = resume_pending = None
    h_ckpt_save = c_ckpt_bytes = None
    if checkpoint_dir:
        import os

        from dist_dqn_tpu.utils import ckpt_schema
        from dist_dqn_tpu.utils.checkpoint import (TrainCheckpointer,
                                                   record_checkpoint_kind)
        # Checkpoint telemetry (ISSUE 12 satellite): save wall/bytes/
        # shard count, successful resumes, and every refused resume by
        # reason — docs/observability.md "Checkpoint/resume metrics".
        h_ckpt_save = reg.histogram(
            tmc.CHECKPOINT_SAVE_SECONDS,
            "whole quiesced checkpoint save wall (fence + sidecar + "
            "orbax commit)", _labels)
        c_ckpt_bytes = reg.counter(
            tmc.CHECKPOINT_BYTES,
            "checkpoint bytes written (sidecar + learner/carry tree)",
            _labels)
        reg.gauge(tmc.CHECKPOINT_SHARDS_SAVED,
                  "replay shards carried by each whole-state save",
                  _labels).set(dp)

        def _count_refused(reason: str) -> None:
            reg.counter(tmc.CHECKPOINT_REFUSED,
                        "resume attempts refused at the sidecar pins",
                        {**_labels, "reason": reason}).inc()

        def _refuse_resume(reason: str, msg: str):
            _count_refused(reason)
            raise ValueError(msg)

        # Default cadence mirrors the fused loop's eval-period rhythm,
        # never finer than one chunk: each save copies the WHOLE ring
        # window (DRAM-sized at real configs) into the sidecar, so a
        # per-chunk default would put a multi-GB memcpy + npz write on
        # every chunk boundary.
        save_period = save_every_frames or max(cfg.eval_every_steps,
                                               chunk_iters * B)
        ckpt = TrainCheckpointer(checkpoint_dir,
                                 save_every_frames=save_period)
        record_checkpoint_kind(checkpoint_dir, "host_loop")
        next_save = save_period

        def _sidecar_path(step: int) -> str:
            return os.path.join(checkpoint_dir, f"host_loop_{step}.npz")

        # Mesh mode keeps the per-shard collect carries in the SIDECAR
        # (flattened leaves, schema v2) — the orbax tree carries only
        # the learner; the single-collect path keeps its one carry in
        # orbax exactly as before (ISSUE 15).
        example_tree = ({"learner": state} if mesh_mode
                        else {"learner": state, "carry": carry})
        # Newest step whose sidecar READS wins: an orbax step whose
        # sidecar is torn or missing is not a checkpoint — delete it
        # loudly and fall back to the next older one, instead of
        # failing the resume outright (the sidecar.write:torn game-day
        # invariant, scripts/chaos_run.py sharded_ckpt_crash).
        side = step = None
        fell_back = False
        import zipfile
        for cand in sorted(ckpt.all_steps(), reverse=True):
            try:
                with np.load(_sidecar_path(cand)) as f:
                    side = {k: f[k] for k in f.files}
                step = cand
                break
            # Only the CONTENT-level failures a truncated npz actually
            # produces (zip/header/pickle/key errors) plus an absent
            # file count as torn. A transient I/O OSError (stale NFS
            # handle, mount race) propagates instead — deleting a
            # committed step on a transient read error would destroy
            # valid training state.
            except (FileNotFoundError, ValueError, EOFError, KeyError,
                    zipfile.BadZipFile) as e:
                fell_back = True
                _count_refused("torn_sidecar")
                log_fn(f"# checkpoint step {cand}: sidecar unreadable "
                       f"({type(e).__name__}: {e}) — deleting the "
                       "unusable step and falling back to the previous "
                       "one")
                ckpt.delete(cand)
                try:
                    os.remove(_sidecar_path(cand))
                except OSError:
                    pass
        if side is not None:
            ver = int(side.get("sidecar_version", 0))
            if ver != ckpt_schema.SIDECAR_VERSION:
                _refuse_resume(
                    "sidecar_version",
                    f"checkpoint at {checkpoint_dir!r} carries sidecar "
                    f"schema v{ver}, this build reads "
                    f"v{ckpt_schema.SIDECAR_VERSION} — resume with a "
                    "matching build (utils/ckpt_schema.py documents the "
                    "history), or start a fresh --checkpoint-dir")
            if int(side["chunk_iters"]) != chunk_iters:
                # next_chunk/env_steps cursors are in chunk units; a
                # different --chunk-iters would silently misinterpret
                # them and break the bit-identical resume contract.
                _refuse_resume(
                    "chunk_iters",
                    f"checkpoint at {checkpoint_dir!r} was written with "
                    f"--chunk-iters {int(side['chunk_iters'])}, this "
                    f"run uses {chunk_iters} — resume with the same "
                    "loop shape (the ring/env config is already "
                    "validated by the snapshot shapes)")
            if int(side["dp"]) != dp:
                # Lane blocks are positional (shard s owns env lanes
                # [s*L, (s+1)*L)), so a changed mesh width cannot
                # restore the striped window bit-identically. The apex
                # ITEM store migrates across shard counts; this lane
                # store refuses.
                _refuse_resume(
                    "dp",
                    f"checkpoint at {checkpoint_dir!r} was written at "
                    f"--mesh-devices {int(side['dp'])}, this run uses "
                    f"{dp} — resume with the same mesh width "
                    "(re-sharding a lane-striped host-replay window is "
                    "not supported; docs/fault_tolerance.md 'resuming "
                    "a sharded run')")
            if bool(side["sharded_collect"]) != mesh_mode:
                # The collect carries live in different places per mode
                # (per-shard sidecar leaves vs the orbax tree), so a
                # mode flip cannot restore either representation.
                _refuse_resume(
                    "sharded_collect",
                    f"checkpoint at {checkpoint_dir!r} was written "
                    f"with sharded_collect="
                    f"{bool(side['sharded_collect'])}, this run "
                    f"resolves sharded_collect={mesh_mode} — resume "
                    "with the same collect mode (the collect carries "
                    "are stored per mode)")
            if per_enabled and \
                    int(side["prio_writeback_batch"]) \
                    != prio_writeback_batch:
                # The restored pending write-back entries flush when
                # the list crosses prio_writeback_batch: a different
                # cadence would apply |TD| updates on a different
                # schedule than the killed run — silent divergence
                # from the bit-identical contract.
                _refuse_resume(
                    "prio_writeback_batch",
                    f"checkpoint at {checkpoint_dir!r} was written "
                    f"with prio_writeback_batch="
                    f"{int(side['prio_writeback_batch'])}, this run "
                    f"uses {prio_writeback_batch} — resume with the "
                    "same PER write-back cadence")
            if int(side.get("population", 1)) != 1:
                # v4 (ISSUE 20): this loop has no stacked-member plane —
                # a population sidecar's state shapes carry a leading
                # [M] axis its solo restore templates cannot absorb.
                _refuse_resume(
                    "population",
                    f"checkpoint at {checkpoint_dir!r} was written with "
                    f"population={int(side['population'])} stacked "
                    "members, but --runtime host-replay trains a single "
                    "policy — the member axis is checkpoint structure. "
                    "Resume it under the fused --population runtime, or "
                    "start a fresh --checkpoint-dir")
            if bool(side["per"]) != per_enabled:
                _refuse_resume(
                    "per",
                    f"checkpoint at {checkpoint_dir!r} was written with "
                    f"prioritized={bool(side['per'])}, this run "
                    f"configures prioritized={per_enabled} — a uniform "
                    "snapshot cannot honestly seed a sum-tree (and vice "
                    "versa); resume with the same sampler, or start a "
                    "fresh --checkpoint-dir")
            if per_enabled and \
                    int(side["per_sampler_kind"]) != int(device_sampling):
                # The mass shadow would restore either way, but draw
                # timing and fp reduction order differ between the host
                # tree and the device plane — a silent backend swap
                # breaks the bit-identical-resume contract (ISSUE 18).
                _kinds = {0: "host sum-tree", 1: "device plane"}
                _refuse_resume(
                    "sampler_kind",
                    f"checkpoint at {checkpoint_dir!r} was written with "
                    f"the {_kinds[int(side['per_sampler_kind'])]} PER "
                    f"backend, this run configures the "
                    f"{_kinds[int(device_sampling)]} — resume with the "
                    "same --device-sampling setting, or start a fresh "
                    "--checkpoint-dir")
            _, tree = ckpt.restore_latest(example_tree, step=step)
            state = tree["learner"]
            if not mesh_mode:
                carry = tree["carry"]
            else:
                # Per-shard collect carries from the sidecar (ISSUE
                # 15): flattened leaves keyed carry{s}_leaf{i},
                # re-built against the freshly-initialized carries'
                # treedef (same cfg/env => same structure), committed
                # back to each shard's own device.
                cdef = jax.tree.structure(carries[0])
                n_leaves = len(jax.tree.leaves(carries[0]))
                carries = [
                    jax.device_put(
                        jax.tree.unflatten(
                            cdef, [side[f"carry{s}_leaf{i}"]
                                   for i in range(n_leaves)]),
                        mesh_devs[s])
                    for s in range(dp)]
            ring_side = {k[len("ring_"):]: v for k, v in side.items()
                         if k.startswith("ring_")}
            if not mesh_mode:
                ring.load_state_dict(ring_side)
                if per_sampler is not None:
                    # Exact priority state (ISSUE 12): shadow mass,
                    # running max AND the sum-tree heap (incl. native
                    # delta drift) — resumed draws see the killed run's
                    # priorities, not max-priority amnesia.
                    per_sampler.load_state_dict(
                        {k[len("per_"):]: v for k, v in side.items()
                         if k.startswith("per_")})
            else:
                # N per-shard rings (+ per-shard PER sampler state when
                # attached), shard count pinned inside.
                store.load_state_dict(ring_side)
            env_steps = int(side["env_steps"])
            grad_steps = int(side["grad_steps"])
            # Resume lineage baseline (ISSUE 16): post-restore appends
            # stamp the resumed version, not 0.
            if mesh_mode:
                store.current_params_version = grad_steps
            else:
                ring.current_params_version = grad_steps
            sample_k = int(side["sample_k"])
            if prefetcher is not None:
                # Per-index batch RNG: the prefetcher must continue the
                # killed run's index sequence, not restart at 0.
                prefetcher.seek(sample_k)
            if prefetchers is not None:
                # dp > 1: every shard's prefetcher shares the one batch
                # cursor (stream (k, s) is shard s's slice of batch k).
                for p in prefetchers:
                    p.seek(sample_k)
            train_debt_iters = int(side["train_debt_iters"])
            start_chunk = int(side["next_chunk"])
            next_save = env_steps + save_period
            resumed = True
            # Deferred-but-unflushed PER write-backs ride the sidecar
            # verbatim: flushing early at save time would apply |TD|
            # updates sooner than the uninterrupted run does, breaking
            # the bit-identical pin — so the pending list is restored
            # as-is and flushes on the killed run's schedule.
            from dist_dqn_tpu.replay.host_ring import PerSample
            for j in range(int(side.get("wb_count", 0))):
                prios_j = np.asarray(side["wb_prios"][j], np.float64)

                def _wb_aux(s: int) -> "PerSample":
                    leaf = np.asarray(side[f"wb{s}_leaf"][j], np.int64)
                    return PerSample(
                        leaf=leaf,
                        t_idx=np.zeros_like(leaf, np.int32),
                        b_idx=np.zeros_like(leaf, np.int32),
                        slot_gen=np.asarray(side[f"wb{s}_slot_gen"][j],
                                            np.int64),
                        weights=np.zeros(leaf.shape[0], np.float32),
                        generation=0)

                aux = (_wb_aux(0) if not mesh_mode
                       else [_wb_aux(s) for s in range(dp)])
                wb_pending.append((aux, prios_j))
            if bool(side["has_stats"]):
                # Episode-stat scalars of the already-dispatched next
                # chunk: host floats; jax.device_get at the loop's
                # fetch point is a no-op on them. Mesh mode stores one
                # (cr, cc) pair per shard as [dp] arrays.
                if not mesh_mode:
                    resume_stats = (np.float32(side["stats_cr"]),
                                    np.float32(side["stats_cc"]))
                else:
                    resume_stats = [
                        (np.float32(side["stats_cr"][s]),
                         np.float32(side["stats_cc"][s]))
                        for s in range(dp)]
            if bool(side["has_pending"]):
                # Serial path: the next chunk's collected records were
                # materialized into the checkpoint; the body's
                # monolithic fetch reads host arrays identically. Mesh
                # mode stores one record dict per shard
                # (pending{s}_{field}).
                if not mesh_mode:
                    resume_pending = {
                        k[len("pending_"):]: v for k, v in side.items()
                        if k.startswith("pending_")}
                else:
                    import re as _re
                    _pat = _re.compile(r"^pending(\d+)_([a-z_]+)$")
                    resume_pending = [dict() for _ in range(dp)]
                    for k, v in side.items():
                        m = _pat.match(k)
                        if m is not None:
                            resume_pending[int(m.group(1))][
                                m.group(2)] = v
            log_fn(json.dumps({"resumed_at_frames": env_steps,
                               "resumed_at_chunk": start_chunk,
                               "resumed_dp": dp,
                               "resumed_per": per_enabled}))
            reg.counter(tmc.CHECKPOINT_RESUMES,
                        "successful whole-state resumes",
                        _labels).inc()
            # Resuming from the checkpoint IS the recovery proof for an
            # injected mid-run crash (in-process chaos replay); a
            # resume that fell back past an injected torn sidecar
            # proves that seam recovered too.
            chaos.mark_recovered("host_replay.chunk")
            # ...and for a crash injected at a shard's collect dispatch
            # (ISSUE 15): the resumed process restores that shard's
            # carry from the sidecar, which is the surviving path.
            chaos.mark_recovered("host_replay.collect")
            if fell_back:
                chaos.mark_recovered("sidecar.write")

    d2h_bytes_total = 0
    d2h_bytes_by_shard = [0] * dp if mesh_mode else None
    fence_wait_total = 0.0
    sample_s_total = 0.0
    prefetch_wait_s_total = 0.0
    overlap_fracs = []
    history = []
    metrics = None
    t_start = time.perf_counter()
    records = stats = handle = None
    # The restored step already exists on disk: the save guard below
    # must treat it as saved, or resuming a COMPLETED run would re-save
    # its final step (orbax raises StepAlreadyExists) instead of
    # passing straight to the summary.
    last_saved = env_steps if resumed else -1

    def _save_checkpoint(g: int) -> None:
        """Quiesced whole-state save at the end of chunk ``g``'s body.
        Every shard's in-flight evacuation is fenced first (idempotent —
        the next body re-waits for free) so each ring snapshot is the
        complete window; the serial path's un-appended next-chunk
        records, the dispatched episode-stat scalars AND any deferred
        PER write-backs are materialized INTO the checkpoint instead of
        being perturbed — reads only, so the continuing run stays
        bit-identical to an unsaved one."""
        nonlocal last_saved
        if env_steps <= last_saved:
            return
        t_save = time.perf_counter()
        if pipeline and handle is not None:
            handle.wait()
        if not mesh_mode:
            side = {f"ring_{k}": v for k, v in ring.state_dict().items()}
            if per_sampler is not None:
                side.update({f"per_{k}": v for k, v in
                             per_sampler.state_dict().items()})
        else:
            # ShardedHostReplay snapshot: per-shard rings + (when PER)
            # per-shard sampler state, each under its own fence.
            side = {f"ring_{k}": v for k, v in store.state_dict().items()}
            # Per-shard collect carries (ISSUE 15, schema v2): shard
            # s's donated carry, flattened to leaves — the orbax tree
            # carries only the learner in mesh mode.
            for s in range(dp):
                for i, leaf in enumerate(
                        jax.tree.leaves(jax.device_get(carries[s]))):
                    side[f"carry{s}_leaf{i}"] = np.asarray(leaf)
        side.update(
            sidecar_version=np.int64(ckpt_schema.SIDECAR_VERSION),
            env_steps=np.int64(env_steps),
            grad_steps=np.int64(grad_steps),
            sample_k=np.int64(sample_k),
            train_debt_iters=np.int64(train_debt_iters),
            next_chunk=np.int64(g + 1),
            chunk_iters=np.int64(chunk_iters),
            dp=np.int64(dp),
            per=np.bool_(per_enabled),
            per_sampler_kind=np.int64(int(device_sampling)),
            # v4 (ISSUE 20): member-axis width pin — this loop always
            # trains ONE policy; the restore path refuses any other M.
            population=np.int64(1),
            sharded_collect=np.bool_(mesh_mode),
            prio_writeback_batch=np.int64(prio_writeback_batch),
            wb_count=np.int64(len(wb_pending)),
            has_stats=np.bool_(stats is not None),
            has_pending=np.bool_(records is not None))
        if wb_pending:
            # Deferred |TD| write-backs ride along verbatim (see the
            # restore path's comment: an early flush would break the
            # bit-identical pin).
            if not mesh_mode:
                side["wb0_leaf"] = np.stack(
                    [a.leaf for a, _ in wb_pending])
                side["wb0_slot_gen"] = np.stack(
                    [a.slot_gen for a, _ in wb_pending])
            else:
                for s in range(dp):
                    side[f"wb{s}_leaf"] = np.stack(
                        [aux[s].leaf for aux, _ in wb_pending])
                    side[f"wb{s}_slot_gen"] = np.stack(
                        [aux[s].slot_gen for aux, _ in wb_pending])
            side["wb_prios"] = np.stack(
                [np.asarray(p, np.float64) for _, p in wb_pending])
        if stats is not None:
            if not mesh_mode:
                s_cr, s_cc = jax.device_get(stats)
                side.update(stats_cr=np.float32(s_cr),
                            stats_cc=np.float32(s_cc))
            else:
                got = jax.device_get(stats)
                side.update(
                    stats_cr=np.asarray([g_[0] for g_ in got],
                                        np.float32),
                    stats_cc=np.asarray([g_[1] for g_ in got],
                                        np.float32))
        if records is not None:
            if not mesh_mode:
                side.update({f"pending_{k}":
                             np.asarray(jax.device_get(v))
                             for k, v in records.items()})
            else:
                for s, rec in enumerate(records):
                    side.update({f"pending{s}_{k}":
                                 np.asarray(jax.device_get(v))
                                 for k, v in rec.items()})
        # Schema gate (ISSUE 12 satellite): a code path emitting a
        # field utils/ckpt_schema.py does not name fails HERE, at save
        # time, instead of becoming a silently-unread key at restore.
        ckpt_schema.validate_sidecar(side.keys())
        # Sidecar BEFORE the orbax commit (atomic tmp+rename): any
        # committed step implies its sidecar exists, so a crash between
        # the two leaves the previous step as the resume point.
        path = _sidecar_path(env_steps)
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            np.savez(fh, **side)
        # Chaos seam (ISSUE 12): "torn" lands a truncated sidecar at
        # the FINAL path (crash mid-write on a filesystem without
        # atomic-rename semantics) while the orbax commit proceeds —
        # the resume path must detect the unreadable sidecar, delete
        # the unusable step and fall back to the previous one.
        cev = chaos.fire("sidecar.write")
        if cev is not None and cev.fault == "torn":
            with open(tmp, "rb") as fh:
                blob = fh.read()
            with open(path, "wb") as fh:
                fh.write(blob[: max(16, len(blob) // 7)])
            os.remove(tmp)
        else:
            os.replace(tmp, path)
        orbax_tree = ({"learner": state} if mesh_mode
                      else {"learner": state, "carry": carry})
        ckpt.save(env_steps, orbax_tree)
        ckpt.wait()
        last_saved = env_steps
        # Prune sidecars in lockstep with orbax's max_to_keep: each one
        # holds a full ring-window copy, so orphans from pruned steps
        # would leak window-sized files every save period.
        import glob as _glob
        keep = set(ckpt.all_steps())
        for old in _glob.glob(os.path.join(checkpoint_dir,
                                           "host_loop_*.npz")):
            try:
                step = int(os.path.basename(old)[len("host_loop_"):-4])
            except ValueError:
                continue
            if step not in keep:
                os.remove(old)
        wall = time.perf_counter() - t_save
        h_ckpt_save.observe(wall)
        c_ckpt_bytes.inc(
            os.path.getsize(path)
            + int(sum(getattr(leaf, "nbytes", 0) for leaf in
                      jax.tree.leaves(orbax_tree))))
        fr.record("checkpoint", "host_replay.save", frames=env_steps,
                  wall_s=round(wall, 3), shards=dp)
        log_fn(json.dumps({"host_replay_checkpoint": env_steps,
                           "save_s": round(wall, 3),
                           "shards_saved": dp}))

    if ckpt is not None:
        # Emergency checkpoint on watchdog abort (ISSUE 8; all shards
        # since ISSUE 12): the quiesced whole-state save needs
        # main-thread fencing, so the abort path saves a side snapshot
        # instead — the learner tree PLUS every replay shard's ring
        # (and PER sampler) state, each taken under its own generation
        # fence, so the data is per-shard consistent even while the
        # main thread is wedged. Honest limits: the loop cursors are
        # NOT quiesced, so this is a redeploy/forensics artifact, not
        # a bit-identical resume point (docs/fault_tolerance.md) — the
        # emergency sidecar deliberately does NOT carry the resume
        # schema's cursor fields.
        from dist_dqn_tpu.utils.checkpoint import save_pytree

        _emerg_state = {"state": state}

        def _emergency_save():
            import os as _os
            save_pytree(_os.path.join(checkpoint_dir, "emergency_learner"),
                        {"learner": _emerg_state["state"]})
            if not mesh_mode:
                # One fence hold for ring + sampler (RLock): appends
                # may still be in flight on the abort path, and a
                # publish between the two snapshots would tear sampler
                # mass against ring state.
                with ring._fence:
                    eside = {f"ring_{k}": v
                             for k, v in ring.state_dict().items()}
                    if per_sampler is not None:
                        eside.update({f"per_{k}": v for k, v in
                                      per_sampler.state_dict().items()})
            else:
                eside = {f"ring_{k}": v
                         for k, v in store.state_dict().items()}
            eside.update(dp=np.int64(dp), per=np.bool_(per_enabled),
                         env_steps=np.int64(env_steps))
            from dist_dqn_tpu.utils.checkpoint import atomic_savez
            atomic_savez(_os.path.join(checkpoint_dir,
                                       "emergency_sidecar.npz"),
                         **eside)

        tm_watchdog.register_emergency_hook("host_replay.checkpoint",
                                            _emergency_save)

    def _dispatch_chunk():
        """One chunk's collect: the single program (dp=1) or the
        per-shard dispatch pass (mesh mode). Returns (records, stats)
        — per-shard LISTS in mesh mode."""
        nonlocal carry
        if mesh_mode:
            return dispatch_collect(state)
        carry, r, st = collect_jit(carry, collect_params(state),
                                   chunk_iters)
        return r, st

    # --profile-dir (ISSUE 19 satellite): same contract as the fused
    # loop — trace the first post-warmup chunk (chunk 1; a run that is
    # all one chunk traces that one) into the given directory.
    _tracer = _devtime.maybe_trace_first_chunk(profile_dir)
    _profile_chunk = (min(start_chunk + 1, num_chunks - 1)
                      if profile_dir else -1)
    try:
        if num_chunks and not resumed:
            # Chunk 0: prologue dispatch + evacuation submit.
            records, stats = _dispatch_chunk()
            if pipeline:
                handle = submit_evac(records)
                records = None
        elif resumed and start_chunk < num_chunks \
                and resume_stats is None and resume_pending is None:
            # EXTENSION resume (found by driving the CLI, ISSUE 12): the
            # checkpoint is a FINAL save — no chunk was in flight — and
            # this run's --total-env-steps reaches past it. Run the
            # prologue dispatch against the restored carry/ring, exactly
            # like a fresh start. Honest contract: extension is a
            # supported CONTINUATION, not the bit-identical-resume pin —
            # an uninterrupted longer run would have dispatched this
            # chunk one train event earlier (the collect-ahead
            # schedule), so params at the boundary differ by one
            # staleness event.
            records, stats = _dispatch_chunk()
            if pipeline:
                handle = submit_evac(records)
                records = None
        elif resumed:
            # Re-establish the loop invariants at the top of body
            # ``start_chunk`` exactly as the killed run held them: the
            # fenced chunk is already inside the checkpointed ring
            # (pipeline) or rides along as pending records (serial).
            stats = resume_stats
            if pipeline:
                handle = _ResumedEvacHandle()
            else:
                records = resume_pending
        for g in range(start_chunk, num_chunks):
            if g == _profile_chunk:
                _tracer.start()
            t0 = time.perf_counter()
            next_records = next_stats = None
            if pipeline:
                # Stage 1 — look-ahead dispatch: chunk g+1's device
                # compute starts now and overlaps chunk g's evacuation
                # tail + training. Its collect uses the params BEFORE
                # chunk g's train event (one event stale — the price of
                # the overlap; the serial path below dispatches at the
                # same point in the data-dependency order, so the two
                # paths stay bit-identical).
                if g + 1 < num_chunks:
                    next_records, next_stats = _dispatch_chunk()
                _beat_collect()
                t_dispatch = time.perf_counter()
                # Stage 2 — fence on chunk g's evacuation (submitted
                # last iteration / at the prologue): its last slice
                # must be published before the train event may sample.
                # The wait is the portion of the evacuation left on
                # the critical path; in steady state the worker
                # finished it while the device ran chunk g-1's trains
                # tail and chunk g's collect.
                handle.wait()
                t_fence = time.perf_counter()
                fence_wait_s = t_fence - t_dispatch
                evac_s = handle.stats["evac_s"]
                d2h_bytes = handle.stats["bytes"]
                if mesh_mode:
                    # Per-shard conservation accounting (ISSUE 15):
                    # what each shard's own device evacuated this chunk
                    # (the worker already counted it into the {shard}
                    # telemetry families).
                    for s, st in enumerate(handle.per_shard):
                        d2h_bytes_by_shard[s] += st["bytes"]
                overlap = max(0.0, min(1.0, 1.0 - fence_wait_s
                                       / max(evac_s, 1e-9)))
                t_evac_parts = None
            else:
                # Serial reference: one monolithic blocking fetch (per
                # shard in mesh mode — each shard's records come off
                # its OWN device, no lane re-split), one monolithic
                # append, device idle throughout (the round-5 measured
                # shape), THEN the look-ahead dispatch — same pre-train
                # params as the pipelined path, with zero evacuation
                # overlap.
                if not mesh_mode:
                    host = {k: np.asarray(jax.device_get(v))
                            for k, v in records.items()}
                    t_mono_fetch = time.perf_counter()
                    ring.add_chunk(host["obs"], host["action"],
                                   host["reward"], host["terminated"],
                                   host["truncated"])
                    t_fence = time.perf_counter()
                    d2h_bytes = int(sum(v.nbytes
                                        for v in host.values()))
                    del host
                else:
                    hosts = [{k: np.asarray(jax.device_get(v))
                              for k, v in rec.items()}
                             for rec in records]
                    t_mono_fetch = time.perf_counter()
                    for s, host in enumerate(hosts):
                        store.add_chunk(s, host["obs"], host["action"],
                                        host["reward"],
                                        host["terminated"],
                                        host["truncated"])
                        b_s = int(sum(v.nbytes for v in host.values()))
                        d2h_bytes_by_shard[s] += b_s
                        c_shard_d2h[s].inc(b_s)
                    t_fence = time.perf_counter()
                    d2h_bytes = int(sum(
                        v.nbytes for host in hosts
                        for v in host.values()))
                    del hosts
                fence_wait_s = evac_s = t_fence - t0
                c_d2h.inc(d2h_bytes)
                overlap = 0.0
                t_evac_parts = (t_mono_fetch - t0, t_fence - t_mono_fetch)
                if g + 1 < num_chunks:
                    next_records, next_stats = _dispatch_chunk()
                _beat_collect()
            records = next_records
            fr.record("fence", "host_replay.chunk", chunk=g,
                      fence_wait_s=round(fence_wait_s, 4),
                      evac_s=round(evac_s, 4), d2h_bytes=d2h_bytes)
            env_steps += chunk_iters * B
            d2h_bytes_total += d2h_bytes
            fence_wait_total += fence_wait_s
            overlap_fracs.append(overlap)
            # Both paths record the overlap instruments (a serial run's
            # flat-zero overlap series is the dashboard A/B baseline),
            # and the row's ring occupancy is snapshotted HERE — after
            # the fence, before chunk g+1's background appends can
            # advance it — so pipelined and serial rows report the same
            # deterministic post-chunk-g state.
            g_overlap.set(overlap)
            h_fence.observe(fence_wait_s)
            ring_transitions = (store.size if mesh_mode
                                else ring.size) * B

            # Stage 3 — train event for chunk g (samples the window
            # INCLUDING chunk g, exactly as the serial path does).
            did = 0
            ev_sample_s = ev_wait_s = 0.0
            ev_depth_sum = ev_stale = 0
            sampleable = (store.can_sample(cfg.learner.n_step)
                          if mesh_mode
                          else ring.can_sample(cfg.learner.n_step))
            if sampleable and ring_transitions >= cfg.replay.min_fill:
                train_debt_iters += chunk_iters
                events = train_debt_iters // max(cfg.train_every, 1)
                train_debt_iters -= events * max(cfg.train_every, 1)
                grads_this_chunk = events * updates_per_train
                if grads_this_chunk and mesh_mode:
                    # Data-parallel train event (ISSUE 10): each shard's
                    # pipeline delivers its OWN row block onto its local
                    # chip; assembly stitches the blocks into one global
                    # row-sharded batch and the shard_map'd step runs
                    # one pmean gradient allreduce per update. Per-shard
                    # fences: every shard's ring published chunk g
                    # (fenced above), so each shard's generation is
                    # stable across the event.
                    fence_gens = store.generation
                    lb = train_batch // dp
                    if prefetchers is not None:
                        s0 = [(p.sample_s_total, p.wait_s_total,
                               p.stale_total) for p in prefetchers]
                        for s, p in enumerate(prefetchers):
                            p.request(grads_this_chunk, fence_gens[s])
                        for i in range(grads_this_chunk):
                            parts, w_parts, auxes = [], [], []
                            for s, p in enumerate(prefetchers):
                                dev, aux = p.pop(fence_gens[s])
                                ev_depth_sum += len(p)
                                if per_samplers is not None:
                                    tr, w_s = dev
                                    parts.append(tr)
                                    w_parts.append(w_s)
                                else:
                                    parts.append(dev)
                                auxes.append(aux)
                            batch = assemble_tree(parts)
                            w = (assemble_tree(w_parts)
                                 if per_samplers is not None else weights)
                            state, metrics = train_jit(state, batch, w)
                            _wb_add(auxes, metrics)
                        for s, p in enumerate(prefetchers):
                            ev_sample_s += p.sample_s_total - s0[s][0]
                            ev_wait_s += p.wait_s_total - s0[s][1]
                            ev_stale += p.stale_total - s0[s][2]
                        sample_k = prefetchers[0].next_k
                    else:
                        # Serial dp reference (--no-prefetch): identical
                        # per-(k, shard) RNG streams, so it draws the
                        # SAME batches the prefetched path does.
                        for i in range(grads_this_chunk):
                            t_s = time.perf_counter()
                            parts, w_parts, auxes = [], [], []
                            for s in range(dp):
                                host, aux = shard_samples[s](sample_k)
                                if per_samplers is not None:
                                    tr, w_s = host
                                    parts.append(shard_puts[s](tr))
                                    w_parts.append(shard_puts[s](w_s))
                                else:
                                    parts.append(shard_puts[s](host))
                                auxes.append(aux)
                            ev_sample_s += time.perf_counter() - t_s
                            sample_k += 1
                            batch = assemble_tree(parts)
                            w = (assemble_tree(w_parts)
                                 if per_samplers is not None else weights)
                            state, metrics = train_jit(state, batch, w)
                            _wb_add(auxes, metrics)
                    did = grads_this_chunk
                    grad_steps += did
                    # Lineage baseline (ISSUE 16): appends from here on
                    # are born at this params version, and staleness at
                    # sample time is measured against it.
                    store.current_params_version = grad_steps
                    sample_s_total += ev_sample_s
                    prefetch_wait_s_total += ev_wait_s
                elif grads_this_chunk:
                    # The window every one of this event's batches must
                    # see: chunk g is published (fenced above) and
                    # chunk g+1's appends are gated until the event's
                    # last sample is drawn, so the generation is stable
                    # across the event.
                    fence_gen = ring.generation

                    def _unpack(dev):
                        # PER stages (batch, IS weights) as one tree;
                        # uniform reuses the constant device ones.
                        return dev if per_sampler is not None \
                            else (dev, weights)

                    if prefetcher is not None:
                        # Sample-ahead: the prefetcher thread samples/
                        # gathers/uploads batch i+1.. while batch i
                        # trains; pops verify the generation tag.
                        s0 = (prefetcher.sample_s_total,
                              prefetcher.wait_s_total,
                              prefetcher.stale_total)
                        prefetcher.request(grads_this_chunk, fence_gen)
                        for i in range(grads_this_chunk):
                            dev, aux = prefetcher.pop(fence_gen)
                            ev_depth_sum += len(prefetcher)
                            batch, w = _unpack(dev)
                            state, metrics = train_jit(state, batch, w)
                            _wb_add(aux, metrics)
                        ev_sample_s = prefetcher.sample_s_total - s0[0]
                        ev_wait_s = prefetcher.wait_s_total - s0[1]
                        ev_stale = prefetcher.stale_total - s0[2]
                        sample_k = prefetcher.next_k
                    elif stager is not None:
                        # Serial reference with main-thread double
                        # buffering (--no-prefetch): batch i+1's gather
                        # + upload still overlap step i's device time,
                        # but the sample itself stays on this thread.
                        t_s = time.perf_counter()
                        host, aux = sample_host(sample_k)
                        stager.stage(host, aux=aux)
                        ev_sample_s += time.perf_counter() - t_s
                        sample_k += 1
                        for i in range(grads_this_chunk):
                            dev, aux = stager.pop()
                            batch, w = _unpack(dev)
                            state, metrics = train_jit(state, batch, w)
                            _wb_add(aux, metrics)
                            if i + 1 < grads_this_chunk:
                                t_s = time.perf_counter()
                                host, nxt = sample_host(sample_k)
                                stager.stage(host, aux=nxt)
                                ev_sample_s += time.perf_counter() - t_s
                                sample_k += 1
                    else:
                        # Fully serial H2D reference
                        # (--no-prefetch --no-double-buffer):
                        # sample -> upload -> train, one at a time.
                        t_s = time.perf_counter()
                        host, aux = sample_host(sample_k)
                        dev = put_batch(host)
                        ev_sample_s += time.perf_counter() - t_s
                        sample_k += 1
                        for i in range(grads_this_chunk):
                            batch, w = _unpack(dev)
                            state, metrics = train_jit(state, batch, w)
                            _wb_add(aux, metrics)
                            if i + 1 < grads_this_chunk:
                                t_s = time.perf_counter()
                                host, aux = sample_host(sample_k)
                                dev = put_batch(host)
                                ev_sample_s += \
                                    time.perf_counter() - t_s
                                sample_k += 1
                    did = grads_this_chunk
                    grad_steps += did
                    # Lineage baseline (ISSUE 16): see the mesh branch.
                    ring.current_params_version = grad_steps
                    sample_s_total += ev_sample_s
                    prefetch_wait_s_total += ev_wait_s
            # Chunk g+1's evacuation: every sample for chunk g's event
            # has been drawn above, so chunk g+1's slices may publish
            # from here on without changing what those samples saw —
            # submit now, and its transfers overlap chunk g's train
            # execution and chunk g+2's collect.
            if pipeline and records is not None:
                handle = submit_evac(records)
                records = None
            if did:
                jax.block_until_ready(state.params)
            if ckpt is not None:
                _emerg_state["state"] = state
            hb_train.beat()
            t_train = time.perf_counter()
            fr.record("train", "host_replay.train_event", chunk=g,
                      grad_steps=did)

            # Fused episode-stat fetch (ISSUE 3 satellite): ONE
            # device_get for both scalars, and its wall accounted in
            # the row instead of hiding between t_train and the log.
            # Mesh mode fetches every shard's pair in the one call and
            # sums — the global stats are the sum over lane blocks.
            if not mesh_mode:
                cr, cc = jax.device_get(stats)
            else:
                got = jax.device_get(stats)
                cr = sum(float(g_[0]) for g_ in got)
                cc = sum(float(g_[1]) for g_ in got)
            stats = next_stats
            t_stats = time.perf_counter()
            ep = float(cr) / max(float(cc), 1.0)

            # The chunk's wall by cause, all from timestamps the loop
            # already took. The train section (t_fence -> t_train) ends
            # at a real fence (block_until_ready above), so minus its
            # host-blocked share it is the ledger's `busy`; `sample`
            # only blocks when no prefetcher runs (with one, the
            # blocking share is prefetch_wait).
            _prefetching = (prefetcher is not None
                            or prefetchers is not None)
            sample_blocked = 0.0 if _prefetching else ev_sample_s
            train_busy = max((t_train - t_fence) - sample_blocked
                             - ev_wait_s, 0.0)
            chip = _ledger.observe_chunk(
                t_stats - t0, train_busy, sample=sample_blocked,
                evac_fence=fence_wait_s, prefetch_wait=ev_wait_s)
            _devtime.sweep_device_memory(reg)

            row = {
                "env_frames": env_steps, "grad_steps": grad_steps,
                "episode_return": round(ep, 3),
                "env_steps_per_sec": round(
                    chunk_iters * B / max(t_train - t0, 1e-9), 1),
                # Whole-loop rate (ISSUE 3 satellite): includes stat
                # fetches and logging, so it reconciles with the
                # end-of-run summary rate; the per-chunk rate above
                # excludes them by construction.
                "env_steps_per_sec_loop": round(
                    env_steps / max(t_stats - t_start, 1e-9), 1),
                "chunk_train_s": round(t_train - t_fence, 4),
                "chunk_stats_fetch_s": round(t_stats - t_train, 4),
                "evac_s": round(evac_s, 4),
                "evac_fence_wait_s": round(fence_wait_s, 4),
                "evac_overlap_frac": round(overlap, 4),
                # Upper bound on device idle attributable to
                # evacuation: the fence wait (pipelined — the device
                # may still be running collect g+1 under it) or the
                # whole evacuation (serial — nothing is dispatched).
                "device_idle_est_s": round(fence_wait_s, 4),
                "d2h_bytes": d2h_bytes,
                "ring_transitions": ring_transitions,
                "ring_gb": round((store.nbytes if mesh_mode
                                  else ring.nbytes) / 1e9, 3),
                # Sample-side overlap accounting (ISSUE 5): sample_s is
                # the host sampling wall this chunk (on the critical
                # path when prefetch is off, overlapped when on);
                # prefetch_wait_s is the share still blocking the main
                # thread; prefetch_depth the mean batches staged ahead
                # at pop time; stale_batches the generation-fence drops.
                "sample_s": round(ev_sample_s, 4),
                # Ledger view of this chunk (ISSUE 19): measured device-
                # busy and the derived unattributed host residual; the
                # cumulative per-cause series is
                # dqn_chip_idle_seconds_total{loop="host_replay"}.
                "chip_busy_s": round(chip["busy"], 4),
                "idle_other_s": round(chip["other"], 4),
                "prefetch_wait_s": round(ev_wait_s, 4),
                "prefetch_depth": round(ev_depth_sum / (did * dp), 2)
                if did else 0.0,
                "stale_batches": ev_stale,
            }
            if t_evac_parts is not None:
                row["chunk_collect_fetch_s"] = round(t_evac_parts[0], 4)
                row["chunk_ring_s"] = round(t_evac_parts[1], 4)
            if prefetchers is not None:
                row["h2d_staged_bytes"] = sum(p.bytes_staged
                                              for p in prefetchers)
            elif prefetcher is not None:
                row["h2d_staged_bytes"] = prefetcher.bytes_staged
            elif stager is not None:
                row["h2d_staged_bytes"] = stager.bytes_staged
            if did:
                loss_val = float(jax.device_get(metrics["loss"]))
                row["loss"] = round(loss_val, 4)
                # Divergence sentinel (ISSUE 4): a NaN/Inf loss dumps a
                # forensics bundle instead of training on silently.
                tm_watchdog.observe_divergence(loss=loss_val,
                                               step=grad_steps)
            history.append(row)
            log_fn(json.dumps(row))
            if g == _profile_chunk and _tracer.stop():
                log_fn(json.dumps({"profile_trace": profile_dir}))
            if ckpt is not None and env_steps >= next_save:
                next_save = env_steps + save_period
                _save_checkpoint(g)
            # Chaos seam (ISSUE 8): the deliberate mid-run kill the
            # resume-bit-identical pin uses — fired AFTER the save so
            # "killed at chunk k" means "with a checkpoint at k".
            cev = chaos.fire("host_replay.chunk")
            if cev is not None and cev.fault == "crash":
                raise chaos.ChaosInjectedError("host_replay.chunk",
                                               cev.fault)
        if ckpt is not None and num_chunks:
            # Final whole-state save: resuming a completed run is a
            # no-op pass straight to the summary.
            _save_checkpoint(num_chunks - 1)
    finally:
        if worker is not None:
            worker.close()
        if workers is not None:
            for w in workers:
                w.close()
        if prefetcher is not None:
            prefetcher.close()
        if prefetchers is not None:
            for p in prefetchers:
                p.close()
        if ckpt is not None:
            tm_watchdog.unregister_emergency_hook("host_replay.checkpoint")
            try:
                ckpt.close()
            except Exception as e:  # noqa: BLE001 — surfaced already
                log_fn(f"# host-replay checkpoint close failed: "
                       f"{type(e).__name__}: {e}")
        for hb in hb_collects:
            hb.close()
        hb_train.close()

    # Apply any accumulated-but-unflushed |TD| write-backs before the
    # summary counts them (the PER twin of the apex barrier flush).
    _wb_flush()
    wall = time.perf_counter() - t_start
    # Pin anchor for the pipelined-vs-serial equivalence test: a cheap
    # whole-params digest (float64 fold of float32 leaves, deterministic
    # on one host).
    param_checksum = float(sum(
        np.float64(np.sum(np.asarray(leaf, np.float64)))
        for leaf in jax.tree.leaves(jax.device_get(state.params))))
    # The checksum doubles as the sentinel's divergence signal: NaN/Inf
    # parameters at run end produce a bundle even when no per-chunk loss
    # was sampled (e.g. a run that never reached min_fill). Finiteness
    # only — the sentinel's explosion tracking compares consecutive
    # observations of ONE run's stream, and this is a once-per-run value
    # (two runs in one process would cross-compare).
    if not math.isfinite(param_checksum):
        tm_watchdog.observe_divergence(param_checksum=param_checksum,
                                       step=grad_steps)
    n = max(len(overlap_fracs), 1)
    g_grad_rate.set(grad_steps / wall)
    _prefetch_on = prefetcher is not None or prefetchers is not None
    _samplers = ([per_sampler] if per_sampler is not None
                 else per_samplers if per_samplers is not None else [])
    return {
        "env_steps": env_steps, "grad_steps": grad_steps,
        "wall_s": round(wall, 1),
        "env_steps_per_sec": round(env_steps / wall, 1),
        "grad_steps_per_sec": round(grad_steps / wall, 1),
        # n-chip scale-out provenance (ISSUE 10): the dp mesh width this
        # run's aggregate rates were produced over (1 = single chip).
        "dp_size": dp,
        # Learner-utilization config provenance (ISSUE 6): the knobs
        # that shaped this run's grad-step numbers.
        "replay_ratio": replay_ratio,
        "train_batch": train_batch,
        "actor_dtype": cfg.network.actor_dtype or "float32",
        # Sharded-collect provenance + per-shard conservation evidence
        # (ISSUE 15): in mesh mode each entry of d2h_bytes_by_shard is
        # the bytes shard s's OWN device evacuated, and
        # ring_bytes_by_shard the bytes appended into shard s's ring —
        # elementwise equality is the zero-cross-shard-scatter proof
        # scaling_bench's collect arm asserts.
        "sharded_collect": mesh_mode,
        "collect_lane_block": (B // dp) if mesh_mode else B,
        "collect_dispatch_s_total": round(collect_dispatch_s_total, 4),
        "d2h_bytes_by_shard": d2h_bytes_by_shard,
        "ring_bytes_by_shard": (list(store.bytes_by_shard)
                                if mesh_mode else None),
        "ring_transitions": (store.size if mesh_mode
                             else ring.size) * B,
        "ring_gb": round((store.nbytes if mesh_mode else ring.nbytes)
                         / 1e9, 3),
        "window_transitions_max": num_slots * B,
        "pipeline": pipeline,
        "evac_slices": (evac_slices if (evacuator is not None
                                        or workers is not None) else 0),
        "d2h_bytes_total": d2h_bytes_total,
        "evac_fence_wait_s_total": round(fence_wait_total, 4),
        "evac_overlap_frac_mean": round(sum(overlap_fracs) / n, 4),
        "param_checksum": param_checksum,
        "double_buffer": stager is not None or _prefetch_on,
        "h2d_staged_bytes": (
            sum(p.bytes_staged for p in prefetchers)
            if prefetchers is not None
            else prefetcher.bytes_staged if prefetcher is not None
            else stager.bytes_staged if stager is not None else 0),
        # Sample-side pipeline summary (ISSUE 5).
        "prefetch": _prefetch_on,
        "prefetch_depth": prefetch_depth if _prefetch_on else 0,
        "prioritized": bool(_samplers),
        # PER backend provenance (ISSUE 18): which priority-mass
        # backend drew this run's batches — scaling_bench's collect arm
        # records it beside the dp width.
        "sampler": ("device" if (_samplers and device_sampling)
                    else "tree" if _samplers else "uniform"),
        "sample_s_total": round(sample_s_total, 4),
        "prefetch_wait_s_total": round(prefetch_wait_s_total, 4),
        "stale_batches": (
            sum(p.stale_total for p in prefetchers)
            if prefetchers is not None
            else prefetcher.stale_total if prefetcher is not None else 0),
        "prio_writeback_flushes": sum(s.writeback_flushes
                                      for s in _samplers),
        "prio_writeback_rows": sum(s.writeback_rows for s in _samplers),
        "prio_writeback_dropped": sum(s.writeback_dropped
                                      for s in _samplers),
        "is_weight_mean": round(is_w_sum / is_w_count, 6)
        if is_w_count else 1.0,
        "is_weight_min": round(is_w_min, 6) if is_w_count else 1.0,
        # The ledger's cumulative buckets (telemetry/devtime.py).
        "chip_time": _ledger.snapshot(),
        "history": history,
    }
