"""The Ape-X learner service: TPU inference + assembly + replay + training.

One process owns the accelerator and runs four roles in one loop
(BASELINE.json:5,9):

  * inference server — drains actor observation records from the shm ring,
    runs the jitted epsilon-greedy policy (per-actor epsilon ladder) and
    posts actions to each actor's mailbox; params never leave the device;
  * assembler — folds per-lane step streams into n-step transitions
    (actors/assembler.py);
  * priority bootstrapper — computes initial |TD| for new transitions in
    power-of-two-bucketed batches on the device (Ape-X inserts with real
    priorities, not max-seeding). On the ingest fast path (ISSUE 2,
    docs/ingest_pipeline.md) the bootstrap rides the SAME dispatched
    program as the batched act — one device round-trip per ingest pass;
  * learner — samples the host PER shard (batch g+1 staged through the
    double-buffered H2D path while step g trains), one jitted train step
    per ``grad_batch_per_env_step`` inserted transitions, writes
    priorities back in batched sum-tree updates.

Throughput counters (env-steps/sec/chip, grad-steps/sec) are the
north-star metrics (BASELINE.json:2) and are reported every flush.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
import uuid
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from dist_dqn_tpu import chaos
from dist_dqn_tpu.actors.assembler import NStepAssembler
from dist_dqn_tpu.actors.transport import (ShmMailbox, ShmRing, shm_dir,
                                           decode_arrays, encode_arrays)
from dist_dqn_tpu.config import ExperimentConfig
from dist_dqn_tpu.actors.act_dispatch import pack_act_rows
from dist_dqn_tpu.telemetry import collectors as tmc, get_registry
from dist_dqn_tpu.telemetry import watchdog as tm_watchdog
from dist_dqn_tpu.utils.metrics import MetricLogger

_PRIO_CHUNK = 256
# Ingest fast path (ISSUE 2): the fused/batched bootstrap dispatch takes
# up to this many pending transitions in ONE device program, padded to
# one of exactly TWO row buckets — _PRIO_CHUNK (the lockstep regime:
# a few rows per pass) or _PRIO_MAX_ROWS (the saturated regime: a full
# batch, zero padding). Two buckets, not the full power-of-two ladder,
# because the FUSED program's compile variants are the cross-product
# with the act-row buckets — 2 x O(log actors) keeps the warmup
# compiles bounded. The in-between case (257..2047 pending) pads to the
# large bucket: ~8x bytes worst case; the staging byte counters keep it
# visible. The legacy split path (fused_ingest=False) keeps the
# per-256 loop: that IS the A/B baseline.
_PRIO_MAX_ROWS = 2048


@dataclasses.dataclass
class ApexRuntimeConfig:
    """Host-side knobs for the actor/learner split."""

    host_env: str = "CartPole-v1"   # host env actors step (ale:<Game> for ALE)
    num_actors: int = 2
    envs_per_actor: int = 4
    total_env_steps: int = 10_000
    # Learner cadence: one grad step per this many inserted transitions,
    # scaled by the learner batch size (Ape-X trains ~batch/8 per insert).
    inserts_per_grad_step: int = 64
    ring_mb: int = 64
    log_every_s: float = 5.0
    # Learner checkpoint/resume (SURVEY.md §5: the learner state is the
    # recovery point; actors/replay are stateless and refill).
    checkpoint_dir: Optional[str] = None
    save_every_steps: int = 100_000    # env steps between checkpoints
    # Opt-in replay-state checkpointing (VERDICT round-3 next #7): also
    # snapshot the host replay shard beside the learner checkpoint on
    # every save and restore it on startup, trading ring-sized writes
    # (a 60k pixel shard is ~1.7 GB) for resuming with a warm,
    # already-distributed buffer instead of a min_fill refill. The
    # default stays stateless (utils/checkpoint.py has the cost math).
    checkpoint_replay: bool = False
    # Periodic greedy evaluation on a service-owned env instance.
    eval_every_steps: int = 0          # 0 disables
    eval_episodes: int = 5
    # DCN path: actors on OTHER hosts connect over TCP (full-duplex record
    # stream, actors/transport.py). tcp_port None disables the listener;
    # 0 binds an ephemeral port (exposed as service.tcp_address).
    # num_remote_actors are spawned locally by the service for tests /
    # single-host runs; real remote actors run
    # ``python -m dist_dqn_tpu.actors.remote`` against tcp_address.
    tcp_port: Optional[int] = None
    num_remote_actors: int = 0
    # True (default): the service spawns its remote actors as local
    # processes — the single-host DCN stand-in. False: the slots stay open
    # for external workers started on other hosts against tcp_address.
    spawn_remote_actors: bool = True
    # Multi-learner: shard each training batch over this many local
    # devices with gradients pmean-allreduced over ICI (the service-side
    # counterpart of the fused mesh trainer; the NCCL-allreduce
    # replacement, BASELINE.json:5). 1 = single device; 0 = all local.
    learner_devices: int = 1
    # C++ n-step assembly (actors/_native/assembler.cc; ~6x the Python
    # path on pixel frames). Feed-forward configs only — the R2D2
    # sequence assembler is Python. A failed native build raises; False
    # selects the Python assembler.
    native_assembly: bool = True
    # Host-loop tracing (utils/trace.py): write a Chrome trace-event file
    # here covering ingestion / priority / sample / train spans — the host
    # counterpart of the device xprof trace. None disables (no overhead).
    trace_path: Optional[str] = None
    # On-device priority sampling for the host-DRAM shard (the
    # BASELINE.json:5 wording): priority plane in accelerator memory,
    # stratified draws via the Pallas kernel above its crossover. Items
    # stay in host DRAM. Off by default — the C++ host tree wins below
    # pod-scale shard sizes.
    device_sampling: bool = False
    # Ingest-stall watchdog (SURVEY.md §5 failure detection): warn when no
    # actor record has arrived for this many seconds while the run is not
    # finished — actors may be wedged in ways process supervision can't
    # see (remote workers gone, transport stuck). 0 disables.
    stall_warn_s: float = 30.0
    # Multi-host cadence: under a jax.distributed runtime, how often each
    # host fires the counter-agreement collective (actors/multihost.py).
    # The call BLOCKS until every host joins, so this is a minimum period,
    # not a timer the hosts must hit together.
    sync_every_s: float = 0.05
    # Loop-responsiveness bound: at most this many train steps per
    # service-loop pass. The cadence target is a RATIO (grad steps per
    # inserts); when the learner is slower than the ratio asks, an
    # unbounded catch-up loop would monopolize the host thread and
    # starve ingestion/acting (measured: the round-4 CPU calibration
    # run stalled ingest ~100s at a time). Bounding the per-pass work
    # keeps actors fed while the learner runs flat out; the debt simply
    # persists — standard Ape-X "learner as fast as it can" semantics.
    # Multi-host lockstep stays intact: every host computes the same
    # bounded step count from agreed counters.
    train_steps_per_pass: int = 4
    # Learner pipelining: keep up to this many train steps in flight —
    # the host samples/stages upcoming batches and writes completed steps'
    # priorities while the device works (JAX dispatch is async). Priority
    # updates lag by at most this many steps — standard Ape-X async-learner
    # semantics. 0 = fully synchronous. Depth >1 mainly pays off when
    # device round-trip LATENCY (not compute) dominates.
    pipeline_depth: int = 2
    # Ingest fast path (ISSUE 2): fuse the batched-act and priority-
    # bootstrap programs into ONE jitted dispatch per ingest pass
    # (feed-forward configs; the R2D2 path has no device bootstrap):
    # half the device calls per pass. False restores the split
    # dispatches (the A/B baseline benchmarks/apex_feeder_bench.py
    # measures against).
    fused_ingest: bool = True
    # Batched priority write-backs: accumulate this many train steps'
    # |TD| write-backs in a fixed-size pending buffer and apply them as
    # ONE sum-tree update (vectorized propagation over all rows) instead
    # of one per step. Priorities lag the learner by at most this many
    # steps on top of pipeline_depth — the expected_gen guard still
    # drops updates for overwritten slots. 1 = legacy per-step flush.
    prio_writeback_batch: int = 8
    # Double-buffered H2D staging (replay/staging.py): sample + upload
    # batch g+1 into reusable pinned-host staging buffers while step g
    # trains. Single-device learners only (the multi-host/multi-learner
    # paths shard batches themselves); 0 = legacy serial sample->upload.
    stage_depth: int = 2
    # Zero-copy ingest subsystem (ISSUE 9, dist_dqn_tpu/ingest/):
    # "zerocopy" (default) negotiates a trajectory schema at hello and
    # ships raw-array frames — seqlock shm slot rings for same-host
    # actors (no socket stack), length-prefixed zero-copy frames under
    # the ISSUE 8 CRC framing on TCP. "legacy" keeps the bit-pinned
    # JSON-header codec everywhere (the A/B baseline) — DEPRECATED
    # since ISSUE 14: scheduled for removal after one release of A/B
    # parity (docs/ingest_pipeline.md §7 records the criterion).
    transport: str = "zerocopy"
    # Frame-stack dedup plane (ISSUE 14): actors on frame-stacked pixel
    # envs ship each physical frame ONCE per episode stream (novel
    # frame + back-references; the service reconstructs full stacks at
    # append time). Negotiated per actor at hello — a non-dedup actor
    # joins a dedup-capable service on the plain zero-copy layout.
    # False (--no-wire-dedup) disables the capability fleet-wide.
    wire_dedup: bool = True
    # Batched shm slot publishes (ISSUE 14): feeder processes coalesce
    # this many step records into one seqlock slot publish (the
    # handshake amortization lever for unthrottled producers). Sizes
    # the slot rings accordingly; 1 = the bit-pinned per-record wire.
    # Real rollout actors are lock-step and always publish per record.
    shm_batch: int = 1
    # Ingest-side per-shard sampling (ISSUE 14, requires ingest_shards
    # > 1): per-shard worker threads run the stratified draw + gather
    # where the data lives and hand the learner pre-packed batches
    # through a bounded queue — train events stop paying sample time
    # on the learner thread. Draw math pinned bit-identical to the
    # facade draw (replay/sharded.py ShardSampleService).
    shard_sampling: bool = False
    # Actor-side priority pre-computation (ISSUE 9 piece 3, zerocopy
    # only): act replies carry the inference-time q planes, actors echo
    # them on their step frames, and insertion priorities are computed
    # host-side from the frames — the ingest pass performs ZERO
    # priority-bootstrap device dispatches (pinned via device_calls).
    # Rides the Python assembler (q-plane threading); False restores
    # the learner-side bootstrap (+ native assembly where configured).
    actor_priorities: bool = True
    # Sticky ingest routing (ISSUE 9 piece 4, store landed in ISSUE 10):
    # replay-shard count. > 1 splits the store into that many
    # PrioritizedHostReplay shards (replay/sharded.py) and every
    # actor's stream lands in its sticky crc32 shard — the id threaded
    # through frame headers since PR 9, now consumed by the append
    # path. Requires per-actor insert attribution (zerocopy transport
    # with actor priorities, or a recurrent config) and the host tree
    # sampler; the constructor rejects anything else loudly.
    ingest_shards: int = 1
    # Prometheus scrape endpoint (telemetry/server.py): serve the process
    # registry's /metrics on this port (0 = ephemeral, logged as
    # telemetry_port). None disables. Same surface as the fused
    # runtime's --telemetry-port.
    telemetry_port: Optional[int] = None
    # Bind address for the scrape endpoint: loopback by default (the
    # metric/debug surface is unauthenticated); "0.0.0.0" exposes it to
    # scrapers outside the container/VM (--telemetry-host).
    telemetry_host: str = "127.0.0.1"
    # --profile-dir (ISSUE 19 satellite): capture a jax.profiler trace
    # of the FIRST train event (dispatch through priority materialize —
    # the apex analogue of the fused loop's first post-warmup chunk)
    # into this directory. For a window at an arbitrary point of a live
    # run, hit /debug/profile?seconds=N on the telemetry server instead.
    profile_dir: Optional[str] = None


class ApexLearnerService:
    def __init__(self, cfg: ExperimentConfig, rt: ApexRuntimeConfig,
                 log_fn=print):
        import jax  # deferred: this process owns the accelerator
        import jax.numpy as jnp

        from dist_dqn_tpu.agents.dqn import make_actor_step, make_learner
        from dist_dqn_tpu.models import build_network
        from dist_dqn_tpu.replay.host import PrioritizedHostReplay

        self.jax, self.jnp = jax, jnp
        self.cfg, self.rt = cfg, rt
        self.run_id = uuid.uuid4().hex[:8]
        self.log = MetricLogger(log_fn=log_fn)
        # Actor id space: [0, num_actors) are local (shm transport),
        # [num_actors, total_actors) are remote (TCP/DCN transport).
        self.total_actors = rt.num_actors + rt.num_remote_actors

        # ingest_shards validation FIRST — before any shm segment or
        # socket exists, so a rejected config cannot leak transports
        # out of a half-built service (ISSUE 10; the sharded store
        # itself is constructed further down).
        if rt.ingest_shards < 1:
            raise ValueError(
                f"ingest_shards must be >= 1, got {rt.ingest_shards}")
        if rt.shm_batch < 1:
            raise ValueError(f"shm_batch must be >= 1, got "
                             f"{rt.shm_batch}")
        if rt.shard_sampling and rt.ingest_shards < 2:
            raise ValueError(
                "shard_sampling requires ingest_shards > 1: the "
                "per-shard sampling threads live where the sharded "
                "store's data lives — a single store has no shard "
                "workers to move the draw into")
        if rt.transport == "legacy":
            log_fn("# DEPRECATION: --transport legacy is the bit-pinned"
                   " A/B fallback only and is scheduled for removal "
                   "after one release of zerocopy A/B parity "
                   "(docs/ingest_pipeline.md §7; apex_feeder_bench "
                   "--ab rows are the parity evidence)")
        if rt.device_sampling and rt.transport == "legacy":
            raise ValueError(
                "--transport legacy with --device-sampling is not "
                "supported: the legacy concatenated bootstrap path is "
                "the bit-pinned A/B fallback and stays on the host "
                "tree sampler — use --transport zerocopy for the "
                "device priority planes")
        if rt.device_sampling and rt.shard_sampling:
            raise ValueError(
                "--shard-sampling with --device-sampling is redundant: "
                "the per-shard worker threads exist to move HOST tree "
                "draws off the learner thread, and the device planes "
                "already run each shard's draw on its own chip — pick "
                "one")
        if rt.ingest_shards > 1:
            if cfg.network.lstm_size <= 0 and not (
                    rt.transport == "zerocopy" and rt.actor_priorities):
                raise ValueError(
                    "ingest_shards > 1 requires per-actor insert "
                    "attribution: run --transport zerocopy with actor "
                    "priorities (the default), or a recurrent (R2D2) "
                    "config — the legacy bootstrap path concatenates "
                    "transitions across actors before inserting, so "
                    "sticky placement would be a lie there")

        # Transport endpoints (created before actors spawn).
        self.req_ring = ShmRing(f"req_{self.run_id}",
                                capacity=rt.ring_mb * 1024 * 1024,
                                create=True)
        self.act_boxes = [
            ShmMailbox(f"act_{self.run_id}_{i}", max_size=1 << 20,
                       create=True)
            for i in range(rt.num_actors)
        ]
        self.tcp_server = None
        self.tcp_address = None
        if rt.tcp_port is not None or rt.num_remote_actors:
            from dist_dqn_tpu.actors.transport import TcpRecordServer
            # Loopback unless an external port was explicitly requested —
            # the record stream is unauthenticated, so the single-host
            # stand-in mode must not listen on all interfaces.
            host = "0.0.0.0" if rt.tcp_port is not None else "127.0.0.1"
            self.tcp_server = TcpRecordServer(host=host,
                                              port=rt.tcp_port or 0)
            self.tcp_address = self.tcp_server.address
        self._actor_conn: Dict[int, int] = {}   # remote actor id -> conn id
        self.stop_path = str(shm_dir() / f"stop_{self.run_id}")

        # Probe the env for action count + an obs example (host-side).
        from dist_dqn_tpu.envs.gym_adapter import make_host_env
        probe = make_host_env(rt.host_env, 1)
        self.num_actions = probe.num_actions
        obs_example = probe.reset()[0]
        # Dedup capability probe (ISSUE 14): the env's declared
        # frame-stack depth sizes the slot rings for dedup boundary
        # records (worst case ~2x a plain record — every frame slot of
        # both stacks inline plus tables).
        self._probe_frame_stack = int(getattr(probe, "frame_stack", 0)
                                      or 0)
        del probe

        # Zero-copy ingest (ISSUE 9): sticky-shard router + per-local-
        # actor seqlock slot rings (created HERE, attached by spawned
        # actors — same ownership model as the mailboxes above). Slot
        # geometry derives from the env probe; the actor's hello carries
        # its own derivation and a mismatch fails at connect.
        #
        # ingest_shards > 1 (ISSUE 10): the sharded store exists now —
        # the replay splits into N PrioritizedHostReplay shards and
        # every actor's stream lands in its sticky crc32 shard
        # (replay/sharded.py; config validated at the top of __init__,
        # before any transport existed).
        from dist_dqn_tpu import ingest
        self._ingest = ingest
        self.router = ingest.StickyShardRouter(rt.ingest_shards)
        self._decoders: Dict[int, object] = {}   # actor id -> StepDecoder
        self._zc_rings: Dict[int, object] = {}
        self._expected_schema = None
        if rt.transport == "zerocopy":
            self._expected_schema = ingest.step_schema(
                obs_example.shape, obs_example.dtype, rt.envs_per_actor)
            # Slot must fit the larger of a step record and the legacy-
            # coded hello ([lanes, obs] + JSON header) with headroom;
            # dedup-capable fleets also fit the dedup worst case
            # (boundary record with every frame inline + tables), and
            # batching feeders fit shm_batch records per slot.
            base = max(ingest.max_record_bytes(self._expected_schema),
                       rt.envs_per_actor * obs_example.nbytes + 4096)
            if rt.wire_dedup and self._probe_frame_stack >= 2:
                try:
                    base = max(base, ingest.max_dedup_record_bytes(
                        self._expected_schema, self._probe_frame_stack))
                except ValueError:
                    pass    # obs layout doesn't match the declared
                    #         stack: actors won't negotiate dedup either
            if rt.shm_batch > 1:
                from dist_dqn_tpu.ingest.shm_ring import batch_bytes
                base = max(base,
                           batch_bytes([base] * rt.shm_batch))
            for i in range(rt.num_actors):
                self._zc_rings[i] = ingest.ShmSlotRing(
                    f"req_{self.run_id}_zc_{i}", slot_size=base,
                    nslots=8, create=True)
        elif rt.transport != "legacy":
            raise ValueError(f"unknown transport {rt.transport!r} "
                             f"(expected 'zerocopy' or 'legacy')")

        net = build_network(cfg.network, self.num_actions)
        self.net = net
        # Multi-host (jax.distributed runtime): every host runs its own
        # service — actors + replay shard — and train steps are collective
        # over the GLOBAL mesh (actors/multihost.py). Non-zero processes
        # compute silently; process 0 reports.
        self.distributed = jax.process_count() > 1
        if self.distributed:
            from dist_dqn_tpu.parallel.distributed import main_process_log
            self.log = MetricLogger(log_fn=main_process_log(log_fn))
        # Multi-learner: batches shard over the dp mesh axis, gradients
        # pmean over ICI, learner state replicated.
        self.n_learners = (len(jax.local_devices())
                           if rt.learner_devices == 0
                           else rt.learner_devices)
        if self.distributed:
            if rt.learner_devices != 1:
                log_fn("# distributed mode: the train mesh spans every "
                       "global device; --learner-devices ignored")
            self.n_learners = jax.local_device_count()
        elif self.n_learners > len(jax.devices()):
            raise ValueError(
                f"learner_devices={self.n_learners} but only "
                f"{len(jax.devices())} devices are available")
        if not self.distributed and cfg.learner.batch_size % self.n_learners:
            raise ValueError(
                f"batch_size={cfg.learner.batch_size} not divisible by "
                f"learner_devices={self.n_learners}")
        axis = "dp" if (self.n_learners > 1 or self.distributed) else None
        # Recurrent (R2D2) configs swap in the sequence learner, the
        # carry-threaded policy and the sequence assembler; the transport,
        # actors and replay shard are shared (BASELINE.json:10).
        if cfg.network.core.kind != "lstm":
            raise ValueError(
                "the apex service assembles sequences with the LSTM's "
                "(c, h) pair on the wire (actors/assembler.py); a "
                f"network.core.kind={cfg.network.core.kind!r} state is "
                "not carried there — use the fused loop")
        self.recurrent = cfg.network.lstm_size > 0
        if self.recurrent:
            from dist_dqn_tpu.actors.assembler import SequenceAssembler
            from dist_dqn_tpu.agents.r2d2 import (make_r2d2_learner,
                                                  make_recurrent_actor_step)
            init, train_step = make_r2d2_learner(net, cfg.learner,
                                                 cfg.replay,
                                                 axis_name=axis)
            self._act = jax.jit(make_recurrent_actor_step(net,
                                                          return_q=True))
            self.seq_len = (cfg.replay.burn_in + cfg.replay.unroll_length
                            + cfg.learner.n_step)
            stride = cfg.replay.sequence_stride or cfg.replay.unroll_length
            self._asm_factory = (
                lambda lanes: SequenceAssembler(lanes, self.seq_len,
                                                stride))
            self.assemblers = [
                self._asm_factory(rt.envs_per_actor)
                for _ in range(self.total_actors)
            ]
            self._carry: List = [None] * self.total_actors
            self._prev_carry: List = [None] * self.total_actors
            self._prev_q: List = [None] * self.total_actors
            self._prio_fn = None
            self._fused = None
            # R2D2 already seeds priorities from its inference-time q
            # planes service-side; the frame-shipped plane loop is the
            # feed-forward path's (ISSUE 9).
            self.actor_prio = False
            self._act_q = None
        else:
            init, train_step = make_learner(net, cfg.learner,
                                            axis_name=axis)
            act_fn = make_actor_step(net)
            self._act = jax.jit(act_fn)
            # Actor-side priorities (ISSUE 9 piece 3): the act program
            # also returns (q_sel, q_max); the planes ride the reply,
            # the actor echoes them on its next frame, and insertion
            # priorities fold host-side — ZERO bootstrap dispatches.
            self.actor_prio = (rt.transport == "zerocopy"
                               and rt.actor_priorities)
            self._act_q = (jax.jit(make_actor_step(net, return_q=True))
                           if self.actor_prio else None)
            asm_cls = NStepAssembler
            if rt.native_assembly and not self.actor_prio:
                from dist_dqn_tpu.actors.assembler import \
                    NativeNStepAssembler, _assembler_lib
                # Force the g++ build now, not mid-run; a failed build
                # raises (native_assembly=False selects the Python path).
                _assembler_lib()
                asm_cls = NativeNStepAssembler
            elif rt.native_assembly and self.actor_prio:
                log_fn("# actor-side priorities thread q planes through "
                       "the Python assembler; native assembly applies "
                       "to the legacy/bootstrap path only")
            self._asm_factory = (
                lambda lanes: asm_cls(lanes, cfg.learner.n_step,
                                      cfg.learner.gamma))
            self.assemblers = [
                self._asm_factory(rt.envs_per_actor)
                for _ in range(self.total_actors)
            ]

            def prio_fn(params, target_params, obs, action, reward,
                        discount, next_obs):
                # Scalar-Q view regardless of head type: with a C51 head,
                # q_values reduces the distribution to its expectation, so
                # initial priorities stay a meaningful |TD| for Rainbow
                # configs too (the learner's cross-entropy priorities take
                # over after the first update).
                q = net.apply(params, obs, method=net.q_values)
                qa = jnp.take_along_axis(q, action[:, None], axis=-1)[:, 0]
                boot = jnp.max(
                    net.apply(target_params, next_obs, method=net.q_values),
                    axis=-1)
                return jnp.abs(qa - (reward + discount * boot))

            self._prio_fn = jax.jit(prio_fn)

            def fused_fn(params, target_params, obs, rng, eps,
                         b_obs, b_action, b_reward, b_discount, b_next_obs):
                # One dispatched program serves BOTH per-pass device jobs:
                # the batched epsilon-greedy act for this burst's actors
                # AND the |TD| priority bootstrap for one pending chunk
                # — half the per-pass dispatch count.
                actions = act_fn(params, obs, rng, eps)
                prios = prio_fn(params, target_params, b_obs, b_action,
                                b_reward, b_discount, b_next_obs)
                return actions, prios

            # With actor-side priorities the bootstrap has nothing to
            # compute, so there is nothing to fuse: the act(+q) program
            # is the single per-pass dispatch. _prio_fn stays jitted for
            # legacy-codec actors joining a zerocopy service mid-fleet.
            self._fused = (jax.jit(fused_fn)
                           if rt.fused_ingest and not self.actor_prio
                           else None)
        self.state = None
        self._init_learner = init
        self._mh = None
        self._host_params = None
        self._mesh = None
        if self.distributed:
            from dist_dqn_tpu.actors.multihost import MultihostLearner
            self._mh = MultihostLearner()
            self._local_batch, _ = self._mh.shard_batch_size(
                cfg.learner.batch_size)
            data_specs, metric_specs = self._step_specs(axis)
            self._train_step = self._mh.wrap_train_step(
                train_step, data_specs, metric_specs)
            self._init_learner = self._mh.wrap_init(init)
        elif axis is None:
            self._train_step = jax.jit(train_step, donate_argnums=0)
        else:
            self._train_step = self._shard_train_step(train_step, axis)

        # Replay-ratio engine (ISSUE 6): fold N grad sub-steps into ONE
        # scanned dispatch (agents/dqn.py make_scan_train) — the apex
        # learner takes the same scan path the fused loop runs, so one
        # dispatch buys N steps. Train-event batches resolve through the
        # same pow2 bucket rule as the other runtimes
        # (loop_common.resolve_train_batch).
        from dist_dqn_tpu import loop_common
        self.replay_ratio = loop_common.resolve_replay_ratio(cfg)
        self.train_batch = loop_common.resolve_train_batch(cfg)
        if not self.distributed and self.train_batch % self.n_learners:
            raise ValueError(
                f"train batch {self.train_batch} not divisible by "
                f"learner_devices={self.n_learners} (rows shard evenly "
                "over the learner mesh)")
        self._train_scan = None
        if self.replay_ratio > 1:
            if self.recurrent or self.distributed:
                log_fn("# replay.updates_per_chunk > 1 is not supported "
                       "on the recurrent / multi-host apex paths yet; "
                       "running at replay ratio 1")
                self.replay_ratio = 1
            elif self.n_learners == 1:
                from dist_dqn_tpu.agents.dqn import make_scan_train
                self._train_scan = jax.jit(make_scan_train(train_step),
                                           donate_argnums=0)
            else:
                # Data-parallel replay-ratio scan (ISSUE 10): the SAME
                # scanned N-sub-step program, lifted over the local
                # learner mesh — rows shard on batch axis 1 and the
                # priorities come back [N, B] (flatten=False) so the
                # host's chronological [N*B] reshape is sub-step-major,
                # not device-block-major (scan_train_step_specs).
                from dist_dqn_tpu.agents.dqn import make_scan_train
                from dist_dqn_tpu.parallel.learner import (
                    make_sharded_train_step, scan_train_step_specs)
                scan_data, scan_metrics = scan_train_step_specs(axis)
                self._train_scan = make_sharded_train_step(
                    make_scan_train(train_step, flatten=False),
                    self._learner_mesh(), scan_data, scan_metrics)
        if self.distributed and self.train_batch != cfg.learner.batch_size:
            log_fn("# replay.train_batch widening is single-host only "
                   "(multi-host batches shard from learner.batch_size); "
                   "ignored")
            self.train_batch = cfg.learner.batch_size
        from dist_dqn_tpu.telemetry import devtime as _devtime
        self._devtime = _devtime
        # The ledger's `busy`: the wall train steps occupied the device
        # queue, summed at their retirement fences (_finalize_train),
        # from this anchor on.
        self._train_busy_s = 0.0
        self._busy_anchor = time.perf_counter()
        self._ledger = _devtime.UtilizationLedger("apex")
        self._ledger_busy_seen = 0.0
        self._ledger_t_last = time.perf_counter()
        # --profile-dir (ISSUE 19 satellite): one-shot trace of the
        # first train event, started at its first dispatch and stopped
        # at its first retirement fence (_finalize_train).
        self._profile_tracer = _devtime.maybe_trace_first_chunk(
            rt.profile_dir)
        # The apex actors pull the live learner params for acting; the
        # once-per-chunk bf16 snapshot the fused/host-replay loops cast
        # has no natural boundary here yet — say so, act in fp32.
        self.actor_dtype = "float32"
        if cfg.network.actor_dtype not in ("", "float32"):
            log_fn("# network.actor_dtype is not applied by the apex "
                   "service yet (acting uses the live learner params); "
                   "running actor inference in float32")

        if rt.ingest_shards > 1:
            # Sharded store (ISSUE 10): N per-shard sum-trees, inserts
            # routed by the sticky shard id every frame header carries,
            # draws stratified across shards by tree mass, slot ids
            # globally encoded so the pipelined write-back path works
            # unchanged (replay/sharded.py). --device-sampling (ISSUE
            # 18) swaps every shard's tree for an on-device priority
            # plane pinned to its sticky chip; the global ladder and
            # the write-back/generation semantics are identical.
            from dist_dqn_tpu.replay.sharded import ShardedPrioritizedReplay
            self.replay = ShardedPrioritizedReplay(
                rt.ingest_shards, cfg.replay.capacity,
                alpha=cfg.replay.priority_exponent,
                priority_eps=cfg.replay.priority_eps,
                sampler="device" if rt.device_sampling else "tree")
        else:
            self.replay = PrioritizedHostReplay(
                cfg.replay.capacity, alpha=cfg.replay.priority_exponent,
                priority_eps=cfg.replay.priority_eps,
                sampler="device" if rt.device_sampling else "tree")
        # Ingest-side per-shard sampling (ISSUE 14): the stratified
        # draw + gather move into per-shard worker threads; the learner
        # pops pre-packed batches (config validated at the top of
        # __init__ — requires the sharded store above).
        self._shard_sampler = None
        if rt.shard_sampling:
            from dist_dqn_tpu.replay.sharded import ShardSampleService
            self._shard_sampler = ShardSampleService(
                self.replay, depth=max(rt.pipeline_depth, 1))
        # Ape-X per-actor epsilon ladder: eps_i = base ** (1 + i/(N-1)*alpha).
        n_act = max(self.total_actors - 1, 1)
        self.actor_eps = np.array([
            cfg.actor.apex_epsilon_base
            ** (1 + i / n_act * cfg.actor.apex_epsilon_alpha)
            for i in range(self.total_actors)
        ], np.float32)

        self._prev_obs: List[Optional[np.ndarray]] = \
            [None] * self.total_actors
        self._prev_actions: List[Optional[np.ndarray]] = \
            [None] * self.total_actors
        self._pending: List[Dict[str, np.ndarray]] = []
        self._pending_count = 0
        # Actor-side priority bookkeeping (ISSUE 9): drained-but-not-
        # yet-inserted transitions awaiting their bootstrap q_max from
        # THIS pass's act flush, keyed by act-request id; the per-actor
        # last flush planes cover the final-drain edge at shutdown.
        self._req_seq = 0
        self._prio_await: List = []          # (actor, rid, emitted)
        self._flush_q: Dict[int, np.ndarray] = {}    # rid -> q_max rows
        self._last_flush_q: Dict[int, np.ndarray] = {}
        # (idx, gen, metrics, t_dispatch) per dispatched train step.
        self._in_flight = deque()
        self._act_queue: List = []  # (actor, obs, t) awaiting batched act
        self._obs_spec = None       # (per-env obs shape, dtype), first hello
        self._last_record = time.perf_counter()
        self._stall_warned = False
        self.env_steps = 0
        self.grad_steps = 0
        self._rng = None
        self._ckpt = None
        self._eval_env = None
        self._next_eval = rt.eval_every_steps or float("inf")
        # Async eval (multi-host): worker thread + its pending result and a
        # dedicated rng so eval never races the main loop's key stream.
        self._eval_thread: Optional[threading.Thread] = None
        # Worker threads append, the main loop pops: deque ops are atomic,
        # so a result finishing between the poller's load and clear cannot
        # be silently erased (a single shared slot could drop one).
        self._eval_results: deque = deque()
        self._eval_rng = None
        self.bad_records = 0
        self.actor_restarts = 0
        # Training episode returns, accumulated from the RAW per-lane
        # reward stream the drain path already sees (in the env's
        # training units, i.e. post-preprocessing clipping) — the apex
        # counterpart of the fused loop's episode_return metric; unlike
        # a synchronous host eval env (one device call per step) it
        # costs no dispatches.
        self._ep_accum: Dict[int, np.ndarray] = {}
        self._ep_returns: deque = deque(maxlen=64)
        self.episodes_completed = 0
        # Pipelined priority bootstraps: (device prios, items, count)
        # awaiting materialization+insert (see _flush_pending).
        self._boot_inflight: deque = deque()
        # Batched priority write-backs (ISSUE 2): materialized train-step
        # priorities pending the next batched sum-tree update, as
        # (idx, priorities, gen) triples; bounded by prio_writeback_batch.
        self._prio_pending: List = []
        # Device round-trip accounting (ISSUE 2): every dispatched
        # program increments its kind here; the feeder bench divides by
        # ingest passes to report round-trips per pass.
        self.device_calls: Dict[str, int] = {}
        # Device-sampling dispatch watermark: how many per-shard plane
        # draws device_calls has already mirrored (ISSUE 18).
        self._replay_draws_counted = 0
        self.ingest_passes = 0
        # H2D staging for the learner (replay/staging.py): single-device
        # only — multi-host/multi-learner batches are sharded by their
        # own wrappers from host numpy.
        self._stager = None
        if (rt.stage_depth > 0 and not self.distributed
                and self.n_learners == 1):
            from dist_dqn_tpu.replay.staging import DoubleBufferedStager
            self._stager = DoubleBufferedStager(depth=rt.stage_depth,
                                                name="apex_service")
        from dist_dqn_tpu.utils.trace import make_tracer
        self.tracer = make_tracer(rt.trace_path, process_name="apex-learner")
        self._init_telemetry()
        self.telemetry_server = None
        if rt.telemetry_port is not None:
            from dist_dqn_tpu.telemetry import start_server
            from dist_dqn_tpu.telemetry import fleet as _fleet
            self.telemetry_server = start_server(rt.telemetry_port,
                                                 host=rt.telemetry_host)
            self.log.log_fn(json.dumps(
                {"telemetry_port": self.telemetry_server.port}))
            # Fleet registry (ISSUE 16): announce after bind — the
            # descriptor must carry the resolved ephemeral port. No-op
            # unless DQN_FLEET_DIR is configured for the run.
            _fleet.register_endpoint("learner", self.telemetry_server.port,
                                     host=rt.telemetry_host,
                                     labels={"loop": "apex"})
        self.global_env_steps = 0
        self._resume_global = 0
        self._next_sync = 0.0
        if self.distributed:
            # Collective ordering must be identical on every process, so
            # the learner init (the group's first collective, plus the
            # checkpoint restore when configured) happens HERE — the first
            # actor hello lands at different times on different hosts.
            self._ensure_learner(obs_example)

    def _init_telemetry(self):
        """Registry instruments for the service loop (ISSUE 1): pipeline
        queue depths, throughput counters, and the two latency
        histograms — grad-step dispatch->materialize and host-param-
        mirror staleness — that localize a learner-utilization drop
        (docs/observability.md has the triage order)."""
        reg = get_registry()
        self._tm_env_steps = reg.counter(
            tmc.ENV_STEPS, "env transitions ingested from actors")
        self._tm_grad_steps = reg.counter(
            tmc.GRAD_STEPS, "learner train steps dispatched")
        self._tm_grad_latency = reg.histogram(
            tmc.GRAD_LATENCY,
            "train-step dispatch -> priority materialization")
        self._tm_param_staleness = reg.histogram(
            tmc.PARAM_STALENESS,
            "age of the host param mirror at each refresh")
        self._tm_act_queue = reg.gauge(
            "dqn_service_act_queue_requests",
            "actor act requests awaiting the batched device call")
        self._tm_pending = reg.gauge(
            "dqn_service_pending_transitions",
            "assembled transitions awaiting priority bootstrap dispatch")
        self._tm_boot_inflight = reg.gauge(
            "dqn_service_bootstrap_inflight",
            "priority-bootstrap chunks dispatched, not yet inserted")
        self._tm_train_inflight = reg.gauge(
            "dqn_service_train_inflight",
            "pipelined train steps awaiting priority write-back")
        # Experience-lineage staleness (ISSUE 16): every sampled batch
        # ages its wire lineage stamps into the shared families.
        self._tm_sample_age, self._tm_sample_staleness = \
            tmc.lineage_histograms("apex")
        # Ingest fast path (ISSUE 2): dispatch accounting. One counter
        # series per dispatched-program kind, cached on first use.
        self._tm_device_calls: Dict[str, object] = {}
        self._tm_fanin = reg.histogram(
            tmc.DISPATCH_FANIN,
            "obs rows per batched act/fused dispatch",
            buckets=tmc.FANIN_BUCKETS)
        self._tm_ingest_passes = reg.counter(
            tmc.INGEST_PASSES,
            "drain bursts that ingested at least one actor record")
        self._tm_prio_pending = reg.gauge(
            tmc.PRIO_WRITEBACK_PENDING,
            "train steps accumulated toward the next batched priority "
            "write-back")
        self._tm_bad_records = reg.counter(
            "dqn_service_bad_records_total",
            "malformed/misrouted records rejected at the TCP boundary")
        # Zero-copy ingest (ISSUE 9): transitions inserted with frame-
        # shipped priorities — each one a bootstrap dispatch that never
        # happened (the acceptance pin divides device_calls by these).
        self._tm_actor_prio = reg.counter(
            tmc.INGEST_ACTOR_PRIO_TRANSITIONS,
            "transitions inserted with actor-shipped |TD| priorities "
            "(zero learner-side bootstrap dispatches)")
        # Frame-dedup plane (ISSUE 14): reused frame slots + wire bytes
        # saved, swept from the per-actor decoders' plain-int counters
        # on the log cadence (no registry calls on the decode path).
        self._tm_dedup_frames = reg.counter(
            tmc.INGEST_DEDUP_FRAMES_REUSED,
            "frame-stack slots served by dedup back-references instead "
            "of wire bytes")
        self._tm_dedup_bytes = reg.counter(
            tmc.INGEST_DEDUP_BYTES_SAVED,
            "wire bytes the dedup plane avoided vs the undeduped "
            "zero-copy layout")
        self._dedup_swept = (0, 0)
        self._dedup_retired = (0, 0)   # counters of replaced decoders
        self._tm_ring_dropped = reg.gauge(
            "dqn_transport_ring_dropped",
            "records the shm ring dropped (producer overrun)")
        self._tm_ring_pending = reg.gauge(
            "dqn_transport_ring_pending_bytes",
            "bytes queued in the shm ring awaiting drain")
        self._tm_record_age = reg.gauge(
            "dqn_ingest_last_record_age_seconds",
            "seconds since the last valid actor record")
        self._tm_stalls = reg.counter(
            "dqn_ingest_stalls_total", "watchdog-detected ingest stalls")
        self._tm_actor_restarts = reg.counter(
            "dqn_actor_restarts_total",
            "dead actor processes restarted by supervision")
        self._tm_degraded = reg.gauge(
            tmc.INGEST_DEGRADED,
            "1 while supervision sees at least half the actor fleet "
            "dead (degraded, not wedged — ISSUE 8)")
        self._degraded = False
        self._tm_actor_alive: Dict[int, object] = {}
        self._tm_episodes = reg.counter(
            "dqn_episodes_completed_total", "training episodes finished")
        # Learner-utilization config surface (ISSUE 6): which replay
        # ratio / batch width / actor dtype shaped this learner's rate.
        _ll = {"loop": "apex"}
        reg.gauge(tmc.LEARNER_REPLAY_RATIO,
                  "grad sub-steps per scanned train dispatch",
                  _ll).set(self.replay_ratio)
        reg.gauge(tmc.LEARNER_TRAIN_BATCH,
                  "effective (bucketed) train batch width",
                  _ll).set(self.train_batch)
        reg.gauge(tmc.LEARNER_ACTOR_DTYPE_INFO,
                  "1 for the active actor inference dtype",
                  {**_ll, "dtype": self.actor_dtype}).set(1)
        # Checkpoint/resume telemetry (ISSUE 12 satellite): replay-
        # snapshot save wall + bytes; resumes/refusals count at the
        # restore sites (docs/observability.md).
        self._tm_ckpt_save = reg.histogram(
            tmc.CHECKPOINT_SAVE_SECONDS,
            "replay-snapshot save wall (flushes + npz write)", _ll)
        self._tm_ckpt_bytes = reg.counter(
            tmc.CHECKPOINT_BYTES,
            "checkpoint bytes written (replay snapshot)", _ll)
        reg.gauge(tmc.CHECKPOINT_SHARDS_SAVED,
                  "replay shards carried by each snapshot",
                  _ll).set(getattr(self.replay, "num_shards", 1))
        # None until the FIRST mirror exists: construction->first-refresh
        # spans the jit compile and is not mirror staleness — observing
        # it would park a false 60s+ outlier in the triage histogram.
        self._last_param_refresh = None

    def _dedup_totals(self):
        """(frames_reused, bytes_saved) summed over every LIVE dedup
        decoder plus the retired accumulator — a re-hello replaces an
        actor's decoder with zeroed counters, so the old one's totals
        fold into ``_dedup_retired`` first (_validate_hello); keeping
        the sum monotone is what lets the sweep emit deltas safely."""
        frames, saved = self._dedup_retired
        for dec in self._decoders.values():
            frames += getattr(dec, "frames_reused", 0)
            saved += getattr(dec, "bytes_saved", 0)
        return frames, saved

    def _sweep_dedup_counters(self):
        frames, saved = self._dedup_totals()
        seen_f, seen_b = self._dedup_swept
        if frames > seen_f:
            self._tm_dedup_frames.inc(frames - seen_f)
        if saved > seen_b:
            self._tm_dedup_bytes.inc(saved - seen_b)
        self._dedup_swept = (frames, saved)

    def _actor_alive_gauge(self, actor_id: int):
        g = self._tm_actor_alive.get(actor_id)
        if g is None:
            g = get_registry().gauge(
                "dqn_actor_alive", "1 while the actor process is alive",
                labels={"actor": str(actor_id)})
            self._tm_actor_alive[actor_id] = g
        return g

    def _count_device_call(self, kind: str,
                           rows: Optional[int] = None) -> None:
        """One dispatched device program of ``kind`` (act / fused /
        bootstrap / train). ``rows`` feeds the fan-in histogram for the
        act-path dispatches."""
        self.device_calls[kind] = self.device_calls.get(kind, 0) + 1
        c = self._tm_device_calls.get(kind)
        if c is None:
            c = get_registry().counter(
                tmc.SERVICE_DEVICE_CALLS,
                "device programs dispatched by the service loop",
                labels={"call": kind})
            self._tm_device_calls[kind] = c
        c.inc()
        if rows is not None:
            self._tm_fanin.observe(float(rows))

    def _step_specs(self, axis: str):
        """(data_specs, metric_specs) PartitionSpecs for the train step:
        the ONE shared spec set in parallel/learner.py (the fused path's
        spec idiom), so the apex, host-replay and multi-host learners
        cannot drift apart."""
        from dist_dqn_tpu.parallel.learner import train_step_specs

        return train_step_specs(axis, recurrent=self.recurrent)

    def _learner_mesh(self):
        """The local learner dp mesh (first ``n_learners`` devices)."""
        from dist_dqn_tpu.parallel import make_mesh

        if self._mesh is None:
            self._mesh = make_mesh(
                devices=self.jax.devices()[:self.n_learners])
        return self._mesh

    def _shard_train_step(self, train_step, axis: str):
        """Lift the per-device train step onto the local learner mesh:
        batch leaves shard over ``axis``, learner state replicates, and the
        pmean inside the step (agents/) allreduces gradients over ICI."""
        from dist_dqn_tpu.parallel.learner import make_sharded_train_step

        data_specs, metric_specs = self._step_specs(axis)
        return make_sharded_train_step(train_step, self._learner_mesh(),
                                       data_specs, metric_specs)

    # -- actor lifecycle ----------------------------------------------------
    def _spawn_one(self, actor_id: int):
        """(Re)start one actor process; returns the Process handle."""
        import multiprocessing as mp

        from dist_dqn_tpu.actors.actor import run_actor, run_remote_actor
        ctx = mp.get_context("spawn")
        if actor_id < self.rt.num_actors:
            # feeder:<spec> host envs swap the rollout actor for the
            # in-RAM trajectory feeder (actors/feeder.py) — identical
            # spawn contract, no emulator in the loop. Feeders take the
            # slot-batching knob (unthrottled producers); actors take
            # the dedup capability switch (lock-step, batch 1).
            target = run_actor
            kwargs = {"transport": self.rt.transport,
                      "dedup": self.rt.wire_dedup}
            if self.rt.host_env.startswith("feeder:"):
                from dist_dqn_tpu.actors.feeder import run_feeder
                target = run_feeder
                kwargs = {"transport": self.rt.transport,
                          "shm_batch": self.rt.shm_batch}
            p = ctx.Process(
                target=target,
                args=(actor_id, self.rt.host_env, self.rt.envs_per_actor,
                      1000 + 7 * actor_id, f"req_{self.run_id}",
                      f"act_{self.run_id}_{actor_id}", self.stop_path),
                kwargs=kwargs,
                daemon=True)
        else:
            p = ctx.Process(
                target=run_remote_actor,
                args=(actor_id, self.rt.host_env, self.rt.envs_per_actor,
                      1000 + 7 * actor_id,
                      ("127.0.0.1", self.tcp_address[1]), self.stop_path),
                kwargs={"transport": self.rt.transport,
                        "dedup": self.rt.wire_dedup},
                daemon=True)
        p.start()
        return p

    def spawn_actors(self):
        self.procs: Dict[int, object] = {}
        for i in range(self.rt.num_actors):
            self.procs[i] = self._spawn_one(i)
        # Locally-spawned remote actors (single-host stand-in for DCN
        # workers; real ones run actors/remote.py on other hosts).
        if self.rt.spawn_remote_actors:
            for j in range(self.rt.num_remote_actors):
                actor_id = self.rt.num_actors + j
                self.procs[actor_id] = self._spawn_one(actor_id)

    def supervise_actors(self):
        """Failure handling for actor churn (SURVEY.md §5): actors are
        stateless workers, so a dead process is simply restarted — its
        fresh hello resets the assembly lanes and recurrent carry, and the
        learner never notices beyond a briefly idle lane.

        Fleet-decimation alarm (ISSUE 8): restarts handle ONE dead
        actor; half the fleet dead at once (bad image rollout, host
        OOM-killer sweep, preemption wave) is a different animal — the
        run degrades (ingest rate collapses, the learner idles at its
        cadence target) rather than wedging, and this alarm is what
        says so: ``dqn_ingest_degraded`` = 1 plus one log line per
        degradation episode, cleared when the fleet recovers."""
        dead = 0
        for actor_id, p in list(self.procs.items()):
            alive = p.is_alive()
            self._actor_alive_gauge(actor_id).set(float(alive))
            if not alive:
                dead += 1
                self.actor_restarts += 1
                self._tm_actor_restarts.inc()
                self.procs[actor_id] = self._spawn_one(actor_id)
        fleet = max(len(self.procs), 1)
        decimated = fleet > 1 and dead * 2 >= fleet
        self._tm_degraded.set(float(decimated))
        if decimated and not self._degraded:
            self._degraded = True
            self.log.log_fn(json.dumps(
                {"ingest_degraded": True, "dead_actors": dead,
                 "fleet": fleet, "env_steps": self.env_steps}))
            self.tracer.instant("ingest_degraded", dead=dead, fleet=fleet)
        elif not decimated and self._degraded:
            self._degraded = False
            self.log.log_fn(json.dumps(
                {"ingest_degraded": False, "env_steps": self.env_steps}))

    def shutdown(self):
        if self._shard_sampler is not None:
            self._shard_sampler.close()
        self._sweep_dedup_counters()   # final partial-period deltas
        with open(self.stop_path, "w") as f:
            f.write("stop")
        for p in getattr(self, "procs", {}).values():
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
        if self.tcp_server is not None:
            self.tcp_server.close()
        if self.telemetry_server is not None:
            self.telemetry_server.close()
        self.req_ring.unlink()
        for ring in self._zc_rings.values():
            ring.close()
            ring.unlink()
        for b in self.act_boxes:
            b.unlink()
        try:
            os.unlink(self.stop_path)
        except OSError:
            pass

    # -- core loop ----------------------------------------------------------
    def _ensure_learner(self, obs_example: np.ndarray):
        if self.state is None:
            jax = self.jax
            self._rng = jax.random.PRNGKey(self.cfg.seed)
            self._rng, k = jax.random.split(self._rng)
            self.state = self._init_learner(k, self.jnp.asarray(obs_example))
            if self.rt.checkpoint_dir:
                from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer
                self._ckpt = TrainCheckpointer(
                    self.rt.checkpoint_dir,
                    save_every_frames=self.rt.save_every_steps)
                restored = self._ckpt.restore_latest(self.state)
                if restored is not None:
                    # Resume the cursor too: the run continues toward the
                    # same total_env_steps (replay refills from live actors).
                    resumed, self.state = restored
                    if self.distributed:
                        # The saved cursor is the GLOBAL agreed count:
                        # local env_steps restarts at 0 and the offset
                        # folds into the agreement result instead (else
                        # each host's copy would be psummed N times).
                        self._resume_global = resumed
                        self.global_env_steps = resumed
                    else:
                        self.env_steps = resumed
                    if self.rt.eval_every_steps:
                        # Next eval is one full period out, not immediately.
                        self._next_eval = resumed + self.rt.eval_every_steps
                    self.log.log_fn(
                        f'{{"resumed_at_env_steps": {resumed}}}')
                    if self.rt.checkpoint_replay:
                        self._load_replay_snapshot()
            self._refresh_host_params()

    def _refresh_host_params(self):
        """Local numpy mirror of the replicated params for the process-
        local programs — act, eval, priority bootstraps must not feed
        GLOBAL mesh arrays into single-process jits. The target net is
        mirrored only where something reads it (the feed-forward priority
        bootstrap); the R2D2 path would otherwise D2H-copy it every train
        burst for nothing."""
        if self.distributed and self.state is not None:
            target = (self._mh.host_copy(self.state.target_params)
                      if self._prio_fn is not None else None)
            self._host_params = (self._mh.host_copy(self.state.params),
                                 target)
            # Param-broadcast staleness: how old the previous mirror got
            # before this refresh replaced it — the act/eval/bootstrap
            # programs ran on params at most this stale.
            now = time.perf_counter()
            if self._last_param_refresh is not None:
                self._tm_param_staleness.observe(
                    now - self._last_param_refresh)
            self._last_param_refresh = now

    @property
    def _policy_params(self):
        return self._host_params[0] if self.distributed \
            else self.state.params

    @property
    def _target_policy_params(self):
        return self._host_params[1] if self.distributed \
            else self.state.target_params

    def _reply_actions(self, actor: int, obs: np.ndarray, t: int) -> int:
        """Queue one actor's act request; the device call happens batched in
        ``_flush_act_queue`` at the end of the drain burst. Returns the
        request id — the key under which this request's flush will file
        its q_max plane (the bootstrap inputs for transitions emitted by
        the record that carried ``obs``)."""
        self._req_seq += 1
        self._act_queue.append((actor, obs, t, self._req_seq))
        return self._req_seq

    def _flush_act_queue(self):
        """Sebulba-style batched inference: ONE device call serves every
        actor that reported this burst.

        Per-record inference pays a full dispatch per actor — at
        hundreds of actors that latency, not compute, caps ingestion.
        Queued rows concatenate
        into a single [R, ...] act call (per-row epsilon from the Ape-X
        ladder broadcasts inside the act fn) padded up to a power-of-two
        row bucket so XLA compiles O(log actors) variants, then actions
        split back out to each actor's reply channel.
        """
        if not self._act_queue:
            return
        jax, jnp = self.jax, self.jnp
        burst = self._act_queue
        self._act_queue = []
        # Shared pow2 packing (actors/act_dispatch.py): the same bucket
        # rule + zero-padding the serving micro-batcher dispatches with.
        obs_cat, eps, rows, total = pack_act_rows(
            [obs for _, obs, _, _ in burst],
            [self.actor_eps[actor] for actor, _, _, _ in burst])
        padded = obs_cat.shape[0]
        self._rng, k = jax.random.split(self._rng)
        # Fused fast path (ISSUE 2): when a bootstrap batch is pending,
        # ride it along with this burst's act in ONE dispatched program
        # instead of two back-to-back device calls.
        boot = (self._pop_boot_batch()
                if (self._fused is not None and not self.recurrent)
                else None)
        with self.tracer.span("act.batched", actors=len(burst), rows=total,
                              fused_bootstrap=boot is not None):
            if self.recurrent:
                cs, hs = [], []
                for (actor, obs, _, _), r in zip(burst, rows):
                    carry = self._carry[actor] or self.net.initial_state(r)
                    c0 = np.asarray(carry[0], np.float32)
                    h0 = np.asarray(carry[1], np.float32)
                    # The assembler stores the carry ENTERING this step.
                    self._prev_carry[actor] = (c0, h0)
                    cs.append(c0)
                    hs.append(h0)
                lstm = cs[0].shape[-1]
                pad = np.zeros((padded - total, lstm), np.float32)
                carry_cat = (jnp.asarray(np.concatenate(cs + [pad])),
                             jnp.asarray(np.concatenate(hs + [pad])))
                carry_new, actions, q_sel, q_max = self._act(
                    self._policy_params, carry_cat, jnp.asarray(obs_cat), k,
                    jnp.asarray(eps))
                c_np = np.asarray(carry_new[0], np.float32)
                h_np = np.asarray(carry_new[1], np.float32)
                qs_np = np.asarray(q_sel, np.float32)
                qm_np = np.asarray(q_max, np.float32)
                self._count_device_call("act", rows=total)
            elif boot is not None:
                b_batch, b_items, b_count = boot
                actions, prios = self._fused(
                    self._policy_params, self._target_policy_params,
                    jnp.asarray(obs_cat), k, jnp.asarray(eps),
                    jnp.asarray(b_batch["obs"]),
                    jnp.asarray(b_batch["action"]),
                    jnp.asarray(b_batch["reward"]),
                    jnp.asarray(b_batch["discount"]),
                    jnp.asarray(b_batch["next_obs"]))
                # Same pipelined-insert path as the standalone bootstrap:
                # the batch's priorities materialize on a later pass.
                self._boot_inflight.append((prios, b_items, b_count))
                self._count_device_call("fused_act_bootstrap", rows=total)
            elif self._act_q is not None:
                # Actor-priority path (ISSUE 9): ONE dispatched program
                # per pass — act + the q planes that ride the replies.
                actions, q_sel, q_max = self._act_q(
                    self._policy_params, jnp.asarray(obs_cat), k,
                    jnp.asarray(eps))
                qs_np = np.asarray(q_sel, np.float32)
                qm_np = np.asarray(q_max, np.float32)
                self._count_device_call("act", rows=total)
            else:
                actions = self._act(self._policy_params, jnp.asarray(obs_cat),
                                    k, jnp.asarray(eps))
                self._count_device_call("act", rows=total)
            acts_np = np.asarray(actions, np.int32)
        prio = not self.recurrent and self._act_q is not None
        off = 0
        for (actor, obs, t, rid), r in zip(burst, rows):
            sl = slice(off, off + r)
            off += r
            if self.recurrent:
                self._carry[actor] = (c_np[sl], h_np[sl])
                self._prev_q[actor] = (qs_np[sl], qm_np[sl])
            self._prev_actions[actor] = acts_np[sl]
            self._prev_obs[actor] = obs
            q_rows = None
            if prio:
                # File this request's q_max under its id: transitions
                # the SAME record emitted bootstrap from these planes
                # (their bootstrap obs IS the obs acted on here).
                q_rows = (qs_np[sl], qm_np[sl])
                self._flush_q[rid] = qm_np[sl]
                self._last_flush_q[actor] = qm_np[sl]
            if actor in self._decoders:
                # Zero-copy reply: actions (+ q planes on the prio
                # path) with the sticky shard id stamped — the actor
                # echoes both on its next frame.
                payload = self._ingest.encode_reply(
                    acts_np[sl], actor=actor, t=t,
                    shard=self.router.shard_for(actor),
                    q_sel=q_rows[0] if q_rows else None,
                    q_max=q_rows[1] if q_rows else None,
                    params_version=int(self.grad_steps))
            else:
                payload = encode_arrays({"action": acts_np[sl]})
            if actor < self.rt.num_actors:
                self.act_boxes[actor].write(payload, version=t + 1)
            else:
                conn = self._actor_conn.get(actor)
                if conn is not None:
                    self.tcp_server.send(conn, payload)

    def _record_seen(self):
        """Feed the stall watchdog — called only once a record has passed
        every validation gate, so a flood of malformed records (capped bad-
        record logging) cannot mask a genuine ingest stall."""
        self._last_record = time.perf_counter()
        self._stall_warned = False

    def _watchdog(self, now: float):
        """Ingest-stall detection: actors can wedge without dying (remote
        host gone, transport stuck); supervision only catches exits. Warn
        once per stall with the silence duration; any record clears it."""
        if not self.rt.stall_warn_s:
            return
        silent = now - self._last_record
        if silent >= self.rt.stall_warn_s and not self._stall_warned:
            self._stall_warned = True
            self._tm_stalls.inc()
            self.log.log_fn(f'{{"ingest_stalled_s": {silent:.1f}, '
                            f'"env_steps": {self.env_steps}}}')
            self.tracer.instant("ingest_stalled", silent_s=round(silent, 1))

    class HelloRejectedError(ValueError):
        """Protocol/transport/schema drift detected at connect — the
        one record-level error that must stay LOUD on the same-host
        path (a drifted local build is a deploy bug, not wire churn):
        the shm drain's error boundary re-raises this type."""

    def _hello_reject(self, detail: str, conn_id: Optional[int]):
        """Protocol/transport drift fails LOUDLY at connect (ISSUE 9
        satellite): TCP peers get a structured NACK (they raise and
        exit rather than retry-hammering); the raise below surfaces as
        one counted bad record on TCP and as a hard service error on
        the same-host path (a drifted local build is a deploy bug)."""
        if conn_id is not None and self.tcp_server is not None:
            from dist_dqn_tpu.actors.transport import \
                PROTO_MISMATCH_NACK_KIND
            self.tcp_server.send(conn_id, encode_arrays(
                {}, {"kind": PROTO_MISMATCH_NACK_KIND, "detail": detail}))
        raise self.HelloRejectedError(f"hello rejected: {detail}")

    def _validate_hello(self, actor: int, meta: Dict,
                        conn_id: Optional[int]) -> None:
        """Explicit protocol-version + transport-mode negotiation. A
        version mismatch used to be undetectable until it surfaced as
        CRC/desync noise mid-stream; now it is one loud connect error.
        Zero-copy hellos also register the actor's declared schema —
        the layout every later frame of the session is decoded with."""
        from dist_dqn_tpu.ingest import PROTOCOL_VERSION, StepDecoder, \
            TrajectorySchema
        proto = meta.get("proto")
        if proto is not None and int(proto) != PROTOCOL_VERSION:
            self._hello_reject(
                f"actor {actor} speaks wire protocol {proto}, service "
                f"speaks {PROTOCOL_VERSION} — upgrade in lockstep",
                conn_id)
        peer_transport = meta.get("transport", "legacy")
        if peer_transport == "zerocopy" and self.rt.transport != "zerocopy":
            self._hello_reject(
                f"actor {actor} wants zerocopy transport but the "
                f"service runs --transport legacy", conn_id)
        if self.rt.ingest_shards > 1 and not self.recurrent \
                and peer_transport != "zerocopy":
            # Sharded-store placement needs per-actor insert attribution
            # (ISSUE 10): a legacy-codec actor's transitions would take
            # the concatenated bootstrap path, whose unattributed insert
            # the sharded store rejects — failing HERE, at connect, is
            # one rejected hello instead of a learner-loop crash on the
            # actor's first drained window.
            self._hello_reject(
                f"actor {actor} speaks the legacy codec but the service "
                f"runs ingest_shards={self.rt.ingest_shards}: sharded "
                "placement needs the zerocopy actor-priority path — "
                "upgrade the actor, or run ingest_shards=1", conn_id)
        if peer_transport == "zerocopy":
            if "schema" not in meta:
                self._hello_reject(
                    f"zerocopy hello from actor {actor} without a "
                    f"trajectory schema", conn_id)
            schema = TrajectorySchema.from_dict(meta["schema"])
            # Canonical-layout gate: the declared schema must be
            # exactly step_schema over its own obs field — a peer
            # declaring extra/renamed/re-typed fields would decode but
            # mis-feed every downstream consumer; reject at connect.
            from dist_dqn_tpu.ingest import step_schema
            obs_field = schema.fields[0] if schema.fields else None
            if (obs_field is None or obs_field.name != "obs"
                    or schema != step_schema(obs_field.shape,
                                             obs_field.dtype,
                                             schema.lanes)):
                self._hello_reject(
                    f"actor {actor} declared a non-canonical step "
                    f"schema {schema.to_dict()}", conn_id)
            # Frame-dedup capability (ISSUE 14): declared per actor at
            # hello — the service is always dedup-CAPABLE, so mixed
            # fleets (dedup pixel actors + plain vector actors + legacy
            # JSON actors) coexist; only the DECLARED layout must be
            # internally consistent, or the hello rejects.
            old_dec = self._decoders.get(actor)
            if old_dec is not None and getattr(old_dec, "bytes_saved",
                                               None) is not None:
                # Retire the replaced decoder's savings so the
                # monotone-total sweep cannot lose them (re-hello
                # rebuilds decoders with zeroed counters).
                rf, rb = self._dedup_retired
                self._dedup_retired = (rf + old_dec.frames_reused,
                                       rb + old_dec.bytes_saved)
            dedup_fs = int(meta.get("dedup", 0) or 0)
            if dedup_fs and not self.rt.wire_dedup:
                # --no-wire-dedup must hold fleet-wide (it is the
                # dedup-off A/B arm): an EXTERNAL worker that did not
                # get its own --no-wire-dedup is told to re-hello
                # plain rather than silently contaminating the arm.
                self._hello_reject(
                    f"actor {actor} declared frame dedup but the "
                    f"service runs --no-wire-dedup — restart the "
                    f"worker with --no-wire-dedup", conn_id)
            if dedup_fs:
                from dist_dqn_tpu.ingest import (DedupStepDecoder,
                                                 validate_dedup_stack)
                try:
                    validate_dedup_stack(schema, dedup_fs)
                except ValueError as e:
                    self._hello_reject(
                        f"actor {actor} declared frame dedup the "
                        f"schema cannot carry: {e}", conn_id)
                # History sizing: decoded stacks are VIEWS into the
                # rolling frame ring; the deepest holder is the n-step
                # (or sequence) assembler, so the ring must outlive its
                # maximum window by a margin. Sized for the WORST case
                # of every record being a boundary (general) record,
                # each of which consumes frame_stack slots (a reseed),
                # not the canonical path's one.
                hold = (self.seq_len + (self.cfg.replay.sequence_stride
                                        or self.cfg.replay.unroll_length)
                        if self.recurrent else self.cfg.learner.n_step)
                self._decoders[actor] = DedupStepDecoder(
                    schema, dedup_fs, t0=int(meta["t"]),
                    history=max(32, (hold + 4) * dedup_fs + 2 * dedup_fs))
            else:
                self._decoders[actor] = StepDecoder(schema)
            asm = self.assemblers[actor]
            cur_lanes = getattr(asm, "num_lanes", None) \
                or len(getattr(asm, "lanes", ()))
            if self.actor_prio and (
                    not getattr(asm, "with_q", False)
                    or cur_lanes != schema.lanes):
                # q planes ride this actor's frames: thread them
                # through a q-aware assembler sized to the DECLARED
                # lane count. Swapped only on first negotiation (or a
                # lane-count change) — a re-hello must not discard the
                # previous assembler's drained-but-uninserted output.
                self.assemblers[actor] = NStepAssembler(
                    schema.lanes, self.cfg.learner.n_step,
                    self.cfg.learner.gamma, with_q=True)
            elif not self.actor_prio and cur_lanes != schema.lanes:
                # No-priority/recurrent modes: the pre-built assembler
                # was sized envs_per_actor — an external worker with a
                # different lane count would silently truncate (or
                # crash) lane iteration; rebuild at the declared width.
                self.assemblers[actor] = self._asm_factory(schema.lanes)

    def _handle_record(self, payload: bytes, conn_id: Optional[int] = None,
                       transport_kind: str = "legacy"):
        ingest = self._ingest
        if ingest.is_zc(payload):
            # Zero-copy record: schema negotiated at hello, payload is
            # raw array bytes — decode to views, no JSON, no copies.
            try:
                hdr = ingest.peek_header(payload)
                dec = self._decoders.get(hdr["actor"])
                if dec is None:
                    raise ingest.WireFormatError(
                        f"zero-copy record for actor {hdr['actor']} "
                        f"before a schema hello")
                arrays, meta = dec.decode(payload, hdr=hdr)
            except ingest.WireFormatError as e:
                self.router.decode_error(type(e).__name__)
                if conn_id is not None and self.tcp_server is not None:
                    # Same contract as the CRC gate one layer down
                    # (transport.py): the lock-step sender's action
                    # will never come — NACK so it reconnects NOW
                    # instead of waiting out its stall bound.
                    from dist_dqn_tpu.actors.transport import \
                        CORRUPT_FRAME_NACK_KIND
                    self.tcp_server.send(conn_id, encode_arrays(
                        {}, {"kind": CORRUPT_FRAME_NACK_KIND}))
                raise
        else:
            arrays, meta = decode_arrays(payload)
            # dqn_ingest_* labels identify the CODEC, not the channel
            # (collectors.py): a JSON-codec record over TCP is the
            # legacy arm of the A/B, not zero-copy wire traffic.
            transport_kind = "legacy"
        actor, t = int(meta["actor"]), int(meta["t"])
        if conn_id is not None:
            # Remote actor: only the remote id range is valid over TCP (a
            # misconfigured worker must not feed a LOCAL actor's lanes),
            # and replies route to the connection its latest record
            # arrived on (survives reconnects after churn).
            if not self.rt.num_actors <= actor < self.total_actors:
                raise ValueError(f"TCP record for out-of-range actor id "
                                 f"{actor}")
            self._actor_conn[actor] = conn_id
        elif not 0 <= actor < self.rt.num_actors:
            raise ValueError(f"shm record for out-of-range actor id {actor}")
        # Validate observation shape/dtype HERE, inside the per-record
        # error boundary: a malformed remote record must surface as one
        # bad_records increment, not as a concatenate error later in the
        # batched act flush that would take down the whole service.
        for key in ("obs", "next_obs"):
            arr = arrays.get(key)
            if arr is None:
                continue
            if self._obs_spec is None:
                self._obs_spec = (arr.shape[1:], arr.dtype)
            elif (arr.shape[1:] != self._obs_spec[0]
                  or arr.dtype != self._obs_spec[1]):
                raise ValueError(
                    f"actor {actor} {key} {arr.shape[1:]}/{arr.dtype} does "
                    f"not match the session spec {self._obs_spec}")
        # Ingest accounting (ISSUE 9): bytes/records per transport and
        # the sticky shard this actor's stream lands in — only for
        # records that passed every validation gate above.
        self.router.record(actor, len(payload), transport_kind)
        if meta["kind"] == "hello":
            self._validate_hello(actor, meta, conn_id)
            self._ensure_learner(arrays["obs"][0])
            self._record_seen()
            if self._prev_obs[actor] is not None:
                # Re-hello = reconnect: the step stream has a gap, so drop
                # partial assembly windows (and the recurrent carry — the
                # next act restarts it from zeros) rather than bridging it.
                # The partial episode-return accumulator goes with them: a
                # restarted actor begins fresh episodes, and folding the
                # aborted episode's partial return into the next completed
                # one would contaminate the learning signal.
                self.assemblers[actor].reset()
                self._ep_accum.pop(actor, None)
                if self.recurrent:
                    self._carry[actor] = None
            self._reply_actions(actor, arrays["obs"], t)
            return
        if self._prev_obs[actor] is None:
            raise ValueError(f"step record for actor {actor} before hello")
        self._record_seen()
        # step record: completes (prev_obs, prev_action) -> transition.
        terminated = arrays["terminated"].astype(bool)
        truncated = arrays["truncated"].astype(bool)
        self._track_episode_returns(actor, arrays["reward"], terminated,
                                    truncated)
        if self.recurrent:
            self.assemblers[actor].step(
                self._prev_obs[actor], self._prev_actions[actor],
                arrays["reward"], terminated, truncated,
                *self._prev_carry[actor], *self._prev_q[actor])
            # Zero the carry for lanes whose episode just ended, BEFORE the
            # next act (the incoming obs rows are post-reset there).
            done = np.logical_or(terminated, truncated)
            if done.any():
                keep = (~done).astype(np.float32)[:, None]
                c = self._carry[actor]
                self._carry[actor] = (c[0] * keep, c[1] * keep)
        else:
            asm = self.assemblers[actor]
            if getattr(asm, "with_q", False):
                q_sel = meta.get("q_sel")
                if q_sel is None:
                    raise ValueError(
                        f"actor {actor} negotiated actor-side "
                        f"priorities but shipped a frame without q "
                        f"planes")
                asm.step(self._prev_obs[actor], self._prev_actions[actor],
                         arrays["reward"], terminated, truncated,
                         arrays["next_obs"], q_sel=q_sel,
                         q_max=meta["q_max"])
            else:
                asm.step(self._prev_obs[actor], self._prev_actions[actor],
                         arrays["reward"], terminated, truncated,
                         arrays["next_obs"])
        self.env_steps += arrays["reward"].shape[0]
        self._tm_env_steps.inc(arrays["reward"].shape[0])
        if not self.recurrent and getattr(self.assemblers[actor],
                                          "with_q", False):
            # Actor-priority path: this record's emissions bootstrap
            # from the obs the act request below will flush q planes
            # for — park them keyed by that request id; insertion
            # happens right after the flush (_insert_actor_prio).
            rid = self._reply_actions(actor, arrays["obs"], t)
            emitted = self.assemblers[actor].drain()
            if emitted is not None:
                self._stamp_lineage(emitted, meta)
                self._prio_await.append((actor, rid, emitted))
            return
        emitted = self.assemblers[actor].drain()
        if emitted is not None:
            if self.recurrent:
                # Seed with the R2D2 actor-side rule: TD magnitudes from
                # the inference-time Q planes the assembler recorded (no
                # extra device passes, unlike a burn-in unroll per insert).
                from dist_dqn_tpu.actors.assembler import \
                    initial_sequence_priorities
                prios = initial_sequence_priorities(
                    emitted, self.cfg.replay.burn_in,
                    self.cfg.replay.unroll_length, self.cfg.learner.gamma,
                    self.cfg.replay.priority_mix,
                    self.cfg.learner.value_rescale)
                emitted.pop("q_sel")
                emitted.pop("q_max")
                self.replay.add(emitted, priorities=prios,
                                shard=self.router.shard_for(actor))
            else:
                self._stamp_lineage(emitted, meta)
                self._pending.append(emitted)
                self._pending_count += emitted["action"].shape[0]
        self._reply_actions(actor, arrays["obs"], t)

    def _stamp_lineage(self, emitted: Dict, meta: Dict) -> None:
        """Attach the record's wire lineage stamp (ISSUE 16) to every
        transition it emitted. Record granularity: an n-step window
        spans at most n_step actor steps, so the completing record's
        birth time / acting-params version bound the whole window —
        plenty for a staleness histogram. The replay stores are
        field-generic (add/sample/checkpoint/reshard carry any key),
        and the train-arg selection names its fields explicitly, so the
        extra keys ride to sample time and never reach the device."""
        bt = meta.get("birth_time")
        if bt is None:
            return
        n = emitted["action"].shape[0]
        emitted["lineage_birth_time"] = np.full(n, bt, np.float64)
        emitted["lineage_params_version"] = np.full(
            n, int(meta.get("params_version", 0)), np.int64)

    def _insert_actor_prio(self) -> None:
        """Insert transitions whose priorities came off the wire
        (ISSUE 9 piece 3): the frame shipped ``q_sel`` (start of each
        n-step window), this pass's act flush produced ``q_max`` of the
        bootstrap obs, and the fold

            p = |q_start - (R + discount * q_max[boot_lane])|

        runs in pure numpy — the priority twin of the R2D2 seeding
        rule, and the reason the zerocopy ingest pass dispatches ZERO
        bootstrap programs. Terminal windows carry discount 0, so their
        bootstrap term vanishes exactly as in the device ``prio_fn``."""
        if not self._prio_await:
            self._flush_q.clear()
            return
        pend, self._prio_await = self._prio_await, []
        for actor, rid, emitted in pend:
            q_max = self._flush_q.get(rid)
            if q_max is None:
                # Shutdown edge: the loop ended between drain and
                # flush — fall back to the actor's last known planes
                # (one record's priorities slightly stale, not lost).
                q_max = self._last_flush_q.get(actor)
            q_start = emitted.pop("q_start")
            boot_lane = emitted.pop("boot_lane")
            boot_q = emitted.pop("boot_q")
            boot = (q_max[boot_lane] if q_max is not None
                    else np.zeros_like(q_start))
            # Episode-end windows pinned their own in-band bootstrap q
            # (the flush q below was computed on the POST-reset obs —
            # the wrong episode for them); within-episode windows
            # (boot_q NaN) bootstrap from this flush exactly.
            boot = np.where(np.isnan(boot_q), boot, boot_q)
            prios = np.abs(q_start
                           - (emitted["reward"] + emitted["discount"]
                              * boot))
            with self.tracer.span("priority.actor_insert",
                                  count=int(prios.shape[0])):
                self.replay.add(emitted, priorities=prios,
                                shard=self.router.shard_for(actor))
            self._tm_actor_prio.inc(int(prios.shape[0]))
        self._flush_q.clear()

    def _pop_boot_batch(self, force: bool = False):
        """Take up to ``_PRIO_MAX_ROWS`` pending transitions for one
        batched bootstrap dispatch -> (padded batch, true items, count),
        or None below the ``_PRIO_CHUNK`` threshold (sub-chunk
        remainders keep accumulating unless forced). The batch pads to
        one of two row buckets (``_PRIO_CHUNK`` / ``_PRIO_MAX_ROWS`` —
        see the constant's comment) by repeating the last row (its
        priority is computed then discarded at insert)."""
        if self._pending_count == 0:
            return None
        if not force and self._pending_count < _PRIO_CHUNK:
            return None
        # One concatenation per backlog: a stored single-dict remainder
        # is reused as-is and sliced into VIEWS, so draining a B-row
        # backlog copies O(B) bytes total, not O(B^2/_PRIO_MAX_ROWS).
        if len(self._pending) == 1:
            cat = self._pending[0]
        else:
            cat = {k: np.concatenate([p[k] for p in self._pending])
                   for k in self._pending[0]}
        n = cat["action"].shape[0]
        take = min(n, _PRIO_MAX_ROWS)
        if n > take:
            self._pending = [{k: v[take:] for k, v in cat.items()}]
            self._pending_count = n - take
        else:
            self._pending, self._pending_count = [], 0
        items = {k: v[:take] for k, v in cat.items()}
        padded = _PRIO_CHUNK if take <= _PRIO_CHUNK else _PRIO_MAX_ROWS
        if padded != take:
            pad = padded - take
            batch = {k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                     for k, v in items.items()}
        else:
            batch = items
        return batch, items, take

    def _flush_pending(self, force: bool = False):
        """Compute initial priorities on-device and insert into the shard.

        The bootstrap is PIPELINED like the train steps: each chunk's
        jitted |TD| program is dispatched asynchronously and its result
        is materialized on a later pass, when the device has likely
        finished. JAX's async dispatch means ``np.asarray`` blocks on
        the device round-trip PER CHUNK, which a synchronous bootstrap
        pays on the ingestion critical path. Items therefore
        enter the shard up to a few chunks late — a beat of sampling
        delay with no semantic effect.
        """
        self._drain_bootstraps(force)
        if self._pending_count == 0:
            return
        if self.rt.fused_ingest and self._prio_fn is not None:
            # Fast path: whatever the fused act dispatch did not take
            # this pass goes out in power-of-two-bucketed batches of up
            # to _PRIO_MAX_ROWS — one device call per ~8 legacy chunks.
            while True:
                popped = self._pop_boot_batch(force)
                if popped is None:
                    break
                batch, items, count = popped
                with self.tracer.span("priority.bootstrap.dispatch",
                                      count=count,
                                      rows=batch["action"].shape[0]):
                    prios = self._prio_fn(
                        self._policy_params, self._target_policy_params,
                        *(self.jnp.asarray(batch[k])
                          for k in ("obs", "action", "reward",
                                    "discount", "next_obs")))
                    self._count_device_call("bootstrap")
                self._boot_inflight.append((prios, items, count))
        else:
            if not force and self._pending_count < _PRIO_CHUNK:
                return
            cat = {k: np.concatenate([p[k] for p in self._pending])
                   for k in self._pending[0]}
            self._pending, self._pending_count = [], 0
            n = cat["action"].shape[0]
            with self.tracer.span("priority.bootstrap.dispatch", count=n):
                self._dispatch_bootstraps(cat, n)
        if force:
            self._drain_bootstraps(True)

    def _dispatch_bootstraps(self, cat, n: int):
        jnp = self.jnp
        for lo in range(0, n, _PRIO_CHUNK):
            hi = min(lo + _PRIO_CHUNK, n)
            pad = _PRIO_CHUNK - (hi - lo)

            def pad_to(x):
                return np.concatenate([x[lo:hi], np.repeat(x[hi - 1:hi],
                                                           pad, axis=0)]) \
                    if pad else x[lo:hi]

            prios = self._prio_fn(
                self._policy_params, self._target_policy_params,
                jnp.asarray(pad_to(cat["obs"])),
                jnp.asarray(pad_to(cat["action"])),
                jnp.asarray(pad_to(cat["reward"])),
                jnp.asarray(pad_to(cat["discount"])),
                jnp.asarray(pad_to(cat["next_obs"])))
            self._count_device_call("bootstrap")
            self._boot_inflight.append(
                (prios, {k: v[lo:hi] for k, v in cat.items()}, hi - lo))

    def _drain_bootstraps(self, block: bool = False):
        """Insert chunks whose device priorities have materialized.

        Non-blocking by default (``is_ready`` probe where the runtime
        exposes it); the backlog is bounded — past ``pipeline_depth + 2``
        chunks the oldest is materialized blocking, so a busy device
        cannot grow an unbounded not-yet-inserted queue.
        """
        limit = self.rt.pipeline_depth + 2
        while self._boot_inflight:
            prios, items, count = self._boot_inflight[0]
            if not block and len(self._boot_inflight) <= limit:
                ready = getattr(prios, "is_ready", None)
                if ready is not None and not ready():
                    return
            self._boot_inflight.popleft()
            with self.tracer.span("priority.bootstrap.insert", count=count):
                self.replay.add(items,
                                priorities=np.asarray(prios)[:count])

    def _host_sequence_sample(self, items, weights):
        """Host [S, L, ...] arrays -> time-major numpy SequenceSample
        (the staging path uploads it as one pytree; the legacy path wraps
        it in jnp right after)."""
        from dist_dqn_tpu.types import SequenceSample

        def tm(x):  # [S, L, ...] -> [L, S, ...]
            return np.moveaxis(x, 0, 1)

        S = items["action"].shape[0]
        return SequenceSample(
            obs=tm(items["obs"]), action=tm(items["action"]),
            reward=tm(items["reward"]), done=tm(items["done"]),
            reset=tm(items["reset"]),
            start_state=(np.asarray(items["state_c"]),
                         np.asarray(items["state_h"])),
            weights=np.asarray(weights, np.float32),
            t_idx=np.zeros((S,), np.int32),     # host shard tracks its own
            b_idx=np.zeros((S,), np.int32))     # indices (idx from sample())

    def _sequence_sample(self, items, weights):
        """Host [S, L, ...] arrays -> time-major device SequenceSample."""
        return self.jax.tree.map(self.jnp.asarray,
                                 self._host_sequence_sample(items, weights))

    def _host_train_args(self, items, weights):
        """The train step's batch args as HOST numpy pytrees — what the
        double-buffered stager copies into its pinned buffers."""
        from dist_dqn_tpu.types import Transition
        if self.recurrent:
            return (self._host_sequence_sample(items, weights),)
        return (Transition(obs=items["obs"], action=items["action"],
                           reward=items["reward"],
                           discount=items["discount"],
                           next_obs=items["next_obs"]),
                np.asarray(weights, np.float32))

    def _sample_replay(self, batch_size: int, beta: float):
        """One replay draw -> (items, idx, weights, generations):
        through the ingest-side per-shard sampling service when armed
        (the learner thread then only pops a pre-packed batch whose
        generations were snapshotted at draw time, under the shard
        locks), else the facade's inline draw."""
        if self._shard_sampler is not None:
            out = self._shard_sampler.sample(batch_size, beta)
        else:
            items, idx, weights = self.replay.sample(batch_size, beta)
            out = items, idx, weights, self.replay.generation(idx)
            if self.rt.device_sampling:
                # Dispatch-budget accounting (ISSUE 18 via PR 2's
                # device_calls): one sample dispatch per shard per
                # train event — counted from the samplers' own dispatch
                # counters so the pin covers exactly what ran.
                seen = (self.replay.device_sample_dispatches
                        if hasattr(self.replay,
                                   "device_sample_dispatches")
                        else self.replay.device_sampler.draw_dispatches)
                for _ in range(seen - self._replay_draws_counted):
                    self._count_device_call("replay_sample")
                self._replay_draws_counted = seen
        tmc.observe_sample_lineage(out[0], self.grad_steps,
                                   self._tm_sample_age,
                                   self._tm_sample_staleness)
        return out

    def _stage_batch(self, batch_size: int, beta: float) -> None:
        """Sample one batch and begin its H2D upload (replay/staging.py):
        the sample+copy+upload for step g+1 runs while step g trains."""
        with self.tracer.span("replay.sample", batch=batch_size):
            items, idx, weights, gen = self._sample_replay(batch_size,
                                                           beta)
        with self.tracer.span("h2d.stage", batch=batch_size):
            self._stager.stage(self._host_train_args(items, weights),
                               aux=(idx, gen))

    def _sample_scan_args(self, batch_size: int, beta: float):
        """N independently-drawn batches stacked on a leading sub-step
        axis for the replay-ratio scan dispatch (ISSUE 6); aux carries
        the CONCATENATED (idx, gen) in sub-step order, matching the
        flattened [N*B] priorities the scan returns — chronological,
        so the batched write-back's last-wins holds across sub-steps."""
        from dist_dqn_tpu.types import Transition
        items_l, idx_l, w_l, gen_l = [], [], [], []
        with self.tracer.span("replay.sample", batch=batch_size,
                              substeps=self.replay_ratio):
            for _ in range(self.replay_ratio):
                items, idx, weights, gen = self._sample_replay(
                    batch_size, beta)
                items_l.append(items)
                idx_l.append(idx)
                w_l.append(np.asarray(weights, np.float32))
                gen_l.append(gen)
        batch = Transition(*(np.stack([it[k] for it in items_l])
                             for k in ("obs", "action", "reward",
                                       "discount", "next_obs")))
        return ((batch, np.stack(w_l)),
                (np.concatenate(idx_l), np.concatenate(gen_l)))

    def _stage_scan_batch(self, batch_size: int, beta: float) -> None:
        """The scan path's ``_stage_batch`` twin: sample N stacked
        batches and begin their H2D upload behind the stager."""
        args, aux = self._sample_scan_args(batch_size, beta)
        with self.tracer.span("h2d.stage", batch=batch_size,
                              substeps=self.replay_ratio):
            self._stager.stage(args, aux=aux)

    def _min_fill_items(self) -> int:
        """min_fill counts transitions; in sequence mode convert to
        sequences (each loss region covers unroll_length steps)."""
        if not self.recurrent:
            return self.cfg.replay.min_fill
        per_seq = max(self.cfg.replay.unroll_length, 1)
        return max(self.cfg.replay.min_fill // per_seq,
                   2 * self.cfg.learner.batch_size)

    def _inserts_per_grad(self) -> int:
        """inserts_per_grad_step is defined in TRANSITIONS; in sequence
        mode replay.added counts sequences, each covering unroll_length
        loss transitions, so convert to keep the configured replay ratio."""
        inserts = self.rt.inserts_per_grad_step
        if self.recurrent:
            inserts = max(
                inserts // max(self.cfg.replay.unroll_length, 1), 1)
        return inserts

    def _maybe_train(self):
        if self.distributed:
            return self._maybe_train_distributed()
        if len(self.replay) < self._min_fill_items():
            return
        # The replay ratio multiplies the grad-step/insert cadence: N
        # sub-steps per collected chunk of inserts (ISSUE 6).
        target = (self.replay.added * self.replay_ratio
                  // self._inserts_per_grad())
        self._train_to_target(target, self.env_steps, self.train_batch)

    def _maybe_train_distributed(self):
        """Multi-host cadence (actors/multihost.py): agree on global
        counters, then every host runs the SAME number of collective train
        steps (its own shard's batch slice each). Ingestion stays async;
        only this path is lockstep."""
        if time.perf_counter() < self._next_sync:
            return
        ready = int(len(self.replay) >= self._min_fill_items())
        agreed = self._mh.agree(np.array(
            [self.replay.added, ready, self.env_steps], np.int64))
        self._next_sync = time.perf_counter() + self.rt.sync_every_s
        g_added, ready_count, g_env = (int(v) for v in agreed)
        # Resumed runs: env_steps restarts at 0 on every host (the saved
        # cursor was the GLOBAL count — psumming it back would multiply it
        # by the host count); the offset re-enters here once.
        self.global_env_steps = g_env + self._resume_global
        if int(ready_count) < self._mh.nprocs:
            return  # some host's shard is still below min_fill
        target = g_added // self._inserts_per_grad()
        before = self.grad_steps
        self._train_to_target(target, self.global_env_steps,
                              self._local_batch)
        if self.grad_steps > before:
            # Fresh local mirror for act/eval/priority bootstraps.
            self._refresh_host_params()

    def _train_to_target(self, target_grad_steps: int, progress_steps: int,
                         batch_size: int):
        cfg = self.cfg
        jnp = self.jnp
        # Bounded per pass (see ApexRuntimeConfig.train_steps_per_pass);
        # identical on every host in the lockstep path because both
        # operands of the min come from agreed counters.
        target_grad_steps = min(
            target_grad_steps,
            self.grad_steps + max(self.rt.train_steps_per_pass, 1))
        beta = min(1.0, cfg.replay.importance_exponent
                   + (1 - cfg.replay.importance_exponent)
                   * progress_steps / max(self.rt.total_env_steps, 1))
        while self.grad_steps < target_grad_steps:
            self._profile_tracer.start()
            if self._train_scan is not None:
                # Replay-ratio scan path (ISSUE 6): one dispatch runs N
                # sub-steps over independently-drawn stacked batches.
                # The per-pass bound may be overshot by up to N-1 steps
                # (the dispatch is atomic); the cadence debt absorbs it.
                if self._stager is not None:
                    if len(self._stager) == 0:
                        self._stage_scan_batch(batch_size, beta)
                    args, (idx, gen) = self._stager.pop()
                    with self.tracer.span("train_step.dispatch",
                                          substeps=self.replay_ratio):
                        self.state, metrics = self._train_scan(self.state,
                                                               *args)
                    self._count_device_call("train")
                    if self.grad_steps + self.replay_ratio \
                            < target_grad_steps:
                        self._stage_scan_batch(batch_size, beta)
                else:
                    args, (idx, gen) = self._sample_scan_args(batch_size,
                                                              beta)
                    args = self.jax.tree.map(jnp.asarray, args)
                    with self.tracer.span("train_step.dispatch",
                                          substeps=self.replay_ratio):
                        self.state, metrics = self._train_scan(self.state,
                                                               *args)
                    self._count_device_call("train")
                self.grad_steps += self.replay_ratio
                self._tm_grad_steps.inc(self.replay_ratio)
                self._in_flight.append((idx, gen, metrics,
                                        time.perf_counter()))
                while len(self._in_flight) > self.rt.pipeline_depth:
                    self._finalize_train()
                continue
            if self._stager is not None:
                # Double-buffered path: batch g comes off the stager
                # (uploaded while step g-1 trained); batch g+1 is staged
                # right after g's dispatch, so its sample+H2D overlaps
                # g's device time. A burst never leaves stale batches
                # staged: the last step stages no successor.
                if len(self._stager) == 0:
                    self._stage_batch(batch_size, beta)
                args, (idx, gen) = self._stager.pop()
                with self.tracer.span("train_step.dispatch"):
                    self.state, metrics = self._train_step(self.state,
                                                           *args)
                self._count_device_call("train")
                if self.grad_steps + 1 < target_grad_steps:
                    self._stage_batch(batch_size, beta)
            else:
                with self.tracer.span("replay.sample", batch=batch_size):
                    items, idx, weights, gen = self._sample_replay(
                        batch_size, beta)
                with self.tracer.span("train_step.dispatch"):
                    if self.recurrent:
                        sample = self._sequence_sample(items, weights)
                        self.state, metrics = self._train_step(self.state,
                                                               sample)
                    else:
                        from dist_dqn_tpu.types import Transition
                        batch = Transition(
                            obs=jnp.asarray(items["obs"]),
                            action=jnp.asarray(items["action"]),
                            reward=jnp.asarray(items["reward"]),
                            discount=jnp.asarray(items["discount"]),
                            next_obs=jnp.asarray(items["next_obs"]))
                        w_dev = jnp.asarray(weights)
                        self.state, metrics = self._train_step(
                            self.state, batch, w_dev)
                self._count_device_call("train")
            self.grad_steps += 1
            self._tm_grad_steps.inc()
            self._in_flight.append((idx, gen, metrics,
                                    time.perf_counter()))
            # Retire completed steps beyond the pipeline window; the oldest
            # has had the longest to finish, so this rarely blocks.
            while len(self._in_flight) > self.rt.pipeline_depth:
                self._finalize_train()

    def _flush_ledger_window(self):
        """Close the current utilization-ledger window: wall since the
        last flush against the train steps' queue-occupied delta.
        The apex loop has no chunk boundary, so the log cadence (and a
        final flush before the summary) is its decomposition unit;
        unattributed wall lands in the `other` bucket."""
        now = time.perf_counter()
        busy_total = self._train_busy_s
        self._ledger.observe_chunk(now - self._ledger_t_last,
                                   busy_total - self._ledger_busy_seen)
        self._ledger_t_last = now
        self._ledger_busy_seen = busy_total

    def _finalize_train(self):
        """Materialize the oldest in-flight step's priorities and queue
        them for the next BATCHED write-back (blocks on the device only
        if that step still runs)."""
        if not self._in_flight:
            return
        idx, gen, metrics, t_dispatch = self._in_flight.popleft()
        # The data-parallel scan path keeps priorities [N, local_b] per
        # shard (global [N, B]); reshape(-1) recovers the sub-step-major
        # chronological order the batched write-back pairs with its
        # concatenated idx. A no-op for the already-flat paths.
        prios = np.asarray(metrics["priorities"]).reshape(-1)
        # Dispatch -> materialized: the np.asarray above blocked until the
        # device finished this step, so this IS the grad-step round-trip
        # (pipelining means it includes up to pipeline_depth-1 queued
        # steps — the operationally honest number for the host loop).
        t_retire = time.perf_counter()
        self._tm_grad_latency.observe(t_retire - t_dispatch)
        # The wall from max(dispatch, previous retirement) to now is the
        # interval this step occupied the device queue — overlapping
        # in-flight steps never double-count. An upper bound (queue-
        # occupied, not kernel-active), as the grad latency above.
        self._train_busy_s += max(
            t_retire - max(t_dispatch, self._busy_anchor), 0.0)
        self._busy_anchor = t_retire
        if self._profile_tracer.stop():
            print(f"# profile_trace {self.rt.profile_dir}")
        self._last_loss = float(metrics["loss"])
        # Divergence sentinel (ISSUE 4): every retired step's loss and
        # grad norm — NaN/Inf dumps a forensics bundle once instead of
        # the run training on to garbage. Scalars from the step just
        # materialized above, so no extra device round-trip.
        grad_norm = metrics.get("grad_norm")
        tm_watchdog.observe_divergence(
            loss=self._last_loss,
            grad_norm=(float(grad_norm) if grad_norm is not None
                       else None),
            step=self.grad_steps)
        # Batched priority write-backs (ISSUE 2): accumulate completed
        # steps' (idx, |TD|, gen) and apply them as ONE vectorized
        # sum-tree update — K batch-sized set() calls collapse into one
        # propagation pass. expected_gen still drops updates for slots
        # overwritten in the meantime (priority misattribution guard),
        # and chronological concat order keeps last-write-wins semantics
        # for slots sampled by several of the batched steps.
        self._prio_pending.append((idx, prios, gen))
        self._flush_prio_writebacks()

    def _flush_prio_writebacks(self, force: bool = False):
        """Apply accumulated train-step priorities in one batched
        sum-tree update once ``prio_writeback_batch`` steps are pending
        (or immediately, when forced at barriers/shutdown)."""
        limit = max(self.rt.prio_writeback_batch, 1)
        if not self._prio_pending:
            return
        if not force and len(self._prio_pending) < limit:
            return
        pending, self._prio_pending = self._prio_pending, []
        idx = np.concatenate([e[0] for e in pending])
        prios = np.concatenate([e[1] for e in pending])
        gen = np.concatenate([e[2] for e in pending])
        with self.tracer.span("replay.update_priorities",
                              steps=len(pending), rows=idx.shape[0]):
            self.replay.update_priorities(idx, prios, expected_gen=gen)

    def _finalize_all_train(self):
        while self._in_flight:
            self._finalize_train()
        self._flush_prio_writebacks(force=True)

    def _evaluate_impl(self, params) -> tuple:
        """Greedy episodes on a service-owned env; the recurrent policy
        threads its own eval carry. Returns (mean undiscounted return,
        step-capped episode count). Uses only eval-owned mutable state
        (``_eval_env``/``_eval_rng``) plus the given param snapshot, so it
        is safe to run from the async eval thread while the main loop keeps
        training."""
        from dist_dqn_tpu.envs.gym_adapter import make_host_env
        n = self.rt.eval_episodes
        if self._eval_env is None:
            self._eval_env = make_host_env(self.rt.host_env, n,
                                           for_eval=True,
                                           seed=10_000 + self.cfg.seed)
        if self._eval_rng is None:
            self._eval_rng = self.jax.random.PRNGKey(self.cfg.seed + 991)
        from dist_dqn_tpu.utils.host_eval import run_greedy_episodes

        returns, truncated, self._eval_rng = run_greedy_episodes(
            self._eval_env, self._act, params, self._eval_rng, episodes=n,
            recurrent_carry=(self.net.initial_state(n) if self.recurrent
                             else None))
        return float(returns.mean()), float(truncated)

    def _evaluate(self) -> float:
        """Synchronous eval (single-host path)."""
        ret, truncated = self._evaluate_impl(self._policy_params)
        if truncated:
            # Step-capped: record the truncation so a downward-biased
            # eval_return is not mistaken for a policy regression.
            self.log.record(eval_episodes_truncated=truncated)
        return ret

    def _start_async_eval(self):
        """Multi-host eval must not stall the pod: an inline eval on host 0
        blocks every peer at its next agreement collective for the whole
        eval (up to 10k env steps). Evaluate from the host param mirror in
        a background thread instead; the collective cadence continues and
        the result is logged when the thread finishes."""
        if self._eval_thread is not None and self._eval_thread.is_alive():
            self.log.record(eval_skipped=1.0)  # previous eval still running
            return
        params = self._policy_params  # mirror tuple is replaced, not mutated
        at_steps = self._progress()

        def work():
            try:
                self._eval_results.append(
                    (at_steps, self._evaluate_impl(params)))
            except Exception as e:  # noqa: BLE001 — surfaced by the poller
                self._eval_results.append((at_steps, e))

        self._eval_thread = threading.Thread(target=work, daemon=True,
                                             name="apex-eval")
        self._eval_thread.start()

    def _poll_async_eval(self):
        while True:
            try:
                at_steps, res = self._eval_results.popleft()
            except IndexError:
                return
            if isinstance(res, Exception):
                self.log.log_fn(f"# async eval failed: {res!r}")
                continue
            ret, truncated = res
            if truncated:
                self.log.record(eval_episodes_truncated=truncated)
            self.log.record(env_steps=at_steps, eval_return=ret)
            self.log.flush()

    def _progress(self) -> int:
        """Run-cursor: local env steps, or the group-agreed GLOBAL count in
        multi-host mode (identical on every host at each sync, so all
        hosts make termination/eval/checkpoint decisions in the same
        order — the collective-pairing invariant)."""
        return self.global_env_steps if self.distributed else self.env_steps

    def _replay_snapshot_path(self) -> str:
        # Multi-host: each process owns its shard, so each snapshots its
        # own file beside the shared learner checkpoint.
        suffix = (f"_p{self.jax.process_index()}" if self.distributed
                  else "")
        return os.path.join(self.rt.checkpoint_dir,
                            f"replay_shard{suffix}.npz")

    def _save_replay_snapshot(self) -> None:
        if not (self.rt.checkpoint_replay and self.rt.checkpoint_dir):
            return
        # Close the pipelined-bootstrap window first: transitions whose
        # priorities are still in flight (up to a few _PRIO_CHUNKs of
        # the NEWEST experience) must land in the shard before it is
        # snapshotted, or a crash-resume permanently drops them. Same
        # for actor-priority transitions parked on this pass's flush.
        self._insert_actor_prio()
        self._flush_pending(force=True)
        # Same for accumulated-but-unapplied learner priorities: the
        # snapshot must carry the freshest |TD| mass the learner computed.
        self._flush_prio_writebacks(force=True)
        if not len(self.replay):
            return
        from dist_dqn_tpu.utils.checkpoint import atomic_savez

        path = self._replay_snapshot_path()
        t0 = time.perf_counter()
        # Atomic: a crash mid-write leaves the old one.
        atomic_savez(path, **self.replay.state_dict())
        wall = time.perf_counter() - t0
        self._tm_ckpt_save.observe(wall)
        self._tm_ckpt_bytes.inc(os.path.getsize(path))
        self.log.log_fn(json.dumps({
            "replay_snapshot_s": round(wall, 3),
            "replay_snapshot_mb": round(os.path.getsize(path) / 2**20, 1),
            "replay_snapshot_items": len(self.replay),
            "replay_snapshot_shards": getattr(self.replay, "num_shards",
                                              1)}))

    def _load_replay_snapshot(self) -> None:
        """Restore the replay snapshot beside the learner checkpoint.
        Since ISSUE 12 a snapshot written at a DIFFERENT shard count is
        a supported migration, not a refusal: records redistribute to
        the new layout by their global slot encoding with priorities
        preserved (replay/sharded.py restore_replay_snapshot) — a dp=2
        checkpoint restores at dp=1 or dp=4, every record exactly once
        (pinned by tests/test_sharded_replay.py). Migrations are
        statistically continuous, not bit-identical: per-slot write
        generations reset, so deferred write-backs from the killed run
        drop at the generation guard (the safe direction)."""
        from dist_dqn_tpu.replay.sharded import restore_replay_snapshot

        path = self._replay_snapshot_path()
        if not os.path.exists(path):
            return
        t0 = time.perf_counter()
        with np.load(path) as state:
            info = restore_replay_snapshot(self.replay, dict(state))
        get_registry().counter(
            tmc.CHECKPOINT_RESUMES,
            "successful whole-state resumes",
            {"loop": "apex"}).inc()
        self.log.log_fn(json.dumps({
            "replay_snapshot_restored_items": len(self.replay),
            "replay_snapshot_restore_s":
                round(time.perf_counter() - t0, 3),
            "replay_snapshot_resharded": bool(info["resharded"]),
            "replay_snapshot_from_shards": info["from_shards"],
            "replay_snapshot_to_shards": info["to_shards"]}))

    def _track_episode_returns(self, actor: int, reward: np.ndarray,
                               terminated: np.ndarray,
                               truncated: np.ndarray) -> None:
        """Per-lane raw-reward accumulation -> completed episode returns
        (training units). Reconnect resets re-zero via shape mismatch:
        a fresh hello changes nothing here because rewards restart with
        the new episode anyway."""
        acc = self._ep_accum.get(actor)
        if acc is None or acc.shape != reward.shape:
            acc = np.zeros_like(reward, dtype=np.float64)
        acc = acc + reward
        done = np.logical_or(terminated, truncated)
        if done.any():
            finished = acc[done]
            self._ep_returns.extend(finished.tolist())
            self.episodes_completed += int(done.sum())
            self._tm_episodes.inc(int(done.sum()))
            acc = np.where(done, 0.0, acc)
        self._ep_accum[actor] = acc

    def _drain_transports(self, burst: int = 256) -> bool:
        """One ingest burst: pop up to ``burst`` records from the shm ring
        and the TCP listener and route each through ``_handle_record``.
        Returns whether anything arrived. This is the production ingest
        path — the fan-in stress test (tests/test_fanin_stress.py) drives
        it directly with synthesized 256-actor record streams."""
        drained = False
        # Zero-copy slot rings (ISSUE 9): one SPSC ring per local actor
        # — no socket stack, no shared-ring contention, records decode
        # to views over one owned copy out of the slot.
        for actor_id, ring in self._zc_rings.items():
            for _ in range(burst):
                rec = ring.pop()
                if rec is None:
                    break
                drained = True
                try:
                    with self.tracer.span("ingest.shm_record"):
                        self._handle_record(rec, transport_kind="shm")
                except self.HelloRejectedError:
                    raise      # local build drift: fail loudly at connect
                except Exception as e:
                    # Same degrade-don't-die boundary as the TCP drain:
                    # a record rejected at the codec gate (chaos
                    # ingest.decode, a torn-then-garbled slot) must
                    # cost ONE record, not the training run. The
                    # lock-step actor's lane stalls; the ingest stall
                    # watchdog + supervision own that recovery.
                    self.bad_records += 1
                    self._tm_bad_records.inc()
                    if self.bad_records <= 5:
                        self.log.log_fn(
                            f"# bad shm record actor {actor_id} "
                            f"({self.bad_records}): "
                            f"{type(e).__name__}: {e}")
        for _ in range(burst):
            rec = self.req_ring.pop()
            if rec is None:
                break
            drained = True
            with self.tracer.span("ingest.shm_record"):
                self._handle_record(rec)
        if self.tcp_server is not None:
            for _ in range(burst):
                rec = self.tcp_server.pop()
                if rec is None:
                    break
                drained = True
                conn_id, payload = rec
                try:
                    with self.tracer.span("ingest.tcp_record"):
                        self._handle_record(payload, conn_id=conn_id,
                                            transport_kind="tcp")
                except Exception as e:
                    # Network input is untrusted (the listener may face
                    # other hosts): a malformed or misrouted record must
                    # not take down the training run. Logged (rate-
                    # limited) so a genuine service bug surfacing here is
                    # visible, not silently counted away.
                    self.bad_records += 1
                    self._tm_bad_records.inc()
                    if self.bad_records <= 5:
                        self.log.log_fn(
                            f"# bad TCP record ({self.bad_records})"
                            f": {type(e).__name__}: {e}")
        if drained:
            # One INGEST PASS = one drain burst that moved records. The
            # bench divides device_calls by this to report round-trips
            # per pass (ISSUE 2).
            self.ingest_passes += 1
            self._tm_ingest_passes.inc()
        return drained

    def run(self):
        """Main service loop until total_env_steps processed."""
        self.spawn_actors()
        # Watchdog clock starts AFTER spawn: slow fleet startup (imports,
        # env builds, first inference) is not an ingest stall.
        self._last_record = time.perf_counter()
        last_log = time.perf_counter()
        # Stall-watchdog heartbeats (ISSUE 4; null-safe until the CLI
        # arms --forensics-dir): "apex.ingest" proves the drain/act half
        # of the loop is turning over, "apex.learner" the train half. A
        # loop pass wedged inside a device call, a transport lock or the
        # sum tree leaves BOTH stale and the forensics stacks show where.
        # Startup grace covers the first pass's jit compiles.
        hb_ingest = tm_watchdog.heartbeat(
            "apex.ingest", startup_grace_s=tm_watchdog.STARTUP_GRACE_S)
        hb_learner = tm_watchdog.heartbeat(
            "apex.learner", startup_grace_s=tm_watchdog.STARTUP_GRACE_S)

        # Emergency checkpoint on watchdog abort (ISSUE 8): save the
        # live learner state before the SIGTERM — the state reference
        # swap is atomic and device arrays immutable, so the side
        # thread reads a consistent post-step snapshot. To a SIDE
        # location via its own one-shot checkpointer: the canonical
        # wedge is the main thread stuck INSIDE the shared manager's
        # save (slow storage), and a concurrent save on that manager
        # would tear the in-flight commit instead of preserving state.
        def _emergency_save():
            if self.rt.checkpoint_dir and self.state is not None:
                from dist_dqn_tpu.utils.checkpoint import save_pytree
                save_pytree(os.path.join(self.rt.checkpoint_dir,
                                         "emergency_learner"),
                            {"learner": self.state})
                if self.rt.checkpoint_replay and len(self.replay):
                    # All replay shards too (ISSUE 12): the raw store
                    # snapshot WITHOUT the quiescing flushes the
                    # periodic save runs (those touch service state the
                    # wedged main thread may hold) — in-flight
                    # priorities of the newest few chunks may be
                    # missing, honestly a salvage artifact, but every
                    # shard's items are present instead of a
                    # learner-only snapshot.
                    from dist_dqn_tpu.utils.checkpoint import \
                        atomic_savez
                    atomic_savez(os.path.join(self.rt.checkpoint_dir,
                                              "emergency_replay.npz"),
                                 **self.replay.state_dict())

        tm_watchdog.register_emergency_hook("apex.checkpoint",
                                            _emergency_save)
        try:
            while self._progress() < self.rt.total_env_steps:
                # Chaos seam (ISSUE 8): the learner-process kill for
                # game days — die with SIGKILL semantics (no cleanup,
                # no stop file) at a plan-determined loop pass, so the
                # learner-restart invariant (actors re-attach via
                # re-hello, trajectory resumes from the checkpoint) is
                # exercised at a reproducible dataflow position.
                cev = chaos.fire("service.loop")
                if cev is not None and cev.fault == "crash":
                    os._exit(137)
                drained = self._drain_transports()
                self._flush_act_queue()
                self._insert_actor_prio()
                self._flush_pending()
                hb_ingest.beat()
                self._maybe_train()
                hb_learner.beat()
                if self._ckpt is not None:
                    if self._ckpt.maybe_save(self._progress(), self.state):
                        self._save_replay_snapshot()
                if self._progress() >= self._next_eval:
                    self._next_eval = self._progress() \
                        + self.rt.eval_every_steps
                    self._finalize_all_train()
                    # Eval is a process-local program: in multi-host mode
                    # only the reporting host plays episodes — in a
                    # BACKGROUND thread, so its peers are not stalled at
                    # their next agreement collective for the eval's
                    # duration; all hosts advance _next_eval identically
                    # (agreed counter).
                    if self.distributed:
                        if self.jax.process_index() == 0:
                            self._start_async_eval()
                    else:
                        with self.tracer.span("eval"):
                            eval_return = self._evaluate()
                        self.log.record(env_steps=self._progress(),
                                        eval_return=eval_return)
                        self.log.flush()
                    last_log = time.perf_counter()
                self._poll_async_eval()
                if not drained:
                    time.sleep(0.0002)
                now = time.perf_counter()
                if now - last_log > self.rt.log_every_s:
                    self.supervise_actors()
                    self._watchdog(now)
                    # Queue-depth sweep (off the per-record hot path; one
                    # gauge write each per log period).
                    self._tm_act_queue.set(len(self._act_queue))
                    self._tm_pending.set(self._pending_count)
                    self._tm_boot_inflight.set(len(self._boot_inflight))
                    self._tm_train_inflight.set(len(self._in_flight))
                    self._tm_prio_pending.set(len(self._prio_pending))
                    self._tm_ring_dropped.set(self.req_ring.dropped)
                    self._tm_ring_pending.set(self.req_ring.pending_bytes)
                    self._tm_record_age.set(now - self._last_record)
                    self._sweep_dedup_counters()
                    # Once per log period: ledger the window's wall
                    # against the train steps' queue-occupied delta
                    # (the apex loop has no chunk boundary — the log
                    # window is its decomposition unit; unattributed
                    # wall lands in the `other` bucket), and sweep
                    # device memory stats.
                    self._flush_ledger_window()
                    self._devtime.sweep_device_memory()
                    self.tracer.counter("replay_size", len(self.replay))
                    self.tracer.counter("env_steps", self.env_steps)
                    self.tracer.flush()
                    self.log.record(env_steps=self.env_steps,
                                    grad_steps=self.grad_steps,
                                    replay_size=float(len(self.replay)),
                                    loss=getattr(self, "_last_loss", 0.0),
                                    actor_restarts=float(
                                        self.actor_restarts),
                                    ring_dropped=float(
                                        self.req_ring.dropped))
                    if self._ep_returns:
                        self.log.record(
                            episode_return=float(
                                np.mean(self._ep_returns)),
                            episodes_completed=float(
                                self.episodes_completed))
                    self.log.flush()
                    last_log = now
            self._insert_actor_prio()
            self._flush_pending(force=True)
            self._finalize_all_train()
            if self._eval_thread is not None:
                self._eval_thread.join(timeout=60)
                self._poll_async_eval()
            if self._ckpt is not None:
                self._ckpt.save(self._progress(), self.state)
                self._ckpt.close()
                self._save_replay_snapshot()
        finally:
            tm_watchdog.unregister_emergency_hook("apex.checkpoint")
            hb_ingest.close()
            hb_learner.close()
            self.tracer.close()
            self.shutdown()
        dedup_frames, dedup_saved = self._dedup_totals()
        self._flush_ledger_window()
        return {"env_steps": self.env_steps, "grad_steps": self.grad_steps,
                # Zero-copy ingest provenance (ISSUE 9): which transport
                # carried the run, what it cost on the wire, and where
                # the sticky router placed it.
                "transport": self.rt.transport,
                "actor_priorities": bool(self._act_q is not None),
                "ingest_bytes": dict(self.router.bytes_by_transport),
                "bytes_on_wire": int(
                    sum(self.router.bytes_by_transport.values())),
                # Near-data experience plane (ISSUE 14): what the dedup
                # wire avoided shipping, how slots batched, and whether
                # sampling ran ingest-side.
                "dedup_frames_reused": int(dedup_frames),
                "dedup_bytes_saved": int(dedup_saved),
                "shm_batch": self.rt.shm_batch,
                "shard_sampling": self._shard_sampler is not None,
                # Sampling-axis provenance (ISSUE 18): which backend
                # drew this run's batches.
                "sampler": ("device" if self.rt.device_sampling
                            else "tree"),
                "shard_sample_batches": (self._shard_sampler.batches
                                         if self._shard_sampler else 0),
                "records_by_shard": dict(self.router.records_by_shard),
                "replay_added_by_shard": dict(
                    getattr(self.replay, "added_by_shard", {}) or {}),
                "ingest_decode_errors": self.router.decode_errors,
                # Learner-utilization config provenance (ISSUE 6).
                "replay_ratio": self.replay_ratio,
                "train_batch": self.train_batch,
                "actor_dtype": self.actor_dtype,
                "global_env_steps": self.global_env_steps,
                "episodes_completed": self.episodes_completed,
                "episode_return_recent":
                    (float(np.mean(self._ep_returns))
                     if self._ep_returns else None),
                "replay_size": len(self.replay),
                "ring_dropped": self.req_ring.dropped,
                # Ingest fast path accounting (ISSUE 2): dispatched device
                # programs by kind, drain bursts that carried records, and
                # the ratio the feeder bench regresses on.
                "device_calls": dict(self.device_calls),
                "ingest_passes": self.ingest_passes,
                "ingest_device_calls_per_pass": round(
                    (self.device_calls.get("act", 0)
                     + self.device_calls.get("fused_act_bootstrap", 0)
                     + self.device_calls.get("bootstrap", 0))
                    / max(self.ingest_passes, 1), 3),
                # Full backlogs backpressure rather than drop; a nonzero
                # count means the learner is not keeping up with actors.
                "tcp_backpressure": (self.tcp_server.backpressure_events
                                     if self.tcp_server else 0),
                # The ledger's busy/idle decomposition of wall time.
                "chip_time": self._ledger.snapshot(),
                "bad_records": self.bad_records,
                "actor_restarts": self.actor_restarts}


def run_apex(cfg: ExperimentConfig, rt: ApexRuntimeConfig, log_fn=print):
    """Convenience entry: build the service, run to completion."""
    service = ApexLearnerService(cfg, rt, log_fn=log_fn)
    return service.run()
