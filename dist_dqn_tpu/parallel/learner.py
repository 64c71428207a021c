"""Multi-chip fused training: shard_map over the ICI mesh.

Composition of the per-device fused loop (train_loop.py) into the pod-scale
program the driver describes (BASELINE.json:5):

  * envs + replay shard over the ``dp`` mesh axis — each device rolls out
    its own env lanes and owns one replay shard in its HBM (the TPU-native
    reading of "replay shards across TPU-VM host DRAM"; the host-DRAM
    variant for external envs is replay/host.py + actors/),
  * learner state is replicated; gradients cross the ICI once per update
    via ``pmean`` inside the learner (agents/dqn.py, agents/r2d2.py) — the
    NCCL-allreduce replacement,
  * chunk metrics are psum-reduced so the host sees global numbers.

Everything below is spec plumbing: which carry leaves live on which mesh
axis. The actual math is unchanged single-device code — that's the point
of SPMD.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict

import jax
from jax.sharding import Mesh, PartitionSpec as P

from dist_dqn_tpu.telemetry import get_registry

from dist_dqn_tpu.agents.dqn import LearnerState
from dist_dqn_tpu.config import ExperimentConfig
from dist_dqn_tpu.envs.base import JaxEnv
from dist_dqn_tpu.train_loop import TrainCarry, fused_parts, \
    make_fused_train


def _carry_specs(replay_spec, axis: str) -> TrainCarry:
    """Pytree-prefix PartitionSpecs for every TrainCarry field.

    Env-batched leaves — the actor state's among them, whatever its tree —
    shard their leading env axis; the ring brings its own specs
    (replay/device_ring.py); learner state and scalar counters are
    replicated (kept consistent by pmean/psum inside the body).
    """
    shard0 = P(axis)            # leading env axis
    repl = P()
    return TrainCarry(
        env_state=shard0, obs=shard0, actor_carry=shard0,
        replay=replay_spec,
        learner=LearnerState(params=repl, target_params=repl,
                             opt_state=repl, steps=repl, rng=repl),
        rng=shard0, iteration=repl,
        ep_return=shard0, completed_return=repl, completed_count=repl,
        loss_sum=repl, train_count=repl, agent_sums=repl)


def _mesh_wrap(mesh: Mesh, specs, init_local, run_local):
    """Lift per-device (init, run_chunk) bodies to jit-compiled functions on
    GLOBAL arrays; the carry is donated so replay shards update in place in
    each device's HBM."""
    # donation: PRNG-key-only init (run() donates the carry).
    # mesh-axis: dp specs via _carry_specs.
    init = jax.jit(
        jax.shard_map(init_local, mesh=mesh, in_specs=P(),
                         out_specs=specs, check_vma=False))

    @partial(jax.jit, static_argnums=1, donate_argnums=0)
    def run(carry, num_iters: int):
        # mesh-axis: specs name the dp axis (see _carry_specs).
        body = jax.shard_map(
            lambda c: run_local(c, num_iters), mesh=mesh,
            in_specs=(specs,), out_specs=(specs, P()), check_vma=False)
        return body(carry)

    # Mesh-chunk telemetry (ISSUE 1): dispatch count + host-side dispatch
    # latency. JAX dispatch is async, so this times the enqueue, not the
    # execution — a GROWING dispatch latency means the device queue is
    # full and the host is now rate-limited by the mesh program (the
    # chunk wall itself is measured by the caller, train.py).
    reg = get_registry()
    c_chunks = reg.counter("dqn_mesh_chunks_total",
                           "fused mesh chunks dispatched")
    h_dispatch = reg.histogram("dqn_mesh_chunk_dispatch_seconds",
                               "host-side mesh chunk enqueue latency")

    def run_instrumented(carry, num_iters: int):
        t0 = time.perf_counter()
        out = run(carry, num_iters)
        h_dispatch.observe(time.perf_counter() - t0)
        c_chunks.inc()
        return out

    # train.py compiles the chunk program ahead of its first dispatch
    # (_compile_chunk) through the callable it is handed.
    run_instrumented.lower = run.lower
    return init, run_instrumented


def make_mesh_fused_train(cfg: ExperimentConfig, env: JaxEnv, net,
                          mesh: Mesh, axis: str = "dp"):
    """Returns (init, run) on GLOBAL arrays: ``init(key)`` builds the pod-
    wide carry; ``run(carry, num_iters)`` executes a fused chunk across the
    mesh and reports global metrics.

    The ISSUE 6 learner-utilization knobs ride the per-device body
    unchanged: the replay-ratio scan and the deferred PER flush run
    inside each shard's chunk (every device draws its own sub-step
    batches from its local replay shard; gradients still pmean once per
    sub-step), and the pow2-bucketed ``replay.train_batch`` resolves
    through ``loop_common.shard_sizes`` — so the per-shard width, not
    the global one, must divide evenly. The donated global carry keeps
    the same aliasing contract the single-chip audit pins
    (utils/donation.py): ``run`` donates argnum 0 below.
    """
    ndp = mesh.shape[axis]
    _, replay = fused_parts(cfg, env, net, axis, ndp)
    init_local, run_local = make_fused_train(cfg, env, net, axis_name=axis,
                                             num_shards=ndp)
    return _mesh_wrap(mesh, _carry_specs(replay.specs(axis), axis),
                      init_local, run_local)


# Debt D1c (ROADMAP.md): perf/tools/compile_rehearsal.py imports this name;
# the next `benchmark` PR points it at make_mesh_fused_train and removes it.
make_mesh_r2d2_train = make_mesh_fused_train


def train_step_specs(axis: str, recurrent: bool = False):
    """(data_specs, metric_specs) for one data-parallel train step: batch
    leaves shard their row axis over ``axis``, IS weights shard with
    them, pmean-reduced scalars replicate, per-example priorities stay
    sharded. The ONE spec set every host-side data-parallel learner
    (apex service, host-replay runtime, multi-host wrapper) lifts the
    per-device step with — the specs cannot drift apart per runtime.
    """
    from dist_dqn_tpu.types import SequenceSample, Transition

    repl = P()
    if recurrent:
        # Time-major [L, S, ...] fields shard the sequence axis (1).
        data_specs = (SequenceSample(
            obs=P(None, axis), action=P(None, axis),
            reward=P(None, axis), done=P(None, axis),
            reset=P(None, axis), start_state=(P(axis), P(axis)),
            weights=P(axis), t_idx=P(axis), b_idx=P(axis)),)
        metric_specs = {"loss": repl, "raw_loss": repl,
                        "priorities": P(axis), "grad_norm": repl}
    else:
        data_specs = (jax.tree.map(
            lambda _: P(axis),
            Transition(obs=0, action=0, reward=0, discount=0,
                       next_obs=0)),
            P(axis))  # batch, weights
        metric_specs = {"loss": repl, "raw_loss": repl,
                        "priorities": P(axis), "grad_norm": repl,
                        "mean_q_target_gap": repl}
    return data_specs, metric_specs


def scan_train_step_specs(axis: str):
    """Specs for the replay-ratio SCAN dispatch (agents/dqn.py
    make_scan_train with ``flatten=False``): batches carry a leading
    sub-step axis N, so rows shard on axis 1 and the returned
    priorities keep [N, local_rows] shape per shard — the host reshapes
    the global [N, B] to the chronological [N*B] the batched write-back
    expects (a sharded flat concat would interleave by device block,
    not by sub-step)."""
    from dist_dqn_tpu.types import Transition

    repl = P()
    data_specs = (jax.tree.map(
        lambda _: P(None, axis),
        Transition(obs=0, action=0, reward=0, discount=0, next_obs=0)),
        P(None, axis))  # stacked batches, stacked weights
    metric_specs = {"loss": repl, "raw_loss": repl,
                    "priorities": P(None, axis), "grad_norm": repl,
                    "mean_q_target_gap": repl}
    return data_specs, metric_specs


def make_sharded_train_step(train_step, mesh: Mesh, data_specs,
                            metric_specs):
    """Lift a per-device train step (built with ``axis_name`` set, so the
    pmean grad allreduce lives INSIDE it — agents/) onto ``mesh``: batch
    leaves shard per ``data_specs``, learner state replicates, and the
    state is donated so replicas update in place. Shared by the apex
    service's local learner mesh and the host-replay dp runtime."""
    repl = P()

    def sharded(state, *data):
        state_spec = jax.tree.map(lambda _: repl, state,
                                  is_leaf=lambda x: x is None)
        # mesh-axis: data_specs/metric_specs name the axis
        # (train_step_specs / scan_train_step_specs).
        body = jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(state_spec,) + tuple(data_specs),
            out_specs=(state_spec, metric_specs), check_vma=False)
        return body(state, *data)

    return jax.jit(sharded, donate_argnums=0)


def replicated_device_views(tree, devices):
    """Per-device single-device views of a mesh-REPLICATED pytree
    (ISSUE 15, sharded collect): every mesh device already holds a full
    replica of a ``P()``-sharded array, so handing shard ``s``'s
    collect program ``views[s]`` moves ZERO bytes — the Sebulba
    actor-side param refresh without the PR 10 host mirror (which paid
    one D2H per chunk and re-uploaded on dispatch). The caller owns
    lifetime: views alias the replica buffers, so snapshot (copy/cast)
    the tree first if a donated consumer will overwrite it."""

    def view(x, d):
        for sh in x.addressable_shards:
            if sh.device == d:
                return sh.data
        # Uncommitted (host-resident) leaf — e.g. a single-device test
        # tree that never replicated: a put is correct, just not free.
        return jax.device_put(x, d)

    return [jax.tree.map(lambda x, d=d: view(x, d), tree)
            for d in devices]


def global_metrics(metrics: Dict) -> Dict:
    """Device-get + float-cast a metrics dict for logging; mirrors each
    value into a ``dqn_mesh_<name>`` registry gauge on the way."""
    got = jax.device_get(metrics)
    out = {k: float(v) for k, v in got.items()}
    reg = get_registry()
    for k, v in out.items():
        reg.gauge(f"dqn_mesh_{k}", f"mesh chunk metric {k!r}").set(v)
    return out
