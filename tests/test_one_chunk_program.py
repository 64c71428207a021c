"""The one builder of the fused chunk program (train_loop.make_fused_train)
over every ring family, on one device and on a mesh; its one spec set; and
the combinations it refuses (train_loop.fused_parts)."""
import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dist_dqn_tpu.config import CONFIGS, PopulationConfig
from dist_dqn_tpu.envs import make_jax_env
from dist_dqn_tpu.models import build_network
from dist_dqn_tpu.parallel import make_mesh, make_mesh_fused_train
from dist_dqn_tpu.parallel.learner import _carry_specs
from dist_dqn_tpu.replay.device import TimeRingState
from dist_dqn_tpu.replay.prioritized_device import PrioritizedRingState
from dist_dqn_tpu.replay.sequence_device import SequenceRingState
from dist_dqn_tpu.train_loop import TrainCarry, fused_parts, make_fused_train


def _cfg(family):
    if family == "sequence":
        cfg = CONFIGS["r2d2"]
        cfg = dataclasses.replace(
            cfg, env_name="cartpole",
            network=dataclasses.replace(
                cfg.network, torso="mlp", mlp_features=(16,), hidden=0,
                lstm_size=8, compute_dtype="float32"),
            replay=dataclasses.replace(
                cfg.replay, capacity=1024, min_fill=64, burn_in=2,
                unroll_length=4, sequence_stride=2),
            learner=dataclasses.replace(cfg.learner, n_step=2,
                                        batch_size=16))
    else:
        cfg = CONFIGS["cartpole"]
        cfg = dataclasses.replace(
            cfg,
            network=dataclasses.replace(cfg.network, mlp_features=(16,)),
            replay=dataclasses.replace(
                cfg.replay, capacity=1024, min_fill=64,
                prioritized=family == "prioritized"),
            learner=dataclasses.replace(cfg.learner, batch_size=16))
    return dataclasses.replace(
        cfg, actor=dataclasses.replace(cfg.actor, num_envs=8))


RING = {"uniform": TimeRingState, "prioritized": PrioritizedRingState,
        "sequence": SequenceRingState}


@pytest.mark.parametrize("num_devices", [1, 2])
@pytest.mark.parametrize("family", ["uniform", "prioritized", "sequence"])
def test_one_builder_one_carry_one_spec_set(family, num_devices):
    cfg = _cfg(family)
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    _, replay = fused_parts(cfg, env, net)
    assert replay.sequence == (family == "sequence")
    assert replay.prioritized == (family != "uniform")
    if num_devices == 1:
        mesh = None
        init, run_chunk = make_fused_train(cfg, env, net)
        run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
    else:
        mesh = make_mesh(devices=jax.devices()[:num_devices])
        init, run = make_mesh_fused_train(cfg, env, net, mesh)
    carry = init(np.asarray(jax.random.PRNGKey(0)))
    steps = 0.0
    for _ in range(2):
        carry, metrics = run(carry, 40)
        steps += float(metrics["grad_steps_in_chunk"])
    assert int(metrics["env_frames"]) == 80 * 8
    assert steps > 0 and np.isfinite(float(metrics["loss"]))
    assert type(carry) is TrainCarry
    assert type(carry.replay) is RING[family]
    # the actor state: none for a feed-forward network, the LSTM's for a
    # recurrent one — lanes leading either way
    state = jax.tree.leaves(carry.actor_carry)
    assert [x.shape for x in state] == (
        [(8, 8), (8, 8)] if family == "sequence" else [])

    # The one spec set is a prefix of the carry's tree, each spec fits the
    # leaves under it, and on a mesh it is how they are laid out.
    specs = _carry_specs(replay.specs("dp"), "dp")

    def check(spec, subtree):
        for leaf in jax.tree.leaves(subtree):
            assert leaf.ndim >= len(spec), (spec, leaf.shape)
            if mesh is not None:
                assert leaf.sharding.is_equivalent_to(
                    NamedSharding(mesh, spec), leaf.ndim), (spec, leaf.shape)

    jax.tree.map(check, specs, carry, is_leaf=lambda x: isinstance(x, P))


def test_what_the_sequence_ring_cannot_serve_is_still_refused():
    cfg = _cfg("sequence")
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    ratio2 = dataclasses.replace(cfg, replay=dataclasses.replace(
        cfg.replay, updates_per_chunk=2))
    for build in (lambda: make_fused_train(ratio2, env, net),
                  lambda: make_mesh_fused_train(
                      ratio2, env, net,
                      make_mesh(devices=jax.devices()[:2]))):
        with pytest.raises(ValueError, match=(
                r"replay\.updates_per_chunk \(the replay-ratio scan\) is "
                r"not supported by the recurrent R2D2 loop yet; leave it at "
                r"1 or use a feed-forward config")):
            build()

    from dist_dqn_tpu.train import train

    pop2 = dataclasses.replace(cfg, population=PopulationConfig(size=2))
    with pytest.raises(ValueError, match=(
            r"--population is not supported by the recurrent \(R2D2\) fused "
            r"loop yet \(its sequence learner has no member axis\)")):
        train(pop2, total_env_steps=80, chunk_iters=10,
              log_fn=lambda s: None)


def test_the_actor_dtype_split_is_the_loops_and_serves_a_recurrent_agent():
    """The one body casts the acting parameters once a chunk for any agent:
    ``network.actor_dtype=bfloat16`` holds for a recurrent configuration
    too. The actor state and the learner's masters stay float32."""
    cfg = _cfg("sequence")
    cfg = dataclasses.replace(cfg, network=dataclasses.replace(
        cfg.network, actor_dtype="bfloat16"))
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_fused_train(cfg, env, net)
    run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
    carry = init(jax.random.PRNGKey(0))
    text = run.lower(carry, 80).as_text()
    assert "bf16" in text
    carry, metrics = run(carry, 80)
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert np.isfinite(float(metrics["loss"]))
    assert {x.dtype for x in jax.tree.leaves(
        (carry.actor_carry, carry.learner.params))} == {np.dtype("float32")}
