"""Distributed learner tests on the virtual 8-device CPU mesh (SURVEY.md §4).

The gradient-allreduce path (shard_map + pmean, replacing the reference's
NCCL allreduce, BASELINE.json:5) is checked for *numerical equivalence*
against the single-device learner, and the full multi-chip fused trainer is
executed end-to-end for both uniform and prioritized replay.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dist_dqn_tpu.agents.dqn import make_learner
from dist_dqn_tpu.config import CONFIGS, LearnerConfig
from dist_dqn_tpu.models.qnets import QNetwork
from dist_dqn_tpu.parallel import make_mesh, make_mesh_fused_train
from dist_dqn_tpu.envs import make_jax_env
from dist_dqn_tpu.models import build_network
from dist_dqn_tpu.types import Transition


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh from conftest")
    return make_mesh()


def _fixed_batch(key, batch, obs_dim=6, num_actions=3):
    ks = jax.random.split(key, 4)
    return Transition(
        obs=jax.random.normal(ks[0], (batch, obs_dim)),
        action=jax.random.randint(ks[1], (batch,), 0, num_actions),
        reward=jax.random.normal(ks[2], (batch,)),
        discount=jnp.full((batch,), 0.97),
        next_obs=jax.random.normal(ks[3], (batch, obs_dim)),
    )


@pytest.mark.parametrize("head", ["dqn", "c51", "qrdqn", "mdqn", "iqn"])
def test_sharded_train_step_matches_single_device(mesh, head):
    """8 learners on batch shards + pmean == 1 learner on the full batch,
    for every head family — INCLUDING IQN, whose tau draws are made
    shard-invariant by folding each example's global batch position into
    the draw key (models/qnets.py sample_quantiles; VERDICT round-3 ask
    #8), so the sharded step sees the exact fractions the full-batch
    step does."""
    if head == "iqn":
        from dist_dqn_tpu.models.qnets import ImplicitQuantileNetwork

        net = ImplicitQuantileNetwork(
            num_actions=3, torso="mlp", mlp_features=(32, 16), hidden=0,
            embed_dim=8, num_tau=4, num_tau_target=4, num_tau_act=4)
    else:
        net_kw = dict(num_actions=3, torso="mlp", mlp_features=(32, 16),
                      hidden=0)
        if head == "c51":
            net_kw.update(num_atoms=11, v_min=-5.0, v_max=5.0)
        elif head == "qrdqn":
            net_kw.update(num_atoms=8, quantile=True)
        net = QNetwork(**net_kw)
    cfg = LearnerConfig(learning_rate=1e-2, munchausen=(head == "mdqn"),
                        double_dqn=(head != "mdqn"))
    init_s, step_s = make_learner(net, cfg)
    _, step_d = make_learner(net, cfg, axis_name="dp")

    state = init_s(jax.random.PRNGKey(0), jnp.zeros((6,)))
    batch = _fixed_batch(jax.random.PRNGKey(1), 32)

    state_spec = jax.tree.map(lambda _: P(), state,
                              is_leaf=lambda x: x is None)
    metric_specs = {"loss": P(), "raw_loss": P(), "priorities": P("dp"),
                    "grad_norm": P(), "mean_q_target_gap": P()}
    dist = jax.jit(jax.shard_map(
        step_d, mesh=mesh,
        in_specs=(state_spec, jax.tree.map(lambda _: P("dp"), batch)),
        out_specs=(state_spec, metric_specs), check_vma=False))

    s1, m1 = jax.jit(step_s)(state, batch)
    s2, m2 = dist(state, batch)

    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    # Priorities are per-example and order-preserving across shards.
    np.testing.assert_allclose(np.asarray(m1["priorities"]),
                               np.asarray(m2["priorities"]), rtol=2e-4,
                               atol=1e-6)


def _tiny_cartpole_cfg(prioritized: bool):
    cfg = CONFIGS["cartpole"]
    return dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(32,)),
        actor=dataclasses.replace(cfg.actor, num_envs=16),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=64,
                                   prioritized=prioritized),
        learner=dataclasses.replace(cfg.learner, batch_size=32),
        total_env_steps=4000,
    )


@pytest.mark.slow
def test_mesh_r2d2_train_runs(mesh):
    """R2D2 across the mesh: sequence replay sharded, learner allreduced."""
    cfg = CONFIGS["r2d2"]
    cfg = dataclasses.replace(
        cfg,
        env_name="cartpole",
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(16,), hidden=0,
                                    lstm_size=8, compute_dtype="float32"),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=64,
                                   burn_in=2, unroll_length=4,
                                   sequence_stride=2),
        learner=dataclasses.replace(cfg.learner, n_step=2, batch_size=32),
        actor=dataclasses.replace(cfg.actor, num_envs=16),
        total_env_steps=4000,
    )
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run = make_mesh_fused_train(cfg, env, net, mesh)
    carry = init(jax.random.PRNGKey(0))
    carry, metrics = run(carry, 40)
    carry, metrics = run(carry, 40)
    assert int(metrics["env_frames"]) == 80 * 16
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert np.isfinite(float(metrics["loss"]))
    p0 = jax.tree.leaves(carry.learner.params)[0]
    assert np.all(np.isfinite(np.asarray(p0)))
    assert len(carry.ep_return.sharding.device_set) == 8


def test_mesh_fused_train_runs_iqn(mesh):
    """The sampled-tau head across the mesh: the learner rng is
    replicated, so every shard draws the SAME tau fractions for its own
    batch shard (shards differ in data, not fractions); grads pmean to
    one replicated parameter set."""
    cfg = _tiny_cartpole_cfg(prioritized=True)
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, iqn=True, iqn_embed_dim=8,
                                    iqn_tau_samples=4,
                                    iqn_tau_target_samples=4,
                                    iqn_tau_act=4))
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run = make_mesh_fused_train(cfg, env, net, mesh)
    carry = init(jax.random.PRNGKey(0))
    carry, metrics = run(carry, 40)
    assert int(metrics["env_frames"]) == 40 * 16
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert np.isfinite(float(metrics["loss"]))
    p0 = jax.tree.leaves(carry.learner.params)[0]
    assert np.all(np.isfinite(np.asarray(p0)))


@pytest.mark.parametrize("prioritized", [False, True])
def test_mesh_fused_train_runs(mesh, prioritized):
    cfg = _tiny_cartpole_cfg(prioritized)
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run = make_mesh_fused_train(cfg, env, net, mesh)
    carry = init(jax.random.PRNGKey(0))
    carry, metrics = run(carry, 40)
    carry, metrics = run(carry, 40)
    assert int(metrics["env_frames"]) == 80 * 16
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert np.isfinite(float(metrics["loss"]))
    # Learner params replicated: one logical value, finite.
    p0 = jax.tree.leaves(carry.learner.params)[0]
    assert np.all(np.isfinite(np.asarray(p0)))
    # Env lanes are sharded across the mesh.
    assert len(carry.ep_return.sharding.device_set) == 8
