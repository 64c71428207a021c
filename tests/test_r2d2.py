"""R2D2 stack tests: recurrent net step/unroll parity, sequence-ring
storage/seeding/overwrite semantics, learner TD math vs a numpy reference,
and an end-to-end fused-loop learning smoke (SURVEY.md §4)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from dist_dqn_tpu.agents.r2d2 import make_r2d2_learner
from dist_dqn_tpu.config import CONFIGS, LearnerConfig, ReplayConfig
from dist_dqn_tpu.models.recurrent import RecurrentQNetwork
from dist_dqn_tpu.replay import sequence_device as sring
from dist_dqn_tpu.types import SequenceSample

import pytest


def _tiny_net(num_actions=3, lstm=8):
    return RecurrentQNetwork(num_actions=num_actions, torso="mlp",
                             mlp_features=(16,), hidden=0, lstm_size=lstm,
                             dueling=True)


def test_unroll_matches_iterated_steps():
    net = _tiny_net()
    obs = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 4))
    carry0 = net.initial_state(2)
    params = net.init(jax.random.PRNGKey(0), carry0, obs, method=net.unroll)
    _, q_unroll = net.apply(params, carry0, obs, method=net.unroll)
    carry, qs = carry0, []
    for t in range(6):
        carry, qt = net.apply(params, carry, obs[t])
        qs.append(qt)
    np.testing.assert_allclose(np.stack(qs), np.asarray(q_unroll), atol=1e-5)


def test_unroll_reset_restarts_hidden_state():
    net = _tiny_net()
    obs = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 4))
    carry0 = net.initial_state(2)
    params = net.init(jax.random.PRNGKey(0), carry0, obs, method=net.unroll)
    reset = jnp.zeros((6, 2), bool).at[3].set(True)
    _, q_reset = net.apply(params, carry0, obs, reset, method=net.unroll)
    _, q_fresh = net.apply(params, carry0, obs[3:], method=net.unroll)
    np.testing.assert_allclose(np.asarray(q_reset[3:]), np.asarray(q_fresh),
                               atol=1e-5)


def _seq_fill(state, steps, num_envs, seq_len, stride, dones=()):
    for t in range(steps):
        obs = jnp.full((num_envs, 2), float(t))
        carry = (jnp.full((num_envs, 4), float(t)),
                 jnp.full((num_envs, 4), -float(t)))
        state = sring.sequence_ring_add(
            state, obs, jnp.full((num_envs,), t % 3, jnp.int32),
            jnp.full((num_envs,), float(t)),
            jnp.full((num_envs,), t in dones),
            jnp.full((num_envs,), False), carry, seq_len, stride)
    return state


def test_sequence_ring_merged_rows_matches_tiled():
    """Flat [T*B, ...] obs storage (replay.flat_storage for pixel
    sequence rings) is a pure re-layout: the same adds and sample key
    must yield identical sequences, states, and weights."""
    def drive(merge):
        state = sring.sequence_ring_init(12, 2, jnp.zeros((3, 2)),
                                         stored_state=4,
                                         merge_obs_rows=merge)
        for w in range(14):               # wraps past slot 11
            obs = (jnp.full((2, 3, 2), float(w))
                   + jnp.arange(2, dtype=jnp.float32)[:, None, None] * 100)
            carry = (jnp.full((2, 4), float(w)), jnp.zeros((2, 4)))
            state = sring.sequence_ring_add(
                state, obs, jnp.full((2,), w % 3, jnp.int32),
                jnp.full((2,), float(w)),
                jnp.full((2,), w == 6), jnp.zeros((2,), jnp.bool_),
                carry, seq_len=4, stride=1, merge_obs_rows=merge)
        return sring.sequence_ring_sample(
            state, jax.random.PRNGKey(3), batch_size=6, seq_len=4,
            alpha=0.6, beta=jnp.float32(0.4), merge_obs_rows=merge)

    a, b = drive(False), drive(True)
    np.testing.assert_array_equal(np.asarray(a.obs), np.asarray(b.obs))
    for name in ("action", "reward", "done", "reset", "weights",
                 "t_idx", "b_idx"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)))
    for i in range(2):
        np.testing.assert_array_equal(np.asarray(a.start_state[i]),
                                      np.asarray(b.start_state[i]))


def test_sequence_seeding_alignment_and_overwrite():
    # 10 slots, L=4, stride=2: writes 0..9; start w becomes seedable when
    # write w+3 lands; seeded starts are the even write indices.
    state = sring.sequence_ring_init(10, 1, jnp.zeros((2,)), stored_state=4)
    state = _seq_fill(state, 9, 1, seq_len=4, stride=2)
    p = np.asarray(state.priorities)[:, 0]
    # Complete windows start at writes 0..5; stride keeps {0, 2, 4}.
    np.testing.assert_array_equal(p > 0,
                                  [True, False, True, False, True,
                                   False, False, False, False, False])
    # Wrap: writes 9..11 overwrite slots 9, 0, 1 -> start 0 cleared,
    # new starts 6, 8 seeded.
    state = _seq_fill(state, 3, 1, seq_len=4, stride=2)  # writes 9, 10, 11
    p = np.asarray(state.priorities)[:, 0]
    assert p[0] == 0.0 and p[1] == 0.0          # overwritten slots cleared
    assert p[6] > 0 and p[8] > 0                # newly completed starts


def test_sequence_sample_gathers_window_and_state():
    state = sring.sequence_ring_init(16, 2, jnp.zeros((2,)), stored_state=4)
    state = _seq_fill(state, 12, 2, seq_len=4, stride=1, dones=(5,))
    s = sring.sequence_ring_sample(state, jax.random.PRNGKey(0),
                                   batch_size=8, seq_len=4, alpha=0.6,
                                   beta=jnp.float32(0.4))
    obs = np.asarray(s.obs)           # [L=4, S=8, 2]
    start = np.asarray(s.t_idx)
    for i in range(8):
        t0 = obs[0, i, 0]
        np.testing.assert_allclose(obs[:, i, 0], [t0, t0 + 1, t0 + 2, t0 + 3])
        assert float(np.asarray(s.start_state[0])[i, 0]) == t0
        assert float(start[i]) == t0  # no wrap yet: slot == write index
    # reset flags: step after the done at write 5 opens a new episode.
    reset = np.asarray(s.reset)
    obs0 = obs[:, :, 0]
    np.testing.assert_array_equal(reset[1:], obs0[1:] == 6.0)
    assert not reset[0].any()
    assert s.weights.shape == (8,) and float(np.max(np.asarray(s.weights))) <= 1.0


def test_sequence_update_ignores_overwritten_starts():
    state = sring.sequence_ring_init(8, 1, jnp.zeros((2,)), stored_state=4)
    state = _seq_fill(state, 8, 1, seq_len=3, stride=1)
    # Slot 2 is a valid start; slot 7 is not (window incomplete).
    state = sring.sequence_ring_update(
        state, jnp.array([2, 7], jnp.int32), jnp.array([0, 0], jnp.int32),
        jnp.array([5.0, 5.0]))
    p = np.asarray(state.priorities)[:, 0]
    assert p[2] > 4.9 and p[7] == 0.0
    assert float(state.max_priority) >= 5.0


def _numpy_r2d2_targets(q_online, q_target, rewards, dones, actions, burn,
                        unroll, n, gamma):
    """Naive per-sequence reference for the within-window n-step TD error."""
    S = rewards.shape[1]
    td = np.zeros((unroll, S))
    for s in range(S):
        for k in range(unroll):
            ret, disc = 0.0, 1.0
            for j in range(n):
                ret += disc * rewards[burn + k + j, s]
                disc *= gamma * (1.0 - float(dones[burn + k + j, s]))
            a_star = int(np.argmax(q_online[k + n, s]))
            target = ret + disc * q_target[k + n, s, a_star]
            td[k, s] = q_online[k, s, actions[burn + k, s]] - target
    return td


def test_r2d2_learner_td_matches_numpy():
    burn, unroll, n, gamma = 2, 3, 2, 0.9
    L = burn + unroll + n
    S, A = 4, 3
    net = _tiny_net(num_actions=A)
    rng = jax.random.PRNGKey(0)
    obs = jax.random.normal(rng, (L, S, 4))
    sample = SequenceSample(
        obs=obs,
        action=jax.random.randint(jax.random.PRNGKey(1), (L, S), 0, A),
        reward=jax.random.normal(jax.random.PRNGKey(2), (L, S)),
        done=jnp.zeros((L, S), bool).at[4, 1].set(True),
        reset=jnp.zeros((L, S), bool).at[5, 1].set(True),
        start_state=net.initial_state(S),
        weights=jnp.ones((S,)),
        t_idx=jnp.zeros((S,), jnp.int32),
        b_idx=jnp.zeros((S,), jnp.int32),
    )
    lcfg = LearnerConfig(gamma=gamma, n_step=n, double_dqn=True,
                         value_rescale=False, huber_delta=1.0)
    rcfg = ReplayConfig(burn_in=burn, unroll_length=unroll, priority_mix=0.9)
    init, train_step = make_r2d2_learner(net, lcfg, rcfg)
    state = init(jax.random.PRNGKey(3), obs[0, 0])

    # Reference forward pass: same params for online and target (fresh init).
    carry0 = net.initial_state(S)
    _, q_all = net.apply(state.params, carry0, sample.obs, sample.reset,
                         method=net.unroll)
    q_all = np.asarray(q_all)[burn:]
    td_ref = _numpy_r2d2_targets(
        q_all, q_all, np.asarray(sample.reward), np.asarray(sample.done),
        np.asarray(sample.action), burn, unroll, n, gamma)
    prio_ref = 0.9 * np.abs(td_ref).max(0) + 0.1 * np.abs(td_ref).mean(0)

    _, metrics = jax.jit(train_step)(state, sample)
    np.testing.assert_allclose(np.asarray(metrics["priorities"]), prio_ref,
                               atol=1e-4)


@pytest.mark.slow
def test_r2d2_fused_loop_learns_cartpole():
    cfg = CONFIGS["r2d2"]
    cfg = dataclasses.replace(
        cfg,
        env_name="cartpole",
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(64,), hidden=0,
                                    lstm_size=32,
                                    compute_dtype="float32"),
        replay=dataclasses.replace(cfg.replay, capacity=20_000, min_fill=500,
                                   burn_in=4, unroll_length=8,
                                   sequence_stride=4),
        learner=dataclasses.replace(cfg.learner, learning_rate=1e-3,
                                    n_step=2, batch_size=32, gamma=0.99,
                                    target_update_period=250,
                                    value_rescale=True),
        actor=dataclasses.replace(cfg.actor, num_envs=16,
                                  epsilon_decay_steps=15_000),
        total_env_steps=480_000,
        eval_every_steps=20_000,
    )
    from dist_dqn_tpu.train import train
    # SOLVE bar (VERDICT round 2, next #4: lenient bars prove "learning
    # happens", not "works"). Calibrated: eval 500.0 at ~176k frames
    # (~85s) outside pytest; the pytest import environment compiles
    # slightly different float programs and the chaotic trajectory
    # diverges (455.9 max by 240k frames on one run), so the budget
    # carries 2x headroom — verified green UNDER pytest at this budget
    # (passed in 2:05, early-stopped). Early-stops at the bar.
    stop = lambda row: row.get("eval_return", 0.0) >= 475.0  # noqa: E731
    carry, history = train(cfg, chunk_iters=500, log_fn=lambda s: None,
                           stop_fn=stop)
    evals = [row["eval_return"] for row in history if "eval_return" in row]
    assert evals and max(evals) >= 475.0, evals
    assert all(abs(r["loss"]) < 1e3 for r in history)


def test_sequence_sampler_pallas_agrees_with_xla():
    state = sring.sequence_ring_init(64, 4, jnp.zeros((2,)), stored_state=4)
    state = _seq_fill(state, 40, 4, seq_len=4, stride=1, dones=(11, 23))
    key = jax.random.PRNGKey(0)
    kw = dict(batch_size=32, seq_len=4, alpha=0.6, beta=jnp.float32(0.4))
    s_xla = sring.sequence_ring_sample(state, key, **kw)
    s_pal = sring.sequence_ring_sample(state, key, use_pallas=True,
                                       pallas_interpret=True, **kw)
    agree = np.mean((np.asarray(s_xla.t_idx) == np.asarray(s_pal.t_idx))
                    & (np.asarray(s_xla.b_idx) == np.asarray(s_pal.b_idx)))
    assert agree >= 0.95
    np.testing.assert_allclose(np.asarray(s_pal.weights),
                               np.asarray(s_xla.weights), rtol=1e-3,
                               atol=1e-3)


@pytest.mark.slow
def test_r2d2_sharded_train_step_matches_single_device():
    """8 sequence learners on batch shards + pmean == 1 learner full-batch."""
    import pytest
    from jax.sharding import PartitionSpec as P

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh from conftest")
    from dist_dqn_tpu.parallel import make_mesh

    mesh = make_mesh()
    burn, unroll, n = 2, 4, 2
    L, S, A = burn + unroll + n, 16, 3
    net = _tiny_net(num_actions=A)
    rng = jax.random.PRNGKey(0)
    sample = SequenceSample(
        obs=jax.random.normal(rng, (L, S, 4)),
        action=jax.random.randint(jax.random.PRNGKey(1), (L, S), 0, A),
        reward=jax.random.normal(jax.random.PRNGKey(2), (L, S)),
        done=jnp.zeros((L, S), bool).at[3, 2].set(True),
        reset=jnp.zeros((L, S), bool).at[4, 2].set(True),
        start_state=net.initial_state(S),
        weights=jnp.ones((S,)),
        t_idx=jnp.zeros((S,), jnp.int32),
        b_idx=jnp.zeros((S,), jnp.int32),
    )
    lcfg = LearnerConfig(learning_rate=1e-2, gamma=0.95, n_step=n,
                         value_rescale=True)
    rcfg = ReplayConfig(burn_in=burn, unroll_length=unroll)
    init_s, step_s = make_r2d2_learner(net, lcfg, rcfg)
    _, step_d = make_r2d2_learner(net, lcfg, rcfg, axis_name="dp")
    state = init_s(jax.random.PRNGKey(3), sample.obs[0, 0])

    state_spec = jax.tree.map(lambda _: P(), state,
                              is_leaf=lambda x: x is None)
    sample_spec = SequenceSample(
        obs=P(None, "dp"), action=P(None, "dp"), reward=P(None, "dp"),
        done=P(None, "dp"), reset=P(None, "dp"),
        start_state=(P("dp"), P("dp")), weights=P("dp"),
        t_idx=P("dp"), b_idx=P("dp"))
    metric_specs = {"loss": P(), "raw_loss": P(), "priorities": P("dp"),
                    "grad_norm": P()}
    dist = jax.jit(jax.shard_map(
        step_d, mesh=mesh, in_specs=(state_spec, sample_spec),
        out_specs=(state_spec, metric_specs), check_vma=False))

    s1, m1 = jax.jit(step_s)(state, sample)
    s2, m2 = dist(state, sample)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(m1["priorities"]),
                               np.asarray(m2["priorities"]), rtol=2e-4,
                               atol=1e-5)


@pytest.mark.slow
def test_r2d2_fused_loop_with_pallas_sampler_runs(monkeypatch):
    monkeypatch.setenv("DIST_DQN_PALLAS_INTERPRET", "1")
    cfg = CONFIGS["r2d2"]
    cfg = dataclasses.replace(
        cfg,
        env_name="cartpole",
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(16,), hidden=0,
                                    lstm_size=8, compute_dtype="float32"),
        replay=dataclasses.replace(cfg.replay, capacity=512, min_fill=64,
                                   burn_in=2, unroll_length=4,
                                   sequence_stride=2, pallas_sampler=True),
        learner=dataclasses.replace(cfg.learner, n_step=2, batch_size=16),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        total_env_steps=400,
    )
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.train_loop import make_fused_train

    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_fused_train(cfg, env, net)
    run = jax.jit(run_chunk, static_argnums=1)
    carry = init(jax.random.PRNGKey(0))
    carry, metrics = run(carry, 60)
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow
def test_remat_torso_same_params_and_grads():
    """remat is numerics- and checkpoint-transparent: identical param
    structure, outputs, and gradients with the flag on/off."""
    obs = jax.random.normal(jax.random.PRNGKey(1), (5, 3, 4))
    nets = [RecurrentQNetwork(num_actions=3, torso="mlp",
                              mlp_features=(16,), hidden=8, lstm_size=8,
                              dueling=True, remat_torso=flag)
            for flag in (False, True)]
    carry0 = nets[0].initial_state(3)
    params = nets[0].init(jax.random.PRNGKey(0), carry0, obs,
                          method=nets[0].unroll)
    assert (jax.tree.structure(params)
            == jax.tree.structure(nets[1].init(jax.random.PRNGKey(0),
                                               carry0, obs,
                                               method=nets[1].unroll)))

    def loss(p, net):
        _, q = net.apply(p, carry0, obs, method=net.unroll)
        return jnp.sum(q ** 2)

    outs = [jax.value_and_grad(loss)(params, net) for net in nets]
    np.testing.assert_allclose(float(outs[0][0]), float(outs[1][0]),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(outs[0][1]), jax.tree.leaves(outs[1][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)
