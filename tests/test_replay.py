"""Tests for the on-device time-ring replay: storage, wraparound, and exact
n-step/bootstrap semantics at episode boundaries."""
import jax
import jax.numpy as jnp
import numpy as np

from dist_dqn_tpu.replay import device as ring
from dist_dqn_tpu.replay.device import compute_n_step


def _fill(state, steps, num_envs, obs_of=None, rewards=None, term=None,
          trunc=None, store_final=False):
    """Write `steps` slices with obs = step index (broadcast per env)."""
    for t in range(steps):
        obs = (jnp.full((num_envs, 2), float(t)) if obs_of is None
               else obs_of(t))
        state = ring.time_ring_add(
            state, obs,
            jnp.full((num_envs,), t % 3, jnp.int32),
            jnp.full((num_envs,), 0.0 if rewards is None else rewards[t]),
            jnp.full((num_envs,), False if term is None else term[t]),
            jnp.full((num_envs,), False if trunc is None else trunc[t]),
            final_obs=(jnp.full((num_envs, 2), float(t) + 0.5)
                       if store_final else None))
    return state


def test_add_and_wraparound():
    state = ring.time_ring_init(4, 2, jnp.zeros((2,)))
    state = _fill(state, 6, 2)
    assert int(state.size) == 4
    assert int(state.pos) == 2
    # Slots now hold steps [4, 5, 2, 3] (ring order).
    np.testing.assert_allclose(np.asarray(state.obs)[:, 0, 0],
                               [4.0, 5.0, 2.0, 3.0])


def test_compute_n_step_no_done():
    r = jnp.array([[1.0, 2.0, 4.0]])
    z = jnp.zeros((1, 3), bool)
    ret, disc, kstar = compute_n_step(r, z, z, gamma=0.5)
    np.testing.assert_allclose(ret, [1.0 + 1.0 + 1.0])
    np.testing.assert_allclose(disc, [0.125])
    assert int(kstar[0]) == 2


def test_compute_n_step_termination_cuts_window():
    r = jnp.array([[1.0, 2.0, 100.0]])
    term = jnp.array([[False, True, False]])
    trunc = jnp.zeros((1, 3), bool)
    ret, disc, kstar = compute_n_step(r, term, trunc, gamma=0.5)
    # Reward 100 is from the next episode: must not leak in.
    np.testing.assert_allclose(ret, [1.0 + 0.5 * 2.0])
    np.testing.assert_allclose(disc, [0.0])  # terminal: no bootstrap
    assert int(kstar[0]) == 1


def test_compute_n_step_truncation_keeps_bootstrap():
    r = jnp.array([[1.0, 2.0, 100.0]])
    term = jnp.zeros((1, 3), bool)
    trunc = jnp.array([[False, True, False]])
    ret, disc, kstar = compute_n_step(r, term, trunc, gamma=0.5)
    np.testing.assert_allclose(ret, [1.0 + 0.5 * 2.0])
    # Truncated (time-limit) episode still bootstraps: gamma^(k*+1).
    np.testing.assert_allclose(disc, [0.25])
    assert int(kstar[0]) == 1


def test_sample_transitions_consistent():
    """Sampled (obs, next_obs) must be n slots apart when no episode ends."""
    num_envs, n = 3, 2
    state = ring.time_ring_init(64, num_envs, jnp.zeros((2,)))
    state = _fill(state, 50, num_envs, rewards=np.ones(50))
    batch = ring.time_ring_sample(state, jax.random.PRNGKey(0), 128,
                                  n_step=n, gamma=0.9, num_envs=num_envs)
    obs_t = np.asarray(batch.obs)[:, 0]
    next_t = np.asarray(batch.next_obs)[:, 0]
    np.testing.assert_allclose(next_t - obs_t, n)
    np.testing.assert_allclose(np.asarray(batch.reward), 1.9, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(batch.discount), 0.81, rtol=1e-5)


def test_sample_with_termination_mid_window():
    """A terminal at step 10 must cut every window that crosses it."""
    num_envs, steps = 2, 30
    term = np.zeros(steps, bool)
    term[10] = True
    rewards = np.arange(steps, dtype=np.float32)
    state = ring.time_ring_init(64, num_envs, jnp.zeros((2,)))
    state = _fill(state, steps, num_envs, rewards=rewards, term=term)
    batch = ring.time_ring_sample(state, jax.random.PRNGKey(1), 256,
                                  n_step=3, gamma=1.0, num_envs=num_envs)
    obs_t = np.asarray(batch.obs)[:, 0].astype(int)
    for i, t in enumerate(obs_t):
        if t <= 10:
            kstar = min(10 - t, 2)
            want = rewards[t:t + kstar + 1].sum()
            np.testing.assert_allclose(batch.reward[i], want)
            if t + kstar == 10:
                assert float(batch.discount[i]) == 0.0
        else:
            np.testing.assert_allclose(batch.reward[i],
                                       rewards[t:t + 3].sum())


def test_final_obs_used_for_truncation_bootstrap():
    """With final_obs stored, a truncated window bootstraps from the
    pre-reset successor (stored as step + 0.5 in this test)."""
    num_envs, steps = 2, 20
    trunc = np.zeros(steps, bool)
    trunc[7] = True
    state = ring.time_ring_init(32, num_envs, jnp.zeros((2,)),
                                store_final_obs=True)
    state = _fill(state, steps, num_envs, rewards=np.ones(steps),
                  trunc=trunc, store_final=True)
    batch = ring.time_ring_sample(state, jax.random.PRNGKey(2), 256,
                                  n_step=3, gamma=0.9, num_envs=num_envs)
    obs_t = np.asarray(batch.obs)[:, 0]
    next_t = np.asarray(batch.next_obs)[:, 0]
    disc = np.asarray(batch.discount)
    for i, t in enumerate(obs_t.astype(int)):
        if t <= 7 and t + 2 >= 7:  # window crosses the truncation
            kstar = 7 - t
            assert next_t[i] == 7.5  # final_obs of the truncated step
            np.testing.assert_allclose(disc[i], 0.9 ** (kstar + 1),
                                       rtol=1e-6)
        else:
            assert next_t[i] == obs_t[i] + 2.5  # final_obs of step t+2


def test_without_final_obs_truncation_kills_bootstrap():
    num_envs, steps = 2, 20
    trunc = np.zeros(steps, bool)
    trunc[7] = True
    state = ring.time_ring_init(32, num_envs, jnp.zeros((2,)))
    state = _fill(state, steps, num_envs, rewards=np.ones(steps),
                  trunc=trunc)
    batch = ring.time_ring_sample(state, jax.random.PRNGKey(3), 256,
                                  n_step=3, gamma=0.9, num_envs=num_envs)
    obs_t = np.asarray(batch.obs)[:, 0].astype(int)
    disc = np.asarray(batch.discount)
    crossing = (obs_t <= 7) & (obs_t + 2 >= 7)
    assert crossing.any()
    np.testing.assert_allclose(disc[crossing], 0.0)
