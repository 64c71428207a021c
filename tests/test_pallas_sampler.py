"""Pallas priority-sampling kernel (BASELINE.json:5): exactness vs a numpy
inverse-CDF reference (interpret mode on CPU), agreement with the XLA
sampler path, and the fused loop running end to end with the kernel."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.ops.pallas_sampler import pallas_stratified_sample
from dist_dqn_tpu.replay import prioritized_device as pring


def _mass(rng, T, B, zero_frac=0.3):
    w = rng.uniform(0.1, 2.0, (T, B)).astype(np.float32)
    w[rng.uniform(size=(T, B)) < zero_frac] = 0.0
    return w


def test_kernel_matches_numpy_reference():
    rng = np.random.default_rng(0)
    T, B, S = 300, 16, 64
    w = _mass(rng, T, B)
    u = ((np.arange(S) + rng.uniform(size=S)) / S).astype(np.float32)
    t, b, p, tot = map(np.asarray, pallas_stratified_sample(
        jnp.asarray(w).reshape(-1), jnp.asarray(u), B, interpret=True))

    flat = w.reshape(-1)
    cdf = np.cumsum(flat)
    # The first cell whose inclusive CDF reaches the target. Near-total
    # agreement, not exact: the kernel's chunked matmul prefix sums and
    # numpy's sequential cumsum can disagree by an ulp at a stratum
    # boundary.
    ref = np.searchsorted(cdf, u * tot, side="left")
    assert np.mean((t * B + b) == ref) >= 0.98
    np.testing.assert_allclose(p, w[t, b], rtol=1e-6)
    np.testing.assert_allclose(tot, cdf[-1], rtol=1e-5)


def test_kernel_never_selects_zero_mass():
    rng = np.random.default_rng(1)
    T, B, S = 700, 8, 128                   # T % _CHUNK != 0 -> padding path
    w = _mass(rng, T, B, zero_frac=0.9)
    u = ((np.arange(S) + rng.uniform(size=S)) / S).astype(np.float32)
    t, b, p, _ = map(np.asarray, pallas_stratified_sample(
        jnp.asarray(w).reshape(-1), jnp.asarray(u), B, interpret=True))
    assert (p > 0).all()
    assert (w[t, b] > 0).all()
    assert (t < T).all()                    # padded rows never selected


@pytest.mark.parametrize("T,B", [(700, 8), (9, 512), (2100, 128)])
def test_top_of_cdf_stops_at_the_last_row_with_mass(T, B):
    """The kernel carries no margin on its targets: its total is the last
    entry of the CDF it searches, so draws at and just below u = 1 land
    on the last row that holds mass — never on the empty rows after it —
    through the same [rows, 512] view whatever the plane's width."""
    rng = np.random.default_rng(T)
    w = _mass(rng, T, B, zero_frac=0.6) ** 3
    live = T // 3
    w[live:] = 0.0
    w[live - 1, B // 2] = 1e-3              # the last cell with mass
    w[live - 1, B // 2 + 1:] = 0.0
    one = np.float32(1.0)
    u = np.asarray([0.25, 1.0 - 1e-6, np.nextafter(one, np.float32(0)),
                    one], np.float32)
    t, b, p, _ = map(np.asarray, pallas_stratified_sample(
        jnp.asarray(w).reshape(-1), jnp.asarray(u), B, interpret=True))
    assert (w[t, b] > 0).all() and (p > 0).all()
    assert t.max() == live - 1
    assert (t[-1], b[-1]) == (live - 1, B // 2)


def test_kernel_distribution_tracks_mass():
    rng = np.random.default_rng(2)
    T, B, S = 64, 4, 4096
    w = _mass(rng, T, B, zero_frac=0.5)
    u = ((np.arange(S) + rng.uniform(size=S)) / S).astype(np.float32)
    t, b, _, _ = map(np.asarray, pallas_stratified_sample(
        jnp.asarray(w).reshape(-1), jnp.asarray(u), B, interpret=True))
    counts = np.zeros((T, B))
    np.add.at(counts, (t, b), 1.0)
    expect = w / w.sum() * S
    # Stratified sampling: a cell spanning a mass interval of length e
    # buckets receives between ceil(e)-1 and floor(e)+1 points, so every
    # count is strictly within 2 of its expectation (vs ~sqrt(e) noise for
    # iid sampling).
    assert np.abs(counts - expect).max() < 2.0


def test_ring_sampler_pallas_agrees_with_xla():
    state = pring.prioritized_ring_init(128, 4, jnp.zeros((2,)))
    rng = np.random.default_rng(3)
    for tstep in range(100):
        state = pring.prioritized_ring_add(
            state, jnp.full((4, 2), float(tstep)),
            jnp.zeros((4,), jnp.int32),
            jnp.full((4,), rng.normal()), jnp.zeros((4,), bool),
            jnp.zeros((4,), bool))
    state = pring.prioritized_ring_update(
        state, jnp.arange(32, dtype=jnp.int32) % 100,
        jnp.arange(32, dtype=jnp.int32) % 4,
        jnp.asarray(rng.uniform(0.5, 3.0, 32).astype(np.float32)),
        num_envs=4)

    key = jax.random.PRNGKey(0)
    kw = dict(batch_size=64, n_step=3, gamma=0.99, alpha=0.6,
              beta=jnp.float32(0.4), num_envs=4)
    s_xla = pring.prioritized_ring_sample(state, key, **kw)
    s_pal = pring.prioritized_ring_sample(state, key, use_pallas=True,
                                          pallas_interpret=True, **kw)
    agree = np.mean((np.asarray(s_xla.t_idx) == np.asarray(s_pal.t_idx))
                    & (np.asarray(s_xla.b_idx) == np.asarray(s_pal.b_idx)))
    assert agree >= 0.95                    # fp boundary jitter only
    np.testing.assert_allclose(np.asarray(s_pal.weights),
                               np.asarray(s_xla.weights), rtol=1e-3,
                               atol=1e-3)


def test_fused_loop_with_pallas_sampler_runs(monkeypatch):
    monkeypatch.setenv("DIST_DQN_PALLAS_INTERPRET", "1")
    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(16,)),
        replay=dataclasses.replace(cfg.replay, capacity=256, min_fill=32,
                                   prioritized=True, pallas_sampler=True),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
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
    carry, metrics = run(carry, 40)
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert np.isfinite(float(metrics["loss"]))


def test_routing_never_interprets_on_tpu(monkeypatch):
    """On a TPU backend the kernel is compiled by Mosaic or not used:
    neither the routing nor a direct call runs the interpreter there,
    whatever the environment says. Off the chip the config flag alone
    hands back the XLA sampler; the interpreter needs the explicit
    DIST_DQN_PALLAS_INTERPRET=1."""
    from dist_dqn_tpu.loop_common import pallas_routing

    monkeypatch.delenv("DIST_DQN_PALLAS_INTERPRET", raising=False)
    assert pallas_routing(True) == (False, False)
    monkeypatch.setenv("DIST_DQN_PALLAS_INTERPRET", "1")
    assert pallas_routing(True) == (True, True)
    assert pallas_routing(False) == (False, True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_routing(True) == (True, False)
    assert pallas_routing(False) == (False, False)
    with pytest.raises(ValueError, match="never interpreted"):
        pallas_stratified_sample(jnp.ones((8 * 128,)), jnp.full((4,), 0.5),
                                 128, interpret=True)


def test_narrow_plane_draws_match_lane_dense_plane():
    """The kernel sees the flat cells of a narrow plane of 16 lanes
    through a lane-dense view: its draws are those of the XLA sampler on
    the same cells (the apex preset's shape, cut short)."""
    from dist_dqn_tpu.ops.pallas_sampler import stratified_sample_at

    rng = np.random.default_rng(4)
    T, B, S = 1000, 16, 128             # T*B not a multiple of 512
    w = jnp.asarray(_mass(rng, T, B))
    u = jnp.asarray(((np.arange(S) + rng.uniform(size=S)) / S)
                    .astype(np.float32))
    tk, bk, pk, tot = map(np.asarray, stratified_sample_at(
        w.reshape(-1), u, B, use_pallas=True, interpret=True))
    tx, bx, _, _ = map(np.asarray, stratified_sample_at(w.reshape(-1), u, B))
    assert tk.max() < T and bk.max() < B
    assert np.mean((tk == tx) & (bk == bx)) >= 0.95  # fp boundary jitter
    # Same float64 reference as the dense test.
    cdf = np.cumsum(np.asarray(w, np.float64).reshape(-1))
    ref = np.searchsorted(cdf, np.asarray(u) * tot, side="left")
    assert np.mean((tk * B + bk) == ref) >= 0.98
    np.testing.assert_allclose(pk, np.asarray(w)[tk, bk], rtol=1e-6)
