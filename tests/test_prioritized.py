"""Prioritized replay tests: device stratified-CDF sampler and host sum-tree
agree with brute-force references and with each other."""
import jax
import jax.numpy as jnp
import numpy as np

from dist_dqn_tpu.replay import device as ring
from dist_dqn_tpu.replay import prioritized_device as pring
import pytest

from dist_dqn_tpu.replay.host import (NativeSumTree, PrioritizedHostReplay,
                                      SumTree, UniformHostReplay,
                                      make_sum_tree)


# ---------------------------------------------------------------------------
# Host sum-tree
# ---------------------------------------------------------------------------

def test_sumtree_set_total_get():
    t = SumTree(10)  # rounds up to 16 leaves
    idx = np.array([0, 3, 7, 9])
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    t.set(idx, vals)
    assert t.total == 10.0
    np.testing.assert_allclose(t.get(idx), vals)
    t.set(np.array([3]), np.array([5.0]))  # overwrite, shared parents
    assert t.total == 13.0


def test_sumtree_sample_proportions():
    t = SumTree(8)
    t.set(np.arange(4), np.array([1.0, 2.0, 3.0, 4.0]))
    rng = np.random.default_rng(0)
    mass = rng.uniform(0, t.total, size=40_000)
    counts = np.bincount(t.sample(mass), minlength=8)
    freq = counts / counts.sum()
    np.testing.assert_allclose(freq[:4], np.array([1, 2, 3, 4]) / 10.0,
                               atol=0.01)
    assert counts[4:].sum() == 0


def test_sumtree_boundary_mass_maps_in_range():
    t = SumTree(4)
    t.set(np.arange(4), np.ones(4))
    idx = t.sample(np.array([0.0, 3.9999999]))
    assert idx[0] == 0 and idx[1] == 3


def test_host_replay_roundtrip_and_priority_update():
    r = PrioritizedHostReplay(capacity=64, alpha=1.0, seed=1)
    items = {"x": np.arange(32, dtype=np.float32)}
    r.add(items, priorities=np.ones(32))
    got, idx, w = r.sample(16, beta=1.0)
    # Sampled x values are the stored ones at the returned indices.
    np.testing.assert_allclose(got["x"], np.arange(32)[idx])
    # Uniform priorities => all IS weights equal (== 1 after normalization).
    np.testing.assert_allclose(w, 1.0)
    # Spike one priority: it should dominate sampling, and IS weights must
    # follow (N * P(i))^-beta normalized by the batch max.
    r.update_priorities(np.array([5]), np.array([1000.0]))
    _, idx2, w2 = r.sample(64, beta=1.0)
    assert (idx2 == 5).mean() > 0.8
    p_sel = r.tree.get(idx2) / r.tree.total
    want = (len(r) * np.maximum(p_sel, 1e-12)) ** -1.0
    want /= want.max()
    np.testing.assert_allclose(w2, want.astype(np.float32), rtol=1e-5)


def test_update_priorities_generation_guard_drops_stale_writes():
    """A deferred priority write-back must not stamp old |TD| values onto
    slots that were overwritten while the train step was in flight."""
    r = PrioritizedHostReplay(capacity=8, alpha=1.0, seed=3)
    r.add({"x": np.arange(8, dtype=np.float32)}, priorities=np.ones(8))
    idx = np.arange(4)
    gen = r.generation(idx)
    # Ring wraps: slots 0..3 now hold NEW transitions (priority 1.0).
    r.add({"x": np.full(4, 50.0, np.float32)}, priorities=np.ones(4))
    r.update_priorities(idx, np.full(4, 99.0), expected_gen=gen)
    np.testing.assert_allclose(r.tree.get(idx), np.ones(4) + r.priority_eps)
    # Without the guard the same call does overwrite (documented contract).
    r.update_priorities(idx, np.full(4, 99.0))
    assert (r.tree.get(idx) > 90).all()
    # Partial overlap: only the overwritten half is dropped.
    r2 = PrioritizedHostReplay(capacity=8, alpha=1.0, seed=4)
    r2.add({"x": np.arange(8, dtype=np.float32)}, priorities=np.ones(8))
    idx2 = np.array([0, 1, 6, 7])
    gen2 = r2.generation(idx2)
    r2.add({"x": np.full(2, 9.0, np.float32)}, priorities=np.ones(2))
    r2.update_priorities(idx2, np.full(4, 99.0), expected_gen=gen2)
    np.testing.assert_allclose(r2.tree.get([0, 1]),
                               np.ones(2) + r2.priority_eps)
    assert (r2.tree.get([6, 7]) > 90).all()


def test_host_replay_wraparound_overwrites():
    r = PrioritizedHostReplay(capacity=8, alpha=1.0, seed=2)
    r.add({"x": np.arange(8, dtype=np.float32)}, priorities=np.ones(8))
    r.add({"x": np.full(4, 99.0, np.float32)}, priorities=np.ones(4))
    got, _, _ = r.sample(256, beta=0.0)
    vals = set(np.unique(got["x"]))
    assert 0.0 not in vals and 3.0 not in vals  # overwritten slots gone
    assert 99.0 in vals and 4.0 in vals


def test_native_sumtree_matches_numpy():
    """The C++ tree and the numpy tree are drop-in replacements: identical
    totals, leaf reads, and descent results (tie semantics included) across
    random batched writes, overwrites, and samples."""
    cap = 37  # non-power-of-two: both pad to 64
    nat, ref = NativeSumTree(cap), SumTree(cap)
    assert nat.capacity == ref.capacity == 64
    rng = np.random.default_rng(7)
    for _ in range(20):
        n = int(rng.integers(1, 48))
        idx = rng.integers(0, cap, size=n)  # duplicates allowed
        vals = rng.uniform(0.0, 5.0, size=n)
        # Duplicate leaf writes in one batch: numpy fancy-assign keeps the
        # *last* value per index; apply the same contract to both trees.
        _, last = np.unique(idx[::-1], return_index=True)
        keep = n - 1 - last
        nat.set(idx[keep], vals[keep])
        ref.set(idx[keep], vals[keep])
        np.testing.assert_allclose(nat.total, ref.total, rtol=1e-12)
        probe = rng.integers(0, cap, size=16)
        np.testing.assert_allclose(nat.get(probe), ref.get(probe))
        mass = rng.uniform(0.0, ref.total, size=256)
        np.testing.assert_array_equal(nat.sample(mass), ref.sample(mass))


def test_native_sumtree_rebuild_is_exact():
    nat = NativeSumTree(16)
    rng = np.random.default_rng(11)
    for _ in range(50):
        nat.set(rng.integers(0, 16, size=8), rng.uniform(size=8))
    leaves = nat.get(np.arange(16))
    nat._lib.dqn_tree_rebuild(nat._h)
    np.testing.assert_allclose(nat.total, leaves.sum(), rtol=1e-12)
    assert nat._lib.dqn_tree_writes(nat._h) == 0


def test_sumtrees_reject_out_of_range_indices():
    for tree in (NativeSumTree(16), SumTree(16)):
        for bad in (np.array([16]), np.array([-1]), np.array([3, 99])):
            for op in (lambda: tree.set(bad, np.ones(bad.shape[0])),
                       lambda: tree.get(bad)):
                try:
                    op()
                    assert False, f"expected IndexError for idx={bad}"
                except IndexError:
                    pass


def test_device_sampled_host_replay_matches_tree_distribution():
    """sampler="device" (priority plane on the accelerator, Pallas/XLA
    stratified draws) must produce the same P(i) ~ p^alpha distribution
    and IS-weight formula as the host tree path."""
    r = PrioritizedHostReplay(capacity=64, alpha=1.0, seed=5,
                              sampler="device")
    assert r.device_sampler is not None
    x = np.arange(48, dtype=np.float32)
    pr = np.linspace(0.5, 4.0, 48)
    r.add({"x": x}, priorities=pr)
    counts = np.zeros(64)
    w_seen = None
    for _ in range(40):
        items, idx, w = r.sample(256, beta=1.0)
        np.testing.assert_allclose(items["x"], x[idx])
        counts += np.bincount(idx, minlength=64)
        w_seen = (idx, w)
    freq = counts[:48] / counts.sum()
    np.testing.assert_allclose(freq, pr / pr.sum(), atol=0.01)
    assert counts[48:].sum() == 0          # empty slots never sampled
    # IS weights follow (N * P(i))^-beta, batch-max-normalized.
    idx, w = w_seen
    p_sel = pr[idx] / pr.sum()
    want = (48 * p_sel) ** -1.0
    np.testing.assert_allclose(w, (want / want.max()).astype(np.float32),
                               rtol=1e-4)
    # Priority updates flow through: spike one slot, it dominates.
    r.update_priorities(np.array([7]), np.array([1000.0]))
    _, idx2, _ = r.sample(256, beta=0.5)
    assert (idx2 == 7).mean() > 0.8


def test_device_sampler_pallas_interpret_path():
    """The same flow through the actual Pallas kernel (interpret mode)."""
    from dist_dqn_tpu.replay.host import DevicePrioritySampler

    s = DevicePrioritySampler(capacity=1024, lanes=128, seed=1,
                              use_pallas=True, interpret=True)
    pr = np.linspace(1.0, 3.0, 700).astype(np.float32)
    s.set(np.arange(700), pr)
    idx, w = s.sample(512, beta=1.0, size=700)
    assert idx.min() >= 0 and idx.max() < 700
    assert w.max() == 1.0 and (w > 0).all()
    counts = np.bincount(idx, minlength=1024)
    assert counts[700:].sum() == 0


def test_make_sum_tree_backend_selection():
    assert isinstance(make_sum_tree(8, native=True), NativeSumTree)
    assert isinstance(make_sum_tree(8, native=False), SumTree)
    assert isinstance(PrioritizedHostReplay(8).tree, NativeSumTree)


def test_make_sum_tree_default_raises_when_native_build_fails(monkeypatch):
    """A native library that cannot be built is an error on the default
    path — never a quiet switch to the numpy tree; ``native=False`` stays
    the explicit way to get that tree."""
    from dist_dqn_tpu.replay import host

    def broken_build():
        raise OSError("g++ failed")

    monkeypatch.setattr(host, "_native_tree_lib", broken_build)
    for native in (None, True):
        with pytest.raises(OSError, match="g\\+\\+ failed"):
            make_sum_tree(8, native=native)
    with pytest.raises(OSError):
        PrioritizedHostReplay(8)
    assert isinstance(make_sum_tree(8, native=False), SumTree)


# ---------------------------------------------------------------------------
# Device stratified-CDF sampler
# ---------------------------------------------------------------------------

def _device_state(num_slots=16, num_envs=2, steps=12, priorities=None):
    st = pring.prioritized_ring_init(num_slots, num_envs, jnp.zeros((2,)))
    for t in range(steps):
        st = pring.prioritized_ring_add(
            st, jnp.full((num_envs, 2), float(t)),
            jnp.zeros((num_envs,), jnp.int32),
            jnp.ones((num_envs,)),
            jnp.zeros((num_envs,), bool), jnp.zeros((num_envs,), bool))
    if priorities is not None:
        st = st._replace(priorities=jnp.asarray(priorities).reshape(-1))
    return st


def test_device_sample_proportional_to_priority_alpha():
    num_slots, num_envs, steps, n = 16, 2, 12, 2
    pr = np.zeros((num_slots, num_envs), np.float32)
    pr[:steps] = np.random.default_rng(3).uniform(
        0.1, 2.0, size=(steps, num_envs))
    st = _device_state(num_slots, num_envs, steps, pr)
    alpha = 0.6
    sample = pring.prioritized_ring_sample(
        st, jax.random.PRNGKey(0), 4096, n_step=n, gamma=0.99, alpha=alpha,
        beta=jnp.float32(1.0), num_envs=num_envs)
    # Valid starts: slots [0, steps - n) across both envs.
    valid = pr[:steps - n] ** alpha
    expect = valid / valid.sum()
    counts = np.zeros_like(expect)
    t_np, b_np = np.asarray(sample.t_idx), np.asarray(sample.b_idx)
    for t, b in zip(t_np, b_np):
        assert t < steps - n, "sampled an invalid window start"
        counts[t, b] += 1
    np.testing.assert_allclose(counts / counts.sum(), expect, atol=0.02)


def test_device_weights_match_formula():
    num_slots, num_envs, steps, n = 8, 1, 6, 1
    pr = np.zeros((num_slots, num_envs), np.float32)
    pr[:steps, 0] = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    st = _device_state(num_slots, num_envs, steps, pr)
    beta = 0.5
    s = pring.prioritized_ring_sample(
        st, jax.random.PRNGKey(1), 512, n_step=n, gamma=0.99, alpha=1.0,
        beta=jnp.float32(beta), num_envs=num_envs)
    valid = pr[:steps - n, 0]
    total, n_valid = valid.sum(), len(valid)
    p_sel = valid[np.asarray(s.t_idx)] / total
    want = (n_valid * p_sel) ** (-beta)
    want = want / want.max()
    np.testing.assert_allclose(np.asarray(s.weights), want, rtol=1e-4)


def test_device_update_and_max_priority_seeding():
    st = _device_state(steps=10)
    st = pring.prioritized_ring_update(
        st, jnp.array([2, 3]), jnp.array([0, 0]), jnp.array([7.0, 0.5]),
        num_envs=2)
    assert float(st.max_priority) >= 7.0
    np.testing.assert_allclose(st.priorities[2 * 2 + 0], 7.0 + 1e-6,
                               rtol=1e-5)
    # The next added slice is seeded at the new max.
    st2 = pring.prioritized_ring_add(
        st, jnp.zeros((2, 2)), jnp.zeros((2,), jnp.int32), jnp.ones((2,)),
        jnp.zeros((2,), bool), jnp.zeros((2,), bool))
    np.testing.assert_allclose(st2.priorities[10 * 2:11 * 2],
                               float(st.max_priority))


def test_device_sample_payload_matches_uniform_semantics():
    """The prioritized gather must produce the same transition contents as
    the uniform sampler's shared gather path."""
    st = _device_state(steps=12)
    s = pring.prioritized_ring_sample(
        st, jax.random.PRNGKey(4), 64, n_step=2, gamma=0.9, alpha=0.0,
        beta=jnp.float32(1.0), num_envs=2)
    ref = ring.gather_transitions(st.ring, s.t_idx, s.b_idx, 2, 0.9, 2)
    np.testing.assert_allclose(s.batch.obs, ref.obs)
    np.testing.assert_allclose(s.batch.reward, ref.reward)
    np.testing.assert_allclose(s.batch.discount, ref.discount)


@pytest.mark.slow
def test_fused_loop_with_per_learns_cartpole():
    """PER-enabled fused loop end-to-end on CartPole (smoke + learning)."""
    import dataclasses
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg, replay=dataclasses.replace(cfg.replay, prioritized=True,
                                        priority_exponent=0.6,
                                        importance_exponent=0.4))
    carry, history = train(cfg, total_env_steps=48_000, chunk_iters=1000,
                           log_fn=lambda s: None)
    best = max(max((r.get("eval_return", 0) for r in history)),
               max(r["episode_return"] for r in history))
    assert best >= 100.0, history


def _filled_shard(sampler="tree", n=96, capacity=64, seed=3):
    """A shard driven past wraparound with mixed priorities."""
    rep = PrioritizedHostReplay(capacity, alpha=0.6, seed=seed,
                                sampler=sampler)
    r = np.random.default_rng(seed)
    for start in range(0, n, 16):
        items = {"obs": r.normal(size=(16, 5)).astype(np.float32),
                 "action": r.integers(0, 3, 16).astype(np.int32)}
        rep.add(items, priorities=r.uniform(0.1, 2.0, 16))
    return rep


@pytest.mark.parametrize("sampler", ["tree", "device"])
def test_host_replay_snapshot_roundtrip(sampler):
    """state_dict/load_state_dict (VERDICT round-3 next #7): a restored
    shard reproduces contents, cursor, counters, and the priority mass —
    sampling from the restored shard draws the same items with the same
    IS-weight scale as the original."""
    rep = _filled_shard(sampler=sampler)
    state = rep.state_dict()

    rep2 = PrioritizedHostReplay(rep.capacity, alpha=0.6, seed=99,
                                 sampler=sampler)
    rep2.load_state_dict(state)
    assert len(rep2) == len(rep)
    assert rep2.added == rep.added and rep2._pos == rep._pos
    np.testing.assert_array_equal(rep2._slot_gen, rep._slot_gen)
    for k in rep._data:
        np.testing.assert_array_equal(rep2._data[k], rep._data[k])
    if sampler == "tree":
        idx = np.arange(rep.capacity, dtype=np.int64)
        np.testing.assert_allclose(rep2.tree.get(idx), rep.tree.get(idx),
                                   rtol=1e-6)
    else:
        rep.device_sampler._flush_writes()
        rep2.device_sampler._flush_writes()
        np.testing.assert_allclose(np.asarray(rep2.device_sampler._plane),
                                   np.asarray(rep.device_sampler._plane),
                                   rtol=1e-6)
    # The generation guard survives the round-trip: stale write-backs
    # captured before the snapshot are still dropped after restore.
    items, idx, _ = rep2.sample(8, beta=0.4)
    gen = rep2.generation(idx)
    rep2.add({"obs": np.zeros((64, 5), np.float32),
              "action": np.zeros(64, np.int32)})  # overwrite everything
    rep2.update_priorities(idx, np.full(8, 123.0), expected_gen=gen)
    if sampler == "tree":
        assert rep2.tree.get(idx).max() < 100.0 ** 0.6


def test_host_replay_snapshot_rejects_mismatched_shape():
    rep = _filled_shard()
    state = rep.state_dict()
    other = PrioritizedHostReplay(128, alpha=0.6)
    with pytest.raises(ValueError, match="capacity"):
        other.load_state_dict(state)
    other = PrioritizedHostReplay(rep.capacity, alpha=0.5)
    with pytest.raises(ValueError, match="alpha"):
        other.load_state_dict(state)


def test_uniform_host_replay_snapshot_roundtrip():
    rep = UniformHostReplay(32, seed=1)
    r = np.random.default_rng(0)
    rep.add({"obs": r.normal(size=(20, 4)).astype(np.float32)})
    state = rep.state_dict()
    rep2 = UniformHostReplay(32, seed=2)
    rep2.load_state_dict(state)
    assert len(rep2) == 20 and rep2._pos == rep._pos
    np.testing.assert_array_equal(rep2._data["obs"], rep._data["obs"])
