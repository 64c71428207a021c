"""Frame-dedup ring storage (replay.frame_dedup): single stored frames +
sample-time stack rebuild must be EXACTLY equal to storing full stacks —
including reset-boundary re-tiling, ring wrap-around, both storage
layouts, and the prioritized plane (VERDICT round-4 next #2: the 4x HBM
saving that lifts the v5e pixel window toward 1M transitions)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.replay import device as ring

H, W, S = 6, 5, 4


def _rolling_stream(rng, steps, lanes, done=None):
    """Synthesize (obs[t], action, reward, term, trunc) honoring the
    rolling-stack contract the pixel envs declare (envs/base.py):
    obs shifts one frame per step; a done at t re-tiles obs_{t+1}.
    ``done`` [steps, lanes] places the episode ends by hand."""
    frames = rng.integers(0, 255, (steps + 1, lanes, H, W), np.uint8)
    if done is None:
        done = rng.random((steps, lanes)) < 0.25
    term = np.logical_and(done, rng.random((steps, lanes)) < 0.5)
    trunc = np.logical_and(done, ~term)
    obs = np.zeros((steps, lanes, H, W, S), np.uint8)
    cur = np.repeat(frames[0][..., None], S, axis=-1)  # reset: tiled
    for t in range(steps):
        obs[t] = cur
        nxt = np.concatenate([cur[..., 1:], frames[t + 1][..., None]],
                             axis=-1)
        tiled = np.repeat(frames[t + 1][..., None], S, axis=-1)
        cur = np.where(done[t][:, None, None, None], tiled, nxt)
    action = rng.integers(0, 6, (steps, lanes)).astype(np.int32)
    reward = rng.normal(size=(steps, lanes)).astype(np.float32)
    return obs, action, reward, term, trunc


def _fill(state, obs, action, reward, term, trunc, dedup, merge,
          add=ring.time_ring_add):
    for t in range(obs.shape[0]):
        o = obs[t][..., -1:] if dedup else obs[t]
        if merge:
            o = o.reshape(o.shape[0], -1)
        state = add(
            state, jnp.asarray(o), jnp.asarray(action[t]),
            jnp.asarray(reward[t]), jnp.asarray(term[t]),
            jnp.asarray(trunc[t]), merge_obs_rows=merge)
    return state


@pytest.mark.parametrize("merge", [False, True])
@pytest.mark.parametrize("steps,slots", [(40, 64), (200, 64)])
def test_dedup_gather_exactly_matches_stacked(merge, steps, slots):
    """Every field of gathered transitions is bitwise identical between
    full-stack storage and dedup storage, at identical (t, b) indices —
    covering unwrapped (40 < 64) and wrapped (200 > 64) rings."""
    rng = np.random.default_rng(0)
    lanes, n_step = 3, 3
    obs, action, reward, term, trunc = _rolling_stream(rng, steps, lanes)

    full = ring.time_ring_init(
        slots, lanes,
        jnp.zeros((H * W * S,) if merge else (H, W, S), jnp.uint8),
        merge_obs_rows=merge)
    dd = ring.time_ring_init(
        slots, lanes,
        jnp.zeros((H * W,) if merge else (H, W, 1), jnp.uint8),
        merge_obs_rows=merge)
    full = _fill(full, obs, action, reward, term, trunc, False, merge)
    dd = _fill(dd, obs, action, reward, term, trunc, True, merge)

    size = min(steps, slots)
    # Valid dedup starts: skip the oldest S-1 (no rebuild context).
    offsets = np.arange(S - 1, size - n_step)
    oldest = (steps - size) % slots
    t_idx = jnp.asarray((oldest + offsets) % slots, jnp.int32)
    reps = (len(offsets) + lanes - 1) // lanes
    b_idx = jnp.asarray(np.tile(np.arange(lanes), reps)[:len(offsets)],
                        jnp.int32)

    a = ring.gather_transitions(full, t_idx, b_idx, n_step, 0.97, lanes,
                                merge_obs_rows=merge)
    b = ring.gather_transitions(dd, t_idx, b_idx, n_step, 0.97, lanes,
                                merge_obs_rows=merge, frame_stack=S,
                                frame_shape=(H, W, 1))
    a_obs = np.asarray(a.obs).reshape(len(offsets), H, W, S)
    a_next = np.asarray(a.next_obs).reshape(len(offsets), H, W, S)
    np.testing.assert_array_equal(a_obs, np.asarray(b.obs))
    # next_obs only matters where the bootstrap is live; the stacked
    # ring's post-reset next_obs at done boundaries is itself a reset
    # stack, which dedup rebuilds identically — so compare everywhere.
    np.testing.assert_array_equal(a_next, np.asarray(b.next_obs))
    np.testing.assert_array_equal(np.asarray(a.action), np.asarray(b.action))
    np.testing.assert_array_equal(np.asarray(a.reward), np.asarray(b.reward))
    np.testing.assert_array_equal(np.asarray(a.discount),
                                  np.asarray(b.discount))


@pytest.mark.parametrize("sampler", ["uniform", "prioritized"])
@pytest.mark.parametrize("merge", [False, True])
@pytest.mark.parametrize("steps,slots", [(5, 8), (12, 5)])  # unwrapped / wrapped
@pytest.mark.parametrize("j", range(1, S))
@pytest.mark.parametrize("leaf", ["obs", "next_obs"])
def test_dedup_reset_at_every_lookback_distance(leaf, j, steps, slots, merge,
                                                sampler):
    """A reset exactly ``j`` steps before the sampled slot (``obs``) or
    before its bootstrap slot (``next_obs``), for every j in 1..S-1: the
    stacks both samplers return are the rolling-stack stream's, bitwise.
    The ring holds S + 1 steps, so n_step=1 leaves ONE valid start and the
    draw cannot miss the reset; lane 1 has no reset at all; each step's
    action names its (step, lane), which the oracle reads back."""
    from dist_dqn_tpu.replay import prioritized_device as pring

    lanes, n_step, batch = 2, 1, 32
    start = steps - 1 - n_step                 # the one valid window start
    anchor = start if leaf == "obs" else start + n_step
    done = np.zeros((steps, lanes), bool)
    done[anchor - j, 0] = True
    obs, _, reward, term, trunc = _rolling_stream(
        np.random.default_rng(10 * j + steps), steps, lanes, done=done)
    action = np.arange(steps * lanes, dtype=np.int32).reshape(steps, lanes)

    stored = jnp.zeros((H * W,) if merge else (H, W, 1), jnp.uint8)
    kw = dict(merge_obs_rows=merge, frame_stack=S, frame_shape=(H, W, 1))
    if sampler == "uniform":
        st = ring.time_ring_init(slots, lanes, stored, merge_obs_rows=merge)
        st = _fill(st, obs, action, reward, term, trunc, True, merge)
        got = ring.time_ring_sample(st, jax.random.PRNGKey(j), batch, n_step,
                                    0.97, lanes, **kw)
    else:
        st = pring.prioritized_ring_init(slots, lanes, stored,
                                         merge_obs_rows=merge)
        st = _fill(st, obs, action, reward, term, trunc, True, merge,
                   add=pring.prioritized_ring_add)
        got = pring.prioritized_ring_sample(
            st, jax.random.PRNGKey(j), batch, n_step, 0.97, alpha=0.6,
            beta=jnp.float32(0.4), num_envs=lanes, **kw).batch

    t, b = np.divmod(np.asarray(got.action), lanes)
    assert (t == start).all() and set(b) == {0, 1}
    np.testing.assert_array_equal(np.asarray(got.obs), obs[t, b])
    # The stream's post-reset obs IS the tiled stack the rebuild returns.
    np.testing.assert_array_equal(np.asarray(got.next_obs),
                                  obs[t + n_step, b])


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit, cond, scan) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_dedup_rebuild_is_one_row_gather_per_leaf():
    """What carries over from the CPU to the chip is the program's shape:
    on a flat ring the rebuild reads the obs buffer with ONE row gather for
    ``obs`` and one for ``next_obs`` (not one per frame), and never
    concatenates size-1-minor frames — the form XLA:TPU lowers to a
    pad-and-add pass over transposed [N, H, W, 1] copies (PERF.md, PR 28)."""
    slots, lanes, n = 16, 4, 8
    st = ring.time_ring_init(slots, lanes, jnp.zeros((H * W,), jnp.uint8),
                             merge_obs_rows=True)
    idx = jnp.zeros((n,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda s, t, b: ring.gather_transitions(
            s, t, b, 3, 0.97, lanes, merge_obs_rows=True, frame_stack=S,
            frame_shape=(H, W, 1)))(st, idx, idx)
    assert jaxpr.out_avals[0].shape == (n, H, W, S)  # Transition.obs
    eqns = list(_eqns(jaxpr.jaxpr))
    ring_reads = [e for e in eqns if e.primitive.name == "gather"
                  and e.invars[0].aval.shape == st.obs.shape
                  and e.invars[0].aval.dtype == jnp.uint8]
    assert 1 <= len(ring_reads) <= 2, len(ring_reads)
    frame_concats = [e for e in eqns if e.primitive.name == "concatenate"
                     and e.invars[0].aval.dtype == jnp.uint8
                     and e.invars[0].aval.shape[-1] == 1]
    assert not frame_concats


def test_dedup_uniform_sample_range_excludes_contextless_slots():
    """time_ring_sample with frame_stack must never draw a start whose
    rebuild context is unstored (the oldest S-1 slots)."""
    rng = np.random.default_rng(1)
    lanes, slots, steps, n_step = 2, 32, 20, 2
    obs, action, reward, term, trunc = _rolling_stream(rng, steps, lanes)
    dd = ring.time_ring_init(slots, lanes, jnp.zeros((H, W, 1), jnp.uint8))
    dd = _fill(dd, obs, action, reward, term, trunc, True, False)
    # 20 steps stored at slots 0..19; dedup-valid starts are 3..15.
    for seed in range(5):
        batch = ring.time_ring_sample(dd, jax.random.PRNGKey(seed), 64,
                                      n_step, 0.97, lanes, frame_stack=S,
                                      frame_shape=(H, W, 1))
        assert batch.obs.shape == (64, H, W, S)
    assert bool(ring.time_ring_can_sample(dd, n_step, frame_stack=S))


def test_dedup_prioritized_mask_and_gather():
    """The PER plane's valid-start mask excludes the contextless oldest
    slots and the prioritized gather returns rebuilt stacks."""
    from dist_dqn_tpu.replay import prioritized_device as pring

    rng = np.random.default_rng(2)
    lanes, slots, steps, n_step = 2, 32, 20, 2
    obs, action, reward, term, trunc = _rolling_stream(rng, steps, lanes)
    st = pring.prioritized_ring_init(slots, lanes,
                                     jnp.zeros((H, W, 1), jnp.uint8))
    st = _fill(st, obs, action, reward, term, trunc, True, False,
               add=pring.prioritized_ring_add)
    mask = np.asarray(pring._valid_start_mask(st.ring, n_step, S, slots))
    assert not mask[:S - 1].any()          # contextless slots excluded
    assert mask[S - 1:steps - n_step].all()
    s = pring.prioritized_ring_sample(st, jax.random.PRNGKey(0), 32,
                                      n_step, 0.97, alpha=0.6,
                                      beta=jnp.float32(0.4), num_envs=lanes,
                                      frame_stack=S, frame_shape=(H, W, 1))
    assert s.batch.obs.shape == (32, H, W, S)
    assert bool((np.asarray(s.t_idx) >= S - 1).all())


@pytest.mark.parametrize("merge", [False, True])
@pytest.mark.parametrize("steps", [30, 150])  # unwrapped / wrapped (slots=64)
def test_sequence_dedup_rebuild_matches_stacked(merge, steps):
    """The R2D2 sequence ring's dedup rebuild: [L, S_] windows from
    single stored frames are bitwise identical to windows gathered from
    full-stack storage, at identical (t, b) starts — across resets and
    ring wrap."""
    from dist_dqn_tpu.replay import sequence_device as sring

    rng = np.random.default_rng(3)
    lanes, slots, L = 3, 64, 6
    obs, action, reward, term, trunc = _rolling_stream(rng, steps, lanes)
    carry = (np.zeros((lanes, 4), np.float32),
             np.zeros((lanes, 4), np.float32))

    def fill(dedup):
        stored = obs[..., -1:] if dedup else obs
        shape = (H * W * stored.shape[-1],) if merge else stored.shape[2:]
        st = sring.sequence_ring_init(slots, lanes,
                                      jnp.zeros(shape, jnp.uint8), 4,
                                      merge_obs_rows=merge)
        for t in range(steps):
            o = stored[t].reshape(lanes, -1) if merge else stored[t]
            st = sring.sequence_ring_add(
                st, jnp.asarray(o), jnp.asarray(action[t]),
                jnp.asarray(reward[t]), jnp.asarray(term[t]),
                jnp.asarray(trunc[t]), tuple(map(jnp.asarray, carry)),
                L, 3, merge_obs_rows=merge)
        return st

    full, dd = fill(False), fill(True)
    size = min(steps, slots)
    # Valid dedup starts: context stored AND the full window stored.
    offsets = np.arange(S - 1, size - L)
    oldest = (steps - size) % slots
    t_idx = jnp.asarray((oldest + offsets) % slots, jnp.int32)
    b_idx = jnp.asarray(
        np.tile(np.arange(lanes),
                (len(offsets) + lanes - 1) // lanes)[:len(offsets)],
        jnp.int32)

    want = (full.ring.obs.reshape(slots, lanes, H, W, S) if merge
            else full.ring.obs)[sring._window_slots(t_idx, L, slots),
                                b_idx[None, :]]
    got = sring._rebuild_seq_stacks(dd.ring, t_idx, b_idx, L, S,
                                    merge, (H, W, 1), slots, lanes)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# The hand-placed stream of the sequence-rebuild cases: 44 steps into 32
# slots (steps 12..43 stored, step t in slot t % 32), windows of L = 6. A
# window wraps the ring's end when it starts at steps 27..31. One episode
# end a lane, so that a window sees the one it asks for: lane 0 has none.
_SEQ_L, _SEQ_SLOTS, _SEQ_STEPS = 6, 32, 44
_SEQ_END = {("inside", False): (1, 20), ("before", False): (2, 20),
            ("inside", True): (3, 31), ("before", True): (4, 27)}


@functools.lru_cache(maxsize=None)
def _seq_rings(merge, dtype=np.uint8):
    """(stream's done flags, stacked ring, dedup ring) of that stream."""
    from dist_dqn_tpu.replay import sequence_device as sring

    rng = np.random.default_rng(11)
    lanes = 1 + len(_SEQ_END)
    done = np.zeros((_SEQ_STEPS, lanes), bool)
    for lane, step in _SEQ_END.values():
        done[step, lane] = True
    obs, action, reward, term, trunc = _rolling_stream(rng, _SEQ_STEPS,
                                                       lanes, done=done)
    obs = obs.astype(dtype)
    carry = (jnp.zeros((lanes, 4), jnp.float32),) * 2
    add = jax.jit(functools.partial(
        sring.sequence_ring_add, seq_len=_SEQ_L, stride=1,
        merge_obs_rows=merge))

    def fill(stored):
        shape = (H * W * stored.shape[-1],) if merge else stored.shape[2:]
        st = sring.sequence_ring_init(_SEQ_SLOTS, lanes,
                                      jnp.zeros(shape, dtype), 4,
                                      merge_obs_rows=merge)
        for t in range(_SEQ_STEPS):
            o = stored[t].reshape(lanes, -1) if merge else stored[t]
            st = add(st, jnp.asarray(o), jnp.asarray(action[t]),
                     jnp.asarray(reward[t]), jnp.asarray(term[t]),
                     jnp.asarray(trunc[t]), carry)
        return st

    return done, fill(obs), fill(obs[..., -1:])


def _stacked_windows(full, merge, t_idx, b_idx):
    """The stacked ring's windows at those starts: what a rebuild owes."""
    from dist_dqn_tpu.replay import sequence_device as sring

    obs = full.ring.obs
    if merge:
        obs = obs.reshape(_SEQ_SLOTS, -1, H, W, S)
    return obs[sring._window_slots(t_idx, _SEQ_L, _SEQ_SLOTS),
               b_idx[None, :]]


@pytest.mark.parametrize("same_lane", [False, True])
@pytest.mark.parametrize("wrap", [False, True])
@pytest.mark.parametrize("where", ["inside", "before"])
@pytest.mark.parametrize("age", [0, 1, 2, None])
@pytest.mark.parametrize("merge", [False, True])
def test_sequence_rebuild_at_every_end_position(merge, age, where, wrap,
                                                same_lane):
    """The sequence ring's rebuild against the stacked ring's window
    gather, byte for byte, with the episode end at every distance of the
    lookback (``age`` 0, 1, 2; None: no end in sight) from the window's
    LAST position (``inside``) or its FIRST (``before``: the end lies in
    the context the extended gather fetches), in a window that wraps the
    ring's end or does not, beside a second window of the same lane (one
    step on, so the two overlap) or of another."""
    from dist_dqn_tpu.replay import sequence_device as sring

    done, full, dd = _seq_rings(merge)
    L, slots = _SEQ_L, _SEQ_SLOTS
    lane, end = _SEQ_END[(where, wrap)]
    if age is None:
        lane, start = 0, (28 if wrap else 20)
    elif where == "inside":
        start = end + 1 + age - (L - 1)
        assert done[start + L - 2 - age, lane]
    else:
        start = end + 1 + age
        assert done[start - 1 - age, lane]
    assert (start % slots + L > slots) == wrap
    t_idx = jnp.asarray([start % slots, (start + 1) % slots], jnp.int32)
    b_idx = jnp.asarray([lane, lane if same_lane else 0], jnp.int32)

    want = _stacked_windows(full, merge, t_idx, b_idx)
    got = sring._rebuild_seq_stacks(dd.ring, t_idx, b_idx, L, S, merge,
                                    (H, W, 1), slots, 1 + len(_SEQ_END))
    assert got.shape == (L, 2, H, W, S) and got.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("merge", [False, True])
def test_sequence_rebuild_of_wider_frames_is_exact(merge):
    """Frames that are not four uint8 to a word take the plain stacking
    of the same channels: every window start the ring holds, all lanes."""
    from dist_dqn_tpu.replay import sequence_device as sring

    _, full, dd = _seq_rings(merge, np.uint16)
    L, slots, lanes = _SEQ_L, _SEQ_SLOTS, 1 + len(_SEQ_END)
    starts = np.arange(_SEQ_STEPS - slots + S - 1, _SEQ_STEPS - L + 1)
    t_idx = jnp.asarray(np.repeat(starts % slots, lanes), jnp.int32)
    b_idx = jnp.asarray(np.tile(np.arange(lanes), len(starts)), jnp.int32)
    want = _stacked_windows(full, merge, t_idx, b_idx)
    got = sring._rebuild_seq_stacks(dd.ring, t_idx, b_idx, L, S, merge,
                                    (H, W, 1), slots, lanes)
    assert got.dtype == jnp.uint16
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("merge", [False, True])
def test_sequence_ring_sample_dedup_obs_contract(merge):
    """What the learner and the benchmark's ring comparison read:
    ``sample.obs`` is the logical ``[L, B, H, W, S]`` uint8 array, and it
    holds the stacked ring's windows at the starts that were drawn."""
    from dist_dqn_tpu.replay import sequence_device as sring

    _, full, dd = _seq_rings(merge)
    L, batch = _SEQ_L, 7
    s = sring.sequence_ring_sample(
        dd, jax.random.PRNGKey(4), batch, L, 0.9, jnp.float32(0.6),
        merge_obs_rows=merge, frame_stack=S, frame_shape=(H, W, 1))
    assert s.obs.shape == (L, batch, H, W, S) and s.obs.dtype == jnp.uint8
    np.testing.assert_array_equal(
        np.asarray(_stacked_windows(full, merge, s.t_idx, s.b_idx)),
        np.asarray(s.obs))


@pytest.mark.parametrize("merge", [False, True])
def test_sequence_rebuild_reads_each_frame_once(merge):
    """What carries over from the CPU to the chip is the program's shape
    (as for ``gather_transitions`` above): the only gather of frames reads
    the RING, once — no ``take_along_axis`` pass over already gathered
    frames — and nothing is concatenated on a size-1 minor axis: the two
    constructs that cost ``r2d2.preset`` four frame gathers and four
    size-1-minor copies a grad step (PERF.md, PR 31)."""
    from dist_dqn_tpu.replay import sequence_device as sring

    _, _, dd = _seq_rings(merge)
    idx = jnp.zeros((5,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda r, t, b: sring._rebuild_seq_stacks(
            r, t, b, _SEQ_L, S, merge, (H, W, 1), _SEQ_SLOTS,
            1 + len(_SEQ_END)))(dd.ring, idx, idx)
    assert jaxpr.out_avals[0].shape == (_SEQ_L, 5, H, W, S)
    eqns = list(_eqns(jaxpr.jaxpr))
    frame_gathers = [e.invars[0].aval.shape for e in eqns
                     if e.primitive.name == "gather"
                     and e.invars[0].aval.dtype == jnp.uint8]
    assert frame_gathers == [dd.ring.obs.shape], frame_gathers
    minor_concats = [e for e in eqns if e.primitive.name == "concatenate"
                     and e.invars[0].aval.dtype == jnp.uint8
                     and e.invars[0].aval.shape[-1] == 1
                     and e.params["dimension"] == e.invars[0].aval.ndim - 1]
    assert not minor_concats


def test_r2d2_fused_loop_dedup_trains():
    """make_fused_train with a recurrent net and frame_dedup: sequence
    replay over single stored frames trains the learner end to end."""
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.train_loop import make_fused_train

    cfg = CONFIGS["r2d2"]
    cfg = dataclasses.replace(
        cfg,
        env_name="pixel_catch",
        network=dataclasses.replace(cfg.network, torso="small", hidden=16,
                                    lstm_size=8, compute_dtype="float32"),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        replay=dataclasses.replace(cfg.replay, capacity=1024, min_fill=128,
                                   burn_in=2, unroll_length=4,
                                   sequence_stride=2, frame_dedup=True),
        learner=dataclasses.replace(cfg.learner, n_step=1, batch_size=4),
        train_every=4,
    )
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run = make_fused_train(cfg, env, net)
    carry = init(jax.random.PRNGKey(0))
    carry, metrics = run(carry, 80)
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert np.isfinite(float(metrics["loss"]))
    # Stored obs is single-frame sized.
    assert carry.replay.ring.obs.size == (1024 // 4) * 4 * 84 * 84


def test_dedup_mesh_fused_train_runs():
    """frame_dedup composes with the multi-chip SPMD wrapper: per-shard
    rings store single frames, rebuilt stacks feed the pmean-allreduced
    learner on the virtual 8-device mesh."""
    import jax as _jax

    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.parallel import make_mesh, make_mesh_fused_train

    if len(_jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh from conftest")
    cfg = CONFIGS["atari"]
    cfg = dataclasses.replace(
        cfg,
        env_name="pixel_catch",
        network=dataclasses.replace(cfg.network, torso="small", hidden=16,
                                    compute_dtype="float32"),
        actor=dataclasses.replace(cfg.actor, num_envs=16),
        replay=dataclasses.replace(cfg.replay, capacity=1024, min_fill=64,
                                   frame_dedup=True),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        train_every=2,
        total_env_steps=4000,
    )
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    mesh = make_mesh()
    init, run = make_mesh_fused_train(cfg, env, net, mesh)
    carry = init(jax.random.PRNGKey(0))
    carry, metrics = run(carry, 40)
    assert int(metrics["env_frames"]) == 40 * 16
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert np.isfinite(float(metrics["loss"]))


def test_dedup_fused_loop_trains_and_validates():
    """make_fused_train with frame_dedup: trains on a real rolling-stack
    env (PixelCatch), and the contract violations raise named errors."""
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.train_loop import make_fused_train

    cfg = CONFIGS["atari"]
    cfg = dataclasses.replace(
        cfg,
        env_name="pixel_catch",
        network=dataclasses.replace(cfg.network, torso="small", hidden=32,
                                    compute_dtype="float32"),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        replay=dataclasses.replace(cfg.replay, capacity=512, min_fill=64,
                                   frame_dedup=True),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        train_every=2,
    )
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run = make_fused_train(cfg, env, net)
    carry = init(jax.random.PRNGKey(0))
    carry, metrics = run(carry, 60)
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert np.isfinite(float(metrics["loss"]))
    # Stored obs is single-frame: the ring obs leaf's last axis is 1
    # (or flat rows of H*W); either way 4x smaller than the stack.
    ring_obs = jax.tree.leaves(carry.replay)[0]
    assert ring_obs.size == 512 * 84 * 84  # slots*B lanes * one frame

    with pytest.raises(ValueError, match="rolling frame stack"):
        vec_cfg = dataclasses.replace(cfg, env_name="cartpole")
        venv = make_jax_env("cartpole")
        make_fused_train(vec_cfg, venv, build_network(
            dataclasses.replace(cfg.network, torso="mlp",
                                mlp_features=(8,), hidden=0),
            venv.num_actions))

    with pytest.raises(ValueError, match="store_final_obs"):
        sf_cfg = dataclasses.replace(
            cfg, replay=dataclasses.replace(cfg.replay,
                                            store_final_obs=True))
        make_fused_train(sf_cfg, env, net)
