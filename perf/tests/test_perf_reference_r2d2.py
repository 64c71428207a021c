"""The seam between the harness and a configuration's reference module, and
the sequence learner's plain reference against the program's, toy size."""
import dataclasses
import types

import numpy as np
import pytest

from perf.harness import reference_check
from perf.harness.manifest import ManifestError
from perf.reference import dqn_float32, r2d2_float32, sequence_ring

SEQS = 8


def _sequence_setup(compute_dtype="float32", replay=None, **learner):
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg, replay = CONFIGS["r2d2"], replay or {}
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, torso="small", hidden=32,
                                    lstm_size=16, lstm_unroll=1,
                                    compute_dtype=compute_dtype,
                                    lstm_dtype=compute_dtype),
        # a ring of 64 time slices x 4 lanes for the ring's own comparison
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        replay=dataclasses.replace(cfg.replay, burn_in=4, unroll_length=8,
                                   sequence_stride=4, capacity=256,
                                   **replay),
        learner=dataclasses.replace(cfg.learner, n_step=3, batch_size=SEQS,
                                    **learner))
    env = make_jax_env(cfg.env_name)
    return cfg, env, build_network(cfg.network, env.num_actions)


def _sequence_check(setup, seed=7, net=None):
    cfg, env, built = setup
    return reference_check.make_check(r2d2_float32, cfg, env, net or built,
                                      SEQS)(seed)


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_sequence_reference_agrees_with_the_programs_learner(compute_dtype):
    """Loss, sequence priorities, Q-values at the training positions, the
    gradient read back from Adam's moments and the optimizer's step of
    ``make_r2d2_learner`` against ``r2d2_float32`` on seeded weights: windows
    with episode ends in the burn-in and among the loss positions."""
    result = _sequence_check(_sequence_setup(compute_dtype))
    assert result["ok"], result
    assert result["tolerances"] == dict(
        r2d2_float32.TOLERANCES[compute_dtype], **r2d2_float32.RING_LIMITS)
    if compute_dtype == "float32":
        assert result["also"]["priority_rows_outside"] == 0.0


def test_the_seeded_windows_hold_episode_ends_and_resets():
    cfg, env, _ = _sequence_setup()
    batch = r2d2_float32.seeded_batch(7, 3, 64, cfg, env)
    steps = 4 + 8 + 3
    assert batch["obs"].shape == (steps, 64, *env.observation_shape)
    assert batch["done"].any() and not batch["done"].all(axis=0).any()
    np.testing.assert_array_equal(batch["reset"][1:], batch["done"][:-1])
    assert not batch["reset"][0].any()
    again = r2d2_float32.seeded_batch(7, 3, 64, cfg, env)
    assert all(np.array_equal(a, b) for a, b in zip(
        (batch["obs"], batch["reward"]), (again["obs"], again["reward"])))
    assert not np.array_equal(
        batch["obs"], r2d2_float32.seeded_batch(7, 4, 64, cfg, env)["obs"])


# -- the sequence ring against the plain rules ------------------------------
def _ring_numbers(replay, seed=5):
    cfg, env, _ = _sequence_setup(replay=replay)
    return {name: value for name, (value, _) in
            r2d2_float32.make_further_check(cfg, env)(seed).items()}


@pytest.mark.parametrize("dedup", [True, False])
def test_the_programs_sequence_ring_keeps_the_plain_rules(dedup):
    """Inserts past a wrap, write-backs of the loop's size (an eighth of
    each on dead cells), a stratified draw and its windows — stacks rebuilt
    from single frames across episode ends under dedup — against
    ``sequence_ring.py``: nothing differs, on a seed above 2**31 too."""
    for seed in (5, 2 ** 31 + 11):
        numbers = _ring_numbers({"frame_dedup": dedup}, seed)
        assert set(numbers) == set(r2d2_float32.RING_LIMITS)
        assert all(numbers[k] <= r2d2_float32.RING_LIMITS[k]
                   for k in numbers), numbers


@pytest.mark.parametrize("broken,number", [
    ("rebuild", "ring_windows"), ("reset", "ring_windows"),
    ("state", "ring_windows"), ("writeback", "ring_writeback"),
    ("resurrect", "ring_writeback"), ("never_cleared", "ring_starts"),
    ("weights", "ring_weights"), ("uniform_draw", "ring_strata")])
def test_a_broken_sequence_ring_fails_its_number(broken, number, monkeypatch):
    """The control of the ring's comparison: one byte of one rebuilt stack,
    one ``reset`` flag, one stored state handed on from the wrong lane, a
    write-back 0.01% off or onto a dead start, a start that is never
    cleared, one importance weight 0.1% off, a draw that ignores the
    priorities — each moves its own number past its limit."""
    import jax.numpy as jnp

    from dist_dqn_tpu.replay import sequence_device as sring

    real = {n: getattr(sring, n) for n in (
        "_rebuild_seq_stacks", "sequence_ring_sample", "sequence_ring_add",
        "sequence_ring_update")}

    def rebuild(*a, **k):
        return real["_rebuild_seq_stacks"](*a, **k).at[3, 1, 2, 2, 0].add(1)

    def sample(state, *a, **k):
        if broken == "uniform_draw":
            state = state._replace(priorities=jnp.where(
                state.priorities > 0, 1.0, 0.0))
        out = real["sequence_ring_sample"](state, *a, **k)
        if broken == "reset":
            out = out._replace(reset=out.reset.at[0, 0].set(True))
        if broken == "state":
            out = out._replace(start_state=(
                out.start_state[0], jnp.roll(out.start_state[1], 1, 0)))
        if broken == "weights":
            out = out._replace(weights=out.weights.at[2].multiply(1.001))
        return out

    def add(state, *a, **k):
        out = real["sequence_ring_add"](state, *a, **k)
        return out._replace(priorities=jnp.maximum(out.priorities,
                                                   state.priorities))

    def update(state, t, b, p, eps=1e-6):
        if broken == "resurrect":
            return state._replace(priorities=state.priorities.at[t, b].set(
                jnp.abs(p) + eps))
        return real["sequence_ring_update"](state, t, b, p * 1.0001, eps)

    patch = {"rebuild": ("_rebuild_seq_stacks", rebuild),
             "never_cleared": ("sequence_ring_add", add),
             "writeback": ("sequence_ring_update", update),
             "resurrect": ("sequence_ring_update", update)}.get(
                 broken, ("sequence_ring_sample", sample))
    monkeypatch.setattr(sring, *patch)
    numbers = _ring_numbers({"frame_dedup": True})
    assert numbers[number] > r2d2_float32.RING_LIMITS[number], numbers


def test_plain_ring_rules_by_hand():
    """Ten steps written into eight slots, windows of 3 every 2 steps,
    stacks of 2: alive starts are the even steps whose window is whole and
    not overwritten (2, 4, 6), drawable all but the oldest stored step;
    the stack across an episode's end repeats the new episode's first
    frame; a draw outside its stratum is counted."""
    alive, drawable = sequence_ring.alive_starts(10, 8, 1, 3, 2, 2)
    assert sorted(np.flatnonzero(alive[:, 0])) == [2, 4, 6]
    assert sorted(np.flatnonzero(drawable[:, 0])) == [4, 6]     # 2: oldest
    assert list(sequence_ring.absolute_step(np.array([0, 1, 2, 7]), 10, 8)
                ) == [8, 9, 2, 7]
    done = np.zeros((10, 1), bool)
    done[4] = True                          # step 5 opens an episode
    steps = {"action": np.arange(10)[:, None], "truncated": done,
             "reward": np.arange(10.0)[:, None], "terminated": ~done & done}
    want = sequence_ring.window_fields(steps, np.array([4]), np.array([0]),
                                       3, 2)
    assert want["frame_of"][:, 0].tolist() == [[3, 4], [5, 5], [5, 6]]
    assert want["done"][:, 0].tolist() == [True, False, False]
    assert want["reset"][:, 0].tolist() == [False, True, False]
    mass = np.array([[1.0], [0.0], [1.0], [2.0]])
    slots, lanes = np.array([0, 2, 3, 3]), np.zeros(4, int)
    assert sequence_ring.strata_missed(mass, slots, lanes) == 0
    # backwards: the two outer draws miss, the inner two touch their strata
    assert sequence_ring.strata_missed(mass, slots[::-1], lanes) == 2
    assert sequence_ring.strata_missed(mass, np.array([0, 1, 3, 3]),
                                       lanes) == 1               # no mass
    plane, largest = sequence_ring.write_back(
        (mass[:, :1] > 0).astype(np.float32), np.float32(1.0),
        np.array([0, 1]), np.array([0, 0]), np.array([-3.0, 5.0]), 0.5)
    assert plane[:, 0].tolist() == [3.5, 0.0, 1.0, 1.0] and largest == 3.5


@pytest.mark.parametrize("wrong", ["burn_in_gradient", "value_rescale",
                                   "double_dqn", "priority_mix"])
def test_a_wrong_sequence_formula_fails_the_comparison(wrong, monkeypatch):
    """Each part of the published mathematics is held: a gradient that flows
    through the burn-in prefix, targets without the value rescaling, the
    plain maximum where the online network chooses, and a priority that is
    the mean alone, each come out NOT ok in the float32 tolerances."""
    if wrong == "burn_in_gradient":
        monkeypatch.setattr(r2d2_float32, "_leave_burn_in", lambda s: s)
    else:
        real_hyper = r2d2_float32.hyper_from_config
        changed = {"value_rescale": {"value_rescale": False},
                   "double_dqn": {"double_dqn": False},
                   "priority_mix": {"eta": 0.0}}[wrong]
        monkeypatch.setattr(r2d2_float32, "hyper_from_config",
                            lambda c: real_hyper(c)._replace(**changed))
    result = _sequence_check(_sequence_setup())
    assert not result["ok"], result
    errors, limits = result["errors"], result["tolerances"]
    if wrong == "burn_in_gradient":     # the values agree, the gradient not
        assert errors["loss"] <= limits["loss"]
        assert errors["grad"] > 10 * limits["grad"]
    if wrong == "priority_mix":         # only the priorities move
        assert errors["grad"] <= limits["grad"]
        assert errors["priorities"] > 10 * limits["priorities"]


def test_sequence_flops_against_a_hand_count():
    """One small shape by hand: an 84x84x4 frame through the small torso
    (16 8x8/4 -> 20x20, 32 4x4/2 -> 9x9, dense 2592 -> 32), an LSTM of 16
    on 32 inputs, dueling heads on 6 actions; 4 + 8 + 3 steps."""
    cfg, env, _ = _sequence_setup()
    conv1, conv2 = 20 * 20 * 8 * 8 * 4 * 16, 9 * 9 * 4 * 4 * 16 * 32
    dense = 9 * 9 * 32 * 32
    torso = conv1 + conv2 + dense
    gates = 4 * (32 + 16) * 16
    heads = 16 * (6 + 1)
    window, train = 15, 11
    macs = (2 * window * (torso + gates) + 2 * train * heads      # forward x2
            + train * (2 * torso - conv1 + 2 * gates + 2 * heads))
    assert r2d2_float32.grad_step_flops(cfg, env) == 2.0 * SEQS * macs


def test_dqn_flops_are_the_shape_count_of_perf_reduce():
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from perf.reduce import flops

    for name, dueling in (("atari", False), ("apex", True)):
        cfg = CONFIGS[name]
        env = make_jax_env(cfg.env_name)
        assert dqn_float32.grad_step_flops(cfg, env) == flops.grad_step_flops(
            cfg.learner.batch_size, dueling=dueling, double_dqn=True)
    # 86.9 MFLOP a sample (PERF.md section 7)
    assert dqn_float32.grad_step_flops(CONFIGS["atari"], env) == pytest.approx(
        256 * 86.9e6, rel=1e-3)


@pytest.mark.parametrize("missing", ["make_program", "TOLERANCES",
                                     "grad_step_flops", "seeded_batch"])
def test_a_reference_module_without_a_name_is_refused_by_that_name(missing):
    cfg, env, net = _sequence_setup()
    partial = types.SimpleNamespace(**{
        k: getattr(r2d2_float32, k)
        for k in reference_check.REFERENCE_NAMES if k != missing})
    with pytest.raises(ManifestError, match=missing):
        reference_check.make_check(partial, cfg, env, net, SEQS)


def test_tolerances_must_cover_the_compute_type_and_every_quantity():
    cfg, env, net = _sequence_setup("bfloat16")
    for table in ({"float32": r2d2_float32.TOLERANCES["float32"]},
                  {"bfloat16": {"q": 0.03}}):
        partial = types.SimpleNamespace(**{
            k: getattr(r2d2_float32, k)
            for k in reference_check.REFERENCE_NAMES})
        partial.TOLERANCES = table
        with pytest.raises(ManifestError, match="TOLERANCES"):
            reference_check.make_check(partial, cfg, env, net, SEQS)


def test_the_seam_leaves_the_dqn_comparison_as_it_was():
    """``dqn_float32`` through ``make_program`` / ``seeded_batch`` reads the
    same errors, to the last digit, as the arithmetic the harness held
    itself before the seam (PR 23), written out here once more: the
    learner built by hand, weights beside the batch, the same seeded draws
    in the same order, the same two jitted programs."""
    import jax
    import jax.numpy as jnp

    from dist_dqn_tpu.agents.dqn import make_learner
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.types import Transition

    rows, seed = 16, 2 ** 31 + 9
    cfg = CONFIGS["apex"]
    cfg = dataclasses.replace(
        cfg, network=dataclasses.replace(cfg.network, torso="small",
                                         hidden=32),
        learner=dataclasses.replace(cfg.learner, batch_size=rows))
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    through_seam = reference_check.make_check(dqn_float32, cfg, env, net,
                                              rows)(seed)

    hp = dqn_float32.hyper_from_config(cfg)
    init, train_step = make_learner(net, cfg.learner)
    obs_shape = tuple(env.observation_shape)
    gamma_n = cfg.learner.gamma ** cfg.learner.n_step

    def old_batch(index):
        rng = np.random.default_rng([seed, index])
        frames = lambda: rng.integers(0, 256, (rows, *obs_shape),  # noqa
                                      dtype=np.uint8)
        return {"obs": frames(), "next_obs": frames(),
                "action": rng.integers(0, env.num_actions, rows).astype(
                    np.int32),
                "reward": rng.choice([0.5, 1.0, 1.5, 2.0], rows).astype(
                    np.float32),
                "discount": (gamma_n * (rng.random(rows) > 0.05)).astype(
                    np.float32),
                "weights": rng.uniform(0.2, 1.0, rows).astype(np.float32)}

    example = jnp.zeros(obs_shape, jnp.uint8)

    @jax.jit
    def seeded_state(seed, batches, weights):
        k_online, k_lagged = jax.random.split(jax.random.PRNGKey(seed))
        state, _ = jax.lax.scan(
            lambda s, bw: (train_step(s, Transition(**bw[0]), bw[1])[0],
                           None),
            init(k_online, example), (batches, weights))
        target = jax.tree.map(lambda t, l: 0.5 * t + 0.5 * l,
                              state.target_params,
                              init(k_lagged, example).params)
        return state._replace(target_params=target)

    @jax.jit
    def both_sides(state, batch, weights):
        new_state, metrics = train_step(state, Transition(**batch), weights)
        ref = dqn_float32.step(state.params, state.target_params,
                               dict(batch, weights=weights), hp)
        adam, new_adam = (reference_check._find_adam(s.opt_state)
                          for s in (state, new_state))
        grads = jax.tree.map(
            lambda new, old: (new - 0.9 * old) / (1.0 - 0.9),
            new_adam.mu, adam.mu)
        return {
            "q": (net.apply(state.params, batch["obs"]), ref["q"]),
            "priorities": (metrics["priorities"], ref["priorities"]),
            "loss": (metrics["loss"], ref["loss"]),
            "grad": (grads, ref["grads"]), "grad_scale": ref["grad_scale"],
            "optimizer": (
                jax.tree.map(jnp.subtract, new_state.params, state.params),
                dqn_float32.adam_delta(grads, adam.mu, adam.nu, adam.count,
                                       hp))}

    warm = [old_batch(i) for i in range(reference_check.WARM_STEPS)]
    warm_weights = np.stack([b.pop("weights") for b in warm])
    state = seeded_state(
        np.uint32(seed % 2 ** 32),
        {k: np.stack([b[k] for b in warm]) for k in warm[0]}, warm_weights)
    batch = old_batch(reference_check.WARM_STEPS)
    weights = batch.pop("weights")
    got = jax.device_get(both_sides(state, batch, weights))
    by_hand = {
        "q": reference_check._rel_max(*got["q"]),
        "priorities": reference_check._rel_max(
            *got["priorities"], reference_check.PRIORITY_ROWS_PERCENTILE),
        "loss": reference_check._rel_max(*got["loss"]),
        "grad": reference_check._rel_l2(*got["grad"],
                                        scale=float(got["grad_scale"])),
        "optimizer": reference_check._rel_l2(*got["optimizer"]),
    }
    assert through_seam["ok"]
    assert through_seam["errors"] == by_hand
    np.testing.assert_array_equal(
        dqn_float32.seeded_batch(seed, 2, rows, cfg, env)["obs"],
        old_batch(2)["obs"])
