"""The plain float32 reference against the program's learner, toy size."""
import dataclasses

import pytest

from perf.harness import reference_check
from perf.reference import dqn_float32

BATCH = 32
CASES = {
    # name: (dueling, double_dqn, prioritized -> importance weights)
    "plain": (False, False, False),
    "double": (False, True, False),
    "dueling": (True, True, False),
    "importance_weighted": (True, True, True),
}


def _setup(dueling, double_dqn, prioritized, compute_dtype):
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["atari"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, torso="small", hidden=64,
                                    dueling=dueling,
                                    compute_dtype=compute_dtype),
        learner=dataclasses.replace(cfg.learner, batch_size=BATCH,
                                    double_dqn=double_dqn,
                                    target_update_period=2),
        replay=dataclasses.replace(cfg.replay, prioritized=prioritized))
    env = make_jax_env(cfg.env_name)
    return cfg, env, build_network(cfg.network, env.num_actions)


def _check(setup, seed=11, net=None):
    cfg, env, built = setup
    return reference_check.make_check(dqn_float32, cfg, env, net or built,
                                      BATCH)(seed)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_reference_agrees_with_the_programs_learner(case, compute_dtype):
    result = _check(_setup(*CASES[case], compute_dtype))
    assert result["ok"], result
    assert result["tolerances"] == dqn_float32.TOLERANCES[compute_dtype]


def test_the_result_is_a_function_of_the_seed_alone():
    """Two checks of one seed read the same errors to the last digit; another
    seed is another state and another batch."""
    check = reference_check.make_check(
        dqn_float32, *_setup(True, True, True, "bfloat16"), BATCH)
    first, again, other = check(3), check(3), check(4)
    assert first["errors"] == again["errors"]
    assert first["errors"] != other["errors"]
    assert first["ok"] and other["ok"]


@pytest.mark.parametrize("wrong", ["double_dqn", "dueling", "weights",
                                   "learning_rate"])
def test_a_wrong_formula_fails_the_comparison(wrong, monkeypatch):
    """Dropping part of the mathematics is caught, in bf16 tolerances. (At
    a state this close to initialisation the Q-values of different actions
    lie close together, so double-Q against the plain maximum moves |TD| by
    1-5% of its largest value, depending on the seed: the seed here is one
    where it is well outside; the other three are tens of percent in any.)"""
    import jax.numpy as jnp

    real_hyper, real_step = dqn_float32.hyper_from_config, dqn_float32.step
    if wrong == "weights":
        def step(params, target, batch, hp):
            return real_step(params, target, dict(
                batch, weights=jnp.ones_like(batch["weights"])), hp)
        monkeypatch.setattr(dqn_float32, "step", step)
    else:
        def hyper(c):
            hp = real_hyper(c)
            value = {"double_dqn": False, "dueling": False,
                     "learning_rate": hp.learning_rate * 1.5}[wrong]
            return hp._replace(**{wrong: value})
        monkeypatch.setattr(dqn_float32, "hyper_from_config", hyper)
    result = _check(_setup(True, True, True, "bfloat16"), seed=5)
    assert not result["ok"], result
    if wrong == "learning_rate":    # the gradient agrees, the step does not
        assert result["errors"]["grad"] <= result["tolerances"]["grad"]
        assert result["errors"]["optimizer"] > 0.3


def test_a_lower_precision_than_bf16_fails():
    """The bf16 tolerances tell bf16 from a coarser type: the program's side
    in float8-rounded weights is outside them, by a wide margin."""
    setup = _setup(True, True, True, "bfloat16")
    fine = _check(setup)
    coarse = _check(setup, net=reference_check.CoarseNet(setup[2]))
    assert fine["ok"] and not coarse["ok"], coarse
    assert coarse["errors"]["q"] > 4 * fine["errors"]["q"]
    assert coarse["errors"]["q"] > fine["tolerances"]["q"]


def test_hyper_refuses_what_the_reference_does_not_compute():
    from dist_dqn_tpu.config import CONFIGS

    with pytest.raises(NotImplementedError):
        dqn_float32.hyper_from_config(CONFIGS["rainbow"])
    with pytest.raises(NotImplementedError):
        dqn_float32.hyper_from_config(CONFIGS["r2d2"])


def test_reference_imports_nothing_of_the_program():
    """The arithmetic imports nothing of the program; ``make_program`` and,
    where a module has one, ``make_further_check`` alone name it (the
    learner and the replay this reference stands beside), inside their
    bodies."""
    import inspect

    from perf.reference import plain, r2d2_float32, sequence_ring

    for module in (dqn_float32, r2d2_float32, plain, sequence_ring):
        source = inspect.getsource(module)
        for name in ("make_program", "make_further_check"):
            if hasattr(module, name):
                source = source.replace(
                    inspect.getsource(getattr(module, name)), "")
        assert "dist_dqn_tpu" not in source.replace(
            "``dist_dqn_tpu``", "").replace(
            "nothing from\n``dist_dqn_tpu", ""), module.__name__
        assert "import flax" not in source and "import optax" not in source
