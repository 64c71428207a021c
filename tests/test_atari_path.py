"""Single-chip Atari-shaped path (BASELINE.json:8): fused loop over the
synthetic 84x84 pixel env with the Nature CNN, small sizes for CPU CI."""
import dataclasses

import jax

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.envs import make_jax_env
from dist_dqn_tpu.models import build_network
from dist_dqn_tpu.train_loop import make_fused_train

import pytest


pytestmark = pytest.mark.slow  # convergence/multiprocess: full-suite selection only

@pytest.mark.parametrize("flat", [False, True], ids=["tiled", "flat"])
def test_atari_config_fused_smoke(flat):
    cfg = CONFIGS["atari"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, hidden=64,
                                    compute_dtype="float32"),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        replay=dataclasses.replace(cfg.replay, capacity=256, min_fill=32,
                                   flat_storage=flat),
        learner=dataclasses.replace(cfg.learner, batch_size=8),
        train_every=4,
    )
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_fused_train(cfg, env, net)
    run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
    carry = init(jax.random.PRNGKey(0))
    carry, metrics = run(carry, 48)
    assert int(metrics["env_frames"]) == 48 * 4
    assert float(metrics["grad_steps_in_chunk"]) > 0
    assert abs(float(metrics["loss"])) < 1e3
    # uint8 pixel ring: final_obs not stored (memory). Storage layout is
    # the replay.flat_storage knob: tiled keeps [slots, B, 84, 84, 4]
    # (faster gathers), flat stores merged 2-D rows [slots*B, 28224] —
    # immune to XLA tile padding on multi-GB rings (train_loop.py /
    # replay/device.py merge_obs_rows; the sample path reshapes back
    # before the learner sees the batch — this parametrization runs the
    # SAME training both ways).
    ring = carry.replay
    assert ring.final_obs is None
    if flat:
        # merged rows and flat cells share one order: t * B + b; a row may
        # be stored wider than its frame (device_ring.merged_row_boundary)
        assert ring.obs.shape[0] == ring.action.shape[0]
        assert ring.obs.shape[1] >= 84 * 84 * 4
    else:
        assert ring.obs.shape[2:] == (84, 84, 4)
    assert ring.obs.dtype.name == "uint8"


def test_store_final_obs_override_enables_exact_truncation_path():
    """replay.store_final_obs=True forces the exact truncation bootstrap on a
    pixel ring (the auto heuristic would skip it for uint8 obs)."""
    cfg = CONFIGS["atari"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, hidden=64,
                                    compute_dtype="float32"),
        actor=dataclasses.replace(cfg.actor, num_envs=2),
        replay=dataclasses.replace(cfg.replay, capacity=64, min_fill=16,
                                   store_final_obs=True),
        learner=dataclasses.replace(cfg.learner, batch_size=4),
        train_every=4,
    )
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_fused_train(cfg, env, net)
    carry = init(jax.random.PRNGKey(0))
    assert carry.replay.final_obs is not None
    assert carry.replay.final_obs.dtype.name == "uint8"
    carry, metrics = jax.jit(run_chunk, static_argnums=1,
                             donate_argnums=0)(carry, 24)
    assert abs(float(metrics["loss"])) < 1e3


def test_flat_storage_bit_equal_to_tiled():
    """Ring storage layout must be invisible to training: the same seed
    run under tiled and flat storage yields bit-identical learner
    params (reshape is a pure re-layout; any divergence means the
    insert/sample boundary changed numerics)."""
    import numpy as np

    def run(flat):
        cfg = CONFIGS["atari"]
        cfg = dataclasses.replace(
            cfg,
            network=dataclasses.replace(cfg.network, hidden=32,
                                        compute_dtype="float32"),
            actor=dataclasses.replace(cfg.actor, num_envs=4),
            replay=dataclasses.replace(cfg.replay, capacity=128,
                                       min_fill=24, flat_storage=flat),
            learner=dataclasses.replace(cfg.learner, batch_size=8),
            train_every=4,
        )
        env = make_jax_env(cfg.env_name)
        net = build_network(cfg.network, env.num_actions)
        init, run_chunk = make_fused_train(cfg, env, net)
        run_j = jax.jit(run_chunk, static_argnums=1)
        carry = init(jax.random.PRNGKey(7))
        carry, metrics = run_j(carry, 40)
        return jax.device_get(carry.learner.params), \
            float(metrics["loss"])

    p_tiled, loss_tiled = run(False)
    p_flat, loss_flat = run(True)
    assert loss_tiled == loss_flat
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 p_tiled, p_flat)


def test_r2d2_flat_storage_bit_equal_to_tiled():
    """Same layout-invisibility contract for the SEQUENCE ring: pixel
    R2D2 training under tiled vs flat obs storage is bit-identical."""
    import numpy as np

    def run(flat):
        cfg = CONFIGS["r2d2"]
        cfg = dataclasses.replace(
            cfg,
            env_name=CONFIGS["atari"].env_name,
            network=dataclasses.replace(cfg.network, torso="small",
                                        hidden=32, lstm_size=8,
                                        compute_dtype="float32",
                                        lstm_dtype="float32"),
            actor=dataclasses.replace(cfg.actor, num_envs=4),
            replay=dataclasses.replace(cfg.replay, capacity=256,
                                       min_fill=32, burn_in=2,
                                       unroll_length=4,
                                       sequence_stride=2,
                                       flat_storage=flat),
            learner=dataclasses.replace(cfg.learner, n_step=2,
                                        batch_size=8),
            train_every=4,
        )
        env = make_jax_env(cfg.env_name)
        net = build_network(cfg.network, env.num_actions)
        init, run_chunk = make_fused_train(cfg, env, net)
        run_j = jax.jit(run_chunk, static_argnums=1)
        carry = init(jax.random.PRNGKey(7))
        carry, metrics = run_j(carry, 40)
        return jax.device_get(carry.learner.params), \
            float(metrics["loss"])

    p_tiled, loss_tiled = run(False)
    p_flat, loss_flat = run(True)
    assert loss_tiled == loss_flat
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 p_tiled, p_flat)
