"""Checkpoint/resume (SURVEY.md §5): orbax round-trip of the learner state
and the train()-level save/restore cycle."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from dist_dqn_tpu.agents.dqn import make_learner
from dist_dqn_tpu.config import CONFIGS, LearnerConfig
from dist_dqn_tpu.models.qnets import QNetwork
from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer

import pytest


def _learner_state(seed=0):
    net = QNetwork(num_actions=3, torso="mlp", mlp_features=(16,), hidden=0)
    init, step = make_learner(net, LearnerConfig())
    return init(jax.random.PRNGKey(seed), jnp.zeros((4,)))


def test_checkpointer_roundtrip(tmp_path):
    state = _learner_state(seed=0)
    ckpt = TrainCheckpointer(str(tmp_path / "ckpt"), save_every_frames=100)
    assert ckpt.restore_latest(state) is None      # empty dir
    ckpt.save(1000, state)
    ckpt.wait()

    other = _learner_state(seed=1)                 # different values
    frames, restored = ckpt.restore_latest(other)
    assert frames == 1000
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Optimizer moments and counters survive too.
    assert int(restored.steps) == int(state.steps)
    ckpt.close()


def test_checkpointer_retention_and_cadence(tmp_path):
    state = _learner_state()
    ckpt = TrainCheckpointer(str(tmp_path / "ckpt"), save_every_frames=100,
                             max_to_keep=2)
    assert ckpt.maybe_save(0, state)               # first boundary
    assert not ckpt.maybe_save(50, state)          # below next boundary
    assert ckpt.maybe_save(120, state)
    assert ckpt.maybe_save(500, state)
    ckpt.wait()
    frames, _ = ckpt.restore_latest(state)
    assert frames == 500
    ckpt.close()


@pytest.mark.slow
def test_train_resumes_from_checkpoint(tmp_path):
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(32,)),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=128),
        learner=dataclasses.replace(cfg.learner, batch_size=32),
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        eval_every_steps=10**9,
    )
    ckpt_dir = str(tmp_path / "run")
    carry1, _ = train(cfg, total_env_steps=4000, chunk_iters=250,
                      log_fn=lambda s: None, checkpoint_dir=ckpt_dir)
    steps1 = int(carry1.learner.steps)
    assert steps1 > 0

    # Relaunching the identical command continues toward the same total:
    # resumes at 4000 and trains only the remaining 2000 frames.
    logs = []
    carry2, hist2 = train(cfg, total_env_steps=6000, chunk_iters=250,
                          log_fn=logs.append, checkpoint_dir=ckpt_dir)
    resumed = [json.loads(s) for s in logs if "resumed_at_frames" in s]
    assert resumed and resumed[0]["resumed_at_frames"] == 4000
    assert hist2[-1]["env_frames"] == 6000
    assert hist2[0]["env_frames"] > 4000           # cursor continued
    # The resumed learner continued from the saved one (steps accumulated).
    assert int(carry2.learner.steps) > steps1

    # A fully-finished run resumes at its total and trains zero frames.
    logs3 = []
    _, hist3 = train(cfg, total_env_steps=6000, chunk_iters=250,
                     log_fn=logs3.append, checkpoint_dir=ckpt_dir)
    assert not hist3
    resumed3 = [json.loads(s) for s in logs3 if "resumed_at_frames" in s]
    assert resumed3 and resumed3[0]["resumed_at_frames"] == 6000


@pytest.mark.slow
def test_standalone_evaluate_checkpoint(tmp_path):
    """dist_dqn_tpu.evaluate loads what train() saved and plays greedy
    episodes with no training machinery (the deploy-side surface)."""
    import pytest

    from dist_dqn_tpu.evaluate import evaluate_checkpoint
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(32,)),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=128),
        learner=dataclasses.replace(cfg.learner, batch_size=32),
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        eval_every_steps=10**9,
    )
    ckpt_dir = str(tmp_path / "run")
    with pytest.raises(FileNotFoundError):
        evaluate_checkpoint(cfg, ckpt_dir, episodes=2)
    train(cfg, total_env_steps=3000, chunk_iters=250,
          log_fn=lambda s: None, checkpoint_dir=ckpt_dir)
    out = evaluate_checkpoint(cfg, ckpt_dir, episodes=4, seed=1)
    # Saved cursor lands on a chunk boundary at or past the request.
    assert out["frames"] >= 3000 and out["config"] == "cartpole"
    # Undertrained but must be a real playable policy returning a finite
    # CartPole return (episodes end between 1 and 500 steps).
    assert 1.0 <= out["eval_return"] <= 500.0


def test_evaluate_all_steps_walks_the_learning_curve(tmp_path, capsys):
    """`evaluate --all-steps` restores EVERY retained checkpoint (oldest
    first) and prints one JSON line each — a learning curve from the run
    directory."""
    import json
    import sys
    from unittest import mock

    from dist_dqn_tpu.evaluate import main
    from dist_dqn_tpu.train import train
    from dist_dqn_tpu.utils.checkpoint import list_checkpoint_steps

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(32,)),
        replay=dataclasses.replace(cfg.replay, capacity=512, min_fill=64),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        eval_every_steps=10**9,
    )
    ckpt_dir = str(tmp_path / "run")
    # Two chunks x 300 frames with a 300-frame save period -> multiple
    # retained steps.
    train(cfg, total_env_steps=600, chunk_iters=75, log_fn=lambda s: None,
          checkpoint_dir=ckpt_dir, save_every_frames=300)
    steps = list_checkpoint_steps(ckpt_dir)
    assert len(steps) >= 2 and list(steps) == sorted(steps)

    argv = ["evaluate", "--config", "cartpole", "--platform", "cpu",
            "--checkpoint-dir", ckpt_dir, "--episodes", "1",
            "--all-steps",
            "--set", "network.mlp_features=32",
            "--set", "actor.num_envs=4"]
    with mock.patch.object(sys, "argv", argv):
        main()
    rows = [json.loads(line) for line in
            capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert "device" in rows.pop(0)   # the CLI's first line names the device
    assert [r["frames"] for r in rows] == list(steps)
    assert all(1.0 <= r["eval_return"] <= 500.0 for r in rows)


def test_architecture_mismatch_error_names_the_cause(tmp_path):
    """Restoring a checkpoint onto a DIFFERENT architecture (e.g. the
    user forgot a --set flag at evaluate time) must say so up front
    instead of leading with orbax's raw pytree-path dump."""
    import pytest

    from dist_dqn_tpu.evaluate import evaluate_checkpoint
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(32,)),
        replay=dataclasses.replace(cfg.replay, capacity=512, min_fill=64),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        eval_every_steps=10**9,
    )
    ckpt_dir = str(tmp_path / "run")
    train(cfg, total_env_steps=300, chunk_iters=75, log_fn=lambda s: None,
          checkpoint_dir=ckpt_dir)
    mismatched = dataclasses.replace(
        cfg, network=dataclasses.replace(cfg.network, dueling=True))
    with pytest.raises(ValueError,
                       match="same --config and --set overrides"):
        evaluate_checkpoint(mismatched, ckpt_dir, episodes=1)
    # The opposite drift (checkpoint has heads the live net lacks) must
    # also error — partial restore would otherwise silently evaluate a
    # structural subset of the saved policy.
    dueling_dir = str(tmp_path / "dueling")
    train(mismatched, total_env_steps=300, chunk_iters=75,
          log_fn=lambda s: None, checkpoint_dir=dueling_dir)
    with pytest.raises(ValueError,
                       match="same --config and --set overrides"):
        evaluate_checkpoint(cfg, dueling_dir, episodes=1)


def test_evaluate_is_optimizer_agnostic(tmp_path):
    """evaluate needs only the policy params: a checkpoint saved with a
    SCHEDULED optimizer (extra schedule-count leaf in opt_state) must
    evaluate WITHOUT the training run's optimizer flags — the deploy
    surface partial-restores the params subtree (restore_params)."""
    from dist_dqn_tpu.evaluate import evaluate_checkpoint
    from dist_dqn_tpu.train import train

    scheduled = CONFIGS["cartpole"]
    scheduled = dataclasses.replace(
        scheduled,
        network=dataclasses.replace(scheduled.network, mlp_features=(32,)),
        replay=dataclasses.replace(scheduled.replay, capacity=512,
                                   min_fill=64),
        learner=dataclasses.replace(scheduled.learner, batch_size=16,
                                    lr_schedule="cosine",
                                    lr_decay_steps=100,
                                    lr_end_value=1e-5),
        actor=dataclasses.replace(scheduled.actor, num_envs=4),
        eval_every_steps=10**9,
    )
    ckpt_dir = str(tmp_path / "run")
    train(scheduled, total_env_steps=300, chunk_iters=75,
          log_fn=lambda s: None, checkpoint_dir=ckpt_dir)
    # Same network, DEFAULT (constant-lr) optimizer: restore must work.
    plain = dataclasses.replace(
        scheduled, learner=dataclasses.replace(
            scheduled.learner, lr_schedule="constant", lr_decay_steps=0,
            lr_end_value=0.0))
    out = evaluate_checkpoint(plain, ckpt_dir, episodes=2)
    assert out["frames"] > 0
    assert 1.0 <= out["eval_return"] <= 500.0

    # --export-params: the deploy artifact round-trips bit-equal.
    import numpy as np

    from dist_dqn_tpu.evaluate import _build_eval
    from dist_dqn_tpu.utils.checkpoint import (TrainCheckpointer,
                                               restore_pytree)

    export = str(tmp_path / "deploy_params")
    out = evaluate_checkpoint(plain, ckpt_dir, episodes=2,
                              export_params=export)
    assert out["exported_params"] == export
    example, _, _ = _build_eval(plain, 2, 0.001, 0)
    reloaded = restore_pytree(export, example.params)
    ckpt = TrainCheckpointer(ckpt_dir)
    try:
        _, direct = ckpt.restore_params(example.params)
    finally:
        ckpt.close()
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), reloaded, direct)


def test_standalone_evaluate_risk_profile_swap(tmp_path):
    """An IQN checkpoint restores under a DIFFERENT deploy-time risk
    profile (--risk-cvar-eta): parameters are risk-agnostic, so the same
    learned quantiles yield a family of policies; non-IQN configs must
    reject the flag."""
    import pytest

    from dist_dqn_tpu.evaluate import _apply_risk_eta, evaluate_checkpoint
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["iqn"]
    cfg = dataclasses.replace(
        cfg,
        env_name="cartpole",
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(32,), hidden=0,
                                    iqn_embed_dim=8, iqn_tau_samples=4,
                                    iqn_tau_target_samples=4, iqn_tau_act=4,
                                    compute_dtype="float32"),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=128,
                                   pallas_sampler=False),
        learner=dataclasses.replace(cfg.learner, batch_size=32),
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        eval_every_steps=10**9,
        train_every=1,
    )
    ckpt_dir = str(tmp_path / "run")
    train(cfg, total_env_steps=3000, chunk_iters=250,
          log_fn=lambda s: None, checkpoint_dir=ckpt_dir)
    neutral = evaluate_checkpoint(cfg, ckpt_dir, episodes=2, seed=1)
    averse_cfg = _apply_risk_eta(cfg, 0.3)
    averse = evaluate_checkpoint(averse_cfg, ckpt_dir, episodes=2, seed=1)
    for out in (neutral, averse):
        assert 1.0 <= out["eval_return"] <= 500.0
    # The override must actually reach the built network's acting
    # fractions — otherwise --risk-cvar-eta is a silent no-op.
    import numpy as np

    from dist_dqn_tpu.models import build_network

    assert averse_cfg.network.risk_cvar_eta == 0.3
    taus_neutral = np.asarray(build_network(cfg.network, 2).act_taus())
    taus_averse = np.asarray(
        build_network(averse_cfg.network, 2).act_taus())
    np.testing.assert_allclose(taus_averse, taus_neutral * 0.3, rtol=1e-6)
    with pytest.raises(ValueError):
        _apply_risk_eta(CONFIGS["cartpole"], 0.3)


def test_standalone_evaluate_checkpoint_on_host_env(tmp_path):
    """--host-env: a checkpoint trained on the JAX env evaluates on the
    REAL host env (here gymnasium CartPole-v1 against the JAX cartpole
    twin) — the deploy-side path for ale:/dmc: training runs."""
    from dist_dqn_tpu.evaluate import evaluate_checkpoint_host
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(32,)),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=128),
        learner=dataclasses.replace(cfg.learner, batch_size=32),
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        eval_every_steps=10**9,
    )
    ckpt_dir = str(tmp_path / "run")
    with pytest.raises(FileNotFoundError):
        evaluate_checkpoint_host(cfg, ckpt_dir, "CartPole-v1", episodes=2)
    train(cfg, total_env_steps=3000, chunk_iters=250,
          log_fn=lambda s: None, checkpoint_dir=ckpt_dir)
    out = evaluate_checkpoint_host(cfg, ckpt_dir, "CartPole-v1",
                                   episodes=4, seed=1)
    assert out["frames"] >= 3000 and out["host_env"] == "CartPole-v1"
    assert 1.0 <= out["eval_return"] <= 500.0
    assert out["episodes_truncated"] == 0


def test_evaluate_host_env_uses_host_action_count(tmp_path, monkeypatch):
    """The ale: deploy path must size the Q-head from the HOST env (fake
    Breakout: 4 actions), not the config's 6-action JAX stand-in — a
    checkpoint saved with 4 heads restores and plays."""
    import numpy as np

    from dist_dqn_tpu.agents.dqn import make_learner
    from dist_dqn_tpu.evaluate import evaluate_checkpoint_host
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer

    monkeypatch.setenv("DQN_FAKE_ALE", "1")
    cfg = CONFIGS["atari"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, torso="small", hidden=32,
                                    compute_dtype="float32"))
    # Save an (untrained) 4-action learner state, exactly what an
    # ale:Breakout apex run would checkpoint.
    net = build_network(cfg.network, 4)
    init, _ = make_learner(net, cfg.learner)
    state = init(jax.random.PRNGKey(0),
                 jnp.zeros((84, 84, 4), jnp.uint8))
    ckpt_dir = str(tmp_path / "bk")
    ckpt = TrainCheckpointer(ckpt_dir)
    ckpt.save(1234, state)
    ckpt.close()
    out = evaluate_checkpoint_host(cfg, ckpt_dir, "ale:Breakout",
                                   episodes=2, seed=0, max_steps=300)
    assert out["frames"] == 1234
    assert np.isfinite(out["eval_return"])


def test_r2d2_checkpoint_restores_across_throughput_knobs(tmp_path):
    """Flipping the R2D2 throughput knobs (lstm_unroll, lstm_dtype,
    remat_torso) must not orphan existing checkpoints: the param tree is
    knob-invariant (tests/test_recurrent_knobs.py pins the math), so an
    orbax save under one knob setting restores under another."""
    from dist_dqn_tpu.agents.r2d2 import make_r2d2_learner
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer

    base = CONFIGS["r2d2"]
    base = dataclasses.replace(
        base,
        network=dataclasses.replace(base.network, torso="mlp",
                                    mlp_features=(16,), hidden=0,
                                    lstm_size=8, dueling=False,
                                    compute_dtype="float32"),
        replay=dataclasses.replace(base.replay, burn_in=2, unroll_length=4,
                                   sequence_stride=2),
        learner=dataclasses.replace(base.learner, n_step=2, batch_size=8))

    def learner_state(net_cfg, seed):
        net = build_network(net_cfg, 2)
        init, _ = make_r2d2_learner(net, base.learner, base.replay)
        return init(jax.random.PRNGKey(seed), jnp.zeros((4,), jnp.float32))

    cfg_a = dataclasses.replace(base.network, lstm_unroll=1,
                                lstm_dtype="float32", remat_torso=False)
    cfg_b = dataclasses.replace(base.network, lstm_unroll=8,
                                lstm_dtype="bfloat16", remat_torso=True)
    saved = learner_state(cfg_a, seed=3)
    ckpt_dir = str(tmp_path / "knobs")
    ckpt = TrainCheckpointer(ckpt_dir)
    ckpt.save(42, saved)
    ckpt.close()
    ckpt = TrainCheckpointer(ckpt_dir)
    restored = ckpt.restore_latest(learner_state(cfg_b, seed=9))
    ckpt.close()
    assert restored is not None and restored[0] == 42
    jax.tree.map(np.testing.assert_array_equal, restored[1].params,
                 saved.params)


def test_evaluate_host_env_recurrent_branch(tmp_path):
    """The recurrent branch of evaluate_checkpoint_host: LSTM checkpoint,
    carry threaded and zeroed on episode ends, host CartPole-v1."""
    from dist_dqn_tpu.agents.r2d2 import make_r2d2_learner
    from dist_dqn_tpu.evaluate import evaluate_checkpoint_host
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer

    cfg = CONFIGS["r2d2"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(16,), hidden=0,
                                    lstm_size=8, dueling=False,
                                    remat_torso=False,
                                    compute_dtype="float32"),
        replay=dataclasses.replace(cfg.replay, burn_in=2, unroll_length=4,
                                   sequence_stride=2),
        learner=dataclasses.replace(cfg.learner, n_step=2, batch_size=8))
    net = build_network(cfg.network, 2)
    init, _ = make_r2d2_learner(net, cfg.learner, cfg.replay)
    state = init(jax.random.PRNGKey(0), jnp.zeros((4,), jnp.float32))
    ckpt_dir = str(tmp_path / "r2d2host")
    ckpt = TrainCheckpointer(ckpt_dir)
    ckpt.save(7, state)
    ckpt.close()
    out = evaluate_checkpoint_host(cfg, ckpt_dir, "CartPole-v1",
                                   episodes=3, seed=0, max_steps=600)
    assert out["frames"] == 7
    assert 1.0 <= out["eval_return"] <= 500.0


@pytest.mark.slow
def test_standalone_evaluate_checkpoint_recurrent(tmp_path):
    """The R2D2 branch of evaluate_checkpoint: restore an LSTM learner
    checkpoint and play carry-threaded greedy episodes."""
    from dist_dqn_tpu.evaluate import evaluate_checkpoint
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["r2d2"]
    cfg = dataclasses.replace(
        cfg,
        env_name="cartpole",
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(32,), hidden=0,
                                    lstm_size=16, dueling=False,
                                    remat_torso=False,
                                    compute_dtype="float32"),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=64,
                                   burn_in=2, unroll_length=6,
                                   sequence_stride=3),
        learner=dataclasses.replace(cfg.learner, batch_size=16, n_step=2),
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        eval_every_steps=10**9,
    )
    ckpt_dir = str(tmp_path / "r2d2_run")
    train(cfg, total_env_steps=2000, chunk_iters=125,
          log_fn=lambda s: None, checkpoint_dir=ckpt_dir)
    out = evaluate_checkpoint(cfg, ckpt_dir, episodes=3, seed=2)
    assert out["frames"] >= 2000 and out["config"] == "r2d2"
    assert 1.0 <= out["eval_return"] <= 500.0


def test_explicit_step_restore_keeps_save_schedule(tmp_path):
    """restore_latest(step=OLD) is an eval-surface read; it must not
    regress the save schedule and re-save over newer retained steps
    (ADVICE round 3)."""
    state = _learner_state(seed=0)
    ckpt = TrainCheckpointer(str(tmp_path / "ckpt"), save_every_frames=100)
    ckpt.save(100, state)
    ckpt.save(200, state)
    ckpt.wait()
    # Latest-resume path DOES advance the schedule past the cursor.
    frames, _ = ckpt.restore_latest(state)
    assert frames == 200 and ckpt._next_save == 300
    # Explicit-step restore of an OLD step leaves it untouched...
    frames, _ = ckpt.restore_latest(state, step=100)
    assert frames == 100 and ckpt._next_save == 300
    # ...so a subsequent cursor inside the already-covered window does
    # not overwrite newer retained steps.
    assert not ckpt.maybe_save(250, state)
    assert ckpt.all_steps() == (100, 200)
    ckpt.close()


def test_host_all_steps_skips_only_missing_checkpoints(tmp_path, capsys):
    """The host --all-steps walk skips a step whose checkpoint vanished
    mid-walk (live retention) via the DISTINCT CheckpointMissingError —
    an unrelated FileNotFoundError from the evaluation (missing ROM)
    still propagates loudly (ADVICE round 3)."""
    import sys
    from unittest import mock

    from dist_dqn_tpu import evaluate as ev

    state = _learner_state(seed=0)
    ckpt = TrainCheckpointer(str(tmp_path / "run"), save_every_frames=100)
    ckpt.save(100, state)
    ckpt.save(200, state)
    ckpt.wait()
    ckpt.close()

    def fake_host_eval(cfg, ckpt_dir, host_env, episodes, seed, step,
                       member=None):
        if step == 100:
            raise ev.CheckpointMissingError("step 100 vanished")
        return {"eval_return": 1.0, "frames": step, "episodes": episodes,
                "config": cfg.name, "host_env": host_env,
                "episodes_truncated": 0}

    argv = ["evaluate", "--config", "cartpole", "--platform", "cpu",
            "--checkpoint-dir", str(tmp_path / "run"), "--episodes", "1",
            "--all-steps", "--host-env", "CartPole-v1"]
    with mock.patch.object(sys, "argv", argv), \
            mock.patch.object(ev, "evaluate_checkpoint_host",
                              side_effect=fake_host_eval):
        ev.main()
    rows = [json.loads(line) for line in
            capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert "device" in rows.pop(0)   # the CLI's first line names the device
    assert rows[0]["frames"] == 100 and "skipped" in rows[0]
    assert rows[1]["frames"] == 200 and rows[1]["eval_return"] == 1.0

    with mock.patch.object(sys, "argv", argv), \
            mock.patch.object(ev, "evaluate_checkpoint_host",
                              side_effect=FileNotFoundError("no ROM")), \
            pytest.raises(FileNotFoundError, match="no ROM"):
        ev.main()


@pytest.mark.parametrize("mode", ["vector", "pixel_dedup"])
def test_checkpoint_replay_resumes_bit_equal(tmp_path, mode):
    """--checkpoint-replay saves the WHOLE fused carry, so an
    interrupted+resumed run must reproduce the uninterrupted run's
    parameters BIT-EXACTLY — the property learner-only checkpoints
    cannot give (replay refills with fresh experience there). VERDICT
    round-3 next #7. The pixel_dedup variant pins the same property for
    the frame-dedup ring carry (single-frame obs leaves)."""
    from dist_dqn_tpu.train import train

    if mode == "vector":
        cfg = CONFIGS["cartpole"]
        cfg = dataclasses.replace(
            cfg,
            network=dataclasses.replace(cfg.network, mlp_features=(16,)),
            replay=dataclasses.replace(cfg.replay, capacity=512,
                                       min_fill=64),
            learner=dataclasses.replace(cfg.learner, batch_size=16),
            actor=dataclasses.replace(cfg.actor, num_envs=4),
            eval_every_steps=0,
        )
    else:
        cfg = CONFIGS["atari"]
        cfg = dataclasses.replace(
            cfg,
            env_name="pixel_catch",
            network=dataclasses.replace(cfg.network, torso="small",
                                        hidden=16,
                                        compute_dtype="float32"),
            replay=dataclasses.replace(cfg.replay, capacity=512,
                                       min_fill=64, frame_dedup=True),
            learner=dataclasses.replace(cfg.learner, batch_size=8),
            actor=dataclasses.replace(cfg.actor, num_envs=4),
            train_every=2,
            eval_every_steps=0,
        )
    quiet = lambda s: None  # noqa: E731

    ref_carry, _ = train(cfg, total_env_steps=600, chunk_iters=75,
                         log_fn=quiet)

    d = str(tmp_path / "run")
    train(cfg, total_env_steps=300, chunk_iters=75, log_fn=quiet,
          checkpoint_dir=d, checkpoint_replay=True)
    carry2, hist = train(cfg, total_env_steps=600, chunk_iters=75,
                         log_fn=quiet, checkpoint_dir=d,
                         checkpoint_replay=True)
    assert hist[-1]["env_frames"] == 600
    for a, b in zip(jax.tree.leaves(ref_carry.learner.params),
                    jax.tree.leaves(carry2.learner.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # The replay ring came back too (contents, not just shapes).
    for a, b in zip(jax.tree.leaves(ref_carry.replay),
                    jax.tree.leaves(carry2.replay)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", ["feed_forward", "recurrent"])
def test_whole_carry_checkpoint_from_before_the_one_carry_restores(
        tmp_path, kind):
    """A ``--checkpoint-replay`` directory written by the two-loop code —
    ``TrainCarry`` without an actor state, ``R2D2Carry`` with the pair in
    third place: the trees built here, saved as that code saved them —
    restores under the one carry and continues bit-equal to the
    uninterrupted run (utils/checkpoint.py checkpoint_tree)."""
    import collections

    from dist_dqn_tpu.train import train
    from dist_dqn_tpu.utils.checkpoint import record_checkpoint_kind

    shared = ["replay", "learner", "rng", "iteration", "ep_return",
              "completed_return", "completed_count", "loss_sum",
              "train_count"]
    if kind == "feed_forward":
        cfg = CONFIGS["cartpole"]
        cfg = dataclasses.replace(
            cfg,
            network=dataclasses.replace(cfg.network, mlp_features=(16,)),
            replay=dataclasses.replace(cfg.replay, capacity=512,
                                       min_fill=64),
            learner=dataclasses.replace(cfg.learner, batch_size=16))
        Old = collections.namedtuple("TrainCarry",
                                     ["env_state", "obs"] + shared)
    else:
        cfg = CONFIGS["r2d2"]
        cfg = dataclasses.replace(
            cfg,
            env_name="cartpole",
            network=dataclasses.replace(
                cfg.network, torso="mlp", mlp_features=(16,), hidden=0,
                lstm_size=8, compute_dtype="float32"),
            replay=dataclasses.replace(
                cfg.replay, capacity=512, min_fill=64, burn_in=2,
                unroll_length=4, sequence_stride=2),
            learner=dataclasses.replace(cfg.learner, n_step=2,
                                        batch_size=16))
        Old = collections.namedtuple(
            "R2D2Carry", ["env_state", "obs", "actor_carry"] + shared)
    cfg = dataclasses.replace(
        cfg, actor=dataclasses.replace(cfg.actor, num_envs=4),
        eval_every_steps=0)
    kw = dict(chunk_iters=75, log_fn=lambda s: None)

    ref_carry, _ = train(cfg, total_env_steps=600, **kw)
    half, _ = train(cfg, total_env_steps=300, **kw)
    d = str(tmp_path / "run")
    ckpt = TrainCheckpointer(d, save_every_frames=100_000)
    record_checkpoint_kind(d, "carry")
    ckpt.save(300, Old(**{f: getattr(half, f) for f in Old._fields}))
    ckpt.close()

    carry, hist = train(cfg, total_env_steps=600, checkpoint_dir=d,
                        checkpoint_replay=True, **kw)
    assert [row["env_frames"] for row in hist] == [600]   # resumed at 300
    assert type(carry) is type(ref_carry)
    for a, b in zip(jax.tree.leaves(ref_carry), jax.tree.leaves(carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_replay_completed_run_does_not_rerun(tmp_path):
    """Relaunching a FINISHED --checkpoint-replay run must be a no-op
    (the restored carry's cumulative counter must not reset the loop
    cursor to zero and train the whole budget again)."""
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(16,)),
        replay=dataclasses.replace(cfg.replay, capacity=512, min_fill=64),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        eval_every_steps=0,
    )
    quiet = lambda s: None  # noqa: E731
    d = str(tmp_path / "run")
    train(cfg, total_env_steps=300, chunk_iters=75, log_fn=quiet,
          checkpoint_dir=d, checkpoint_replay=True)
    _, hist = train(cfg, total_env_steps=300, chunk_iters=75, log_fn=quiet,
                    checkpoint_dir=d, checkpoint_replay=True)
    assert hist == []


def test_checkpoint_replay_runs_stay_evaluable(tmp_path):
    """evaluate.py must handle --checkpoint-replay (full-carry)
    checkpoints: the kind marker routes the restore through a carry
    template and extracts the learner — single-point and --all-steps
    curve both work (code-review round 4)."""
    from dist_dqn_tpu.evaluate import (evaluate_checkpoint,
                                       evaluate_checkpoint_curve)
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(16,)),
        replay=dataclasses.replace(cfg.replay, capacity=512, min_fill=64),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        eval_every_steps=0,
    )
    d = str(tmp_path / "run")
    train(cfg, total_env_steps=600, chunk_iters=75, log_fn=lambda s: None,
          checkpoint_dir=d, checkpoint_replay=True, save_every_frames=300)
    out = evaluate_checkpoint(cfg, d, episodes=2)
    assert out["frames"] == 600 and 1.0 <= out["eval_return"] <= 500.0
    rows = evaluate_checkpoint_curve(cfg, d, episodes=1)
    assert [r["frames"] for r in rows] and rows[-1]["frames"] == 600


def test_checkpoint_kind_mismatch_names_the_flag(tmp_path):
    """Resuming a directory with the OTHER --checkpoint-replay setting
    must say the flag is the cause, not claim an architecture drift."""
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(16,)),
        replay=dataclasses.replace(cfg.replay, capacity=512, min_fill=64),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        eval_every_steps=0,
    )
    d = str(tmp_path / "run")
    train(cfg, total_env_steps=300, chunk_iters=75, log_fn=lambda s: None,
          checkpoint_dir=d)
    with pytest.raises(ValueError, match="checkpoint-replay"):
        train(cfg, total_env_steps=600, chunk_iters=75,
              log_fn=lambda s: None, checkpoint_dir=d,
              checkpoint_replay=True)
