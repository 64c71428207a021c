"""--set dotted-path config overrides (config.apply_overrides): typed
coercion, nesting, section/unknown-field errors, and the train CLI
honoring the flag end-to-end."""
import pytest

from dist_dqn_tpu.config import CONFIGS, apply_overrides


def test_typed_coercion_across_field_kinds():
    cfg = apply_overrides(CONFIGS["atari"], [
        "network.dueling=true",
        "network.torso=small",
        "learner.batch_size=64",
        "learner.learning_rate=3e-4",
        "network.mlp_features=128,64",
        "replay.capacity=0x1000",
        "train_every=2",
    ])
    assert cfg.network.dueling is True
    assert cfg.network.torso == "small"
    assert cfg.learner.batch_size == 64
    assert cfg.learner.learning_rate == pytest.approx(3e-4)
    assert cfg.network.mlp_features == (128, 64)
    assert cfg.replay.capacity == 4096
    assert cfg.train_every == 2
    # The source preset is untouched (frozen dataclasses, pure replace).
    assert CONFIGS["atari"].network.dueling is False


def test_int_fields_accept_unambiguous_shorthand():
    """1e6 / 2.5e5 / 200_000 spellings have exactly one integer meaning;
    the coercion takes them. Non-integral floats stay errors (ADVICE
    round 3)."""
    cfg = apply_overrides(CONFIGS["atari"], [
        "replay.capacity=1e6",
        "replay.min_fill=2.5e4",
        "total_env_steps=200_000",
    ])
    assert cfg.replay.capacity == 1_000_000
    assert cfg.replay.min_fill == 25_000
    assert cfg.total_env_steps == 200_000
    with pytest.raises(ValueError, match="batch_size: expected an int"):
        apply_overrides(CONFIGS["atari"], ["learner.batch_size=1.5"])


def test_optional_field_accepts_none_and_bool():
    cfg = apply_overrides(CONFIGS["atari"],
                          ["replay.store_final_obs=true"])
    assert cfg.replay.store_final_obs is True
    cfg = apply_overrides(cfg, ["replay.store_final_obs=none"])
    # Round-trips back to the auto default.
    assert cfg.replay.store_final_obs is None


@pytest.mark.parametrize("bad, hint", [
    ("network.duelling=true", "unknown field"),
    ("network=big", "config section"),
    ("learner.batch_size", "dotted.path=value"),
    ("network.dueling=maybe", "expected a bool"),
    ("network.dueling.x=1", "past a leaf"),
    ("learner.batch_size=abc", "batch_size: expected an int"),
    ("learner.learning_rate=fast", "learning_rate: expected a float"),
])
def test_errors_name_the_problem(bad, hint):
    with pytest.raises(ValueError, match=hint):
        apply_overrides(CONFIGS["atari"], [bad])


def test_train_cli_honors_set(tmp_path, capsys):
    """End-to-end through the real CLI surface: --set reshapes the run."""
    import json
    import sys
    from unittest import mock

    from dist_dqn_tpu.train import main

    argv = ["train", "--config", "cartpole", "--platform", "cpu",
            "--total-env-steps", "600", "--chunk-iters", "150",
            "--set", "actor.num_envs=4",
            "--set", "network.mlp_features=16",
            "--set", "replay.capacity=512",
            "--set", "replay.min_fill=64",
            "--set", "learner.batch_size=16"]
    with mock.patch.object(sys, "argv", argv):
        main()
    rows = [json.loads(line) for line in
            capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    # The CLI's first JSON line names the device as JAX reports it;
    # the run manifest (ISSUE 4) follows, carries the same block, and
    # must fingerprint the OVERRIDDEN config, not the preset.
    assert rows[0]["device"]["platform"] == "cpu"
    assert set(rows[0]["device"]) == {"platform", "kind", "count"}
    assert rows[1]["manifest"]["device"] == rows[0]["device"]
    assert rows[1]["manifest"]["config"]["actor"]["num_envs"] == 4
    # 4 env lanes (not the preset's 16): 150-iter chunks advance 600
    # frames each.
    metric_rows = [r for r in rows if "env_frames" in r]
    assert metric_rows and metric_rows[0]["env_frames"] == 600


def test_train_cli_eval_zero_disables_without_save_churn(tmp_path, capsys):
    """An explicit --eval-every-steps 0 DISABLES eval (it used to fall
    through a truthiness test to the config period), and the checkpoint
    cadence must not collapse to save-every-chunk when it does."""
    import json
    import os
    import sys
    from unittest import mock

    from dist_dqn_tpu.train import main

    ckpt_dir = str(tmp_path / "ck")
    argv = ["train", "--config", "cartpole", "--platform", "cpu",
            "--total-env-steps", "1200", "--chunk-iters", "100",
            "--eval-every-steps", "0",
            "--checkpoint-dir", ckpt_dir,
            "--set", "actor.num_envs=4",
            "--set", "network.mlp_features=16",
            "--set", "replay.capacity=512",
            "--set", "replay.min_fill=64",
            "--set", "learner.batch_size=16"]
    with mock.patch.object(sys, "argv", argv):
        main()
    rows = [json.loads(line) for line in
            capsys.readouterr().out.splitlines()
            if line.startswith("{") and "env_frames" in line]
    assert rows and all("eval_return" not in r for r in rows)
    # 3 chunks ran; the save cadence fell back to a sane default —
    # first boundary crossing (400) plus the end-of-run save (1200),
    # NOT one per chunk (800 would appear if the cadence collapsed).
    steps = {d for d in os.listdir(ckpt_dir) if d.isdigit()}
    assert steps == {"400", "1200"}


def test_train_cli_reports_bad_set_cleanly(capsys):
    """A bad --set exits via parser.error (clean usage message naming the
    failing path), not a traceback."""
    import sys
    from unittest import mock

    from dist_dqn_tpu.train import main

    argv = ["train", "--config", "cartpole", "--platform", "cpu",
            "--set", "learner.batch_size=abc"]
    with mock.patch.object(sys, "argv", argv):
        with pytest.raises(SystemExit) as exc:
            main()
    assert exc.value.code == 2
    assert "learner.batch_size: expected an int" in capsys.readouterr().err
