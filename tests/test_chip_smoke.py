"""chip_smoke.py (repo root): the leg functions at toy size on the CPU,
the script's refusal to pass without a chip, and the helpers it leans on
(compile-cache placement, spawn-safe imports).

The script itself only passes on an accelerator; nothing in the
environment makes it pass here. What tier-1 pins is that every leg's
plumbing — CLI capture, chunk checks, stage-table check, checkpoint/evaluate
round trip, draw comparison, shard/replica checks — runs end to end, so a
chip call is never spent on a bug in the smoke script.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = ("network.mlp_features=16", "replay.capacity=512",
       "replay.min_fill=64", "learner.batch_size=16", "eval_episodes=2")


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


def test_leg_fused_toy(meter, capsys):
    out = chip_smoke.leg_fused(
        meter, config="cartpole", overrides=TOY + ("actor.num_envs=4",),
        chunk_iters=50, chunks=3, episodes=2)
    assert out["env_frames"] == 600 and out["grad_steps"] > 0
    assert out["compile_s"] > 0 and out["stage_instructions"] > 0
    # The CLI's own log still reached stdout, device line first.
    first = json.loads(capsys.readouterr().out.splitlines()[0])
    assert first["device"]["platform"] == "cpu"


def test_leg_fused_fails_without_a_post_fill_chunk(meter):
    with pytest.raises(chip_smoke.SmokeFailure, match="post-fill"):
        chip_smoke.leg_fused(
            meter, config="cartpole",
            overrides=TOY + ("actor.num_envs=4",), chunk_iters=10, chunks=2)


def test_leg_kernel_toy(meter, monkeypatch):
    """Off the chip the kernel only runs interpreted, and only on
    request; the leg then expects NO Mosaic call in the compiled text."""
    overrides = TOY + ("actor.num_envs=4", "replay.prioritized=true",
                       "replay.pallas_sampler=true")
    with pytest.raises(chip_smoke.SmokeFailure, match="XLA sampler"):
        chip_smoke.leg_kernel(meter, config="cartpole", overrides=overrides,
                              chunk_iters=40, chunks=2)
    monkeypatch.setenv("DIST_DQN_PALLAS_INTERPRET", "1")
    out = chip_smoke.leg_kernel(meter, config="cartpole",
                                overrides=overrides, chunk_iters=40,
                                chunks=2)
    assert out["mosaic_custom_call"] is False
    assert out["plane_shape"] == [128 * 4]
    assert out["run_plane"]["mass_gap_kernel_vs_xla"] \
        <= chip_smoke.MASS_GAP_TOL
    assert out["run_plane"]["exact_kernel_vs_xla"] \
        >= chip_smoke.MIN_EXACT_KERNEL_VS_XLA
    assert out["integer_plane"]["exact_kernel_vs_f64"] >= 0.98
    assert out["integer_plane"]["exact_kernel_vs_xla"] >= 0.98


def test_leg_mesh_toy(meter):
    out = chip_smoke.leg_mesh(
        meter, config="cartpole", overrides=TOY + ("actor.num_envs=8",),
        chunk_iters=50, chunks=2, num_devices=4)
    assert out["env_frames"] == 800
    assert out["obs_shard_shape"][0] == 2          # 8 lanes over 4 devices
    assert out["replicated_param_leaves"] > 0
    assert out["stage_instructions"] > 0


def _run_script(cwd):
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_script_fails_fast_without_a_chip():
    proc = _run_script(REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no accelerator" in proc.stderr


def test_script_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code (JAX reads
    the variable itself). Unset: <checkout>/.jax_cache, a fixed path."""
    import jax

    from dist_dqn_tpu.utils import backend

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv(backend.CACHE_ENV, "/x")
    assert backend.enable_compile_cache() == "/x"
    assert updates == []
    monkeypatch.delenv(backend.CACHE_ENV)
    expected = os.path.join(REPO, ".jax_cache")
    assert backend.enable_compile_cache() == expected
    assert updates == [("jax_compilation_cache_dir", expected)]


def test_accelerator_is_required_not_assumed():
    from dist_dqn_tpu.utils import backend

    with pytest.raises(RuntimeError, match="no accelerator"):
        backend.require_accelerator()
    assert backend.device_summary()["platform"] == "cpu"


def test_spawned_children_do_not_touch_the_backend():
    """A chip belongs to one process. multiprocessing's spawn re-imports
    the parent's ``__main__`` and the child's target module: none of
    those imports may initialise a JAX backend."""
    code = (
        "import runpy, sys\n"
        "sys.argv = ['x']\n"
        "for mod in ('dist_dqn_tpu.train', 'dist_dqn_tpu.actors.actor',\n"
        "            'dist_dqn_tpu.actors.feeder', 'chip_smoke'):\n"
        "    runpy.run_module(mod, run_name='__mp_main__')\n"
        "runpy.run_path('benchmarks/serving_bench.py',\n"
        "               run_name='__mp_main__')\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
