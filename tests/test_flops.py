"""The FLOP counts ``train_mfu`` is made of, held to the compiler.

``perf/reduce/flops.py`` and each reference module's ``grad_step_flops``
count a grad step from shapes. XLA's ``cost_analysis()`` counts what it
compiled — right for straight-line code, and a ``lax.scan`` / ``while``
body ONCE whatever its trip count. So every comparison here compiles ONE
un-scanned step at toy size (scans fully unrolled or one trip long) and
holds the shape count to that census; the census prices a whole chunk
program nowhere.
"""
import dataclasses
import importlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from perf.reduce import flops, peaks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _census_flops(compiled) -> float:
    """FLOPs of one execution by XLA's own count."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def test_cost_analysis_matches_analytic_nature_cnn():
    """``flops.cnn_layer_macs`` — the layer count every pixel
    configuration's ``grad_step_flops`` starts from — against the compiled
    forward pass of the atari network."""
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["atari"]
    net = build_network(cfg.network, 6)
    obs = jnp.zeros((32, 84, 84, 4), jnp.uint8)
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0), obs)
    compiled = jax.jit(net.apply).lower(params, obs).compile()
    want = 2.0 * 32 * sum(flops.cnn_layer_macs(
        (84, 84, 4), flops.NATURE_CONVS, 512, 6, dueling=False))
    assert want / 1.1 < _census_flops(compiled) < want * 1.1


def _dqn_toy():
    from dist_dqn_tpu.config import CONFIGS

    cfg = CONFIGS["atari"]
    return dataclasses.replace(
        cfg, learner=dataclasses.replace(cfg.learner, batch_size=32))


def _r2d2_toy(lstm_size: int, seqs: int):
    """perf/tests/test_perf_reference_r2d2.py's sizes, the LSTM's scan
    unrolled past the window so that the step is straight-line code."""
    from dist_dqn_tpu.config import CONFIGS

    cfg = CONFIGS["r2d2"]
    return dataclasses.replace(
        cfg,
        network=dataclasses.replace(
            cfg.network, torso="small", hidden=32, lstm_size=lstm_size,
            lstm_unroll=64, compute_dtype="float32", lstm_dtype="float32",
            remat_torso=False),
        replay=dataclasses.replace(cfg.replay, burn_in=4, unroll_length=8,
                                   sequence_stride=4, capacity=256),
        learner=dataclasses.replace(cfg.learner, n_step=3, batch_size=seqs))


def _hybrid_toy(toy_config: dict, wider):
    """A perf test's toy core, widened until the core is a third of the
    count (at the toy widths the torso is 99% of it) and with every scan
    one trip long (``chunk_size`` >= the 12-step window)."""
    from dist_dqn_tpu.config import CONFIGS, apply_overrides

    return apply_overrides(CONFIGS[toy_config["preset"]],
                           list(toy_config["overrides"]) + list(wider))


def _twotower_toy():
    from perf.tests.test_perf_run_twotower import TOY_CORE_CONFIG

    return _hybrid_toy(TOY_CORE_CONFIG, (
        "network.hidden=256", "network.core.mamba_num_heads=8",
        "network.core.mamba_head_dim=64", "network.core.ssm_state_size=16",
        "network.core.chunk_size=16",
        "network.core.moe_intermediate_size=512",
        "network.core.moe_shared_expert_intermediate_size=512",
        "network.core.head_dim=64", "network.core.attention_window=16"))


def _laguna_toy():
    from perf.tests.test_perf_laguna import TOY_LAGUNA_CONFIG

    return _hybrid_toy(TOY_LAGUNA_CONFIG, (
        "network.hidden=256", "network.core.head_dim=64",
        "network.core.intermediate_size=1024",
        "network.core.moe_intermediate_size=512",
        "network.core.moe_shared_expert_intermediate_size=512"))


def _step_census_over_shape_count(reference_name: str, cfg) -> float:
    """XLA's count of the program's own train step (as the reference check
    builds it) over the module's ``grad_step_flops``."""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    reference = importlib.import_module(f"perf.reference.{reference_name}")
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, train_step, _ = reference.make_program(cfg, env, net)
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    batch = reference.seeded_batch(1, 0, cfg.learner.batch_size, cfg, env)
    compiled = jax.jit(train_step).lower(state, batch).compile()
    return _census_flops(compiled) / reference.grad_step_flops(cfg, env)


# The census also counts what the shape count leaves out by its own
# definition (elementwise work, the loss, the optimizer): ratios read
# 1.04 / 1.01 / 1.09 / 1.10 when this was written.
TOYS = {"dqn_float32": _dqn_toy,
        "r2d2_float32": lambda: _r2d2_toy(lstm_size=16, seqs=8),
        "twotower_float32": _twotower_toy,
        "laguna_float32": _laguna_toy}


@pytest.mark.parametrize("reference_name", sorted(TOYS))
def test_grad_step_flops_match_the_compiled_step(reference_name):
    """Every reference module's ``grad_step_flops`` — the numerator of
    ``train_mfu`` and ``loss_grad_mfu`` in that configuration's cells —
    against the census of one un-scanned step of the program's learner."""
    ratio = _step_census_over_shape_count(reference_name,
                                          TOYS[reference_name]())
    assert 1 / 1.2 < ratio < 1.2, ratio


def test_r2d2_analytic_cell_flops_match_unrolled_census():
    """The recurrent count where the CELL carries it: an LSTM of 2,048 on
    the small torso is four fifths of ``r2d2_float32.grad_step_flops``
    (four gates a step, forward for both networks over the window, backward
    over the training positions), the scan unrolled to straight-line
    code."""
    ratio = _step_census_over_shape_count(
        "r2d2_float32", _r2d2_toy(lstm_size=2048, seqs=4))
    assert 1 / 1.2 < ratio < 1.2, ratio


def test_peak_lookup_and_mfu():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    assert peaks.peak("TPU v5e", "hbm_bytes_per_s") == 819e9
    assert 19.7e12 / peaks.peak("TPU v5 lite", "bf16_flops") \
        == pytest.approx(0.1)


def test_unknown_accelerator_kind_raises():
    """A device missing from the peak table is an error naming the kind —
    the CPU included: no number under a device metric's name."""
    for kind in ("TPU v99", jax.devices()[0].device_kind):
        with pytest.raises(KeyError, match=kind):
            peaks.peak(kind, "bf16_flops")


def test_platform_flag_scripts_require_an_accelerator_by_default():
    """The four benchmark scripts that take --platform: with no platform
    named, a CPU backend is refused before anything is timed (one child,
    all four mains)."""
    code = (
        "import runpy, sys\n"
        "for name in ('sampler_bench', 'learner_bench', 'population_bench',\n"
        "             'r2d2_pixel_learning'):\n"
        "    sys.argv = [name]\n"
        "    try:\n"
        "        runpy.run_path(f'benchmarks/{name}.py', run_name='__main__')\n"
        "    except RuntimeError as e:\n"
        "        assert 'no accelerator' in str(e), e\n"
        "    else:\n"
        "        raise SystemExit(f'{name} ran on a CPU backend')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout[-1000:] + proc.stderr[-2000:]
    assert proc.stdout.strip() == ""            # no row was printed
