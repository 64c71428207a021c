"""The chunk program's one compilation (train.py ``_compile_chunk``): a
plain statement ahead of the first dispatch, in the single-device, mesh and
population loops alike. Its failure is the run's failure, and the first
dispatch compiles nothing."""
from __future__ import annotations

import dataclasses

import jax
import pytest
from test_stages import _toy_cfg as _stages_toy_cfg

from dist_dqn_tpu import train as train_mod
from dist_dqn_tpu.config import PopulationConfig
from dist_dqn_tpu.telemetry import stages

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _toy_cfg(population: int = 1):
    return dataclasses.replace(
        _stages_toy_cfg(), population=PopulationConfig(size=population))


def _run(cfg, **kw):
    return train_mod.train(cfg, total_env_steps=8 * 25 * 2, chunk_iters=25,
                           log_fn=lambda _line: None, **kw)


@pytest.fixture()
def backend_compiles():
    """Names of the programs JAX's backend compiles, in order (the event
    ``perf/harness/compile_meter.py`` counts)."""
    names = []

    def listener(event, _secs, fun_name=None, **_):
        if event == BACKEND_COMPILE:
            names.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    yield names
    jax.monitoring.unregister_event_duration_listener(listener)


def test_chunk_program_compile_error_surfaces(monkeypatch):
    def refuse(_compiled):
        raise RuntimeError("the chunk program is refused")

    monkeypatch.setattr(stages, "keep", refuse)
    with pytest.raises(RuntimeError, match="the chunk program is refused"):
        _run(_toy_cfg())


LOOPS = {"single": ({}, {}),
         "mesh2": ({}, {"num_devices": 2}),
         "population": ({"population": 2}, {})}


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_chunk_program_is_compiled_once_ahead_of_the_first_dispatch(
        loop, backend_compiles, monkeypatch):
    cfg_kw, train_kw = LOOPS[loop]
    ahead = []
    compile_chunk = train_mod._compile_chunk

    def metered(*args):
        before = len(backend_compiles)
        compile_chunk(*args)
        ahead.extend(backend_compiles[before:])

    monkeypatch.setattr(train_mod, "_compile_chunk", metered)
    stages.keep(None)
    _, history = _run(_toy_cfg(**cfg_kw), **train_kw)
    assert len(history) == 2
    # one backend compile ahead of the loop, and it is the chunk program's
    assert len(ahead) == 1, ahead
    # ... and nowhere else: neither of the two dispatches compiled it again
    assert backend_compiles.count(ahead[0]) == 1, backend_compiles
    assert "act" in stages.table().values()
