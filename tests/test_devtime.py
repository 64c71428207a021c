"""Chip-time attribution plane (ISSUE 19): unit + integration coverage.

Four layers:

  * ProgramRegistry/ProgramRecord — get-or-create identity, one-shot
    cost attachment (failures degrade to flops=None, never retrying on
    the hot path), snapshot shape, registry-derived learner MFU (None
    on chips without a known peak — the gauge must be ABSENT, not 0);
  * UtilizationLedger — busy + named causes + derived ``other`` residual
    conserve each chunk's wall, with clamping at the estimate edges;
  * sweep_device_memory — ``memory_stats()`` returning None, raising,
    or reporting partial/garbage dicts sweeps to exactly what was
    reported (gauges absent, never a crash) and the host-tracked peak
    is monotone;
  * the chaos A/B the acceptance pins: an injected ``evac.drain`` stall
    on a real host-replay run lands in the ledger's ``evac_fence``
    bucket, the run's programs all show in its summary census, and the
    per-cause totals conserve against the run wall.
"""
from __future__ import annotations

import dataclasses
import os
import types

import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.telemetry import collectors as tmc
from dist_dqn_tpu.telemetry import devtime
from dist_dqn_tpu.telemetry.exposition import render_prometheus
from dist_dqn_tpu.telemetry.registry import Registry


@pytest.fixture(autouse=True)
def _fresh_program_registry():
    """Tests below mutate the process-global registry (the loops use
    it); leave a clean one behind either way."""
    yield
    devtime.reset_program_registry()


class _Cost:
    """A stand-in for jax.stages.Compiled: just the cost census."""

    def __init__(self, flops=None, nbytes=None):
        self._c = {}
        if flops is not None:
            self._c["flops"] = flops
        if nbytes is not None:
            self._c["bytes accessed"] = nbytes

    def cost_analysis(self):
        return self._c


def _tiny_cfg():
    cfg = CONFIGS["cartpole"]
    return dataclasses.replace(
        cfg,
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(32,), hidden=0,
                                    compute_dtype="float32"),
        replay=dataclasses.replace(cfg.replay, capacity=4096, min_fill=64,
                                   prioritized=False),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
    )


# ---------------------------------------------------------------------------
# ProgramRegistry / ProgramRecord
# ---------------------------------------------------------------------------

def test_register_is_get_or_create_and_snapshots():
    reg = devtime.ProgramRegistry(metrics=Registry())
    rec = reg.register("p", loop="l", cost=_Cost(100.0, 50.0),
                       role="train")
    assert reg.register("p", loop="l") is rec
    assert reg.get("p", "l") is rec
    assert reg.get("p", "other") is None
    rec.count_dispatch(3)
    rec.add_device_seconds(0.5)
    snap = reg.snapshot("l")["p"]
    assert snap["flops"] == 100.0 and snap["bytes"] == 50.0
    assert snap["dispatches"] == 3.0
    assert snap["device_seconds"] == pytest.approx(0.5)
    assert snap["arith_intensity"] == pytest.approx(2.0)
    assert reg.snapshot("other") == {}
    # add_device_seconds ignores non-positive samples (clock skew at a
    # fence must not walk the counter backwards).
    rec.add_device_seconds(-1.0)
    assert rec.device_seconds == pytest.approx(0.5)


def test_attach_cost_is_one_shot_and_failures_degrade():
    reg = devtime.ProgramRegistry(metrics=Registry())
    rec = reg.register("p", loop="l")
    assert not rec.cost_attached

    def boom():
        raise RuntimeError("no cost model on this backend")

    rec.attach_cost(boom)
    # A failed harvest still closes the one shot: the hot path must not
    # retry a failing trace every dispatch.
    assert rec.cost_attached and rec.flops is None and rec.bytes is None
    rec.attach_cost(_Cost(1.0))
    assert rec.flops is None
    # Zero-arg callables returning a census are unwrapped; the first
    # SUCCESSFUL harvest wins and later attaches are ignored.
    rec2 = reg.register("q", loop="l", cost=lambda: _Cost(7.0, 2.0))
    assert rec2.flops == 7.0
    rec2.attach_cost(_Cost(999.0))
    assert rec2.flops == 7.0


def test_lowered_without_cost_analysis_is_compiled_for_the_census():
    """On a TPU ``Lowered.cost_analysis()`` returns None — only the
    compiled executable has a census there. A source that has none but
    can be compiled is compiled, so the census is not silently absent
    on the one platform it exists for."""
    class _TpuLowered:
        compiles = 0

        def cost_analysis(self):
            return None

        def compile(self):
            _TpuLowered.compiles += 1
            return _Cost(11.0, 4.0)

    reg = devtime.ProgramRegistry(metrics=Registry())
    rec = reg.register("p", loop="l", cost=lambda: _TpuLowered())
    assert (rec.flops, rec.bytes) == (11.0, 4.0)
    assert _TpuLowered.compiles == 1


def test_learner_mfu_registry_derived_and_absent_on_cpu():
    metrics = Registry()
    reg = devtime.reset_program_registry(metrics)
    rec = reg.register("train", loop="l", cost=_Cost(1e12), role="train")
    other = reg.register("act", loop="l", cost=_Cost(1e30), role="act")
    other.count_dispatch(5)
    other.add_device_seconds(3.0)
    tpu = types.SimpleNamespace(device_kind="TPU v4")

    # No device time on any role="train" record yet -> underivable, and
    # set_learner_mfu must leave the gauge ABSENT (a 0 would read as a
    # real 0% utilization on a dashboard).
    assert devtime.set_learner_mfu("l", device=tpu, reg=metrics) is None
    assert tmc.LEARNER_MFU not in render_prometheus(metrics)

    rec.count_dispatch(10)
    rec.add_device_seconds(1.0)
    # Only the role="train" census counts: 1e12 FLOPs x 10 execs over
    # 1 s against the v4 peak (275 TFLOP/s); the act program's absurd
    # FLOPs must not leak into the numerator.
    want = (1e12 * 10) / 1.0 / 275e12
    assert reg.learner_mfu("l", device=tpu) == pytest.approx(want)
    assert devtime.set_learner_mfu("l", device=tpu, reg=metrics) \
        == pytest.approx(want)
    assert tmc.LEARNER_MFU in render_prometheus(metrics)

    # CPU (unknown chip peak) -> None, never a made-up denominator.
    cpu = types.SimpleNamespace(device_kind="cpu")
    assert reg.learner_mfu("l", device=cpu) is None


# ---------------------------------------------------------------------------
# UtilizationLedger
# ---------------------------------------------------------------------------

def test_ledger_conserves_wall_and_derives_other():
    led = devtime.UtilizationLedger("t", reg=Registry())
    out = led.observe_chunk(10.0, 4.0, sample=1.0, evac_fence=2.0)
    assert out["busy"] == 4.0
    assert out["other"] == pytest.approx(3.0)
    snap = led.snapshot()
    assert snap["chunks"] == 1.0
    total = snap["busy"] + sum(snap[c] for c in devtime.IDLE_CAUSES)
    assert total == pytest.approx(10.0)


def test_ledger_clamps_estimates():
    led = devtime.UtilizationLedger("t", reg=Registry())
    # busy is an estimate sampled at fences: it can overshoot the wall
    # (clock edges) and the named causes can over-explain it — neither
    # may produce a negative bucket.
    out = led.observe_chunk(1.0, 5.0, sample=3.0)
    assert out["busy"] == 1.0
    assert out["other"] == 0.0
    assert led.snapshot()["sample"] == pytest.approx(3.0)
    out = led.observe_chunk(-2.0, -1.0)
    assert out["wall"] == 0.0 and out["busy"] == 0.0


# ---------------------------------------------------------------------------
# Device memory telemetry
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, ident, stats):
        self.id = ident
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_sweep_device_memory_none_partial_and_raising():
    metrics = Registry()
    devs = [
        _Dev(0, None),                          # CPU: reports nothing
        _Dev(1, {"bytes_in_use": 100, "bytes_limit": 400,
                 "weird": "not-a-number"}),     # partial + garbage kind
        _Dev(2, RuntimeError("no stats")),      # backend raises
    ]
    swept = devtime.sweep_device_memory(reg=metrics, devices=devs)
    assert set(swept) == {"1"}
    assert swept["1"]["bytes_in_use"] == 100.0
    assert swept["1"]["bytes_limit"] == 400.0
    assert "weird" not in swept["1"]
    assert swept["1"]["peak_bytes_in_use_seen"] >= 100.0
    rendered = render_prometheus(metrics)
    assert 'device="1"' in rendered
    assert 'device="0"' not in rendered and 'device="2"' not in rendered

    # The host-tracked high-water mark is monotone across sweeps even
    # when the backend's own bytes_in_use drops.
    peak0 = swept["1"]["peak_bytes_in_use_seen"]
    swept2 = devtime.sweep_device_memory(
        reg=metrics, devices=[_Dev(1, {"bytes_in_use": 40})])
    assert swept2["1"]["peak_bytes_in_use_seen"] == peak0

    # A jax-free / deviceless sweep degrades to an empty dict.
    assert devtime.sweep_device_memory(reg=Registry(), devices=[]) == {}


# ---------------------------------------------------------------------------
# On-demand profiling
# ---------------------------------------------------------------------------

def test_capture_profile_writes_loadable_trace(tmp_path):
    out = devtime.capture_profile(0, base_dir=str(tmp_path))
    assert "error" not in out, out
    assert os.path.isdir(out["trace_dir"])
    assert out["files"] >= 1, "an xprof window must land on disk"
    assert out["seconds"] == 0.0
    # The HTTP handler passes the query value through as a string.
    out2 = devtime.capture_profile("0", base_dir=str(tmp_path))
    assert "error" not in out2 and out2["trace_dir"] != out["trace_dir"]
    assert devtime.capture_profile("nope")["error"].startswith("bad")


# ---------------------------------------------------------------------------
# The acceptance A/B: chaos evac stall -> evac_fence, census complete
# ---------------------------------------------------------------------------

def test_host_replay_chaos_evac_stall_lands_in_evac_fence():
    """An injected ``evac.drain`` stall blocks the loop at the evac
    fence it already holds — the ledger must file that wait under
    ``evac_fence`` (not ``other``), the run's summary census must name
    both registered programs with dispatch counts, and the per-cause
    totals must conserve against the run wall."""
    from dist_dqn_tpu import chaos
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    devtime.reset_program_registry()
    plan = chaos.FaultPlan(seed=7, events=(
        chaos.FaultEvent("evac.drain", "stall", at_hit=2,
                         args={"delay_s": 0.8}),))
    with chaos.installed(plan, registry=Registry()) as inj:
        out = run_host_replay(_tiny_cfg(), total_env_steps=3200,
                              chunk_iters=50, log_fn=lambda s: None)
    assert [e["seam"] for e in inj.injected] == ["evac.drain"]

    chip = out["chip_time"]
    assert chip["chunks"] == 8.0  # 3200 / (50 iters x 8 lanes)
    # The 0.8 s stall sat on the critical path at the fence; a tiny
    # CPU chunk has nowhere near that much pipeline slack to hide it.
    assert chip["evac_fence"] >= 0.4, chip
    # Conservation: the decomposition never exceeds the run wall and
    # busy never exceeds the decomposed total.
    total = chip["busy"] + sum(chip[c] for c in devtime.IDLE_CAUSES)
    assert 0.0 < total <= out["wall_s"] + 1e-6
    assert chip["busy"] <= total

    progs = out["programs"]
    assert set(progs) >= {"host_replay.collect",
                          "host_replay.train_step"}
    assert progs["host_replay.train_step"]["dispatches"] \
        == out["grad_steps"]
    # Train device-seconds were attributed at the existing fences and
    # reconcile with the ledger's busy total exactly (same samples).
    assert progs["host_replay.train_step"]["device_seconds"] \
        == pytest.approx(chip["busy"])
