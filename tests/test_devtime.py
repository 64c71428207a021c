"""telemetry/devtime.py: unit + integration coverage.

  * UtilizationLedger — busy + named causes + derived ``other`` residual
    conserve each chunk's wall, with clamping at the estimate edges;
  * sweep_device_memory — ``memory_stats()`` returning None, raising,
    or reporting partial/garbage dicts sweeps to exactly what was
    reported (gauges absent, never a crash) and the host-tracked peak
    is monotone;
  * capture_profile — a zero-second window lands a loadable trace;
  * the chaos A/B: an injected ``evac.drain`` stall on a real
    host-replay run lands in the ledger's ``evac_fence`` bucket, and the
    per-cause totals conserve against the run wall.
"""
from __future__ import annotations

import dataclasses
import os

import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.telemetry import devtime
from dist_dqn_tpu.telemetry.exposition import render_prometheus
from dist_dqn_tpu.telemetry.registry import Registry


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
# The acceptance A/B: chaos evac stall -> evac_fence
# ---------------------------------------------------------------------------

def test_host_replay_chaos_evac_stall_lands_in_evac_fence():
    """An injected ``evac.drain`` stall blocks the loop at the evac
    fence it already holds — the ledger must file that wait under
    ``evac_fence`` (not ``other``), and the per-cause totals must
    conserve against the run wall."""
    from dist_dqn_tpu import chaos
    from dist_dqn_tpu.host_replay_loop import run_host_replay

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
    assert "programs" not in out
