"""Flight recorder / stall watchdog / crash forensics (ISSUE 4):
ring semantics and overhead pins, fault injection — a deliberately
wedged EvacuationWorker and an injected NaN loss must each produce a
complete forensics bundle (named stacks, flight tail, registry
snapshot, manifest) within the configured deadline and flip /healthz to
503 — plus the /debug routes, the run manifest, and the evaluate-CLI
telemetry surface.
"""
import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from dist_dqn_tpu import telemetry
from dist_dqn_tpu.telemetry import flight as tm_flight
from dist_dqn_tpu.telemetry import manifest as tm_manifest
from dist_dqn_tpu.telemetry import watchdog as tm_watchdog
from dist_dqn_tpu.telemetry.flight import FlightRecorder, NullFlightRecorder


@pytest.fixture(autouse=True)
def _fresh_forensics_globals(monkeypatch):
    """Each test gets a fresh flight ring, no installed watchdog, a
    fresh sentinel and no run manifest (all are process globals)."""
    monkeypatch.delenv("DQN_FORENSICS_DIR", raising=False)
    monkeypatch.delenv("DQN_FLIGHT_RECORDER", raising=False)
    monkeypatch.delenv("DQN_FLIGHT_CAPACITY", raising=False)
    tm_flight._reset_for_tests()
    tm_watchdog._reset_for_tests()
    tm_manifest._reset_for_tests()
    yield
    tm_watchdog._reset_for_tests()
    tm_flight._reset_for_tests()
    tm_manifest._reset_for_tests()


def _tiny_cartpole(**learner_overrides):
    from dist_dqn_tpu.config import CONFIGS
    cfg = CONFIGS["cartpole"]
    return dataclasses.replace(
        cfg,
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=128),
        learner=dataclasses.replace(cfg.learner, **learner_overrides),
        eval_every_steps=0)


def _wait_for(predicate, timeout_s=10.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


# -- flight recorder ----------------------------------------------------------

def test_flight_ring_wraps_and_keeps_newest():
    r = FlightRecorder(capacity=8)
    for i in range(20):
        r.record("k", f"e{i}", i=i)
    assert r.total == 20
    assert len(r) == 8
    tail = r.tail()
    assert [e["name"] for e in tail] == [f"e{i}" for i in range(12, 20)]
    assert r.tail(3) == tail[-3:]
    ev = tail[-1]
    assert ev["kind"] == "k" and ev["i"] == 19
    assert ev["thread"] == "MainThread" and ev["t"] > 0
    snap = json.loads(json.dumps(r.snapshot()))  # JSON-able
    assert snap["total"] == 20 and len(snap["events"]) == 8


def test_null_flight_recorder_is_inert_and_env_disables():
    n = NullFlightRecorder()
    n.record("k", "x", a=1)
    assert n.tail() == [] and n.total == 0 and not n.enabled
    os.environ["DQN_FLIGHT_RECORDER"] = "0"
    tm_flight._reset_for_tests()
    assert not tm_flight.get_flight().enabled  # the --no-flight-recorder
    del os.environ["DQN_FLIGHT_RECORDER"]      # env plumbing
    tm_flight._reset_for_tests()
    assert tm_flight.get_flight().enabled


def test_flight_record_overhead_microbench():
    """The per-event cost the 'disabled cost ~zero / enabled cost ~1µs'
    claim rests on: generous 50µs/event bound absorbs CI noise while
    still catching an accidental O(capacity) or I/O regression."""
    r = FlightRecorder(capacity=1024)
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        r.record("span", "bench", dur_s=0.001)
    per_event = (time.perf_counter() - t0) / n
    assert per_event < 50e-6, f"record() costs {per_event * 1e6:.1f}µs"


def test_make_tracer_feeds_flight_ring():
    """With no Chrome trace path, span call sites still feed the flight
    ring (FlightTracer) — and the true NullTracer returns when the
    recorder is disabled."""
    from dist_dqn_tpu.utils.trace import FlightTracer, NullTracer, \
        make_tracer
    fr = tm_flight.configure(enabled=True, capacity=64)
    tr = make_tracer(None)
    assert isinstance(tr, FlightTracer)
    with tr.span("work", rows=3):
        pass
    tr.instant("boom", why="test")
    tr.counter("depth", 2)
    by_name = {e["name"]: e for e in fr.tail()}
    assert by_name["work"]["kind"] == "span" and by_name["work"]["rows"] == 3
    assert by_name["work"]["dur_s"] >= 0
    assert by_name["boom"]["kind"] == "instant"
    assert by_name["depth"]["value"] == 2.0
    tm_flight.configure(enabled=False)
    assert type(make_tracer(None)) is NullTracer


# -- watchdog -----------------------------------------------------------------

def test_heartbeat_lifecycle_drives_healthz():
    wd = tm_watchdog.install_watchdog(deadline_s=0.15, poll_s=0.05,
                                      log_fn=None)
    hb = telemetry.heartbeat("test.stage")
    assert wd.healthz()[0]
    _wait_for(lambda: not wd.healthz()[0], what="stale heartbeat")
    ok, stale = wd.healthz()
    assert "test.stage" in stale
    # the sweep counted the stall episode
    _wait_for(lambda: telemetry.get_registry().counter(
        tm_watchdog.WATCHDOG_STALLS,
        labels={"stage": "test.stage"}).value >= 1, what="stall counter")
    hb.beat()
    assert wd.healthz()[0]
    # a FINISHED stage is not a stall: expire again, then close
    _wait_for(lambda: not wd.healthz()[0], what="second expiry")
    hb.close()
    assert wd.healthz()[0]


def test_startup_grace_covers_the_first_compile_window():
    """Loop heartbeats register BEFORE their first jit compile; the
    startup grace keeps that window from reading as a stall, and drops
    at the first beat."""
    wd = tm_watchdog.install_watchdog(deadline_s=0.1, poll_s=0.05,
                                      log_fn=None)
    hb = telemetry.heartbeat("grace.stage", startup_grace_s=30.0)
    time.sleep(0.3)
    assert wd.healthz()[0]     # deadline passed, grace still covering
    hb.beat()                  # stage proved itself: normal deadline now
    _wait_for(lambda: not wd.healthz()[0], what="post-grace staleness")
    hb.close()


@pytest.fixture()
def wedged_worker(tmp_path):
    """A deliberately wedged EvacuationWorker under an armed watchdog
    (deadline 0.3 s) beside a telemetry server: yields ``(healthz url,
    release)``; ``release.set()`` un-wedges the drain."""
    import jax.numpy as jnp

    from dist_dqn_tpu.replay.staging import (EvacuationWorker,
                                             StreamedEvacuator)
    tm_watchdog.install_watchdog(forensics_dir=str(tmp_path),
                                 deadline_s=0.3, poll_s=0.05, log_fn=None)
    release = threading.Event()

    def wedged_on_slice(tree, lo, hi):
        release.wait(timeout=120)  # the injected hang: append never returns

    evac = StreamedEvacuator(num_slices=2, name="wedge")
    worker = EvacuationWorker(evac, wedged_on_slice, name="wedge")
    server = telemetry.start_server(0)
    try:
        worker.submit({"obs": jnp.zeros((8, 2, 4)),
                       "action": jnp.zeros((8, 2), jnp.int32)})
        yield f"http://127.0.0.1:{server.port}/healthz", release
    finally:
        release.set()
        worker.close()
        server.close()
    # a closed worker deregisters its stage: no post-run false stall
    assert "evac.wedge" not in tm_watchdog.get_watchdog().stages()


def _healthz(url):
    """(status, body) of /healthz; a 503 is an answer, not an error."""
    try:
        with urllib.request.urlopen(url, timeout=30) as response:
            return response.status, response.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# Waits below are sized for six busy workers on eight cores; each is for
# an EVENT (a bundle renamed into place, a status), never a duration.
LOADED_S = 60.0


def test_wedged_evacuation_worker_dumps_bundle(wedged_worker, tmp_path):
    """Acceptance (ISSUE 4): a deliberately wedged EvacuationWorker
    heartbeat produces a forensics bundle — stacks NAMING the wedged
    thread, non-empty flight tail, registry snapshot, manifest."""
    # bundles rename from "*.writing" only when complete — the poll
    # must not read a half-written one
    done = lambda: [b for b in os.listdir(tmp_path)  # noqa: E731
                    if b.endswith("watchdog_stall")]
    _wait_for(done, timeout_s=LOADED_S, what="forensics bundle")
    bundle = tmp_path / done()[0]
    reason = json.loads((bundle / "reason.json").read_text())
    assert "evac.wedge" in reason["detail"]["stale"]
    stacks = (bundle / "stacks.txt").read_text()
    assert "evac-wedge" in stacks          # the wedged thread BY NAME
    assert "wedged_on_slice" in stacks     # parked exactly here
    flight_dump = json.loads((bundle / "flight.json").read_text())
    names = [e["name"] for e in flight_dump["events"]]
    assert "evac.wedge.submit" in names    # non-empty, relevant tail
    registry_dump = json.loads((bundle / "registry.json").read_text())
    assert any(k.startswith("dqn_") for k in registry_dump)
    man = json.loads((bundle / "manifest.json").read_text())
    assert man["schema_version"] == tm_manifest.SCHEMA_VERSION


def test_wedged_evacuation_worker_flips_healthz_and_recovers(wedged_worker):
    """... and /healthz flips to 503 naming the stage, then back to 200
    once the drain finishes and beats resume."""
    url, release = wedged_worker
    _wait_for(lambda: _healthz(url)[0] == 503, timeout_s=LOADED_S,
              what="healthz 503")
    assert "evac.wedge" in json.loads(
        _healthz(url)[1])["stale_stages_age_s"]
    release.set()
    _wait_for(lambda: _healthz(url)[0] == 200, timeout_s=LOADED_S,
              what="healthz recovery")


def test_debug_routes_serve_stacks_flight_config():
    tm_flight.get_flight().record("chunk", "dbg_marker", x=1)
    tm_manifest.set_run_manifest({"schema_version": 1, "git_sha": "abc"})
    server = telemetry.start_server(0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        stacks = urllib.request.urlopen(base + "/debug/stacks").read() \
            .decode()
        assert "MainThread" in stacks and "telemetry-http" in stacks
        fl = json.loads(urllib.request.urlopen(base + "/debug/flight")
                        .read())
        assert any(e["name"] == "dbg_marker" for e in fl["events"])
        cfgd = json.loads(urllib.request.urlopen(base + "/debug/config")
                          .read())
        assert cfgd == {"schema_version": 1, "git_sha": "abc"}
        # healthz without a watchdog stays the static ok
        assert urllib.request.urlopen(base + "/healthz").read() == b"ok\n"
    finally:
        server.close()


# -- divergence sentinel ------------------------------------------------------

def test_sentinel_nonfinite_trips_once_and_dumps(tmp_path):
    reg = telemetry.Registry()
    s = tm_watchdog.DivergenceSentinel(forensics_dir=str(tmp_path),
                                       log_fn=None, registry=reg)
    assert s.observe(loss=0.5, grad_norm=1.0, step=1) is None
    assert s.observe(loss=float("nan"), step=2) == "loss_nonfinite"
    bundles = [b for b in os.listdir(tmp_path) if "divergence" in b]
    assert len(bundles) == 1
    assert s.observe(loss=float("nan"), step=3) == "loss_nonfinite"
    assert len([b for b in os.listdir(tmp_path)
                if "divergence" in b]) == 1  # latched: one bundle
    # ...and ONE counted trip per episode — a run that stays NaN must
    # not read as thousands of trips.
    assert reg.counter(tm_watchdog.DIVERGENCE_TRIPS,
                       labels={"signal": "loss_nonfinite"}).value == 1
    assert s.observe(grad_norm=float("inf"),
                     step=4) == "grad_norm_nonfinite"  # distinct signal
    assert len(os.listdir(tmp_path)) == 2


def test_sentinel_checksum_explosion(tmp_path):
    s = tm_watchdog.DivergenceSentinel(forensics_dir=str(tmp_path),
                                       explosion_factor=1e4, log_fn=None)
    assert s.observe(param_checksum=2.0) is None
    assert s.observe(param_checksum=3.0) is None
    assert s.observe(param_checksum=1e9) == "param_checksum_explosion"
    reason = json.loads(
        (tmp_path / os.listdir(tmp_path)[0] / "reason.json").read_text())
    assert reason["reason"] == "divergence_param_checksum_explosion"


def test_nan_loss_injection_produces_bundle(tmp_path):
    """Acceptance (ISSUE 4): an injected NaN loss (absurd learning rate
    -> params overflow -> non-finite TD loss) trips the sentinel wired
    into the fused train loop and produces a forensics bundle."""
    from dist_dqn_tpu.train import train
    tm_watchdog.install_sentinel(forensics_dir=str(tmp_path),
                                 log_fn=lambda s: None)
    cfg = _tiny_cartpole(learning_rate=1e30)
    train(cfg, total_env_steps=3_000, chunk_iters=50,
          log_fn=lambda s: None)
    bundles = [b for b in os.listdir(tmp_path) if "divergence" in b]
    assert bundles, "NaN/Inf loss never tripped the sentinel"
    bundle = tmp_path / bundles[0]
    reason = json.loads((bundle / "reason.json").read_text())
    assert reason["reason"].startswith("divergence_")
    registry_dump = json.loads((bundle / "registry.json").read_text())
    assert any(k.startswith(tm_watchdog.DIVERGENCE_TRIPS)
               for k in registry_dump)
    man = json.loads((bundle / "manifest.json").read_text())
    assert man["schema_version"] == tm_manifest.SCHEMA_VERSION
    # an ARMED sentinel's latched trip flips /healthz to 503 too
    server = telemetry.start_server(0)
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz")
        assert exc_info.value.code == 503
        assert json.loads(exc_info.value.read())["diverged"]
    finally:
        server.close()


# -- overhead pin -------------------------------------------------------------

def test_cartpole_smoke_rate_within_noise_of_recorder_off():
    """Acceptance (ISSUE 4): the CartPole CPU smoke's steps/sec with the
    flight recorder ON is within noise of the recorder-disabled run.
    CPU CI wall clocks are jittery, so the bound is a loose 2.5x either
    way — tight enough to catch a recorder accidentally put on the
    per-env-step (rather than per-chunk/per-span) path."""
    from dist_dqn_tpu.train import train
    cfg = _tiny_cartpole()

    def run_once():
        t0 = time.perf_counter()
        train(cfg, total_env_steps=3_000, chunk_iters=50,
              log_fn=lambda s: None)
        return time.perf_counter() - t0

    tm_flight.configure(enabled=True)
    run_once()                      # compile warmup (shared jit cache)
    t_on = run_once()
    tm_flight.configure(enabled=False)
    t_off = run_once()
    assert t_on < t_off * 2.5 and t_off < t_on * 2.5, \
        f"recorder on/off walls diverged: on={t_on:.3f}s off={t_off:.3f}s"


# -- manifest + evaluate CLI surface -----------------------------------------

def test_build_manifest_fields_and_config_hash():
    from dist_dqn_tpu.config import CONFIGS
    m = tm_manifest.build_manifest(CONFIGS["cartpole"], argv=["prog", "-x"])
    assert m["schema_version"] == tm_manifest.SCHEMA_VERSION
    assert m["versions"]["python"]
    assert m["versions"]["numpy"]          # imported in this process
    assert m["config_name"] == "cartpole"
    assert len(m["config_hash"]) == 16
    assert m["argv"] == ["prog", "-x"]
    assert m["git_sha"] is None or len(m["git_sha"]) == 40
    # same config -> same hash; different config -> different hash
    assert tm_manifest.build_manifest(
        CONFIGS["cartpole"])["config_hash"] == m["config_hash"]
    assert tm_manifest.build_manifest(
        CONFIGS["atari"])["config_hash"] != m["config_hash"]
    tm_manifest.set_run_manifest(m)
    assert tm_manifest.get_run_manifest()["config_name"] == "cartpole"


def test_evaluate_cli_serves_telemetry(tmp_path):
    """ISSUE 4 satellite: evaluate.py grew --telemetry-port /
    --telemetry-snapshot — an eval run announces its scrape port and
    dumps an exit snapshot like a train run. The telemetry surface must
    hold even when the evaluation itself fails (e.g. the PRE-EXISTING
    orbax partial_restore incompatibility test_checkpoint.py carries on
    this box) — the exit snapshot is precisely for post-mortems."""
    from dist_dqn_tpu.train import train
    ckpt_dir = tmp_path / "ckpt"
    cfg = _tiny_cartpole()
    train(cfg, total_env_steps=300, chunk_iters=50,
          checkpoint_dir=str(ckpt_dir), log_fn=lambda s: None)
    snap = tmp_path / "eval_snapshot.json"
    proc = subprocess.run(
        [sys.executable, "-m", "dist_dqn_tpu.evaluate",
         "--config", "cartpole", "--checkpoint-dir", str(ckpt_dir),
         "--episodes", "1", "--platform", "cpu",
         "--telemetry-port", "0", "--telemetry-snapshot", str(snap)],
        capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    assert any("telemetry_port" in r for r in rows), \
        proc.stderr or proc.stdout
    assert snap.exists(), proc.stderr or proc.stdout
    json.loads(snap.read_text())  # valid snapshot JSON, even on failure
    if proc.returncode == 0:  # checkpoint restore healthy on this box
        assert any("eval_return" in r for r in rows)
