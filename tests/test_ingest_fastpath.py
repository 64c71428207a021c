"""Ape-X service ingest fast path (ISSUE 2): fused act+bootstrap
dispatch, batched priority write-backs, double-buffered H2D staging.

The load-bearing assertions:

* the DISPATCH BUDGET regression test drives the production ingest
  machinery (the fan-in stress pattern: synthesized wire-protocol
  records straight into the shm ring) and pins the fused path to ONE
  ingest device call per pass — and the split reference to >= 2x that —
  so the round-trip reduction the feeder bench measures cannot silently
  regress;
* the DOUBLE-BUFFER correctness test runs the host-replay loop with
  staging on and off at the same seed and requires bit-identical loss
  histories — batch g+1 staged while g trains must change WHEN work
  happens, never WHAT is computed;
* the staging unit tests pin the copy semantics (mutating the source
  after stage() cannot corrupt the staged batch — the pinned-buffer
  guarantee) and the depth/reuse contract;
* the batched write-back test pins one concatenated update_priorities
  call == the per-step sequence, including last-write-wins for slots
  sampled by several batched steps.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from dist_dqn_tpu.actors.service import (ApexLearnerService,
                                         ApexRuntimeConfig, _PRIO_CHUNK,
                                         _PRIO_MAX_ROWS)
from dist_dqn_tpu.actors.transport import ShmRing, encode_arrays
from dist_dqn_tpu.config import CONFIGS

OBS_DIM = 4  # CartPole-v1 observation (the rt.host_env probe's shape)


def _ingest_cfg(n_step=3):
    base = CONFIGS["cartpole"]
    return dataclasses.replace(
        base,
        network=dataclasses.replace(base.network, torso="mlp",
                                    mlp_features=(32,), hidden=0,
                                    dueling=False,
                                    compute_dtype="float32"),
        # min_fill above anything the test inserts: the budget test
        # isolates INGEST dispatches (train calls are counted separately
        # and would only add noise here).
        replay=dataclasses.replace(base.replay, capacity=65_536,
                                   prioritized=True, min_fill=50_000),
        learner=dataclasses.replace(base.learner, batch_size=32,
                                    n_step=n_step),
    )


class _Stream:
    """Wire-protocol record stream (the fan-in stress pattern)."""

    def __init__(self, actor_ids, lanes, seed=0):
        self.lanes = lanes
        self.rng = np.random.default_rng(seed)
        self.t = {a: 0 for a in actor_ids}

    def _obs(self):
        return self.rng.normal(size=(self.lanes, OBS_DIM)) \
            .astype(np.float32)

    def hello(self, a):
        return encode_arrays({"obs": self._obs()},
                             {"kind": "hello", "actor": a, "t": self.t[a]})

    def step(self, a):
        self.t[a] += 1
        done = self.rng.random(self.lanes) < 0.02
        return encode_arrays(
            {"obs": self._obs(),
             "reward": self.rng.normal(size=self.lanes).astype(np.float32),
             "terminated": done.astype(np.uint8),
             "truncated": np.zeros(self.lanes, np.uint8),
             "next_obs": self._obs()},
            {"kind": "step", "actor": a, "t": self.t[a]})


def _ingest_calls(service) -> int:
    dc = service.device_calls
    return (dc.get("act", 0) + dc.get("fused_act_bootstrap", 0)
            + dc.get("bootstrap", 0))


def _drive_rounds(service, stream, ring, rounds):
    """Push one step record per actor, then run one service pass (the
    production drain -> act flush -> bootstrap flush order). Returns the
    ingest device calls observed per round."""
    ids = sorted(stream.t)
    per_round = []
    for _ in range(rounds):
        for a in ids:
            assert ring.push(stream.step(a))
        before = _ingest_calls(service)
        service._drain_transports()
        service._flush_act_queue()
        service._flush_pending()
        per_round.append(_ingest_calls(service) - before)
    return per_round


def _build_service(fused: bool, n_actors=32, lanes=16):
    # transport="legacy": the fused act+bootstrap dispatch is the
    # LEGACY experience path's optimization — on the zerocopy default
    # (ISSUE 9) actors ship their |TD| planes in-frame and the ingest
    # pass dispatches NO bootstrap at all (its stricter 1.0-calls/pass
    # budget is pinned by tests/test_ingest.py); this file pins the
    # fused-vs-split budget on the transport that owns it.
    rt = ApexRuntimeConfig(num_actors=n_actors, envs_per_actor=lanes,
                           total_env_steps=10 ** 9, ring_mb=8,
                           stall_warn_s=0.0, log_every_s=10 ** 9,
                           fused_ingest=fused, transport="legacy")
    service = ApexLearnerService(_ingest_cfg(), rt,
                                 log_fn=lambda *a: None)
    ring = ShmRing(f"req_{service.run_id}")
    stream = _Stream(range(n_actors), lanes, seed=7)
    for a in range(n_actors):
        assert ring.push(stream.hello(a))
    service._drain_transports()
    service._flush_act_queue()
    return service, stream, ring


def test_fused_ingest_dispatch_budget():
    """THE regression pin: with 32 actors x 16 lanes every warm round
    assembles 512 transitions (> _PRIO_CHUNK, < _PRIO_MAX_ROWS), and the
    fused path must serve act AND bootstrap in EXACTLY ONE device call
    per ingest pass; the split reference pays >= 2x that on the same
    stream. A third dispatch creeping into the fast path fails here."""
    assert 32 * 16 > _PRIO_CHUNK and 32 * 16 < _PRIO_MAX_ROWS
    service, stream, ring = _build_service(fused=True)
    try:
        # Warmup: n_step assembly windows fill; acts still dispatch.
        _drive_rounds(service, stream, ring, 3)
        fused_rounds = _drive_rounds(service, stream, ring, 6)
        assert fused_rounds == [1] * 6, fused_rounds
        # Forced flush drains sub-chunk remainders without extra calls
        # in steady state (everything already rode the fused dispatch).
        service._flush_pending(force=True)
        assert len(service.replay) > 0
        fused_total = _ingest_calls(service)
        env_steps_fused = service.env_steps
    finally:
        service.shutdown()

    service, stream, ring = _build_service(fused=False)
    try:
        _drive_rounds(service, stream, ring, 3)
        split_rounds = _drive_rounds(service, stream, ring, 6)
        # Same stream shape: one act + >=ceil(512/256)=2 bootstrap
        # chunks (episode boundaries emit a few extra transitions, so
        # some rounds cross one more 256 boundary).
        assert all(r >= 3 for r in split_rounds), split_rounds
        service._flush_pending(force=True)
        assert service.env_steps == env_steps_fused
        split_total = _ingest_calls(service)
    finally:
        service.shutdown()
    assert split_total >= 2 * fused_total, (split_total, fused_total)


def test_fused_ingest_same_transitions_and_priorities_as_split():
    """Fusing the dispatch must not change WHAT is inserted: identical
    record streams through the fused and split services end with the
    same replay size, the same stored transitions, and the same
    bootstrap priority mass (same params at init => same |TD|)."""
    results = {}
    for fused in (True, False):
        service, stream, ring = _build_service(fused=fused, n_actors=8,
                                               lanes=16)
        try:
            _drive_rounds(service, stream, ring, 8)
            service._flush_pending(force=True)
            replay = service.replay
            n = len(replay)
            idx = np.arange(n, dtype=np.int64)
            results[fused] = {
                "n": n,
                "obs": replay._data["obs"][:n].copy(),
                "action": replay._data["action"][:n].copy(),
                "mass": replay.tree.get(idx).copy(),
            }
        finally:
            service.shutdown()
    a, b = results[True], results[False]
    assert a["n"] == b["n"] > 0
    np.testing.assert_array_equal(a["obs"], b["obs"])
    np.testing.assert_array_equal(a["action"], b["action"])
    np.testing.assert_allclose(a["mass"], b["mass"], rtol=1e-5)


def test_host_replay_double_buffer_matches_serial():
    """Double-buffer correctness (ISSUE 2 satellite): batch g+1 staged
    while g trains must yield IDENTICAL learner results to the serial
    path — same seed, same sample order, bit-identical loss history."""
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(32,), hidden=0,
                                    compute_dtype="float32"),
        replay=dataclasses.replace(cfg.replay, capacity=4096, min_fill=64,
                                   prioritized=False),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
    )
    # prefetch=False on both legs: this pin isolates the legacy
    # main-thread double-buffer knob (the prefetched path owns its own
    # stager and is pinned by test_host_replay_pipeline.py).
    out_db = run_host_replay(cfg, total_env_steps=3200, chunk_iters=50,
                             log_fn=lambda s: None, double_buffer=True,
                             prefetch=False)
    out_serial = run_host_replay(cfg, total_env_steps=3200, chunk_iters=50,
                                 log_fn=lambda s: None,
                                 double_buffer=False, prefetch=False)
    assert out_db["double_buffer"] and not out_serial["double_buffer"]
    assert out_db["grad_steps"] == out_serial["grad_steps"] > 0
    assert out_db["h2d_staged_bytes"] > 0
    losses_db = [r["loss"] for r in out_db["history"] if "loss" in r]
    losses_serial = [r["loss"] for r in out_serial["history"]
                     if "loss" in r]
    assert losses_db and losses_db == losses_serial


class TestDoubleBufferedStager:
    def _stager(self, depth=2):
        from dist_dqn_tpu.replay.staging import DoubleBufferedStager
        return DoubleBufferedStager(depth=depth, name="test")

    def test_copy_semantics_pin_pinned_buffers(self):
        """Mutating the source AFTER stage() must not corrupt the staged
        batch: the stager copies into its own persistent buffers."""
        s = self._stager()
        x = {"a": np.arange(6, dtype=np.float32)}
        want = x["a"].copy()
        s.stage(x)
        x["a"][:] = -1.0
        batch, _ = s.pop()
        np.testing.assert_array_equal(np.asarray(batch["a"]), want)

    def test_fifo_order_and_aux(self):
        s = self._stager()
        s.stage({"a": np.full(4, 1.0, np.float32)}, aux="first")
        s.stage({"a": np.full(4, 2.0, np.float32)}, aux="second")
        b1, aux1 = s.pop()
        b2, aux2 = s.pop()
        assert aux1 == "first" and aux2 == "second"
        assert float(np.asarray(b1["a"])[0]) == 1.0
        assert float(np.asarray(b2["a"])[0]) == 2.0

    def test_depth_bound_and_buffer_reuse(self):
        s = self._stager(depth=2)
        for i in range(2):
            s.stage({"a": np.full(4, float(i), np.float32)})
        with pytest.raises(RuntimeError, match="depth"):
            s.stage({"a": np.zeros(4, np.float32)})
        # Cycle many batches through: the buffer pool must not grow.
        for i in range(10):
            s.pop()
            s.stage({"a": np.full(4, float(i + 2), np.float32)})
        assert len(s._bufs) == 2 and all(b is not None for b in s._bufs)
        assert s.staged_total == 12

    def test_structure_and_shape_guards(self):
        s = self._stager()
        s.stage({"a": np.zeros(4, np.float32)})
        s.pop()
        with pytest.raises(ValueError, match="structure"):
            s.stage({"b": np.zeros(4, np.float32)})
        with pytest.raises(ValueError, match="does not match"):
            s.stage({"a": np.zeros(8, np.float32)})
        with pytest.raises(RuntimeError, match="empty"):
            s.pop()


def test_batched_priority_writeback_matches_per_step():
    """One concatenated update_priorities call == the per-step sequence:
    same final leaf mass, last-write-wins for slots several batched
    steps sampled, expected_gen still dropping overwritten slots."""
    from dist_dqn_tpu.replay.host import PrioritizedHostReplay

    def fresh():
        r = PrioritizedHostReplay(64, alpha=0.6, seed=0, native=False)
        r.add({"x": np.arange(32, dtype=np.float32)},
              priorities=np.ones(32))
        return r

    steps = [
        (np.array([0, 3, 7]), np.array([0.5, 1.5, 2.5])),
        (np.array([3, 9, 1]), np.array([4.0, 0.25, 0.75])),  # 3 again
        (np.array([7, 0, 5]), np.array([0.1, 3.0, 1.0])),    # 7, 0 again
    ]
    serial, batched = fresh(), fresh()
    gens = [serial.generation(idx) for idx, _ in steps]
    for (idx, p), gen in zip(steps, gens):
        serial.update_priorities(idx, p, expected_gen=gen)
    batched.update_priorities(
        np.concatenate([idx for idx, _ in steps]),
        np.concatenate([p for _, p in steps]),
        expected_gen=np.concatenate(gens))
    all_idx = np.arange(32, dtype=np.int64)
    np.testing.assert_allclose(batched.tree.get(all_idx),
                               serial.tree.get(all_idx), rtol=1e-12)

    # Overwritten slots: a generation bump between sample and flush must
    # drop exactly those rows in the batched call too.
    stale = fresh()
    gen = stale.generation(np.array([2, 4]))
    before = stale.tree.get(np.array([2], np.int64)).copy()
    stale._slot_gen[2] += 1  # slot 2 overwritten while in flight
    stale.update_priorities(np.array([2, 4]), np.array([9.0, 9.0]),
                            expected_gen=gen)
    after = stale.tree.get(np.array([2], np.int64))
    np.testing.assert_allclose(after, before)  # dropped (stale gen)
    assert stale.tree.get(np.array([4], np.int64))[0] > before[0]


def test_service_flush_prio_writebacks_batches():
    """The service-side buffer honors prio_writeback_batch: nothing is
    applied below the threshold, one forced flush applies everything."""
    service, stream, ring = _build_service(fused=True, n_actors=4,
                                           lanes=8)
    try:
        service.rt.prio_writeback_batch = 4
        idx = np.array([0, 1], np.int64)
        # Seed the shard so update_priorities has live slots.
        service.replay.add({"obs": np.zeros((4, OBS_DIM), np.float32),
                            "action": np.zeros(4, np.int32),
                            "reward": np.zeros(4, np.float32),
                            "discount": np.ones(4, np.float32),
                            "next_obs": np.zeros((4, OBS_DIM),
                                                 np.float32)},
                           priorities=np.ones(4))
        gen = service.replay.generation(idx)
        mass_before = service.replay.tree.get(idx).copy()
        service._prio_pending.append((idx, np.array([5.0, 6.0]), gen))
        service._flush_prio_writebacks()          # 1 < 4: buffered
        np.testing.assert_allclose(service.replay.tree.get(idx),
                                   mass_before)
        service._flush_prio_writebacks(force=True)
        assert (service.replay.tree.get(idx) > mass_before).all()
        assert service._prio_pending == []
    finally:
        service.shutdown()


def test_feeder_flags_mutually_exclusive():
    """ADVICE r5: the synthetic stream must honor the real actor
    contract — a terminated step is never also truncated."""
    from dist_dqn_tpu.actors.feeder import (FeederSpecEnv, _build_pool,
                                            POOL_RECORDS)
    from dist_dqn_tpu.actors.transport import decode_arrays

    rng = np.random.default_rng(0)
    _, steps = _build_pool(rng, 0, 64, (4,), np.dtype(np.float32))
    assert len(steps) == POOL_RECORDS
    for payload in steps:
        arrays, _ = decode_arrays(payload)
        both = arrays["terminated"].astype(bool) \
            & arrays["truncated"].astype(bool)
        assert not both.any()

    env = FeederSpecEnv("feeder:vector", seed=1)
    env._rng = np.random.default_rng(2)
    # Force the flag branch often enough to be meaningful.
    import dist_dqn_tpu.actors.feeder as feeder_mod
    old_t, old_tr = feeder_mod.P_TERMINATED, feeder_mod.P_TRUNCATED
    feeder_mod.P_TERMINATED, feeder_mod.P_TRUNCATED = 0.5, 0.9
    try:
        for _ in range(500):
            _, _, te, tr, _ = env.step(0)
            assert not (te and tr)
    finally:
        feeder_mod.P_TERMINATED, feeder_mod.P_TRUNCATED = old_t, old_tr


def test_host_replay_rejects_recurrent_and_logs_active_sampler():
    """ISSUE 5 satellite: the false "prioritized not supported" notice
    is gone — a prioritized config RUNS prioritized and the loop logs
    which sampler is active (with alpha/beta and the write-back batch);
    a uniform config logs uniform."""
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = CONFIGS["cartpole"]
    cfg_r = dataclasses.replace(
        cfg, network=dataclasses.replace(cfg.network, lstm_size=8))
    with pytest.raises(ValueError, match="lstm"):
        run_host_replay(cfg_r, total_env_steps=10, log_fn=lambda s: None)

    notices = []
    cfg_p = dataclasses.replace(
        cfg,
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=32,
                                   prioritized=True),
        learner=dataclasses.replace(cfg.learner, batch_size=8))
    out = run_host_replay(cfg_p, total_env_steps=400, chunk_iters=20,
                          log_fn=notices.append)
    assert not any("not supported" in str(n) for n in notices)
    sampler_lines = [str(n) for n in notices
                     if "sampler: prioritized" in str(n)]
    assert sampler_lines, notices[:3]
    assert "alpha=0.6" in sampler_lines[0]
    assert "beta=0.4" in sampler_lines[0]
    assert "prio_writeback_batch=8" in sampler_lines[0]
    assert out["prioritized"] is True

    uniform_notices = []
    cfg_u = dataclasses.replace(
        cfg_p, replay=dataclasses.replace(cfg_p.replay,
                                          prioritized=False))
    run_host_replay(cfg_u, total_env_steps=400, chunk_iters=20,
                    log_fn=uniform_notices.append)
    assert any("sampler: uniform" in str(n) for n in uniform_notices)


def test_host_replay_validates_chunk_iters_before_compile():
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg, actor=dataclasses.replace(cfg.actor, num_envs=8),
        replay=dataclasses.replace(cfg.replay, capacity=1024))
    with pytest.raises(ValueError) as e:
        run_host_replay(cfg, total_env_steps=100, chunk_iters=5000,
                        log_fn=lambda s: None)
    msg = str(e.value)
    assert "--chunk-iters" in msg and "replay.capacity" in msg
