"""Pipelined host-replay runtime (ISSUE 3): overlap must change WHEN
work happens, never WHAT is computed.

The load-bearing assertions:

* the PIPELINE EQUIVALENCE pin runs the hybrid loop with the three-stage
  pipeline on and off at the same seed and requires bit-identical loss
  histories, grad counts and a bit-identical whole-params checksum —
  the mirror of test_ingest_fastpath.py's double-buffer pin — plus D2H
  byte conservation (streaming the evacuation moves the same bytes);
  what the pipeline overlaps is a second test, stated in counts and in
  the order of its flight events, never in a CPU wall;
* the GENERATION FENCE test hammers the ring with a background slice
  writer while sampling concurrently and requires every sampled
  transition to be internally consistent — a sampler can never observe
  a half-appended slice;
* the EVACUATION WORKER tests pin the failure contract: an exception in
  the worker propagates at the fence (and poisons later submits), and
  the thread always joins — no hang, no silent half-evacuated chunk;
* the BENCH A/B smoke runs benchmarks/host_replay_bench.py --ab on CPU
  at a tiny size so the serial-vs-pipelined harness cannot bit-rot
  (the trace_ab row must report conserved bytes and matching numerics).
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.replay.host_ring import HostTimeRing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def test_pipeline_matches_serial_numerics():
    """THE equivalence pin: the pipelined path (streamed sub-chunk
    evacuation, background worker, collect-ahead dispatch) must yield
    IDENTICAL learner results to the --no-pipeline serial reference —
    same seed, bit-identical loss history, bit-identical params — while
    moving the same D2H bytes. No statement about time lives here."""
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = _tiny_cfg()
    out_p = run_host_replay(cfg, total_env_steps=3200, chunk_iters=50,
                            log_fn=lambda s: None, pipeline=True,
                            evac_slices=3)
    out_s = run_host_replay(cfg, total_env_steps=3200, chunk_iters=50,
                            log_fn=lambda s: None, pipeline=False)
    assert out_p["pipeline"] and not out_s["pipeline"]
    assert out_p["grad_steps"] == out_s["grad_steps"] > 0
    losses_p = [r["loss"] for r in out_p["history"] if "loss" in r]
    losses_s = [r["loss"] for r in out_s["history"] if "loss" in r]
    assert losses_p and losses_p == losses_s
    assert out_p["param_checksum"] == out_s["param_checksum"]
    # D2H conservation: slicing the stream must not change its volume.
    assert out_p["d2h_bytes_total"] == out_s["d2h_bytes_total"] > 0
    assert sum(r["d2h_bytes"] for r in out_p["history"]) == \
        out_p["d2h_bytes_total"]


def test_pipeline_overlap_is_stated_in_counts():
    """What the pipeline takes off the critical path, in what it counts
    and in the order its own thread records — never in a CPU wall: every
    chunk's evacuation goes to the worker in ``evac_slices`` slices; every
    chunk is fenced once; and chunk g+1's evacuation is submitted BEFORE
    chunk g's train event is fenced, so it is in flight while those train
    steps run. The serial reference streams nothing and pins overlap 0."""
    from dist_dqn_tpu.telemetry import flight as tm_flight

    chunks = 3200 // (50 * 8)

    def run(pipeline):
        from dist_dqn_tpu.host_replay_loop import run_host_replay

        tm_flight._reset_for_tests()
        out = run_host_replay(_tiny_cfg(), total_env_steps=3200,
                              chunk_iters=50, log_fn=lambda s: None,
                              pipeline=pipeline, evac_slices=3)
        return out, tm_flight.get_flight().tail()

    try:
        out_p, events_p = run(True)
        out_s, events_s = run(False)
    finally:
        tm_flight._reset_for_tests()
    for events in (events_p, events_s):    # fences waited = chunks
        assert [e["chunk"] for e in events
                if e["name"] == "host_replay.chunk"] == list(range(chunks))
    drained = [e for e in events_p if e["name"] == "evac.host_replay.drained"]
    assert [e["slices"] for e in drained] == [3] * chunks
    assert out_p["evac_slices"] == 3 and out_s["evac_slices"] == 0
    assert not [e for e in events_s if e["name"].startswith("evac.")]
    # the k-th submit is chunk k's (the prologue submits chunk 0)
    main = [e["name"] if e["name"] != "host_replay.train_event"
            else ("train", e["chunk"]) for e in events_p
            if e["name"] in ("evac.host_replay.submit",
                             "host_replay.train_event")]
    submits = [i for i, name in enumerate(main)
               if name == "evac.host_replay.submit"]
    assert len(submits) == chunks
    in_flight_over_a_train_event = [
        g for g in range(1, chunks)
        if main.index(("train", g - 1)) == submits[g] + 1]
    assert in_flight_over_a_train_event == list(range(1, chunks))
    # the overlap figure: a share by construction, exactly 0 when serial
    assert out_s["evac_overlap_frac_mean"] == 0.0
    for row in out_p["history"] + out_s["history"]:
        assert 0.0 <= row["evac_overlap_frac"] <= 1.0


def test_pipeline_rows_account_stats_and_loop_rate():
    """ISSUE 3 satellites: the fused episode-stat fetch is one timed
    row field (not an unattributed sync), and rows carry the whole-loop
    rate that reconciles with the end-of-run summary rate."""
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    out = run_host_replay(_tiny_cfg(), total_env_steps=1600,
                          chunk_iters=50, log_fn=lambda s: None)
    assert out["history"]
    for row in out["history"]:
        assert row["chunk_stats_fetch_s"] >= 0.0
        assert row["env_steps_per_sec_loop"] > 0.0
    # The last row's loop rate and the summary rate measure the same
    # quantity up to the final logging call — same order of magnitude,
    # unlike the per-chunk rate which excludes stats/log time entirely.
    last = out["history"][-1]["env_steps_per_sec_loop"]
    assert out["env_steps_per_sec"] <= last * 1.05


class TestGenerationFence:
    def test_sample_never_sees_half_appended_slice(self):
        """Background slice appends vs concurrent sampling: every
        transition drawn must be internally consistent (obs == action
        == reward == the writing slice's sequence number). A torn
        append — data without its size/pos publication, or a sampler
        reading mid-write — fails the cross-field equality."""
        ring = HostTimeRing(num_slots=256, num_envs=4, obs_shape=(3,),
                            obs_dtype=np.float32)
        n_slices, C = 400, 16
        rng = np.random.default_rng(0)
        stop = threading.Event()
        errors = []

        def writer():
            for s in range(1, n_slices + 1):
                v = np.float32(s)
                ring.add_chunk(
                    np.full((C, 4, 3), v, np.float32),
                    np.full((C, 4), s, np.int32),
                    np.full((C, 4), v, np.float32),
                    np.zeros((C, 4), bool), np.zeros((C, 4), bool))
            stop.set()

        def sampler():
            while not stop.is_set():
                if not ring.can_sample(1):
                    continue
                hb = ring.sample(rng, 64, n_step=1, gamma=0.99).batch
                a = hb.action.astype(np.float32)
                if not (np.all(hb.obs == a[:, None])
                        and np.all(hb.reward == a)):
                    errors.append((hb.obs[:2], hb.action[:2],
                                   hb.reward[:2]))
                    return

        t_w = threading.Thread(target=writer)
        t_s = threading.Thread(target=sampler)
        t_s.start()
        t_w.start()
        t_w.join(timeout=60)
        t_s.join(timeout=60)
        assert not t_w.is_alive() and not t_s.is_alive()
        assert not errors, f"torn sample observed: {errors[0]}"
        assert ring.generation == n_slices

    def test_wait_generation(self):
        ring = HostTimeRing(num_slots=16, num_envs=2, obs_shape=(2,),
                            obs_dtype=np.float32)
        assert ring.wait_generation(0)
        assert not ring.wait_generation(1, timeout=0.05)

        def later():
            time.sleep(0.05)
            ring.add_chunk(np.zeros((2, 2, 2), np.float32),
                           np.zeros((2, 2), np.int32),
                           np.zeros((2, 2), np.float32),
                           np.zeros((2, 2), bool), np.zeros((2, 2), bool))

        t = threading.Thread(target=later)
        t.start()
        assert ring.wait_generation(1, timeout=10)
        t.join()


class TestStreamedEvacuator:
    def _records(self, C=12, B=3):
        import jax.numpy as jnp
        return {
            "obs": jnp.arange(C * B * 2, dtype=jnp.float32
                              ).reshape(C, B, 2),
            "action": jnp.arange(C * B, dtype=jnp.int32).reshape(C, B),
        }

    def test_slices_cover_chunk_in_order(self):
        """The streamed slices must tile [0, C) exactly once, in time
        order, and reassemble to the monolithic fetch bit-for-bit."""
        import jax

        from dist_dqn_tpu.replay.staging import StreamedEvacuator

        ev = StreamedEvacuator(num_slices=5, name="test_evac")
        records = self._records()
        want = jax.device_get(records)
        got, spans = [], []
        stats = ev.drain(ev.start(records),
                         lambda tree, lo, hi: (
                             got.append({k: v.copy()
                                         for k, v in tree.items()}),
                             spans.append((lo, hi))))
        assert spans == [(0, 3), (3, 6), (6, 8), (8, 10), (10, 12)]
        re = {k: np.concatenate([s[k] for s in got]) for k in want}
        np.testing.assert_array_equal(re["obs"], want["obs"])
        np.testing.assert_array_equal(re["action"], want["action"])
        assert stats["slices"] == 5
        assert stats["bytes"] == sum(v.nbytes for v in want.values())

    def test_repeated_chunks_accumulate_counters(self):
        from dist_dqn_tpu.replay.staging import StreamedEvacuator

        ev = StreamedEvacuator(num_slices=2, name="test_evac")
        for _ in range(3):
            ev.drain(ev.start(self._records()), lambda tree, lo, hi: None)
        assert ev.slices_total == 6
        # One split program compiled for the repeated (treedef, C) shape.
        assert len(ev._split_cache) == 1

    def test_more_slices_than_iters_clamps(self):
        from dist_dqn_tpu.replay.staging import StreamedEvacuator

        ev = StreamedEvacuator(num_slices=64, name="test_evac")
        spans = []
        stats = ev.drain(ev.start(self._records(C=4)),
                         lambda tree, lo, hi: spans.append((lo, hi)))
        assert spans == [(0, 1), (1, 2), (2, 3), (3, 4)]
        assert stats["slices"] == 4

    def test_rejects_bad_slice_count(self):
        from dist_dqn_tpu.replay.staging import StreamedEvacuator

        with pytest.raises(ValueError, match="num_slices"):
            StreamedEvacuator(num_slices=0)


class TestEvacuationWorker:
    def _worker(self, on_slice, num_slices=3):
        from dist_dqn_tpu.replay.staging import (EvacuationWorker,
                                                 StreamedEvacuator)
        ev = StreamedEvacuator(num_slices=num_slices, name="test_worker")
        return EvacuationWorker(ev, on_slice, name="test_worker")

    def _records(self):
        import jax.numpy as jnp
        return {"x": jnp.ones((9, 2, 4), jnp.float32)}

    def test_handle_completes_and_clean_shutdown(self):
        done = []
        w = self._worker(lambda tree, lo, hi: done.append((lo, hi)))
        try:
            h = w.submit(self._records())
            assert h.wait(timeout=30)
            assert h.done and h.stats["slices"] == 3
            assert [lo for lo, _ in done] == sorted(lo for lo, _ in done)
        finally:
            w.close()
        assert not w._thread.is_alive()

    def test_worker_exception_propagates_no_hang(self):
        """ISSUE 3 satellite: an exception in the evacuation worker
        must re-raise at the fence AND poison later submits — never a
        hung thread or a silently half-evacuated chunk."""

        def boom(tree, lo, hi):
            raise RuntimeError("ring append exploded")

        w = self._worker(boom)
        try:
            h = w.submit(self._records())
            with pytest.raises(RuntimeError, match="exploded"):
                h.wait(timeout=30)
            assert w.failed is not None
            with pytest.raises(RuntimeError, match="worker died"):
                w.submit(self._records())
        finally:
            w.close()
        assert not w._thread.is_alive()

    def test_queued_jobs_fail_after_worker_death(self):
        """Jobs already queued behind the failing one must fail too —
        their fences would otherwise hang the training loop forever."""
        gate = threading.Event()

        def slow_boom(tree, lo, hi):
            gate.wait(timeout=30)
            raise RuntimeError("late failure")

        w = self._worker(slow_boom, num_slices=1)
        try:
            h1 = w.submit(self._records())
            h2 = w.submit(self._records())
            gate.set()
            with pytest.raises(RuntimeError, match="late failure"):
                h1.wait(timeout=30)
            with pytest.raises(RuntimeError, match="late failure"):
                h2.wait(timeout=30)
        finally:
            w.close()
        assert not w._thread.is_alive()

    def test_loop_surfaces_worker_failure(self):
        """End to end: a ring append that blows up mid-run must abort
        run_host_replay with the worker's exception (after closing the
        worker), not wedge the fence."""
        from dist_dqn_tpu import host_replay_loop as hrl

        class _BoomRing(HostTimeRing):
            def add_chunk(self, *a, **k):
                if self.generation >= 2:
                    raise RuntimeError("DRAM append failed")
                super().add_chunk(*a, **k)

        orig = hrl.HostTimeRing
        hrl.HostTimeRing = _BoomRing
        try:
            with pytest.raises(RuntimeError, match="DRAM append failed"):
                hrl.run_host_replay(_tiny_cfg(), total_env_steps=3200,
                                    chunk_iters=50, log_fn=lambda s: None,
                                    pipeline=True, evac_slices=2)
        finally:
            hrl.HostTimeRing = orig


def test_prefetch_matches_serial_numerics():
    """THE ISSUE 5 equivalence pin: the background SamplePrefetcher
    (sample -> gather -> stage off the main thread) must yield
    IDENTICAL learner results to the --no-prefetch serial reference in
    uniform mode — per-batch-index RNG streams make batch content a
    pure function of (k, ring window), so thread timing changes WHEN a
    batch is drawn, never what is trained on."""
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = _tiny_cfg()
    out_p = run_host_replay(cfg, total_env_steps=3200, chunk_iters=50,
                            log_fn=lambda s: None, prefetch=True)
    out_s = run_host_replay(cfg, total_env_steps=3200, chunk_iters=50,
                            log_fn=lambda s: None, prefetch=False)
    out_ss = run_host_replay(cfg, total_env_steps=3200, chunk_iters=50,
                             log_fn=lambda s: None, prefetch=False,
                             double_buffer=False)
    assert out_p["prefetch"] and not out_s["prefetch"]
    assert out_p["grad_steps"] == out_s["grad_steps"] > 0
    losses_p = [r["loss"] for r in out_p["history"] if "loss" in r]
    losses_s = [r["loss"] for r in out_s["history"] if "loss" in r]
    assert losses_p and losses_p == losses_s
    assert out_p["param_checksum"] == out_s["param_checksum"]
    # ...and the double-buffered reference equals the fully serial one.
    assert out_s["param_checksum"] == out_ss["param_checksum"]
    # No batch went stale (appends are gated on the event's samples),
    # and the overlap accounting measured real work on both sides.
    assert out_p["stale_batches"] == 0
    assert out_p["sample_s_total"] > 0.0
    assert out_s["sample_s_total"] > 0.0
    assert out_s["prefetch_wait_s_total"] == 0.0
    for row in out_p["history"]:
        assert row["prefetch_wait_s"] >= 0.0
        assert row["stale_batches"] == 0


def test_host_replay_per_end_to_end():
    """PER host-replay trains end to end under the full pipeline:
    write-backs flow (batched, generation-guarded), IS weights are
    sane, the summary says which sampler ran."""
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = _tiny_cfg()
    cfg = dataclasses.replace(
        cfg, replay=dataclasses.replace(cfg.replay, prioritized=True))
    out = run_host_replay(cfg, total_env_steps=3200, chunk_iters=50,
                          log_fn=lambda s: None, prefetch=True,
                          prio_writeback_batch=4)
    assert out["prioritized"] and out["prefetch"]
    assert out["grad_steps"] > 0
    assert out["prio_writeback_flushes"] > 0
    assert out["prio_writeback_rows"] > 0
    # Every row carries a batch worth of write-backs minus the
    # generation-guard drops.
    assert out["prio_writeback_rows"] + out["prio_writeback_dropped"] \
        == out["grad_steps"] * cfg.learner.batch_size
    assert 0.0 < out["is_weight_min"] <= out["is_weight_mean"] <= 1.0
    assert np.isfinite(out["param_checksum"])


class TestRingPrioritySampler:
    def _ring(self, slots=64, lanes=2, steps=48):
        ring = HostTimeRing(slots, lanes, (3,), np.float32)
        for lo in range(0, steps, 12):
            C = min(12, steps - lo)
            ring.add_chunk(np.ones((C, lanes, 3), np.float32),
                           np.zeros((C, lanes), np.int32),
                           np.zeros((C, lanes), np.float32),
                           np.zeros((C, lanes), bool),
                           np.zeros((C, lanes), bool))
        return ring

    def test_oversampling_ratio_and_is_compensation(self):
        """ISSUE 5 satellite: a slot with 10x the priority of its peers
        is drawn ~10x as often (alpha=1), and its IS weight compensates
        by the inverse ratio (beta=1)."""
        from dist_dqn_tpu.replay.host_ring import RingPrioritySampler

        ring = self._ring()
        s = RingPrioritySampler(ring, n_step=1, alpha=1.0, beta=1.0,
                                eps=0.0, name="test_per")
        # All slots seeded at max priority 1.0; boost ONE valid slot.
        hot_t, hot_b = 7, 1
        hot_leaf = np.array([hot_t * ring.num_envs + hot_b])
        s.update_priorities(hot_leaf, np.array([10.0]),
                            expected_gen=ring.slot_gen[[hot_t]])
        rng = np.random.default_rng(3)
        draws = 40_000
        hot = others = 0
        w_hot, w_other = [], []
        for _ in range(draws // 200):
            _, aux = s.sample(rng, 200, gamma=0.99)
            is_hot = aux.leaf == hot_leaf[0]
            hot += int(is_hot.sum())
            others += int((~is_hot).sum())
            w_hot.extend(aux.weights[is_hot].tolist())
            w_other.extend(aux.weights[~is_hot].tolist())
        # Expected ratio: p_hot / p_other = 10 (alpha = 1). The hot
        # slot's draw share vs the MEAN other slot's share:
        valid_slots = (ring.size - 1) * ring.num_envs  # n_step=1, no
        per_other = others / (valid_slots - 1)         # dedup context
        ratio = hot / max(per_other, 1e-9)
        assert 7.0 < ratio < 13.0, ratio
        # IS weights: w ~ (N p)^-1, so hot weight / other weight = 1/10.
        w_ratio = np.mean(w_hot) / np.mean(w_other)
        assert 0.07 < w_ratio < 0.13, w_ratio

    def test_writeback_generation_guard_drops_overwritten(self):
        """A write-back whose slot was overwritten between sample and
        flush must be dropped, not stamped onto the new transition."""
        from dist_dqn_tpu.replay.host_ring import RingPrioritySampler

        ring = self._ring(slots=16, lanes=2, steps=12)
        s = RingPrioritySampler(ring, n_step=1, alpha=1.0, beta=1.0,
                                eps=0.0, name="test_per_guard")
        rng = np.random.default_rng(0)
        _, aux = s.sample(rng, 8, gamma=0.99)
        # Overwrite the whole ring (16 slots) => every sampled slot's
        # generation moves on.
        ring.add_chunk(np.zeros((12, 2, 3), np.float32),
                       np.zeros((12, 2), np.int32),
                       np.zeros((12, 2), np.float32),
                       np.zeros((12, 2), bool), np.zeros((12, 2), bool))
        ring.add_chunk(np.zeros((12, 2, 3), np.float32),
                       np.zeros((12, 2), np.int32),
                       np.zeros((12, 2), np.float32),
                       np.zeros((12, 2), bool), np.zeros((12, 2), bool))
        applied, dropped = s.update_priorities(
            aux.leaf, np.full(8, 99.0), expected_gen=aux.slot_gen)
        assert applied == 0 and dropped == 8
        # The poisoned priority never entered the tree: no leaf mass
        # anywhere near 99^alpha.
        assert s.tree.total < ring.num_slots * ring.num_envs * 2.0

    def test_tree_tracks_appends_under_fence(self):
        """The publish hook keeps tree mass == valid region after every
        append, including wraparound evictions."""
        from dist_dqn_tpu.replay.host_ring import RingPrioritySampler

        ring = HostTimeRing(16, 2, (3,), np.float32)
        s = RingPrioritySampler(ring, n_step=2, alpha=1.0, beta=1.0,
                                eps=0.0, name="test_per_sync")
        for _ in range(5):  # wraps the 16-slot ring
            ring.add_chunk(np.zeros((8, 2, 3), np.float32),
                           np.zeros((8, 2), np.int32),
                           np.zeros((8, 2), np.float32),
                           np.zeros((8, 2), bool),
                           np.zeros((8, 2), bool))
            valid = max(ring.size - 2, 0) * 2  # (size - n_step) * lanes
            assert s.tree.total == pytest.approx(valid)  # all prio 1.0


class TestSamplePrefetcher:
    """Unit coverage mirroring TestEvacuationWorker: the fence
    handshake, stale drop+redraw, exception propagation, shutdown."""

    def _ring_and_sampler(self, slots=128, lanes=2):
        ring = HostTimeRing(slots, lanes, (3,), np.float32)

        def append(v, C=16):
            ring.add_chunk(np.full((C, lanes, 3), v, np.float32),
                           np.full((C, lanes), int(v), np.int32),
                           np.full((C, lanes), v, np.float32),
                           np.zeros((C, lanes), bool),
                           np.zeros((C, lanes), bool))

        def sample_fn(k):
            rng = np.random.default_rng(
                np.random.SeedSequence(0, spawn_key=(k,)))
            hs = ring.sample(rng, 32, n_step=1, gamma=0.99)
            return {"obs": hs.batch.obs, "action": hs.batch.action,
                    "reward": hs.batch.reward}, hs
        return ring, append, sample_fn

    def _prefetcher(self, sample_fn, ring, **kw):
        from dist_dqn_tpu.replay.staging import SamplePrefetcher
        kw.setdefault("name", "test_prefetch")
        return SamplePrefetcher(sample_fn, depth=2,
                                wait_generation=ring.wait_generation,
                                **kw)

    def test_request_pop_in_order_and_shutdown(self):
        ring, append, sample_fn = self._ring_and_sampler()
        append(1.0)
        p = self._prefetcher(sample_fn, ring)
        try:
            p.request(4, ring.generation)
            batches = [p.pop(ring.generation) for _ in range(4)]
            # Content is internally consistent and deterministic: the
            # same k against the same window redraws identically.
            for k, (dev, aux) in enumerate(batches):
                obs = np.asarray(dev["obs"])
                assert np.all(obs == 1.0)
                redraw, re_aux = sample_fn(k)
                np.testing.assert_array_equal(
                    np.asarray(dev["action"]), redraw["action"])
                assert aux.generation == re_aux.generation
            assert p.stale_total == 0
        finally:
            p.close()
        assert not p._thread.is_alive()

    def test_request_ahead_of_publication_waits_for_fence(self):
        """A request for a generation the ring has not reached yet must
        block the worker on the fence, then sample the NEW window —
        the handshake that keeps look-ahead honest."""
        ring, append, sample_fn = self._ring_and_sampler()
        append(1.0)
        p = self._prefetcher(sample_fn, ring)
        try:
            target = ring.generation + 1
            p.request(1, target)  # window not published yet
            time.sleep(0.1)
            assert len(p) == 0   # worker is parked on the fence
            append(2.0)          # publish generation 2
            dev, aux = p.pop(target)
            assert aux.generation >= target
        finally:
            p.close()

    def test_stale_batch_dropped_and_redrawn(self):
        """A batch sampled against an older window than the pop's fence
        is counted, dropped and re-drawn at the fenced window."""
        ring, append, sample_fn = self._ring_and_sampler()
        append(1.0)
        p = self._prefetcher(sample_fn, ring)
        try:
            old_gen = ring.generation
            p.request(2, old_gen)
            # Let the worker sample both batches against the old window.
            deadline = time.time() + 30
            while p.sampled_total < 2 and time.time() < deadline:
                time.sleep(0.01)
            assert p.sampled_total == 2
            append(2.0)  # window moves on
            dev, aux = p.pop(ring.generation)  # fence ahead of the tags
            assert p.stale_total >= 1
            assert aux.generation >= old_gen + 1
            # The redraw saw the new window: slots from the new chunk
            # exist, and every obs matches its action stamp (no tear).
            obs = np.asarray(dev["obs"])
            act = np.asarray(dev["action"]).astype(np.float32)
            assert np.all(obs == act[:, None])
        finally:
            p.close()

    def test_concurrent_append_vs_prefetch_hammer(self):
        """Fence hammer: background appends race prefetched sampling;
        every popped batch must be internally consistent (obs == action
        == reward stamps) and at least as new as its requested fence."""
        ring, append, sample_fn = self._ring_and_sampler()
        append(1.0)
        p = self._prefetcher(sample_fn, ring)
        stop = threading.Event()
        errors = []

        def writer():
            v = 2.0
            while not stop.is_set():
                append(v)
                v += 1.0
                time.sleep(0.001)

        t_w = threading.Thread(target=writer, name="hammer-writer")
        t_w.start()
        try:
            for _ in range(60):
                fence = ring.generation
                p.request(1, fence)
                dev, aux = p.pop(fence)
                if aux.generation < fence:
                    errors.append(("stale delivered", aux.generation,
                                   fence))
                obs = np.asarray(dev["obs"])
                act = np.asarray(dev["action"]).astype(np.float32)
                rew = np.asarray(dev["reward"])
                if not (np.all(obs == act[:, None])
                        and np.all(rew == act)):
                    errors.append(("torn batch", obs[:2], act[:2]))
        finally:
            stop.set()
            t_w.join(timeout=30)
            p.close()
        assert not errors, errors[0]
        assert not p._thread.is_alive()

    def test_worker_exception_propagates_no_hang(self):
        """An exception inside sample_fn must re-raise from pop() AND
        poison later requests — never a hung pop."""
        from dist_dqn_tpu.replay.staging import SamplePrefetcher

        def boom(k):
            raise RuntimeError("gather exploded")

        p = SamplePrefetcher(boom, depth=2, name="test_prefetch_boom")
        try:
            p.request(1, 0)
            # pop re-raises the worker's own exception (the
            # _EvacJob.wait discipline); request names the dead worker.
            with pytest.raises(RuntimeError, match="exploded"):
                p.pop(0)
            assert p.failed is not None
            with pytest.raises(RuntimeError, match="died"):
                p.request(1, 0)
        finally:
            p.close()
        assert not p._thread.is_alive()

    def test_loop_surfaces_prefetcher_failure(self):
        """End to end: a sampler that blows up mid-run must abort
        run_host_replay with the worker's exception, not wedge a pop."""
        from dist_dqn_tpu import host_replay_loop as hrl

        class _BoomRing(HostTimeRing):
            def sample(self, *a, **k):
                if self.generation >= 3:
                    raise RuntimeError("DRAM gather failed")
                return super().sample(*a, **k)

        orig = hrl.HostTimeRing
        hrl.HostTimeRing = _BoomRing
        try:
            with pytest.raises(RuntimeError, match="DRAM gather failed"):
                hrl.run_host_replay(_tiny_cfg(), total_env_steps=3200,
                                    chunk_iters=50,
                                    log_fn=lambda s: None,
                                    prefetch=True)
        finally:
            hrl.HostTimeRing = orig


def test_host_replay_bench_ab_smoke():
    """ISSUE 3/5 CI satellite: the three-arm A/B harness
    (uniform-serial vs uniform-prefetch vs PER-prefetch) runs end to
    end on CPU at a tiny size; the trace_ab row must report conserved
    D2H bytes, the uniform numerics pin, sample_s measured off the
    critical path (prefetch_wait < serial sample_s), and a healthy PER
    arm (nonzero write-backs, sane IS weights). Tier-1-safe: one small
    subprocess, CPU-clamped sizes."""
    proc = subprocess.run(
        [sys.executable, "benchmarks/host_replay_bench.py", "--allow-cpu",
         "--ab", "--chunks", "3", "--chunk-iters", "10", "--lanes", "4",
         "--batch-size", "16", "--train-every", "2", "--window", "4096"],
        cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rows = []
    for line in proc.stdout.splitlines():
        try:
            rows.append(json.loads(line))
        except ValueError:
            pass
    legs = {r.get("phase"): r for r in rows if "phase" in r}
    assert {"ab_uniform_serial", "ab_uniform_prefetch",
            "ab_per_prefetch", "trace_ab"} <= set(legs)
    ab = legs["trace_ab"]
    assert ab["d2h_bytes_conserved"] is True
    # The uniform numerics pin: prefetching changes WHEN sampling
    # happens, never what is trained on.
    assert ab["numerics_match"] is True
    # Acceptance: sample_s measured off the critical path.
    assert ab["sample_off_critical_path"] is True
    assert ab["prefetch_wait_s_total"] < ab["serial_sample_s_total"]
    assert legs["ab_uniform_serial"]["prefetch"] is False
    assert legs["ab_uniform_prefetch"]["prefetch"] is True
    assert legs["ab_per_prefetch"]["prioritized"] is True
    # The PER arm is alive: write-backs flowed, IS weights sane.
    assert ab["per_prio_writeback_rows"] > 0
    assert 0.0 < ab["per_is_weight_min"] <= ab["per_is_weight_mean"] \
        <= 1.0
    assert legs["ab_per_prefetch"]["grad_steps"] > 0
    assert ab["platforms"] == "cpu"
