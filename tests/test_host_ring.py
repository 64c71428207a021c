"""Host-DRAM time-ring + hybrid collect/train loop (host_replay_loop.py):
the DRAM-resident twin of the device ring must produce numerically
identical transitions, and the hybrid loop must run the full
collect -> D2H -> ring -> sample -> H2D -> train cycle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.replay import device as dring
from dist_dqn_tpu.replay.host_ring import HostTimeRing

from tests.test_frame_dedup import H, W, S, _rolling_stream


@pytest.mark.parametrize("dedup", [False, True])
@pytest.mark.parametrize("steps,slots", [(40, 64), (200, 64)])
def test_host_ring_matches_device_ring(dedup, steps, slots):
    """Identical streams + identical (t, b) indices -> identical
    transitions from the host ring and the device ring, deduped or not,
    wrapped (200 > 64) or not."""
    rng = np.random.default_rng(0)
    lanes, n_step = 3, 3
    obs, action, reward, term, trunc = _rolling_stream(rng, steps, lanes)
    stored = obs[..., -1:] if dedup else obs

    host = HostTimeRing(slots, lanes, stored.shape[2:], np.uint8,
                        frame_stack=S if dedup else 0)
    for lo in range(0, steps, 40):  # chunked like the hybrid loop feeds it
        hi = min(lo + 40, steps)
        host.add_chunk(stored[lo:hi], action[lo:hi], reward[lo:hi],
                       term[lo:hi], trunc[lo:hi])

    dev = dring.time_ring_init(slots, lanes,
                               jnp.zeros(stored.shape[2:], jnp.uint8))
    for t in range(steps):
        dev = dring.time_ring_add(dev, jnp.asarray(stored[t]),
                                  jnp.asarray(action[t]),
                                  jnp.asarray(reward[t]),
                                  jnp.asarray(term[t]),
                                  jnp.asarray(trunc[t]))

    size = min(steps, slots)
    extra = S - 1 if dedup else 0
    offsets = np.arange(extra, size - n_step)
    oldest = (steps - size) % slots
    t_idx = ((oldest + offsets) % slots).astype(np.int32)
    b_idx = np.tile(np.arange(lanes),
                    (len(offsets) + lanes - 1) // lanes)[:len(offsets)] \
        .astype(np.int32)

    hb = host.gather(t_idx, b_idx, n_step, 0.97)
    db = dring.gather_transitions(dev, jnp.asarray(t_idx),
                                  jnp.asarray(b_idx), n_step, 0.97, lanes,
                                  frame_stack=S if dedup else 0)
    np.testing.assert_array_equal(hb.obs, np.asarray(db.obs))
    np.testing.assert_array_equal(hb.next_obs, np.asarray(db.next_obs))
    np.testing.assert_array_equal(hb.action, np.asarray(db.action))
    # f32 accumulation order differs host (numpy) vs device (XLA) by
    # ~1 ulp on the n-step reward sums; indices/frames stay exact.
    np.testing.assert_allclose(hb.reward, np.asarray(db.reward), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(hb.discount, np.asarray(db.discount),
                               rtol=1e-6, atol=1e-6)


def test_host_ring_chunk_wrap_and_bytes():
    ring = HostTimeRing(10, 2, (3,), np.float32)
    for start in range(0, 24, 6):
        chunk = np.arange(start, start + 6, dtype=np.float32)
        obs = np.repeat(chunk[:, None, None], 2, axis=1)
        obs = np.repeat(obs, 3, axis=2)
        ring.add_chunk(obs, np.zeros((6, 2), np.int32),
                       np.zeros((6, 2), np.float32),
                       np.zeros((6, 2), bool), np.zeros((6, 2), bool))
    assert ring.size == 10 and ring.pos == 24 % 10
    # The newest slot holds the last written value.
    assert ring.obs[(ring.pos - 1) % 10, 0, 0] == 23.0
    assert ring.nbytes > 0
    with pytest.raises(ValueError, match="exceeds"):
        ring.add_chunk(np.zeros((11, 2, 3), np.float32),
                       np.zeros((11, 2), np.int32),
                       np.zeros((11, 2), np.float32),
                       np.zeros((11, 2), bool), np.zeros((11, 2), bool))


def _fill_ring(ring, steps, lanes, chunk=10, obs_dim=3):
    """Append a recognizable stream: obs/action/reward all carry the
    global step number, so slot identity checks are cross-checkable."""
    for lo in range(0, steps, chunk):
        hi = min(lo + chunk, steps)
        t = np.arange(lo, hi, dtype=np.float32)
        obs = np.repeat(np.repeat(t[:, None, None], lanes, 1), obs_dim, 2)
        ring.add_chunk(obs, np.broadcast_to(t[:, None].astype(np.int32),
                                            (hi - lo, lanes)),
                       np.broadcast_to(t[:, None], (hi - lo, lanes)),
                       np.zeros((hi - lo, lanes), bool),
                       np.zeros((hi - lo, lanes), bool))


@pytest.mark.parametrize("steps,extra", [(80, 0), (80, 3)])
def test_sample_indices_stay_in_valid_region_after_wraparound(steps,
                                                              extra):
    """ISSUE 5 satellite (pre-existing test gap): after the ring wraps,
    sampled (t_idx, b_idx) must stay inside the SAME valid region the
    uniform draw advertises — the oldest `size - n_step` slots minus
    the dedup context skip — and the exposed identities must be the
    slots the batch was actually gathered at."""
    slots, lanes, n_step = 32, 2, 3
    stack = extra + 1 if extra else 0
    ring = HostTimeRing(slots, lanes, (3,) if not stack else (1,),
                        np.float32, frame_stack=stack)
    _fill_ring(ring, steps, lanes, obs_dim=3 if not stack else 1)
    assert ring.size == slots and ring.pos == steps % slots  # wrapped

    offsets = np.arange(extra, ring.size - n_step)
    valid_t = set(((ring.pos - ring.size + offsets) % slots).tolist())
    rng = np.random.default_rng(7)
    hs = ring.sample(rng, 512, n_step=n_step, gamma=0.99)
    assert set(hs.t_idx.tolist()) <= valid_t
    assert hs.b_idx.min() >= 0 and hs.b_idx.max() < lanes
    assert hs.generation == ring.generation
    # The identities are REAL: the stored stream stamps the global step
    # number into action AND reward, and the oldest valid slot maps to
    # step steps - slots + extra — so each sampled action must equal its
    # slot's stored step, which the t index recovers modulo the ring.
    stored_step = hs.batch.action  # == global step written at that t
    assert np.all((stored_step % slots) == (hs.t_idx % slots))
    # And the gathered batch is the one at those identities: re-gather
    # at the exposed (t, b) pairs and compare bit-for-bit.
    again = ring.gather(hs.t_idx, hs.b_idx, n_step, 0.99)
    np.testing.assert_array_equal(again.obs, hs.batch.obs)
    np.testing.assert_array_equal(again.reward, hs.batch.reward)


def test_slot_generation_stamps_track_overwrites():
    """slot_gen must carry the generation that last wrote each t-slot —
    the write-back staleness guard."""
    ring = HostTimeRing(8, 2, (2,), np.float32)
    for _ in range(3):  # 3 chunks x 4 slots over an 8-slot ring: wraps
        ring.add_chunk(np.zeros((4, 2, 2), np.float32),
                       np.zeros((4, 2), np.int32),
                       np.zeros((4, 2), np.float32),
                       np.zeros((4, 2), bool), np.zeros((4, 2), bool))
    assert ring.generation == 3
    # slots 0..3 were written by chunk 1 then overwritten by chunk 3;
    # slots 4..7 by chunk 2.
    np.testing.assert_array_equal(ring.slot_gen,
                                  [3, 3, 3, 3, 2, 2, 2, 2])


def test_hybrid_loop_vector_env_trains():
    """run_host_replay on CartPole: the full cycle executes, the learner
    steps at the fused cadence, metrics are finite."""
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg,
        network=dataclasses.replace(cfg.network, mlp_features=(16,)),
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        replay=dataclasses.replace(cfg.replay, capacity=2_048, min_fill=64),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        train_every=2,
    )
    out = run_host_replay(cfg, total_env_steps=4_000, chunk_iters=50,
                          log_fn=lambda s: None)
    assert out["env_steps"] >= 4_000
    assert out["grad_steps"] >= 50
    assert out["ring_transitions"] > 500
    last = out["history"][-1]
    assert np.isfinite(last["loss"])
    assert last["d2h_bytes"] > 0


def test_train_cli_host_replay_runtime(capsys):
    """--runtime host-replay is a first-class train-CLI surface: the
    hybrid loop runs end to end and prints the summary JSON."""
    import json
    import sys
    from unittest import mock

    from dist_dqn_tpu import train as tr

    argv = ["train", "--config", "cartpole", "--runtime", "host-replay",
            "--platform", "cpu", "--total-env-steps", "2000",
            "--chunk-iters", "50",
            "--set", "network.mlp_features=(16,)",
            "--set", "replay.capacity=1024",
            "--set", "replay.min_fill=64",
            "--set", "learner.batch_size=16",
            "--set", "actor.num_envs=8"]
    with mock.patch.object(sys, "argv", argv):
        tr.main()
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    assert rows[-1]["env_steps"] >= 2000
    assert rows[-1]["grad_steps"] > 0
    assert rows[-1]["window_transitions_max"] == 1024


def test_hybrid_loop_pixel_dedup():
    """Pixel env + frame_dedup: D2H streams single frames (7 KB/step,
    not 28), the host ring rebuilds stacks, the CNN learner trains."""
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = CONFIGS["atari"]
    cfg = dataclasses.replace(
        cfg,
        env_name="pixel_catch",
        network=dataclasses.replace(cfg.network, torso="small", hidden=32,
                                    compute_dtype="float32"),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        replay=dataclasses.replace(cfg.replay, capacity=1_024, min_fill=64,
                                   frame_dedup=True),
        learner=dataclasses.replace(cfg.learner, batch_size=8),
        train_every=4,
    )
    out = run_host_replay(cfg, total_env_steps=1_200, chunk_iters=50,
                          log_fn=lambda s: None)
    assert out["grad_steps"] > 0
    last = out["history"][-1]
    # 50 iters x 4 lanes x 84x84x1 u8 + small fields: single frames.
    assert last["d2h_bytes"] < 50 * 4 * 84 * 84 * 2
    assert np.isfinite(last["loss"])
