"""On-device replay-ratio engine (ISSUE 6): more grad steps per
collected chunk must change HOW MANY updates run, never WHAT each one
computes.

The load-bearing assertions:

* the FUSED EQUIVALENCE pin: ``replay.updates_per_chunk=N`` draws the
  same N batches — and lands the same params, bit for bit — as the
  pre-existing ``updates_per_train=N`` serial scan (same key stream:
  the ratio multiplies the scan length, it does not re-derive keys);
  the mirror of PR 5's uniform prefetch pin;
* the RATIO-1 pin: the default config runs the exact pre-knob program
  (param checksums equal with the knobs at their defaults, explicit
  ratio 1, and an explicit float32 actor dtype);
* the PER WRITE-BACK pin: N sub-steps' priority updates collapse to ONE
  flush with deterministic chronological last-wins on slots several
  sub-steps sampled (replay/prioritized_device.py
  prioritized_ring_update_batched over device.last_write_wins_scatter);
* the APEX SCAN pin: ``make_scan_train`` over N stacked batches ==
  N jitted serial train steps, bit for bit, priorities concatenated in
  sub-step order;
* the DONATION AUDIT: the compiled fused chunk aliases its donated
  carry completely (alias_bytes == argument bytes on this backend) at
  every ratio — the "no unintended device copies" check from the
  jax.stages evidence (utils/donation.py).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.envs import make_jax_env
from dist_dqn_tpu.models import build_network
from dist_dqn_tpu.train_loop import make_fused_train


def _tiny_cfg(ratio=1, upt=1, prioritized=False, actor_dtype="float32",
              train_batch=0):
    cfg = CONFIGS["cartpole"]
    return dataclasses.replace(
        cfg,
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(32,), hidden=0,
                                    compute_dtype="float32",
                                    actor_dtype=actor_dtype),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=64,
                                   prioritized=prioritized,
                                   updates_per_chunk=ratio,
                                   train_batch=train_batch),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        updates_per_train=upt,
    )


def _run_fused(cfg, chunks=3, iters=40):
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_fused_train(cfg, env, net)
    run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
    carry = init(jax.random.PRNGKey(0))
    metrics = None
    for _ in range(chunks):
        carry, metrics = run(carry, iters)
    checksum = float(sum(
        np.float64(np.sum(np.asarray(leaf, np.float64)))
        for leaf in jax.tree.leaves(jax.device_get(carry.learner.params))))
    return carry, jax.device_get(metrics), checksum


def test_fused_ratio_equals_serial_updates():
    """THE equivalence pin: ratio N == updates_per_train N, bit for bit
    (same scan length, same key stream), with N x the grad steps."""
    _, m1, ck1 = _run_fused(_tiny_cfg(ratio=1))
    _, m4, ck4 = _run_fused(_tiny_cfg(ratio=4))
    _, mu, cku = _run_fused(_tiny_cfg(ratio=1, upt=4))
    assert float(m4["grad_steps_in_chunk"]) == \
        4 * float(m1["grad_steps_in_chunk"]) > 0
    assert ck4 == cku
    assert np.isfinite(ck4)


def test_fused_ratio1_default_program_unchanged():
    """Ratio 1 + float32 actor dtype + train_batch 0 IS the pre-knob
    program: explicit defaults and implicit defaults land identical
    params (the param_checksum A/B pin guarding the dtype split)."""
    _, _, ck_default = _run_fused(_tiny_cfg())
    _, _, ck_explicit = _run_fused(
        _tiny_cfg(ratio=1, actor_dtype="float32", train_batch=0))
    assert ck_default == ck_explicit


def test_fused_per_ratio_runs_and_scales():
    """PER + ratio: the deferred last-wins flush path compiles, trains,
    scales the grad count, and stays finite."""
    _, m1, _ = _run_fused(_tiny_cfg(ratio=1, prioritized=True))
    carry, m4, ck = _run_fused(_tiny_cfg(ratio=4, prioritized=True))
    assert float(m4["grad_steps_in_chunk"]) == \
        4 * float(m1["grad_steps_in_chunk"]) > 0
    assert np.isfinite(ck)
    # The flush really landed: the priority plane moved off its
    # max-priority seeding for sampled slots.
    prios = np.asarray(carry.replay.priorities)
    assert (prios[prios > 0] != float(carry.replay.max_priority)).any()


def test_actor_dtype_split_keeps_fp32_masters():
    """bf16 acting must never touch the learner's master params: every
    float leaf stays float32 and the run stays finite."""
    carry, metrics, ck = _run_fused(_tiny_cfg(ratio=2,
                                              actor_dtype="bfloat16"))
    for leaf in jax.tree.leaves(carry.learner.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32
    assert np.isfinite(ck)
    assert float(metrics["grad_steps_in_chunk"]) > 0


def test_train_batch_pow2_bucketing():
    """replay.train_batch widens the train event batch to the next
    power of two; 0 keeps learner.batch_size exactly."""
    from dist_dqn_tpu import loop_common

    assert loop_common.resolve_train_batch(_tiny_cfg()) == 16
    assert loop_common.resolve_train_batch(
        _tiny_cfg(train_batch=24)) == 32
    assert loop_common.resolve_train_batch(
        _tiny_cfg(train_batch=32)) == 32
    with pytest.raises(ValueError):
        loop_common.resolve_replay_ratio(_tiny_cfg(ratio=0))
    with pytest.raises(ValueError):
        loop_common.make_actor_param_cast("float16")
    # And the fused loop actually trains at the widened width.
    _, m, ck = _run_fused(_tiny_cfg(train_batch=24))
    assert np.isfinite(ck) and float(m["grad_steps_in_chunk"]) > 0


def test_per_batched_writeback_last_wins():
    """N sub-steps' updates collapse to one flush; a slot sampled by
    several sub-steps ends at the LAST sub-step's |TD| (+eps),
    deterministically — not whichever XLA's scatter applied last."""
    from dist_dqn_tpu.replay import prioritized_device as pring

    state = pring.prioritized_ring_init(8, 4, jnp.zeros((2,), jnp.float32))
    # Three "sub-steps" of two rows each; slot (1, 2) written by sub-
    # steps 0 and 2, slot (3, 1) by sub-steps 1 and 2.
    t_idx = jnp.array([[1, 3], [3, 5], [1, 3]], jnp.int32)
    b_idx = jnp.array([[2, 1], [1, 0], [2, 1]], jnp.int32)
    prios = jnp.array([[10.0, 20.0], [30.0, 40.0], [1.0, 2.0]])
    out = pring.prioritized_ring_update_batched(state, t_idx, b_idx,
                                                prios, 4, eps=0.5)
    got = np.asarray(out.priorities)                # cells t * 4 + b
    assert got[1 * 4 + 2] == pytest.approx(1.0 + 0.5)   # last: step 2
    assert got[3 * 4 + 1] == pytest.approx(2.0 + 0.5)   # last: step 2
    assert got[5 * 4 + 0] == pytest.approx(40.0 + 0.5)  # single writer
    assert float(out.max_priority) == pytest.approx(40.5)
    # Jitted path (how the chunk program runs it) agrees.
    out_j = jax.jit(pring.prioritized_ring_update_batched,
                    static_argnames=("num_envs", "eps"))(
                        state, t_idx, b_idx, prios, num_envs=4, eps=0.5)
    np.testing.assert_array_equal(got, np.asarray(out_j.priorities))


def test_last_write_wins_scatter_matches_serial_loop():
    """Property check against the obvious serial reference on random
    collision-heavy index streams."""
    from dist_dqn_tpu.replay.device import last_write_wins_scatter

    rng = np.random.default_rng(0)
    for _ in range(5):
        plane = rng.normal(size=32).astype(np.float32)
        idx = rng.integers(0, 32, size=64).astype(np.int32)
        vals = rng.normal(size=64).astype(np.float32)
        ref = plane.copy()
        for i, v in zip(idx, vals):   # chronological: later wins
            ref[i] = v
        got = np.asarray(last_write_wins_scatter(
            jnp.asarray(plane), jnp.asarray(idx), jnp.asarray(vals)))
        np.testing.assert_array_equal(got, ref)


def test_scan_train_matches_serial_steps():
    """make_scan_train over N stacked batches == N jitted serial steps,
    bit for bit — the apex service's replay-ratio dispatch."""
    from dist_dqn_tpu.agents.dqn import make_learner, make_scan_train
    from dist_dqn_tpu.config import LearnerConfig, NetworkConfig
    from dist_dqn_tpu.types import Transition

    net = build_network(NetworkConfig(torso="mlp", mlp_features=(32,),
                                      hidden=0), 2)
    init, step = make_learner(net, LearnerConfig(batch_size=8))
    state = init(jax.random.PRNGKey(0), jnp.zeros((4,), jnp.float32))
    jit_step = jax.jit(step)
    r = np.random.default_rng(0)
    N, B = 3, 8

    def mk():
        return Transition(
            obs=jnp.asarray(r.normal(size=(B, 4)).astype(np.float32)),
            action=jnp.asarray(r.integers(0, 2, B, np.int32)),
            reward=jnp.asarray(r.normal(size=B).astype(np.float32)),
            discount=jnp.full(B, 0.99, jnp.float32),
            next_obs=jnp.asarray(r.normal(size=(B, 4)).astype(np.float32)))

    batches = [mk() for _ in range(N)]
    s_serial, prios = state, []
    for b in batches:
        s_serial, m = jit_step(s_serial, b, jnp.ones(B))
        prios.append(np.asarray(m["priorities"]))
    stacked = Transition(*(jnp.stack([getattr(b, f) for b in batches])
                           for f in Transition._fields))
    scan = jax.jit(make_scan_train(step))
    s_scan, m_scan = scan(state, stacked, jnp.ones((N, B), jnp.float32))
    for a, b in zip(jax.tree.leaves(s_serial.params),
                    jax.tree.leaves(s_scan.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.concatenate(prios),
                                  np.asarray(m_scan["priorities"]))
    assert np.asarray(m_scan["priorities"]).shape == (N * B,)


def test_host_replay_ratio_prefetch_pin():
    """Host-replay at ratio 2: the prefetcher draws the event's batches
    from the same per-index RNG streams as the serial path — identical
    params (PR 5's pin extended over the ratio), and 2x the grad steps
    of ratio 1."""
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    def hr_cfg(ratio):
        cfg = _tiny_cfg(ratio=ratio)
        return dataclasses.replace(
            cfg, replay=dataclasses.replace(cfg.replay, capacity=4096))

    out1 = run_host_replay(hr_cfg(1), total_env_steps=1600, chunk_iters=50,
                           log_fn=lambda s: None)
    out2 = run_host_replay(hr_cfg(2), total_env_steps=1600, chunk_iters=50,
                           log_fn=lambda s: None)
    out2s = run_host_replay(hr_cfg(2), total_env_steps=1600, chunk_iters=50,
                            log_fn=lambda s: None, prefetch=False)
    assert out2["grad_steps"] == 2 * out1["grad_steps"] > 0
    assert out2["param_checksum"] == out2s["param_checksum"]
    assert out2["replay_ratio"] == 2
    assert out2["train_batch"] == 16
    assert out2["actor_dtype"] == "float32"
    assert out2["grad_steps_per_sec"] > 0


def test_fused_chunk_donation_audit():
    """The jax.stages evidence: the donated fused-chunk carry aliases
    completely — argument bytes == alias bytes (no unintended device
    copy of the replay ring or learner state), at ratio 1 and 4."""
    from dist_dqn_tpu.utils import donation

    for ratio in (1, 4):
        cfg = _tiny_cfg(ratio=ratio, prioritized=True)
        env = make_jax_env(cfg.env_name)
        net = build_network(cfg.network, env.num_actions)
        init, run_chunk = make_fused_train(cfg, env, net)
        carry = init(jax.random.PRNGKey(0))
        ring_bytes = sum(np.asarray(leaf).nbytes
                         for leaf in jax.tree.leaves(carry.replay))
        compiled = jax.jit(run_chunk, static_argnums=1,
                           donate_argnums=0).lower(carry, 20).compile()
        rep = donation.assert_donation(
            compiled, min_aliased_pairs=10, min_alias_bytes=ring_bytes,
            what=f"fused chunk (ratio {ratio})")
        if rep.get("alias_bytes") is not None \
                and rep.get("argument_bytes") is not None:
            assert rep["alias_bytes"] == rep["argument_bytes"]


def test_apex_service_scan_path_trains():
    """The apex service's replay-ratio wiring: the scanned dispatch
    trains in strides of N, priorities come back [N*B] and flush
    through the batched write-back without error."""
    from dist_dqn_tpu.actors.service import (ApexLearnerService,
                                             ApexRuntimeConfig)
    from dist_dqn_tpu.actors.transport import ShmRing, encode_arrays

    base = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        base,
        network=dataclasses.replace(base.network, torso="mlp",
                                    mlp_features=(32,), hidden=0,
                                    compute_dtype="float32"),
        replay=dataclasses.replace(base.replay, capacity=4096,
                                   prioritized=True, min_fill=64,
                                   updates_per_chunk=4),
        learner=dataclasses.replace(base.learner, batch_size=16,
                                    n_step=1))
    rt = ApexRuntimeConfig(num_actors=2, envs_per_actor=8,
                           total_env_steps=10 ** 9, ring_mb=8,
                           stall_warn_s=0.0, log_every_s=10 ** 9,
                           train_steps_per_pass=8)
    service = ApexLearnerService(cfg, rt, log_fn=lambda *a: None)
    try:
        assert service.replay_ratio == 4
        assert service._train_scan is not None
        ring = ShmRing(f"req_{service.run_id}")
        r = np.random.default_rng(3)

        def obs():
            return r.normal(size=(8, 4)).astype(np.float32)

        for a in range(2):
            assert ring.push(encode_arrays(
                {"obs": obs()}, {"kind": "hello", "actor": a, "t": 0}))
        service._drain_transports()
        service._flush_act_queue()
        for t in range(1, 25):
            for a in range(2):
                done = r.random(8) < 0.05
                assert ring.push(encode_arrays(
                    {"obs": obs(),
                     "reward": r.normal(size=8).astype(np.float32),
                     "terminated": done.astype(np.uint8),
                     "truncated": np.zeros(8, np.uint8),
                     "next_obs": obs()},
                    {"kind": "step", "actor": a, "t": t}))
            service._drain_transports()
            service._flush_act_queue()
            service._flush_pending(force=True)
        assert len(service.replay) >= 64
        service._maybe_train()
        assert service.grad_steps > 0
        assert service.grad_steps % 4 == 0
        service._finalize_all_train()
        assert np.isfinite(service._last_loss)
    finally:
        service.shutdown()


def test_train_cli_flag_routing(monkeypatch, capsys):
    """ISSUE 6 satellite: --replay-ratio / --actor-dtype apply where
    supported and emit the standard ignored-flag warning where not —
    apex warns (and strips) the dtype split but takes the ratio; the
    recurrent fused loop warns both."""
    import sys

    import dist_dqn_tpu.actors.service as svc_mod
    from dist_dqn_tpu import train as train_mod

    seen = {}

    def fake_run_apex(cfg, rt, log_fn=print):
        seen["cfg"] = cfg
        return {}

    monkeypatch.setattr(svc_mod, "run_apex", fake_run_apex)
    monkeypatch.setattr(train_mod, "train",
                        lambda cfg, **kw: seen.setdefault("fused", cfg)
                        or (None, []))
    monkeypatch.setattr(sys, "argv", [
        "train", "--config", "cartpole", "--runtime", "apex",
        "--replay-ratio", "2", "--actor-dtype", "bfloat16"])
    train_mod.main()
    out = capsys.readouterr().out
    assert "--actor-dtype" in out and "ignored" in out
    assert seen["cfg"].replay.updates_per_chunk == 2      # ratio applied
    assert seen["cfg"].network.actor_dtype == "float32"   # dtype stripped

    monkeypatch.setattr(sys, "argv", [
        "train", "--config", "r2d2", "--replay-ratio", "2",
        "--actor-dtype", "bfloat16"])
    train_mod.main()
    out = capsys.readouterr().out
    assert "--replay-ratio" in out and "--actor-dtype" in out
    cfg = seen["fused"]
    assert cfg.replay.updates_per_chunk == 1              # both ignored
    assert cfg.network.actor_dtype == "float32"


def test_replay_ratio_sweep_smoke():
    """The learner_bench sweep harness cannot bit-rot: two tiny points,
    rows carry the acceptance fields, grad counts scale with the
    ratio."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "benchmarks"))
    import json

    from learner_bench import replay_ratio_sweep

    rows = []
    replay_ratio_sweep(2, ratios=(1, 2), chunk_iters=30,
                       emit=lambda s: rows.append(json.loads(s)))
    assert [r["replay_ratio"] for r in rows] == [1, 2]
    for r in rows:
        for key in ("grad_steps_per_sec", "train_batch", "actor_dtype",
                    "scaling_vs_ratio1", "aliased_pairs"):
            assert key in r
    assert rows[1]["grad_steps_per_chunk"] == \
        2 * rows[0]["grad_steps_per_chunk"] > 0
