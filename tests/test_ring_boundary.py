"""The layout in which the device ring's merged-row obs buffer crosses the
chunk program's boundary, decided by the width its rows are stored at
(replay/device_ring.py ``merged_row_boundary``): the rule on shapes, the
programs ``train.train`` dispatches on one device and on a mesh, the carry's
bits, the ``--checkpoint-replay`` resume — and, under the TPU's own compiler,
that the stored width is what takes the two whole-ring copies out of the
program."""
import dataclasses
import json
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dist_dqn_tpu import telemetry
from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.envs import make_jax_env
from dist_dqn_tpu.models import build_network
from dist_dqn_tpu.parallel import make_mesh, make_mesh_fused_train
from dist_dqn_tpu.replay import device_ring
from dist_dqn_tpu.replay.device_ring import merged_row_boundary
from dist_dqn_tpu.train import train
from dist_dqn_tpu.train_loop import fused_parts, make_fused_train
from dist_dqn_tpu.utils.donation import assert_donation


# -- (a) the rule, on shapes --------------------------------------------------
# (shape, crosses the boundary row-major, padded bytes row-major, padded
# bytes of the order that pads least, stored row width): the three rings of
# the benchmark's cells, one that is row-major by itself, and a four-way
# shard of each.
SHAPES = [
    # atari +0.19%, apex +1.58%, r2d2 +1.59%
    ((200_000, 28_224), True, 5_657_600_000, 5_646_606_336, 28_288),
    ((1_000_000, 7_056), False, 7_168_000_000, 7_056_451_584, 7_056),
    ((640_000, 7_056), False, 4_587_520_000, 4_515_840_000, 7_056),
    ((20_000, 28_224), True, 565_760_000, 565_760_000, 28_224),
    ((50_000, 28_224), True, 1_414_400_000, 1_412_554_752, 28_288),
    ((250_000, 7_056), False, 1_792_000_000, 1_764_790_272, 7_056),
    ((160_000, 7_056), False, 1_146_880_000, 1_128_960_000, 7_056),
    ((5_000, 28_224), True, 141_440_000, 141_440_000, 28_224),
]


@pytest.mark.parametrize("shape,row_major,row_major_bytes,default,width",
                         SHAPES)
def test_the_rule_on_shapes(shape, row_major, row_major_bytes, default,
                            width):
    assert merged_row_boundary(shape, np.uint8) == (
        row_major, row_major_bytes, default, width)


def _preset(name, *overrides):
    from dist_dqn_tpu.config import apply_overrides

    return apply_overrides(CONFIGS[name], list(overrides))


@pytest.mark.parametrize("cfg,num_shards,expected", [
    (_preset("atari"), 1, SHAPES[0]),
    (_preset("apex", "replay.frame_dedup=true"), 1, SHAPES[1]),
    (_preset("r2d2", "replay.frame_dedup=true", "replay.capacity=640000"), 1,
     SHAPES[2]),
    # atari.dp4: each shard holds the one-chip preset
    (_preset("atari", "actor.num_envs=256", "replay.capacity=800000"), 4,
     SHAPES[0]),
    # --mesh-devices 4 on the preset, 64 lanes split four ways: a shard of
    # 1.4 GB is stored tiled by the auto rule (loop_common
    # resolve_flat_storage) and states nothing; stored flat, it is stated
    (_preset("atari"), 4, None),
    (_preset("atari", "replay.flat_storage=true"), 4, SHAPES[4]),
], ids=["atari", "apex", "r2d2", "atari.dp4", "atari-4-way",
        "atari-4-way-flat"])
def test_the_ring_decides_from_its_per_shard_shape(cfg, num_shards, expected):
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    _, replay = fused_parts(cfg, env, net, num_shards=num_shards)
    if expected is None:
        assert replay.boundary is None
        return
    shape, *decided = expected
    assert replay.boundary == tuple(decided)
    # the ring is allocated at the stored width, and nothing else changes
    lanes = cfg.actor.num_envs // num_shards
    state = jax.eval_shape(replay.init, jax.ShapeDtypeStruct(
        (lanes,) + tuple(env.observation_shape), env.observation_dtype))
    assert getattr(state, "ring", state).obs.shape == (shape[0], decided[-1])


def test_a_tiled_ring_decides_nothing():
    cfg = CONFIGS["cartpole"]
    env = make_jax_env(cfg.env_name)
    _, replay = fused_parts(cfg, env, build_network(cfg.network,
                                                    env.num_actions))
    assert replay.boundary is None


# -- toy programs with a merged-row ring --------------------------------------
def _toy(family):
    """Pixel observations, a ring of 1,024 merged rows: u8[1024, 28224]
    (+0.23% row-major: stored 28,288 wide) or, deduplicated,
    u8[1024, 7056] (+1.59%: stored as it is unless a test moves the
    constant, then 7,168 wide)."""
    if family == "recurrent":
        cfg = CONFIGS["r2d2"]
        cfg = dataclasses.replace(
            cfg, env_name="pixel_catch",
            network=dataclasses.replace(
                cfg.network, torso="small", hidden=16, lstm_size=8,
                compute_dtype="float32", lstm_dtype="float32"),
            replay=dataclasses.replace(
                cfg.replay, capacity=1024, min_fill=64, burn_in=2,
                unroll_length=4, sequence_stride=2, frame_dedup=True),
            learner=dataclasses.replace(cfg.learner, n_step=2,
                                        batch_size=8))
    else:
        cfg = CONFIGS["atari"]
        cfg = dataclasses.replace(
            cfg, env_name="pixel_catch", train_every=2,
            network=dataclasses.replace(cfg.network, torso="small",
                                        hidden=16, compute_dtype="float32"),
            replay=dataclasses.replace(
                cfg.replay, capacity=1024, min_fill=64, flat_storage=True,
                prioritized=family == "per_dedup",
                frame_dedup=family == "per_dedup"),
            learner=dataclasses.replace(cfg.learner, batch_size=16))
    cfg = dataclasses.replace(
        cfg, actor=dataclasses.replace(cfg.actor, num_envs=8))
    env = make_jax_env(cfg.env_name)
    return cfg, env, build_network(cfg.network, env.num_actions)


def _programs(family, num_devices):
    """(init, run) as ``train.train`` builds them."""
    cfg, env, net = _toy(family)
    if num_devices == 1:
        init, run_chunk = make_fused_train(cfg, env, net)
        return init, jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
    return make_mesh_fused_train(
        cfg, env, net, make_mesh(devices=jax.devices()[:num_devices]))


def _obs(carry):
    return getattr(carry.replay, "ring", carry.replay).obs


FAMILIES = [("uniform", 1), ("per_dedup", 1), ("recurrent", 1),
            ("uniform", 2)]
WIDTH = {"uniform": (28_224, 28_288), "per_dedup": (7_056, 7_168),
         "recurrent": (7_056, 7_168)}


# -- (b) the dispatched programs hold the wider ring and alias it -------------
@pytest.mark.parametrize("family,num_devices", FAMILIES)
def test_both_jit_sites_hold_the_stored_width_and_alias_the_ring(
        family, num_devices, monkeypatch):
    # every merged-row ring made row-major, the 7,056-B rows too
    monkeypatch.setattr(device_ring, "ROW_MAJOR_MAX_EXTRA", 1.0)
    init, run = _programs(family, num_devices)
    carry = init(np.asarray(jax.random.PRNGKey(0)))
    obs = _obs(carry)
    # one shard's rows whole: the lane axis of a merged-row buffer is its
    # second, and each shard's rows are stored at the whole-tile width
    assert obs.sharding.shard_shape(obs.shape) == (
        obs.shape[0], WIDTH[family][1])
    compiled = run.lower(carry, 20).compile()
    # the donated ring is updated in place
    assert_donation(compiled, min_alias_bytes=obs.nbytes // num_devices,
                    what=f"{family} chunk on {num_devices} device(s)")
    carry, metrics = run(carry, 20)
    assert obs.is_deleted()
    assert int(metrics["env_frames"]) == 20 * 8
    # a row's tail past the logical width is never written
    assert not np.asarray(_obs(carry)).reshape(
        obs.shape[0], num_devices, -1)[..., WIDTH[family][0]:].any()


# -- (c) the carry's bits -----------------------------------------------------
@pytest.mark.parametrize("family,num_devices", FAMILIES)
def test_whole_carry_bit_equal_wider_rows_against_logical(
        family, num_devices, monkeypatch):
    logical, stored = WIDTH[family]

    def three_chunks(max_extra):
        monkeypatch.setattr(device_ring, "ROW_MAJOR_MAX_EXTRA", max_extra)
        cfg, env, net = _toy(family)
        _, replay = fused_parts(cfg, env, net, num_shards=num_devices)
        assert replay.boundary.row_width == (stored if max_extra > 0
                                             else logical)
        init, run = _programs(family, num_devices)
        carry = init(np.asarray(jax.random.PRNGKey(3)))
        steps = 0.0
        for _ in range(3):
            carry, metrics = run(carry, 30)
            steps += float(metrics["grad_steps_in_chunk"])
        assert steps > 0
        carry = jax.tree.map(np.asarray, carry)
        # the ring by its logical columns, a shard at a time
        rows = _obs(carry)
        head = rows.reshape(len(rows), num_devices, -1)[..., :logical]
        ring = getattr(carry.replay, "ring", carry.replay)._replace(obs=head)
        return carry._replace(replay=(
            carry.replay._replace(ring=ring)
            if hasattr(carry.replay, "ring") else ring))

    wider, as_logical = three_chunks(1.0), three_chunks(-1.0)
    assert np.asarray(_obs(wider)).any()
    jax.tree.map(np.testing.assert_array_equal, wider, as_logical)


# -- (d) save, kill, resume ---------------------------------------------------
@pytest.mark.parametrize("num_devices", [1, 2])
def test_checkpoint_replay_resume_keeps_the_width_and_the_bits(
        tmp_path, num_devices):
    cfg, _, _ = _toy("uniform")
    kw = dict(chunk_iters=25, num_devices=num_devices,
              checkpoint_replay=True, save_every_frames=1)
    whole, _ = train(cfg, total_env_steps=4 * 25 * 8, log_fn=lambda s: None,
                     checkpoint_dir=str(tmp_path / "whole"), **kw)
    d = str(tmp_path / "killed")
    # "killed" after two chunks: the run ends there, its checkpoint stays
    train(cfg, total_env_steps=2 * 25 * 8, log_fn=lambda s: None,
          checkpoint_dir=d, **kw)
    lines = []
    resumed, history = train(cfg, total_env_steps=4 * 25 * 8,
                             log_fn=lines.append, checkpoint_dir=d, **kw)
    rows = [json.loads(s) for s in lines]
    assert {"resumed_at_frames": 2 * 25 * 8, "with_replay": True} in rows
    assert [r["env_frames"] for r in history] == [3 * 25 * 8, 4 * 25 * 8]
    assert _obs(resumed).shape == (1024 // num_devices,
                                   28_288 * num_devices)
    jax.tree.map(np.testing.assert_array_equal,
                 jax.tree.map(np.asarray, resumed),
                 jax.tree.map(np.asarray, whole))


# -- the set-up row and the gauge ---------------------------------------------
@pytest.mark.parametrize("family,engaged", [("uniform", 1), ("per_dedup", 0)])
def test_the_set_up_row_and_the_gauge_say_whether_it_engaged(family, engaged):
    cfg, _, _ = _toy(family)
    lines = []
    train(cfg, total_env_steps=25 * 8, chunk_iters=25, log_fn=lines.append)
    (row,) = [json.loads(s)["ring_boundary"] for s in lines
              if "ring_boundary" in s]
    logical, stored = WIDTH[family]
    assert row == {"row_major": engaged,
                   "row_major_bytes": 1024 * stored,
                   "default_bytes": min(logical * 1024, 1024 * stored),
                   "row_width": stored if engaged else logical,
                   # ... and the scalar planes: 1024 flat cells, f32
                   "planes": {"cells": 1024, "shape": [1024],
                              "bytes": 4096}}
    gauge = telemetry.get_registry().gauge("dqn_ring_boundary_row_major", "")
    assert gauge.value == engaged


# -- under the TPU's compiler -------------------------------------------------
@pytest.fixture(scope="module")
def v5e():
    """One described (not attached) v5e chip: the TPU compiler is installed
    here and compiles for it. Sizes and instruction text, never a time."""
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def _ring_copies(compiled, shape):
    tag = re.escape("u8[%d,%d]" % shape)
    text = compiled.as_text()
    return (len(re.findall(rf"= {tag}\{{[^}}]*\}} copy\(", text)),
            re.findall(rf"{tag}\{{([\d,]+):", text.split("\n")[0]))


@pytest.mark.parametrize("max_extra,width,copies,order", [
    (device_ring.ROW_MAJOR_MAX_EXTRA, 28_288, 0, "1,0"),
    (-1.0, 28_224, 2, "0,1"),
], ids=["stored-28288-wide", "stored-28224-wide"])
def test_v5e_the_stored_width_takes_the_ring_copies_out(
        v5e, monkeypatch, max_extra, width, copies, order):
    """u8[1024, 28224]: slots-minor pads least, so the device's default is
    ``{0,1}`` and the chunk program copies the ring in and out; stored
    28,288 wide, the same program has it ``{1,0}`` on both sides, no copy,
    no second ring among its temporaries, and the ring aliased."""
    monkeypatch.setattr(device_ring, "ROW_MAJOR_MAX_EXTRA", max_extra)
    cfg, env, net = _toy("uniform")
    one = SingleDeviceSharding(v5e)
    key = jax.ShapeDtypeStruct((2,), np.uint32, sharding=one)
    init, run_chunk = make_fused_train(cfg, env, net)
    carry = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(init, key))
    compiled = jax.jit(run_chunk, static_argnums=1,
                       donate_argnums=0).lower(carry, 20).compile()
    assert _ring_copies(compiled, (1024, width)) == (copies, [order, order])
    assert_donation(compiled, min_alias_bytes=1024 * width)
    ring_bytes = 1024 * 28_224
    assert (compiled.memory_analysis().temp_size_in_bytes > ring_bytes) == (
        copies > 0)
