"""The acting observation carried once, as 32-bit words (envs/base.py
``held_in_words``): one word a pixel, byte ``k`` = frame ``k`` of the
rolling stack. The byte path — the stack held as ``u8[..., depth]``, what
every other depth or dtype keeps — is the fixture every test compares
with: ``held_in_words`` patched to False in the modules that ask it."""
import collections
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu import telemetry, train as train_mod, train_loop
from dist_dqn_tpu.agents.dqn import make_actor_step
from dist_dqn_tpu.agents.r2d2 import make_recurrent_actor_step
from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.envs import base, make_jax_env
from dist_dqn_tpu.envs.pixel_catch import PixelCatch
from dist_dqn_tpu.models import build_network
from dist_dqn_tpu.replay import device_ring
from dist_dqn_tpu.train import train
from dist_dqn_tpu.train_loop import make_fused_train
from dist_dqn_tpu.utils.checkpoint import (TrainCheckpointer,
                                           record_checkpoint_kind)

PIXEL_ENVS = ["pixel_pong", "pixel_breakout", "pixel_catch", "dmc_pixels"]


@pytest.fixture
def byte_path(monkeypatch):
    """The program as it is for a depth or dtype the words do not cover."""
    def engage():
        for mod in (base, train_loop, device_ring, train_mod):
            monkeypatch.setattr(mod, "held_in_words", lambda env: False)
    return engage


def _episodes(env, steps, lanes=3):
    """``steps`` auto-resetting vector steps under seeded random actions:
    the observations entering each step and the ``done`` leaving it."""
    state, obs = env.v_reset(jax.random.PRNGKey(5), lanes)
    actions = jax.random.randint(jax.random.PRNGKey(6), (steps, lanes), 0,
                                 env.num_actions)

    def step(carry, a):
        state, obs = carry
        state, out = env.v_step(state, a)
        done = jnp.logical_or(out.terminated, out.truncated)
        return (state, out.obs), (obs, out.next_obs, done, out.reward)

    (state, last), ys = jax.jit(
        lambda s, o: jax.lax.scan(step, (s, o), actions))(state, obs)
    return jax.tree.map(np.asarray, (env.stack_obs(env.observe(state)),
                                     last) + ys)


# -- (a) the roll and the re-tiling --------------------------------------------
@pytest.mark.parametrize("name", PIXEL_ENVS)
def test_word_roll_is_the_concatenate_byte_for_byte(name, byte_path):
    env = make_jax_env(name, max_steps=5)      # episode ends inside the run
    assert base.held_in_words(env)
    state, _ = env.v_reset(jax.random.PRNGKey(0), 2)
    assert env.observe(state).shape == (2, 84 * 84)
    assert env.observe(state).dtype == jnp.uint32
    words = _episodes(env, 13)
    byte_path()
    state, _ = env.v_reset(jax.random.PRNGKey(0), 2)
    assert env.observe(state).shape == (2, 84, 84, 4)
    assert env.observe(state).dtype == jnp.uint8
    for got, want in zip(words, _episodes(env, 13)):
        np.testing.assert_array_equal(got, want)
    held, last, obs, next_obs, done, _ = words
    assert done.any() and not done.all()
    # what the state holds IS the observation
    np.testing.assert_array_equal(held, last)
    # the frame_stack contract (envs/base.py): within an episode the stack
    # rolls by one frame; a reset re-tiles the first frame
    after = np.concatenate([obs[1:], last[None]])
    np.testing.assert_array_equal(next_obs[..., :-1], obs[..., 1:])
    for t, b in zip(*np.nonzero(~done)):
        np.testing.assert_array_equal(after[t, b], next_obs[t, b])
    for t, b in zip(*np.nonzero(done)):
        np.testing.assert_array_equal(after[t, b],
                                      np.repeat(after[t, b][..., :1], 4, -1))


def test_the_word_views_are_the_stack():
    stack = jax.random.randint(jax.random.PRNGKey(1), (3, 84, 84, 4), 0,
                               256).astype(jnp.uint8)
    words = base.stack_to_words(stack)
    assert words.shape == (3, 7056) and words.dtype == jnp.uint32
    # byte k of a word is frame k (little-endian)
    np.testing.assert_array_equal(
        np.asarray(words), np.asarray(stack).reshape(3, 7056, 4).astype(
            np.uint32) @ (1 << (8 * np.arange(4))).astype(np.uint32))
    split = base.words_split(words)
    assert split.shape == (7056, 3, 4) and split.dtype == jnp.uint8
    for view in (base.words_to_stack(words, (84, 84)),
                 base.split_stack(split, (84, 84))):
        assert view.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(view), np.asarray(stack))
    np.testing.assert_array_equal(np.asarray(base.split_rows(split)),
                                  np.asarray(stack).reshape(3, -1))
    np.testing.assert_array_equal(np.asarray(base.words_newest(words)),
                                  np.asarray(stack[..., 3]).reshape(3, -1))


# -- (b) acting on the words ---------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["feed_forward", "recurrent"])
def test_act_on_words_is_act_on_the_stack(kind, dtype):
    env = make_jax_env("pixel_catch")
    cfg = CONFIGS["r2d2" if kind == "recurrent" else "atari"]
    net = build_network(dataclasses.replace(
        cfg.network, torso="small", hidden=16, compute_dtype=dtype,
        **(dict(lstm_size=8, lstm_dtype=dtype) if kind == "recurrent"
           else {})), env.num_actions)
    stack = jax.random.randint(jax.random.PRNGKey(2), (5, 84, 84, 4), 0,
                               256).astype(jnp.uint8)
    words = base.stack_to_words(stack)
    key, eps = jax.random.PRNGKey(3), jnp.float32(0.3)
    if kind == "recurrent":
        state = net.initial_state(5)
        params = net.init(jax.random.PRNGKey(4), state, stack)
        step = make_recurrent_actor_step(net, return_q=True)
        act = lambda obs: step(params, state, obs, key, eps)  # noqa: E731
    else:
        params = net.init(jax.random.PRNGKey(4), stack)
        step = make_actor_step(net, return_q=True)
        act = lambda obs: step(params, obs, key, eps)         # noqa: E731
    want = jax.jit(act)(stack)
    got = jax.jit(lambda w: act(base.split_stack(base.words_split(w),
                                                 (84, 84))))(words)
    jax.tree.map(np.testing.assert_array_equal,
                 jax.tree.map(np.asarray, got),
                 jax.tree.map(np.asarray, want))


# -- (c) the loop: ring, actions, parameters -----------------------------------
def _toy(family, env_name="pixel_catch"):
    if family == "sequence":
        cfg = CONFIGS["r2d2"]
        cfg = dataclasses.replace(
            cfg, env_name=env_name,
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
            cfg, env_name=env_name, train_every=2,
            network=dataclasses.replace(cfg.network, torso="small",
                                        hidden=16, compute_dtype="float32"),
            replay=dataclasses.replace(
                cfg.replay, capacity=1024, min_fill=64,
                flat_storage=family != "tiled",
                prioritized=family == "dedup",
                frame_dedup=family == "dedup"),
            learner=dataclasses.replace(cfg.learner, batch_size=16))
    return dataclasses.replace(
        cfg, actor=dataclasses.replace(cfg.actor, num_envs=8),
        eval_every_steps=0)


def _run(cfg, env, chunks=2, iters=30):
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_fused_train(cfg, env, net)
    run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
    carry = init(np.asarray(jax.random.PRNGKey(3)))
    steps = 0.0
    for _ in range(chunks):
        carry, metrics = run(carry, iters)
        steps += float(metrics["grad_steps_in_chunk"])
    assert steps > 0
    return carry


@pytest.mark.parametrize("family", ["stacked", "tiled", "dedup", "sequence"])
def test_the_loop_on_words_is_the_loop_on_bytes_bit_for_bit(family,
                                                             byte_path):
    cfg = _toy(family)
    env = make_jax_env(cfg.env_name)
    words = _run(cfg, env)
    assert words.obs == ()                       # carried once
    assert env.observe(words.env_state).dtype == jnp.uint32
    byte_path()
    bytes_ = _run(cfg, env)
    assert bytes_.obs == ()
    assert env.observe(bytes_.env_state).shape == (8, 84, 84, 4)
    # the observation itself, then everything else the carry holds: the
    # ring's rows and planes (the actions taken among them), the learner
    np.testing.assert_array_equal(
        np.asarray(base.words_to_stack(env.observe(words.env_state),
                                       (84, 84))),
        np.asarray(env.observe(bytes_.env_state)))
    blank = {env.obs_field: ()}
    got, want = (c._replace(env_state=c.env_state._replace(**blank))
                 for c in (words, bytes_))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert np.asarray(getattr(got.replay, "ring", got.replay).action).any()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- (d) what the words do not cover -------------------------------------------
class _TwoFrames(PixelCatch):
    observation_shape = (84, 84, 2)
    frame_stack = 2


def _set_up(cfg, monkeypatch, env=None):
    if env is not None:
        monkeypatch.setattr(train_mod, "make_jax_env", lambda name: env)
    lines = []
    carry, _ = train(cfg, total_env_steps=20 * 8, chunk_iters=20,
                     log_fn=lines.append)
    rows = [json.loads(s) for s in lines]
    gauge = telemetry.get_registry().gauge("dqn_obs_words", "")
    return carry, [r["obs_words"] for r in rows if "obs_words" in r], \
        gauge.value


def test_four_uint8_frames_engage_the_words(monkeypatch):
    carry, said, gauge = _set_up(_toy("stacked"), monkeypatch)
    assert said == [True] and gauge == 1
    assert carry.obs == ()
    assert carry.env_state.frames.dtype == jnp.uint32


def test_another_depth_keeps_the_bytes(monkeypatch):
    env = _TwoFrames()
    assert not base.held_in_words(env)
    carry, said, gauge = _set_up(_toy("stacked"), monkeypatch, env)
    assert said == [False] and gauge == 0
    assert carry.obs == ()                       # still carried once
    frames = carry.env_state.frames
    assert frames.dtype == jnp.uint8 and frames.shape == (8, 84, 84, 2)
    # the rolling-stack contract holds on the byte path too
    _, last, obs, next_obs, done, _ = _episodes(_TwoFrames(max_steps=5), 9)
    np.testing.assert_array_equal(next_obs[..., :-1], obs[..., 1:])
    assert done.any()


def test_another_dtype_keeps_its_observation(monkeypatch):
    cfg = CONFIGS["cartpole"]
    cfg = dataclasses.replace(
        cfg, network=dataclasses.replace(cfg.network, mlp_features=(16,)),
        replay=dataclasses.replace(cfg.replay, capacity=512, min_fill=64),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        eval_every_steps=0)
    env = make_jax_env("cartpole")
    assert not base.held_in_words(env) and env.obs_field is None
    carry, said, gauge = _set_up(cfg, monkeypatch)
    assert said == [] and gauge == 0             # no merged-row ring: no row
    assert carry.obs.dtype == jnp.float32 and carry.obs.shape == (8, 4)


# -- (e) a checkpoint of the parent's carry ------------------------------------
def test_whole_carry_checkpoint_with_the_twin_observation_restores(tmp_path):
    """A ``--checkpoint-replay`` directory as the program wrote it while the
    carry held ``obs`` beside the env state's own stack, both
    ``u8[B, 84, 84, 4]``: restored, the twin dropped and the stack packed
    to words (train_loop.py twin_obs_checkpoint), the run continues bit-equal
    to the uninterrupted one."""
    cfg = _toy("stacked")
    env = make_jax_env(cfg.env_name)
    kw = dict(chunk_iters=25, log_fn=lambda s: None)
    ref, _ = train(cfg, total_env_steps=600, **kw)
    half, _ = train(cfg, total_env_steps=400, **kw)
    # the fields the carry had then
    Old = collections.namedtuple(
        "TrainCarry", [f for f in half._fields
                       if f not in ("actor_carry", "agent_sums")])
    stack = env.stack_obs(half.env_state.frames)
    old = Old(**dict(
        {f: getattr(half, f) for f in Old._fields}, obs=stack,
        env_state=half.env_state._replace(frames=jnp.copy(stack))))
    d = str(tmp_path / "run")
    ckpt = TrainCheckpointer(d, save_every_frames=100_000)
    record_checkpoint_kind(d, "carry")
    ckpt.save(400, old)
    ckpt.close()
    carry, hist = train(cfg, total_env_steps=600, checkpoint_dir=d,
                        checkpoint_replay=True, **kw)
    assert [row["env_frames"] for row in hist] == [600]   # resumed at 400
    assert carry.obs == () and carry.env_state.frames.dtype == jnp.uint32
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chip_smoke_mesh_leg_reads_the_held_observation():
    """``chip_smoke.leg_mesh`` runs the ``atari`` preset on the chip: its
    per-device check of the acting observation reads the env state where
    the carry holds no twin."""
    import chip_smoke

    out = chip_smoke.leg_mesh(
        chip_smoke.CompileMeter(), config="atari", overrides=(
            "env_name=pixel_catch", "network.torso=small",
            "network.hidden=16", "network.compute_dtype=float32",
            "replay.capacity=1024", "replay.min_fill=64",
            "learner.batch_size=16", "actor.num_envs=8", "train_every=2"),
        chunk_iters=30, chunks=2, num_devices=2)
    assert out["env_frames"] == 480
    assert out["obs_shard_shape"] == [4, 84 * 84]   # 8 lanes of words over 2
