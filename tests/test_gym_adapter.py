"""Host-env adapter tests (CartPole via gymnasium; Atari pipeline pieces)."""
import numpy as np
import pytest

from dist_dqn_tpu.envs.gym_adapter import (
    AtariPreprocessing, HostVectorEnv, _area_resize_84, _to_gray,
    is_pixel_env, make_host_env)


def test_area_resize_shapes_and_range():
    frame = np.random.default_rng(0).integers(
        0, 256, size=(210, 160), dtype=np.uint8)
    out = _area_resize_84(frame)
    assert out.shape == (84, 84)
    assert out.dtype == np.uint8
    # Constant image stays constant under resize.
    flat = _area_resize_84(np.full((210, 160), 77, np.uint8))
    assert int(flat.min()) >= 76 and int(flat.max()) <= 78


def test_to_gray_weights():
    rgb = np.zeros((4, 4, 3), np.uint8)
    rgb[..., 1] = 255
    assert abs(int(_to_gray(rgb)[0, 0]) - int(0.587 * 255)) <= 1


def test_host_vector_env_cartpole_contract():
    pytest.importorskip("gymnasium")
    env = make_host_env("CartPole-v1", num_envs=3, seed=0)
    obs = env.reset()
    assert obs.shape == (3, 4)
    for _ in range(250):  # long enough to hit an auto-reset
        obs, next_obs, r, term, trunc = env.step(np.ones(3, np.int64))
    assert obs.shape == (3, 4) and next_obs.shape == (3, 4)
    assert r.dtype == np.float32
    # Post-reset obs differs from pre-reset next_obs on done steps.
    # (CartPole always terminates well before 250 steps of constant action.)


class _FakeAtari:
    """Minimal gymnasium-like env emitting RGB frames."""

    def __init__(self):
        self.t = 0

    class _Space:
        n = 6

    action_space = _Space()

    def reset(self, seed=None):
        self.t = 0
        return np.full((210, 160, 3), 10, np.uint8), {}

    def step(self, action):
        self.t += 1
        frame = np.full((210, 160, 3), min(10 * self.t, 255), np.uint8)
        return frame, 3.0, self.t >= 9, False, {}


def test_host_pong_contract_and_episode():
    """The numpy PixelPong twin honors the Atari-shaped contract: 84x84x4
    uint8 stacks, +-1 rewards, first-to-5 termination, step-cap truncation."""
    from dist_dqn_tpu.envs.gym_adapter import make_host_env
    from dist_dqn_tpu.envs.host_pong import HostPixelPong

    env = HostPixelPong()
    obs = env.reset(seed=0)
    assert obs.shape == (84, 84, 4) and obs.dtype == np.uint8
    assert env.num_actions == 6
    rewards, terms = [], []
    for t in range(6000):
        obs, r, term, trunc = env.step(t % 6)
        rewards.append(r)
        assert obs.shape == (84, 84, 4)
        # The new frame entered the back of the stack, ball/paddles lit.
        assert obs[:, :, -1].max() == 255
        if term or trunc:
            terms.append((term, trunc))
            break
    assert set(np.unique(rewards)) <= {-1.0, 0.0, 1.0}
    assert sum(abs(r) for r in rewards) >= 5  # points were scored
    assert terms, "episode never ended"

    # Vector adapter: the "pong" name wires through make_host_env.
    v = make_host_env("pong", 2, seed=1)
    assert v.num_actions == 6
    obs = v.reset()
    assert obs.shape == (2, 84, 84, 4) and obs.dtype == np.uint8
    obs, nxt, r, te, tr = v.step(np.array([2, 3]))
    assert obs.shape == nxt.shape == (2, 84, 84, 4)


def test_host_pong_matches_jax_pixel_pong_shapes():
    """Both Pong implementations expose identical action/observation specs
    so the fused and apex runtimes train interchangeable networks."""
    from dist_dqn_tpu.envs.host_pong import HostPixelPong
    from dist_dqn_tpu.envs.pixel_pong import PixelPong

    assert HostPixelPong.num_actions == PixelPong.num_actions
    assert HostPixelPong().reset(0).shape == PixelPong.observation_shape


def test_host_pong_step_parity_with_jax_twin():
    """Inject identical state into both Pong implementations and compare
    one deterministic step — guards the hand-duplicated physics constants
    against one-sided edits (no scoring, so no RNG enters)."""
    import jax
    import jax.numpy as jnp

    from dist_dqn_tpu.envs import pixel_pong
    from dist_dqn_tpu.envs.host_pong import HostPixelPong

    jenv = pixel_pong.PixelPong()
    henv = HostPixelPong()
    cases = [
        # (ball xyvxvy, pad_y, opp_y, action): free flight, wall bounce,
        # and an agent-paddle hit with spin.
        ((40.0, 40.0, 1.6, 0.7), 40.0, 40.0, 2),
        ((40.0, 2.0, 1.6, -1.0), 60.0, 30.0, 3),
        ((77.0, 50.0, 1.6, 0.5), 50.0, 40.0, 0),
    ]
    for ball, pad_y, opp_y, action in cases:
        henv.reset(seed=0)
        henv._ball = np.array(ball, np.float32)
        henv._pad_y, henv._opp_y = pad_y, opp_y
        jstate = pixel_pong.PixelPongState(
            ball=jnp.asarray(ball, jnp.float32), pad_y=jnp.float32(pad_y),
            opp_y=jnp.float32(opp_y), score=jnp.zeros((2,), jnp.int32),
            t=jnp.int32(0),
            frames=jenv.stack_held(jnp.zeros((84, 84, 4), jnp.uint8)),
            rng=jax.random.PRNGKey(0))
        jnew, jobs, jr, jterm, jtrunc = jenv.env_step(jstate,
                                                      jnp.int32(action))
        hobs, hr, hterm, htrunc = henv.step(action)
        np.testing.assert_allclose(np.asarray(jnew.ball), henv._ball,
                                   rtol=1e-5, err_msg=str(ball))
        np.testing.assert_allclose(float(jnew.pad_y), henv._pad_y,
                                   rtol=1e-6)
        np.testing.assert_allclose(float(jnew.opp_y), henv._opp_y,
                                   rtol=1e-6)
        assert float(jr) == hr and bool(jterm) == hterm
        # Rendering parity: the freshly rasterized frame is identical.
        np.testing.assert_array_equal(np.asarray(jobs[:, :, -1]),
                                      hobs[:, :, -1])


def test_atari_preprocessing_stack_skip_clip():
    env = AtariPreprocessing(_FakeAtari(), frame_skip=4, stack=4)
    obs = env.reset()
    assert obs.shape == (84, 84, 4)
    assert (obs[..., 0] == obs[..., 3]).all()  # reset tiles the first frame
    obs, r, term, trunc = env.step(0)
    assert r == 1.0                      # 4 * 3.0 clipped to 1.0
    assert not term
    # Frame-skip: 4 inner steps happened; stack shifted by one.
    obs2, r2, term2, _ = env.step(0)
    obs3, r3, term3, _ = env.step(0)     # inner t reaches 9 -> terminates
    assert term3
    assert env.num_actions == 6


def test_host_vector_env_autoreset_next_obs():
    env = HostVectorEnv(lambda: AtariPreprocessing(_FakeAtari()), 2)
    env.reset()
    done_seen = False
    for _ in range(5):
        obs, next_obs, r, term, trunc = env.step(np.zeros(2, np.int64))
        if term.any():
            done_seen = True
            # obs was auto-reset; next_obs is the pre-reset frame.
            assert not np.array_equal(obs[0], next_obs[0])
    assert done_seen


def test_host_breakout_contract_and_parity_with_jax_twin():
    """The Breakout numpy twin (envs/host_breakout.py): interface
    contract through make_host_env, fire-to-serve/lives semantics, and
    injected-state step parity with the JAX env — same guard as the
    Pong twin against one-sided physics edits."""
    import jax
    import jax.numpy as jnp

    from dist_dqn_tpu.envs import pixel_breakout
    from dist_dqn_tpu.envs.host_breakout import HostPixelBreakout
    from dist_dqn_tpu.envs.pixel_breakout import PixelBreakout

    assert HostPixelBreakout.num_actions == PixelBreakout.num_actions
    assert HostPixelBreakout().reset(0).shape == \
        PixelBreakout.observation_shape

    # Vector adapter wiring + pixel-env classification.
    v = make_host_env("breakout", 2, seed=1)
    obs = v.reset()
    assert obs.shape == (2, 84, 84, 4) and obs.dtype == np.uint8
    assert is_pixel_env("breakout")

    # NOOP never serves; FIRE does.
    henv = HostPixelBreakout()
    henv.reset(seed=0)
    for _ in range(5):
        _, r, term, _ = henv.step(0)
        assert r == 0.0 and not term and not henv._in_play
    henv.step(1)
    assert henv._in_play

    # Injected-state parity: free flight, a brick hit (reward + brick
    # removed + bounce), a paddle hit with spin, and a lost ball (life).
    jenv = pixel_breakout.PixelBreakout()
    cases = [
        # (ball xyvxvy, pad_x, action)
        ((40.0, 50.0, 1.0, -2.0), 40.0, 0),   # free flight upward
        ((40.0, 37.0, 0.0, -2.0), 40.0, 0),   # into the brick wall
        ((42.0, 76.5, 1.0, 2.0), 40.0, 2),    # paddle hit, off-center
        ((70.0, 81.5, 0.0, 2.0), 20.0, 0),    # past the paddle: life lost
    ]
    for ball, pad_x, action in cases:
        henv.reset(seed=0)
        henv._in_play = True
        henv._ball = np.array(ball, np.float32)
        henv._pad_x = pad_x
        jstate, _ = jenv.reset(jax.random.PRNGKey(0))
        jstate = jstate._replace(
            ball=jnp.asarray(ball, jnp.float32),
            pad_x=jnp.float32(pad_x), in_play=jnp.bool_(True))
        jnew, jobs, jr, jterm, _ = jenv.env_step(jstate, jnp.int32(action))
        hobs, hr, hterm, _ = henv.step(action)
        np.testing.assert_allclose(np.asarray(jnew.ball), henv._ball,
                                   rtol=1e-5, err_msg=str(ball))
        np.testing.assert_allclose(float(jnew.pad_x), henv._pad_x,
                                   rtol=1e-6)
        assert float(jr) == hr and bool(jterm) == hterm, ball
        assert int(jnew.lives) == henv._lives, ball
        assert bool(jnew.in_play) == henv._in_play, ball
        np.testing.assert_array_equal(np.asarray(jnew.bricks),
                                      henv._bricks, err_msg=str(ball))
        np.testing.assert_array_equal(np.asarray(jobs[:, :, -1]),
                                      hobs[:, :, -1])
