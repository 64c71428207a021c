"""The device rings' scalar-per-step planes — action, reward, terminated,
truncated, the prioritized ring's priorities — are flat ``[T * B]`` cells in
the ``t * B + b`` order (replay/device.py). Held here, for the uniform, the
prioritized and the sequence ring at ``B`` in {3, 16, 64} on rings that have
wrapped, to a plain numpy ``[T, B]`` ring: (a) the planes cell for cell
after adds, draws and write-backs; (b) a key's draw, weights, returns,
discounts and rebuilt stacks, with the XLA sampler and the Pallas
interpreter; (c) the two write-backs on duplicate cells; (d) a census of
the chunk program for whole-plane reshapes and copies — on the CPU and
under the v5e compiler; and a whole-carry checkpoint from before the planes
were flat is refused by shape, in words."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu import loop_common
from dist_dqn_tpu.ops.pallas_sampler import (importance_weights,
                                             pallas_stratified_sample)
from dist_dqn_tpu.replay import device as ring
from dist_dqn_tpu.replay import prioritized_device as pring
from dist_dqn_tpu.replay import sequence_device as sring

H = W = 3          # frame
S = 4              # stack depth
T = 12             # ring slots
STEPS = 29         # written: wraps twice, ends off a slot boundary
N_STEP, GAMMA, ALPHA, BETA, EPS = 3, 0.97, 0.6, 0.4, 1e-3
SEQ_L, STRIDE, LSTM = 5, 2, 4
DRAWS = 16
LANES = [3, 16, 64]
KINDS = ["uniform", "prioritized", "sequence"]
FIELDS = ("action", "reward", "terminated", "truncated")


def _stream(lanes):
    """A seeded rolling-stack stream (envs/base.py ``frame_stack``): obs
    [STEPS, B, H, W, S] u8, and per-step fields. ``action`` names its own
    (step, lane), so a drawn transition says where it came from."""
    rng = np.random.default_rng(100 + lanes)
    frames = rng.integers(0, 255, (STEPS + 1, lanes, H, W), dtype=np.uint8)
    done = rng.random((STEPS, lanes)) < 0.2
    term = np.logical_and(done, rng.random((STEPS, lanes)) < 0.5)
    obs = np.zeros((STEPS, lanes, H, W, S), np.uint8)
    cur = np.repeat(frames[0][..., None], S, axis=-1)
    for t in range(STEPS):
        obs[t] = cur
        rolled = np.concatenate([cur[..., 1:], frames[t + 1][..., None]], -1)
        tiled = np.repeat(frames[t + 1][..., None], S, axis=-1)
        cur = np.where(done[t][:, None, None, None], tiled, rolled)
    return dict(
        obs=obs, terminated=term, truncated=np.logical_and(done, ~term),
        action=np.arange(STEPS * lanes, dtype=np.int32).reshape(STEPS, lanes),
        reward=rng.normal(size=(STEPS, lanes)).astype(np.float32),
        state=rng.normal(size=(2, STEPS, lanes, LSTM)).astype(np.float32))


class PlainRing:
    """The ``[T, B]`` reference: numpy planes written a row at a time."""

    def __init__(self, kind, lanes):
        self.kind, self.lanes = kind, lanes
        self.planes = {"action": np.zeros((T, lanes), np.int32),
                       "reward": np.zeros((T, lanes), np.float32),
                       "terminated": np.zeros((T, lanes), bool),
                       "truncated": np.zeros((T, lanes), bool)}
        self.step_of = np.full((T,), -1)      # absolute step a slot holds
        self.priorities = np.zeros((T, lanes), np.float32)
        self.largest = np.float32(1.0)
        self.pos = self.size = self.writes = 0

    def add(self, step, stream):
        p = self.pos
        for name in FIELDS:
            self.planes[name][p] = stream[name][step]
        self.step_of[p] = step
        if self.kind == "prioritized":
            self.priorities[p] = self.largest
        elif self.kind == "sequence":
            self.writes += 1
            self.priorities[p] = 0.0
            start = self.writes - SEQ_L
            if start >= 0 and start % STRIDE == 0:
                self.priorities[(p - (SEQ_L - 1)) % T] = self.largest
        self.pos, self.size = (p + 1) % T, min(self.size + 1, T)

    def start_mask(self):
        """[T] bool: slots a draw may return."""
        offset = (np.arange(T) - (self.pos - self.size)) % T
        context = np.logical_and(offset >= S - 1, offset < self.size)
        if self.kind == "sequence":
            return context
        return np.logical_and(context, offset < self.size - N_STEP)

    def update(self, t_idx, b_idx, new):
        for t, b, p in zip(t_idx, b_idx, np.abs(new) + np.float32(EPS)):
            if self.kind == "sequence" and not self.priorities[t, b] > 0:
                p = np.float32(0.0)
            self.priorities[t, b] = p
            self.largest = max(self.largest, p)


def _new_priorities(t_idx, b_idx, lanes, round_):
    """A write-back's values, a function of the cell: a cell drawn twice
    is written the same value twice, whatever order a scatter takes."""
    cell = np.asarray(t_idx) * lanes + np.asarray(b_idx)
    return (0.25 + ((cell * 7 + round_) % 11) / 3.0).astype(np.float32)


def _device_init(kind, lanes):
    example = jnp.zeros((H * W,), jnp.uint8)        # dedup, merged rows
    if kind == "uniform":
        return ring.time_ring_init(T, lanes, example, merge_obs_rows=True)
    if kind == "prioritized":
        return pring.prioritized_ring_init(T, lanes, example,
                                           merge_obs_rows=True)
    return sring.sequence_ring_init(T, lanes, example, LSTM,
                                    merge_obs_rows=True)


def _device_add(kind, state, step, stream):
    args = (jnp.asarray(stream["obs"][step][..., -1].reshape(-1, H * W)),
            *(jnp.asarray(stream[name][step]) for name in FIELDS))
    if kind == "uniform":
        return ring.time_ring_add(state, *args, merge_obs_rows=True)
    if kind == "prioritized":
        return pring.prioritized_ring_add(state, *args, merge_obs_rows=True)
    return sring.sequence_ring_add(
        state, *args, tuple(jnp.asarray(stream["state"][:, step])), SEQ_L,
        STRIDE, merge_obs_rows=True)


LAYOUT = dict(merge_obs_rows=True, frame_stack=S, frame_shape=(H, W, 1))


def _device_sample(kind, state, key, lanes, pallas=False):
    kw = dict(use_pallas=pallas, pallas_interpret=pallas, **LAYOUT)
    if kind == "prioritized":
        return pring.prioritized_ring_sample(
            state, key, DRAWS, N_STEP, GAMMA, ALPHA, jnp.float32(BETA),
            lanes, **kw)
    return sring.sequence_ring_sample(state, key, DRAWS, SEQ_L, ALPHA,
                                      jnp.float32(BETA), **kw)


def _device_update(kind, state, t_idx, b_idx, new, lanes):
    if kind == "prioritized":
        return pring.prioritized_ring_update(state, t_idx, b_idx, new,
                                             lanes, eps=EPS)
    return sring.sequence_ring_update(state, t_idx, b_idx, new, eps=EPS)


@functools.lru_cache(maxsize=None)
def _driven(kind, lanes):
    """(stream, the program's ring, the plain ring) after the same seeded
    adds, and for the prioritized rings draws and write-backs in between
    and after."""
    stream, state, plain = _stream(lanes), _device_init(kind, lanes), \
        PlainRing(kind, lanes)
    for step in range(STEPS):
        state = _device_add(kind, state, step, stream)
        plain.add(step, stream)
        if kind != "uniform" and step >= T and step % 5 == 0:
            s = _device_sample(kind, state, jax.random.PRNGKey(step), lanes)
            new = _new_priorities(s.t_idx, s.b_idx, lanes, step)
            state = _device_update(kind, state, s.t_idx, s.b_idx,
                                   jnp.asarray(new), lanes)
            plain.update(np.asarray(s.t_idx), np.asarray(s.b_idx), new)
    return stream, state, plain


# -- (a) the planes, cell for cell --------------------------------------------
@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("kind", KINDS)
def test_flat_planes_are_the_plain_ring_cell_for_cell(kind, lanes):
    _, state, plain = _driven(kind, lanes)
    inner = getattr(state, "ring", state)
    assert int(inner.pos) == plain.pos and int(inner.size) == T
    for name in FIELDS:
        got = np.asarray(getattr(inner, name))
        assert got.shape == (T * lanes,) and got.dtype == \
            plain.planes[name].dtype
        np.testing.assert_array_equal(got, plain.planes[name].reshape(-1))
    if kind == "prioritized":
        assert state.priorities.shape == (T * lanes,)
        np.testing.assert_array_equal(np.asarray(state.priorities),
                                      plain.priorities.reshape(-1))
    elif kind == "sequence":
        # the one plane still [T, B]: the benchmark's ring check reads it so
        np.testing.assert_array_equal(np.asarray(state.priorities),
                                      plain.priorities)
    if kind != "uniform":
        assert float(state.max_priority) == plain.largest


# -- (b) a key's draw and what it gathers -------------------------------------
def _tb_draw(plain, key, pallas):
    """The draw as it was made over a ``[T, B]`` plane: mask a row at a
    time, flatten, inverse CDF (the kernel through the same flattening)."""
    pri = jnp.asarray(plain.priorities)
    mask = jnp.asarray(plain.start_mask())
    w = jnp.where(mask[:, None], pri ** ALPHA, 0.0)                # [T, B]
    if plain.kind == "sequence":
        w = jnp.where(pri > 0.0, w, 0.0)
        n_valid = jnp.sum((w > 0.0).astype(jnp.float32))
    else:
        n_valid = jnp.sum(mask.astype(jnp.float32)) * plain.lanes
    u = (jnp.arange(DRAWS, dtype=jnp.float32)
         + jax.random.uniform(key, (DRAWS,))) / DRAWS
    flat = w.reshape(-1)
    if pallas:
        t, b, mass, total = pallas_stratified_sample(flat, u, plain.lanes,
                                                     interpret=True)
    else:
        cdf = jnp.cumsum(flat)
        total = cdf[-1]
        idx = jnp.clip(jnp.searchsorted(cdf, u * total), 0, flat.size - 1)
        t, b, mass = idx // plain.lanes, idx % plain.lanes, flat[idx]
    np.testing.assert_array_equal(np.asarray(mass),
                                  np.asarray(w)[np.asarray(t), np.asarray(b)])
    assert (np.asarray(mass) > 0).all()
    return (np.asarray(t), np.asarray(b),
            np.asarray(importance_weights(mass, total, n_valid,
                                          jnp.float32(BETA))))


def _plain_transitions(plain, stream, t_idx, b_idx):
    """n-step returns, discounts, actions and both stacks at those cells,
    from the ``[T, B]`` planes and the stream's own stacks."""
    tt = (t_idx[:, None] + np.arange(N_STEP)[None, :]) % T
    lane = b_idx[:, None]
    returns, discount, kstar = ring.compute_n_step(
        *(jnp.asarray(plain.planes[name][tt, lane])
          for name in ("reward", "terminated", "truncated")), GAMMA)
    kstar = np.asarray(kstar)
    trunc_at_k = plain.planes["truncated"][(t_idx + kstar) % T, b_idx]
    discount = np.asarray(discount) * (1.0 - trunc_at_k.astype(np.float32))
    step = plain.step_of[t_idx]
    return dict(reward=np.asarray(returns), discount=discount,
                action=plain.planes["action"][t_idx, b_idx],
                obs=stream["obs"][step, b_idx],
                next_obs=stream["obs"][step + kstar + 1, b_idx])


@pytest.mark.parametrize("sampler", ["xla", "pallas"])
@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("kind", KINDS)
def test_a_key_draws_what_it_drew_over_the_tb_planes(kind, lanes, sampler):
    stream, state, plain = _driven(kind, lanes)
    key = jax.random.PRNGKey(1000 + lanes)
    pallas = sampler == "pallas"
    if kind == "uniform":
        # the uniform ring has one sampler: its draw is two randints
        got = ring.time_ring_sample(state, key, DRAWS, N_STEP, GAMMA, lanes,
                                    **LAYOUT)
        k_t, k_b = jax.random.split(key)
        u = jax.random.randint(k_t, (DRAWS,), 0, T - N_STEP - (S - 1))
        t_idx = np.asarray((plain.pos - T + S - 1 + u) % T)
        b_idx = np.asarray(jax.random.randint(k_b, (DRAWS,), 0, lanes))
    else:
        s = _device_sample(kind, state, key, lanes, pallas=pallas)
        t_idx, b_idx, weights = _tb_draw(plain, key, pallas)
        np.testing.assert_array_equal(np.asarray(s.t_idx), t_idx)
        np.testing.assert_array_equal(np.asarray(s.b_idx), b_idx)
        np.testing.assert_array_equal(np.asarray(s.weights), weights)
        assert plain.start_mask()[t_idx].all()
        got = s.batch if kind == "prioritized" else s
    if kind != "sequence":
        want = _plain_transitions(plain, stream, t_idx, b_idx)
        for name, value in want.items():
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          value, err_msg=name)
        return
    # windows, time-major [L, draws]: every field by absolute step
    steps = plain.step_of[t_idx][None, :] + np.arange(SEQ_L)[:, None]
    lane = b_idx[None, :]
    done = np.logical_or(stream["terminated"], stream["truncated"])
    np.testing.assert_array_equal(np.asarray(got.obs),
                                  stream["obs"][steps, lane])
    np.testing.assert_array_equal(np.asarray(got.action),
                                  stream["action"][steps, lane])
    np.testing.assert_array_equal(np.asarray(got.reward),
                                  stream["reward"][steps, lane])
    np.testing.assert_array_equal(np.asarray(got.done), done[steps, lane])
    np.testing.assert_array_equal(np.asarray(got.reset)[1:],
                                  done[steps, lane][:-1])
    for plane, drawn in zip(stream["state"], got.start_state):
        np.testing.assert_array_equal(np.asarray(drawn),
                                      plane[steps[0], b_idx])


# -- (c) the two write-backs --------------------------------------------------
@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("lanes", LANES)
def test_both_write_backs_agree_where_cells_repeat(lanes, jit):
    """Four sub-steps' write-backs, a third of the cells drawn again by a
    later sub-step: one flush (``_update_batched``) leaves what the
    sub-steps' own write-backs leave one after the other — the last
    writer's value — and what a plain loop over a ``[T, B]`` plane leaves.
    Inside a sub-step a repeated cell carries one value, as a draw's does."""
    _, state, plain = _driven("prioritized", lanes)
    rng = np.random.default_rng(lanes)
    cells = rng.integers(0, T * lanes, (4, DRAWS))
    cells[1:, :DRAWS // 3] = cells[0, :DRAWS // 3]     # across sub-steps
    cells[:, -1] = cells[:, -2]                        # inside each
    new = rng.gamma(2.0, 0.5, cells.shape).astype(np.float32)
    new[:, -1] = new[:, -2]
    t_idx, b_idx = (jnp.asarray((cells // lanes).astype(np.int32)),
                    jnp.asarray((cells % lanes).astype(np.int32)))

    one, flush = (functools.partial(f, num_envs=lanes, eps=EPS) for f in (
        pring.prioritized_ring_update, pring.prioritized_ring_update_batched))
    if jit:
        one, flush = jax.jit(one), jax.jit(flush)
    serial = state
    for k in range(len(cells)):
        serial = one(serial, t_idx[k], b_idx[k], jnp.asarray(new[k]))
    flushed = flush(state, t_idx, b_idx, jnp.asarray(new))

    want = plain.priorities.copy().reshape(-1)
    for k in range(len(cells)):
        want[cells[k]] = new[k] + np.float32(EPS)
    np.testing.assert_array_equal(np.asarray(serial.priorities), want)
    np.testing.assert_array_equal(np.asarray(flushed.priorities), want)
    assert float(serial.max_priority) == float(flushed.max_priority) == \
        max(plain.largest, (new + np.float32(EPS)).max())


# -- (d) the chunk program holds no whole-plane reshape or copy ---------------
def _toy(kind):
    """A toy chunk program with a merged-row dedup ring of 128 slots x 8
    lanes: planes of 1,024 cells."""
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    if kind == "sequence":
        cfg = CONFIGS["r2d2"]
        cfg = dataclasses.replace(
            cfg, env_name="pixel_catch",
            network=dataclasses.replace(
                cfg.network, torso="small", hidden=16, lstm_size=8,
                compute_dtype="float32", lstm_dtype="float32"),
            replay=dataclasses.replace(
                cfg.replay, capacity=1024, min_fill=64, burn_in=2,
                unroll_length=4, sequence_stride=2, frame_dedup=True),
            learner=dataclasses.replace(cfg.learner, n_step=2, batch_size=8))
    else:
        cfg = CONFIGS["atari"]
        cfg = dataclasses.replace(
            cfg, env_name="pixel_catch", train_every=2,
            network=dataclasses.replace(cfg.network, torso="small",
                                        hidden=16, compute_dtype="float32"),
            replay=dataclasses.replace(
                cfg.replay, capacity=1024, min_fill=64, flat_storage=True,
                frame_dedup=True, prioritized=kind != "uniform",
                pallas_sampler=kind == "kernel"),
            learner=dataclasses.replace(cfg.learner, batch_size=16))
    cfg = dataclasses.replace(
        cfg, actor=dataclasses.replace(cfg.actor, num_envs=8))
    env = make_jax_env(cfg.env_name)
    return cfg, env, build_network(cfg.network, env.num_actions)


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit, cond, scan) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


PLANE_CELLS = 1024
RELAYOUTS = ("reshape", "transpose", "copy", "copy_p", "squeeze",
             "expand_dims")


@pytest.mark.parametrize("kind,allowed", [
    ("uniform", 0), ("prioritized", 0), ("kernel", 0),
    # the sequence ring's [T, B] priorities, flattened for the sampler
    ("sequence", 1)])
def test_the_chunk_program_reshapes_no_whole_plane(kind, allowed,
                                                   monkeypatch):
    """Traced on the CPU: no ``reshape`` / ``transpose`` / ``copy`` reads or
    makes an array of exactly the plane's cells anywhere in the chunk
    program — the planes are written, gathered, scattered and drawn from in
    the shape they are stored in. (The kernel's own view is a ``pad`` of
    the masked masses to whole ``[rows, 512]`` chunks: one pass, larger
    than the plane.)"""
    from dist_dqn_tpu.train_loop import make_fused_train

    if kind == "kernel":
        monkeypatch.setenv("DIST_DQN_PALLAS_INTERPRET", "1")
    cfg, env, net = _toy(kind)
    init, run_chunk = make_fused_train(cfg, env, net)
    carry = jax.eval_shape(init, jax.random.PRNGKey(0))
    inner = getattr(carry.replay, "ring", carry.replay)
    assert {getattr(inner, name).shape for name in FIELDS} == \
        {(PLANE_CELLS,)}
    jaxpr = jax.make_jaxpr(lambda c: run_chunk(c, 10))(carry)
    found = [
        (e.primitive.name, [v.aval.shape for v in e.invars + e.outvars
                            if hasattr(v.aval, "shape")])
        for e in _eqns(jaxpr.jaxpr) if e.primitive.name in RELAYOUTS
        and any(getattr(v.aval, "size", 0) == PLANE_CELLS
                for v in e.invars + e.outvars)]
    assert len(found) == allowed, found


@pytest.fixture(scope="module")
def v5e():
    """One described (not attached) v5e chip: the TPU compiler is installed
    here and compiles for it. Instruction text, never a time."""
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def test_v5e_the_chunk_program_copies_no_plane(v5e, monkeypatch):
    """Under the TPU's compiler, the prioritized toy program with the Mosaic
    kernel routed in: no ``copy`` whose result is a plane, and no array of
    the plane's ``[slots, lanes]`` twin anywhere in the compiled text."""
    from jax.sharding import SingleDeviceSharding

    from dist_dqn_tpu.train_loop import make_fused_train

    monkeypatch.setattr(loop_common, "pallas_routing",
                        lambda enabled: (enabled, False))
    cfg, env, net = _toy("kernel")
    one = SingleDeviceSharding(v5e)
    init, run_chunk = make_fused_train(cfg, env, net)
    carry = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(init, jax.ShapeDtypeStruct((2,), np.uint32,
                                                  sharding=one)))
    text = jax.jit(run_chunk, static_argnums=1, donate_argnums=0).lower(
        carry, 20).compile().as_text()
    assert "per_stratified_sample" in text
    assert not re.findall(r"= (?:f32|s32|pred)\[1024\]\{[^}]*\} copy\(", text)
    assert not re.findall(r"(?:f32|s32|pred)\[128,8\]", text)


# -- a checkpoint from before the planes were flat ----------------------------
def test_a_tb_plane_checkpoint_is_refused_by_shape_in_words(tmp_path):
    from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer

    def tree(shape):
        return {"replay": {"reward": jnp.zeros(shape, jnp.float32),
                           "pos": jnp.int32(3)},
                "iteration": jnp.int32(40)}

    ckpt = TrainCheckpointer(str(tmp_path), save_every_frames=1)
    ckpt.save(320, tree((T, 16)))
    ckpt.wait()
    with pytest.raises(ValueError, match=r"per-step planes flat.*"
                       r"cannot be resumed(?s:.*)stored shape: \(12, 16\)"):
        ckpt.restore_latest(tree((T * 16,)))
    # the same tree in the shapes it was saved in restores
    frames, restored = ckpt.restore_latest(tree((T, 16)))
    assert frames == 320 and restored["replay"]["reward"].shape == (T, 16)
    ckpt.close()
