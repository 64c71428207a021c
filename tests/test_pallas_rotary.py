"""The queries' rotary pass into the learner's attention kernels
(``ops/pallas_attention.py`` ``rotary_embed_forward`` / ``_backward``),
interpreted on the CPU, against what it replaces on a TPU:
``sequence_core.rotate``, then ``attend``'s scaling, cast and ``kv_major`` —
to the last bit going in, and against autodiff of the plain route coming back;
at the ``laguna_q`` preset's head size and both of its embeddings (``W``:
rotary over all 128 dims; ``F``: YaRN over 64 of them, ``attention_factor``
above 1, the other 64 passing); that the comparison tells a wrong rotation;
and the start-up gauge.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.models import sequence_core
from dist_dqn_tpu.ops import pallas_attention

CORE = CONFIGS["laguna_q"].network.core
ROPE = {"W": CORE.rope_window, "F": CORE.rope_full}
B, KV, D = 2, 2, CORE.head_dim
SCALE = D ** -0.5

#: case -> (steps T, padded length, positions' start a lane, restart (lane,
#: step) or None): a ragged call is padded to whole blocks of the attention
#: kernels' queries; a reset inside the call restarts the positions.
CASES = {
    "whole_blocks": (64, 64, (512, 40), None),
    "ragged": (37, 48, (0, 3000), None),
    "reset_in_the_call": (40, 40, (17, 600), (0, 9)),
}


def _call(kind, G, case, seed=0):
    """``(x [B, T, KV, G, D], tables, length, a cotangent [B, KV, G, length,
    D])`` of one call."""
    T, length, starts, restart = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(keys[0], (B, T, KV, G, D))
    position = np.asarray(starts)[:, None] + np.arange(T)
    if restart:
        lane, step = restart
        position[lane, step:] = np.arange(T - step)
    tables = sequence_core.rotary_tables(jnp.asarray(position, jnp.int32),
                                        ROPE[kind], D)
    return x, tables, length, jax.random.normal(keys[1],
                                                (B, KV, G, length, D))


def _plain(x, tables, length, dtype, rotate=sequence_core.rotate):
    """``attend``'s ``kv_major(rotate(x) * D ** -0.5)``."""
    T, G = x.shape[1], x.shape[3]
    q = rotate(x.reshape(B, T, KV * G, D), tables).reshape(x.shape) * SCALE
    q = jnp.moveaxis(q.astype(dtype), 1, -2)
    return jnp.pad(q, ((0, 0),) * 3 + ((0, length - T), (0, 0)))


def _pass(x, tables, length, dtype, back=False):
    """The kernel over ``x``: step-major in, KV-head-major out; with ``back``
    the other kernel over a cotangent, by the negative angle."""
    wide, shifts = pallas_attention.wide_tables(tables, D)
    wide = jnp.pad(wide, ((0, 0), (0, 0), (0, length - wide.shape[2]),
                          (0, 0)))
    if back:
        return pallas_attention._embed(
            x, pallas_attention.negative_angle(wide), shifts, dtype,
            interpret=True)
    T = x.shape[1]
    x = jnp.pad(x.reshape(B, T, -1), ((0, 0), (0, length - T), (0, 0)))
    return pallas_attention._embed(x, wide, shifts, dtype, kv=KV,
                                   interpret=True)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind,G", [("W", 8), ("W", 6), ("F", 6), ("F", 8)])
def test_the_forward_pass_is_rotate_scale_cast_and_layout(kind, G, case):
    """Equal to the last bit in float32, and so after the cast; the rows
    behind ``T`` come out zero; in an ``F`` layer the 64 dims past the rotary
    ones are ``x`` times the scale and nothing else."""
    x, tables, length, _ = _call(kind, G, case)
    for dtype in (jnp.float32, jnp.bfloat16):
        got = jax.jit(_pass, static_argnums=(2, 3))(x, tables, length, dtype)
        want = jax.jit(_plain, static_argnums=(2, 3))(x, tables, length,
                                                      dtype)
        assert got.dtype == dtype and got.shape == (B, KV, G, length, D)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    T = x.shape[1]
    assert not np.asarray(got[..., T:, :], np.float32).any()
    if kind == "F":
        passing = jnp.moveaxis(x[..., D // 2:] * SCALE, 1, -2)
        np.testing.assert_array_equal(
            np.asarray(got[..., :T, D // 2:], np.float32),
            np.asarray(passing.astype(jnp.bfloat16), np.float32))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind,G", [("W", 8), ("F", 6)])
def test_the_backward_pass_is_autodiff_of_the_plain_route(kind, G, case):
    """The cotangent comes float32 from the attention backward and is rounded
    to the queries' type in the kernel, where ``_attend_bwd`` rounds it; then
    the scale and the rotation by the negative angle, in float32."""
    x, tables, length, pull = _call(kind, G, case)
    T = x.shape[1]
    for dtype in (jnp.float32, jnp.bfloat16):
        want = jax.jit(lambda x, pull: jax.vjp(
            lambda x: _plain(x, tables, length, dtype), x)[1](
                pull.astype(dtype))[0])(x, pull)
        got = jax.jit(lambda pull: _pass(pull, tables, length, dtype,
                                         back=True))(pull)
        np.testing.assert_allclose(
            got[:, :T].reshape(x.shape), want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kind", ["W", "F"])
def test_the_negated_tables_undo_the_rotation(kind):
    """There with the tables and back with their rolled planes negated is
    ``x`` times ``D ** -1`` (the scale, twice) times ``attention_factor``
    squared on the rotary dims — a rotation's transpose is its inverse."""
    x, tables, length, _ = _call(kind, 6, "reset_in_the_call")
    there = _pass(x, tables, length, jnp.float32)
    back = _pass(there, tables, length, jnp.float32, back=True)
    factor = np.full(D, 1.0)
    factor[:int(D * ROPE[kind].rotary_factor)] = (
        ROPE[kind].attention_factor ** 2)
    np.testing.assert_allclose(back.reshape(x.shape), x * SCALE ** 2 * factor,
                               rtol=1e-5, atol=1e-6)


def _halves_swapped_without_the_sign(x, tables):
    cos, sin = tables
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos + x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _passing_dims_rotated(x, tables):
    """The embedding over ALL of the head's dims with the first frequencies
    again for the dims that should pass."""
    wide = tuple(jnp.concatenate([t, t], axis=-1) for t in tables)
    return sequence_core.rotate(x, wide)


@pytest.mark.parametrize("kind,wrong", [
    ("W", _halves_swapped_without_the_sign),
    ("F", _halves_swapped_without_the_sign), ("F", _passing_dims_rotated)])
def test_a_wrong_rotation_fails_the_comparison(kind, wrong):
    x, tables, length, _ = _call(kind, 6, "whole_blocks")
    got = _pass(x, tables, length, jnp.float32)
    np.testing.assert_allclose(
        got, _plain(x, tables, length, jnp.float32), rtol=1e-6, atol=1e-7)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(
            got, _plain(x, tables, length, jnp.float32, rotate=wrong),
            rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("kind,G", [("W", 8), ("F", 6)])
def test_attend_takes_the_queries_before_their_embedding(kind, G):
    """``attend(.., rotary=tables)`` of the projection's output is ``attend``
    of the rotated queries: output and the gradients to ``x``, keys and
    values, through both rotary kernels and both attention kernels (ragged,
    a restart in the call)."""
    history, T = 16, 37
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(keys[0], (B, T, KV, G, D))
    k, v = (jax.random.normal(key, (B, history + T, KV, D))
            for key in keys[1:3])
    pull = jax.random.normal(keys[3], x.shape)
    seg = jnp.asarray(np.arange(T) >= np.asarray([[20], [T]]), jnp.int32)
    position = jnp.where(seg == 0, history + jnp.arange(T),
                         jnp.arange(T) - 20)
    k_position = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(history), (B, history)), position], 1)
    k_seg = jnp.concatenate([jnp.zeros((B, history), jnp.int32), seg], 1)
    tables = sequence_core.rotary_tables(position, ROPE[kind], D)

    def attended(q, k, v, **more):
        return pallas_attention.attend(
            q, k, v, position, seg, k_position, k_seg, history=history,
            window=16 if kind == "W" else None, dtype=jnp.float32,
            interpret=True, tiles=pallas_attention.Tiles(8, 16), **more)

    def plain(x, k, v):
        q = sequence_core.rotate(x.reshape(B, T, KV * G, D), tables)
        return attended(q.reshape(x.shape), k, v)

    def fused(x, k, v):
        return attended(x, k, v, rotary=tables)

    got, want = (jax.jit(jax.value_and_grad(
        lambda *a: (lambda o: (jnp.sum(o * pull), o))(f(*a)),
        argnums=(0, 1, 2), has_aux=True))(x, k, v) for f in (fused, plain))
    np.testing.assert_array_equal(got[0][1], want[0][1])
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_the_start_up_gauge_counts_the_query_head_rows(monkeypatch):
    """``HybridQNetwork.rotary_head_rows`` — what ``train.train`` sets
    ``dqn_learner_rotary_head_rows`` from — for the ``laguna_q`` preset's
    batch (4 windows, 512 burn-in + 1,536): windows x steps x query heads,
    both calls, over the three ``W`` layers (64 heads) and the two ``F``
    layers (48); nothing off a TPU, where ``rotate`` runs."""
    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["laguna_q"]
    net = build_network(cfg.network, 6)
    shape = (cfg.learner.batch_size, cfg.replay.burn_in,
             cfg.replay.unroll_length + cfg.learner.n_step)
    assert net.rotary_head_rows(*shape) == {}
    monkeypatch.setattr(loop_common, "pallas_routing",
                        lambda enabled: (enabled, False))
    assert net.rotary_head_rows(*shape) == {
        "window": 3 * 4 * (512 + 1536) * 64, "full": 2 * 4 * (512 + 1536) * 48}
