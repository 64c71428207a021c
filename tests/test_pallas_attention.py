"""The learner's fused attention kernels (``ops/pallas_attention.py``),
interpreted on the CPU at toy sizes, against the path they replace on a TPU:
``_RotaryAttention.blockwise`` over a masked softmax — outputs and the
gradients to queries, keys and values, for both kinds of layer, both group
sizes of the ``laguna_q`` preset and every shape a ring and a window's resets
can give the mask; both of them against one masked softmax over ALL keys,
which shares no key range with either; that the comparison tells a band one
step too wide; the order ``window_keys`` hands a window layer's keys over in;
which route a learner takes where; and the static grid the start-up gauge
reports.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.models import sequence_core
from dist_dqn_tpu.ops import pallas_attention
from tests.test_laguna_core import SEQS, _setup

B, KV, D = 2, 2, 8
HISTORY = WINDOW = 16
# 8 queries x 16 keys a tile: the 40-step window below is five query blocks
# over four key blocks, the ring in front of them one
TILES = pallas_attention.Tiles(8, 16)

#: case -> (steps T, the ring's step counter a lane, resets (lane, step)).
#: A counter below ``HISTORY`` is a ring whose episode opened inside the
#: burn-in: fewer valid keys than slots (a ``W`` layer's right-aligned, an
#: ``F`` layer's a prefix). One above it is a ring acting has wrapped: slot
#: order is not position order.
CASES = {
    "no_reset": (40, (HISTORY, HISTORY), ()),
    "reset_in_the_call": (40, (HISTORY, HISTORY),
                          ((0, 9), (0, 10), (0, 27), (1, 0), (1, 16))),
    "reset_in_the_burn_in": (40, (5, 0), ()),
    "wrapped_ring": (40, (2 * HISTORY + 3, 7 * HISTORY - 1), ((1, 30),)),
    "ragged_window": (37, (HISTORY, 11), ((0, 20),)),
    "shorter_than_the_window": (5, (HISTORY + 2, 3), ((1, 2),)),
}


def _layer(kind, G, kv=KV):
    core = dataclasses.replace(CONFIGS["laguna_q"].network.core,
                               sliding_window=WINDOW,
                               num_key_value_heads=kv, head_dim=D)
    return sequence_core._MIXERS[kind](core, jnp.float32, heads=G * kv)


def _window(kind, G, case, seed=0, kv=KV):
    """One call's queries, keys with their marks, and a cotangent."""
    T, steps, resets = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (B, T, kv, G, D))
    new_k, new_v = (jax.random.normal(k, (B, T, kv, D)) for k in keys[1:3])
    ring = tuple(jax.random.normal(k, (B, HISTORY, kv, D))
                 for k in keys[3:5])
    reset = np.zeros((B, T), bool)
    for lane, step in resets:
        reset[lane, step] = True
    seg = sequence_core.segments(jnp.asarray(reset))
    index = jnp.arange(T)
    opened = jax.lax.cummax(jnp.where(
        jnp.diff(seg, axis=1, prepend=0) > 0, index, -1), axis=1)
    steps = jnp.asarray(steps, jnp.float32)
    position = jnp.where(seg == 0, steps.astype(jnp.int32)[:, None] + index,
                         index - opened)
    marks = _layer(kind, G, kv).window_keys(new_k, new_v, position, seg,
                                            ring + (steps,))
    return q, marks, position, seg, jax.random.normal(keys[5], q.shape)


def _masked_softmax(q, keys, values, see):
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, keys) * D ** -0.5
    scores = jnp.where(see[:, None, None], scores, -1e30)
    return jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, axis=-1),
                      values)


def _paths(kind, G, case, window=WINDOW, which=("kernels", "blocks"),
           kv=KV):
    """``(out, dq, dk, dv)`` of each path of ``which``: the ``kernels``
    (interpreted; told ``window``), the ``blocks``, and ``every_key`` — one
    masked softmax of every query over ALL keys under the mask rule, which
    reads no key range."""
    q, (keys, values, key_position, key_seg), position, seg, pull = _window(
        kind, G, case, kv=kv)
    layer = _layer(kind, G, kv)

    def kernels(q, keys, values):
        return pallas_attention.attend(
            q, keys, values, position, seg, key_position, key_seg,
            history=HISTORY, window=window if kind == "W" else None,
            dtype=jnp.float32, interpret=True, tiles=TILES)

    def blocks(q, keys, values):
        return layer.blockwise(_masked_softmax, q, keys, values, position,
                               seg, key_position, key_seg)

    def every_key(q, keys, values):
        below = position[:, :, None] - key_position[:, None, :]
        see = (key_seg[:, None, :] == seg[:, :, None]) & (below >= 0)
        if kind == "W":
            see = see & (below < WINDOW)
        return _masked_softmax(q, keys, values, see)

    def with_grads(f):
        out, grads = jax.jit(jax.value_and_grad(
            lambda *a: (lambda o: (jnp.sum(o * pull), o))(f(*a)),
            argnums=(0, 1, 2), has_aux=True))(q, keys, values)
        return (out[1],) + grads

    paths = {"kernels": kernels, "blocks": blocks, "every_key": every_key}
    return tuple(with_grads(paths[name]) for name in which)


def _assert_close(got, want):
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind,G", [("W", 8), ("W", 6), ("F", 6), ("F", 8)])
def test_the_kernels_are_blockwise_attention(kind, G, case):
    """Output and the gradients to ``q``, ``k``, ``v``: the mask built in the
    kernel from the four int32 vectors is ``blockwise``'s, whatever the ring
    holds and wherever the episodes open; padding rows and keys add
    nothing; the static key ranges leave out no key a query sees."""
    _assert_close(*_paths(kind, G, case))


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_hold_at_one_query_head_a_kv_head(case):
    """The ``ouro_q`` preset's grouping — G = 1 over 16 KV heads, where the
    presets before it ran 6, 7 and 8 query heads over 2 to 8: the forward's
    ``[1, bq]`` statistics and ``[1, D, bq]`` accumulator, the backward's
    ``dq [1, Tp, D]`` block, output and the gradients to ``q``, ``k``, ``v``
    against ``blockwise``."""
    _assert_close(*_paths("F", 1, case, kv=16))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("path", ["kernels", "blocks"])
@pytest.mark.parametrize("kind,G", [("W", 8), ("F", 6)])
def test_no_key_range_drops_a_key_a_query_sees(kind, G, path, case):
    """The kernels and the blocks share ``key_ranges``, so the comparison
    above cannot see a range that leaves a visible key out of both. Here each
    is held to a masked softmax of every query over ALL keys, the mask from
    the marks alone: whatever the ring holds — wrapped by acting, cut short
    by a reset in the burn-in — a ``W`` layer's band by index is its band by
    position, output and the three gradients."""
    _assert_close(*_paths(kind, G, case, which=(path, "every_key")))


@pytest.mark.parametrize("case", ["no_reset", "wrapped_ring"])
def test_a_band_one_step_too_wide_fails_the_comparison(case):
    """The kernel told a window of 17 against the blocks' 16: every query
    past its 16th step sees one key more, and the comparison above says so."""
    with pytest.raises(AssertionError):
        _assert_close(*_paths("W", 8, case, window=WINDOW + 1))


@pytest.mark.parametrize("kind", ["W", "F"])
def test_window_keys_hands_a_window_layer_its_keys_in_the_order_of_time(kind):
    """Rings whose counters lie below, at and above ``HISTORY`` (no multiple
    of it among those above): a key of the ring is what the slot of its
    position holds, the valid ones are the last ``min(steps, HISTORY)``
    positions, and in a ``W`` layer ``index - position`` is one number a
    lane, the ring's keys' and the new keys' alike — the order ``key_ranges``
    counts on. An ``F`` layer's ring stays as it lies."""
    rng = np.random.default_rng(52)
    steps = np.concatenate([[0, 1, 5, HISTORY - 1, HISTORY, HISTORY + 1],
                            rng.integers(HISTORY + 2, 9 * HISTORY, size=10)])
    steps = steps[(steps <= HISTORY) | (steps % HISTORY != 0)]
    lanes, T = len(steps), 6
    slot = jnp.broadcast_to(jnp.arange(HISTORY, dtype=jnp.float32)[
        None, :, None, None], (lanes, HISTORY, KV, D))
    new = jnp.full((lanes, T, KV, D), -1.0)
    position = jnp.asarray(steps)[:, None] + jnp.arange(T)
    keys, values, key_position, key_seg = (
        np.asarray(x) for x in _layer(kind, 8).window_keys(
            new, new, position, jnp.zeros((lanes, T), jnp.int32),
            (slot, 2 * slot, jnp.asarray(steps, jnp.float32))))
    index = np.arange(HISTORY + T)
    for lane, count in enumerate(steps):
        valid = key_seg[lane] == 0
        assert (np.sort(key_position[lane, valid])
                == np.arange(max(count - HISTORY, 0), count + T)).all()
        ring = valid[:HISTORY]
        at = key_position[lane, :HISTORY][ring] % HISTORY
        assert (keys[lane, :HISTORY, 0, 0][ring] == at).all()
        assert (values[lane, :HISTORY, -1, -1][ring] == 2 * at).all()
        assert (key_seg[lane, :HISTORY][~ring]
                == pallas_attention.INVALID_KEY).all()
        if kind == "W":
            assert set(index[valid] - key_position[lane, valid]) == {
                HISTORY - count}
        else:
            assert (keys[lane, :HISTORY, 0, 0] == np.arange(HISTORY)).all()


@pytest.mark.parametrize("steps,history,window,tiles,blocks", [
    (1536, 512, 512, pallas_attention.TILES, None),
    (1536, 512, None, pallas_attention.TILES, None),
    (512, 512, 512, pallas_attention.TILES, None),
    (40, 16, 16, TILES, None), (37, 16, None, TILES, None),
    (5, 16, 16, TILES, None),
    (1536, 512, 512, pallas_attention.Tiles(128, 256), None),
    # the ``smallthinker_q`` preset's calls: the window is eight tiles
    (4096, 4096, 4096, pallas_attention.TILES, 72),
    (4096, 4096, None, pallas_attention.TILES, 100)])
def test_the_static_grid_visits_the_band_and_the_triangle(
        steps, history, window, tiles, blocks):
    """``key_block_census``: visited + skipped is the rectangle; visited is
    the blocks the MASK RULE needs and no more — for every query (index
    ``history + t``) the keys at index distance ``0 .. window - 1`` in front
    of it (a ``W`` layer's keys lie in the order of time), all of them in an
    ``F`` layer — counted here query by query; ``query_ranges`` is the same
    set of visits read by key block."""
    tiles = pallas_attention.fitted(tiles, steps, history)
    bq, bk = tiles.bq, tiles.bk
    visited, skipped = pallas_attention.key_block_census(
        steps, history, window, tiles)
    key_blocks = -(-(history + steps) // bk)
    assert visited + skipped == -(-steps // bq) * key_blocks
    reads = np.zeros((-(-steps // bq), key_blocks), bool)
    for t in range(steps):
        seen = np.arange(history + t + 1)
        if window:
            seen = seen[history + t - seen < window]
        reads[t // bq, seen // bk] = True
    assert visited == reads.sum()
    assert blocks is None or visited == blocks
    first, count = pallas_attention.query_ranges(steps, history, window, bq,
                                                 bk)
    for block, (f, c) in enumerate(zip(first, count)):
        assert (np.flatnonzero(reads[:, block]) == np.arange(f, f + c)).all()
    # a window layer's share does not grow with the window's length
    if window and steps >= 3 * window:
        assert visited <= -(-steps // bq) * (-(-(window + bq) // bk) + 1)


def _learner_step(monkeypatch, interpret):
    """One ``make_r2d2_learner`` step of the toy ``laguna_q`` network on a
    seeded batch (resets in the burn-in and among the loss positions):
    ``(lowered text, loss, priorities, params after)``."""
    from perf.reference import laguna_float32

    if interpret:
        monkeypatch.setenv("DIST_DQN_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("DIST_DQN_PALLAS_INTERPRET", raising=False)
    cfg, env, net = _setup()
    init, train_step, _ = laguna_float32.make_program(cfg, env, net)
    batch = {k: jnp.asarray(v) for k, v in laguna_float32.seeded_batch(
        7, 0, SEQS, cfg, env).items() if k != "start_state"}
    batch["start_state"] = ()
    state = jax.jit(init)(jax.random.PRNGKey(7))
    step = jax.jit(train_step)
    text = step.lower(state, batch).as_text()
    new, metrics = step(state, batch)
    return text, metrics["loss"], metrics["priorities"], new.params


def test_the_learner_takes_the_kernels_only_where_it_is_told(monkeypatch):
    """Off a TPU the learner's program holds no kernel (``blockwise`` runs);
    with ``DIST_DQN_PALLAS_INTERPRET=1`` — ``loop_common.pallas_routing``'s
    switch for toy tests, the route a TPU takes — the same step goes through
    the interpreted kernels, the queries' rotary pass in front of them,
    forward and backward, and lands where the blocks land within float32
    noise."""
    passes = []
    embed = pallas_attention._embed

    def counted(*args, kv=None, **more):
        passes.append("back" if kv is None else "there")
        return embed(*args, kv=kv, **more)

    monkeypatch.setattr(pallas_attention, "_embed", counted)
    text, loss, priorities, params = _learner_step(monkeypatch, False)
    assert "custom_call" not in text and "pallas" not in text.lower()
    assert pallas_attention.FORWARD_NAME not in text
    assert not passes       # ``rotate`` and ``kv_major``, as ever
    fused_text, fused_loss, fused_priorities, fused_params = _learner_step(
        monkeypatch, True)
    assert fused_text != text
    # the queries of every rotary sublayer went through the rotary kernel,
    # and their gradients came back through its twin
    assert passes.count("there") >= 2 * passes.count("back") > 0
    np.testing.assert_allclose(fused_loss, loss, rtol=1e-5)
    np.testing.assert_allclose(fused_priorities, priorities, rtol=1e-4,
                               atol=1e-6)
    for got, want in zip(jax.tree.leaves(fused_params),
                         jax.tree.leaves(params)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6)


def test_the_start_up_gauge_is_the_kernels_static_grid(monkeypatch):
    """``HybridQNetwork.attention_key_blocks`` — what ``train.train`` sets
    ``dqn_learner_attention_key_blocks`` from — for the ``laguna_q`` preset's
    batch of 4 windows (512 burn-in + 1,536): each kind's visited + skipped
    is its layers' rectangles, the window layers (a band) skip a larger
    share of theirs than the full layers (a triangle), and off a TPU, where
    no kernel runs, there is nothing to report."""
    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["laguna_q"]
    net = build_network(cfg.network, 6)
    shape = (cfg.learner.batch_size, cfg.replay.burn_in,
             cfg.replay.unroll_length + cfg.learner.n_step)
    assert shape == (4, 512, 1536)
    assert net.attention_key_blocks(*shape) == {}
    monkeypatch.setattr(loop_common, "pallas_routing",
                        lambda enabled: (enabled, False))
    found = net.attention_key_blocks(*shape)
    assert set(found) == {"window", "full"}
    tiles = pallas_attention.TILES
    for name, windowed, layers in (("window", True, 3), ("full", False, 2)):
        rectangle = sum(-(-T // tiles.bq) * -(-(512 + T) // tiles.bk)
                        for T in (512, 1536))
        visited, skipped = found[name]
        assert visited + skipped == layers * 4 * 8 * rectangle
        each = [pallas_attention.key_block_census(
            T, 512, 512 if windowed else None) for T in (512, 1536)]
        assert visited == layers * 4 * 8 * sum(v for v, _ in each)
    window, full = (found[k][1] / sum(found[k]) for k in ("window", "full"))
    assert window > full > 0.2


# -- under the TPU's compiler -------------------------------------------------
@pytest.fixture(scope="module")
def v5e():
    """One described (not attached) v5e chip: the TPU compiler is installed
    here and compiles for it (``tests/test_ring_boundary.py``'s fixture).
    What Mosaic accepts and the program's sizes, never a time."""
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compiled_for(v5e_args, fn):
    """``fn`` compiled for the described chip, as text. A compile for a
    described chip is written to the persistent cache but cannot be read back
    without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*v5e_args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("kind,G,steps,backward,lanes,kv", [
    ("W", 8, 1536, True, 4, 8), ("F", 6, 1536, True, 4, 8),
    ("W", 8, 512, False, 4, 8), ("F", 1, 1536, True, 2, 16),
    ("F", 1, 512, False, 2, 16)])
def test_the_kernels_compile_for_v5e_at_the_presets_shapes(v5e, kind, G,
                                                           steps, backward,
                                                           lanes, kv):
    """The ``laguna_q`` preset's calls (4 windows, 8 KV heads of 128, a ring
    of 512 in front) and the ``ouro_q`` preset's (2 windows, ONE query head
    over each of 16 KV heads) through Mosaic at ``TILES``: what the interpreter cannot
    refuse — a block off the (8, 128) tiling, a transpose or a reshape
    Mosaic has no rule for, more VMEM than a kernel may have (the backward
    keeps a KV head's whole ``dq``, 6.3 MB, twice). The compiled program
    holds the kernels by name and no score-shaped array."""
    d, history = 128, 512

    def attended(q, keys, values, *marks):
        return pallas_attention.attend(
            q, keys, values, *marks, history=history,
            window=512 if kind == "W" else None, dtype=jnp.bfloat16)

    def loss_grads(q, keys, values, *marks):
        return jax.grad(lambda *a: jnp.sum(attended(*a, *marks) ** 2),
                        argnums=(0, 1, 2))(q, keys, values)

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e)

    S = history + steps
    args = (shape(lanes, steps, kv, G, d), shape(lanes, S, kv, d),
            shape(lanes, S, kv, d), shape(lanes, steps, dtype=jnp.int32),
            shape(lanes, steps, dtype=jnp.int32),
            shape(lanes, S, dtype=jnp.int32), shape(lanes, S, dtype=jnp.int32))
    text = _compiled_for(args, loss_grads if backward else attended)
    assert pallas_attention.FORWARD_NAME in text
    assert (pallas_attention.BACKWARD_NAME in text) == backward
    # no [.., queries, keys] array in HBM: the largest thing is q's size
    assert not any(f",{steps},{keys}]" in text or f",{keys},{steps}]" in text
                   for keys in (S, 1024, 2048))


@pytest.mark.parametrize("kind,G,steps,preset", [
    ("W", 8, 1536, "laguna_q"), ("F", 6, 1536, "laguna_q"),
    ("W", 8, 512, "laguna_q"), ("F", 6, 512, "laguna_q"),
    ("F", 1, 1536, "ouro_q")])
def test_the_rotary_kernels_compile_for_v5e_at_the_presets_shapes(
        v5e, kind, G, steps, preset):
    """The queries' pass there and back at the ``laguna_q`` preset's calls:
    a block of ``EMBED_BLOCK`` steps x a KV head's ``G`` tiles of 128 lanes,
    the lane rotations by 64 (``W``) and by 96 and 32 (``F``) through
    Mosaic; and at the ``ouro_q`` preset's, a block ONE tile wide (G = 1, 16
    KV heads, one roll by 64 over all dims)."""
    core = CONFIGS[preset].network.core
    rope = core.rope_window if kind == "W" else core.rope_full
    kv = core.num_key_value_heads

    def there(x, position):
        wide, shifts = pallas_attention.wide_tables(
            sequence_core.rotary_tables(position, rope, 128), 128)
        q = pallas_attention._embed(x, wide, shifts, jnp.bfloat16, kv=kv)
        return q, pallas_attention._embed(q.astype(jnp.float32), wide, shifts,
                                          jnp.bfloat16)

    text = _compiled_for(
        (jax.ShapeDtypeStruct((4, steps, kv * G * 128), jnp.float32,
                              sharding=v5e),
         jax.ShapeDtypeStruct((4, steps), jnp.int32, sharding=v5e)), there)
    assert pallas_attention.EMBED_FORWARD_NAME in text
    assert pallas_attention.EMBED_BACKWARD_NAME in text


@pytest.mark.parametrize("kind,heads", [("W", 64), ("F", 48)])
def test_a_sublayers_grad_pass_holds_no_half_empty_array(v5e, monkeypatch,
                                                         kind, heads):
    """One ``_RotaryAttention`` at the preset's shapes, ``jax.grad`` through
    ``jax.checkpoint``, the kernel route forced, compiled for v5e: the four
    kernels by name, and no array whose minor dimension is a rotary half —
    ``rotate``'s two halves of the queries (``[4,1536,64,64]``,
    ``[4,1536,48,32]``) and an ``F`` layer's passing dims
    (``[4,1536,48,64]``) were the cell's largest device operation at a quarter
    of the HBM's rate (``PERF.md`` §6, PR 48). Any form that slices the minor
    dimension at 64 or 32 in HLO brings them back."""
    from dist_dqn_tpu import loop_common

    monkeypatch.setattr(loop_common, "pallas_routing",
                        lambda enabled: (enabled, False))
    cfg = CONFIGS["laguna_q"]
    layer = sequence_core._RotaryAttention(
        cfg.network.core, jnp.bfloat16, heads=heads, windowed=kind == "W")

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e)

    u, seg = shape(4, 1536, cfg.network.hidden), shape(4, 1536,
                                                       dtype=jnp.int32)
    ring = (shape(4, 512, 8, 128), shape(4, 512, 8, 128), shape(4))
    params = jax.tree.map(
        lambda a: shape(*a.shape, dtype=a.dtype),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), u, seg, ring))

    def loss(params, u, seg, ring):
        out, carry = jax.checkpoint(
            lambda params, u: layer.apply(params, u, seg, ring))(params, u)
        return jnp.sum(out ** 2) + sum(jnp.sum(c) for c in carry)

    text = _compiled_for((params, u, seg, ring),
                         jax.grad(loss, argnums=(0, 1)))
    for name in (pallas_attention.FORWARD_NAME, pallas_attention.BACKWARD_NAME,
                 pallas_attention.EMBED_FORWARD_NAME,
                 pallas_attention.EMBED_BACKWARD_NAME):
        assert name in text
    for half_empty in ("[4,1536,64,64]", "[4,1536,48,32]", "[4,1536,48,64]"):
        assert half_empty not in text


@pytest.mark.parametrize("history,kv,G", [
    (4096, 4, 7), (8192, 4, 7), (2048, 8, 6), (512, 8, 8), (2048, 16, 1)])
def test_the_decode_kernel_compiles_for_v5e_at_the_presets_rings(v5e, history,
                                                                 kv, G):
    """One acting step of 16 lanes over a ring of the ``smallthinker_q``,
    ``laguna_q`` and ``ouro_q`` (16 KV heads, one query head each) presets — the new key and value into their slot, then
    ``decode`` — through Mosaic at ``DECODE_BLOCK``: the kernel by name, the
    ring handed to it where it lies (its ``[B, S * KV, D]`` view is the same
    bytes: no copy), and no bfloat16 array of a ring's size, in any layout."""
    lanes, d = 16, 128

    def step(q, old_k, old_v, new_k, new_v, position):
        at = (jnp.arange(lanes), position % history)
        ring_k, ring_v = old_k.at[at].set(new_k), old_v.at[at].set(new_v)
        return pallas_attention.decode(
            q, ring_k, ring_v, jnp.minimum(position + 1, history),
            jnp.bfloat16), ring_k, ring_v

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e)

    text = _compiled_for(
        (shape(lanes, kv, G, d), shape(lanes, history, kv, d),
         shape(lanes, history, kv, d), shape(lanes, kv, d),
         shape(lanes, kv, d), shape(lanes, dtype=jnp.int32)), step)
    assert pallas_attention.DECODE_NAME in text
    assert f"bf16[{lanes},{history}," not in text
    assert f"bf16[{lanes},{kv},{history}," not in text
    flat = f"f32[{lanes},{history * kv},{d}]"
    made = [line for line in text.splitlines() if f" = {flat}" in line]
    assert made and all(" bitcast(" in line for line in made), made


def test_an_acting_program_holds_no_bfloat16_ring(v5e, monkeypatch):
    """A toy ``FEWE`` network's acting step (bfloat16 compute;
    ``tests/test_smallthinker_core.py``'s toy of the ``smallthinker_q``
    preset) lowered for v5e the way ``scripts/chunk_program_hash.py`` lowers a chunk
    program: on the route a TPU takes its text holds the decode kernel and no
    bfloat16 tensor of a ring's shape; on the plain route, which casts the
    rings in front of its products, it holds one a ring."""
    from dist_dqn_tpu import loop_common
    from tests.test_smallthinker_core import _setup as toy_smallthinker

    cfg, env, net = toy_smallthinker(compute_dtype="bfloat16")
    assert cfg.network.core.pattern == "FEWE"
    lanes = cfg.actor.num_envs
    obs = jax.ShapeDtypeStruct((lanes,) + tuple(env.observation_shape),
                               jnp.float32, sharding=v5e)
    carry = jax.eval_shape(lambda: net.initial_state(lanes))
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0), carry, obs)
    on_chip = functools.partial(jax.tree.map, lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=v5e))
    rings = {"x".join(map(str, layer[0].shape)) for layer in carry if layer}
    assert len(rings) == 2      # the full layer's and the window layer's

    def lowered(route):
        monkeypatch.setattr(loop_common, "pallas_routing", route)
        return jax.jit(net.apply, donate_argnums=1).lower(
            on_chip(params), on_chip(carry), obs).as_text()

    text = lowered(lambda enabled: (enabled, False))
    assert pallas_attention.DECODE_NAME in text
    assert not any(f"tensor<{ring}xbf16>" in text for ring in rings)
    plain = lowered(lambda enabled: (False, False))
    assert pallas_attention.DECODE_NAME not in plain
    assert all(f"tensor<{ring}xbf16>" in plain for ring in rings)
