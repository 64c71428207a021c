"""Acting's attention kernel (``ops/pallas_attention.py decode``), interpreted
on the CPU at toy sizes, against the path it replaces on a TPU: the plain
masked softmax of ``_RotaryAttention``'s ``attend`` over the float32 ring —
for every group size of the presets, a ring that is and is not whole blocks,
and lanes just reset, part full, exactly full and wrapped; that the ring's
values are rounded in the kernel exactly as a whole-ring cast rounds them;
and the start-up gauge of what an acting step reads and copies.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.ops import pallas_attention

LANES, D, BLOCK = 4, 8, 16


def _attend(q, ring_k, ring_v, count, dtype):
    """``_RotaryAttention``'s ``attend`` at one query a head: the whole ring
    cast in front of the two products."""
    see = jnp.arange(ring_k.shape[1]) < count[:, None]
    scores = jnp.einsum("bkgd,bskd->bkgs", q.astype(dtype),
                        ring_k.astype(dtype),
                        preferred_element_type=jnp.float32) * D ** -0.5
    scores = jnp.where(see[:, None, None], scores, -1e30)
    return jnp.einsum("bkgs,bskd->bkgd",
                      jax.nn.softmax(scores, axis=-1).astype(dtype),
                      ring_v.astype(dtype),
                      preferred_element_type=jnp.float32)


def _step(G, KV, history, seed=0):
    """One acting step's operands: lane 0 just reset (its one key is all it
    sees, whatever the ring still holds), lane 1 half full, lane 2 exactly
    full, lane 3 wrapped long ago."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (LANES, KV, G, D))
    ring_k, ring_v = (jax.random.normal(k, (LANES, history, KV, D))
                      for k in keys[1:])
    position = jnp.asarray([0, history // 2 - 1, history - 1,
                            7 * history + 3])
    return q, ring_k, ring_v, jnp.minimum(position + 1, history)


@pytest.mark.parametrize("history", [48, 37, 16, 5],
                         ids=["whole_blocks", "ragged", "one_block",
                              "less_than_a_block"])
@pytest.mark.parametrize("G,KV", [(7, 4), (6, 8), (8, 8), (1, 16), (1, 4),
                                  (3, 2)])
def test_the_kernel_is_attend_over_the_float32_ring(G, KV, history):
    """The mask built in the kernel from a lane's count is ``attend``'s; the
    rows that pad a KV head's queries to eight (7 of 8 at the ``ouro_q``
    preset's ONE query head a KV head over 16 KV heads), the scores between a
    head's queries and another head's keys, and what lies past a ragged
    ring's last slot add nothing."""
    args = _step(G, KV, history)
    got = pallas_attention.decode(*args, jnp.float32, interpret=True,
                                  block=BLOCK)
    assert got.shape == (LANES, KV, G, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _attend(*args, jnp.float32), rtol=2e-4,
                               atol=2e-5)


def test_a_lane_just_reset_sees_its_one_key():
    """Position 0: the output is the value in slot 0, whatever the other
    slots hold — numbers no float32 sum would survive among them."""
    q, ring_k, ring_v, count = _step(7, 4, 48)
    ring_k = ring_k.at[0, 1:].set(3e38)
    ring_v = ring_v.at[0, 1:].set(-3e38)
    got = pallas_attention.decode(q, ring_k, ring_v, count, jnp.float32,
                                  interpret=True, block=BLOCK)
    np.testing.assert_allclose(
        got[0], jnp.broadcast_to(ring_v[0, 0][:, None], got[0].shape),
        rtol=1e-6)


@pytest.mark.parametrize("G,KV", [(7, 4), (8, 8), (1, 16)])
def test_the_ring_is_rounded_in_the_kernel_as_the_cast_rounds_it(G, KV):
    """A ring whose values bfloat16 cannot hold, read by the kernel with
    bfloat16 operands, gives BIT FOR BIT what the same kernel gives over the
    ring cast to bfloat16 beforehand — the rounding on the way to the
    product is the whole-ring ``astype``'s — and lands on ``attend``'s
    cast-then-product within bfloat16's noise; with float32 operands it
    gives something else."""
    q, ring_k, ring_v, count = _step(G, KV, 48, seed=3)
    rounded = tuple(r.astype(jnp.bfloat16).astype(jnp.float32)
                    for r in (ring_k, ring_v))
    assert not np.array_equal(rounded[0], ring_k)

    def kernel(ring_k, ring_v, dtype=jnp.bfloat16):
        return pallas_attention.decode(q, ring_k, ring_v, count, dtype,
                                       interpret=True, block=BLOCK)

    got = kernel(ring_k, ring_v)
    np.testing.assert_array_equal(got, kernel(*rounded))
    np.testing.assert_allclose(
        got, _attend(q, ring_k, ring_v, count, jnp.bfloat16), rtol=2e-2,
        atol=2e-2)
    assert not np.array_equal(got, kernel(ring_k, ring_v, jnp.float32))


def test_more_than_eight_heads_a_kv_head_are_refused():
    q, ring_k, ring_v, count = _step(9, 2, 16)
    with pytest.raises(ValueError, match="8"):
        pallas_attention.decode(q, ring_k, ring_v, count, jnp.float32,
                                interpret=True)


def test_the_start_up_gauge_is_the_rings_an_acting_step_reads(monkeypatch):
    """``HybridQNetwork.attention_ring_bytes`` — what ``train.train`` sets
    ``dqn_actor_attention_ring_bytes`` from — for the ``smallthinker_q``
    preset's 16 lanes, from shapes alone: three window rings of 4,096 slots
    and one full ring of 8,192, keys and values, 4 KV heads of 128 in
    float32; where ``attend`` runs it copies them once more in bfloat16, on
    the kernel's route nothing is copied; a core without rings has nothing
    to report."""
    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.models import build_network

    net = build_network(CONFIGS["smallthinker_q"].network, 6)
    lanes = CONFIGS["smallthinker_q"].actor.num_envs
    assert lanes == 16
    assert net.attention_ring_bytes(lanes) == {
        "window": (805_306_368, 402_653_184),
        "full": (536_870_912, 268_435_456)}
    assert sum(read for read, _ in net.attention_ring_bytes(1).values()) == (
        net.state_bytes_a_lane()["attention_window"]
        + net.state_bytes_a_lane()["attention_full"] - 4 * 4)   # 4 counters
    monkeypatch.setattr(loop_common, "pallas_routing",
                        lambda enabled: (enabled, False))
    assert net.attention_ring_bytes(lanes) == {
        "window": (805_306_368, 0), "full": (536_870_912, 0)}
    assert build_network(CONFIGS["twotower_q"].network,
                         6).attention_ring_bytes(lanes) == {}
