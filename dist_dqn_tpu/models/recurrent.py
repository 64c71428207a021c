"""Recurrent (R2D2) Q-network: torso -> LSTM core -> dueling head.

Covers the driver's R2D2 config (BASELINE.json:10): an LSTM Q-network whose
single-step form drives acting (carry threaded through the fused loop) and
whose unrolled form drives sequence learning with burn-in.

TPU notes: the torso (convs — where the FLOPs are) runs in ``compute_dtype``
(bfloat16) on the MXU; the LSTM core and heads run in float32 — the cell is
a [B, H] x [H+E, 4H] matmul, small next to the torso, and a float32 carry
keeps the scan numerically stable and its dtype invariant. The unrolled form
embeds all T*B frames in ONE batched conv call (maximal MXU tiling) and only
the tiny cell recurrence runs under ``nn.scan``.

Episode boundaries: both forms accept per-step reset flags and zero the
carry *before* consuming a post-reset observation, so a learner unroll that
crosses an episode boundary recomputes exactly the hidden states the actor
saw — no stale state leaks across resets.
"""
from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

Array = jnp.ndarray
LSTMCarry = Tuple[Array, Array]  # (c, h), each [B, lstm_size] float32


@jax.custom_jvp
def _hold(x: Array) -> Array:
    """``x`` held as an array of its own in the forward computation (an
    optimization barrier): what made it stays out of its reader's fusion.
    The tangent passes by, so a backward is what it was — a barrier on
    the cotangent would split the bias gradient's reduction from the
    kernel that makes the cotangent."""
    return jax.lax.optimization_barrier(x)


@_hold.defjvp
def _hold_jvp(primals, tangents):
    return _hold(primals[0]), tangents[0]


class _HeldCNNTorso(nn.Module):
    """``models/qnets.py CNNTorso`` — its layers, parameter names and values
    — with the output of every convolution that another convolution reads
    held (``_hold``), before its ``relu``: the held array then serves the
    next convolution, that one's weight gradient and the ``relu``'s mask.
    ``_Embed`` names it ``CNNTorso_0``, what flax names a ``CNNTorso``
    there, so checkpoints interchange. A path of the recurrent network's
    own: the DQN programs build ``CNNTorso`` and stay what they were."""

    layers: Tuple[Tuple[int, int, int], ...]
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: Array) -> Array:
        x = x.astype(self.dtype)
        for i, (features, kernel, stride) in enumerate(self.layers):
            x = nn.Conv(features, (kernel, kernel), strides=(stride, stride),
                        padding="VALID", dtype=self.dtype)(x)
            if i + 1 < len(self.layers):
                x = _hold(x)
            x = nn.relu(x)
        return x.reshape((x.shape[0], -1))


class _Embed(nn.Module):
    """Torso + pre-LSTM dense: [N, ...obs] -> [N, E] float32.

    A separate module (not a method) so ``nn.remat`` can wrap it: under
    rematerialization the unroll's [T*B] conv activations — the dominant
    learner-memory term for pixel R2D2 — are recomputed in the backward
    pass instead of living in HBM across the whole sequence loss.

    The torso holds each convolution's output that another convolution
    reads (``_HeldCNNTorso``), inside what remat wraps: conv1 reads the
    uint8 stacks with the cast fused, and where its output has no second
    reader — a pass that keeps nothing for a backward — the v5e compiler
    nests it in conv2's fusion, a third slower than the two apart, and
    conv2 in conv3's once conv1 alone is held (PERF.md §7.8).
    """

    torso: str
    mlp_features: Tuple[int, ...]
    hidden: int
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, obs: Array) -> Array:
        from dist_dqn_tpu.models.qnets import CNN_TORSO_LAYERS, MLPTorso

        x = obs
        if x.dtype == jnp.uint8:
            x = x.astype(self.compute_dtype) / 255.0
        if self.torso in CNN_TORSO_LAYERS:
            x = _HeldCNNTorso(CNN_TORSO_LAYERS[self.torso],
                              dtype=self.compute_dtype,
                              name="CNNTorso_0")(x)
        elif self.torso == "mlp":
            x = MLPTorso(self.mlp_features, dtype=self.compute_dtype)(x)
        else:
            raise ValueError(f"unknown torso {self.torso!r}")
        if self.hidden:
            x = nn.relu(nn.Dense(self.hidden, dtype=self.compute_dtype,
                                 name="embed")(x))
        return x.astype(jnp.float32)


class _ResetCell(nn.Module):
    """LSTM cell that zeroes its carry where ``reset`` is set.

    Scanned over time by ``RecurrentQNetwork.unroll``; the single-step path
    is a length-1 unroll of the same instance, so acting and learning share
    parameters by construction.

    ``dtype`` sets the gate-matmul compute dtype (bfloat16 puts the cell's
    [B, E+H] x [*, 4H] products on the MXU); the (c, h) carry is cast back
    to float32 every step so the recurrence stays numerically stable and
    the carry dtype is invariant across configs/checkpoints.
    """

    lstm_size: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, carry: LSTMCarry, inputs):
        x, reset = inputs  # x: [B, E] float32; reset: [B] bool
        keep = (~reset).astype(jnp.float32)[:, None]
        carry = (carry[0] * keep, carry[1] * keep)
        new_carry, h = nn.OptimizedLSTMCell(
            self.lstm_size, dtype=self.dtype, name="lstm")(carry, x)
        new_carry = tuple(c.astype(jnp.float32) for c in new_carry)
        return new_carry, h.astype(jnp.float32)


class RecurrentQNetwork(nn.Module):
    """LSTM Q-network with optional dueling head (R2D2, BASELINE.json:10).

    Two entry points sharing one parameter set (``unroll`` is the single
    compact method; ``__call__`` is a length-1 unroll):
      * ``apply(params, carry, obs, reset)``                  — one step
      * ``apply(params, carry, obs, reset, method='unroll')`` — [T, B, ...]
    Both return ``(new_carry, q)`` with q float32 ([B, A] / [T, B, A]).
    """

    num_actions: int
    torso: str = "nature"
    mlp_features: Tuple[int, ...] = (256, 256)
    hidden: int = 512
    lstm_size: int = 512
    dueling: bool = True
    compute_dtype: jnp.dtype = jnp.float32
    # Recompute torso activations in the backward pass (HBM for FLOPs) —
    # for long-unroll pixel configs where [T*B] conv activations dominate.
    remat_torso: bool = False
    # Cell gate-matmul dtype (carry stays float32) and lax.scan unroll
    # factor for the time loop — learner-throughput knobs, math unchanged.
    lstm_dtype: jnp.dtype = jnp.float32
    lstm_unroll: int = 1
    # Present for API parity with QNetwork (scalar-Q head only).
    num_atoms: int = 1
    noisy: bool = False

    def initial_state(self, batch_size: int) -> LSTMCarry:
        shape = (batch_size, self.lstm_size)
        return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))

    def stored_state(self, carry: LSTMCarry) -> LSTMCarry:
        """What the replay ring keeps of a lane's state with each step:
        the pair entering it."""
        return carry

    def window_state(self, stored: LSTMCarry, batch_size: int,
                     burn_in: int) -> LSTMCarry:
        """The state a learner's window starts from: the one stored with
        its first step."""
        return stored

    def _embed(self, obs: Array) -> Array:
        """[N, ...obs] -> [N, E] float32 embedding (torso + pre-LSTM dense).

        The same param names are produced with and without remat (nn.remat
        is transform-transparent), so checkpoints interchange freely.
        """
        cls = nn.remat(_Embed) if self.remat_torso else _Embed
        return cls(self.torso, self.mlp_features, self.hidden,
                   self.compute_dtype, name="torso")(obs)

    def _q_head(self, h: Array) -> Array:
        """[N, H] -> [N, A] float32 (dueling combine when configured)."""
        adv = nn.Dense(self.num_actions, name="advantage")(h)
        if not self.dueling:
            return adv
        val = nn.Dense(1, name="value")(h)
        return val + adv - jnp.mean(adv, axis=-1, keepdims=True)

    def __call__(self, carry: LSTMCarry, obs: Array,
                 reset: Optional[Array] = None
                 ) -> Tuple[LSTMCarry, Array]:
        """One step: obs [B, ...], reset [B] bool (None = no resets)."""
        carry, q = self.unroll(carry, obs[None],
                               None if reset is None else reset[None])
        return carry, q[0]

    @nn.compact
    def unroll(self, carry: LSTMCarry, obs: Array,
               reset: Optional[Array] = None) -> Tuple[LSTMCarry, Array]:
        """Unrolled: obs [T, B, ...], reset [T, B]; returns q [T, B, A].

        reset[t] zeroes the carry before step t (i.e. obs[t] opens a new
        episode). The torso runs once over the flattened [T*B] batch.
        """
        T, B = obs.shape[:2]
        if reset is None:
            reset = jnp.zeros((T, B), jnp.bool_)
        x = self._embed(obs.reshape((T * B,) + obs.shape[2:]))
        x = x.reshape((T, B, -1))
        core = nn.scan(_ResetCell, variable_broadcast="params",
                       split_rngs={"params": False},
                       in_axes=0, out_axes=0,
                       unroll=self.lstm_unroll)(
            self.lstm_size, dtype=self.lstm_dtype, name="core")
        carry, hs = core(carry, (x, reset))
        q = self._q_head(hs.reshape((T * B, -1)))
        return carry, q.reshape((T, B, self.num_actions))
