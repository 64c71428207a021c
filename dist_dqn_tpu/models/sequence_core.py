"""Recurrent Q-network over a hybrid sequence core: torso -> Dense
``hidden`` -> a stack of pre-norm residual layers -> RMSNorm -> dueling
heads (``config.CoreConfig`` kind "hybrid"); the stack and its norm run once,
or ``loops`` times over the same parameters (below).

Every layer is ``x + mixer(RMSNorm(x))`` — ``x + RMSNorm'(mixer(RMSNorm(x)))``
under ``sandwich_norm``, as ``ouro`` writes its layers — with no bias but the
state-space layer's convolution, and nothing but ``x`` and the carry passes
from one to the next, except the routing under ``router_ahead`` (below); one
letter of
``CoreConfig.pattern`` a layer, as ``nemotron_h`` writes its
``hybrid_override_pattern``:

``M``  Mamba-2 (Dao & Gu 2024). ``[z | xBC | dt] = u W_in``; ``xBC =
       silu(causal depthwise conv(xBC) + b)``, split into ``x [H, P]`` and
       ``B, C [G, N]`` (head h reads group ``h // (H / G)``); ``dt =
       softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``h_t = exp(dt_t A)
       h_{t-1} + dt_t x_t (x) B_t``; ``y_t = C_t . h_t + D x_t``; ``y =
       RMSNorm_grouped(y silu(z)) w``; ``out = y W_out``. Computed in the
       chunked form (``ssd_chunked``): products inside chunks of
       ``chunk_size`` steps, a scan over the chunks' states; its backward
       is the chunked form's own. Between the two projections every
       activation is made once, in the split and the order its reader
       takes it (``PERF.md`` §6, PR 45): the window is cut into its chunks
       BEFORE the in-projection, ``[B, nc, Q, ..]`` from there to the
       out-projection; ``z``, ``x``, ``B``, ``C`` and ``dt`` are five
       products of ``u`` with the columns of the one ``W_in``
       (``split_products``: no ``[.., 10304]`` array, no split of an
       activation), ``x``, ``B``, ``C`` each
       with its own columns of the depthwise convolution, whose first taps
       read the chunk before; the running sum of ``dt A`` inside a chunk
       and the grouped norm's sums are products with a triangle and with
       the groups' indicator matrix. ``ssd_chunked`` takes and returns
       chunked arrays, ``y`` as ``[B, nc, Q, H, P]``.
``E``  Mixture of experts beside a shared expert. Router logits in
       float32, ``s = sigmoid(logits)``, the top k of ``s + bias`` chosen,
       gates ``s[chosen] / sum(s[chosen]) * scale``; an expert is ``W_down
       relu(W_up u)^2``. The layer is TOLD which experts it holds
       (``experts_held``, expert parallelism's share of the layer): it
       routes over all of them and adds what its own give; what the absent
       ones would add is left out. The held experts are computed DENSELY —
       two matmuls over all of them, each expert's block scaled by its
       gate, zero where it was not chosen — so the layer's time does not
       follow the routing (PERF.md: a dispatch whose rows follow the
       routing follows the seed). The bias is a leaf no gradient reaches.
``*``  Grouped-query attention, causal within an episode, no position
       embedding (``nemotron_h`` builds none in these layers).
``F``, ``W``  Grouped-query attention as ``laguna`` writes it
       (``_RotaryAttention``): queries and keys rotated by their step's
       position in its episode (``rotary_frequencies``: plain, or YaRN over
       part of the dims) BEFORE a key is cached, a sigmoid gate a head on
       the attended values, heads a layer (``attention_heads_per_layer``).
       ``F`` sees the whole episode, ``W`` its last ``sliding_window``
       steps. Both as ``smallthinker`` writes them too: a kind whose
       ``rotary_factor`` is 0 has NO position embedding (no tables, nothing
       rotated, and on a TPU no rotary kernel: the queries reach the fused
       kernels through ``attend(rotary=None)``), and without
       ``attention_gate`` there is no ``g_proj`` leaf and no gate. A
       learner's window (``T > 1``) is computed by blocks of
       ``QUERY_BLOCK`` queries (the kernels' tile; the window need not be
       one), ``F`` against the keys up to its block (the causal triangle
       by blocks), ``W`` against the keys a window back and its own (a
       band whose cost does not grow with the window's length: its keys are
       handed over in the order of time, so the band is a range of indices
       whatever the ring's state), by
       one of two paths that hold to one mask rule
       (``_RotaryAttention.window_keys``): on a TPU the fused kernels of
       ``ops/pallas_attention.py``, which keep a tile's scores in VMEM,
       forward and backward, and take the queries from ``q_proj`` as they
       lie: a rotary kernel in front rotates them by lane rotations, scales,
       casts and lays them out in one pass over whole tiles (``rotate``
       writes its halves as arrays half a tile of lanes wide or less; it
       stays the keys' path, acting's, every other backend's, and that
       kernel's oracle); on every other backend ``blockwise``, plain
       ``jax.numpy`` whose blocks' scores go through HBM — the kernels'
       oracle, which reads the key ranges they read. ``loop_common.pallas_routing``
       chooses (no option does). The acting step (``T == 1``) writes its
       key and value into their slot and its one query a head reads the
       ring — float32, whatever the compute type — by the same routing: on
       a TPU ``pallas_attention.decode`` reads each ring ONCE where it lies
       in HBM, a block of slots at a time, rounds the block to the compute
       type in VMEM on its way to the product (the rounding of a whole-ring
       ``astype``), keeps scores, mask, online softmax and the values
       product there, and writes nothing the size of a ring; slots past
       the lane's count are masked, not skipped, so a step costs the same
       at every position. On every other backend ``attend`` — one masked
       softmax over the ring cast to the compute type in front of its two
       products (a second and third pass over the ring) — which is also
       ``blockwise``'s block function and ``decode``'s oracle.
``D``  A dense gated MLP, ``W_down (silu(W_gate u) * W_up u)``.
``E`` takes the expert's form from the configuration (``expert_act``:
``relu2`` above, or gated as ``D`` with ``silu`` or ``relu``, ReGLU), its
correction bias where ``router_bias`` says so, the rule of its gates
(``router_scores``: ``sigmoid`` above, or ``softmax``: the top k of the
logits, a softmax over those k; no bias, no scale), and has no shared expert
— no ``shared_*`` leaf, no ``moe_shared`` scope — where that expert's width
is 0.

The router's road across a sublayer (``router_ahead``, ``smallthinker``: the
router reads the layer's input BEFORE attention). The published layer is ``u =
RMSNorm_1(x)``, ``h = x + Attn(u)``, ``y = h + MoE(RMSNorm_2(h); routed by u
W_r)``: two sublayers here, each under its own ``nn.remat``. The sublayer
BEFORE an ``E`` (``routes_ahead``) holds the router's weights beside its norm
(``layer_i/router``) and makes the float32 logits ``[B, T, routed]`` from its
own normed input, under the scope ``moe_router`` (outside the attention's
scope: the attention readers do not count it); ``_Core`` hands them to the
``E`` sublayer, which has no ``router`` leaf, chooses and gates from them
(``moe_router`` again) and hands on none. What crosses the boundary — forward,
in the backward's recomputation, and as a cotangent on the way back — is that
one array, not a second ``[B, T, hidden]`` stream; the router's weights get
their gradient in the attention sublayer's backward, and ``x`` gets the
gates' gradient through ``u``.

Loops (``loops`` > 1, ``ouro``: a looped language model's ``total_ut_steps``).
The whole stack — every sublayer of the pattern, then the final norm — is
applied ``loops`` times over ONE set of parameters (``layer_i`` and
``norm_f`` exist once, whatever ``loops`` says): the norm's output after turn
r is turn r + 1's input, and the heads read the last turn's. A weight's
gradient is the sum over its turns. The turns share NO state: turn r's keys
and values are projections of turn r's hidden state, so the carry has one
entry a sublayer A TURN (``applied``; entry ``r * len(pattern) + l``), and
everything that walks it — the empty state, a reset, the bytes a lane
carries, the key blocks and rotary rows a learner's pass runs — counts every
turn. ``HybridQNetwork.turns`` writes the turns out, one after the other and
each ring an array of its own, under one scope, ``loops``.

State. A lane's acting state is, for every ``M`` layer, the convolution's
look-back ``[B, K-1, channels]`` and the state ``h [B, H, P, N]``, and for
every ``*`` layer the keys and values of its last ``history`` steps with a
validity plane (``[B, history, KV, D]`` twice, ``[B, history]``): all
float32, all zero when empty, so that ``Agent.reset_state``'s product with
``1 - done`` empties a lane. An ``F`` or ``W`` layer keeps a RING of keys
and values (``[B, history, KV, D]`` twice) and the lane's step counter
``[B]``: step p of an episode lies in slot ``p mod history``, so acting
writes one slot a step and moves none, what is valid follows from the
counter alone, and emptying a lane is setting its counter to zero
(``reset_state``: the ring is left as it is). ``history`` is a layer's own:
``sliding_window`` in a ``W`` layer, ``attention_window`` in an ``F`` layer
while acting — which then sees that many steps back and no further.
``reset[t]`` (``obs[t]`` opens an episode) cuts every look-back at t inside
a window: the scan's carried state, the convolution's taps, the
attention's keys before t, and the positions, which restart at 0. The
replay ring stores none of it (``stored_state``): at megabytes a lane a
step it cannot, so a learner's window starts from the zero state and burns
in (R2D2's zero-state strategy), with ``history`` the burn-in's length.

Same two entry points as ``models/recurrent.py``: ``apply(params, carry,
obs, reset)`` is one step, ``method=net.unroll`` takes ``[T, B, ...]``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dist_dqn_tpu import loop_common
from dist_dqn_tpu.config import CoreConfig, RopeConfig
from dist_dqn_tpu.models.recurrent import _Embed
from dist_dqn_tpu.ops import pallas_attention

Array = jnp.ndarray
F32 = jnp.float32


def _normal(stddev: float):
    return nn.initializers.normal(stddev)


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    """``x / rms(x) * scale`` in float32 over the last axis."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def grouped_rms_norm(x: Array, scale: Array, eps: float, groups: int
                     ) -> Array:
    """``rms_norm`` with the mean square taken over each of ``groups``
    equal slices of the last axis. The sum over a group and its spread back
    over the group's channels are products with the groups' indicator
    matrix: the array keeps its shape from end to end, where a ``[..,
    groups, width]`` view is a relayout and its reduce and broadcast run as
    passes of their own."""
    x = x.astype(F32)
    width = x.shape[-1] // groups
    member = (jnp.arange(x.shape[-1])[:, None] // width
              == jnp.arange(groups)).astype(F32)        # [channels, groups]
    exact = jax.lax.Precision.HIGHEST
    mean_sq = jnp.dot(x * x, member, precision=exact) / width
    return x * jnp.dot(jax.lax.rsqrt(mean_sq + eps), member.T,
                       precision=exact) * scale


def segments(reset: Array) -> Array:
    """``[B, T]`` int32: how many episodes have opened up to and including
    step t. What a call was handed (state, look-back, cached keys) lies in
    segment 0, so a reset at step 0 already parts from it."""
    return jnp.cumsum(reset.astype(jnp.int32), axis=1)


def split_products(u: Array, w: Array, splits: Tuple[int, ...]):
    """``u w`` cut at the columns ``splits``: each part a product of its
    own with those columns of ``w``, float32 out; no ``[.., columns]``
    array is made and split. The columns are taken as ROWS of ``w.T``: the
    weight crosses the jit boundary column-major (the order that pads
    least), and a view ``w[:, lo:hi]`` made the compiler relay every
    float32 leaf beside it — parameter, target, both moments — each step
    (``PERF.md`` §6, PR 45)."""
    if math.prod(u.shape[:-1]) * 8 <= w.shape[0]:
        # a few rows (acting): the product is bound by reading w, so w is
        # read once, its cast inside the product, and the output is small
        return tuple(jnp.split(
            jnp.dot(u, w, preferred_element_type=F32), splits, axis=-1))
    edges = (0,) + splits + (w.shape[1],)
    rows = w.T
    return tuple(jnp.einsum("...k,ck->...c", u, rows[lo:hi],
                            preferred_element_type=F32)
                 for lo, hi in zip(edges, edges[1:]))


def ssd_chunked(x: Array, dt: Array, a: Array, b: Array, c: Array,
                seg: Array, state: Array, dtype) -> Tuple[Array, Array]:
    """The selective state-space recurrence in its chunked form, over
    arrays already cut into chunks of Q steps.

    ``x [B, nc, Q, H, P]``, ``dt [B, nc, Q, H]`` (after softplus; 0 on a
    padding step: it decays nothing and adds nothing), ``a [H]``
    (negative), ``b, c [B, nc, Q, G, N]``, ``seg [B, nc, Q]``
    (``segments``), ``state [B, H, P, N]`` float32 entering step 0. Returns
    ``y [B, nc, Q, H, P]`` float32 (without the ``D x`` skip) and the state
    after the last step.

    Inside a chunk the output is a masked product: ``y_i = sum_j L_ij (C_i
    . B_j) dt_j x_j`` with ``L_ij = exp(sum_{j<k<=i} dt_k a)`` for ``j <=
    i`` in one segment; each chunk hands on ``sum_j L_(Q-1)j dt_j x_j (x)
    B_j`` plus the decayed state it was handed, and adds ``C_i . state_in``
    decayed to step i. A step that opens an episode cuts all three by its
    segment number. Decays and the carried state are float32; the products
    take ``dtype`` operands and accumulate in float32."""
    B, nc, Q, H, P = x.shape
    G, N = b.shape[3:]
    # dt folded into x: what a step adds to the state is dt x (x) B
    xdt = (x * dt[..., None]).astype(dtype).reshape(B, nc, Q, G, H // G, P)
    b, c = b.astype(dtype), c.astype(dtype)
    # the running sum of dt a over a chunk's steps, as a product with the
    # lower triangle (a [Q, Q] product where a cumsum is a serial scan)
    causal = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    cs = jnp.einsum("ij,bzjh->bzih", causal.astype(F32), dt * a,
                    precision=jax.lax.Precision.HIGHEST)  # [B,nc,Q,H] <= 0
    cs = cs.reshape(B, nc, Q, G, H // G)
    # the segment each chunk's incoming state belongs to
    seg_in = jnp.concatenate(
        [jnp.zeros((B, 1), seg.dtype), seg[:, :-1, -1]], axis=1)

    # -- inside the chunks -------------------------------------------------
    same = seg[:, :, :, None] == seg[:, :, None, :]     # [B, nc, Qi, Qj]
    allowed = jnp.logical_and(same, causal)[..., None, None]
    decay = jnp.exp(jnp.where(
        allowed, cs[:, :, :, None] - cs[:, :, None, :], -jnp.inf))
    cb = jnp.einsum("bzign,bzjgn->bzijg", c, b,
                    preferred_element_type=F32)
    weights = (decay * cb[..., None]).astype(dtype)     # [B,nc,Qi,Qj,G,Hg]
    y = jnp.einsum("bzijgh,bzjghp->bzighp", weights, xdt,
                   preferred_element_type=F32)

    # -- what each chunk hands on -------------------------------------------
    to_end = jnp.where((seg == seg[:, :, -1:])[..., None, None],
                       jnp.exp(cs[:, :, -1:] - cs), 0.0)
    added = jnp.einsum("bzjghp,bzjgn->bzghpn",
                       (xdt * to_end[..., None]).astype(dtype), b,
                       preferred_element_type=F32)      # [B,nc,G,Hg,P,N]
    kept = jnp.where((seg[:, :, -1] == seg_in)[..., None, None],
                     jnp.exp(cs[:, :, -1]), 0.0)        # [B, nc, G, Hg]

    def hand_on(h, inputs):
        kept_z, added_z = inputs
        return h * kept_z[..., None, None] + added_z, h

    state = state.reshape(B, G, H // G, P, N)
    state, entering = jax.lax.scan(
        hand_on, state, (jnp.moveaxis(kept, 1, 0), jnp.moveaxis(added, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # [B,nc,G,Hg,P,N]

    # -- the incoming state's part of each step's output ----------------------
    from_in = jnp.where((seg == seg_in[:, :, None])[..., None, None],
                        jnp.exp(cs), 0.0)               # [B, nc, Q, G, Hg]
    y = y + from_in[..., None] * jnp.einsum(
        "bzign,bzghpn->bzighp", c, entering.astype(dtype),
        preferred_element_type=F32)
    return y.reshape(B, nc, Q, H, P), state.reshape(B, H, P, N)


class _Mamba2(nn.Module):
    """``M``: see the module's docstring. ``carry`` is ``(look-back [B,
    K-1, channels], state [B, H, P, N])``."""

    cfg: CoreConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, u: Array, seg: Array, carry):
        cfg = self.cfg
        H, P, G, N, K = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                         cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel)
        inner, hidden = H * P, u.shape[-1]
        channels = inner + 2 * G * N
        B, T = u.shape[:2]
        w_in = self.param("in_proj", _normal(hidden ** -0.5),
                          (hidden, inner + channels + H))
        conv_w = self.param("conv_kernel", _normal(K ** -0.5), (K, channels))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (channels,))
        # dt = softplus(dt_bias) spread log-uniformly over [1e-3, 1e-1]
        dt_bias = self.param(
            "dt_bias", lambda key, shape: _inverse_softplus(jnp.exp(
                jax.random.uniform(key, shape, F32, math.log(1e-3),
                                   math.log(1e-1)))), (H,))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jnp.arange(1, shape[0] + 1, dtype=F32)), (H,))
        d_skip = self.param("D", nn.initializers.ones, (H,))
        norm_w = self.param("norm", nn.initializers.ones, (inner,))
        w_out = self.param("out_proj", _normal(inner ** -0.5),
                           (inner, hidden))
        tail, state = carry
        # whole chunks of Q steps, each a row of its own from the first
        # product to the last: [B, nc, Q, ...]; only the convolution's
        # look-back and the scan's state pass from a chunk to the next
        Q = min(cfg.chunk_size, T)
        pad = -T % Q
        nc = (T + pad) // Q

        def chunks(v, **how):
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2),
                        **how) if pad else v
            return v.reshape((B, nc, Q) + v.shape[2:])

        with jax.named_scope("ssm"):
            live = chunks(jnp.ones((B, T), jnp.bool_))
            seg_z = chunks(seg, mode="edge")
            z, *xbc, dt = split_products(
                chunks(u.astype(self.dtype)), w_in.astype(self.dtype),
                (inner, 2 * inner, 2 * inner + G * N, inner + channels))
            # which steps a tap may read: step i - d lies in i's segment
            # (the look-back is segment 0); a chunk's first taps read the
            # chunk before it
            keep = min(T, K - 1)
            seg_before = jnp.zeros((B, 1, K - 1), seg.dtype)
            seg_last = jnp.concatenate(
                [seg_before[:, 0, keep:], seg[:, T - keep:]], axis=1)
            if nc > 1:
                seg_before = jnp.concatenate(
                    [seg_before, seg_z[:, :-1, Q - (K - 1):]], axis=1)

            def taps(v, v_before):
                """For d = 0 .. K-1, what step i - d of every chunk holds."""
                joined = jnp.concatenate([v_before, v], axis=2)
                return [joined[:, :, K - 1 - d:K - 1 - d + Q]
                        for d in range(K)]

            reads = [(tap == seg_z)[..., None]
                     for tap in taps(seg_z, seg_before)]

            def convolved(v, lo):
                """Channels lo: of ``xBC`` from their product ``v``: the
                causal depthwise convolution and silu; and the look-back
                they hand on, the last K-1 steps before T."""
                hi = lo + v.shape[-1]
                before = tail.astype(F32)[:, None, :, lo:hi]
                if nc > 1:
                    before = jnp.concatenate(
                        [before, v[:, :-1, Q - (K - 1):]], axis=1)
                conv = conv_b[lo:hi]
                for d, tap in enumerate(taps(v, before)):
                    conv = conv + conv_w[K - 1 - d, lo:hi] * jnp.where(
                        reads[d], tap, 0.0)
                last = jnp.concatenate(
                    [before[:, 0, keep:],
                     v.reshape(B, nc * Q, hi - lo)[:, T - keep:T]], axis=1)
                return jax.nn.silu(conv), last

            (x, b, c), last = zip(*(
                convolved(v, lo)
                for v, lo in zip(xbc, (0, inner, inner + G * N))))
            new_tail = jnp.where((seg_last == seg[:, -1:])[..., None],
                                 jnp.concatenate(last, axis=-1), 0.0)
            x = x.reshape(B, nc, Q, H, P)
            dt = jnp.where(live[..., None],
                           jax.nn.softplus(dt + dt_bias), 0.0)
            y, state = ssd_chunked(
                x, dt, -jnp.exp(a_log), b.reshape(B, nc, Q, G, N),
                c.reshape(B, nc, Q, G, N), seg_z, state, self.dtype)
            y = (y + d_skip[:, None] * x).reshape(B, nc, Q, inner)
            y = grouped_rms_norm(y * jax.nn.silu(z), norm_w, cfg.norm_eps, G)
            out = jnp.dot(y.astype(self.dtype), w_out.astype(self.dtype),
                          preferred_element_type=F32)
            out = out.reshape(B, nc * Q, hidden)[:, :T]
        return out, (new_tail, state)


def _inverse_softplus(x: Array) -> Array:
    return x + jnp.log(-jnp.expm1(-x))


def gated(act, u16: Array, w_gate: Array, up: Array) -> Array:
    """``act(u W_gate) * up`` for ``up = u W_up``, float32 out: what stands
    between the two products of a gated MLP; ``w_gate`` in the operands'
    type, any trailing axes taken as columns."""
    return act(jnp.dot(
        u16, w_gate.reshape(u16.shape[-1], -1),
        preferred_element_type=F32)) * up


#: ``expert_act``: what gates an expert's up-projection; None: no gate
#: matrix, ``relu(up)^2``.
GATE_ACTS = {"relu2": None, "silu": jax.nn.silu, "relu": jax.nn.relu}


def route(logits: Array, bias: Array, k: int, scale: float, rule: str):
    """``(chosen [.., k] int32, gates [.., k] float32)`` by
    ``CoreConfig.router_scores``' ``rule``. "sigmoid": sigmoid scores, the
    top k of score + bias chosen, the chosen scores normalised to sum to
    ``scale``; the bias only chooses. "softmax": the top k of the logits, a
    softmax over those k (what a softmax over all of them gives once the
    chosen are normalised to sum to 1); it takes neither bias nor scale."""
    if rule == "softmax":
        picked, chosen = jax.lax.top_k(logits.astype(F32), k)
        return chosen, jax.nn.softmax(picked, axis=-1)
    scores = jax.nn.sigmoid(logits.astype(F32))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True) * scale


class _Experts(nn.Module):
    """``E``: see the module's docstring. No state. Sows two counters of
    the routing into the ``routing`` collection where it is mutable: the
    share of the tokens' choices that fall on held experts, and the busiest
    held expert's load over the held experts' mean."""

    cfg: CoreConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, u: Array, seg: Array, carry, logits=None):
        cfg = self.cfg
        hidden = u.shape[-1]
        held = jnp.asarray(cfg.experts_held, jnp.int32)
        E, width = len(cfg.experts_held), cfg.moe_intermediate_size
        shared = cfg.moe_shared_expert_intermediate_size
        gate_act = GATE_ACTS[cfg.expert_act]
        # ``router_ahead``: the sublayer before made the logits (``_Layer``).
        # Two sites for the one product: moving the leaf out of here would
        # change the parameter trees of the cores whose router reads ``u``.
        w_router = (None if cfg.router_ahead else self.param(
            "router", _normal(hidden ** -0.5),
            (hidden, cfg.n_routed_experts)))
        bias = (self.param("e_score_correction_bias", nn.initializers.zeros,
                           (cfg.n_routed_experts,))
                if cfg.router_bias else 0.0)
        # [hidden, E, width] / [E, width, hidden]: both reshape to the one
        # matrix over all held experts without moving a byte
        w_up = self.param("experts_up", _normal(hidden ** -0.5),
                          (hidden, E, width))
        w_down = self.param("experts_down", _normal(width ** -0.5),
                            (E, width, hidden))
        if shared:
            s_up = self.param("shared_up", _normal(hidden ** -0.5),
                              (hidden, shared))
            s_down = self.param("shared_down", _normal(shared ** -0.5),
                                (shared, hidden))
        u16 = u.astype(self.dtype)

        def activation(up, name, shape):
            """What stands between an expert's two products, over all the
            columns of ``up = u W_up``: ``relu(up)^2``, or gated ``act(u
            W_gate) * up`` with a gate matrix ``name`` shaped as ``W_up``."""
            if gate_act is None:
                return jnp.square(jax.nn.relu(up))
            w_gate = self.param(name, _normal(hidden ** -0.5), shape)
            return gated(gate_act, u16, w_gate.astype(self.dtype), up)

        with jax.named_scope("moe_router"):
            if w_router is not None:
                logits = jnp.dot(u.astype(F32), w_router,
                                 precision=jax.lax.Precision.HIGHEST)
            chosen, gates = route(logits, bias, cfg.num_experts_per_tok,
                                  cfg.routed_scaling_factor,
                                  cfg.router_scores)
            on_held = chosen[..., None] == held          # [.., k, E]
            gate_of = jnp.sum(jnp.where(on_held, gates[..., None], 0.0),
                              axis=-2)                   # [.., E]
            if (self.is_mutable_collection("routing")
                    and not self.is_initializing()):
                load = jnp.sum(on_held.astype(F32),
                               axis=tuple(range(on_held.ndim - 1)))
                self.sow("routing", "held_share",
                         jnp.sum(load) / chosen.size)
                self.sow("routing", "busiest_over_mean",
                         jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9))
        with jax.named_scope("moe_routed"):
            act = activation(jnp.dot(
                u16, w_up.astype(self.dtype).reshape(hidden, E * width),
                preferred_element_type=F32), "experts_gate", w_up.shape)
            # each expert's block of columns times its gate, as selects
            # over the column index: a view of the columns as [E, width]
            # would be a relayout of the whole activation wherever width
            # is no multiple of the 128 lanes (1856 is not)
            block = jnp.arange(E * width) // width
            gate_wide = sum(jnp.where(block == e, gate_of[..., e:e + 1], 0.0)
                            for e in range(E))
            routed = jnp.dot(
                (act * gate_wide).astype(self.dtype),
                w_down.astype(self.dtype).reshape(E * width, hidden),
                preferred_element_type=F32)
        if not shared:
            return routed, carry
        with jax.named_scope("moe_shared"):
            act = activation(jnp.dot(
                u16, s_up.astype(self.dtype), preferred_element_type=F32),
                "shared_gate", s_up.shape)
            out = routed + jnp.dot(act.astype(self.dtype),
                                   s_down.astype(self.dtype),
                                   preferred_element_type=F32)
        return out, carry


class _Attention(nn.Module):
    """``*``: see the module's docstring. ``carry`` is ``(keys, values [B,
    history, KV, D], valid [B, history])`` of the steps before this call,
    oldest first."""

    cfg: CoreConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, u: Array, seg: Array, carry):
        cfg = self.cfg
        heads, kv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                        cfg.head_dim)
        hidden = u.shape[-1]
        B, T = u.shape[:2]
        w_q = self.param("q_proj", _normal(hidden ** -0.5),
                         (hidden, heads * D))
        w_k = self.param("k_proj", _normal(hidden ** -0.5), (hidden, kv * D))
        w_v = self.param("v_proj", _normal(hidden ** -0.5), (hidden, kv * D))
        w_o = self.param("o_proj", _normal((heads * D) ** -0.5),
                         (heads * D, hidden))
        old_k, old_v, old_valid = carry

        with jax.named_scope("attention"):
            u16 = u.astype(self.dtype)

            def project(w, n):
                return jnp.dot(u16, w.astype(self.dtype),
                               preferred_element_type=F32).reshape(B, T, n, D)

            q = project(w_q, heads).reshape(B, T, kv, heads // kv, D)
            keys = jnp.concatenate([old_k, project(w_k, kv)], axis=1)
            values = jnp.concatenate([old_v, project(w_v, kv)], axis=1)
            # a query sees the cached steps while no episode has opened
            # since (segment 0), and this call's steps of its own segment
            # up to itself
            see_old = jnp.logical_and(old_valid[:, None, :] > 0,
                                      seg[:, :, None] == 0)
            see_new = jnp.logical_and(
                seg[:, :, None] == seg[:, None, :],
                jnp.tril(jnp.ones((T, T), jnp.bool_)))
            see = jnp.concatenate([see_old, see_new], axis=2)   # [B, T, S]
            scores = jnp.einsum(
                "btkgd,bskd->bkgts", q.astype(self.dtype),
                keys.astype(self.dtype),
                preferred_element_type=F32) * D ** -0.5
            scores = jnp.where(see[:, None, None], scores, -jnp.inf)
            attended = jnp.einsum(
                "bkgts,bskd->btkgd",
                jax.nn.softmax(scores, axis=-1).astype(self.dtype),
                values.astype(self.dtype), preferred_element_type=F32)
            out = jnp.dot(
                attended.reshape(B, T, heads * D).astype(self.dtype),
                w_o.astype(self.dtype), preferred_element_type=F32)
            # the last ``history`` steps stay, those of the segment the
            # call ends in
            valid = jnp.concatenate(
                [old_valid * (seg[:, -1:] == 0),
                 (seg == seg[:, -1:]).astype(F32)], axis=1)
            valid = valid[:, T:]
            live = valid[:, :, None, None]      # an empty slot holds zeros
            carry = (keys[:, T:] * live, values[:, T:] * live, valid)
        return out, carry


def rotary_frequencies(rope: RopeConfig, head_dim: int) -> np.ndarray:
    """``inv_freq [d / 2]`` (float64) of a rotary embedding over the first
    ``d = rotary_factor * head_dim`` dims: ``theta^(-2i/d)``; under YaRN
    (``yarn_factor`` > 0) divided by the factor where a dim turns fewer
    than ``beta_slow`` times in ``original_positions`` steps, left alone
    where it turns more than ``beta_fast`` times, a linear ramp over the
    dims between."""
    d = int(head_dim * rope.rotary_factor)
    i = np.arange(d // 2, dtype=np.float64)
    inv_freq = rope.theta ** (-2.0 * i / d)
    if not rope.yarn_factor:
        return inv_freq

    def dim_turning(turns: float) -> float:
        return (d * math.log(rope.original_positions / (turns * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(dim_turning(rope.beta_fast)), 0)
    high = min(math.ceil(dim_turning(rope.beta_slow)), d - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * inv_freq + ramp * inv_freq / rope.yarn_factor


def rotary_tables(position: Array, rope: RopeConfig, head_dim: int):
    """``(cos, sin) [B, T, 1, d / 2]`` float32 of ``position [B, T]`` (steps
    since the episode opened), each times ``attention_factor``."""
    angle = (position.astype(F32)[..., None, None]
             * jnp.asarray(rotary_frequencies(rope, head_dim), F32))
    return (jnp.cos(angle) * rope.attention_factor,
            jnp.sin(angle) * rope.attention_factor)


def rotate(x: Array, tables) -> Array:
    """``x [B, T, n, D]`` float32 rotated by ``rotary_tables``, rotate-half
    layout: dim i of the first half of the rotary dims pairs with dim i of
    their second half; the dims past them pass as they are."""
    cos, sin = tables
    half = cos.shape[-1]
    x1, x2, rest = (x[..., :half], x[..., half:2 * half], x[..., 2 * half:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


class _RotaryAttention(nn.Module):
    """``F`` / ``W``: see the module's docstring. ``carry`` is ``(keys,
    values [B, history, KV, D], steps [B])``: a ring of the lane's rotated
    keys and its values, step p of the episode in slot ``p mod history``,
    and how many steps the episode has had. A learner's window reads an
    ``F`` layer's ring as it lies and a ``W`` layer's turned into the order
    of time (``window_keys``)."""

    cfg: CoreConfig
    dtype: jnp.dtype
    heads: int
    windowed: bool

    @nn.compact
    def __call__(self, u: Array, seg: Array, carry):
        cfg = self.cfg
        heads, kv, D = self.heads, cfg.num_key_value_heads, cfg.head_dim
        rope = cfg.rope_window if self.windowed else cfg.rope_full
        hidden = u.shape[-1]
        B, T = u.shape[:2]
        w_q = self.param("q_proj", _normal(hidden ** -0.5),
                         (hidden, heads * D))
        w_k = self.param("k_proj", _normal(hidden ** -0.5), (hidden, kv * D))
        w_v = self.param("v_proj", _normal(hidden ** -0.5), (hidden, kv * D))
        w_g = (self.param("g_proj", _normal(hidden ** -0.5), (hidden, heads))
               if cfg.attention_gate else None)
        w_o = self.param("o_proj", _normal((heads * D) ** -0.5),
                         (heads * D, hidden))
        old_k, old_v, steps = carry
        history = old_k.shape[1]

        def attend(q, keys, values, see):
            """``q [B, Q, KV, G, D]`` over ``keys, values [B, S, KV, D]``
            where ``see [B, Q, S]``: softmax in float32. A row that sees
            nothing (padding) comes out as a mean, not as NaN."""
            scores = jnp.einsum(
                "bqkgd,bskd->bkgqs", q.astype(self.dtype),
                keys.astype(self.dtype),
                preferred_element_type=F32) * D ** -0.5
            scores = jnp.where(see[:, None, None], scores, -1e30)
            return jnp.einsum(
                "bkgqs,bskd->bqkgd",
                jax.nn.softmax(scores, axis=-1).astype(self.dtype),
                values.astype(self.dtype), preferred_element_type=F32)

        with (jax.named_scope("attention_window") if self.windowed
              else jax.named_scope("attention_full")):
            u16 = u.astype(self.dtype)

            def project(w, n):
                return jnp.dot(u16, w.astype(self.dtype),
                               preferred_element_type=F32).reshape(B, T, n, D)

            # a step's position in its episode: the counter goes on while
            # no episode has opened in this call, and restarts where one has
            index = jnp.arange(T)
            opened = jax.lax.cummax(jnp.where(
                jnp.diff(seg, axis=1, prepend=0) > 0, index, -1), axis=1)
            position = jnp.where(seg == 0,
                                 steps.astype(jnp.int32)[:, None] + index,
                                 index - opened)            # [B, T]
            # a kind that rotates none of its dims has no position embedding
            tables = (rotary_tables(position, rope, D)
                      if rope.rotary_factor else None)

            def turned(x):
                return x if tables is None else rotate(x, tables)

            q = project(w_q, heads)                 # not rotated yet

            def grouped(q):
                return q.reshape(B, T, kv, heads // kv, D)

            new_k = turned(project(w_k, kv))
            new_v = project(w_v, kv)
            if w_g is not None:
                gate = jax.nn.sigmoid(jnp.dot(u16, w_g.astype(self.dtype),
                                              preferred_element_type=F32))
            use_kernel, interpret = loop_common.pallas_routing(True)
            if T == 1 and history:
                # acting: the new key takes its slot, then the one query
                # reads the ring; what the ring holds is what it may see.
                # On a TPU the float32 ring is read once, where it lies
                # (``pallas_attention.decode``); ``attend`` casts all of it
                # in front of its products
                lanes, slot = jnp.arange(B), position[:, 0] % history
                ring_k = old_k.at[lanes, slot].set(new_k[:, 0])
                ring_v = old_v.at[lanes, slot].set(new_v[:, 0])
                if use_kernel:
                    count = jnp.minimum(position[:, 0] + 1, history)  # [B]
                    attended = pallas_attention.decode(
                        grouped(turned(q))[:, 0], ring_k, ring_v, count,
                        self.dtype, interpret=interpret)
                else:
                    see = (jnp.arange(history)
                           < jnp.minimum(position + 1, history))   # [B, S]
                    attended = attend(grouped(turned(q)), ring_k, ring_v,
                                      see[:, None])
            else:
                # a learner's window: the fused kernels on a TPU — the
                # queries rotated on their way into the kernels' layout —
                # the plain blocks anywhere else
                # (``loop_common.pallas_routing``)
                keys, values, key_position, key_seg = self.window_keys(
                    new_k, new_v, position, seg, carry)
                if use_kernel:
                    attended = pallas_attention.attend(
                        grouped(q), keys, values, position, seg,
                        key_position, key_seg, history=history,
                        window=cfg.sliding_window if self.windowed else None,
                        dtype=self.dtype, interpret=interpret, rotary=tables)
                else:
                    attended = self.blockwise(
                        jax.checkpoint(attend), grouped(turned(q)),
                        keys, values, position, seg, key_position, key_seg)
                ring_k, ring_v = self.ring_after(new_k, new_v, position,
                                                 opened[:, -1], carry)
            attended = attended.reshape(B, T, heads, D)
            if w_g is not None:
                attended = attended * gate[..., None]
            out = jnp.dot(
                attended.reshape(B, T, heads * D).astype(self.dtype),
                w_o.astype(self.dtype), preferred_element_type=F32)
            carry = (ring_k, ring_v, (position[:, -1] + 1).astype(F32))
        return out, carry

    def window_keys(self, new_k, new_v, position, seg, carry):
        """The keys a learner's window attends over, ``[ring || this
        call's]``: ``(keys, values [B, history + T, KV, D], position, segment
        [B, history + T])``. The ring's keys lie in segment 0 at the
        positions the counter gives them; one the episode has not reached
        has the segment no query has (``pallas_attention.INVALID_KEY``).

        An ``F`` layer's ring comes as it lies: slot j holds the last
        position below ``steps`` that is j mod history. A ``W`` layer's comes
        in the ORDER OF TIME, right-aligned: index i holds position ``steps -
        history + i`` (slot ``(steps + i) mod history``), invalid where that
        is negative. A key of segment 0 then lies at index ``position +
        history - steps`` whether the ring or this call holds it, and a
        later segment's keys are this call's, a step an index: between a
        query and a key of its segment the distance of the indices is the
        distance of the positions — whether the ring is full, wrapped by
        acting, cut by a reset in the burn-in or empty — and the band a
        window layer reads is a range of indices
        (``pallas_attention.key_ranges``)."""
        old_k, old_v, steps = carry
        history = old_k.shape[1]
        steps = steps.astype(jnp.int32)[:, None]
        slots = jnp.arange(history)
        if self.windowed:
            old_position = steps - history + slots              # [B, history]
            old_seg = jnp.where(old_position >= 0, 0,
                                pallas_attention.INVALID_KEY)
            slot = ((steps + slots) % max(history, 1))[..., None, None]
            old_k, old_v = (
                jnp.take_along_axis(ring, slot, axis=1,
                                    mode="promise_in_bounds")
                for ring in (old_k, old_v))
        else:
            old_position = steps - 1 - (steps - 1 - slots) % max(history, 1)
            old_seg = jnp.where(slots < jnp.minimum(steps, history), 0,
                                pallas_attention.INVALID_KEY)
        return (jnp.concatenate([old_k, new_k], axis=1),
                jnp.concatenate([old_v, new_v], axis=1),
                jnp.concatenate([old_position, position], axis=1),
                jnp.concatenate([old_seg.astype(seg.dtype), seg], axis=1))

    def blockwise(self, attend, q, keys, values, position, seg,
                  key_position, key_seg):
        """The attended values ``[B, T, KV, G, D]`` of T steps over
        ``window_keys``, a block of ``QUERY_BLOCK`` queries at a time (fewer
        where the window or the call is shorter). A key is (position,
        segment); query t sees the keys of its segment at positions up to
        its own and, in a ``W`` layer, less than ``sliding_window`` below
        it. Which keys a block reads at all is the kernels' rule
        (``pallas_attention.key_ranges``): a ``W`` layer's keys lie in the
        order of time, so its band is a range of indices.

        Plain ``jax.numpy``: every block's scores go through HBM. It is the
        path of every backend but a TPU, where ``ops/pallas_attention.py``
        computes the same rule in VMEM, and the oracle that kernel is held
        to (``tests/test_pallas_attention.py``)."""
        B, T = position.shape
        history, window = keys.shape[1] - T, self.cfg.sliding_window
        block = min(window, T, QUERY_BLOCK)
        pad = -T % block

        def padded(v, value=0):
            return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2),
                           constant_values=value) if pad else v

        q, keys, values, position, seg, key_position = (
            padded(v) for v in (q, keys, values, position, seg, key_position))
        key_seg = padded(key_seg, pallas_attention.INVALID_KEY)
        out = []
        for lo in range(0, T + pad, block):
            # the keys a block can see at all: an ``F`` block everything
            # up to itself, a ``W`` block those less than a window in front
            # of its first query (``window_keys``: index distance is
            # position distance)
            first = (max(0, history + lo - window + 1) if self.windowed
                     else 0)
            last = history + lo + block
            below = (position[:, lo:lo + block, None]
                     - key_position[:, None, first:last])
            see = ((key_seg[:, None, first:last]
                    == seg[:, lo:lo + block, None]) & (below >= 0))
            if self.windowed:
                see = see & (below < window)
            out.append(attend(q[:, lo:lo + block], keys[:, first:last],
                              values[:, first:last], see))
        return jnp.concatenate(out, axis=1)[:, :T]

    def ring_after(self, new_k, new_v, position, opened, carry):
        """The ring after T steps: slot j holds the last position of the
        episode the call ends in that is j mod history — one of this call's
        steps where the call reaches back that far, else what the slot
        held (valid only if no episode opened in the call, and then the
        counter says so)."""
        old_k, old_v, _ = carry
        history, T = old_k.shape[1], position.shape[1]
        if not history:
            return old_k, old_v
        last = position[:, -1:]                              # [B, 1]
        back = (last - jnp.arange(history)) % history        # steps back
        source = T - 1 - back                                # [B, history]
        from_new = source >= jnp.maximum(opened, 0)[:, None]
        pick = jnp.clip(source, 0, T - 1)[..., None, None]
        return tuple(
            jnp.where(from_new[..., None, None],
                      jnp.take_along_axis(new, pick, axis=1), old)
            for new, old in ((new_k, old_k), (new_v, old_v)))


class _DenseMLP(nn.Module):
    """``D``: see the module's docstring. No state."""

    cfg: CoreConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, u: Array, seg: Array, carry):
        hidden, width = u.shape[-1], self.cfg.intermediate_size
        w_gate = self.param("gate_proj", _normal(hidden ** -0.5),
                            (hidden, width))
        w_up = self.param("up_proj", _normal(hidden ** -0.5),
                          (hidden, width))
        w_down = self.param("down_proj", _normal(width ** -0.5),
                            (width, hidden))
        with jax.named_scope("mlp_dense"):
            u16 = u.astype(self.dtype)
            act = gated(jax.nn.silu, u16, w_gate.astype(self.dtype), jnp.dot(
                u16, w_up.astype(self.dtype), preferred_element_type=F32))
            out = jnp.dot(act.astype(self.dtype), w_down.astype(self.dtype),
                          preferred_element_type=F32)
        return out, carry


_MIXERS = {"M": _Mamba2, "E": _Experts, "*": _Attention, "D": _DenseMLP,
           "F": functools.partial(_RotaryAttention, windowed=False),
           "W": functools.partial(_RotaryAttention, windowed=True)}
#: The letters whose sublayer is a ``_RotaryAttention``: each takes its
#: head count from ``attention_heads_per_layer``, in the pattern's order.
ROTARY = "FW"
#: Queries a block of ``_RotaryAttention.blockwise``: the kernels' tile.
QUERY_BLOCK = pallas_attention.TILES.bq


def applied(cfg: CoreConfig) -> str:
    """The kind of every sublayer APPLICATION of one forward step, in the
    carry's order: the pattern once a turn (the module's docstring, "Loops");
    entry ``r * len(pattern) + l`` is the state of sublayer l in turn r."""
    return cfg.pattern * cfg.loops


def rotary_heads(cfg: CoreConfig) -> Tuple[int, ...]:
    """Query heads of every sublayer of the pattern; 0 where the sublayer
    is no ``F`` or ``W``."""
    count = iter(cfg.attention_heads_per_layer)
    return tuple(next(count) if kind in ROTARY else 0
                 for kind in cfg.pattern)


def routes_ahead(cfg: CoreConfig) -> Tuple[bool, ...]:
    """Which sublayers of the pattern make the routing of the ``E`` sublayer
    behind them (``router_ahead``): the one before each."""
    if not cfg.router_ahead:
        return (False,) * len(cfg.pattern)
    if cfg.pattern.startswith("E") or "EE" in cfg.pattern:
        raise ValueError(
            f"router_ahead: an E sublayer of {cfg.pattern!r} has no "
            "sublayer of another kind before it to route from")
    return tuple(later == "E" for later in cfg.pattern[1:]) + (False,)


class _Layer(nn.Module):
    """``x + mixer(RMSNorm(x))`` — under ``sandwich_norm`` ``x +
    RMSNorm'(mixer(RMSNorm(x)))``, the second norm's weights the leaf
    ``norm_out`` beside ``norm`` — a module of its own so that ``nn.remat``
    can wrap it: a layer's activations are then recomputed in the backward
    pass, and only its input lives through the loss — and, under
    ``router_ahead``, the routing that crosses from a sublayer to the
    experts behind it: ``routes`` says this sublayer's normed input is what
    the NEXT sublayer's router reads, so the router's weights (``router``,
    beside ``norm``) and its float32 logits ``[B, T, routed]`` are made
    here and returned; the experts' sublayer is handed them (``routing``)
    and hands on none."""

    kind: str
    cfg: CoreConfig
    dtype: jnp.dtype
    heads: int = 0      # an ``F`` or ``W`` sublayer's query heads
    routes: bool = False

    @nn.compact
    def __call__(self, x: Array, seg: Array, carry, routing=None):
        scale = self.param("norm", nn.initializers.ones, (x.shape[-1],))
        its_own = {"heads": self.heads} if self.kind in ROTARY else {}
        u = rms_norm(x, scale, self.cfg.norm_eps)
        out, carry = _MIXERS[self.kind](
            self.cfg, self.dtype, name="mixer", **its_own)(
                u, seg, carry, *(() if routing is None else (routing,)))
        if self.cfg.sandwich_norm:
            out = rms_norm(out, self.param(
                "norm_out", nn.initializers.ones, (x.shape[-1],)),
                self.cfg.norm_eps)
        if not self.routes:
            return x + out, carry, None
        w_router = self.param("router", _normal(x.shape[-1] ** -0.5),
                              (x.shape[-1], self.cfg.n_routed_experts))
        with jax.named_scope("moe_router"):
            logits = jnp.dot(u, w_router,
                             precision=jax.lax.Precision.HIGHEST)
        return x + out, carry, logits


class _Core(nn.Module):
    """The stack of layers and the final norm over ``[B, T, hidden]``: ONE
    turn of a looped core (``HybridQNetwork.turns`` applies it ``loops``
    times), ``carry`` that turn's entries, one a sublayer."""

    cfg: CoreConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: Array, reset: Array, carry):
        seg = segments(reset)
        new_carry = []
        routing = None
        for i, (kind, heads, routes) in enumerate(zip(
                self.cfg.pattern, rotary_heads(self.cfg),
                routes_ahead(self.cfg))):
            x, layer_carry, routing = nn.remat(_Layer)(
                kind, self.cfg, self.dtype, heads, routes,
                name=f"layer_{i}")(x, seg, carry[i], routing)
            new_carry.append(layer_carry)
        scale = self.param("norm_f", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, scale, self.cfg.norm_eps), tuple(new_carry)


class HybridQNetwork(nn.Module):
    """Torso -> Dense ``hidden`` -> hybrid sequence core -> dueling heads;
    ``models/recurrent.py RecurrentQNetwork``'s two entry points and
    ``(new_carry, q)`` returns."""

    num_actions: int
    core: CoreConfig
    torso: str = "nature"
    mlp_features: Tuple[int, ...] = (256, 256)
    hidden: int = 2688
    dueling: bool = True
    compute_dtype: jnp.dtype = jnp.float32
    remat_torso: bool = False

    @property
    def sows_routing(self) -> bool:
        """An expert layer sows its counters (``_Experts``)."""
        return "E" in self.core.pattern

    def initial_state(self, batch_size: int, history: Optional[int] = None):
        """The empty state of ``batch_size`` lanes, one entry a sublayer a
        turn (``applied``);
        ``history``: steps of keys and values a ``*`` or ``F`` layer keeps
        (default ``attention_window``, what acting carries); a ``W`` layer
        keeps ``sliding_window``, whoever asks."""
        cfg = self.core
        if history is None:
            history = cfg.attention_window
        inner = cfg.mamba_num_heads * cfg.mamba_head_dim
        channels = inner + 2 * cfg.n_groups * cfg.ssm_state_size
        cache = (batch_size, history, cfg.num_key_value_heads, cfg.head_dim)
        band = (batch_size, cfg.sliding_window) + cache[2:]

        def zeros(*shape):
            return jnp.zeros(shape, F32)

        return tuple({
            "M": lambda: (zeros(batch_size, cfg.conv_kernel - 1, channels),
                          zeros(batch_size, cfg.mamba_num_heads,
                                cfg.mamba_head_dim, cfg.ssm_state_size)),
            "E": lambda: (),
            "D": lambda: (),
            "*": lambda: (zeros(*cache), zeros(*cache),
                          zeros(batch_size, history)),
            "F": lambda: (zeros(*cache), zeros(*cache), zeros(batch_size)),
            "W": lambda: (zeros(*band), zeros(*band), zeros(batch_size)),
        }[kind]() for kind in applied(cfg))

    def state_bytes_a_lane(self) -> dict:
        """Bytes of one lane's acting state by kind of cache (the scope a
        kind's mixer enters): the sum over the sublayers of that kind, every
        turn of a looped core counted; kinds that keep nothing are left
        out."""
        names = {"M": "ssm", "*": "attention", "F": "attention_full",
                 "W": "attention_window"}
        found: dict = {}
        lane = jax.eval_shape(lambda: self.initial_state(1))
        for kind, layer in zip(applied(self.core), lane):
            for leaf in jax.tree.leaves(layer):
                found[names[kind]] = (found.get(names[kind], 0)
                                      + leaf.size * leaf.dtype.itemsize)
        return found

    def attention_ring_bytes(self, lanes: int) -> dict:
        """``{"window" | "full": (read, copied)}``: the bytes of rings —
        keys and values, summed over the layers of a kind and the turns of a
        looped core — that ONE acting
        step of ``lanes`` lanes reads, and the bytes of ring-sized copies
        the path it takes writes on the way: none through
        ``pallas_attention.decode`` (a TPU), the rings once more in the
        compute type where ``attend`` casts them in front of its products.
        Empty for a core without such layers."""
        found: dict = {}
        width = jnp.dtype(self.compute_dtype).itemsize
        in_place = loop_common.pallas_routing(True)[0]
        state = jax.eval_shape(lambda: self.initial_state(lanes))
        for kind, layer in zip(applied(self.core), state):
            if kind not in ROTARY:
                continue
            name = "window" if kind == "W" else "full"
            read, copied = found.get(name, (0, 0))
            for ring in layer[:2]:
                read += ring.size * ring.dtype.itemsize
                if not in_place and width != ring.dtype.itemsize:
                    copied += ring.size * width
            found[name] = (read, copied)
        return found

    def attention_key_blocks(self, windows: int, burn_in: int,
                             steps: int) -> dict:
        """``{"window" | "full": (visited, skipped)}``: the key blocks the
        fused kernels' forward grids (``ops/pallas_attention.py``) read and
        leave out in ONE forward pass of a learner's batch through the core
        — the burn-in call from the empty state, then the call over the
        other ``steps`` — summed over the layers of a kind (every turn of a
        looped core), their KV heads and the batch's ``windows``: an ``F``
        layer's grid is the causal
        triangle, a ``W`` layer's the band a window wide (``key_ranges``;
        its keys come in the order of time). A grad step runs those grids
        for both networks and once more under ``nn.remat``. Empty where the
        learner takes ``blockwise`` (no TPU), and for a core without such
        layers."""
        cfg = self.core
        found: dict = {}
        if not loop_common.pallas_routing(True)[0]:
            return found
        for kind in applied(cfg):
            if kind not in ROTARY:
                continue
            windowed = kind == "W"
            name = "window" if windowed else "full"
            visited, skipped = found.get(name, (0, 0))
            for T in (burn_in, steps):
                if not T:
                    continue
                # the ring either call is handed: ``window_state``'s
                read, left_out = pallas_attention.key_block_census(
                    T, cfg.sliding_window if windowed else burn_in,
                    cfg.sliding_window if windowed else None)
                visited += windows * cfg.num_key_value_heads * read
                skipped += windows * cfg.num_key_value_heads * left_out
            found[name] = (visited, skipped)
        return found

    def rotary_head_rows(self, windows: int, burn_in: int,
                         steps: int) -> dict:
        """``{"window" | "full": rows}``: the query-head rows (windows x
        steps x query heads, the burn-in call and the call over the other
        ``steps``, summed over the layers of a kind and the turns of a
        looped core) that ONE forward pass
        of a learner's batch sends through the rotary kernel
        (``pallas_attention.attend``'s ``rotary``); 0 for a kind without a
        position embedding, whose queries go to the kernels as they are.
        Empty where the learner takes ``rotate`` (no TPU), and for a core
        without such layers."""
        cfg = self.core
        found: dict = {}
        if not loop_common.pallas_routing(True)[0]:
            return found
        for kind, heads in zip(applied(cfg), rotary_heads(cfg) * cfg.loops):
            if kind in ROTARY:
                name, rope = (("window", cfg.rope_window) if kind == "W"
                              else ("full", cfg.rope_full))
                found[name] = found.get(name, 0) + (
                    windows * (burn_in + steps) * heads
                    if rope.rotary_factor else 0)
        return found

    def reset_state(self, carry, done: Array):
        """``carry`` with the lanes of ``done [B]`` emptied: every leaf
        times ``1 - done``, but an ``F`` or ``W`` layer's ring, which its
        counter alone makes valid and which is left where it lies (0.7 GB
        of the ``laguna_q`` preset's 16 lanes, every acting step)."""
        keep = (~done).astype(F32)

        def emptied(x):
            return x * jax.lax.expand_dims(keep, range(1, x.ndim))

        return tuple(
            layer[:2] + (emptied(layer[2]),) if kind in ROTARY
            else jax.tree.map(emptied, layer)
            for kind, layer in zip(applied(self.core), carry))

    def stored_state(self, carry):
        """What the replay ring keeps of a lane's state with each step:
        nothing — learner windows start from ``window_state``."""
        return ()

    def window_state(self, stored, batch_size: int, burn_in: int):
        """The state a learner's window starts from: empty, the history of
        its ``*`` and ``F`` layers as long as the burn-in that fills it."""
        return self.initial_state(batch_size, history=burn_in)

    def turns(self, x: Array, reset: Array, carry):
        """The core over ``x [B, T, hidden]``: ``_Core`` — one set of
        parameters, ``core`` — applied ``loops`` times, each turn on the turn
        before's normed output and with its own entries of ``carry``
        (``applied``), under the scope ``loops`` where there is more than one.

        The turns are written out, acting's and a learner's window's alike:
        every ring is an array of its own, so an acting step's key goes into
        its slot in place and ``decode`` reads that ring where it lies (a
        scan over a stacked carry would slice a ring out of the stack and
        write it back, 537 MB a lane a step in ``ouro_q``; what one scanned
        body cost a learner's window is ``PERF.md`` §6, PR 53)."""
        cfg = self.core
        core = _Core(cfg, self.compute_dtype, name="core")
        if cfg.loops == 1:
            return core(x, reset, carry)
        per_turn = len(cfg.pattern)
        after = ()
        with jax.named_scope("loops"):
            for r in range(cfg.loops):
                x, its_own = core(
                    x, reset, carry[r * per_turn:(r + 1) * per_turn])
                after += tuple(its_own)
        return x, after

    def __call__(self, carry, obs: Array, reset: Optional[Array] = None):
        """One step: obs [B, ...], reset [B] bool (None = no resets)."""
        carry, q = self.unroll(carry, obs[None],
                               None if reset is None else reset[None])
        return carry, q[0]

    @nn.compact
    def unroll(self, carry, obs: Array, reset: Optional[Array] = None):
        """obs [T, B, ...], reset [T, B]; returns (carry, q [T, B, A]).
        ``reset[t]`` empties the state before step t. The torso runs once
        over the flat [T*B] batch, the core batch-major."""
        T, B = obs.shape[:2]
        if reset is None:
            reset = jnp.zeros((T, B), jnp.bool_)
        embed = nn.remat(_Embed) if self.remat_torso else _Embed
        x = embed(self.torso, self.mlp_features, self.hidden,
                  self.compute_dtype, name="torso")(
                      obs.reshape((T * B,) + obs.shape[2:]))
        x = jnp.swapaxes(x.reshape(T, B, -1), 0, 1)
        x, carry = self.turns(x, reset.T, carry)
        h = jnp.swapaxes(x, 0, 1).reshape(T * B, -1)
        adv = nn.Dense(self.num_actions, name="advantage")(h)
        q = adv
        if self.dueling:
            q = (nn.Dense(1, name="value")(h) + adv
                 - jnp.mean(adv, axis=-1, keepdims=True))
        return carry, q.reshape(T, B, self.num_actions)
