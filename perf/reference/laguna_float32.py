"""Plain float32 reference of one sequence-learner step over the first five
layers of ``laguna`` (``perf/configs/laguna_q.json``; Laguna-XS.2's
``config.json``).

Recurrent replay as in ``r2d2_float32.py`` (Kapturowski et al. 2019: windows
with a burn-in prefix that only refreshes the state, n-step double-Q targets
under the invertible value rescaling, importance weights and the eta-mixed
priority a window) around a recurrent Q-network: convolutions and a dense
layer in front, five published layers — each two residual sublayers, ``h = x
+ Attn(RMSNorm(x))``, ``y = h + MLP(RMSNorm(h))`` — a final RMSNorm and linear
dueling heads. One letter of the pattern a sublayer:

``F``, ``W``  Grouped-query attention as ONE MASKED ``[T, S]`` SOFTMAX a window
       (not the blocks the program computes). ``q = u W_q`` ``[H, 128]``, ``k,
       v = u W_k, u W_v`` ``[8, 128]``, query head h reads KV head ``h // (H /
       8)``. Queries and keys are rotated by the step's position in its
       episode (rotate-half layout). ``W`` (``sliding_attention``): ``inv_freq_i
       = 10000^(-2i/128)``, all 128 dims. ``F`` (``full_attention``): the first
       64 dims, YaRN — ``pos_i = 500000^(2i/64)``; ``low, high = floor, ceil``
       of ``64 ln(4096 / (r 2 pi)) / (2 ln 500000)`` at ``r = 64`` and ``r =
       1``, clamped to ``[0, 63]``; ``ramp_i = clip((i - low) / (high - low),
       0, 1)``; ``inv_freq_i = (1 - ramp_i) / pos_i + ramp_i / (64 pos_i)``;
       cos and sin times ``attention_factor``; the other 64 dims pass
       unrotated. Scores ``q . k / sqrt(128)``; key j is visible to query i iff
       same episode, ``j <= i`` and, in ``W``, ``i - j < 512``; ``a_h = softmax
       . v``; ``out = concat_h(sigmoid(u W_g)_h a_h) W_o``.
``D``  ``W_down (silu(W_gate u) * W_up u)``, width 8,192.
``E``  ``s = sigmoid(u W_r)`` (256), chosen = the top 8 of ``s``, ``w =
       s[chosen] / sum(s[chosen]) * 2.5``; an expert is ``D``'s form at width
       512; the sublayer's output is the published sum over the chosen experts
       THAT ARE HELD (``experts_held``: expert parallelism's share), weights on
       the outputs, plus the shared expert.

Departures from ``config.json``, each also under ``assumed`` or ``reduced`` in
the configuration's file: 5 of 40 layers; 8 HELD of 256 experts (what the
absent 248 would add is left out, as in the program); no token embedding and
no vocabulary head (frames in, action values out); ``gating: true`` read as a
sigmoid gate a head on the attention output; experts and dense MLP as gated
three-matrix ``silu`` MLPs; sigmoid router scores, chosen scores normalised,
no correction bias; no QK-norm; positions count from the step that opened the
episode (only differences enter the scores); a window starts from the EMPTY
state (the program's ring stores none for this core), so its first step is
position 0 whatever the lane's history was.

``reset[t]`` (``obs[t]`` opens an episode) empties every layer's memory before
step t: the keys a query may see, and the position, which restarts at 0. What
leaves the burn-in prefix — each attention sublayer's rotated keys and its
values — is a constant to the gradient.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, one
window a block (exact: every term of the loss belongs to one window), each
sublayer's activations recomputed in its backward (memory, not mathematics);
the parameter tree is read by key names only and nothing is shared with
``models/sequence_core.py`` or ``agents/r2d2.py``. The n-step targets with
their value rescaling are ``r2d2_float32.py``'s, the shared layers
``plain.py``'s, and what the hybrid core's reference already has for any core
— the norm, the torso in front, the gates' normalisation, the seeded batch,
the program's side — is ``twotower_float32.py``'s (imported: names that a
test replaces are looked up in THIS module).

Beside the step: what ``perf/harness/reference_check.py`` asks of every
reference module (``perf/README.md``), and the sequence ring's own check,
which is ``r2d2_float32.make_further_check`` on this configuration's windows
(2,048 steps every 512; the pair it stores is zero wide here) over a ring of
``RING_SLOTS`` time slices: its own 2,048 cannot hold one such window and a
stride.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.plain import (ADAM_B1, CONV_STRIDES, CONVS,  # noqa: F401
                                  adam_delta, clip_by_global_norm, dense,
                                  global_norm)
from perf.reference import r2d2_float32, twotower_float32
from perf.reference.r2d2_float32 import n_step_targets
from perf.reference.twotower_float32 import (gates, leave_burn_in,  # noqa: F401
                                             make_program, rms_norm,
                                             seeded_batch)

# Largest error allowed for each quantity ``reference_check`` compares (its
# docstring defines them; ``q`` is the online network's Q-values at the
# unroll + n_step positions of every window), by the dtype the configuration
# computes in.
#
# bfloat16: from 24 whole checks on the chip at the cell's own widths (PR 46;
# 4 windows x 2,048 steps, 344.8 M parameters; a check compiles for seven
# minutes and runs for one, so not 64 seeds): ``perf/tools/
# reference_study.py --seeds 8 --control 3 --cells laguna_q.preset``, the
# cell's own 9 runs, and after the driver's refusal the refused seed
# (874459851, which reads here what it read there) and 6 fresh ones
# (perf/records/pr46/README.md lists all).
#
# Every number below is an error OVER A SIZE THE SEEDED STATE SETS, and that
# size is what varies. The check's state is three Adam steps from a fresh
# network; each moves every one of 344.8 M parameters by the learning rate,
# so the Q-values travel some units: in 20 of the 24 states they stand 1.4-6
# from the targets (by the reference loss, 0.94-5.3: Huber's |TD| - 0.5) and
# the readings are the narrow ones; in 4 they have landed NEAR the targets
# (reference loss 0.71, 0.11, 0.080, 0.028; in the refused seed Q is 0.02 +-
# 0.11, max |Q| 0.35) and the same absolute errors are read over a tenth of
# the size. Each limit is about three times the largest of the 24, the four
# near states included:
#   grad        0.25-0.49% in 22 states; 1.40% (seed 1618034104: reference
#               loss 0.080) and 2.15% (seed 2147483904: 0.028; |TD| inside
#               Huber's quadratic part, so ``grad_scale`` is small); the
#               control 13.6-16.3%: 2.8 times above the one, 2.3 below the
#               other                                                    -> 6%
#   priorities  0.06-0.42% in 22 states, 0.82% and 2.49% in the near ones
#               (over the largest |TD| of a window, small there)      -> 7.5%
#   loss        0.01-0.52% in 21 states; 1.39%, 2.59%, 2.70% in the near
#               ones: |loss_p - loss_r| is 0.0004-0.010 in every state, the
#               loss it is divided by 0.028-5.3 (the seeded targets
#               spread over 0.4-1.2, so no state brings every TD to 0);
#               r2d2_float32's and twotower_float32's bound           -> 7.5%
#   optimizer   5.6e-5 - 7.7e-5, float32 against float32              -> 3e-4
#   q           0.3-4.1% in 22 states, 8.2% and 24.9% in the near ones: the
#               LARGEST gap over 6,144 positions x 6 actions, over max |Q|.
#               As in ``twotower_float32`` it reads the worst token: where a
#               token's 8th and 9th router scores lie closer than bf16's
#               noise in the residual stream, the program and the reference
#               compute it with a held expert more or less. Read token by
#               token on the refused seed (stream after every sublayer on
#               both sides, routers recomputed on the host): 65 of 6,144
#               tokens differ in a held expert (53 in the first expert
#               sublayer, whose output error is then 2.6% of the stream
#               where every other sublayer adds 0.02-0.4%), their gaps are
#               2-25% (median 18%) and every other token's 1.7% (median;
#               largest 9.7%): a flip moves Q by 0.06-0.09 there, a
#               quarter of max |Q| 0.35 - which Q's spread over positions
#               alone (+- 0.11) nearly makes; the 2-4% of the narrow
#               states are such flips over a larger max |Q|             -> 75%
# The control, the program's network on float8-rounded weights (e4m3; 3
# seeds, none a near state): gradient 13.6-16.3% in every seed: ``grad`` is
# the number that tells bf16 from a coarser type here; loss 0.5-18%, |TD|
# 0.6-5.4% (one seed of three above either bound), ``q`` 2.5-4.5% (inside
# the sound runs' range: it tells nothing apart in this cell and is held
# only against a step that is not this network's). A wrong formula - a
# window one step too long, no head gate, the two rotary embeddings swapped
# or none, gates not normalised, relu^2 for silu, a gradient through the
# burn-in - fails in float32 at toy size (tests/test_laguna_core.py).
# float32 configurations differ from the reference by summation order only;
# no cell runs one, so these are the toy tests' bounds, not read on a chip.
TOLERANCES = {
    "bfloat16": {"q": 0.75, "priorities": 0.075, "loss": 0.075, "grad": 0.06,
                 "optimizer": 3e-4},
    "float32": {"q": 1e-4, "priorities": 1e-4, "loss": 1e-4, "grad": 1e-3,
                "optimizer": 1e-3},
}

ATTENTION = "FW"
# Frames the convolutions in front take at a time: a frame's embedding
# depends on no other frame, and ``plain.conv_valid`` lays every window of
# its input side by side (chip-targeted compile, PR 46: 26.9 GB padded for a
# window's 2,048 frames at once, the step refused at 28.45 of 15.75 GB; at
# 256 the whole step compiles with 8.47 GB of temporaries).
FRAME_BLOCK = 256
# Time slices of the ring the sequence ring's check fills
# (``make_further_check``): a window, a stride and a stack have to fit, and
# the steps fed wrap it once; 0.46 GB of frames at 16 lanes.
RING_SLOTS = 4096


class Rope(NamedTuple):
    """One kind of attention layer's rotary embedding, ``rope_parameters``'
    keys: ``factor`` 0 is ``rope_type`` default."""

    theta: float
    partial_rotary_factor: float
    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


class Core(NamedTuple):
    """The core's shape, as the configuration states it."""

    pattern: str
    norm_eps: float
    heads: Tuple[int, ...]      # query heads of each F / W sublayer, in order
    kv_heads: int
    head_dim: int
    window: int                 # W: steps a query looks back, itself included
    rope_full: Rope
    rope_window: Rope
    routed: int                 # E: experts the router scores
    held: Tuple[int, ...]
    per_token: int
    scale: float


class Hyper(NamedTuple):
    """What the step needs from the configuration (hashable: jit-static)."""

    torso: str
    core: Core
    dueling: bool
    double_dqn: bool
    value_rescale: bool
    burn_in: int
    unroll: int
    n_step: int
    gamma: float
    eta: float
    huber_delta: float
    learning_rate: float
    adam_eps: float
    max_grad_norm: float


# -- the sublayers, one window [T, ...] at a time ----------------------------

def gated_mlp(u, gate, up, down):
    """``(silu(u W_gate) * u W_up) W_down``: the dense MLP, an expert and
    the shared expert alike."""
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def inv_freq(rope: Rope, head_dim: int) -> np.ndarray:
    """The rotary frequencies ``[d / 2]`` over the first ``d =
    partial_rotary_factor * head_dim`` dims, by the formulas at the top."""
    d = int(head_dim * rope.partial_rotary_factor)
    pos = rope.theta ** (2.0 * np.arange(d // 2) / d)
    if not rope.factor:
        return 1.0 / pos

    def correction_dim(rotations):
        return (d * math.log(rope.original_max_position_embeddings
                             / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) / pos + ramp / (rope.factor * pos)


def rotary(x, position, rope: Rope):
    """``x [T, n, D]`` rotated by ``position [T]``: ``x cos + rotate_half(x)
    sin`` over the rotary dims, with ``rotate_half(x) = [-x2, x1]`` of their
    two halves; the dims past them pass."""
    freqs = jnp.asarray(inv_freq(rope, x.shape[-1]), jnp.float32)
    d = 2 * freqs.shape[0]
    angle = position.astype(jnp.float32)[:, None] * freqs       # [T, d/2]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None]   # [T, 1, d]
    cos = jnp.cos(angle) * rope.attention_factor
    sin = jnp.sin(angle) * rope.attention_factor
    turned, rest = x[..., :d], x[..., d:]
    half = jnp.concatenate([-turned[..., d // 2:], turned[..., :d // 2]],
                           axis=-1)
    return jnp.concatenate([turned * cos + half * sin, rest], axis=-1)


def visible(key_position, key_episode, position, episode, window):
    """``[T, S]``: query t sees key s where s lies in its episode, not after
    it and, under a ``window``, fewer than ``window`` steps before it."""
    back = position[:, None] - key_position[None, :]
    see = jnp.logical_and(back >= 0,
                          key_episode[None, :] == episode[:, None])
    return see if window is None else jnp.logical_and(see, back < window)


def head_gate(u, w_gate):
    """``sigmoid(u W_g) [T, H]``: the attention output's gate, one a head."""
    return jax.nn.sigmoid(u @ w_gate)


def attention(p: Dict, u, reset, memory, core: Core, heads: int,
              windowed: bool):
    """``u [T, hidden]`` -> ``[T, hidden]``. ``memory`` is ``(rotated keys,
    values [S0, KV, D], episode count [S0], position in the episode [S0])`` of
    the window's earlier steps."""
    kv, D = core.kv_heads, core.head_dim
    rope = core.rope_window if windowed else core.rope_full
    old_k, old_v, old_episode, old_position = memory
    T = u.shape[0]

    def count(carry, reset_t):
        episode, position = carry
        carry = (episode + reset_t, jnp.where(reset_t, 0, position + 1))
        return carry, carry

    before = ((old_episode[-1], old_position[-1]) if old_episode.shape[0]
              else (jnp.int32(0), jnp.int32(-1)))
    _, (episode, position) = jax.lax.scan(count, before,
                                          reset.astype(jnp.int32))
    q = rotary((u @ p["q_proj"]).reshape(T, heads, D), position, rope)
    keys = jnp.concatenate(
        [old_k, rotary((u @ p["k_proj"]).reshape(T, kv, D), position, rope)])
    values = jnp.concatenate([old_v, (u @ p["v_proj"]).reshape(T, kv, D)])
    episodes = jnp.concatenate([old_episode, episode])
    positions = jnp.concatenate([old_position, position])
    see = visible(positions, episodes, position, episode,
                  core.window if windowed else None)
    # query head i reads KV head i // (heads / kv)
    k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (keys, values))
    scores = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(D)
    weights = jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", weights, v)
    out = (out * head_gate(u, p["g_proj"])[..., None]).reshape(T, heads * D)
    return out @ p["o_proj"], (keys, values, episodes, positions)


def dense_mlp(p: Dict, u, reset, memory, core: Core):
    return gated_mlp(u, p["gate_proj"], p["up_proj"], p["down_proj"]), memory


def experts(p: Dict, u, reset, memory, core: Core):
    """``u [T, hidden]`` -> the held experts' part of the routed sum plus
    the shared expert."""
    scores = jax.nn.sigmoid(u @ p["router"])                    # [T, routed]
    _, chosen = jax.lax.top_k(scores, core.per_token)
    weight = gates(jnp.take_along_axis(scores, chosen, axis=-1), core)
    out = gated_mlp(u, p["shared_gate"], p["shared_up"], p["shared_down"])
    for local, expert in enumerate(core.held):
        # this expert's weight for each token: its gate where it was chosen
        gate = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=-1)
        out = out + gate[:, None] * gated_mlp(
            u, p["experts_gate"][:, local], p["experts_up"][:, local],
            p["experts_down"][local])
    return out, memory


def sublayers(core: Core):
    """``(letter, function(p, u, reset, memory, core))`` of every sublayer,
    an attention sublayer with its own head count."""
    heads = iter(core.heads)
    for kind in core.pattern:
        if kind in ATTENTION:
            def attend(p, u, reset, memory, core, heads=next(heads),
                       windowed=kind == "W"):
                # looked up when called: a test replaces ``attention``
                return attention(p, u, reset, memory, core, heads, windowed)
            yield kind, attend
        else:
            yield kind, {"D": dense_mlp, "E": experts}[kind]


def empty_memory(core: Core):
    """What every sublayer remembers before a window's first step."""
    kv = (0, core.kv_heads, core.head_dim)
    none = jnp.zeros((0,), jnp.int32)
    return tuple((jnp.zeros(kv), jnp.zeros(kv), none, none)
                 if kind in ATTENTION else () for kind in core.pattern)


def core_forward(p: Dict, x, reset, memory, core: Core):
    """The ten sublayers and the final norm over one window's steps ``x [T,
    hidden]``; each sublayer's activations are recomputed in its backward."""
    new_memory = []
    for i, (kind, mixer) in enumerate(sublayers(core)):
        layer = p[f"layer_{i}"]

        @jax.checkpoint
        def block(layer, x, memory_i, mixer=mixer):
            out, memory_i = mixer(
                layer["mixer"], rms_norm(x, layer["norm"], core.norm_eps),
                reset, memory_i, core)
            return x + out, memory_i

        x, memory_i = block(layer, x, memory[i])
        new_memory.append(memory_i)
    return rms_norm(x, p["norm_f"], core.norm_eps), tuple(new_memory)


def _embed(torso: Dict, frames, hp: Hyper):
    """[N, H, W, C] frames -> [N, hidden]: ``twotower_float32``'s front
    (convolutions, the dense layer, relu), ``FRAME_BLOCK`` frames at a time,
    each block's activations recomputed in its backward (memory, not
    mathematics)."""
    block = jax.checkpoint(
        lambda frames: twotower_float32._embed(torso, frames, hp))
    n = frames.shape[0]
    if n <= FRAME_BLOCK or n % FRAME_BLOCK:
        return block(frames)
    blocks = frames.reshape((n // FRAME_BLOCK, FRAME_BLOCK)
                            + frames.shape[1:])
    return jax.lax.map(block, blocks).reshape(n, -1)


def q_window(params: Dict, obs, reset, hp: Hyper):
    """Q-values ``[unroll + n_step, A]`` of ONE window ``obs [T, ...]`` at
    the positions after the burn-in, from the empty state; what the burn-in
    prefix leaves in the sublayers' memories is a constant to the gradient."""
    p = params["params"]
    x = _embed(p["torso"], obs, hp)
    memory = empty_memory(hp.core)
    if hp.burn_in:
        _, memory = core_forward(p["core"], x[:hp.burn_in],
                                 reset[:hp.burn_in], memory, hp.core)
        memory = leave_burn_in(memory)
    hidden, _ = core_forward(p["core"], x[hp.burn_in:], reset[hp.burn_in:],
                             memory, hp.core)
    adv = dense(p["advantage"], hidden)
    if not hp.dueling:
        return adv
    return (dense(p["value"], hidden) + adv
            - jnp.mean(adv, axis=-1, keepdims=True))


# -- the loss: r2d2_float32's, on one window ---------------------------------

def _q_taken(params, window: Dict, hp: Hyper):
    """The online network's Q-values after the burn-in, and ``[unroll]``
    those of the actions taken at the loss positions."""
    q_online = q_window(params, window["obs"], window["reset"], hp)
    taken = window["action"][hp.burn_in:hp.burn_in + hp.unroll]
    return q_online, jnp.take_along_axis(
        q_online[:hp.unroll], taken[:, None].astype(jnp.int32),
        axis=-1)[:, 0]


def _loss(params, target_params, window: Dict, hp: Hyper):
    """``weight * mean over the unroll of huber(TD)`` of one window; aux:
    |TD| ``[unroll]`` and the online Q-values."""
    q_online, qa = _q_taken(params, window, hp)
    q_target = q_window(target_params, window["obs"], window["reset"], hp)
    # r2d2_float32's targets over [T, S]: this window is its one sequence
    td = qa - jax.lax.stop_gradient(n_step_targets(
        q_online[:, None], q_target[:, None],
        window["reward"][hp.burn_in:, None],
        window["done"][hp.burn_in:, None], hp)[:, 0])
    quad = jnp.minimum(jnp.abs(td), hp.huber_delta)
    huber = 0.5 * quad * quad + hp.huber_delta * (jnp.abs(td) - quad)
    return window["weights"] * jnp.mean(huber), (jnp.abs(td), q_online)


def _pull_sum(params, window: Dict, pull, hp: Hyper):
    """Sum of ``pull * Q(obs, action)`` over the loss positions: with
    ``pull`` the size of each position's ``d loss / d Q``, its gradient is
    what the loss's gradient would be if every TD error had the same
    sign."""
    return jnp.sum(pull * _q_taken(params, window, hp)[1])


def step(params, target_params, batch: Dict, hp: Hyper) -> Dict:
    """Loss and gradient of one learner step on a batch as ``seeded_batch``
    lays it out (time-major ``[T, S, ...]``), one window at a time: the
    online Q-values at the training positions, the mean over windows of the
    weighted mean Huber loss, the window priorities, the gradient's global
    norm, the gradient as the optimizer takes it (clipped), and
    ``grad_scale``: the norm that gradient would have if no two TD errors
    cancelled."""
    seqs = batch["weights"].shape[0]
    windows = {k: jnp.moveaxis(batch[k], 1, 0)
               for k in ("obs", "action", "reward", "done", "reset")}
    windows["weights"] = batch["weights"]

    def one_window(total, window):
        (loss, (abs_td, q)), grads = jax.value_and_grad(
            _loss, has_aux=True)(params, target_params, window, hp)
        pull = (window["weights"] * jnp.minimum(abs_td, hp.huber_delta)
                / hp.unroll)
        one_way = jax.grad(_pull_sum)(params, window, pull, hp)
        return (jax.tree.map(jnp.add, total, (loss, grads, one_way)),
                (abs_td, q))

    with jax.default_matmul_precision("highest"):
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (loss, grads, one_way), (abs_td, q) = jax.lax.scan(
            one_window, (jnp.float32(0.0), zeros, zeros), windows)
        abs_td, q = jnp.moveaxis(abs_td, 0, 1), jnp.moveaxis(q, 0, 1)
        loss = loss / seqs
        grads = jax.tree.map(lambda g: g / seqs, grads)
        grads, norm, scale = clip_by_global_norm(grads, hp.max_grad_norm)
        priorities = (hp.eta * jnp.max(abs_td, axis=0)
                      + (1.0 - hp.eta) * jnp.mean(abs_td, axis=0))
    return {"q": q, "loss": loss, "priorities": priorities,
            "grad_norm": norm, "grads": grads,
            "grad_scale": scale * global_norm(one_way) / seqs}


def _rope(rope) -> Rope:
    return Rope(theta=float(rope.theta),
                partial_rotary_factor=float(rope.rotary_factor),
                factor=float(rope.yarn_factor),
                original_max_position_embeddings=int(rope.original_positions),
                beta_fast=float(rope.beta_fast),
                beta_slow=float(rope.beta_slow),
                attention_factor=float(rope.attention_factor))


def hyper_from_config(cfg) -> Hyper:
    """Read the program's ``ExperimentConfig`` by attribute; refuse what this
    reference does not compute rather than compare against something else."""
    net, learner, replay = cfg.network, cfg.learner, cfg.replay
    core = getattr(net, "core", None)
    pattern = getattr(core, "pattern", "")
    unsupported = [name for name, on in (
        ("network.noisy", net.noisy), ("network.num_atoms", net.num_atoms > 1),
        ("network.iqn", net.iqn), ("network.lstm_size", net.lstm_size),
        ("network.core.kind", getattr(core, "kind", None) != "hybrid"),
        ("network.core.pattern", not pattern or set(pattern) - set("FWDE")),
        ("network.core.expert_act",
         getattr(core, "expert_act", None) != "silu"),
        ("network.core.router_bias", getattr(core, "router_bias", True)),
        ("no network.hidden", not net.hidden),
        ("no replay.unroll_length", replay.unroll_length <= 0),
        ("learner.munchausen", learner.munchausen),
        ("learner.target_tau", learner.target_tau > 0),
        ("learner.lr_schedule", learner.lr_schedule != "constant")) if on]
    if unsupported or net.torso not in ("mlp", *CONV_STRIDES):
        raise NotImplementedError(
            f"laguna_float32 does not cover {unsupported or net.torso}")
    return Hyper(
        torso=net.torso,
        core=Core(pattern=pattern, norm_eps=float(core.norm_eps),
                  heads=tuple(core.attention_heads_per_layer),
                  kv_heads=core.num_key_value_heads, head_dim=core.head_dim,
                  window=int(core.sliding_window),
                  rope_full=_rope(core.rope_full),
                  rope_window=_rope(core.rope_window),
                  routed=core.n_routed_experts,
                  held=tuple(core.experts_held),
                  per_token=core.num_experts_per_tok,
                  scale=float(core.routed_scaling_factor)),
        dueling=bool(net.dueling), double_dqn=bool(learner.double_dqn),
        value_rescale=bool(learner.value_rescale),
        burn_in=int(replay.burn_in), unroll=int(replay.unroll_length),
        n_step=int(learner.n_step), gamma=float(learner.gamma),
        eta=float(replay.priority_mix),
        huber_delta=float(learner.huber_delta),
        learning_rate=float(learner.learning_rate),
        adam_eps=float(learner.adam_eps),
        max_grad_norm=float(learner.max_grad_norm))


def make_further_check(cfg, env):
    """``r2d2_float32.make_further_check`` — the program's sequence ring
    against ``sequence_ring.py``'s plain rules, five numbers with their limits
    — on a ring of ``RING_SLOTS`` time slices (that module reads its own
    count once, while it builds the check)."""
    kept = r2d2_float32.RING_SLOTS
    r2d2_float32.RING_SLOTS = RING_SLOTS
    try:
        return r2d2_float32.make_further_check(cfg, env)
    finally:
        r2d2_float32.RING_SLOTS = kept


# -- operations a grad step requires ------------------------------------------

def mean_keys_seen(window: int, reach) -> float:
    """Keys a causal query of a ``window``-step window sees, the mean over
    its steps: all up to itself, or the last ``reach`` of them."""
    reach = window if reach is None else min(reach, window)
    return (reach * (reach + 1) / 2 + (window - reach) * reach) / window


def forward_flops_per_step(cfg, env) -> Dict[str, float]:
    """Multiply-accumulates x 2 one step of one window REQUIRES in a forward
    pass, by part. The attention counts its projections, its gate, and
    scores and weighted values over the keys a query SEES (the causal
    triangle in a full layer, the band of ``sliding_window`` in a window
    layer — not the blocks the program computes them by); the routed experts
    count the rows the routing sends to the held experts at balance
    (``per_token * held / routed`` expert evaluations a token), not the
    dense product; elementwise work is left out."""
    from perf.reduce import flops

    net, core = cfg.network, cfg.network.core
    if net.torso not in CONVS:
        raise NotImplementedError(
            f"laguna_float32 counts {sorted(CONVS)} torsos, not "
            f"{net.torso!r}")
    hidden = net.hidden
    window = (cfg.replay.burn_in + cfg.replay.unroll_length
              + cfg.learner.n_step)
    torso = flops.cnn_layer_macs(tuple(env.observation_shape),
                                 CONVS[net.torso], hidden,
                                 env.num_actions, False)[:-1]
    kv, D = core.num_key_value_heads, core.head_dim
    expert = 3 * hidden * core.moe_intermediate_size
    per_step = {
        "torso": 2.0 * sum(torso),
        "heads": 2.0 * hidden * (env.num_actions + (1 if net.dueling else 0)),
        "attention_full": 0.0, "attention_window": 0.0, "mlp_dense": 0.0,
        "moe_router": 0.0, "moe_routed": 0.0, "moe_shared": 0.0}
    heads = iter(core.attention_heads_per_layer)
    for kind in core.pattern:
        if kind in ATTENTION:
            H = next(heads)
            reach = core.sliding_window if kind == "W" else None
            per_step["attention_window" if kind == "W"
                     else "attention_full"] += 2.0 * (
                hidden * ((H + 2 * kv) * D + H) + H * D * hidden
                + 2 * H * D * mean_keys_seen(window, reach))
        elif kind == "D":
            per_step["mlp_dense"] += 2.0 * 3 * hidden * core.intermediate_size
        else:
            per_step["moe_router"] += 2.0 * hidden * core.n_routed_experts
            per_step["moe_routed"] += 2.0 * (
                core.num_experts_per_tok * len(core.experts_held)
                / core.n_routed_experts * expert)
            per_step["moe_shared"] += 2.0 * (
                3 * hidden * core.moe_shared_expert_intermediate_size)
    return per_step


def grad_step_flops(cfg, env) -> float:
    """FLOPs one grad step requires (``forward_flops_per_step``): both
    networks forward over the whole window (the heads at the training
    positions only), the online network backward — two products a forward
    product — over its ``unroll + n_step`` positions after the burn-in,
    whose state is a constant. Recomputed forwards, elementwise work and
    the optimizer are left out."""
    per_step = forward_flops_per_step(cfg, env)
    train = cfg.replay.unroll_length + cfg.learner.n_step
    window = cfg.replay.burn_in + train
    body = sum(v for k, v in per_step.items() if k != "heads")
    forward = 2 * (window * body + train * per_step["heads"])
    backward = 2 * train * (body + per_step["heads"])
    return float(cfg.learner.batch_size * (forward + backward))
