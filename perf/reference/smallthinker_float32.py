"""Plain float32 reference of one sequence-learner step over one period of
``smallthinker`` (``perf/configs/smallthinker_q.json``;
SmallThinker-21BA3B-Instruct's ``config.json``, layers 0-3).

Recurrent replay as in ``r2d2_float32.py`` (Kapturowski et al. 2019: windows
with a burn-in prefix that only refreshes the state, n-step double-Q targets
under the invertible value rescaling, importance weights and the eta-mixed
priority a window) around a recurrent Q-network: convolutions and a dense
layer in front, four published layers, a final RMSNorm and linear dueling
heads. A published layer, on one window ``x [T, 2560]``:

    u = RMSNorm_1(x)
    logits = u W_r                    [T, 64]: the router reads the layer's
                                      input, BEFORE attention
    h = x + Attn(u)
    y = h + MoE(RMSNorm_2(h); routed by logits)

``Attn``  Grouped-query attention as a MASKED SOFTMAX over all the keys of
       the window, a block of ``QUERY_BLOCK`` queries at a time (whole rows
       of the softmax: exact; the block only bounds what is alive — 28 x
       8,192 x 8,192 scores would be 7.5 GB). ``q = u W_q`` ``[28, 128]``,
       ``k, v = u W_k, u W_v`` ``[4, 128]``, query head h reads KV head ``h
       // 7``; no bias, no QK-norm, no gate. Layout 0 (layer 0 of each 4:
       ``sliding_window_layout`` 0, ``rope_layout`` 0) sees the whole episode
       and has NO position embedding. Layout 1 sees the last 4,096 steps
       (``i - j < 4096``) and rotates queries and keys by the step's
       position in its episode: ``inv_freq_i = 1500000^(-2i/128)``, all 128
       dims, rotate-half layout. Scores ``q . k / sqrt(128)``.
``MoE``   chosen = the top 6 of the 64 logits, ``w = softmax(logits[chosen])``
       (``moe_primary_router_apply_softmax`` + ``norm_topk_prob``: a softmax
       over all 64, the chosen normalised to sum to 1, is the same numbers);
       an expert is ReGLU, ``W_down (relu(W_gate v) * W_up v)`` at width 768,
       on ``v = RMSNorm_2(h)``; the sublayer's output is the published sum
       over the chosen experts THAT ARE HELD (``experts_held``: expert
       parallelism's share), weights on the outputs. No shared expert.

Departures from ``config.json``, each also under ``assumed`` or ``reduced`` in
the configuration's file: 4 of 52 layers; 8 HELD of 64 experts (what the
absent 56 would add is left out, as in the program); no token embedding and
no vocabulary head (frames in, action values out); positions count from the
step that opened the episode (only differences enter the scores); a window
starts from the EMPTY state (the program's ring stores none for this core),
so its first step is position 0 whatever the lane's history was.

``reset[t]`` (``obs[t]`` opens an episode) empties every layer's memory before
step t: the keys a query may see, and the position, which restarts at 0. What
leaves the burn-in prefix — each attention sublayer's keys (rotated where
the layer rotates) and its values — is a constant to the gradient.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, one
window a block (exact: every term of the loss belongs to one window), each
sublayer's activations recomputed in its backward (memory, not mathematics);
the parameter tree is read by key names only — the program keeps a layer as
two entries, the attention sublayer's (``norm``, ``router``, ``mixer``) and
the experts' (``norm``, ``mixer``) — and nothing is shared with
``models/sequence_core.py`` or ``agents/r2d2.py``. The n-step targets with
their value rescaling are ``r2d2_float32.py``'s, the shared layers
``plain.py``'s, the norm, the seeded batch and the program's side
``twotower_float32.py``'s, the mask, the rotation and the frames' blocks
``laguna_float32.py``'s (imported: names that a test replaces are looked up
in THIS module).

Beside the step: what ``perf/harness/reference_check.py`` asks of every
reference module (``perf/README.md``), and the sequence ring's own check,
which is ``r2d2_float32.make_further_check`` on this configuration's windows
(8,192 steps every 4,096; the pair it stores is zero wide here) over the
cell's whole ring of ``RING_SLOTS`` time slices: a smaller one cannot hold
such a window and a stride.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.plain import (ADAM_B1, CONV_STRIDES, CONVS,  # noqa: F401
                                  adam_delta, clip_by_global_norm, dense,
                                  global_norm)
from perf.reference import laguna_float32, r2d2_float32
from perf.reference.laguna_float32 import mean_keys_seen, visible
from perf.reference.r2d2_float32 import n_step_targets
from perf.reference.twotower_float32 import (leave_burn_in,  # noqa: F401
                                             make_program, rms_norm,
                                             seeded_batch)

# Largest error allowed for each quantity ``reference_check`` compares (its
# docstring defines them; ``q`` is the online network's Q-values at the
# unroll + n_step positions of every window), by the dtype the configuration
# computes in.
#
# bfloat16: each limit lies between two readings taken on the chip at the
# cell's own widths (PR 50; 2 windows x 8,192 steps, 281.4 M parameters;
# ``perf/records/pr50/``, ``PERF.md`` §6). Below it, the LARGEST the sound
# program read over 39 seeded states (8 runs of the cell and 31 seeds of
# ``perf/tools/reference_study.py``; the reference's loss 1.99-9.38 in all of
# them: none stands near its targets, as 3 of the sibling cell's 15 did, PR
# 46). Above it, the SMALLEST the float8 control read over 4 seeds
# (``reference_check.CoarseNet``: the nearest precision below bf16) where the
# control tells the two apart, and else a wrong formula read the same way
# (``perf/tools/wrong_formula_study.py``, ``WRONG_FORMULAS`` below):
#               sound, 39 states    above it                          limit
#   grad        0.305-0.544%        control 8.92-16.45%               2%
#               3.7x above the one, 4.5x below the other; the sound readings
#               lie within 1.5x of their median. THE number that tells bf16
#               from a coarser type.
#   priorities  0.006-0.233%        control 1.32-3.61%                0.6%
#               2.6x above, 2.2x below: the readings are six times apart.
#   loss        0.0007-0.488%       control 0.54 / 1.26 / 1.44 / 6.29%: a
#               signed sum that cancels by seed, its smallest 1.1x the sound
#               largest (a heavy tail: 0.488, 0.376, 0.185, 0.154%, the
#               median 0.05%) - NO precision limit lies between. Held against wrong formulas instead: the router
#               fed the experts' input 19.7% (the unnormalised softmax
#               2.31%; silu 0.69%)                                    3%
#               6.1x above the sound largest, 6.6x below the router's.
#   q           0.12-2.35%          control 1.96-2.82%: INSIDE the sound
#               range, as are silu (1.42%), a rotation in the full layer
#               (0.40%) and none in the window layer (0.60%); the
#               unnormalised softmax 2.99%. The LARGEST gap over 8,192
#               positions x 6 actions, over max |Q|: it reads the worst
#               token, and where a token's 6th and 7th router logits lie
#               closer than bf16's noise in the residual stream, program and
#               reference compute it with a held expert more or less, on
#               float8 weights as on sound ones. Held against the one fault
#               it does read: the router fed the experts' input 20.9%
#                                                                     7%
#               3.0x above the sound largest, 3.0x below the router's.
#   optimizer   3.7e-5 - 4.5e-5, float32 against float32 (the control's
#               step is float32's too: 2.3e-5 - 2.6e-5)               3e-4
# The control fails by ``grad`` AND by ``priorities`` on each of its 4 seeds,
# by ``loss`` on one. What these limits do not read at these widths: a
# rotation in the full layer (``grad`` 1.15%, the rest inside the sound
# range), none in the window layer (``grad`` 2.21%: at the limit, nothing to
# lean on) - a fresh network's scores are nearly flat, positions move them
# little - and a window one step off (one key in 4,096). Those, like every
# other wrong formula - the router fed the experts' input, a softmax over
# all 64 left unnormalised, silu for relu (each fails here by ``grad``: 65%,
# 14.1%, 7.2%), a gradient through the burn-in - fail in float32 at toy size
# (tests/test_smallthinker_core.py).
# float32 configurations differ from the reference by summation order only;
# no cell runs one, so these are the toy tests' bounds, not read on a chip.
TOLERANCES = {
    "bfloat16": {"q": 0.07, "priorities": 0.006, "loss": 0.03, "grad": 0.02,
                 "optimizer": 3e-4},
    "float32": {"q": 1e-4, "priorities": 1e-4, "loss": 1e-4, "grad": 1e-3,
                "optimizer": 1e-3},
}

# Queries a block of the attention's softmax (whole rows: exact). At the
# cell's 28 heads x 8,192 keys a block's scores are 470 MB in float32.
QUERY_BLOCK = 512
# Time slices of the ring the sequence ring's check fills
# (``make_further_check``): a window (8,192), a stride (4,096) and a stack
# have to fit — the cell's own 16,384; the steps fed wrap it once.
RING_SLOTS = 16384


class Core(NamedTuple):
    """The core's shape, as the configuration states it."""

    layout: Tuple[int, ...]     # a layer: 0 full and no positions, 1 window
    norm_eps: float
    heads: int
    kv_heads: int
    head_dim: int
    window: int                 # layout 1: steps a query looks back, itself
    #                             included
    theta: float                # layout 1: the rotary base
    routed: int                 # experts the router scores
    held: Tuple[int, ...]
    per_token: int


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


# -- the published layer, one window [T, ...] at a time ----------------------

def rotary(x, position, theta: float):
    """``x [T, n, D]`` rotated by ``position [T]``: ``x cos + rotate_half(x)
    sin`` over all D dims at ``inv_freq_i = theta^(-2i/D)``."""
    return laguna_float32.rotary(x, position, laguna_float32.Rope(
        theta=theta, partial_rotary_factor=1.0, factor=0.0,
        original_max_position_embeddings=0, beta_fast=0.0, beta_slow=0.0,
        attention_factor=1.0))


def embedded(x, position, core: Core, windowed: bool):
    """Queries or keys with their layer's position embedding: rotary in a
    window layer (``rope_layout`` 1), none in a full one."""
    return rotary(x, position, core.theta) if windowed else x


def attention(p: Dict, u, reset, memory, core: Core, windowed: bool):
    """``u [T, hidden]`` -> ``[T, hidden]``. ``memory`` is ``(keys, values
    [S0, KV, D], episode count [S0], position in the episode [S0])`` of the
    window's earlier steps."""
    heads, kv, D = core.heads, core.kv_heads, core.head_dim
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
    q = embedded((u @ p["q_proj"]).reshape(T, heads, D), position, core,
                 windowed)
    keys = jnp.concatenate([old_k, embedded(
        (u @ p["k_proj"]).reshape(T, kv, D), position, core, windowed)])
    values = jnp.concatenate([old_v, (u @ p["v_proj"]).reshape(T, kv, D)])
    episodes = jnp.concatenate([old_episode, episode])
    positions = jnp.concatenate([old_position, position])
    # query head i reads KV head i // (heads / kv)
    k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (keys, values))

    @jax.checkpoint
    def rows(block):
        """Whole rows of the masked softmax, for a block of queries."""
        q_b, position_b, episode_b = block
        see = visible(positions, episodes, position_b, episode_b,
                      core.window if windowed else None)
        scores = jnp.einsum("thd,shd->hts", q_b, k) / np.sqrt(D)
        weights = jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf),
                                 axis=-1)
        return jnp.einsum("hts,shd->thd", weights, v)

    if T <= QUERY_BLOCK or T % QUERY_BLOCK:
        out = rows((q, position, episode))
    else:
        out = jax.lax.map(rows, tuple(
            x.reshape((T // QUERY_BLOCK, QUERY_BLOCK) + x.shape[1:])
            for x in (q, position, episode)))
    return (out.reshape(T, heads * D) @ p["o_proj"],
            (keys, values, episodes, positions))


def router_input(before_attention, before_experts):
    """What the router reads: the layer's normed input, before attention —
    not the experts' own normed input."""
    return before_attention


def gates(logits, chosen):
    """The chosen experts' weights ``[T, k]``: a softmax over the chosen
    logits."""
    return jax.nn.softmax(jnp.take_along_axis(logits, chosen, axis=-1),
                          axis=-1)


def expert_mlp(v, gate, up, down):
    """ReGLU: ``(relu(v W_gate) * v W_up) W_down``."""
    return (jax.nn.relu(v @ gate) * (v @ up)) @ down


def experts(p: Dict, v, logits, core: Core):
    """``v [T, hidden]`` routed by ``logits [T, routed]`` -> the held
    experts' part of the published sum."""
    _, chosen = jax.lax.top_k(logits, core.per_token)
    weight = gates(logits, chosen)
    out = jnp.zeros_like(v)
    for local, expert in enumerate(core.held):
        # this expert's weight for each token: its gate where it was chosen
        gate = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=-1)
        out = out + gate[:, None] * expert_mlp(
            v, p["experts_gate"][:, local], p["experts_up"][:, local],
            p["experts_down"][local])
    return out


def layer(first: Dict, second: Dict, x, reset, memory, core: Core,
          windowed: bool):
    """One published layer on ``x [T, hidden]`` (the formulas at the top):
    ``first`` the attention sublayer's parameters with the router's, ``second``
    the experts'; each sublayer's activations recomputed in its backward."""

    @jax.checkpoint
    def attend(first, x, memory):
        u = rms_norm(x, first["norm"], core.norm_eps)
        out, memory = attention(first["mixer"], u, reset, memory, core,
                                windowed)
        return x + out, u, memory

    @jax.checkpoint
    def mix(first, second, h, u):
        v = rms_norm(h, second["norm"], core.norm_eps)
        logits = router_input(u, v) @ first["router"]
        return h + experts(second["mixer"], v, logits, core)

    h, u, memory = attend(first, x, memory)
    return mix(first, second, h, u), memory


def empty_memory(core: Core):
    """What every layer's attention remembers before a window's first step."""
    kv = (0, core.kv_heads, core.head_dim)
    none = jnp.zeros((0,), jnp.int32)
    return tuple((jnp.zeros(kv), jnp.zeros(kv), none, none)
                 for _ in core.layout)


def core_forward(p: Dict, x, reset, memory, core: Core):
    """The layers and the final norm over one window's steps ``x [T,
    hidden]``; the program's entries ``layer_2i`` and ``layer_2i+1`` are
    published layer i's two sublayers."""
    new_memory = []
    for i, windowed in enumerate(core.layout):
        x, memory_i = layer(p[f"layer_{2 * i}"], p[f"layer_{2 * i + 1}"], x,
                            reset, memory[i], core, bool(windowed))
        new_memory.append(memory_i)
    return rms_norm(x, p["norm_f"], core.norm_eps), tuple(new_memory)


def q_window(params: Dict, obs, reset, hp: Hyper):
    """Q-values ``[unroll + n_step, A]`` of ONE window ``obs [T, ...]`` at
    the positions after the burn-in, from the empty state; what the burn-in
    prefix leaves in the layers' memories is a constant to the gradient."""
    p = params["params"]
    x = laguna_float32._embed(p["torso"], obs, hp)
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


def hyper_from_config(cfg) -> Hyper:
    """Read the program's ``ExperimentConfig`` by attribute; refuse what this
    reference does not compute rather than compare against something else."""
    net, learner, replay = cfg.network, cfg.learner, cfg.replay
    core = getattr(net, "core", None)
    pattern = getattr(core, "pattern", "")
    layers = [pattern[i:i + 2] for i in range(0, len(pattern), 2)]
    heads = tuple(getattr(core, "attention_heads_per_layer", ()))
    plain_rope = getattr(core, "rope_window", None)
    unsupported = [name for name, on in (
        ("network.noisy", net.noisy), ("network.num_atoms", net.num_atoms > 1),
        ("network.iqn", net.iqn), ("network.lstm_size", net.lstm_size),
        ("network.core.kind", getattr(core, "kind", None) != "hybrid"),
        ("network.core.pattern",
         not layers or set(layers) - {"FE", "WE"}),
        ("network.core.attention_heads_per_layer", len(set(heads)) != 1),
        ("network.core.expert_act",
         getattr(core, "expert_act", None) != "relu"),
        ("network.core.router_scores",
         getattr(core, "router_scores", None) != "softmax"),
        ("network.core.router_ahead", not getattr(core, "router_ahead", 0)),
        ("network.core.router_bias", getattr(core, "router_bias", True)),
        ("network.core.moe_shared_expert_intermediate_size",
         getattr(core, "moe_shared_expert_intermediate_size", 1)),
        ("network.core.attention_gate",
         getattr(core, "attention_gate", True)),
        ("network.core.rope_full (a position embedding)",
         getattr(getattr(core, "rope_full", None), "rotary_factor", 1)),
        ("network.core.rope_window (not the plain embedding over all dims)",
         plain_rope is None or plain_rope.rotary_factor != 1.0
         or plain_rope.yarn_factor or plain_rope.attention_factor != 1.0),
        ("no network.hidden", not net.hidden),
        ("no replay.unroll_length", replay.unroll_length <= 0),
        ("learner.munchausen", learner.munchausen),
        ("learner.target_tau", learner.target_tau > 0),
        ("learner.lr_schedule", learner.lr_schedule != "constant")) if on]
    if unsupported or net.torso not in ("mlp", *CONV_STRIDES):
        raise NotImplementedError(
            f"smallthinker_float32 does not cover {unsupported or net.torso}")
    return Hyper(
        torso=net.torso,
        core=Core(layout=tuple(int(pair == "WE") for pair in layers),
                  norm_eps=float(core.norm_eps), heads=heads[0],
                  kv_heads=core.num_key_value_heads, head_dim=core.head_dim,
                  window=int(core.sliding_window),
                  theta=float(core.rope_window.theta),
                  routed=core.n_routed_experts,
                  held=tuple(core.experts_held),
                  per_token=core.num_experts_per_tok),
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

def forward_flops_per_step(cfg, env) -> Dict[str, float]:
    """Multiply-accumulates x 2 one step of one window REQUIRES in a forward
    pass, by part. The attention counts its projections, and scores and
    weighted values over the keys a query SEES (the causal triangle in a
    full layer, the band of ``sliding_window`` in a window layer — not the
    blocks the program computes them by); the routed experts count the rows
    the routing sends to the held experts at balance (``per_token * held /
    routed`` expert evaluations a token: 6 x 8 / 64), not the dense product;
    elementwise work is left out."""
    from perf.reduce import flops

    net, core = cfg.network, cfg.network.core
    if net.torso not in CONVS:
        raise NotImplementedError(
            f"smallthinker_float32 counts {sorted(CONVS)} torsos, not "
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
        "attention_full": 0.0, "attention_window": 0.0,
        "moe_router": 0.0, "moe_routed": 0.0}
    heads = iter(core.attention_heads_per_layer)
    for kind in core.pattern:
        if kind in "FW":
            H = next(heads)
            reach = core.sliding_window if kind == "W" else None
            per_step["attention_window" if kind == "W"
                     else "attention_full"] += 2.0 * (
                hidden * (H + 2 * kv) * D + H * D * hidden
                + 2 * H * D * mean_keys_seen(window, reach))
        else:
            per_step["moe_router"] += 2.0 * hidden * core.n_routed_experts
            per_step["moe_routed"] += 2.0 * (
                core.num_experts_per_tok * len(core.experts_held)
                / core.n_routed_experts * expert)
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


# -- the wrong formulas the comparison is held against ------------------------
# Not the reference: one published formula a name, each as (the function of
# this module it replaces, the wrong one). A reference with one of them in
# place, compared with the sound program, reads what a program with that
# fault would read against the sound reference.
# ``tests/test_smallthinker_core.py`` holds each at toy size;
# ``perf/tools/wrong_formula_study.py`` reads them at the cell's own widths
# on the chip: ``TOLERANCES``' ``q`` and ``loss`` are set below the reading
# of the first.
WRONG_FORMULAS = {
    "router_after_attention": (
        "router_input",
        lambda before_attention, before_experts: before_experts),
    "softmax_over_all_left_unnormalised": (
        "gates", lambda logits, chosen: jnp.take_along_axis(
            jax.nn.softmax(logits, axis=-1), chosen, axis=-1)),
    "silu_for_relu": (
        "expert_mlp", lambda v, gate, up, down:
        (jax.nn.silu(v @ gate) * (v @ up)) @ down),
    "rotary_in_the_full_layer": (
        "embedded", lambda x, position, core, windowed:
        rotary(x, position, core.theta)),
    "no_rotary_in_the_window_layer": (
        "embedded", lambda x, position, core, windowed: x),
    "burn_in_gradient": ("leave_burn_in", lambda memory: memory),
}
