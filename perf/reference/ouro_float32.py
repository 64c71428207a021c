"""Plain float32 reference of one sequence-learner step over one pipeline
stage of ``ouro`` (``perf/configs/ouro_q.json``; Ouro-2.6B's ``config.json``,
layers 0-3): a looped language model's stack, run ``total_ut_steps`` times
over the same weights.

Recurrent replay as in ``r2d2_float32.py`` (Kapturowski et al. 2019: windows
with a burn-in prefix that only refreshes the state, n-step double-Q targets
under the invertible value rescaling, importance weights and the eta-mixed
priority a window) around a recurrent Q-network: convolutions and a dense
layer in front, the looped stack, linear dueling heads behind. The stack, on
one window ``x [T, 2048]``, everything without bias:

    turn r = 0 .. 3, the SAME parameters every turn:
      layer l = 0 .. 3:
        a = RMSNorm_1l(x);  q, k, v = a W_q, a W_k, a W_v     16 heads of 128,
                                         16 KV heads: head h reads KV head h
        q, k <- rotary(q, k)         the step's position in its episode,
                                     theta 1e6, all 128 dims, rotate-half
        o = softmax(q k^T / sqrt(128), causal within the episode) v
                                     over the keys and values of turn r ONLY
        h = x + RMSNorm_2l(o W_o)    sandwich: the sublayer's OUTPUT is normed
        b = RMSNorm_3l(h);  m = W_down (silu(W_gate b) * W_up b)   width 5632
        x = h + RMSNorm_4l(m)
      x = RMSNorm_f(x)               after EVERY turn: it enters the next one
    Q = dueling heads(x after turn 3)

Written out as ``turn_count`` explicit passes over ONE parameter dictionary;
each pass has a memory of its own (``memory[r][l]``: the keys, rotated, and
values that turn r's layer l made for the window's earlier steps) — turn r's
keys are projections of turn r's hidden state, so no two turns share them
(the public implementation indexes its cache by ``current_ut *
num_hidden_layers + layer``). The attention is ONE MASKED ``[T, S]`` SOFTMAX a
window (not the blocks the program computes).

Departures from ``config.json``, each also under ``assumed`` or ``reduced`` in
the configuration's file: 4 of 48 layers, the turn closing over the four
held (published: over all 48); no token embedding and no vocabulary head
(frames in, action values out); NO EXIT GATE — the published gate is a
``Linear(2048, 1)`` whose exit distribution weights the language model's
per-turn losses, and at the published ``early_exit_threshold`` 1 every token
runs all four turns and the last turn's state is the output: the Q-learning
loss stands where that objective stood and reads the last turn alone;
positions count from the step that opened the episode (only differences
enter the scores); a window starts from the EMPTY state (the program's ring
stores none for this core), so its first step is position 0 whatever the
lane's history was.

``reset[t]`` (``obs[t]`` opens an episode) empties every memory before step
t: the keys a query may see, and the position, which restarts at 0. What
leaves the burn-in prefix — every turn's keys and values — is a constant to
the gradient.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, one
window a block (exact: every term of the loss belongs to one window), each
sublayer's activations recomputed in its backward (memory, not mathematics);
the parameter tree is read by key names only (``layer_i`` with ``norm``,
``norm_out``, ``mixer``; ``norm_f``) and nothing is shared with
``models/sequence_core.py`` or ``agents/r2d2.py``. The n-step targets with
their value rescaling are ``r2d2_float32.py``'s, the shared layers
``plain.py``'s, the norm, the seeded batch and the program's side
``twotower_float32.py``'s, the mask, the rotation, the gated MLP and the
frames' blocks ``laguna_float32.py``'s (imported: names that a test replaces
are looked up in THIS module).

Beside the step: what ``perf/harness/reference_check.py`` asks of every
reference module (``perf/README.md``), and the sequence ring's own check,
which is ``r2d2_float32.make_further_check`` on this configuration's windows
(2,048 steps every 512; the pair it stores is zero wide here).
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.plain import (ADAM_B1, CONV_STRIDES, CONVS,  # noqa: F401
                                  adam_delta, clip_by_global_norm, dense,
                                  global_norm)
from perf.reference import laguna_float32, r2d2_float32
from perf.reference.laguna_float32 import (Rope, gated_mlp, mean_keys_seen,
                                           rotary, visible)
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
# cell's own widths (PR 53; 2 windows x 2,048 steps, 212.1 M parameters, 16
# passes over four layers; ``perf/records/pr53/``, ``PERF.md`` §6). Below it,
# the LARGEST the sound program read over 21 seeded states (8 runs of the
# cell, one of them with the learner's turns written out, and 13 seeds of
# ``perf/tools/reference_study.py``; the reference's loss 0.68-5.27). Above
# it, the SMALLEST the float8 control read over 4 seeds
# (``reference_check.CoarseNet``: the nearest precision below bf16). The
# limits were set after the first 8 sound states and 2 control seeds; the
# other 13 and 2 fell inside and outside them:
#               sound, 21 states    control, 4 seeds                  limit
#   q           0.21-0.48%          3.05-4.50%                        1.4%
#               2.9x above the one, 2.2x below the other. No router here, so
#               no token flips a held expert: the worst of 3,072 positions
#               x 6 actions reads what every other one reads.
#   priorities  0.07-0.84%          5.38-12.7%                        2%
#               2.4x above, 2.7x below.
#   loss        0.006-1.12%         6.21-18.2%                        4%
#               a signed sum that cancels by seed (its largest, 1.12%, stands
#               alone: the next are 0.92, 0.85, 0.54%): 3.6x above, 1.6x
#               below the control's smallest reading.
#   grad        0.70-0.99%          17.7-22.5%                        3%
#               3.0x above, 5.9x below: the sound readings lie within 1.4x of
#               each other. THE number that tells bf16 from a coarser type.
#   optimizer   4.5e-5 - 5.2e-5, float32 against float32 (the control's step
#               is float32's too: 4.1e-5 - 4.7e-5)                    3e-4
# Six more sound states were read after the review, on the tree handed in
# (written-out turns, a 131,072-step ring; ``d_*``): ``q`` up to 0.58%,
# ``priorities`` 1.24%, ``loss`` 0.96%, ``grad`` 0.94% - inside every limit,
# the closest 1.6x under its own (``priorities``).
# The control fails by ALL FOUR on every seed. The wrong formulas below, read
# the same way at these widths (``perf/tools/wrong_formula_study.py``; ``q``
# / ``priorities`` / ``loss`` / ``grad``): three turns 63 / 9.5 / 22 / 71%;
# no norm between the turns 307 / 149 / 425 / 70%; pre-norm only 69 / 38 /
# 49 / 63%; one ring shared by the turns 47 / 26 / 75 / 88% - each fails all
# four; theta 10,000 0.65 / 0.75 / 0.16 / **5.7%**: by ``grad`` alone (1.9x
# the limit: a fresh network's scores are nearly flat and positions move them
# little, as in the sibling cells). NOT READ by these four at these widths: a
# residual stream rounded to bfloat16 (0.46 / 0.16 / 0.09 / 0.86%: inside the
# sound range - the program rounds every product's operands to bfloat16
# already, and a norm follows every sublayer, so the stream's rounding adds
# what is there): the check's sixth number, ``stream`` (``STREAM_LIMIT``
# below), reads that one; a gradient through the burn-in fails in float32 at
# toy size with them (tests/test_ouro_core.py).
# float32 configurations differ from the reference by summation order only;
# no cell runs one, so these are the toy tests' bounds, not read on a chip.
TOLERANCES = {
    "bfloat16": {"q": 0.014, "priorities": 0.02, "loss": 0.04, "grad": 0.03,
                 "optimizer": 3e-4},
    "float32": {"q": 1e-4, "priorities": 1e-4, "loss": 1e-4, "grad": 1e-3,
                "optimizer": 1e-3},
}

# ``make_stream_check``: the steps of its one window (a learner's burn-in
# call at the preset's sizes), the weight of every output norm there, and the
# limit by the type the configuration computes in. bfloat16, read on the chip
# at the cell's widths (PR 53, ``perf/records/pr53/d_*``): the sound program
# 2.81e-4 - 3.42e-4 over six seeded states; against the reference whose stream
# is rounded to bfloat16 (``WRONG_FORMULAS``), the nearest precision below the
# float32 the configuration states for it, 1.95e-2 - the limit 5.9x above the
# one and 9.8x below the other. float32: the toy tests' bound (3e-7 sound,
# 1e-2 faulty), not read on a chip.
STREAM_STEPS = 512
STREAM_SCALE = 2.0 ** -8
STREAM_LIMIT = {"bfloat16": 2e-3, "float32": 1e-4}

# Time slices of the ring the sequence ring's check fills
# (``make_further_check``): a window (2,048), a stride (512) and a stack have
# to fit, and the steps fed wrap it once; 0.23 GB of frames at 8 lanes.
RING_SLOTS = 4096


class Core(NamedTuple):
    """The core's shape, as the configuration states it."""

    layers: int                 # published layers held: two sublayers each
    turns: int                  # total_ut_steps
    norm_eps: float
    heads: int
    kv_heads: int
    head_dim: int
    intermediate: int
    theta: float


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


# -- the published formulas a wrong one replaces, one a function ---------------

def turn_count(core: Core) -> int:
    """Passes over the stack: ``total_ut_steps``."""
    return core.turns


def norm_after_turn(x, weight, eps: float, last: bool):
    """The model's final norm, applied after EVERY turn: its output is the
    next turn's input, and the heads' after the last."""
    return rms_norm(x, weight, eps)


def norm_output(out, weight, eps: float):
    """The sandwich: a sublayer's output is normed before it is added."""
    return rms_norm(out, weight, eps)


def memory_of_turn(memory, turn: int):
    """The keys and values turn ``turn`` attends over beside its own: what
    THAT turn made for the window's earlier steps."""
    return memory[turn]


def rope_of(core: Core) -> Rope:
    """Plain rotary over all dims at ``rope_theta``."""
    return Rope(theta=core.theta, partial_rotary_factor=1.0, factor=0.0,
                original_max_position_embeddings=0, beta_fast=0.0,
                beta_slow=0.0, attention_factor=1.0)


def stream(x):
    """The residual stream between sublayers: float32, as it is."""
    return x


# -- the published layer, one window [T, ...] at a time ----------------------

def attention(p: Dict, u, reset, memory, core: Core):
    """``u [T, hidden]`` -> ``[T, hidden]``. ``memory`` is ``(rotated keys,
    values [S0, KV, D], episode count [S0], position in the episode [S0])`` of
    the window's earlier steps, as THIS turn made them."""
    heads, kv, D = core.heads, core.kv_heads, core.head_dim
    rope = rope_of(core)
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
    see = visible(positions, episodes, position, episode, None)
    # query head i reads KV head i // (heads / kv): here its own
    k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (keys, values))
    scores = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(D)
    weights = jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", weights, v).reshape(T, heads * D)
    return out @ p["o_proj"], (keys, values, episodes, positions)


def layer(first: Dict, second: Dict, x, reset, memory, core: Core):
    """One published layer on ``x [T, hidden]`` (the formulas at the top):
    ``first`` the attention sublayer's parameters, ``second`` the MLP's; each
    sublayer's activations recomputed in its backward."""

    @jax.checkpoint
    def attend(first, x, memory):
        out, memory = attention(
            first["mixer"], rms_norm(x, first["norm"], core.norm_eps), reset,
            memory, core)
        return stream(x + norm_output(out, first["norm_out"],
                                      core.norm_eps)), memory

    @jax.checkpoint
    def mix(second, h):
        m = second["mixer"]
        out = gated_mlp(rms_norm(h, second["norm"], core.norm_eps),
                        m["gate_proj"], m["up_proj"], m["down_proj"])
        return stream(h + norm_output(out, second["norm_out"],
                                      core.norm_eps))

    h, memory = attend(first, x, memory)
    return mix(second, h), memory


def empty_memory(core: Core):
    """What every turn's every layer remembers before a window's first
    step: ``memory[turn][layer]``."""
    kv = (0, core.kv_heads, core.head_dim)
    none = jnp.zeros((0,), jnp.int32)
    return tuple(tuple((jnp.zeros(kv), jnp.zeros(kv), none, none)
                       for _ in range(core.layers))
                 for _ in range(core.turns))


def core_forward(p: Dict, x, reset, memory, core: Core):
    """The looped stack over one window's steps ``x [T, hidden]``: the
    layers, then the norm, ``turn_count`` times over the one dictionary
    ``p``; the program's entries ``layer_2l`` and ``layer_2l+1`` are
    published layer l's two sublayers."""
    new_memory = list(memory)
    turns = turn_count(core)
    for turn in range(turns):
        seen, made = memory_of_turn(memory, turn), []
        for l in range(core.layers):
            x, memory_l = layer(p[f"layer_{2 * l}"], p[f"layer_{2 * l + 1}"],
                                x, reset, seen[l], core)
            made.append(memory_l)
        new_memory[turn] = tuple(made)
        x = norm_after_turn(x, p["norm_f"], core.norm_eps,
                            turn == turns - 1)
    return x, tuple(new_memory)


def q_window(params: Dict, obs, reset, hp: Hyper):
    """Q-values ``[unroll + n_step, A]`` of ONE window ``obs [T, ...]`` at
    the positions after the burn-in, from the empty state; what the burn-in
    prefix leaves in the turns' memories is a constant to the gradient."""
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
    cancelled. A weight's gradient is the sum over its ``turn_count`` uses:
    autodiff of the passes written out."""
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
    heads = tuple(getattr(core, "attention_heads_per_layer", ()))
    rope = getattr(core, "rope_full", None)
    unsupported = [name for name, on in (
        ("network.noisy", net.noisy), ("network.num_atoms", net.num_atoms > 1),
        ("network.iqn", net.iqn), ("network.lstm_size", net.lstm_size),
        ("network.core.kind", getattr(core, "kind", None) != "hybrid"),
        ("network.core.pattern",
         not pattern or pattern != "FD" * (len(pattern) // 2)),
        ("network.core.sandwich_norm",
         not getattr(core, "sandwich_norm", False)),
        ("network.core.loops", getattr(core, "loops", 0) < 1),
        ("network.core.attention_heads_per_layer", len(set(heads)) != 1),
        ("network.core.attention_gate",
         getattr(core, "attention_gate", True)),
        ("network.core.rope_full (not the plain embedding over all dims)",
         rope is None or rope.rotary_factor != 1.0 or rope.yarn_factor
         or rope.attention_factor != 1.0),
        ("no network.hidden", not net.hidden),
        ("no replay.unroll_length", replay.unroll_length <= 0),
        ("learner.munchausen", learner.munchausen),
        ("learner.target_tau", learner.target_tau > 0),
        ("learner.lr_schedule", learner.lr_schedule != "constant")) if on]
    if unsupported or net.torso not in ("mlp", *CONV_STRIDES):
        raise NotImplementedError(
            f"ouro_float32 does not cover {unsupported or net.torso}")
    return Hyper(
        torso=net.torso,
        core=Core(layers=len(pattern) // 2, turns=int(core.loops),
                  norm_eps=float(core.norm_eps), heads=heads[0],
                  kv_heads=core.num_key_value_heads, head_dim=core.head_dim,
                  intermediate=int(core.intermediate_size),
                  theta=float(rope.theta)),
        dueling=bool(net.dueling), double_dqn=bool(learner.double_dqn),
        value_rescale=bool(learner.value_rescale),
        burn_in=int(replay.burn_in), unroll=int(replay.unroll_length),
        n_step=int(learner.n_step), gamma=float(learner.gamma),
        eta=float(replay.priority_mix),
        huber_delta=float(learner.huber_delta),
        learning_rate=float(learner.learning_rate),
        adam_eps=float(learner.adam_eps),
        max_grad_norm=float(learner.max_grad_norm))


def make_stream_check(cfg, env):
    """``stream(seed)`` -> ``(value, limit)``: the looped stack's own
    arithmetic where the precision of the residual stream shows, which the
    step's five numbers cannot read (a bfloat16 stream adds to them what the
    bfloat16 products put there already).

    The program's network (``build_network``, its ``unroll`` as a learner's
    burn-in call runs it: ``STREAM_STEPS`` steps of one window from the empty
    state, an episode's end among them) on seeded parameters whose OUTPUT
    norms' weights are ``STREAM_SCALE``: every sublayer then adds little to a
    stream of size 1, and what the products' rounding leaves in the stream
    shrinks with it, while a rounding of the stream itself does not. Read from the program as it runs (Flax's
    ``capture_intermediates``): what its torso handed its core, and what the
    last turn handed the heads. ``core_forward`` gets the same parameters and
    the program's own core input, so nothing in front of the core is
    compared. The value is the largest gap between the two hidden states over
    the largest of the reference's."""
    from dist_dqn_tpu.models import build_network
    from perf.reference.twotower_float32 import _frames

    net = build_network(cfg.network, env.num_actions)
    hp = hyper_from_config(cfg)
    steps = min(STREAM_STEPS, hp.burn_in + hp.unroll + hp.n_step)
    shape = (steps, 1) + tuple(env.observation_shape)
    limit = STREAM_LIMIT[cfg.network.compute_dtype]

    @jax.jit
    def program(key, obs, reset):
        carry = net.initial_state(1, history=steps)
        params = net.init(key, carry, obs, reset, method=net.unroll)["params"]
        core = {name: (dict(leaf, norm_out=leaf["norm_out"] * STREAM_SCALE)
                       if name.startswith("layer_") else leaf)
                for name, leaf in params["core"].items()}
        _, seen = net.apply(
            {"params": dict(params, core=core)}, carry, obs, reset,
            method=net.unroll, mutable=["intermediates"],
            capture_intermediates=lambda module, _: module.name in (
                "torso", "core"))
        seen = seen["intermediates"]
        # the torso's rows are the steps of the one window; the core's calls
        # are the turns, each ``(hidden [1, T, hidden], its state)``
        return (core, seen["torso"]["__call__"][0],
                seen["core"]["__call__"][-1][0][0])

    @jax.jit
    def reference(core, x, reset):
        with jax.default_matmul_precision("highest"):
            return core_forward(core, x, reset, empty_memory(hp.core),
                                hp.core)[0]

    def stream(seed: int):
        seed32 = np.uint32(seed % 2 ** 32)
        reset = np.zeros((steps, 1), bool)
        reset[np.random.default_rng([seed, 0x5354]).integers(1, steps)] = True
        core, x, hidden = program(
            jax.random.PRNGKey(seed32),
            _frames(seed32, np.uint32(0x5354), shape,
                    np.dtype(env.observation_dtype).name), reset)
        want = np.asarray(reference(core, x, reset[:, 0]), np.float64)
        gap = np.max(np.abs(np.asarray(hidden, np.float64) - want))
        return float(gap / np.max(np.abs(want))), limit

    return stream


def make_further_check(cfg, env):
    """``r2d2_float32.make_further_check`` — the program's sequence ring
    against ``sequence_ring.py``'s plain rules, five numbers with their limits
    — on a ring of ``RING_SLOTS`` time slices (that module reads its own
    count once, while it builds the check), and ``stream``
    (``make_stream_check``)."""
    kept = r2d2_float32.RING_SLOTS
    r2d2_float32.RING_SLOTS = RING_SLOTS
    try:
        ring = r2d2_float32.make_further_check(cfg, env)
    finally:
        r2d2_float32.RING_SLOTS = kept
    stream = make_stream_check(cfg, env)
    return lambda seed: dict(ring(seed), stream=stream(seed))


# -- operations a grad step requires ------------------------------------------

def forward_flops_per_step(cfg, env) -> Dict[str, float]:
    """Multiply-accumulates x 2 one step of one window REQUIRES in a forward
    pass, by part, EVERY TURN counted (``loops`` passes over the layers
    held). The attention counts its projections, and scores and weighted
    values over the keys a query SEES (the causal triangle, not the blocks
    the program computes it by, and not cut shorter at a reset: an episode's
    end is the data's, not the model's); elementwise work — the norms, four
    a layer a turn — is left out."""
    from perf.reduce import flops

    net, core = cfg.network, cfg.network.core
    if net.torso not in CONVS:
        raise NotImplementedError(
            f"ouro_float32 counts {sorted(CONVS)} torsos, not {net.torso!r}")
    hidden = net.hidden
    window = (cfg.replay.burn_in + cfg.replay.unroll_length
              + cfg.learner.n_step)
    torso = flops.cnn_layer_macs(tuple(env.observation_shape),
                                 CONVS[net.torso], hidden,
                                 env.num_actions, False)[:-1]
    kv, D = core.num_key_value_heads, core.head_dim
    per_step = {
        "torso": 2.0 * sum(torso),
        "heads": 2.0 * hidden * (env.num_actions + (1 if net.dueling else 0)),
        "attention_full": 0.0, "mlp_dense": 0.0}
    heads = iter(core.attention_heads_per_layer)
    for kind in core.pattern:
        if kind == "F":
            H = next(heads)
            per_step["attention_full"] += core.loops * 2.0 * (
                hidden * (H + 2 * kv) * D + H * D * hidden
                + 2 * H * D * mean_keys_seen(window, None))
        else:
            per_step["mlp_dense"] += core.loops * 2.0 * (
                3 * hidden * core.intermediate_size)
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
# fault would read against the sound reference. ``tests/test_ouro_core.py``
# holds each at toy size; ``perf/tools/wrong_formula_study.py --cell
# ouro_q.preset`` reads them at the cell's own widths on the chip.

def _bfloat16(x):
    """``x`` rounded to bfloat16's 8 significant bits, as an op of its own:
    a pair of casts inside one program is folded away."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


WRONG_FORMULAS = {
    "three_turns": ("turn_count", lambda core: core.turns - 1),
    "no_norm_between_the_turns": (
        "norm_after_turn", lambda x, weight, eps, last:
        rms_norm(x, weight, eps) if last else x),
    "pre_norm_only": ("norm_output", lambda out, weight, eps: out),
    # every turn attending over what the LAST turn made of the earlier steps
    # (a ring the turns share holds the last writer's keys) beside its own
    # keys for this call's
    "one_ring_shared_by_the_turns": (
        "memory_of_turn", lambda memory, turn: memory[-1]),
    "theta_10000": (
        "rope_of", lambda core: Rope(
            theta=10_000.0, partial_rotary_factor=1.0, factor=0.0,
            original_max_position_embeddings=0, beta_fast=0.0, beta_slow=0.0,
            attention_factor=1.0)),
    "bfloat16_residual_stream": ("stream", _bfloat16),
}
