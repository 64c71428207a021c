"""Plain float32 reference of one R2D2 sequence-learner step.

Recurrent replay as published (Kapturowski et al. 2019) around the network
the program has (NOT the published one: convolutions, a dense ``embed``
layer, an LSTM, linear dueling heads; ``perf/configs/r2d2.json`` lists what
the paper's has more): sequences replayed from the recurrent state stored with
their first step, a burn-in prefix that only refreshes that state (no
gradient flows through it), n-step double-Q targets inside the window under
the invertible value rescaling ``h`` (Pohlen et al. 2018), importance
weights per sequence, and the sequence priority ``eta * max|td| + (1 - eta)
* mean|td|``. Written in straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; the parameter tree is read by
its key names only, and nothing is shared with ``agents/r2d2.py`` or
``models/recurrent.py``.

A window is ``burn_in + unroll + n_step`` steps, time-major ``[T, S, ...]``:
the loss covers the ``unroll`` steps after the burn-in, the last ``n_step``
steps exist only to be bootstrapped from. ``reset[t]`` says that ``obs[t]``
opens an episode (the state is zeroed before that step); ``done[t]`` ends
the return at step ``t``. The layers come from ``plain.py`` (a convolution written as its
windows times the kernel as one matrix), and the batch is walked in blocks of ``SEQ_BLOCK``
sequences whose losses and gradients are summed — exact, because every term
of the loss belongs to one sequence — for the same reasons as in
``dqn_float32.py``: compile time and memory, not mathematics.

Departures from the paper, taken from the configuration as it is run: Adam's
epsilon and the global-norm clip as the preset states them, and a truncated
episode is treated as ended (the ring stores no successor frame).

Besides the step it holds what ``perf/harness/reference_check.py`` asks of
every reference module (``perf/README.md``): the program's learner it stands
beside (``make_program``), a seeded batch in that learner's layout, the
tolerances, and the FLOPs of a step; and one thing more, which the harness
takes where a module offers it (``make_further_check``): the program's
sequence ring — insert, alive starts, write-back, the stratified draw, the
window gather with its stack rebuild, the stored states — against the plain
rules of ``sequence_ring.py``. ``make_program`` and ``make_further_check``
are the two places that name the program.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import sequence_ring as plain_ring
from perf.reference.plain import (ADAM_B1, CONV_STRIDES, CONVS,  # noqa: F401
                                  adam_delta, clip_by_global_norm, cnn_torso,
                                  dense, global_norm, mlp_torso)

RESCALE_EPS = 1e-3
SEQ_BLOCK = 4

# Largest error allowed for each quantity ``reference_check`` compares (its
# docstring defines them; ``q`` is the online network's Q-values at the
# unroll + n_step training positions of every sequence, ``priorities`` the
# per-sequence eta-mix), by the dtype the configuration computes in.
#
# bfloat16: set from two studies on the chip at the cell's own widths (PR 29,
# ``perf/tools/reference_study.py --seeds 64 --control 8 --cells
# r2d2.preset``: 64 windows x 125 steps, Nature CNN + embed + LSTM 512; the
# first on frames drawn on the host, the second on frames drawn on the
# device, 128 seeded states in all), each bound at least three times the
# largest reading of either:
#   q           largest 0.70% / 0.73% (median 0.50%): bf16 through the torso
#               and 125 recurrent steps, narrow from seed to seed -> 2.2%
#   priorities  largest 0.61% / 0.56% (median 0.27%)              -> 2%
#   loss        largest 2.15% / 2.51% (median 0.8%); largest where the
#               seeded loss itself is small (0.007-0.16 by seed)  -> 7.5%
#   grad        largest 3.15% / 2.33% (median 0.47%, 90th percentile 1.9%):
#               a broad tail, widest in the same small-loss states -> 10%
#   optimizer   largest 5.3e-5 / 5.7e-5, float32 against float32, held at
#               three times that (no coarser type moves it: the control
#               reads 1.4e-5 to 1.9e-5; it is there for a step that is not
#               Adam's)                                          -> 1.7e-4
# The control, the program's network on float8-rounded weights (e4m3, 4
# significant bits; 8 seeds in each study): gradient 27-42% in every seed
# (the rounding also flushes small gradients), Q 1.7-5.4% (outside 2.2% in
# 14 of 16), |TD| 0.6-1.6%, loss 0.2-4.9%: ``grad`` is the number that tells
# bf16 from a coarser type in every seed, with a factor of 2.7 to the
# control's smallest and 3.2 to the sound runs' largest. A wrong formula — a
# gradient through the burn-in, no value rescaling, the plain maximum for
# double-Q, a mean-only priority — fails in float32 at toy size (perf/tests).
# float32 configurations differ from the reference only by summation order;
# no cell runs one, so these are the toy tests' bounds, not read on a chip.
TOLERANCES = {
    "bfloat16": {"q": 0.022, "priorities": 0.02, "loss": 0.075, "grad": 0.10,
                 "optimizer": 1.7e-4},
    "float32": {"q": 1e-4, "priorities": 1e-4, "loss": 1e-4, "grad": 1e-3,
                "optimizer": 1e-3},
}


class Hyper(NamedTuple):
    """What the step needs from the configuration (hashable: jit-static)."""

    torso: str
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


def _embed(torso: Dict, frames, hp: Hyper):
    """[N, H, W, C] frames -> [N, E]: the convolutions and the dense layer
    in front of the recurrent core."""
    x = frames.astype(jnp.float32)
    if frames.dtype == jnp.uint8:
        x = x / 255.0
    if hp.torso == "mlp":
        x = mlp_torso(torso["MLPTorso_0"], x)
    else:
        x = cnn_torso(torso["CNNTorso_0"], x, CONV_STRIDES[hp.torso])
    if "embed" in torso:
        x = jax.nn.relu(dense(torso["embed"], x))
    return x


def _lstm_step(cell: Dict, state, x):
    """One step of the LSTM (Hochreiter & Schmidhuber 1997, with a forget
    gate): ``state`` is (cell, hidden); the input's matrices carry no bias,
    the hidden state's do."""
    c, h = state

    def gate(name):
        return dense(cell["i" + name], x) + dense(cell["h" + name], h)

    c = (jax.nn.sigmoid(gate("f")) * c
         + jax.nn.sigmoid(gate("i")) * jnp.tanh(gate("g")))
    h = jax.nn.sigmoid(gate("o")) * jnp.tanh(c)
    return c, h


def _leave_burn_in(state):
    """The state the burn-in prefix hands on is a constant to the gradient:
    the prefix refreshes a stale stored state and learns nothing."""
    return jax.lax.stop_gradient(state)


def q_sequence(params: Dict, obs, reset, start_state, hp: Hyper):
    """Q-values ``[unroll + n_step, S, A]`` at the positions after the
    burn-in, from the stored state: the whole window goes through the
    torso frame by frame and through the LSTM step by step; the state that
    leaves the burn-in prefix is a constant to the gradient."""
    p = params["params"]
    steps, seqs = obs.shape[:2]
    x = _embed(p["torso"], obs.reshape((steps * seqs,) + obs.shape[2:]), hp)
    x = x.reshape((steps, seqs, -1))
    keep = 1.0 - reset.astype(jnp.float32)[..., None]

    def one_step(state, inputs):
        x_t, keep_t = inputs
        state = _lstm_step(p["core"]["lstm"],
                           (state[0] * keep_t, state[1] * keep_t), x_t)
        return state, state[1]

    state = tuple(s.astype(jnp.float32) for s in start_state)
    if hp.burn_in:
        state, _ = jax.lax.scan(one_step, state,
                                (x[:hp.burn_in], keep[:hp.burn_in]))
        state = _leave_burn_in(state)
    _, hidden = jax.lax.scan(one_step, state,
                             (x[hp.burn_in:], keep[hp.burn_in:]))
    adv = dense(p["advantage"], hidden)
    if not hp.dueling:
        return adv
    return (dense(p["value"], hidden) + adv
            - jnp.mean(adv, axis=-1, keepdims=True))


def rescale(x):
    """h(x) = sign(x) (sqrt(|x| + 1) - 1) + eps x."""
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + RESCALE_EPS * x


def rescale_inverse(x):
    """h^-1, in closed form (Pohlen et al. 2018, proposition A.2)."""
    root = jnp.sqrt(1.0 + 4.0 * RESCALE_EPS * (jnp.abs(x) + 1.0
                                               + RESCALE_EPS))
    return jnp.sign(x) * (((root - 1.0) / (2.0 * RESCALE_EPS)) ** 2 - 1.0)


def n_step_targets(q_online, q_target, reward, done, hp: Hyper):
    """``[unroll, S]`` targets: for each loss position k the rewards of
    steps k .. k+n-1, each discounted by gamma per step and cut at the first
    ``done``, plus gamma**n times the bootstrap at step k+n unless an
    episode ended on the way. The bootstrap is the target network's value
    of the action the online network (double-Q) or the target network
    itself prefers there; with value rescaling, networks speak in h-space
    and returns add up in plain space."""
    n, unroll = hp.n_step, hp.unroll
    alive = 1.0 - done.astype(jnp.float32)
    # [n, unroll, S]: row j holds step k+j for every loss position k
    rewards = jnp.stack([reward[j:j + unroll] for j in range(n)])
    alives = jnp.stack([alive[j:j + unroll] for j in range(n)])
    # still inside the episode when step k+j's reward arrives / after it
    before = jnp.cumprod(jnp.concatenate(
        [jnp.ones_like(alives[:1]), alives[:-1]]), axis=0)
    powers = hp.gamma ** jnp.arange(n, dtype=jnp.float32)[:, None, None]
    returns = jnp.sum(powers * before * rewards, axis=0)
    discount = hp.gamma ** n * jnp.prod(alives, axis=0)
    at_n_online, at_n_target = q_online[n:n + unroll], q_target[n:n + unroll]
    chooser = at_n_online if hp.double_dqn else at_n_target
    boot = jnp.take_along_axis(
        at_n_target, jnp.argmax(chooser, axis=-1)[..., None], axis=-1)[..., 0]
    if not hp.value_rescale:
        return returns + discount * boot
    return rescale(returns + discount * rescale_inverse(boot))


def _q_taken(params, batch: Dict, hp: Hyper):
    """The online network's Q-values after the burn-in, and ``[unroll, S]``
    those of the actions taken at the loss positions."""
    q_online = q_sequence(params, batch["obs"], batch["reset"],
                          batch["start_state"], hp)
    taken = batch["action"][hp.burn_in:hp.burn_in + hp.unroll]
    return q_online, jnp.take_along_axis(
        q_online[:hp.unroll], taken[..., None].astype(jnp.int32),
        axis=-1)[..., 0]


def _loss_sum(params, target_params, batch: Dict, hp: Hyper):
    """Sum over the sequences of ``weight * mean over the unroll of
    huber(TD)``; aux: |TD| ``[unroll, S]`` and the online Q-values."""
    q_online, qa = _q_taken(params, batch, hp)
    q_target = q_sequence(target_params, batch["obs"], batch["reset"],
                          batch["start_state"], hp)
    td = qa - jax.lax.stop_gradient(n_step_targets(
        q_online, q_target, batch["reward"][hp.burn_in:],
        batch["done"][hp.burn_in:], hp))
    quad = jnp.minimum(jnp.abs(td), hp.huber_delta)
    huber = 0.5 * quad * quad + hp.huber_delta * (jnp.abs(td) - quad)
    return (jnp.sum(batch["weights"] * jnp.mean(huber, axis=0)),
            (jnp.abs(td), q_online))


def _pull_sum(params, batch: Dict, pull, hp: Hyper):
    """Sum of ``pull * Q(obs, action)`` over positions and sequences: with
    ``pull`` the size of each position's ``d loss / d Q``, its gradient is
    what the loss's gradient would be if every TD error had the same
    sign."""
    return jnp.sum(pull * _q_taken(params, batch, hp)[1])


def step(params, target_params, batch: Dict, hp: Hyper) -> Dict:
    """Loss and gradient of one learner step on a batch as ``seeded_batch``
    lays it out: the online Q-values at the training positions, the mean
    over sequences of the weighted mean Huber loss, the sequence
    priorities, the gradient's global norm, the gradient as the optimizer
    takes it (clipped to ``max_grad_norm``), and ``grad_scale``: the norm
    that gradient would have if no two TD errors cancelled."""
    seqs = batch["weights"].shape[0]
    block = max(b for b in range(1, SEQ_BLOCK + 1) if seqs % b == 0)
    used = {k: batch[k] for k in ("obs", "action", "reward", "done", "reset",
                                  "start_state", "weights")}

    def to_blocks(x, axis):
        shape = x.shape[:axis] + (seqs // block, block) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    blocks = {k: (jax.tree.map(lambda x: to_blocks(x, 0), v)
                  if k in ("start_state", "weights") else to_blocks(v, 1))
              for k, v in used.items()}

    def one_block(total, rows):
        (loss, (abs_td, q)), grads = jax.value_and_grad(
            _loss_sum, has_aux=True)(params, target_params, rows, hp)
        pull = (rows["weights"] * jnp.minimum(abs_td, hp.huber_delta)
                / hp.unroll)
        one_way = jax.grad(_pull_sum)(params, rows, pull, hp)
        return (jax.tree.map(jnp.add, total, (loss, grads, one_way)),
                (abs_td, q))

    with jax.default_matmul_precision("highest"):
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (loss, grads, one_way), (abs_td, q) = jax.lax.scan(
            one_block, (jnp.float32(0.0), zeros, zeros), blocks)
        # [blocks, steps, block, ...] -> [steps, S, ...]
        abs_td, q = (jnp.moveaxis(x, 0, 1).reshape(
            (x.shape[1], seqs) + x.shape[3:]) for x in (abs_td, q))
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
    unsupported = [name for name, on in (
        ("network.noisy", net.noisy), ("network.num_atoms", net.num_atoms > 1),
        ("network.iqn", net.iqn), ("no network.lstm_size", not net.lstm_size),
        ("no replay.unroll_length", replay.unroll_length <= 0),
        ("learner.munchausen", learner.munchausen),
        ("learner.target_tau", learner.target_tau > 0),
        ("learner.lr_schedule", learner.lr_schedule != "constant")) if on]
    if unsupported or net.torso not in ("mlp", *CONV_STRIDES):
        raise NotImplementedError(
            f"r2d2_float32 does not cover {unsupported or net.torso}")
    return Hyper(torso=net.torso, dueling=bool(net.dueling),
                 double_dqn=bool(learner.double_dqn),
                 value_rescale=bool(learner.value_rescale),
                 burn_in=int(replay.burn_in),
                 unroll=int(replay.unroll_length),
                 n_step=int(learner.n_step), gamma=float(learner.gamma),
                 eta=float(replay.priority_mix),
                 huber_delta=float(learner.huber_delta),
                 learning_rate=float(learner.learning_rate),
                 adam_eps=float(learner.adam_eps),
                 max_grad_norm=float(learner.max_grad_norm))


# -- what the harness asks of a reference module, beside the step -----------

def make_program(cfg, env, net):
    """The program's side of the comparison: ``init(key)`` and
    ``train_step(state, batch)`` of the learner ``train.train`` builds for
    a recurrent configuration (``agents/r2d2.py make_r2d2_learner``), and
    ``q_of(params, batch)``: the program's network unrolled over the whole
    window from the stored state, at the training positions. ``batch`` is a
    ``seeded_batch``."""
    from dist_dqn_tpu.agents.r2d2 import make_r2d2_learner
    from dist_dqn_tpu.types import SequenceSample

    init, train_step = make_r2d2_learner(net, cfg.learner, cfg.replay)
    # made inside the caller's trace: a constant of its program, not a
    # buffer that stays on the device
    def example():
        return jnp.zeros(tuple(env.observation_shape),
                         np.dtype(env.observation_dtype))

    def q_of(params, batch):
        _, q = net.apply(params, batch["start_state"], batch["obs"],
                         batch["reset"], method=net.unroll)
        return q[cfg.replay.burn_in:]

    return (lambda key: init(key, example()),
            lambda state, batch: train_step(state, SequenceSample(**batch)),
            q_of)


def _largest_gap(got, want) -> float:
    """max |got / want - 1|; infinite where ``want`` has a zero or a hole
    (a draw on a cell without mass)."""
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(np.asarray(got, np.float64) / want - 1.0)
    return float(np.max(np.where(np.isfinite(gap), gap, np.inf)))


def _noise_key(seed):
    """XLA's own bit generator: threefry takes seconds to compile and is no
    better noise for a comparison."""
    return jax.random.key(seed, impl="rbg")


def _noise_like(key, shape, dtype: str):
    if dtype == "uint8":
        return jax.random.bits(key, shape, jnp.uint8)
    return jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _noise(seed, index, shape, dtype):
    """Frames of noise, made where they are used: a window batch of the
    cell's shape is 226 MB, and four of them drawn on the host, stacked
    and sent over were most of the check's seconds."""
    return _noise_like(jax.random.fold_in(_noise_key(seed), index),
                       shape, dtype)


def seeded_batch(seed: int, index: int, batch_size: int, cfg, env) -> Dict:
    """Batch ``index`` of ``seed``: ``batch_size`` windows in the sequence
    learner's own time-major layout, from the seed alone. Frames are noise
    (no two alike; drawn on the device), the stored states are what an LSTM
    holds mid-episode, a few windows hold an episode's end (``done`` at
    step t, ``reset`` at t+1), and importance ``weights`` lie in (0, 1]
    where the configuration samples by priority."""
    rng = np.random.default_rng([seed, index])
    steps = (cfg.replay.burn_in + cfg.replay.unroll_length
             + cfg.learner.n_step)
    shape = (steps, batch_size)
    obs = _noise(np.uint32(seed % 2 ** 32), np.uint32(index),
                 shape + tuple(env.observation_shape),
                 np.dtype(env.observation_dtype).name)
    # About one window in four holds an end, anywhere in it: some fall in
    # the burn-in (a reset only), some among the loss positions (returns
    # cut short, bootstraps dropped).
    done = np.zeros(shape, bool)
    ends = rng.random(batch_size) < 0.25
    done[rng.integers(0, steps - 1, batch_size)[ends],
         np.flatnonzero(ends)] = True
    reset = np.concatenate([np.zeros((1, batch_size), bool), done[:-1]])
    lstm = cfg.network.lstm_size
    cell = (0.5 * rng.standard_normal((batch_size, lstm))).astype(np.float32)
    gate = rng.uniform(0.2, 0.8, (batch_size, lstm)).astype(np.float32)
    return {
        "obs": obs,
        "action": rng.integers(0, env.num_actions, shape).astype(np.int32),
        # One sign, and n-step sums that rescale to both sides of
        # huber_delta = 1 and above a fresh network's Q-values: the TD
        # errors then share a sign and their gradients add up (a sum that
        # cancels is small against its own rounding noise).
        "reward": rng.choice([0.2, 0.4, 0.6, 0.8], shape).astype(np.float32),
        "done": done,
        "reset": reset,
        "start_state": (cell, (gate * np.tanh(cell)).astype(np.float32)),
        "weights": (rng.uniform(0.2, 1.0, batch_size)
                    if cfg.replay.prioritized
                    else np.ones(batch_size)).astype(np.float32),
        # where the ring would say the windows came from: unused by the step
        "t_idx": np.arange(batch_size, dtype=np.int32),
        "b_idx": np.zeros(batch_size, np.int32),
    }


# -- the sequence ring ------------------------------------------------------
# Time slices of the ring the comparison fills (the cell's has 40,000): the
# rules do not depend on the count, 2,048 wrap once under the steps fed and
# keep the frames at 0.25 GB. Everything else is the configuration's: lanes,
# window length, stride, stack, frame shape, windows a draw, alpha, beta.
RING_SLOTS = 2048
# Limits of the five numbers of ``make_further_check``. Four are counts of
# what must be equal: 0 (and 0 in all 72 checks of the study on the chip,
# PR 29). The weights are float32 powers on the chip against float64:
# largest 7.4e-6 over those 72 (median 4.2e-6, narrow); one weight 0.1% off
# reads 1e-3 (perf/tests) and one taken from a neighbouring cell tens of
# per cent                                                        -> 3e-5
RING_LIMITS = {"ring_starts": 0, "ring_writeback": 0, "ring_strata": 0,
               "ring_windows": 0, "ring_weights": 3e-5}


def make_further_check(cfg, env) -> Callable[[int], Dict[str, Tuple]]:
    """``further(seed)`` -> ``{name: (value, limit)}``: the program's
    sequence ring, driven through the calls and static arguments the
    recurrent loop makes (``r2d2_loop.make_r2d2_train``), against
    ``sequence_ring.py`` on the same seeded steps.

    ring_starts     cells where the priority plane after the inserts is not
                    the plain rule's (1 on alive starts, 0 elsewhere) +
                    drawn starts that are not drawable
    ring_writeback  cells where the plane after the write-backs (the loop's
                    size, distinct cells, an eighth of them dead) differs,
                    + 1 if the largest priority does
    ring_strata     draws outside their stratum of the plain running total
    ring_windows    elements of the drawn windows that differ: every byte
                    of the (rebuilt) observations, actions, rewards,
                    ``done``, ``reset``, both planes of the stored state
    ring_weights    largest relative gap of the importance weights
    """
    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.replay import sequence_device as sring

    rcfg = cfg.replay
    lanes, draws = cfg.actor.num_envs, cfg.learner.batch_size
    length = rcfg.burn_in + rcfg.unroll_length + cfg.learner.n_step
    stride = rcfg.sequence_stride or rcfg.unroll_length
    obs_shape = tuple(env.observation_shape)
    stack, stored_shape, frame_shape, _ = loop_common.resolve_frame_dedup(
        rcfg, env, obs_shape)
    cell_slots = max(rcfg.capacity // lanes, length + 2)
    flat = loop_common.resolve_flat_storage(
        rcfg, stored_shape, env.observation_dtype, cell_slots, lanes,
        prefer_flat=bool(stack))
    flatten, unflatten = loop_common.flat_obs_codecs(flat, stored_shape)
    use_pallas, interpret = loop_common.pallas_routing(rcfg.pallas_sampler)
    slots = min(RING_SLOTS, cell_slots)
    if slots < length + stride + stack:
        raise NotImplementedError(
            f"r2d2_float32: a ring of {slots} slots for windows of {length} "
            f"every {stride}")
    written = slots + 3 * stride + 7        # wrapped, and not on a stride
    alpha, beta = rcfg.priority_exponent, rcfg.importance_exponent
    # with dedup a step brings one frame and the ring rebuilds the stacks;
    # without, a step brings its whole observation and the ring returns it
    step_shape = obs_shape[:-1] if stack else obs_shape
    obs_dtype = np.dtype(env.observation_dtype).name

    @jax.jit
    def seeded_planes(seed):
        """What a step brings that is large — its frame and the recurrent
        state entering it — made on the device; the plain side indexes the
        same arrays by absolute step."""
        k_f, k_c, k_h = jax.random.split(jax.random.fold_in(
            _noise_key(seed), 0x52494E47), 3)
        state = tuple(jax.random.normal(
            k, (written, lanes, cfg.network.lstm_size)) for k in (k_c, k_h))
        return _noise_like(k_f, (written, lanes) + step_shape,
                           obs_dtype), state

    @jax.jit
    def program(seed, frames, state, steps, at, new):
        """The program's ring through the loop's own calls: ``written``
        inserts, a write-back at the cells ``at``, a draw."""
        ring = sring.sequence_ring_init(
            slots, lanes, loop_common.ring_obs_example(
                jnp.zeros(stored_shape, frames.dtype), flat),
            cfg.network.lstm_size, merge_obs_rows=flat)

        def insert(ring, t):
            obs = frames[t][..., None] if stack else frames[t]
            return sring.sequence_ring_add(
                ring, flatten(obs), steps["action"][t], steps["reward"][t],
                steps["terminated"][t], steps["truncated"][t],
                (state[0][t], state[1][t]), length, stride,
                merge_obs_rows=flat), None

        ring, _ = jax.lax.scan(insert, ring, jnp.arange(written))
        inserted = ring.priorities
        ring, _ = jax.lax.scan(
            lambda ring, w: (sring.sequence_ring_update(
                ring, w[0], w[1], w[2], eps=rcfg.priority_eps), None),
            ring, (at[0], at[1], new))
        drawn = sring.sequence_ring_sample(
            ring, jax.random.fold_in(jax.random.PRNGKey(seed), 0x44524157),
            draws, length, alpha, jnp.float32(beta), use_pallas=use_pallas,
            pallas_interpret=interpret, merge_obs_rows=flat,
            frame_stack=stack, frame_shape=frame_shape)
        if not stack:
            drawn = drawn._replace(obs=unflatten(drawn.obs))
        return inserted, ring.priorities, ring.max_priority, drawn

    @jax.jit
    def windows_differ(frames, state, drawn, want, start):
        """Elements of the drawn windows that are not what the plain rules
        name: observations from ``frame_of``, the small fields, the state
        stored with each window's first step."""
        lane = drawn.b_idx
        if stack:
            obs = jnp.moveaxis(
                frames[want["frame_of"], lane[None, :, None]], 2, -1)
        else:
            obs = frames[want["frame_of"][..., 0], lane[None, :]]
        count = jnp.sum(obs != drawn.obs)
        for name in ("action", "reward", "done", "reset"):
            count += jnp.sum(want[name] != getattr(drawn, name))
        for plane, got in zip(state, drawn.start_state):
            count += jnp.sum(plane[start, lane] != got)
        return count

    def further(seed: int) -> Dict[str, Tuple]:
        seed32 = np.uint32(seed % 2 ** 32)
        steps = plain_ring.seeded_steps(seed, written, lanes,
                                        env.num_actions)
        rng = np.random.default_rng([seed, 0x57424B])
        frames, state = seeded_planes(seed32)
        alive, drawable = plain_ring.alive_starts(written, slots, lanes,
                                                  length, stride, stack)
        # write-backs of the loop's own size at distinct cells, an eighth
        # of each dead, until three quarters of the alive starts carry a
        # priority of their own
        dead = max(draws // 8, 1)
        rounds = max(3 * int(alive.sum()) // 4 // (draws - dead), 1)
        cells = np.concatenate([
            rng.permutation(np.flatnonzero(alive.reshape(-1)))[
                :rounds * (draws - dead)].reshape(rounds, -1),
            rng.permutation(np.flatnonzero(~alive.reshape(-1)))[
                :rounds * dead].reshape(rounds, -1)], axis=1)
        at = ((cells // lanes).astype(np.int32),
              (cells % lanes).astype(np.int32))
        new = rng.gamma(2.0, 0.5, cells.shape).astype(np.float32)
        inserted, after, largest, drawn = program(seed32, frames, state,
                                                  steps, at, new)
        got = jax.device_get({
            "inserted": inserted, "after": after, "largest": largest,
            "slot": drawn.t_idx, "lane": drawn.b_idx,
            "weights": drawn.weights})
        slot, lane = got["slot"], got["lane"]
        plane = alive.astype(np.float32)
        plane_after, largest_after = plane, np.float32(1.0)
        for slot_w, lane_w, new_w in zip(at[0], at[1], new):
            plane_after, largest_after = plain_ring.write_back(
                plane_after, largest_after, slot_w, lane_w, new_w,
                rcfg.priority_eps)
        mass = np.where(drawable, plane_after.astype(np.float64) ** alpha,
                        0.0)
        # a start that no draw may return is counted under ring_starts;
        # its window is read at the oldest drawable start's place instead
        # of past the steps written
        start = np.where(
            drawable[slot, lane],
            plain_ring.absolute_step(slot, written, slots),
            written - slots + max(stack - 1, 0) + stride)
        want = {k: v.astype(np.int32) if v.dtype == np.int64 else v
                for k, v in plain_ring.window_fields(
                    steps, start, lane, length, stack).items()}
        values = {
            "ring_starts": int(np.sum(got["inserted"] != plane)
                               + np.sum(~drawable[slot, lane])),
            "ring_writeback": int(np.sum(got["after"] != plane_after)
                                  + (got["largest"] != largest_after)),
            "ring_strata": plain_ring.strata_missed(mass, slot, lane),
            "ring_windows": int(windows_differ(
                frames, state, drawn, want, start.astype(np.int32))),
            "ring_weights": _largest_gap(got["weights"], plain_ring.importance(
                mass, drawable, slot, lane, beta)),
        }
        return {name: (values[name], RING_LIMITS[name]) for name in values}

    return further


def grad_step_flops(cfg, env) -> float:
    """FLOPs one grad step requires, from shapes, as 2 x multiply-
    accumulates of what the passes REQUIRE. Per sequence: the torso forward
    on every frame of the window for both networks; its backward on the
    online network's ``unroll + n_step`` frames after the burn-in (two
    forwards a layer, the first layer's input being data); the LSTM's four
    gates per step, forward for both networks over the window and backward
    over the online network's training positions; the heads at the training
    positions. The burn-in prefix has no backward pass: its state is a
    constant. Elementwise work and the optimizer are left out."""
    from perf.reduce import flops

    net = cfg.network
    if net.torso not in CONVS:
        raise NotImplementedError(
            f"r2d2_float32 counts {sorted(CONVS)} torsos, not {net.torso!r}")
    train = cfg.replay.unroll_length + cfg.learner.n_step
    window = cfg.replay.burn_in + train
    # the torso's layers up to the embedding (``hidden`` wide), no head
    torso = flops.cnn_layer_macs(tuple(env.observation_shape),
                                 CONVS[net.torso], net.hidden,
                                 env.num_actions, False)[:-1]
    if not net.hidden:
        raise NotImplementedError(
            "r2d2_float32 counts a torso that ends in a dense layer")
    gates = 4 * (net.hidden + net.lstm_size) * net.lstm_size
    heads = net.lstm_size * (env.num_actions + (1 if net.dueling else 0))
    forward = 2 * window * (sum(torso) + gates) + 2 * train * heads
    backward = train * (2 * sum(torso) - torso[0] + 2 * gates + 2 * heads)
    return 2.0 * cfg.learner.batch_size * (forward + backward)
