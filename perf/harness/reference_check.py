"""The comparison that decides ``correct``: the program's learner step
against the configuration's plain reference, at the configuration's own
widths, outside the measured window.

Everything compared is a function of the code and ``--seed`` alone — never of
how many chunks a window held. The state is made from the seed by the
program itself: ``make_learner``'s ``init``, then ``WARM_STEPS`` of its own
``train_step`` on seeded batches (so Adam has moments and a step count), and
a target network that lags the online one (half way to a second seeded
initialisation: without a lag the double-Q argmax and the plain maximum pick
the same action). On that state both sides take one step on one more seeded
batch: the program's ``train_step`` — the very function the chunk program
scans — and the reference, which gets the same arrays and computes in
float32.
"""
from __future__ import annotations

import time
from typing import Callable, Dict

import numpy as np

WARM_STEPS = 3
PRIORITY_ROWS_PERCENTILE = 95.0

# Largest error allowed, by the dtype the configuration computes in. Each is
# relative to the size of what is compared:
#   q           max |q_p - q_r| over max |q_r|, the Q-values of ``obs``
#   priorities  the same for the per-row |TD|, at the 95th percentile of
#               rows (see ``check`` for why not the maximum)
#   loss        |loss_p - loss_r| / |loss_r|
#   grad        ||g_p - g_r|| over the whole gradient VECTOR as the optimizer
#               takes it (after the global-norm clip), relative to the norm
#               of the gradient the same rows give when their TD errors all
#               pull one way (the reference's ``grad_scale``): the size of
#               what is summed, which rounding noise follows. The gradient's
#               own norm does not serve: where the rows' TD errors cancel it
#               is small, and the same noise then reads ten times larger (on
#               the chip ||g_p - g_r|| stayed within 0.012-0.084 over 128
#               seeded states while ||g_r|| went from 0.38 to 4.9). The
#               program's gradient is read back from its own Adam state:
#               g = (mu' - b1 mu) / (1 - b1)
#   optimizer   ||d_p - d_r|| / ||d_r|| for the parameter change d, where d_r
#               is the reference's Adam applied to the PROGRAM's gradient:
#               float32 arithmetic on both sides in every configuration, so
#               tight in all of them. (The change is not compared across the
#               two gradients: Adam divides each coordinate by its own
#               history, which turns one bf16 rounding in a small coordinate
#               into a large relative error of the step — a heavy-tailed
#               number that says nothing the gradient does not.)
#
# bfloat16 keeps 8 significant bits (2^-8 = 0.4% per rounding); through
# five layers, the loss and the backward pass the roundings add up. The
# bf16 bounds are at least three times the largest error over the seeded
# states of the study on the chip (PERF.md section 6, PR 23: 64 seeds for
# each configuration at its own widths; largest readings Q 0.94%, |TD|
# 0.94%, loss 1.2%, gradient 1.6%), rounded up; the optimizer's, float32
# against float32, read 1.8e-5 at most. A type with fewer bits fails them:
# with the program's weights rounded through float8 (e4m3, 4 significant
# bits) the Q-values are 4-7% off (perf/tests pins it). A wrong formula — no
# importance weights, a dropped dueling mean, another learning rate — moves
# loss, gradient or optimizer by tens of percent; double-Q against the
# plain maximum, at a state this close to initialisation, moves |TD| and
# loss by 1-9% depending on the seed, so that one is caught in most seeds
# and not in all. float32 configurations differ from the reference only by
# summation order.
TOLERANCES = {
    "bfloat16": {"q": 0.03, "priorities": 0.03, "loss": 0.04, "grad": 0.05,
                 "optimizer": 1e-3},
    "float32": {"q": 1e-4, "priorities": 1e-4, "loss": 1e-4, "grad": 1e-3,
                "optimizer": 1e-3},
}


def _find_adam(opt_state):
    """The optimizer state's Adam moments, found by attribute name."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise ValueError("no Adam state (mu, nu) in the learner's opt_state")


def synthetic_batch(seed, batch_size: int, obs_shape, obs_dtype,
                    num_actions: int, gamma_n: float, weighted: bool
                    ) -> Dict[str, np.ndarray]:
    """A seeded batch in the learner's own layout: n-step ``reward``,
    ``discount = gamma**n * (1 - done)``, importance ``weights`` in (0, 1]
    where the configuration samples by priority, ones elsewhere."""
    rng = np.random.default_rng(seed)

    def frames():
        if np.dtype(obs_dtype) == np.uint8:
            return rng.integers(0, 256, (batch_size, *obs_shape),
                                dtype=np.uint8)
        return rng.standard_normal((batch_size, *obs_shape)).astype(
            np.float32)

    return {
        "obs": frames(),
        "next_obs": frames(),
        "action": rng.integers(0, num_actions, batch_size).astype(np.int32),
        # One sign and larger than a fresh network's Q-values, so that the
        # rows' TD errors share a sign and their gradients add up: a sum
        # that cancels is small against its own rounding noise, and its
        # relative error says little. On both sides of huber_delta = 1.
        "reward": rng.choice([0.5, 1.0, 1.5, 2.0],
                             batch_size).astype(np.float32),
        "discount": (gamma_n * (rng.random(batch_size) > 0.05)).astype(
            np.float32),
        "weights": (rng.uniform(0.2, 1.0, batch_size) if weighted
                    else np.ones(batch_size)).astype(np.float32),
    }


def _rel_max(a, b, percentile: float = 100.0) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.percentile(np.abs(a - b), percentile)
                 / max(np.max(np.abs(b)), 1e-12))


def _rel_l2(a, b, scale: float = 0.0) -> float:
    """||a - b|| over ``scale``, or over ||b||, over all leaves of two
    trees of arrays."""
    import jax

    pairs = [(np.asarray(x, np.float64), np.asarray(y, np.float64))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    diff = np.sqrt(sum(float(np.sum((x - y) ** 2)) for x, y in pairs))
    norm = scale or np.sqrt(sum(float(np.sum(y ** 2)) for _, y in pairs))
    return float(diff / max(norm, 1e-30))


def make_check(reference, cfg, env, net, batch_size: int
               ) -> Callable[[int], Dict]:
    """``check(seed)`` for one configuration at ``batch_size`` rows; every
    program is built once, so a tool can draw many seeds."""
    import jax
    import jax.numpy as jnp

    from dist_dqn_tpu.agents.dqn import make_learner
    from dist_dqn_tpu.types import Transition

    hp = reference.hyper_from_config(cfg)
    tolerances = TOLERANCES[cfg.network.compute_dtype]
    init, train_step = make_learner(net, cfg.learner)
    obs_shape = tuple(env.observation_shape)
    obs_dtype = np.dtype(env.observation_dtype)

    def batch_of(seed, index):
        return synthetic_batch(
            [seed, index], batch_size, obs_shape, obs_dtype,
            env.num_actions, cfg.learner.gamma ** cfg.learner.n_step,
            cfg.replay.prioritized)

    @jax.jit
    def seeded_state(seed, batches, weights):
        k_online, k_lagged = jax.random.split(jax.random.PRNGKey(seed))
        example = jnp.zeros(obs_shape, obs_dtype)
        state, _ = jax.lax.scan(
            lambda s, bw: (train_step(s, Transition(**bw[0]), bw[1])[0],
                           None),
            init(k_online, example), (batches, weights))
        target = jax.tree.map(lambda t, l: 0.5 * t + 0.5 * l,
                              state.target_params,
                              init(k_lagged, example).params)
        return state._replace(target_params=target)

    @jax.jit
    def both_sides(state, batch, weights):
        """(program's, reference's) for everything compared."""
        new_state, metrics = train_step(state, Transition(**batch), weights)
        ref = reference.step(state.params, state.target_params, batch,
                             weights, hp)
        adam, new_adam = (_find_adam(s.opt_state) for s in (state, new_state))
        b1 = reference.ADAM_B1
        grads_program = jax.tree.map(
            lambda new, old: (new - b1 * old) / (1.0 - b1),
            new_adam.mu, adam.mu)
        return {
            "q": (net.apply(state.params, batch["obs"]), ref["q"]),
            "priorities": (metrics["priorities"], ref["priorities"]),
            "loss": (metrics["loss"], ref["loss"]),
            "grad_norm": (metrics["grad_norm"], ref["grad_norm"]),
            "grad": (grads_program, ref["grads"]),
            "grad_scale": ref["grad_scale"],
            "optimizer": (
                jax.tree.map(jnp.subtract, new_state.params, state.params),
                reference.adam_delta(grads_program, adam.mu, adam.nu,
                                     adam.count, hp))}

    def check(seed: int) -> Dict:
        t0 = time.perf_counter()
        warm = [batch_of(seed, i) for i in range(WARM_STEPS)]
        warm_weights = np.stack([b.pop("weights") for b in warm])
        state = seeded_state(
            np.uint32(seed % 2 ** 32),
            {k: np.stack([b[k] for b in warm]) for k in warm[0]},
            warm_weights)
        batch = batch_of(seed, WARM_STEPS)
        weights = batch.pop("weights")
        got = jax.device_get(both_sides(state, batch, weights))
        td_program, td_reference = (np.asarray(x, np.float64)
                                    for x in got["priorities"])
        errors = {
            "q": _rel_max(*got["q"]),
            # Rows, not the maximum: where two actions' Q-values differ by
            # less than one bf16 rounding the double-Q argmax may pick the
            # other, and that row's bootstrap is then another action's
            # value. Such rows are few (their share is recorded); 95 of 100
            # rows must agree.
            "priorities": _rel_max(td_program, td_reference,
                                   PRIORITY_ROWS_PERCENTILE),
            "loss": _rel_max(*got["loss"]),
            "grad": _rel_l2(*got["grad"], scale=float(got["grad_scale"])),
            "optimizer": _rel_l2(*got["optimizer"]),
        }
        finite = all(np.isfinite(v) for v in errors.values())
        return {
            "ok": finite and all(errors[k] <= tolerances[k]
                                 for k in tolerances),
            "errors": errors, "tolerances": tolerances,
            # recorded, not judged
            "also": {
                "grad_norm": _rel_max(*got["grad_norm"]),
                "priority_rows_outside": float(np.mean(
                    np.abs(td_program - td_reference)
                    > tolerances["priorities"]
                    * np.max(np.abs(td_reference)))),
                "reference_grad_norm": float(got["grad_norm"][1]),
                "reference_grad_scale": float(got["grad_scale"]),
                "grad_over_own_norm": _rel_l2(*got["grad"]),
                "reference_loss": float(got["loss"][1])},
            "batch_size": batch_size, "warm_steps": WARM_STEPS,
            "seconds": time.perf_counter() - t0}

    return check
