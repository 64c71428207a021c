"""The comparison that decides ``correct``: the program's learner step
against the configuration's plain reference, at the configuration's own
widths, outside the measured window.

Everything compared is a function of the code and ``--seed`` alone — never of
how many chunks a window held. What is specific to a kind of learner comes
from the configuration's reference module (``perf/reference/<name>.py``,
named by the configuration's file; ``REFERENCE_NAMES`` below and
``perf/README.md`` give the contract): which learner the program builds and
how its Q-values are read (``make_program``), what a seeded batch looks like
(``seeded_batch``), the plain float32 step (``step``, ``adam_delta``), and
the tolerances with their reasons (``TOLERANCES``). What is the same for
every configuration stands here.

The state is made from the seed by the program itself: its ``init``, then
``WARM_STEPS`` of its own ``train_step`` on seeded batches (so Adam has
moments and a step count), and a target network that lags the online one
(half way to a second seeded initialisation: without a lag a double-Q argmax
and a plain maximum pick the same action). On that state both sides take one
step on one more seeded batch: the program's ``train_step`` — the very
function the chunk program scans — and the reference, which gets the same
arrays and computes in float32.

Compared, each relative to the size of what is compared:
  q           max |q_p - q_r| over max |q_r|, the Q-values the reference
              module's ``q_of`` reads from the program
  priorities  the same for the per-row priorities, at the 95th percentile
              of rows (see ``check`` for why not the maximum)
  loss        |loss_p - loss_r| / |loss_r|
  grad        ||g_p - g_r|| over the whole gradient VECTOR as the optimizer
              takes it (after the global-norm clip), relative to the norm
              of the gradient the same rows give when their TD errors all
              pull one way (the reference's ``grad_scale``): the size of
              what is summed, which rounding noise follows. The gradient's
              own norm does not serve: where the rows' TD errors cancel it
              is small, and the same noise then reads ten times larger (on
              the chip ||g_p - g_r|| stayed within 0.012-0.084 over 128
              seeded states while ||g_r|| went from 0.38 to 4.9). The
              program's gradient is read back from its own Adam state:
              g = (mu' - b1 mu) / (1 - b1)
  optimizer   ||d_p - d_r|| / ||d_r|| for the parameter change d, where d_r
              is the reference's Adam applied to the PROGRAM's gradient:
              float32 arithmetic on both sides in every configuration, so
              tight in all of them. (The change is not compared across the
              two gradients: Adam divides each coordinate by its own
              history, which turns one bf16 rounding in a small coordinate
              into a large relative error of the step — a heavy-tailed
              number that says nothing the gradient does not.)

A reference module may offer ``make_further_check(cfg, env)`` ->
``further(seed)`` -> ``{name: (value, limit)}``: numbers of its own, for
what its kind of learner has beside the step (a replay that rebuilds what
it stores, say), each with its limit. They join ``errors`` and
``tolerances`` under their names and are held like the five.
"""
from __future__ import annotations

import time
from typing import Callable, Dict

import numpy as np

from .manifest import ManifestError

WARM_STEPS = 3
PRIORITY_ROWS_PERCENTILE = 95.0
COMPARED = ("q", "priorities", "loss", "grad", "optimizer")
# What a reference module must define (perf/README.md says what each is).
REFERENCE_NAMES = ("make_program", "seeded_batch", "hyper_from_config",
                   "step", "adam_delta", "ADAM_B1", "TOLERANCES",
                   "grad_step_flops")


def require_reference(reference) -> None:
    """Refuse a reference module that lacks a name the harness asks for,
    by that name, before anything is built."""
    missing = [n for n in REFERENCE_NAMES if not hasattr(reference, n)]
    if missing:
        raise ManifestError(
            f"reference module {getattr(reference, '__file__', reference)} "
            f"does not define {', '.join(missing)} (perf/README.md: the "
            "contract of a reference module)")


def tolerances_of(reference, cfg) -> Dict[str, float]:
    """The reference's own bounds for the type the configuration computes
    in: one number for each quantity compared."""
    dtype = cfg.network.compute_dtype
    table = reference.TOLERANCES
    if dtype not in table or set(table[dtype]) != set(COMPARED):
        raise ManifestError(
            f"reference module {getattr(reference, '__file__', reference)}: "
            f"TOLERANCES states no bound for each of {COMPARED} under "
            f"compute type {dtype!r}")
    return dict(table[dtype])


class CoarseNet:
    """The control of the comparison: the program's network computing from
    parameters rounded through float8 (e4m3: 4 significant bits against
    bfloat16's 8), the nearest precision below the one the bf16
    configurations state. ``make_check`` with this network in the program's
    place has to come out NOT ok (``perf/tests`` pins it at toy size,
    ``perf/tools/reference_study.py --control`` reads it at the cells' own
    sizes on the chip)."""

    def __init__(self, net):
        self._net = net

    def __getattr__(self, name):
        return getattr(self._net, name)

    def apply(self, params, *args, **kwargs):
        import jax
        import jax.numpy as jnp

        coarse = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), params)
        return self._net.apply(coarse, *args, **kwargs)


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


def _stack(*leaves):
    """Batches one above the other, where they are: a reference module may
    make its large leaves on the device."""
    import jax

    if isinstance(leaves[0], jax.Array):
        return jax.numpy.stack(leaves)
    return np.stack(leaves)


def make_check(reference, cfg, env, net, batch_size: int
               ) -> Callable[[int], Dict]:
    """``check(seed)`` for one configuration at ``batch_size`` rows; every
    program is built once, so a tool can draw many seeds."""
    import jax

    require_reference(reference)
    hp = reference.hyper_from_config(cfg)
    tolerances = tolerances_of(reference, cfg)
    init, train_step, q_of = reference.make_program(cfg, env, net)
    further = (reference.make_further_check(cfg, env)
               if hasattr(reference, "make_further_check") else None)

    def batch_of(seed, index):
        return reference.seeded_batch(seed, index, batch_size, cfg, env)

    @jax.jit
    def seeded_state(seed, batches):
        k_online, k_lagged = jax.random.split(jax.random.PRNGKey(seed))
        state, _ = jax.lax.scan(
            lambda s, batch: (train_step(s, batch)[0], None),
            init(k_online), batches)
        target = jax.tree.map(lambda t, l: 0.5 * t + 0.5 * l,
                              state.target_params, init(k_lagged).params)
        return state._replace(target_params=target)

    @jax.jit
    def both_sides(state, batch):
        """(program's, reference's) for everything compared."""
        new_state, metrics = train_step(state, batch)
        ref = reference.step(state.params, state.target_params, batch, hp)
        adam, new_adam = (_find_adam(s.opt_state) for s in (state, new_state))
        b1 = reference.ADAM_B1
        grads_program = jax.tree.map(
            lambda new, old: (new - b1 * old) / (1.0 - b1),
            new_adam.mu, adam.mu)
        return {
            "q": (q_of(state.params, batch), ref["q"]),
            "priorities": (metrics["priorities"], ref["priorities"]),
            "loss": (metrics["loss"], ref["loss"]),
            "grad_norm": (metrics["grad_norm"], ref["grad_norm"]),
            "grad": (grads_program, ref["grads"]),
            "grad_scale": ref["grad_scale"],
            "optimizer": (
                jax.tree.map(lambda new, old: new - old, new_state.params,
                             state.params),
                reference.adam_delta(grads_program, adam.mu, adam.nu,
                                     adam.count, hp))}

    def check(seed: int) -> Dict:
        t0 = time.perf_counter()
        warm = [batch_of(seed, i) for i in range(WARM_STEPS)]
        state = seeded_state(np.uint32(seed % 2 ** 32),
                             jax.tree.map(_stack, *warm))
        got = jax.device_get(both_sides(state, batch_of(seed, WARM_STEPS)))
        td_program, td_reference = (np.asarray(x, np.float64)
                                    for x in got["priorities"])
        errors = {
            "q": _rel_max(*got["q"]),
            # Rows, not the maximum: where two actions' Q-values differ by
            # less than one rounding of the compute type a greedy argmax
            # may pick the other, and that row's bootstrap is then another
            # action's value. Such rows are few (their share is recorded);
            # 95 of 100 rows must agree.
            "priorities": _rel_max(td_program, td_reference,
                                   PRIORITY_ROWS_PERCENTILE),
            "loss": _rel_max(*got["loss"]),
            "grad": _rel_l2(*got["grad"], scale=float(got["grad_scale"])),
            "optimizer": _rel_l2(*got["optimizer"]),
        }
        limits = dict(tolerances)
        for name, (value, limit) in (further(seed) if further
                                     else {}).items():
            errors[name], limits[name] = value, limit
        finite = all(np.isfinite(v) for v in errors.values())
        return {
            "ok": finite and all(errors[k] <= limits[k] for k in limits),
            "errors": errors, "tolerances": limits,
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
