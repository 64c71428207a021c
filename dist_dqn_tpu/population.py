"""Population training plane (ISSUE 20): M vmap-stacked policies, one
program.

One cartpole/atari policy cannot fill a TPU (its utilisation is not
measured on the current installation). ROADMAP item 6's answer (after Podracer's
"one program, many policies", PAPERS.md, on the commodity-scale terms of
arXiv:2111.01264) is to train M policies — distinct seeds and
hyperparameter variants — as ONE jitted program: every carry leaf
(params, optimizer state, target params, replay ring, env vector, rng)
gains a leading member axis and ``jax.vmap`` advances all M members in
one dispatch per chunk, composing with the in-scan replay ratio and
pow2 train batches unchanged.

Member independence is a hard contract, not a best effort: member k of
an M-run must BIT-MATCH a solo run configured with member k's
hyperparameters and seeded with member k's stream (no cross-member
leakage through replay, RNG or the traced hyperparameters —
tests/test_population.py pins it). That is why

* per-member RNG streams spawn from ``--seed`` with the SeedSequence
  spawn-key discipline (PR 5): member k's base seed is
  ``SeedSequence(seed, spawn_key=(k,))`` — solo-reproducible by seeding
  a plain run with the same derived value;
* per-member epsilon decays through
  ``loop_common.make_member_epsilon`` — the op-for-op twin of the solo
  ``optax.linear_schedule`` with the constants as traced lanes;
* per-member learning rates ride the optimizer STATE
  (``agents.dqn.make_population_optimizer``) so the vmapped update
  applies bit-identically to the solo Adam at the same rate;
* per-member gamma threads into the n-step fold at sample time
  (``replay/device.py compute_n_step`` is pure jnp broadcasting).

The spec JSON (``--population-spec``) carries the per-member vectors:
an object with any of ``epsilon`` (exploration floor epsilon_end),
``lr``, ``gamma`` — each a length-M array. Members without an override
inherit the base config's value.
"""
from __future__ import annotations

import dataclasses
import json
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dist_dqn_tpu.config import ExperimentConfig, PopulationConfig
from dist_dqn_tpu.train_loop import MemberHP, make_fused_train

#: The spec's per-member vector keys, and the config field each one
#: overrides in a member's solo-equivalent run.
SPEC_KEYS = ("epsilon", "lr", "gamma")


@dataclasses.dataclass(frozen=True)
class PopulationSpec:
    """Validated per-member hyperparameter vectors (None = inherit)."""

    epsilon: Optional[Tuple[float, ...]] = None
    lr: Optional[Tuple[float, ...]] = None
    gamma: Optional[Tuple[float, ...]] = None


def parse_spec(text: str, size: int) -> PopulationSpec:
    """Parse + validate a ``--population-spec`` JSON document.

    Accepts an object whose keys are a subset of :data:`SPEC_KEYS`,
    each a length-``size`` array of numbers. Empty text means "no
    overrides". Raises ``ValueError`` with the offending key on any
    shape/range violation — at startup, not as a traced NaN later.
    """
    if not text or not text.strip():
        return PopulationSpec()
    try:
        raw = json.loads(text)
    except json.JSONDecodeError as e:
        raise ValueError(f"population spec is not valid JSON: {e}") from e
    if not isinstance(raw, dict):
        raise ValueError(
            f"population spec must be a JSON object of per-member "
            f"vectors {SPEC_KEYS}, got {type(raw).__name__}")
    unknown = sorted(set(raw) - set(SPEC_KEYS))
    if unknown:
        raise ValueError(
            f"population spec has unknown keys {unknown}; supported "
            f"per-member vectors: {list(SPEC_KEYS)}")
    out = {}
    for key in SPEC_KEYS:
        if key not in raw:
            continue
        vec = raw[key]
        if not isinstance(vec, (list, tuple)) or not all(
                isinstance(v, (int, float)) and not isinstance(v, bool)
                for v in vec):
            raise ValueError(
                f"population spec {key!r} must be an array of numbers")
        if len(vec) != size:
            raise ValueError(
                f"population spec {key!r} has {len(vec)} entries for "
                f"--population {size}; each vector must be length M")
        vals = tuple(float(v) for v in vec)
        if key == "epsilon" and not all(0.0 <= v <= 1.0 for v in vals):
            raise ValueError(
                "population spec 'epsilon' entries must be in [0, 1] "
                "(the per-member exploration floor epsilon_end)")
        if key == "lr" and not all(v > 0.0 for v in vals):
            raise ValueError(
                "population spec 'lr' entries must be > 0")
        if key == "gamma" and not all(0.0 < v <= 1.0 for v in vals):
            raise ValueError(
                "population spec 'gamma' entries must be in (0, 1]")
        out[key] = vals
    return PopulationSpec(**out)


def resolve_spec(cfg: ExperimentConfig) -> PopulationSpec:
    """The config's spec, parsed against its own ``population.size``."""
    spec = parse_spec(cfg.population.spec_json, cfg.population.size)
    if spec.lr is not None and cfg.learner.lr_schedule != "constant":
        raise ValueError(
            "population spec 'lr' requires learner.lr_schedule="
            "'constant' (agents/dqn.py make_population_optimizer: the "
            "anneal horizon is not a stackable member axis)")
    return spec


def member_seeds(seed: int, size: int) -> List[int]:
    """Member k's base seed: ``SeedSequence(seed, spawn_key=(k,))`` —
    the PR 5 stream discipline. A solo run seeded with ``seeds[k]``
    consumes exactly member k's key stream."""
    return [int(np.random.SeedSequence(seed, spawn_key=(k,))
                .generate_state(1)[0]) for k in range(size)]


def member_config(cfg: ExperimentConfig, spec: PopulationSpec,
                  k: int) -> ExperimentConfig:
    """Member k's solo-equivalent config: the base config with member
    k's spec overrides applied statically and the population section
    reset — the reference program of the member-independence pin."""
    actor, learner = cfg.actor, cfg.learner
    if spec.epsilon is not None:
        actor = dataclasses.replace(actor, epsilon_end=spec.epsilon[k])
    if spec.lr is not None:
        learner = dataclasses.replace(learner,
                                      learning_rate=spec.lr[k])
    if spec.gamma is not None:
        learner = dataclasses.replace(learner, gamma=spec.gamma[k])
    return dataclasses.replace(cfg, actor=actor, learner=learner,
                               population=PopulationConfig())


def member_hp(cfg: ExperimentConfig, spec: PopulationSpec) -> MemberHP:
    """The stacked [M] :class:`MemberHP` arrays the vmapped entry
    points consume. ``eps_delta`` folds epsilon_start - epsilon_end on
    the host in float64 and casts to f32 — the exact constant
    ``optax.linear_schedule`` embeds for the solo program, so member
    epsilon is bitwise the solo schedule."""
    M = cfg.population.size
    eps_end = (spec.epsilon if spec.epsilon is not None
               else (cfg.actor.epsilon_end,) * M)
    lr = (spec.lr if spec.lr is not None
          else (cfg.learner.learning_rate,) * M)
    gamma = (spec.gamma if spec.gamma is not None
             else (cfg.learner.gamma,) * M)
    start = float(cfg.actor.epsilon_start)
    return MemberHP(
        eps_delta=jnp.asarray([np.float32(start - float(e))
                               for e in eps_end], jnp.float32),
        eps_end=jnp.asarray(eps_end, jnp.float32),
        gamma=jnp.asarray(gamma, jnp.float32),
        lr=jnp.asarray(lr, jnp.float32))


def extract_member(tree, k: int):
    """Member k's slice of an [M]-stacked pytree (params, carry, ...)."""
    return jax.tree.map(lambda x: x[k], tree)


def stacked_members(tree) -> int:
    """The member-axis width M of a stacked pytree."""
    return int(jax.tree.leaves(tree)[0].shape[0])


def make_population_train(cfg: ExperimentConfig, env, net):
    """(init_population, run_population_chunk) — the vmap-stacked twins
    of ``make_fused_train``'s (init, run_chunk).

    ``init_population(keys, hp)`` vmaps the per-member init over [M]
    base keys + the stacked :class:`MemberHP`;
    ``run_population_chunk(carries, hp, num_iters)`` advances all M
    members ONE dispatch per chunk (jit it with ``static_argnums=2,
    donate_argnums=0`` — the [M]-stacked carries update in place like
    the solo carry does). Each member's lane is the exact solo program:
    same replay ring, same key stream, same schedule arithmetic.
    """
    spec = resolve_spec(cfg)
    init_m, run_m = make_fused_train(cfg, env, net, member_hp=True,
                                     member_lr=spec.lr is not None)

    def init_population(keys, hp: MemberHP):
        return jax.vmap(init_m)(keys, hp)

    def run_population_chunk(carries, hp: MemberHP, num_iters: int):
        return jax.vmap(lambda c, h: run_m(c, h, num_iters))(carries, hp)

    return init_population, run_population_chunk
