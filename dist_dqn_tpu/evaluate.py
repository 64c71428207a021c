"""Standalone checkpoint evaluation:
``python -m dist_dqn_tpu.evaluate --config cartpole --checkpoint-dir d``.

The deploy-side half of the checkpoint story (SURVEY.md §5): load the
newest learner checkpoint a training run (either runtime) saved with
``--checkpoint-dir`` and run greedy episodes on the config's env, without
any training machinery in the loop. Prints one JSON line with the mean
undiscounted return.
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from dist_dqn_tpu.config import CONFIGS, ExperimentConfig, apply_overrides
from dist_dqn_tpu.utils.checkpoint import CheckpointMissingError


def _ckpt_prefix(checkpoint_dir: str):
    """Where the params live inside this directory's checkpoints:
    learner-kind saves the learner at the root; --checkpoint-replay
    (carry-kind) and the host-replay whole-state checkpoints
    (host_loop-kind, ISSUE 8) nest it one level down."""
    from dist_dqn_tpu.utils.checkpoint import read_checkpoint_kind

    return (("learner",)
            if read_checkpoint_kind(checkpoint_dir) in ("carry",
                                                        "host_loop")
            else ())


def _restore_latest(checkpoint_dir: str, example_params, step=None,
                    member=None):
    """(frames, params) from the newest checkpoint (or a specific
    retained ``step``). Read-only surface: never create the directory on
    a typo'd path, and release the orbax manager after the one restore.

    Eval needs only the policy parameters, so this partial-restores the
    params subtree (utils/checkpoint.py restore_params): the training
    run's optimizer structure (e.g. lr-schedule state) never constrains
    an eval invocation, and carry-kind (--checkpoint-replay) runs are
    evaluable without a ring-sized carry template. ``member`` selects
    one policy out of a --population run's [M]-stacked tree (ISSUE 20);
    restore_params refuses the solo/stacked direction mismatches with
    the actual cause.
    """
    from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer

    if not os.path.isdir(checkpoint_dir):
        raise CheckpointMissingError(
            f"no checkpoint found under {checkpoint_dir!r}")
    prefix = _ckpt_prefix(checkpoint_dir)
    ckpt = TrainCheckpointer(checkpoint_dir)
    try:
        restored = ckpt.restore_params(example_params, step=step,
                                       prefix=prefix, member=member)
    except FileNotFoundError as e:
        # Convert to the skippable type ONLY when the requested step is
        # genuinely gone from the retained set (live retention race) —
        # a corrupt-but-present step (interrupted save) must propagate
        # loudly, not be mislabeled as deleted.
        if step is not None and step not in ckpt.all_steps():
            raise CheckpointMissingError(str(e)) from e
        raise
    finally:
        ckpt.close()
    if restored is None:
        raise CheckpointMissingError(
            f"no checkpoint found under {checkpoint_dir!r}")
    return restored


def _build_eval(cfg: ExperimentConfig, episodes: int, epsilon: float,
                seed: int):
    """(example learner pytree, jitted evaluator, eval key) for the
    config's JAX env — shared by the single-point and curve surfaces so
    the compiled evaluator is built exactly once either way."""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    rng = jax.random.PRNGKey(seed)
    rng, k_init, k_eval = jax.random.split(rng, 3)

    from dist_dqn_tpu.agents.agent import make_agent
    from dist_dqn_tpu.train_loop import make_evaluator
    init = make_agent(net, cfg).init_learner
    evaluator = make_evaluator(cfg, env, net, num_episodes=episodes,
                               epsilon=epsilon)

    obs_example = jax.numpy.zeros(env.observation_shape,
                                  env.observation_dtype)
    example = init(k_init, obs_example)
    return example, jax.jit(evaluator), k_eval


def evaluate_checkpoint(cfg: ExperimentConfig, checkpoint_dir: str,
                        episodes: int = 10, seed: int = 0,
                        epsilon: float = 0.001, step: int = None,
                        export_params: str = None,
                        member: int = None) -> dict:
    """Restore the newest checkpoint (or retained ``step``) and play
    greedy episodes.

    ``export_params`` additionally writes the restored policy parameters
    as a standalone pytree checkpoint (utils/checkpoint.save_pytree) —
    the deploy artifact: a few MB of params with no optimizer state,
    loadable anywhere via ``restore_pytree(path, example_params)``
    without the training run's directory or flags.

    Returns {"eval_return": mean, "frames": checkpoint cursor, ...}.
    Raises FileNotFoundError if the directory holds no checkpoint.
    """
    example, evaluator, k_eval = _build_eval(cfg, episodes, epsilon, seed)
    frames, params = _restore_latest(checkpoint_dir, example.params,
                                     step=step, member=member)
    mean_return = float(evaluator(params, k_eval))
    out = {"eval_return": mean_return, "frames": frames,
           "episodes": episodes, "config": cfg.name}
    if member is not None:
        out["member"] = member
    if export_params:
        from dist_dqn_tpu.utils.checkpoint import save_pytree

        save_pytree(os.path.abspath(export_params), params)
        out["exported_params"] = os.path.abspath(export_params)
    return out


def _skip_row(step: int) -> dict:
    """The one shape both --all-steps modes emit for a checkpoint that a
    live training run's retention deleted mid-walk."""
    return {"frames": step,
            "skipped": "checkpoint deleted during walk (live retention)"}


def evaluate_checkpoint_curve(cfg: ExperimentConfig, checkpoint_dir: str,
                              episodes: int = 10, seed: int = 0,
                              epsilon: float = 0.001,
                              log_fn=None, member: int = None) -> list:
    """Evaluate EVERY retained checkpoint step (oldest first) — the
    learning curve of a run directory. One env/net/evaluator build and
    one compile serve all steps; one checkpoint manager restores each
    into the same example pytree. Identical eval rng per step, so curve
    points differ only by the restored parameters. Steps garbage-
    collected mid-walk by a live training run's retention are skipped
    with a log line rather than aborting the walk.
    """
    from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer

    if not os.path.isdir(checkpoint_dir):
        raise FileNotFoundError(
            f"no checkpoint found under {checkpoint_dir!r}")
    rows = []
    prefix = _ckpt_prefix(checkpoint_dir)
    ckpt = TrainCheckpointer(checkpoint_dir)
    try:
        steps = ckpt.all_steps()
        if not steps:
            # The dir exists but holds no complete step yet — the
            # live-run-before-first-save shape, distinct from a missing
            # dir so --wait-for-checkpoint can retry it (still a
            # FileNotFoundError subclass for fail-fast callers).
            raise CheckpointMissingError(
                f"no checkpoint found under {checkpoint_dir!r}")
        # Build (env, net, jitted evaluator) only once a step list
        # exists — an empty dir errors without paying the build.
        example, evaluator, k_eval = _build_eval(cfg, episodes, epsilon,
                                                 seed)
        for step in steps:
            try:
                frames, params = ckpt.restore_params(
                    example.params, step=step, prefix=prefix,
                    member=member)
            except FileNotFoundError:
                # Narrow scope: only the restore is guarded, so an
                # unrelated FileNotFoundError cannot be mislabeled.
                if log_fn:
                    log_fn(_skip_row(step))
                continue
            row = {"eval_return": float(evaluator(params, k_eval)),
                   "frames": frames, "episodes": episodes,
                   "config": cfg.name}
            if member is not None:
                row["member"] = member
            rows.append(row)
            if log_fn:
                log_fn(row)
    finally:
        ckpt.close()
    return rows


def evaluate_checkpoint_host(cfg: ExperimentConfig, checkpoint_dir: str,
                             host_env: str, episodes: int = 10,
                             seed: int = 0, epsilon: float = 0.001,
                             max_steps: int = 20_000,
                             step: int = None, member: int = None) -> dict:
    """Greedy checkpoint episodes on a HOST env (real ALE / DM-Control /
    gymnasium) — the deploy-side counterpart of an Ape-X split training
    run, which steps host envs the JAX stand-ins only approximate.

    The network is built with the HOST env's action count (an ale:
    checkpoint trained on Breakout has 4 heads, not the stand-in's 6),
    one vectorized env instance per episode, whole-game episodes and RAW
    (unclipped) game scores (``for_eval=True``: episodic-life and reward
    clipping are training devices, not scoring rules).
    """
    from dist_dqn_tpu.envs.gym_adapter import make_host_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.utils.host_eval import run_greedy_episodes

    env = make_host_env(host_env, episodes, seed=10_000 + seed,
                        for_eval=True)
    net = build_network(cfg.network, env.num_actions)
    obs = env.reset()
    from dist_dqn_tpu.agents.agent import make_agent
    agent = make_agent(net, cfg)

    rng = jax.random.PRNGKey(seed)
    rng, k_init = jax.random.split(rng)
    example = agent.init_learner(k_init, jax.numpy.asarray(obs[0]))
    frames, params = _restore_latest(checkpoint_dir, example.params,
                                     step=step, member=member)

    returns, truncated, _ = run_greedy_episodes(
        env, jax.jit(agent.act), params, rng, episodes=episodes,
        recurrent_carry=agent.initial_state(episodes), epsilon=epsilon,
        max_steps=max_steps)
    out = {"eval_return": float(returns.mean()), "frames": frames,
           "episodes": episodes, "config": cfg.name, "host_env": host_env,
           "episodes_truncated": truncated}
    if member is not None:
        out["member"] = member
    return out


def _apply_risk_eta(cfg: ExperimentConfig, eta) -> ExperimentConfig:
    """Evaluate an IQN checkpoint under a different risk profile than it
    was trained with (the point of IQN's CVaR acting: one set of learned
    quantiles, a family of policies). Parameters are risk-agnostic, so
    any eta in (0, 1] restores cleanly."""
    import dataclasses

    if not cfg.network.iqn:
        raise ValueError(
            "--risk-cvar-eta only applies to IQN configs (the acting "
            f"fractions of {cfg.name!r} are not tau-conditioned)")
    return dataclasses.replace(
        cfg, network=dataclasses.replace(cfg.network, risk_cvar_eta=eta))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", choices=sorted(CONFIGS), required=True)
    parser.add_argument("--checkpoint-dir", required=True)
    parser.add_argument("--episodes", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--platform", default=None,
                        help="force a JAX platform (e.g. cpu)")
    parser.add_argument("--host-env", default=None,
                        help="evaluate on a HOST env (e.g. ale:Breakout, "
                             "CartPole-v1, dmc:reacher:easy) instead of "
                             "the config's JAX stand-in env")
    parser.add_argument("--risk-cvar-eta", type=float, default=None,
                        help="IQN configs only: act on the lower-eta CVaR "
                             "tail of the learned return distribution "
                             "instead of the trained profile (risk-averse "
                             "deploy-time policy from the same checkpoint)")
    parser.add_argument("--set", dest="overrides", action="append",
                        metavar="PATH=VALUE", default=[],
                        help="override config fields by dotted path (must "
                             "match how the checkpoint was trained, e.g. "
                             "--set network.dueling=true)")
    parser.add_argument("--member", type=int, default=None, metavar="K",
                        help="population checkpoints (ISSUE 20, "
                             "--population runs): evaluate member K of "
                             "the [M]-stacked tree (0-based). Required "
                             "for population directories — a member-less "
                             "restore of a stacked tree is refused with "
                             "the cause — and refused on solo "
                             "directories")
    parser.add_argument("--all-steps", action="store_true",
                        help="evaluate EVERY retained checkpoint step "
                             "(oldest first, one JSON line each) — a "
                             "learning curve from the run directory "
                             "instead of just the newest point")
    parser.add_argument("--export-params", default=None, metavar="PATH",
                        help="also write the restored policy parameters "
                             "as a standalone pytree checkpoint at PATH "
                             "(params only, no optimizer state — the "
                             "deploy artifact; JAX-env surface, newest/"
                             "single step)")
    parser.add_argument("--wait-for-checkpoint", type=float, default=0.0,
                        metavar="SECONDS",
                        help="retry a missing checkpoint (absent dir or "
                             "a live run dir that has not saved yet) for "
                             "up to this many seconds instead of failing "
                             "immediately — for evals launched alongside "
                             "training (default 0: fail fast as before)")
    parser.add_argument("--telemetry-port", type=int, default=None,
                        help="serve this process's telemetry registry "
                             "(/metrics, /metrics.json, /healthz, "
                             "/debug/*) on this port; 0 binds an "
                             "ephemeral port (reported as a "
                             "telemetry_port log line) — eval runs are "
                             "scrapable exactly like train runs "
                             "(docs/observability.md)")
    parser.add_argument("--telemetry-host", default="127.0.0.1",
                        help="bind address for --telemetry-port "
                             "(loopback by default; 0.0.0.0 exposes the "
                             "scrape surface outside the container/VM)")
    parser.add_argument("--telemetry-snapshot", default=None,
                        help="dump a JSON snapshot of the telemetry "
                             "registry to this path at exit (offline "
                             "runs; same data as /metrics.json)")
    parser.add_argument("--fleet-dir", default=None,
                        help="fleet registry directory (ISSUE 16): "
                             "announce this eval's telemetry endpoint "
                             "to the run's aggregator; defaults to "
                             "$DQN_FLEET_DIR")
    args = parser.parse_args(argv)
    if args.export_params and (args.all_steps or args.host_env):
        parser.error("--export-params applies to the single-point JAX-env "
                     "surface (not --all-steps or --host-env)")
    # Telemetry surface parity with the train CLI (ISSUE 4 satellite):
    # eval processes populate the same registry (checkpoint restore
    # spans, env steps), so expose the same scrape/snapshot knobs.
    if args.telemetry_snapshot:
        from dist_dqn_tpu.telemetry import install_snapshot_dump

        install_snapshot_dump(args.telemetry_snapshot)
    if args.fleet_dir:
        import os as _os

        _os.environ["DQN_FLEET_DIR"] = args.fleet_dir
    if args.telemetry_port is not None:
        from dist_dqn_tpu import telemetry
        from dist_dqn_tpu.telemetry import fleet as _fleet

        _srv = telemetry.start_server(args.telemetry_port,
                                      host=args.telemetry_host)
        print(json.dumps({"telemetry_port": _srv.port}))
        _fleet.register_endpoint("eval", _srv.port,
                                 host=args.telemetry_host)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from dist_dqn_tpu.utils import backend
    backend.enable_compile_cache()
    backend.log_device()
    try:
        cfg = apply_overrides(CONFIGS[args.config], args.overrides)
    except ValueError as e:
        parser.error(str(e))
    if args.risk_cvar_eta is not None:
        cfg = _apply_risk_eta(cfg, args.risk_cvar_eta)

    def tag_and_print(out):
        if args.risk_cvar_eta is not None:
            out["risk_cvar_eta"] = args.risk_cvar_eta
        print(json.dumps(out), flush=True)

    def run_one(step=None):
        if args.host_env:
            out = evaluate_checkpoint_host(
                cfg, args.checkpoint_dir, args.host_env,
                episodes=args.episodes, seed=args.seed, step=step,
                member=args.member)
        else:
            out = evaluate_checkpoint(
                cfg, args.checkpoint_dir,
                episodes=args.episodes, seed=args.seed, step=step,
                export_params=args.export_params, member=args.member)
        tag_and_print(out)

    def dispatch():
        # Cheap presence gate BEFORE any env/network build: without it,
        # every --wait-for-checkpoint retry rebuilds the whole eval
        # stack (env + net + jit, seconds on CPU) just to find the dir
        # still empty — and the --all-steps listing paths raise plain
        # FileNotFoundError on an absent dir, which the retry loop
        # deliberately does not catch. One probe makes the absent-dir
        # and empty-live-dir shapes retryable on every mode.
        from dist_dqn_tpu.utils.checkpoint import checkpoint_present

        if not checkpoint_present(args.checkpoint_dir):
            raise CheckpointMissingError(
                f"no checkpoint found under {args.checkpoint_dir!r}")
        if args.all_steps and not args.host_env:
            # One build + one compile + one manager serve the whole curve.
            evaluate_checkpoint_curve(
                cfg, args.checkpoint_dir, episodes=args.episodes,
                seed=args.seed,
                log_fn=tag_and_print, member=args.member)
        elif args.all_steps:
            # Host envs: per-step restores through the single-point
            # surface (episode stepping dominates; no scan-evaluator
            # recompile).
            from dist_dqn_tpu.utils.checkpoint import list_checkpoint_steps

            steps = list_checkpoint_steps(args.checkpoint_dir)
            if not steps:
                # Existing-but-empty run dir: CheckpointMissingError so
                # --wait-for-checkpoint retries (a missing dir raised
                # FileNotFoundError from the listing already).
                raise CheckpointMissingError(
                    f"no checkpoint found under {args.checkpoint_dir!r}")
            for step in steps:
                # A step deleted mid-walk by a live run's retention
                # raises the DISTINCT CheckpointMissingError from the
                # restore — skip it and keep walking. Any other error
                # (missing ROM/asset, plain FileNotFoundError included)
                # propagates loudly; no per-step re-listing, no TOCTOU
                # window (ADVICE round 3).
                try:
                    run_one(step)
                except CheckpointMissingError:
                    tag_and_print(_skip_row(step))
        else:
            run_one()

    # --wait-for-checkpoint (ISSUE 7 satellite): an eval launched beside
    # a fresh training run sees the run dir before its first save lands
    # (the manager mkdirs at construction) — bounded retry instead of an
    # immediate crash. ONLY the distinct CheckpointMissingError retries
    # (utils/checkpoint.py wait_for_checkpoint, shared with the serving
    # CLI); any other failure (missing ROM/asset, corrupt step) stays
    # loud on the first attempt, and the default 0s budget keeps today's
    # fail-fast behavior.
    from dist_dqn_tpu.utils.checkpoint import wait_for_checkpoint

    wait_for_checkpoint(dispatch, args.wait_for_checkpoint)


if __name__ == "__main__":
    main()
