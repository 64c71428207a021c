"""Population training plane (ISSUE 20): M vmap-stacked policies in one
program must change HOW MANY runs advance per dispatch, never WHAT any
single run computes.

The load-bearing assertions:

* the M=1 pin: ``--population 1`` (with or without a spec) routes to
  the plain fused program and lands bit-identical params — the member
  axis disengages entirely, by construction;
* the MEMBER-INDEPENDENCE pin: member k of an M=2 stacked run lands
  bit-identical params to an M=1 stacked run built from member k's
  spec slice and seeded with member k's SeedSequence stream — no
  cross-member leakage through replay, RNG or the traced
  hyperparameter lanes (vmap batching is member-width independent);
* the UNBATCHED-BODY pin: the traced-hyperparameter member body (no
  vmap) IS the plain solo program, bit for bit — the member lanes and
  the ``inject_hyperparams`` optimizer state add zero numerics; the
  vmapped program tracks it to reduction-reorder tolerance (like the
  dp-sharded pmean pin, vmap batching may reorder gradient-sum
  reductions by ~1 ulp);
* the STACKED-CHECKPOINT contract: the checkpoint holds the [M]-
  stacked tree plus a POPULATION width marker; ``restore_params(
  member=k)`` extracts one policy, every direction mismatch (member on
  solo, member-less on stacked, out-of-range, resume at a different M)
  refuses with the actual cause, and the M-mismatch refusal counts
  under dqn_checkpoint_refused_resumes_total{reason="population"};
* the CLI surface: --population warns-and-ignores on runtimes without
  a member axis, refuses the --mesh-devices cross outright, and
  validates the spec at the parser boundary;
* the lint teeth: a jitted ``*population*`` entry point without
  donate_argnums bites in the donation plugin (the TARGET vocabulary
  covers the new plane).
"""
from __future__ import annotations

import dataclasses
import glob
import json

import jax
import numpy as np
import pytest

from dist_dqn_tpu import population as pop
from dist_dqn_tpu.config import CONFIGS, PopulationConfig
from dist_dqn_tpu.envs import make_jax_env
from dist_dqn_tpu.models import build_network
from dist_dqn_tpu.train_loop import make_fused_train

SPEC2 = json.dumps({"epsilon": [0.05, 0.2], "lr": [1e-3, 5e-4],
                    "gamma": [0.99, 0.97]})


def _tiny_cfg(size=1, spec_json="", **kw):
    cfg = CONFIGS["cartpole"]
    return dataclasses.replace(
        cfg,
        actor=dataclasses.replace(cfg.actor, num_envs=8),
        network=dataclasses.replace(cfg.network, torso="mlp",
                                    mlp_features=(32,), hidden=0,
                                    compute_dtype="float32"),
        replay=dataclasses.replace(cfg.replay, capacity=2048, min_fill=64),
        learner=dataclasses.replace(cfg.learner, batch_size=16),
        population=PopulationConfig(size=size, spec_json=spec_json),
        **kw)


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _run_stacked(cfg, seeds, chunks=2, iters=40):
    """A few chunks of the vmap-stacked program; returns final carries."""
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    hp = pop.member_hp(cfg, pop.resolve_spec(cfg))
    init_p, run_p = pop.make_population_train(cfg, env, net)
    keys = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in seeds])
    run = jax.jit(run_p, static_argnums=2, donate_argnums=0)
    carries = init_p(keys, hp)
    for _ in range(chunks):
        carries, metrics = run(carries, hp, iters)
    return jax.device_get(carries), jax.device_get(metrics)


def test_spec_parsing_and_validation():
    spec = pop.parse_spec(SPEC2, 2)
    assert spec.lr == (1e-3, 5e-4)
    assert spec.epsilon == (0.05, 0.2)
    assert spec.gamma == (0.99, 0.97)
    assert pop.parse_spec("", 4) == pop.PopulationSpec()
    with pytest.raises(ValueError, match="not valid JSON"):
        pop.parse_spec("{nope", 2)
    with pytest.raises(ValueError, match="JSON object"):
        pop.parse_spec("[1, 2]", 2)
    with pytest.raises(ValueError, match="unknown keys"):
        pop.parse_spec('{"tau": [1, 2]}', 2)
    with pytest.raises(ValueError, match="length M"):
        pop.parse_spec('{"lr": [0.001]}', 2)
    with pytest.raises(ValueError, match="numbers"):
        pop.parse_spec('{"lr": ["a", "b"]}', 2)
    with pytest.raises(ValueError, match="epsilon"):
        pop.parse_spec('{"epsilon": [0.5, 1.5]}', 2)
    with pytest.raises(ValueError, match="lr"):
        pop.parse_spec('{"lr": [0.001, 0.0]}', 2)
    with pytest.raises(ValueError, match="gamma"):
        pop.parse_spec('{"gamma": [0.99, 0.0]}', 2)
    # The lr-schedule pin: a per-member lr cannot stack an anneal.
    cfg = _tiny_cfg(size=2, spec_json=json.dumps({"lr": [1e-3, 5e-4]}))
    cfg = dataclasses.replace(cfg, learner=dataclasses.replace(
        cfg.learner, lr_schedule="cosine"))
    with pytest.raises(ValueError, match="lr_schedule"):
        pop.resolve_spec(cfg)


def test_member_seeds_spawn_discipline():
    """Member streams come from SeedSequence(seed, spawn_key=(k,)) — the
    PR 5 discipline — so they are solo-reproducible and distinct."""
    seeds = pop.member_seeds(123, 4)
    assert len(set(seeds)) == 4
    for k, s in enumerate(seeds):
        assert s == int(np.random.SeedSequence(
            123, spawn_key=(k,)).generate_state(1)[0])
    # Width-independence: member k's stream does not depend on M.
    assert pop.member_seeds(123, 2) == seeds[:2]


def test_member_config_static_overrides():
    cfg = _tiny_cfg(size=2, spec_json=SPEC2)
    spec = pop.resolve_spec(cfg)
    m1 = pop.member_config(cfg, spec, 1)
    assert m1.actor.epsilon_end == 0.2
    assert m1.learner.learning_rate == 5e-4
    assert m1.learner.gamma == 0.97
    assert m1.population.size == 1 and not m1.population.spec_json


def test_population_m1_bit_identical():
    """--population 1 + spec disengages to the plain program: identical
    params, bit for bit, to the statically-overridden solo run."""
    from dist_dqn_tpu.train import train

    spec1 = json.dumps({"lr": [7e-4], "epsilon": [0.07], "gamma": [0.98]})
    cfg_pop = _tiny_cfg(size=1, spec_json=spec1)
    cfg_solo = pop.member_config(cfg_pop, pop.resolve_spec(cfg_pop), 0)
    kw = dict(total_env_steps=1600, seed=11, chunk_iters=50,
              log_fn=lambda s: None)
    carry_a, _ = train(cfg_pop, **kw)
    carry_b, _ = train(cfg_solo, **kw)
    _assert_trees_equal(carry_a.learner.params, carry_b.learner.params)


def test_member_independence_bitwise():
    """No cross-member leakage, in the two forms a CPU run can hold.

    Bit for bit, within one M=2 program: changing member 1's spec entry
    and seed leaves member 0's parameters identical — nothing of a
    neighbour reaches a member through replay, RNG or the traced
    hyperparameter lanes. Across vmap widths (member k of M=2 against an
    M=1 stacked run of member k's spec slice + seed stream) the program is
    a different compilation, so — like the vmapped M=1 program against the
    solo one below — reductions may reorder: held at that tolerance."""
    seeds = pop.member_seeds(7, 2)
    c2, m2 = _run_stacked(_tiny_cfg(size=2, spec_json=SPEC2), seeds)
    assert float(np.sum(m2["grad_steps_in_chunk"])) > 0
    raw = json.loads(SPEC2)
    other = json.dumps({"epsilon": [raw["epsilon"][0], 0.4],
                        "lr": [raw["lr"][0], 2e-3],
                        "gamma": [raw["gamma"][0], 0.9]})
    c2b, _ = _run_stacked(_tiny_cfg(size=2, spec_json=other),
                          [seeds[0], seeds[1] + 1])
    _assert_trees_equal(pop.extract_member(c2.learner.params, 0),
                        pop.extract_member(c2b.learner.params, 0))
    assert any(np.any(np.asarray(a) != np.asarray(b)) for a, b in zip(
        jax.tree.leaves(pop.extract_member(c2.learner.params, 1)),
        jax.tree.leaves(pop.extract_member(c2b.learner.params, 1))))
    for k in range(2):
        spec_k = json.dumps({key: [raw[key][k]] for key in raw})
        c1, _ = _run_stacked(_tiny_cfg(size=1, spec_json=spec_k),
                             [seeds[k]])
        for a, b in zip(
                jax.tree.leaves(pop.extract_member(c2.learner.params, k)),
                jax.tree.leaves(pop.extract_member(c1.learner.params, 0))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-7)


def test_unbatched_member_body_matches_plain_bitwise():
    """The traced-hyperparameter member body without vmap IS the plain
    solo program (the lanes and the inject_hyperparams optimizer add
    zero numerics); the vmapped M=1 program tracks it to reduction-
    reorder tolerance."""
    spec1 = json.dumps({"lr": [6e-4], "epsilon": [0.03], "gamma": [0.98]})
    cfg = _tiny_cfg(size=1, spec_json=spec1)
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    seed = pop.member_seeds(3, 1)[0]

    # Plain solo program with the overrides applied statically.
    cfg_solo = pop.member_config(cfg, pop.resolve_spec(cfg), 0)
    init_s, run_s = make_fused_train(cfg_solo, env, net)
    run_solo = jax.jit(run_s, static_argnums=1, donate_argnums=0)
    carry_s = init_s(jax.random.PRNGKey(seed))
    for _ in range(2):
        carry_s, _ = run_solo(carry_s, 40)

    # The member body, unbatched (no vmap): hp rides as traced scalars.
    hp = pop.member_hp(cfg, pop.resolve_spec(cfg))
    hp0 = pop.extract_member(hp, 0)
    init_m, run_m = make_fused_train(cfg, env, net, member_hp=True,
                                     member_lr=True)
    run_member = jax.jit(run_m, static_argnums=2, donate_argnums=0)
    carry_m = init_m(jax.random.PRNGKey(seed), hp0)
    for _ in range(2):
        carry_m, _ = run_member(carry_m, hp0, 40)
    _assert_trees_equal(carry_m.learner.params, carry_s.learner.params)

    # Vmapped M=1: same program batched — reductions may reorder.
    c1, _ = _run_stacked(cfg, [seed])
    for a, b in zip(jax.tree.leaves(pop.extract_member(
                        c1.learner.params, 0)),
                    jax.tree.leaves(carry_s.learner.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7)


def test_stacked_checkpoint_roundtrip(tmp_path):
    """Save the [M]-stacked tree + POPULATION marker; extract any
    member; refuse every direction mismatch with the actual cause."""
    from dist_dqn_tpu import telemetry
    from dist_dqn_tpu.telemetry import collectors as tmc
    from dist_dqn_tpu.train import train
    from dist_dqn_tpu.utils.checkpoint import (TrainCheckpointer,
                                               read_population_size)

    d = str(tmp_path / "pop2")
    cfg = _tiny_cfg(size=2, spec_json=SPEC2)
    kw = dict(total_env_steps=1600, seed=5, chunk_iters=50)
    carry, history = train(cfg, **kw, log_fn=lambda s: None,
                           checkpoint_dir=d)
    assert read_population_size(d) == 2
    assert history and history[0]["population"] == 2
    assert len(history[0]["loss_members"]) == 2
    assert "eval_return_members" in history[0]

    mgr = TrainCheckpointer(d)
    example = pop.extract_member(jax.device_get(carry.learner.params), 0)
    for k in range(2):
        step, got = mgr.restore_params(example, member=k)
        _assert_trees_equal(got,
                            pop.extract_member(carry.learner.params, k))
    with pytest.raises(ValueError, match="population-2"):
        mgr.restore_params(example)           # member-less on stacked
    with pytest.raises(ValueError, match="out of range"):
        mgr.restore_params(example, member=5)
    mgr.close()

    # evaluate.py serves a single member of the stacked run.
    from dist_dqn_tpu.evaluate import evaluate_checkpoint
    out = evaluate_checkpoint(pop.member_config(cfg,
                                                pop.resolve_spec(cfg), 1),
                              d, episodes=2, member=1)
    assert out["member"] == 1 and np.isfinite(out["eval_return"])

    # Resume at the same M restores the stacked tree.
    logs = []
    train(cfg, **kw, log_fn=lambda s: logs.append(s), checkpoint_dir=d)
    assert any("resumed_at_frames" in s for s in logs)

    # Resume at a different M refuses with the cause and counts under
    # the sidecar-pin refusal family.
    reg = telemetry.get_registry()
    refused = reg.counter(tmc.CHECKPOINT_REFUSED,
                          "resume attempts refused at the sidecar pins",
                          {"loop": "fused", "reason": "population"})
    before = refused.value
    spec3 = json.dumps({"lr": [1e-3, 5e-4, 2e-4]})
    with pytest.raises(ValueError, match="population"):
        train(_tiny_cfg(size=3, spec_json=spec3), **kw,
              log_fn=lambda s: None, checkpoint_dir=d)
    assert refused.value == before + 1


def test_restore_member_on_solo_dir_refused(tmp_path):
    """A member selector against a plain (solo) checkpoint directory is
    a direction mismatch, not a silent slice of nothing."""
    from dist_dqn_tpu.train import train
    from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer

    d = str(tmp_path / "solo")
    carry, _ = train(_tiny_cfg(), total_env_steps=800, seed=0,
                     chunk_iters=50, log_fn=lambda s: None,
                     checkpoint_dir=d)
    mgr = TrainCheckpointer(d)
    example = jax.device_get(carry.learner.params)
    with pytest.raises(ValueError, match="not a population checkpoint"):
        mgr.restore_params(example, member=0)
    step, got = mgr.restore_params(example)   # member-less still works
    _assert_trees_equal(got, carry.learner.params)
    mgr.close()


def test_train_cli_population_flag_routing(monkeypatch, capsys):
    """ISSUE 20 satellite: --population applies on the fused runtime,
    warns-and-ignores where there is no member axis (apex, recurrent),
    and REFUSES the --mesh-devices cross and malformed specs at the
    parser boundary."""
    import sys

    import dist_dqn_tpu.actors.service as svc_mod
    from dist_dqn_tpu import train as train_mod

    seen = {}
    monkeypatch.setattr(svc_mod, "run_apex",
                        lambda cfg, rt, log_fn=print:
                        seen.__setitem__("apex", cfg) or {})
    monkeypatch.setattr(train_mod, "train",
                        lambda cfg, **kw: seen.__setitem__("fused", cfg)
                        or (None, []))

    monkeypatch.setattr(sys, "argv", [
        "train", "--config", "cartpole", "--population", "2",
        "--population-spec", SPEC2])
    train_mod.main()
    assert seen["fused"].population.size == 2
    assert seen["fused"].population.spec_json == SPEC2

    monkeypatch.setattr(sys, "argv", [
        "train", "--config", "cartpole", "--runtime", "apex",
        "--population", "4"])
    train_mod.main()
    out = capsys.readouterr().out
    assert "--population" in out and "ignored" in out
    assert seen["apex"].population.size == 1

    monkeypatch.setattr(sys, "argv", [
        "train", "--config", "r2d2", "--population", "2"])
    train_mod.main()
    out = capsys.readouterr().out
    assert "recurrent" in out and "ignored" in out
    assert seen["fused"].population.size == 1

    for argv, msg in (
            (["train", "--config", "cartpole", "--population", "2",
              "--mesh-devices", "2"], "mutually exclusive"),
            (["train", "--config", "cartpole", "--population", "0"],
             "must be >= 1"),
            (["train", "--config", "cartpole", "--population", "2",
              "--population-spec", '{"lr": [0.001]}'], "length M")):
        monkeypatch.setattr(sys, "argv", argv)
        with pytest.raises(SystemExit):
            train_mod.main()
        assert msg in capsys.readouterr().err


def test_population_sweep_smoke():
    """The population_bench harness cannot bit-rot: two tiny points,
    rows carry the acceptance fields, the stacked leg advances the same
    per-member grad count as solo in ONE dispatch per chunk."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "benchmarks"))
    from population_bench import population_sweep

    rows = []
    population_sweep(2, sizes=(1, 2), chunk_iters=30,
                     emit=lambda s: rows.append(json.loads(s)))
    assert [r["population"] for r in rows] == [1, 2]
    assert rows[0]["mode"] == "solo" and rows[1]["mode"] == "stacked"
    for r in rows:
        for key in ("grad_steps_per_sec", "grad_steps_per_sec_member",
                    "scaling_vs_m1", "aliased_pairs"):
            assert key in r
    assert rows[1]["grad_steps_per_chunk_member"] == \
        rows[0]["grad_steps_per_chunk_member"] > 0


def test_population_lint_drift_bite(tmp_path):
    """The donation TARGET vocabulary covers the population entry
    points: a jitted `*population*` program without donate_argnums
    bites."""
    from dist_dqn_tpu.analysis.plugins import donation

    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import jax\n"
        "run = jax.jit(run_population_chunk, static_argnums=2)\n")
    assert any(rel == "dist_dqn_tpu/rogue.py"
               for rel, _, _ in donation.scan(tmp_path))
    # Wired correctly, the lint goes quiet.
    (pkg / "rogue.py").write_text(
        "import jax\n"
        "run = jax.jit(run_population_chunk, static_argnums=2,\n"
        "              donate_argnums=0)\n")
    assert not donation.scan(tmp_path)


def test_sidecar_schema_population_pin():
    """The host-replay sidecar names its member-axis width: the field
    is in the schema, the digest matches the appended history entry,
    and the writer cannot omit it."""
    from dist_dqn_tpu.utils import ckpt_schema

    assert "population" in ckpt_schema.SIDECAR_SCALAR_FIELDS
    assert ckpt_schema.SIDECAR_HISTORY[ckpt_schema.SIDECAR_VERSION] == \
        ckpt_schema.sidecar_digest()
    with pytest.raises(ValueError, match="missing required fields"):
        ckpt_schema.validate_sidecar(
            [f for f in ckpt_schema.SIDECAR_SCALAR_FIELDS
             if f != "population"])


def test_host_replay_population_sidecar_refused(tmp_path):
    """A sidecar stamped population != 1 cannot resume into the host-
    replay loop's solo state shapes — refused with the cause (and the
    fused --population runtime named as the right home)."""
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = _tiny_cfg()
    cfg = dataclasses.replace(cfg, replay=dataclasses.replace(
        cfg.replay, capacity=4096))
    d = str(tmp_path / "hr")
    kw = dict(total_env_steps=1600, chunk_iters=50, checkpoint_dir=d,
              save_every_frames=400, log_fn=lambda s: None)
    run_host_replay(cfg, **kw)
    path = max(glob.glob(f"{d}/host_loop_*.npz"),
               key=lambda p: int(p.rsplit("_", 1)[1].split(".")[0]))
    with np.load(path) as f:
        data = {k: f[k] for k in f.files}
    assert int(data["population"]) == 1   # the writer stamps the pin
    data["population"] = np.int64(2)
    np.savez(path, **data)
    with pytest.raises(ValueError, match="population"):
        run_host_replay(cfg, **kw)
