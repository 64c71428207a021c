"""The hybrid sequence core (``dist_dqn_tpu/models/sequence_core.py``)
against its plain reference (``perf/reference/twotower_float32.py``) at toy
widths on the CPU: the learner step through the harness's own comparison,
the expert layer's shares against the uncut layer, the chunked scan against
the per-step recurrence, acting step by step against the unroll, the
float8 control, and one wrong formula a part."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.harness import reference_check
from perf.reference import r2d2_float32, twotower_float32

SEQS = 4
HIDDEN = 32
# 16 shares of 2 experts each; top 3 of 32 routed
TOY_CORE = dict(kind="hybrid", pattern="ME*M", mamba_num_heads=4,
                mamba_head_dim=8, ssm_state_size=8, n_groups=2, chunk_size=4,
                n_routed_experts=32, experts_held=(0, 1),
                num_experts_per_tok=3, moe_intermediate_size=16,
                moe_shared_expert_intermediate_size=24,
                num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                attention_window=16)


def _setup(compute_dtype="float32", pixels=False, **core):
    """The ``twotower_q`` preset at toy widths: on frames through the small
    convolutions (a dedup ring), or — quick to compile — on cartpole's four
    numbers through one dense layer."""
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["twotower_q"]
    cfg = dataclasses.replace(
        cfg, env_name="pixel_pong" if pixels else "cartpole",
        network=dataclasses.replace(
            cfg.network, torso="small" if pixels else "mlp",
            mlp_features=(16,), hidden=HIDDEN,
            compute_dtype=compute_dtype, remat_torso=False,
            core=dataclasses.replace(cfg.network.core,
                                     **dict(TOY_CORE, **core))),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        # burn-in of one whole chunk, 11 more steps: the scan pads one
        replay=dataclasses.replace(cfg.replay, burn_in=4, unroll_length=8,
                                   sequence_stride=4, capacity=256,
                                   frame_dedup=pixels),
        learner=dataclasses.replace(cfg.learner, n_step=3, batch_size=SEQS))
    env = make_jax_env(cfg.env_name)
    return cfg, env, build_network(cfg.network, env.num_actions)


def _check(setup, seed=8, net=None):
    cfg, env, built = setup
    return reference_check.make_check(twotower_float32, cfg, env,
                                      net or built, SEQS)(seed)


@pytest.mark.parametrize("compute_dtype,pixels", [("float32", True),
                                                  ("bfloat16", False)])
def test_hybrid_reference_agrees_with_the_programs_learner(compute_dtype,
                                                           pixels):
    """Loss, window priorities, Q-values, the gradient read back from Adam's
    moments and the optimizer's step of ``make_r2d2_learner`` over the hybrid
    core against ``twotower_float32``: windows from the empty state with
    episode ends in the burn-in and among the loss positions; the ring's
    five numbers beside them."""
    result = _check(_setup(compute_dtype, pixels))
    assert result["tolerances"] == dict(
        twotower_float32.TOLERANCES[compute_dtype],
        **r2d2_float32.RING_LIMITS)
    if compute_dtype == "float32":
        assert result["ok"], result
        return
    # The bfloat16 bounds are the cell's (8 windows x 512 steps at the
    # published widths, where the gradient reads 0.65-0.87%); a toy batch
    # of 4 x 15 steps sums little and reads 2-12% by seed. Every other
    # number is inside its bound, the gradient inside half of what the
    # float8 control reads here (31%).
    errors, limits = result["errors"], result["tolerances"]
    assert all(errors[k] <= limits[k] for k in limits if k != "grad"), result
    assert errors["grad"] < 0.15, result


def test_the_float8_control_fails_the_hybrid_comparison():
    setup = _setup("bfloat16")
    result = _check(setup, net=reference_check.CoarseNet(setup[2]))
    assert not result["ok"] and result["errors"]["grad"] > 0.2, result


@pytest.mark.parametrize("wrong", ["gate_normalisation", "relu2",
                                   "grouped_norm", "segment_mask",
                                   "burn_in_gradient"])
def test_a_wrong_hybrid_formula_fails_the_comparison(wrong, monkeypatch):
    """Each part of the published mathematics is held: gates that are not
    normalised over the chosen experts, a plain relu in the experts, a
    gated norm over all channels at once, attention that sees across an
    episode's end, a gradient through the burn-in. The program agrees with
    the reference inside the float32 tolerances (the test above), so a
    reference with one formula wrong that reads three tolerances away from
    the true one — Q-values, or the gradient for the burn-in's — would come
    out NOT ok against the program."""
    ref = twotower_float32
    cfg, env, net = _setup()
    hp = ref.hyper_from_config(cfg)
    # a window that holds an episode's end among its loss positions
    batch, lane = next(
        (b, int(np.flatnonzero(b["reset"][5:].any(axis=0))[0]))
        for b in (ref.seeded_batch(7, i, SEQS, cfg, env) for i in range(8))
        if b["reset"][5:].any())
    params = jax.jit(lambda key: net.init(
        key, net.initial_state(1), jnp.asarray(batch["obs"][:1, :1]),
        method=net.unroll))(jax.random.PRNGKey(7))
    window = {k: jnp.asarray(batch[k])[:, lane] for k in (
        "obs", "action", "reward", "done", "reset")}
    window["weights"] = jnp.float32(1.0)

    def q_and_grad():
        with jax.default_matmul_precision("highest"):
            if wrong != "burn_in_gradient":
                return ref.q_window(params, window["obs"], window["reset"],
                                    hp)
            return jax.grad(lambda p: ref._loss(p, params, window, hp)[0])(
                params)

    true = q_and_grad()
    real_norm = ref.rms_norm
    monkeypatch.setattr(ref, *{
        "gate_normalisation": ("gates", lambda picked, core:
                               picked * core.scale),
        "relu2": ("relu2_mlp", lambda u, up, down:
                  jnp.maximum(u @ up, 0.0) @ down),
        "grouped_norm": ("rms_norm", lambda x, w, eps, groups=1:
                         real_norm(x, w, eps)),
        "segment_mask": ("visible", lambda kp, ke, p, e:
                         kp[None, :] <= p[:, None]),
        "burn_in_gradient": ("leave_burn_in", lambda memory: memory),
    }[wrong])
    wrong_one = q_and_grad()
    limits = ref.TOLERANCES["float32"]
    if wrong == "burn_in_gradient":
        assert reference_check._rel_l2(wrong_one, true) > 3 * limits["grad"]
    else:
        assert reference_check._rel_max(wrong_one, true) > 3 * limits["q"]


def _layer(kind, **core):
    """One mixer of the program at toy widths, its parameters, and the
    reference's ``Core`` for it."""
    from dist_dqn_tpu.models import sequence_core

    cfg, _, _ = _setup(**core)
    core_cfg = cfg.network.core
    module = sequence_core._MIXERS[kind](core_cfg, jnp.float32)
    return module, twotower_float32.hyper_from_config(cfg).core


def test_the_expert_layers_shares_add_up_to_the_uncut_layer():
    """16 chips each hold 2 of the 32 experts: what the 16 shares of the
    program's layer give for their own experts, with the shared expert —
    which every chip computes alike — counted once, is what the uncut
    reference gives for the whole layer."""
    from dist_dqn_tpu.models import sequence_core

    cfg, _, _ = _setup(experts_held=tuple(range(32)))
    core = twotower_float32.hyper_from_config(cfg).core
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 5, HIDDEN))
    whole = sequence_core._Experts(cfg.network.core, jnp.float32)
    params = whole.init(jax.random.PRNGKey(1), u, None, ())["params"]
    params["e_score_correction_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (32,))
    uncut = jax.vmap(lambda x: twotower_float32.experts(
        params, x, None, (), core)[0])(u)
    shared = twotower_float32.relu2_mlp(u, params["shared_up"],
                                        params["shared_down"])
    total = shared
    for rank in range(16):
        held = (2 * rank, 2 * rank + 1)
        share = sequence_core._Experts(
            dataclasses.replace(cfg.network.core, experts_held=held),
            jnp.float32)
        mine = dict(params,
                    experts_up=params["experts_up"][:, 2 * rank:2 * rank + 2],
                    experts_down=params["experts_down"][2 * rank:2 * rank + 2])
        total = total + share.apply({"params": mine}, u, None, ())[0] - shared
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)
    # and no share is idle: the routed part is not nothing
    assert float(jnp.max(jnp.abs(uncut - shared))) > 1e-2


def test_the_chunked_scan_is_the_per_step_recurrence():
    """``_Mamba2`` (chunks of 4, a window of 11 steps: padded) against the
    reference's step-by-step ``mamba2`` from a non-empty state, with resets
    inside a chunk, at a chunk's first step and at step 0: outputs, the
    state and look-back handed on, and the gradient to the input and every
    parameter."""
    from dist_dqn_tpu.models import sequence_core

    module, core = _layer("M")
    B, T = 3, 11
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    u = jax.random.normal(keys[0], (B, T, HIDDEN))
    reset = np.zeros((B, T), bool)
    reset[0, [2, 3, 9]] = True      # twice inside one chunk
    reset[1, [0, 4]] = True         # the window's and a chunk's first step
    carry = (jax.random.normal(keys[1], (B, 3, 32 + 2 * 2 * 8)),
             jax.random.normal(keys[2], (B, 4, 8, 8)))
    params = module.init(keys[3], u, sequence_core.segments(reset), carry)

    @jax.jit
    def program(params, u):
        return module.apply(params, u, sequence_core.segments(
            jnp.asarray(reset)), carry)

    @jax.jit
    def plain(params, u):
        return jax.vmap(lambda x, r, m: twotower_float32.mamba2(
            params["params"], x, r, m, core))(u, jnp.asarray(reset), carry)

    for got, want in zip(jax.tree.leaves(program(params, u)),
                         jax.tree.leaves(plain(params, u))):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    pull = jax.random.normal(jax.random.PRNGKey(4), (B, T, HIDDEN))

    def loss(f):
        def of(params, u):
            out, (tail, state) = f(params, u)
            return jnp.sum(out * pull) + jnp.sum(state) + jnp.sum(tail)
        return jax.jit(jax.grad(of, argnums=(0, 1)))(params, u)

    for got, want in zip(jax.tree.leaves(loss(program)),
                         jax.tree.leaves(loss(plain))):
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_acting_step_by_step_is_the_unroll():
    """One episode boundary a lane: the network stepped through its carry
    (SSM state, convolution look-back, cached keys and values, emptied by
    ``Agent.reset_state`` after a done) gives the Q-values and the final
    carry of ``unroll`` over the same steps with the reset flags; so does an
    unroll split in two, the way the learner splits burn-in from loss."""
    from dist_dqn_tpu.agents import make_agent

    cfg, env, net = _setup()
    T, B = 13, 2
    obs = jax.random.normal(jax.random.PRNGKey(0),
                            (T, B) + tuple(env.observation_shape))
    done = np.zeros((T, B), bool)
    done[4, 0] = done[7, 1] = done[8, 1] = True
    reset = np.concatenate([np.zeros((1, B), bool), done[:-1]])
    carry = net.initial_state(B)
    params = net.init(jax.random.PRNGKey(1), carry, obs[:1],
                      method=net.unroll)
    agent = make_agent(net, cfg)
    want_carry, want = jax.jit(
        lambda *a: net.apply(*a, method=net.unroll))(params, carry, obs, reset)
    stepped, got = carry, []
    step = jax.jit(net.apply)
    for t in range(T):
        stepped, q = step(params, stepped, obs[t])
        got.append(q)
        if t + 1 < T:
            stepped = jax.jit(agent.reset_state)(stepped,
                                                 jnp.asarray(done[t]))
    np.testing.assert_allclose(jnp.stack(got), want, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(stepped), jax.tree.leaves(want_carry)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert agent.stored_state(carry) == ()


def test_required_flops_count_the_routed_rows_not_the_dense_product():
    """``grad_step_flops`` at the published widths: the routed experts at
    the rows the routing sends here (6 x 8 / 128 expert evaluations a
    token), a sixteenth and a third of the dense product over the 8 held
    ones."""
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env

    cfg = CONFIGS["twotower_q"]
    env = make_jax_env(cfg.env_name)
    parts = twotower_float32.forward_flops_per_step(cfg, env)
    expert = 2 * 2 * 2688 * 1856
    assert parts["moe_routed"] == pytest.approx(4 * 0.375 * expert)
    assert parts["moe_shared"] == pytest.approx(4 * 2 * expert)
    total = twotower_float32.grad_step_flops(cfg, env)
    body = sum(v for k, v in parts.items() if k != "heads")
    assert total == pytest.approx(
        8 * (2 * 512 * body + 2 * 384 * body), rel=1e-3)
