"""The ``laguna_q`` sequence core (``models/sequence_core.py``: rotary gated
attention over the whole episode ``F`` and over a sliding window ``W``, a
dense gated MLP ``D``, gated ``silu`` experts ``E``) against its plain
reference (``perf/reference/laguna_float32.py``) at toy widths on the CPU: the
learner step through the harness's own comparison, the expert sublayer's 32
shares against the uncut layer, blockwise against masked attention, acting
step by step through both rings against the unroll, YaRN's frequencies
against hand-worked values, and one wrong formula a part."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.models import sequence_core
from perf.harness import reference_check
from perf.reference import laguna_float32, r2d2_float32

SEQS = 4
HIDDEN = 32
WINDOW = 4          # the toy ``sliding_window``: learner windows are 4 long
# one published layer of each kind (full attention + dense MLP, window
# attention + experts): 16 shares of 2 experts each, top 3 of 32 routed
TOY_CORE = dict(pattern="FDWE", n_routed_experts=32, experts_held=(0, 1),
                num_experts_per_tok=3, moe_intermediate_size=16,
                moe_shared_expert_intermediate_size=24, intermediate_size=48,
                num_key_value_heads=2, head_dim=8,
                attention_heads_per_layer=(4, 6),
                sliding_window=WINDOW, attention_window=32)


def _setup(compute_dtype="float32", **core):
    """The ``laguna_q`` preset at toy widths on cartpole's four numbers
    through one dense layer: windows of 4 burn-in + 9 + 3 = 16 steps, four
    sliding windows long."""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["laguna_q"]
    cfg = dataclasses.replace(
        cfg, env_name="cartpole",
        network=dataclasses.replace(
            cfg.network, torso="mlp", mlp_features=(16,), hidden=HIDDEN,
            compute_dtype=compute_dtype, remat_torso=False,
            core=dataclasses.replace(cfg.network.core,
                                     **dict(TOY_CORE, **core))),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        replay=dataclasses.replace(cfg.replay, burn_in=4, unroll_length=9,
                                   sequence_stride=4, capacity=256,
                                   frame_dedup=False),
        learner=dataclasses.replace(cfg.learner, n_step=3, batch_size=SEQS))
    env = make_jax_env(cfg.env_name)
    return cfg, env, build_network(cfg.network, env.num_actions)


def _check(setup, seed=8, net=None):
    cfg, env, built = setup
    return reference_check.make_check(laguna_float32, cfg, env,
                                      net or built, SEQS)(seed)


def test_the_preset_is_the_published_layers():
    """``laguna_q`` at its published widths, shapes only (no memory): the
    ten sublayers by name and shape, 344.77 M parameters, and the acting
    state a lane by kind of cache."""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["laguna_q"]
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    obs = jax.ShapeDtypeStruct((1, 1) + tuple(env.observation_shape),
                               env.observation_dtype)
    state = jax.eval_shape(lambda: net.initial_state(1))
    tree = jax.eval_shape(
        lambda key, carry, obs: net.init(key, carry, obs, method=net.unroll),
        jax.random.PRNGKey(0), state, obs)["params"]
    core = jax.tree.map(lambda leaf: leaf.shape, tree["core"])

    def attention(heads):
        return {"q_proj": (2048, heads * 128), "k_proj": (2048, 1024),
                "v_proj": (2048, 1024), "g_proj": (2048, heads),
                "o_proj": (heads * 128, 2048)}

    experts = {"router": (2048, 256), "experts_gate": (2048, 8, 512),
               "experts_up": (2048, 8, 512), "experts_down": (8, 512, 2048),
               "shared_gate": (2048, 512), "shared_up": (2048, 512),
               "shared_down": (512, 2048)}
    dense = {"gate_proj": (2048, 8192), "up_proj": (2048, 8192),
             "down_proj": (8192, 2048)}
    mixers = [attention(48), dense, attention(64), experts, attention(64),
              experts, attention(64), experts, attention(48), experts]
    assert cfg.network.core.pattern == "FDWEWEWEFE"
    assert core == dict({f"layer_{i}": {"norm": (2048,), "mixer": mixer}
                         for i, mixer in enumerate(mixers)},
                        norm_f=(2048,))
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(tree))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(tree)) == 344_770_727
    assert net.state_bytes_a_lane() == {
        "attention_full": 2 * (2 * 2048 * 8 * 128 + 1) * 4,
        "attention_window": 3 * (2 * 512 * 8 * 128 + 1) * 4}


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_laguna_reference_agrees_with_the_programs_learner(compute_dtype):
    """Loss, window priorities, Q-values, the gradient read back from Adam's
    moments and the optimizer's step of ``make_r2d2_learner`` over the core
    against ``laguna_float32``: windows four sliding windows long from the
    empty state, episode ends in the burn-in and among the loss positions;
    the ring's five numbers beside them."""
    result = _check(_setup(compute_dtype))
    assert result["tolerances"] == dict(
        laguna_float32.TOLERANCES[compute_dtype],
        **r2d2_float32.RING_LIMITS)
    if compute_dtype == "float32":
        assert result["ok"], result
        return
    # The bfloat16 bounds are the cell's (4 windows x 2,048 steps at the
    # published widths); a toy batch of 4 x 16 steps sums little and its
    # gradient reads 1-3% by seed (3.2% on this one). Every other number is
    # inside its bound, the gradient a quarter of what the float8 control
    # reads on the same seed (12.8%, below).
    errors, limits = result["errors"], result["tolerances"]
    assert all(errors[k] <= limits[k] for k in limits if k != "grad"), result
    assert errors["grad"] < 0.05, result


def test_the_float8_control_fails_the_laguna_comparison():
    setup = _setup("bfloat16")
    result = _check(setup, net=reference_check.CoarseNet(setup[2]))
    assert not result["ok"] and result["errors"]["grad"] > 0.1, result


def _swap_theta(real):
    def attention(p, u, reset, memory, core, heads, windowed):
        return real(p, u, reset, memory, core._replace(
            rope_full=core.rope_window, rope_window=core.rope_full),
            heads, windowed)
    return attention


@pytest.mark.parametrize("wrong", ["window_off_by_one", "gate_left_out",
                                   "theta_swapped", "gate_normalisation",
                                   "silu_to_relu2", "no_rotation",
                                   "burn_in_gradient"])
def test_a_wrong_laguna_formula_fails_the_comparison(wrong, monkeypatch):
    """Each part of the published mathematics is held: a window one step too
    long, attention without its head gate, the two kinds' rotary embeddings
    swapped, none at all, router gates that are not normalised, ``relu^2``
    in the MLPs, a gradient through the burn-in. The program agrees with
    the reference inside the float32 tolerances (the test above), so a
    reference with one formula wrong that reads three tolerances away from
    the true one would come out NOT ok against the program."""
    ref = laguna_float32
    cfg, env, net = _setup()
    hp = ref.hyper_from_config(cfg)
    # a window without an episode's end: every query past step 4 has keys
    # beyond its sliding window
    batch, lane = next(
        (b, int(np.flatnonzero(~b["reset"].any(axis=0))[0]))
        for b in (ref.seeded_batch(7, i, SEQS, cfg, env) for i in range(8))
        if not b["reset"].all(axis=0).any() and (~b["reset"].any(axis=0)).any())
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
    monkeypatch.setattr(ref, *{
        "window_off_by_one": ("visible", lambda kp, ke, p, e, window:
                              (kp[None] <= p[:, None])
                              & (ke[None] == e[:, None])
                              & (True if window is None else
                                 p[:, None] - kp[None] <= window)),
        "gate_left_out": ("head_gate", lambda u, w: jnp.ones(
            (u.shape[0], w.shape[1]))),
        "theta_swapped": ("attention", _swap_theta(ref.attention)),
        "no_rotation": ("rotary", lambda x, position, rope: x),
        "gate_normalisation": ("gates", lambda picked, core:
                               picked * core.scale),
        "silu_to_relu2": ("gated_mlp", lambda u, gate, up, down:
                          jnp.maximum(u @ up, 0.0) ** 2 @ down),
        "burn_in_gradient": ("leave_burn_in", lambda memory: memory),
    }[wrong])
    wrong_one = q_and_grad()
    limits = ref.TOLERANCES["float32"]
    if wrong == "burn_in_gradient":
        assert reference_check._rel_l2(wrong_one, true) > 3 * limits["grad"]
    else:
        assert reference_check._rel_max(wrong_one, true) > 3 * limits["q"]


def test_the_32_shares_of_an_expert_sublayer_add_up_to_the_uncut_layer():
    """32 chips each hold 8 of 256 experts at the published router width
    and top-8 (toy expert widths): what the 32 shares of the program's
    sublayer give for their own experts, with the shared expert — which
    every chip computes alike — counted once, is what the uncut reference
    gives for the whole layer."""
    cfg, _, _ = _setup(n_routed_experts=256, num_experts_per_tok=8,
                       experts_held=tuple(range(256)))
    core = laguna_float32.hyper_from_config(cfg).core
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 5, HIDDEN))
    whole = sequence_core._Experts(cfg.network.core, jnp.float32)
    params = whole.init(jax.random.PRNGKey(1), u, None, ())["params"]
    assert "e_score_correction_bias" not in params
    uncut = jax.vmap(lambda x: laguna_float32.experts(
        params, x, None, (), core)[0])(u)
    shared = laguna_float32.gated_mlp(
        u, params["shared_gate"], params["shared_up"], params["shared_down"])
    total = shared
    for rank in range(32):
        held = tuple(range(8 * rank, 8 * rank + 8))
        share = sequence_core._Experts(
            dataclasses.replace(cfg.network.core, experts_held=held),
            jnp.float32)
        mine = dict(params, **{
            name: params[name][:, held[0]:held[-1] + 1]
            for name in ("experts_gate", "experts_up")},
            experts_down=params["experts_down"][held[0]:held[-1] + 1])
        total = total + share.apply({"params": mine}, u, None, ())[0] - shared
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)
    # and the routed part is not nothing
    assert float(jnp.max(jnp.abs(uncut - shared))) > 1e-2


@pytest.mark.parametrize("kind,heads,before", [("W", 6, 3), ("F", 4, 3),
                                               ("W", 6, 11)])
def test_blockwise_attention_is_masked_attention(kind, heads, before):
    """``_RotaryAttention`` by blocks of 4 queries over a window of 19 steps
    (padded; a band of two blocks in ``W``, the causal triangle by blocks in
    ``F``) against the reference's one masked ``[T, S]`` softmax: from a ring
    that ``before`` earlier steps have filled — 3: a valid prefix, then
    empty slots in front of the new keys; 11: the ``W`` ring (4 slots) has
    wrapped, its slots out of the order of their positions (an ``F`` ring
    that wraps has forgotten steps the reference still sees) — with
    resets inside a block, at a block's first step and at step 0, a window
    far longer than the sliding window — outputs, and the gradient to the
    input and every parameter. ``blockwise`` is in turn what the TPU's
    kernels are held to (``tests/test_pallas_attention.py``)."""
    cfg, _, _ = _setup()
    core = laguna_float32.hyper_from_config(cfg).core
    module = sequence_core._MIXERS[kind](cfg.network.core, jnp.float32,
                                         heads=heads)
    B, T = 3, 19
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    u = jax.random.normal(keys[0], (B, T, HIDDEN))
    earlier = jax.random.normal(keys[1], (B, before, HIDDEN))
    reset = np.zeros((B, T), bool)
    reset[0, [9, 10, 14]] = True    # twice inside one block
    reset[1, [0, 4]] = True         # the call's and a block's first step
    seg = sequence_core.segments(jnp.asarray(reset))
    no_seg = jnp.zeros((B, before), jnp.int32)
    history = WINDOW if kind == "W" else 8
    empty = (jnp.zeros((B, history, 2, 8)), jnp.zeros((B, history, 2, 8)),
             jnp.zeros((B,)))
    params = module.init(keys[2], u, seg, empty)
    pull = jax.random.normal(keys[3], (B, T, HIDDEN))

    def program(params, u):
        _, carry = module.apply(params, earlier, no_seg, empty)
        return module.apply(params, u, seg, carry)[0]

    def plain(params, u):
        def lane(x, x_before, r):
            _, memory = laguna_float32.attention(
                params["params"], x_before, jnp.zeros((before,), bool),
                laguna_float32.empty_memory(core)[0], core, heads,
                kind == "W")
            return laguna_float32.attention(
                params["params"], x, r, memory, core, heads, kind == "W")[0]
        return jax.vmap(lane)(u, earlier, jnp.asarray(reset))

    np.testing.assert_allclose(jax.jit(program)(params, u),
                               jax.jit(plain)(params, u),
                               rtol=2e-4, atol=2e-5)

    def grads(f):
        return jax.jit(jax.grad(
            lambda params, u: jnp.sum(f(params, u) * pull),
            argnums=(0, 1)))(params, u)

    for got, want in zip(jax.tree.leaves(grads(program)),
                         jax.tree.leaves(grads(plain))):
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("route", ["plain", "kernels"])
def test_acting_step_by_step_through_both_rings_is_the_unroll(
        monkeypatch, route):
    """29 steps — seven sliding windows, so every ``W`` ring wraps many
    times; the ``F`` rings (32 slots) do not — with an episode boundary a
    lane: the network stepped through its carry (one slot of each ring
    written a step, a lane emptied by ``Agent.reset_state`` through its
    counter alone) gives the Q-values of ``unroll`` over the same steps with
    the reset flags; so does an unroll split in two, the way the learner
    splits burn-in from loss; and the three carries go on alike."""
    from dist_dqn_tpu.agents import make_agent
    from dist_dqn_tpu.ops import pallas_attention as kernels

    # "kernels": the route a TPU takes (``loop_common.pallas_routing``),
    # interpreted — acting's one query a head through ``decode`` over the
    # float32 rings, through a reset and past a wrap, the unroll through the
    # learner's kernels
    decoded, decode = [], kernels.decode
    monkeypatch.setattr(kernels, "decode", lambda *a, **k: (
        decoded.append(a[1].shape), decode(*a, **k))[1])
    if route == "kernels":
        monkeypatch.setenv("DIST_DQN_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("DIST_DQN_PALLAS_INTERPRET", raising=False)
    cfg, env, net = _setup()
    T, B = 29, 2
    obs = jax.random.normal(jax.random.PRNGKey(0),
                            (T, B) + tuple(env.observation_shape))
    done = np.zeros((T, B), bool)
    done[4, 0] = done[17, 1] = done[18, 1] = True
    reset = np.concatenate([np.zeros((1, B), bool), done[:-1]])
    carry = net.initial_state(B)
    params = net.init(jax.random.PRNGKey(1), carry, obs[:1],
                      method=net.unroll)
    agent = make_agent(net, cfg)
    unroll = jax.jit(lambda *a: net.apply(*a, method=net.unroll))
    want_carry, want = unroll(params, carry, obs, reset)
    stepped, got = carry, []
    step = jax.jit(net.apply)
    for t in range(T):
        stepped, q = step(params, stepped, obs[t])
        got.append(q)
        if t + 1 < T:
            stepped = jax.jit(agent.reset_state)(stepped,
                                                 jnp.asarray(done[t]))
    np.testing.assert_allclose(jnp.stack(got), want, rtol=1e-4, atol=1e-5)
    half, first = unroll(params, carry, obs[:11], reset[:11])
    split_carry, second = unroll(params, half, obs[11:], reset[11:])
    np.testing.assert_allclose(jnp.concatenate([first, second]), want,
                               rtol=1e-4, atol=1e-5)
    # the rings agree where the counter says they hold something (a lane
    # emptied through its counter keeps what lay in its ring), so the next
    # steps read the same from all three
    for t in range(3):
        nexts = [step(params, c, obs[t]) for c in (stepped, want_carry,
                                                   split_carry)]
        for other in nexts[1:]:
            np.testing.assert_allclose(other[1], nexts[0][1], rtol=1e-4,
                                       atol=1e-5)
        stepped, want_carry, split_carry = (n[0] for n in nexts)
    for layer in stepped:
        if layer:
            np.testing.assert_array_equal(layer[2], [27.0, 13.0])
    rings = {layer[0].shape for layer in carry if layer}
    assert set(decoded) == (rings if route == "kernels" else set())
    assert agent.stored_state(carry) == ()
    # emptying a lane leaves its rings where they lie
    emptied = agent.reset_state(stepped, jnp.asarray([True, False]))
    for kind, was, now in zip(cfg.network.core.pattern, stepped, emptied):
        if kind in sequence_core.ROTARY:
            np.testing.assert_array_equal(now[2], [0.0, 13.0])
            assert now[0] is was[0] and now[1] is was[1]


def test_yarn_frequencies_against_hand_worked_values():
    """``rope_parameters.full_attention`` over its 64 rotary dims: ``low,
    high`` = floor, ceil of 64 ln(4096 / (r 2 pi)) / (2 ln 500000) at r = 64
    and r = 1 = 5.66 -> 5 and 15.80 -> 16; dims 0..5 turn as theta says,
    dims 16..31 64 times slower, a ramp of elevenths between — worked by
    hand for dims 0, 5, 6, 10, 16, 31 — and the plain embedding of the
    window layers beside it. Program and reference agree to the last bit of
    float64."""
    core = CONFIGS["laguna_q"].network.core
    full = sequence_core.rotary_frequencies(core.rope_full, core.head_dim)
    window = sequence_core.rotary_frequencies(core.rope_window,
                                              core.head_dim)
    assert full.shape == (32,) and window.shape == (64,)
    ln = np.log(500000.0)
    by_hand = {
        0: 1.0,
        5: np.exp(-ln * 10 / 64),
        6: np.exp(-ln * 12 / 64) * (10 / 11 + 1 / 11 / 64),
        10: np.exp(-ln * 20 / 64) * (6 / 11 + 5 / 11 / 64),
        16: np.exp(-ln * 32 / 64) / 64,
        31: np.exp(-ln * 62 / 64) / 64,
    }
    for dim, value in by_hand.items():
        assert full[dim] == pytest.approx(value, rel=1e-12), dim
    assert full[6] == pytest.approx(0.0777550, rel=1e-5)
    assert full[16] == pytest.approx(2.2097e-5, rel=1e-4)
    np.testing.assert_allclose(window, 10000.0 ** (-np.arange(64) / 64),
                               rtol=1e-12)
    hp = laguna_float32.hyper_from_config(_setup()[0]).core
    np.testing.assert_allclose(
        laguna_float32.inv_freq(hp.rope_full, 128), full, rtol=1e-14)
    np.testing.assert_allclose(
        laguna_float32.inv_freq(hp.rope_window, 128), window, rtol=1e-14)
    assert core.rope_full.attention_factor == 1.4158883083359672


def test_required_flops_count_the_band_the_triangle_and_the_routed_rows():
    """``grad_step_flops`` at the published widths: a window layer's scores
    over the 448 keys a query of a 2,048-step window sees on average (the
    band), a full layer's over 1,024.5 (the triangle), the routed experts at
    the rows the routing sends here (8 x 8 / 256 expert evaluations a token);
    attention is 76% of what a token's forward through the core requires."""
    from dist_dqn_tpu.envs import make_jax_env

    cfg = CONFIGS["laguna_q"]
    env = make_jax_env(cfg.env_name)
    parts = laguna_float32.forward_flops_per_step(cfg, env)
    assert laguna_float32.mean_keys_seen(2048, 512) == pytest.approx(448.125)
    assert laguna_float32.mean_keys_seen(2048, None) == pytest.approx(1024.5)
    assert parts["attention_window"] == pytest.approx(3 * 2 * (
        2048 * (80 * 128 + 64) + 64 * 128 * 2048 + 2 * 64 * 128 * 448.125))
    assert parts["attention_full"] == pytest.approx(2 * 2 * (
        2048 * (64 * 128 + 48) + 48 * 128 * 2048 + 2 * 48 * 128 * 1024.5))
    expert = 2 * 3 * 2048 * 512
    assert parts["moe_routed"] == pytest.approx(4 * 0.25 * expert)
    assert parts["moe_shared"] == pytest.approx(4 * expert)
    assert parts["mlp_dense"] == pytest.approx(2 * 3 * 2048 * 8192)
    body = sum(v for k, v in parts.items() if k not in ("heads", "torso"))
    attention = parts["attention_window"] + parts["attention_full"]
    assert attention / body == pytest.approx(0.763, abs=2e-3)
    # and of what the program COMPUTES — the dense product over the 8 held
    # experts, 32 times the required rows — attention is 57%
    computed = body + (32 - 1) * parts["moe_routed"]
    assert attention / computed == pytest.approx(0.57, abs=5e-3)
    total = laguna_float32.grad_step_flops(cfg, env)
    whole = sum(v for k, v in parts.items() if k != "heads")
    assert total == pytest.approx(
        4 * (2 * 2048 * whole + 2 * 1536 * whole), rel=1e-3)


def test_the_ring_check_holds_a_whole_window_of_the_preset():
    """The sequence ring's own check at the preset's geometry — windows of
    2,048 steps every 512, 16 lanes, 8,192 rebuilt frames a draw — needs more
    time slices than ``r2d2_float32``'s 2,048 (refused on the chip: a window
    and a stride do not fit): ``laguna_float32`` builds it over 4,096, leaves
    that module's count as it was, and the five numbers come out inside
    their limits."""
    from dist_dqn_tpu.envs import make_jax_env

    cfg = CONFIGS["laguna_q"]
    env = make_jax_env(cfg.env_name)
    with pytest.raises(NotImplementedError, match="2048 slots"):
        r2d2_float32.make_further_check(cfg, env)
    further = laguna_float32.make_further_check(cfg, env)
    assert r2d2_float32.RING_SLOTS == 2048 < laguna_float32.RING_SLOTS
    numbers = further(2 ** 31 + 11)
    assert set(numbers) == set(r2d2_float32.RING_LIMITS)
    assert all(value <= limit for value, limit in numbers.values()), numbers
