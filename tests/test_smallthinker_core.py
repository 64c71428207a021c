"""The ``smallthinker_q`` sequence core (``models/sequence_core.py``: full
attention without positions ``F`` and window rotary attention ``W``, neither
gated, each followed by ReGLU experts ``E`` whose router read the ATTENTION
sublayer's normed input) against its plain reference
(``perf/reference/smallthinker_float32.py``) at toy widths on the CPU: the
learner step through the harness's own comparison, one wrong formula a part,
the eight shares of a layer against the uncut layer, blockwise against
masked attention and the interpreted kernels at a window that is not the
block, acting step by step through both rings against the unroll, the
required operations by hand, and the preset's tree."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS
from dist_dqn_tpu.models import sequence_core
from dist_dqn_tpu.ops import pallas_attention
from perf.harness import reference_check
from perf.reference import r2d2_float32, smallthinker_float32

SEQS = 3
HIDDEN = 32
WINDOW = 6          # the toy ``sliding_window``
BLOCK = 4           # the toy block of queries: not the window
# one published layer of each kind: 8 shares of 2 experts each, top 3 of 16
# routed, 7 query heads a KV head as published
TOY_CORE = dict(pattern="FEWE", n_routed_experts=16, experts_held=(0, 1),
                num_experts_per_tok=3, moe_intermediate_size=16,
                num_key_value_heads=2, head_dim=8,
                attention_heads_per_layer=(14, 14),
                sliding_window=WINDOW, attention_window=32)


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    """Blocks of 4 queries against a window of 6: the band is not the
    block, in the program and in the reference alike."""
    monkeypatch.setattr(sequence_core, "QUERY_BLOCK", BLOCK)
    monkeypatch.setattr(smallthinker_float32, "QUERY_BLOCK", BLOCK)


def _setup(compute_dtype="float32", **core):
    """The ``smallthinker_q`` preset at toy widths on cartpole's four numbers
    through one dense layer: windows of 6 burn-in + 12 + 3 = 21 steps, three
    and a half sliding windows long; the 15 steps after the burn-in are a
    multiple of neither the window nor the block."""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["smallthinker_q"]
    cfg = dataclasses.replace(
        cfg, env_name="cartpole",
        network=dataclasses.replace(
            cfg.network, torso="mlp", mlp_features=(16,), hidden=HIDDEN,
            compute_dtype=compute_dtype, remat_torso=False,
            core=dataclasses.replace(cfg.network.core,
                                     **dict(TOY_CORE, **core))),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        replay=dataclasses.replace(cfg.replay, burn_in=WINDOW,
                                   unroll_length=12, sequence_stride=WINDOW,
                                   capacity=256, frame_dedup=False),
        learner=dataclasses.replace(cfg.learner, n_step=3, batch_size=SEQS))
    env = make_jax_env(cfg.env_name)
    return cfg, env, build_network(cfg.network, env.num_actions)


def _check(setup, seed=9, net=None):
    cfg, env, built = setup
    return reference_check.make_check(smallthinker_float32, cfg, env,
                                      net or built, SEQS)(seed)


def test_the_preset_is_one_published_period(monkeypatch):
    """``smallthinker_q`` at its published widths, shapes only (no memory):
    the eight sublayers by name and shape — no ``g_proj``, no ``shared_*``,
    the router beside the ATTENTION sublayer's norm — 281.4 M parameters,
    the acting state a lane by kind of cache, and the start-up gauges'
    readings: of 128 key blocks a call the full layer's triangle visits 100
    and a window layer's band 72, rotary rows in the window layers only."""
    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["smallthinker_q"]
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    obs = jax.ShapeDtypeStruct((1, 1) + tuple(env.observation_shape),
                               env.observation_dtype)
    state = jax.eval_shape(lambda: net.initial_state(1))
    tree = jax.eval_shape(
        lambda key, carry, obs: net.init(key, carry, obs, method=net.unroll),
        jax.random.PRNGKey(0), state, obs)["params"]
    core = jax.tree.map(lambda leaf: leaf.shape, tree["core"])
    attention = {"norm": (2560,), "router": (2560, 64), "mixer": {
        "q_proj": (2560, 3584), "k_proj": (2560, 512),
        "v_proj": (2560, 512), "o_proj": (3584, 2560)}}
    experts = {"norm": (2560,), "mixer": {
        "experts_gate": (2560, 8, 768), "experts_up": (2560, 8, 768),
        "experts_down": (8, 768, 2560)}}
    assert cfg.network.core.pattern == "FEWEWEWE"
    assert core == dict({f"layer_{i}": experts if i % 2 else attention
                         for i in range(8)}, norm_f=(2560,))
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(tree))
    layer = 20_971_520 + 163_840 + 8 * 5_898_240 + 5_120
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))
    assert count == 4 * layer + 2_560 + 8_108_704 + 15_366 + 2_561
    assert count == 281_434_791
    assert net.state_bytes_a_lane() == {
        "attention_full": (2 * 8192 * 4 * 128 + 1) * 4,
        "attention_window": 3 * (2 * 4096 * 4 * 128 + 1) * 4}
    assert sum(net.state_bytes_a_lane().values()) == pytest.approx(
        83.9e6, rel=1e-3)
    # what the start-up gauges read on a TPU
    monkeypatch.setattr(loop_common, "pallas_routing",
                        lambda enabled: (enabled, False))
    blocks = net.attention_key_blocks(2, 4096, 4096)
    rows = net.rotary_head_rows(2, 4096, 4096)
    # one (lane, KV head) of one call: 8 query blocks x 16 key blocks; the
    # full layer's triangle reads 9 + 10 + ... + 16 = 100 of them, a window
    # layer's band (its keys in the order of time) 9 a query block = 72
    assert blocks == {"full": (2 * 4 * 2 * 100, 2 * 4 * 2 * 28),
                      "window": (3 * 2 * 4 * 2 * 72, 3 * 2 * 4 * 2 * 56)}
    assert rows == {"full": 0, "window": 3 * 2 * 8192 * 28}


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_smallthinker_reference_agrees_with_the_programs_learner(
        compute_dtype):
    """Loss, window priorities, Q-values, the gradient read back from Adam's
    moments and the optimizer's step of ``make_r2d2_learner`` over the core
    against ``smallthinker_float32``: windows three and a half sliding
    windows long from the empty state, episode ends in the burn-in and
    among the loss positions; the ring's five numbers beside them."""
    result = _check(_setup(compute_dtype))
    assert result["tolerances"] == dict(
        smallthinker_float32.TOLERANCES[compute_dtype],
        **r2d2_float32.RING_LIMITS)
    if compute_dtype == "float32":
        assert result["ok"], result
        return
    # The bfloat16 bounds are the cell's (2 windows x 8,192 steps at the
    # published widths); a toy batch of 3 x 21 steps sums little and its
    # gradient reads a few percent by seed. Every other number is inside
    # its bound, the gradient well below what the float8 control reads.
    errors, limits = result["errors"], result["tolerances"]
    assert all(errors[k] <= limits[k] for k in limits if k != "grad"), result
    assert errors["grad"] < 0.05, result


def test_the_float8_control_fails_the_smallthinker_comparison():
    setup = _setup("bfloat16")
    result = _check(setup, net=reference_check.CoarseNet(setup[2]))
    assert not result["ok"] and result["errors"]["grad"] > 0.1, result


WRONG = smallthinker_float32.WRONG_FORMULAS


@pytest.mark.parametrize("wrong", list(WRONG) + ["window_one_short",
                                                 "window_one_long"])
def test_a_wrong_smallthinker_formula_fails_the_comparison(wrong,
                                                           monkeypatch):
    """Each part of the published mathematics is held: the router fed the
    experts' own (post-attention) input, a softmax over all the logits whose
    chosen entries are not normalised, ``silu`` in the experts, a rotary
    embedding in the full layer, none in the window layer, a window of 5 or
    7 for 6, a gradient through the burn-in. The program agrees with the
    reference inside the float32 tolerances (the test above), so a
    reference with one formula wrong that reads three tolerances away from
    the true one would come out NOT ok against the program."""
    ref = smallthinker_float32
    cfg, env, net = _setup()
    hp = ref.hyper_from_config(cfg)
    # a window without an episode's end: every query past step 6 has keys
    # beyond its sliding window
    batch, lane = next(
        (b, int(np.flatnonzero(~b["reset"].any(axis=0))[0]))
        for b in (ref.seeded_batch(7, i, SEQS, cfg, env) for i in range(8))
        if (~b["reset"].any(axis=0)).any())
    params = jax.jit(lambda key: net.init(
        key, net.initial_state(1), jnp.asarray(batch["obs"][:1, :1]),
        method=net.unroll))(jax.random.PRNGKey(7))
    window = {k: jnp.asarray(batch[k])[:, lane] for k in (
        "obs", "action", "reward", "done", "reset")}
    window["weights"] = jnp.float32(1.0)

    def q_or_grad(hp):
        with jax.default_matmul_precision("highest"):
            if wrong != "burn_in_gradient":
                return ref.q_window(params, window["obs"], window["reset"],
                                    hp)
            return jax.grad(lambda p: ref._loss(p, params, window, hp)[0])(
                params)

    true = q_or_grad(hp)
    if wrong in WRONG:
        monkeypatch.setattr(ref, *WRONG[wrong])
    else:
        off = {"window_one_short": -1, "window_one_long": 1}[wrong]
        hp = hp._replace(core=hp.core._replace(window=WINDOW + off))
    wrong_one = q_or_grad(hp)
    limits = ref.TOLERANCES["float32"]
    if wrong == "burn_in_gradient":
        assert reference_check._rel_l2(wrong_one, true) > 3 * limits["grad"]
    else:
        assert reference_check._rel_max(wrong_one, true) > 3 * limits["q"]


@pytest.mark.parametrize("kind", ["F", "W"])
def test_the_8_shares_of_a_layer_add_up_to_the_uncut_layer(kind):
    """8 chips each hold 8 of 64 experts at the published router width and
    top-6 (toy expert widths), attention and the router replicated: what the
    8 shares of the program's layer give for their own experts, with the
    attention sublayer — which every chip computes alike, the router's
    logits in it — counted once, is what the uncut reference gives for the
    whole published layer."""
    cfg, _, _ = _setup(pattern=kind + "E", n_routed_experts=64,
                       num_experts_per_tok=6, experts_held=tuple(range(64)),
                       attention_heads_per_layer=(14,))
    core_cfg = cfg.network.core
    core = smallthinker_float32.hyper_from_config(cfg).core
    B, T = 2, 9
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, HIDDEN))
    seg = jnp.zeros((B, T), jnp.int32)
    history = WINDOW if kind == "W" else 8
    empty = (jnp.zeros((B, history, 2, 8)), jnp.zeros((B, history, 2, 8)),
             jnp.zeros((B,)))
    first = sequence_core._Layer(kind, core_cfg, jnp.float32, heads=14,
                                 routes=True)
    whole = sequence_core._Layer("E", core_cfg, jnp.float32)
    p_first = first.init(jax.random.PRNGKey(1), x, seg, empty)["params"]
    h, _, logits = first.apply({"params": p_first}, x, seg, empty)
    assert logits.shape == (B, T, 64) and "router" in p_first
    p_second = whole.init(jax.random.PRNGKey(2), h, seg, (), logits)["params"]
    assert set(p_second["mixer"]) == {"experts_gate", "experts_up",
                                      "experts_down"}
    uncut = jax.vmap(lambda x: smallthinker_float32.layer(
        p_first, p_second, x, jnp.zeros((T,), bool),
        smallthinker_float32.empty_memory(core)[0], core, kind == "W")[0])(x)
    total = h
    for rank in range(8):
        held = tuple(range(8 * rank, 8 * rank + 8))
        share = sequence_core._Layer(
            "E", dataclasses.replace(core_cfg, experts_held=held),
            jnp.float32)
        mine = dict(p_second, mixer=dict({
            name: p_second["mixer"][name][:, held[0]:held[-1] + 1]
            for name in ("experts_gate", "experts_up")},
            experts_down=p_second["mixer"]["experts_down"][
                held[0]:held[-1] + 1]))
        out, _, handed_on = share.apply({"params": mine}, h, seg, (), logits)
        assert handed_on is None
        total = total + out - h
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)
    # and the routed part is not nothing
    assert float(jnp.max(jnp.abs(uncut - h))) > 1e-2


def _attention_case(kind, before):
    """An attention sublayer of the toy core, 19 steps behind a ring that
    ``before`` earlier steps have filled, resets inside a block, at a
    block's first step and at step 0."""
    cfg, _, _ = _setup()
    core = smallthinker_float32.hyper_from_config(cfg).core
    module = sequence_core._MIXERS[kind](cfg.network.core, jnp.float32,
                                         heads=14)
    B, T = 3, 19
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    u = jax.random.normal(keys[0], (B, T, HIDDEN))
    earlier = jax.random.normal(keys[1], (B, before, HIDDEN))
    reset = np.zeros((B, T), bool)
    reset[0, [9, 10, 14]] = True    # twice inside one block
    reset[1, [0, 4]] = True         # the call's and a block's first step
    seg = sequence_core.segments(jnp.asarray(reset))
    history = WINDOW if kind == "W" else 8
    empty = (jnp.zeros((B, history, 2, 8)), jnp.zeros((B, history, 2, 8)),
             jnp.zeros((B,)))
    params = module.init(keys[2], u, seg, empty)
    assert "g_proj" not in params["params"]
    pull = jax.random.normal(keys[3], (B, T, HIDDEN))

    def program(params, u):
        _, carry = module.apply(params, earlier,
                                jnp.zeros((B, before), jnp.int32), empty)
        return module.apply(params, u, seg, carry)[0]

    def plain(params, u):
        def lane(x, x_before, r):
            _, memory = smallthinker_float32.attention(
                params["params"], x_before, jnp.zeros((before,), bool),
                smallthinker_float32.empty_memory(core)[0], core,
                kind == "W")
            return smallthinker_float32.attention(
                params["params"], x, r, memory, core, kind == "W")[0]
        return jax.vmap(lane)(u, earlier, jnp.asarray(reset))

    def with_grads(f):
        return jax.jit(jax.value_and_grad(
            lambda params, u: (lambda o: (jnp.sum(o * pull), o))(
                f(params, u)), argnums=(0, 1), has_aux=True))(params, u)

    return with_grads(program), with_grads(plain)


@pytest.mark.parametrize("route", ["blockwise", "kernels"])
@pytest.mark.parametrize("kind,before", [("W", 3), ("F", 3), ("W", 11)])
def test_attention_by_blocks_is_masked_attention_where_the_window_is_no_block(
        kind, before, route, monkeypatch):
    """``_RotaryAttention`` by blocks of 4 queries under a window of 6 over
    19 steps (a multiple of neither), 7 query heads a KV head — through
    ``blockwise``, and through the fused kernels interpreted at tiles of 4
    queries x 8 keys — against the reference's masked softmax: from a ring
    that ``before`` earlier steps have filled — 3: a valid prefix, then
    empty slots in front of the new keys (a reset inside the burn-in leaves
    the same); 11: the ``W`` ring (6 slots) has wrapped, its slots out of
    the order of their positions — with resets inside a block, at a
    block's first step and at step 0 — outputs, and the gradient to the
    input and every parameter. The full layer rotates nothing on either
    route; the window layer's band is cut from ranges that do not end on
    its blocks."""
    if route == "kernels":
        from dist_dqn_tpu import loop_common

        monkeypatch.setattr(loop_common, "pallas_routing",
                            lambda enabled: (enabled, True))
        monkeypatch.setattr(pallas_attention, "TILES",
                            pallas_attention.Tiles(BLOCK, 2 * BLOCK))
    ((_, got), got_grads), ((_, want), want_grads) = _attention_case(kind,
                                                                    before)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4)


def test_a_full_layer_launches_no_rotary_kernel(monkeypatch):
    """On the kernels' route a layer without a position embedding hands its
    queries to ``attend`` as they are (``rotary=None``: no ``rotary_embed_*``
    kernel), a window layer its tables."""
    from dist_dqn_tpu import loop_common

    seen = []
    real = pallas_attention.attend
    monkeypatch.setattr(loop_common, "pallas_routing",
                        lambda enabled: (enabled, True))
    monkeypatch.setattr(pallas_attention, "TILES",
                        pallas_attention.Tiles(BLOCK, 2 * BLOCK))
    monkeypatch.setattr(
        pallas_attention, "attend",
        lambda *a, **kw: seen.append(kw["rotary"] is not None) or real(
            *a, **kw))
    cfg, _, _ = _setup()
    B, T = 1, 9
    u = jnp.ones((B, T, HIDDEN))
    seg = jnp.zeros((B, T), jnp.int32)
    for kind, history in (("F", 8), ("W", WINDOW)):
        module = sequence_core._MIXERS[kind](cfg.network.core, jnp.float32,
                                             heads=14)
        empty = (jnp.zeros((B, history, 2, 8)),
                 jnp.zeros((B, history, 2, 8)), jnp.zeros((B,)))
        module.apply(module.init(jax.random.PRNGKey(0), u, seg, empty), u,
                     seg, empty)
    assert seen == [False, False, True, True]     # init and apply, each


@pytest.mark.parametrize("route", ["plain", "kernels"])
def test_acting_step_by_step_through_both_rings_is_the_unroll(
        monkeypatch, route):
    """29 steps — nearly five sliding windows, so every ``W`` ring wraps;
    the ``F`` ring (32 slots) does not — with an episode boundary a lane: the
    network stepped through its carry (one slot of each ring written a step,
    a lane emptied by ``Agent.reset_state`` through its counter alone, the
    routing handed from sublayer to sublayer inside each step) gives the
    Q-values of ``unroll`` over the same steps with the reset flags; so does
    an unroll split in two, the way the learner splits burn-in from loss."""
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


def test_the_routing_crosses_the_sublayer_with_its_gradient(monkeypatch):
    """The router's weights lie beside the attention sublayer's norm and get
    a gradient; the window's input gets one THROUGH THE GATES too — it
    changes when the gates are cut from the graph — and an ``E`` sublayer
    with nothing before it to route from is refused."""
    cfg, env, net = _setup()
    T, B = 16, 3        # 48 tokens: some choose a held expert in each layer
    obs = jax.random.normal(jax.random.PRNGKey(0),
                            (T, B) + tuple(env.observation_shape))
    carry = net.initial_state(B)
    params = net.init(jax.random.PRNGKey(1), carry, obs, method=net.unroll)

    def q_sum(params, obs):
        return jnp.sum(net.apply(params, carry, obs, method=net.unroll)[1]
                       ** 2)

    g_params, g_obs = jax.grad(q_sum, argnums=(0, 1))(params, obs)
    core = g_params["params"]["core"]
    for i in (0, 2):
        assert float(jnp.max(jnp.abs(core[f"layer_{i}"]["router"]))) > 0
        assert "router" not in core[f"layer_{i + 1}"]["mixer"]
    real = sequence_core.route
    monkeypatch.setattr(sequence_core, "route",
                        lambda *a: jax.lax.stop_gradient(real(*a)))
    cut = jax.grad(q_sum, argnums=1)(params, obs)
    assert float(jnp.max(jnp.abs(cut - g_obs))) > 1e-6
    bad = dataclasses.replace(cfg.network.core, pattern="EFE",
                              attention_heads_per_layer=(14,))
    with pytest.raises(ValueError, match="router_ahead"):
        sequence_core.routes_ahead(bad)


def test_required_flops_count_the_band_the_triangle_and_the_routed_rows():
    """``grad_step_flops`` at the published widths, by hand: a window
    layer's scores over the 3,072.25 keys a query of an 8,192-step window
    sees on average (the band of 4,096), the full layer's over 4,096.5 (the
    triangle), the routed experts at the rows the routing sends here (6 x 8
    / 64 = 0.75 expert evaluations a token) — an eighth of what the dense
    product over the 8 held experts computes."""
    from dist_dqn_tpu.envs import make_jax_env

    cfg = CONFIGS["smallthinker_q"]
    env = make_jax_env(cfg.env_name)
    parts = smallthinker_float32.forward_flops_per_step(cfg, env)
    assert smallthinker_float32.mean_keys_seen(8192, 4096) == pytest.approx(
        3072.25)
    assert smallthinker_float32.mean_keys_seen(8192, None) == pytest.approx(
        4096.5)
    projections = 2560 * 36 * 128 + 28 * 128 * 2560
    assert parts["attention_window"] == pytest.approx(3 * 2 * (
        projections + 2 * 28 * 128 * 3072.25))
    assert parts["attention_full"] == pytest.approx(2 * (
        projections + 2 * 28 * 128 * 4096.5))
    expert = 2 * 3 * 2560 * 768
    assert parts["moe_routed"] == pytest.approx(4 * 0.75 * expert)
    assert parts["moe_router"] == pytest.approx(4 * 2 * 2560 * 64)
    assert set(parts) == {"torso", "heads", "attention_full",
                          "attention_window", "moe_router", "moe_routed"}
    total = smallthinker_float32.grad_step_flops(cfg, env)
    whole = sum(v for k, v in parts.items() if k != "heads")
    assert total == pytest.approx(
        2 * (2 * 8192 * whole + 2 * 4096 * whole), rel=1e-3)
    # 427 MFLOP a token's forward (attention 359, experts 37, torso 32) x
    # 2 windows x (2 x 8,192 + 2 x 4,096) = 21.0 TFLOP required a grad
    # step; the dense product over the 8 held experts (8 evaluations a
    # token for 0.75) computes 342 MFLOP a token more
    assert total == pytest.approx(20.98e12, rel=1e-3)


def test_the_ring_check_holds_a_whole_window_of_the_preset():
    """The sequence ring's own check at the preset's geometry — windows of
    8,192 steps every 4,096 — needs the cell's whole ring of 16,384 time
    slices (``r2d2_float32``'s 2,048 and ``laguna_float32``'s 4,096 cannot
    hold a window and a stride): ``smallthinker_float32`` builds it over
    those, and leaves that module's count as it was. (Built only: 3 GB of
    frames are a chip's to hold, and the toy cell runs the five numbers.)"""
    from dist_dqn_tpu.envs import make_jax_env

    cfg = CONFIGS["smallthinker_q"]
    env = make_jax_env(cfg.env_name)
    with pytest.raises(NotImplementedError, match="2048 slots"):
        r2d2_float32.make_further_check(cfg, env)
    assert callable(smallthinker_float32.make_further_check(cfg, env))
    assert r2d2_float32.RING_SLOTS == 2048
    assert smallthinker_float32.RING_SLOTS == (
        cfg.replay.capacity // cfg.actor.num_envs) == 16384
