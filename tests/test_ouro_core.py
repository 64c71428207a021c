"""The ``ouro_q`` sequence core (``models/sequence_core.py``: a stack of full
rotary attention ``F`` and dense MLPs ``D`` with a norm on every sublayer's
input AND output, run ``loops`` times over the same leaves, the final norm
after every turn, a ring a turn a layer) against its plain reference
(``perf/reference/ouro_float32.py``) at toy widths on the CPU: the learner
step through the harness's own comparison, one wrong formula a part, the
stream's precision read by its own check, the turns against the stack applied
by hand, the rings of two turns, acting step by
step against the unroll, the required operations by hand, the preset's tree,
and the un-looped cores as the parent left them."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS, apply_overrides
from dist_dqn_tpu.models import sequence_core
from perf.harness import reference_check
from perf.reference import ouro_float32, r2d2_float32

SEQS = 3
HIDDEN = 32
# two published layers at toy widths: one query head a KV head, as published
TOY_CORE = dict(pattern="FDFD", intermediate_size=24, num_key_value_heads=4,
                head_dim=8, attention_heads_per_layer=(4, 4),
                attention_window=32)


def _setup(compute_dtype="float32", **core):
    """The ``ouro_q`` preset at toy widths on cartpole's four numbers through
    one dense layer: windows of 6 burn-in + 12 + 3 = 21 steps, four turns."""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["ouro_q"]
    cfg = dataclasses.replace(
        cfg, env_name="cartpole",
        network=dataclasses.replace(
            cfg.network, torso="mlp", mlp_features=(16,), hidden=HIDDEN,
            compute_dtype=compute_dtype, remat_torso=False,
            core=dataclasses.replace(cfg.network.core,
                                     **dict(TOY_CORE, **core))),
        actor=dataclasses.replace(cfg.actor, num_envs=4),
        replay=dataclasses.replace(cfg.replay, burn_in=6, unroll_length=12,
                                   sequence_stride=6, capacity=256,
                                   frame_dedup=False),
        learner=dataclasses.replace(cfg.learner, n_step=3, batch_size=SEQS))
    env = make_jax_env(cfg.env_name)
    return cfg, env, build_network(cfg.network, env.num_actions)


def _check(setup, seed=9, net=None):
    cfg, env, built = setup
    return reference_check.make_check(ouro_float32, cfg, env, net or built,
                                      SEQS)(seed)


def _count(tree) -> int:
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


def test_the_preset_is_one_stage_run_four_times(monkeypatch):
    """``ouro_q`` at its published widths, shapes only (no memory): the
    eight sublayers by name and shape — four norms a published layer, no
    ``g_proj`` — 212.1 M parameters whatever ``loops`` says, the acting
    state a lane (sixteen rings: one a turn a layer), and the start-up
    gauges' readings, every one counting sixteen ``F`` applications."""
    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["ouro_q"]
    env = make_jax_env(cfg.env_name)
    obs = jax.ShapeDtypeStruct((1, 1) + tuple(env.observation_shape),
                               env.observation_dtype)

    def tree_of(loops):
        core = dataclasses.replace(cfg.network.core, loops=loops)
        net = build_network(dataclasses.replace(cfg.network, core=core),
                            env.num_actions)
        state = jax.eval_shape(lambda: net.initial_state(1))
        return net, state, jax.eval_shape(
            lambda key, carry, obs: net.init(key, carry, obs,
                                             method=net.unroll),
            jax.random.PRNGKey(0), state, obs)["params"]

    net, state, tree = tree_of(4)
    core = jax.tree.map(lambda leaf: leaf.shape, tree["core"])
    attention = {"norm": (2048,), "norm_out": (2048,), "mixer": {
        "q_proj": (2048, 2048), "k_proj": (2048, 2048),
        "v_proj": (2048, 2048), "o_proj": (2048, 2048)}}
    mlp = {"norm": (2048,), "norm_out": (2048,), "mixer": {
        "gate_proj": (2048, 5632), "up_proj": (2048, 5632),
        "down_proj": (5632, 2048)}}
    assert cfg.network.core.pattern == "FDFDFDFD"
    assert (cfg.network.core.loops, cfg.network.core.sandwich_norm) == (4,
                                                                        True)
    assert core == dict({f"layer_{i}": mlp if i % 2 else attention
                         for i in range(8)}, norm_f=(2048,))
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(tree))
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert _count(tree) == 4 * layer + 2048 + _count(tree["torso"]) + _count(
        tree["advantage"]) + _count(tree["value"])
    assert _count(tree) == pytest.approx(212.1e6, rel=1e-3)
    for loops in (1, 2):
        other = tree_of(loops)[2]
        assert jax.tree.map(lambda leaf: leaf.shape, other) == jax.tree.map(
            lambda leaf: leaf.shape, tree)
    # the carry: one entry a sublayer a turn, a ring a turn a layer
    assert len(state) == 32 and sequence_core.applied(
        cfg.network.core) == "FDFDFDFD" * 4
    assert [len(entry) for entry in state] == [3, 0] * 16
    ring = 2 * 2048 * 16 * 128 * 4
    assert ring == 2048 * 16_384
    assert net.state_bytes_a_lane() == {"attention_full": 16 * (ring + 4)}
    assert 16 * ring == pytest.approx(537e6, rel=1e-3)
    # on a CPU ``attend`` casts every ring in front of its products; on a
    # TPU ``decode`` reads them where they lie and nothing is copied
    assert net.attention_ring_bytes(8) == {
        "full": (8 * 16 * ring, 8 * 16 * ring // 2)}
    monkeypatch.setattr(loop_common, "pallas_routing",
                        lambda enabled: (enabled, False))
    assert net.attention_ring_bytes(8) == {"full": (8 * 16 * ring, 0)}
    # one (lane, KV head) of a forward pass: the burn-in call (512 steps
    # from an empty ring of 512: 1 query block x 2 key blocks) and the call
    # over the other 1,536 (3 query blocks, 2 + 3 + 4 of 4 key blocks)
    assert net.attention_key_blocks(2, 512, 1536) == {
        "full": (16 * 2 * 16 * (2 + 9), 16 * 2 * 16 * 3)}
    assert net.rotary_head_rows(2, 512, 1536) == {
        "full": 16 * 2 * 2048 * 16}


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_ouro_reference_agrees_with_the_programs_learner(compute_dtype):
    """Loss, window priorities, Q-values, the gradient read back from Adam's
    moments — a weight's the sum over its four uses — and the optimizer's
    step of ``make_r2d2_learner`` over the looped core against
    ``ouro_float32``'s four passes written out: windows from the empty
    state, episode ends in the burn-in and among the loss positions; the
    ring's five numbers and the stream's beside them."""
    result = _check(_setup(compute_dtype))
    assert result["tolerances"] == dict(
        ouro_float32.TOLERANCES[compute_dtype], **r2d2_float32.RING_LIMITS,
        stream=ouro_float32.STREAM_LIMIT[compute_dtype])
    if compute_dtype == "float32":
        assert result["ok"], result
        return
    # The bfloat16 bounds are the cell's (2 windows x 2,048 steps at the
    # published widths, where the norms after every sublayer and turn keep
    # the stream's size); a toy batch of 3 x 21 steps at 32 channels sums
    # little and reads a few percent by seed. The ring's numbers and the
    # optimizer's (float32 on both sides) are inside their bounds, the rest
    # well below what the float8 control reads (the test below).
    errors, limits = result["errors"], result["tolerances"]
    assert all(errors[k] <= limits[k] for k in limits
               if k not in ("q", "priorities", "loss", "grad")), result
    assert max(errors[k] for k in ("q", "priorities", "loss",
                                   "grad")) < 0.08, result


def test_the_float8_control_fails_the_ouro_comparison():
    setup = _setup("bfloat16")
    result = _check(setup, net=reference_check.CoarseNet(setup[2]))
    assert not result["ok"] and result["errors"]["grad"] > 0.1, result


WRONG = ouro_float32.WRONG_FORMULAS


def test_the_wrong_formulas_are_the_six_the_loop_can_get_wrong():
    assert set(WRONG) == {
        "three_turns", "no_norm_between_the_turns", "pre_norm_only",
        "one_ring_shared_by_the_turns", "theta_10000",
        "bfloat16_residual_stream"}
    assert all(hasattr(ouro_float32, replaced) for replaced, _ in
               WRONG.values())


@pytest.mark.parametrize("wrong", list(WRONG) + ["burn_in_gradient"])
def test_a_wrong_ouro_formula_fails_the_comparison(wrong, monkeypatch):
    """Each part of the published mathematics is held: three turns for four,
    no norm between the turns, no norm on a sublayer's output, every turn
    reading the last turn's keys of the earlier steps, theta 10,000 for 1e6,
    a residual stream rounded to bfloat16, a gradient through the burn-in.
    The program agrees with the reference inside the float32 tolerances (the
    test above), so a reference with one formula wrong that reads three
    tolerances away from the true one would come out NOT ok against the
    program."""
    ref = ouro_float32
    cfg, env, net = _setup()
    hp = ref.hyper_from_config(cfg)
    batch = ref.seeded_batch(7, 0, SEQS, cfg, env)
    params = jax.jit(lambda key: net.init(
        key, net.initial_state(1), jnp.asarray(batch["obs"][:1, :1]),
        method=net.unroll))(jax.random.PRNGKey(7))
    window = {k: jnp.asarray(batch[k])[:, 0] for k in (
        "obs", "action", "reward", "done", "reset")}
    window["weights"] = jnp.float32(1.0)

    def q_or_grad():
        with jax.default_matmul_precision("highest"):
            if wrong != "burn_in_gradient":
                return ref.q_window(params, window["obs"], window["reset"],
                                    hp)
            return jax.grad(lambda p: ref._loss(p, params, window, hp)[0])(
                params)

    true = q_or_grad()
    monkeypatch.setattr(ref, *WRONG.get(wrong, (
        "leave_burn_in", lambda memory: memory)))
    wrong_one = q_or_grad()
    limits = ref.TOLERANCES["float32"]
    if wrong == "burn_in_gradient":
        assert reference_check._rel_l2(wrong_one, true) > 3 * limits["grad"]
    else:
        assert reference_check._rel_max(wrong_one, true) > 3 * limits["q"]


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_the_stream_check_reads_a_bfloat16_residual_stream(compute_dtype,
                                                           monkeypatch):
    """What the step's five numbers cannot tell from the bfloat16 products'
    own rounding (at the published widths on the chip: ``PERF.md`` §6, PR
    53): ``make_stream_check`` reads the sound program well inside its limit
    in either compute type, and a reference whose stream is rounded to
    bfloat16 — what a program with that fault reads against the sound one —
    several times outside it."""
    cfg, env, _ = _setup(compute_dtype)
    sound = [ouro_float32.make_stream_check(cfg, env)(seed)
             for seed in (1, 2)]
    monkeypatch.setattr(ouro_float32, *WRONG["bfloat16_residual_stream"])
    wrong = [ouro_float32.make_stream_check(cfg, env)(seed)
             for seed in (1, 2)]
    for (value, limit), (faulty, _) in zip(sound, wrong):
        assert limit == ouro_float32.STREAM_LIMIT[compute_dtype]
        assert 2 * value < limit < faulty / 2, (sound, wrong)


def test_the_turns_are_the_stack_applied_to_its_own_output():
    """``HybridQNetwork.turns`` by hand: ``_Core`` — the one set of
    parameters under ``core`` — applied to the torso's output with the first
    turn's entries of the carry gives what the first turn handed on, that
    with the second turn's entries what the second did, and so on; the heads
    read the last; every turn's state leaves in the carry's order.
    A weight's gradient is therefore the sum over its turns: no other form
    of the loop is in the program."""
    cfg, env, net = _setup()
    per_turn = len(cfg.network.core.pattern)
    T, B = 9, 2
    obs = jax.random.normal(jax.random.PRNGKey(0),
                            (T, B) + tuple(env.observation_shape))
    reset = np.zeros((T, B), bool)
    reset[4, 1] = True
    carry = net.initial_state(B)
    params = net.init(jax.random.PRNGKey(1), carry, obs, method=net.unroll)
    (after, q), seen = net.apply(
        params, carry, obs, reset, method=net.unroll,
        mutable=["intermediates"],
        capture_intermediates=lambda module, _: module.name in ("torso",
                                                                "core"))
    seen = seen["intermediates"]
    turns = seen["core"]["__call__"]
    assert len(turns) == cfg.network.core.loops == 4
    stack = sequence_core._Core(cfg.network.core, jnp.float32)
    x = jnp.swapaxes(seen["torso"]["__call__"][0].reshape(T, B, -1), 0, 1)
    for r, (handed_on, state) in enumerate(turns):
        x, made = stack.apply(
            {"params": params["params"]["core"]}, x, reset.T,
            carry[r * per_turn:(r + 1) * per_turn])
        np.testing.assert_allclose(x, handed_on, rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(
                after[r * per_turn:(r + 1) * per_turn])):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    heads = params["params"]
    h = jnp.swapaxes(x, 0, 1)
    adv = h @ heads["advantage"]["kernel"] + heads["advantage"]["bias"]
    value = h @ heads["value"]["kernel"] + heads["value"]["bias"]
    np.testing.assert_allclose(
        q, value + adv - adv.mean(-1, keepdims=True), rtol=1e-4, atol=1e-5)


def test_every_turn_keeps_a_ring_and_reads_its_own():
    """After some steps the rings of two turns of one layer differ (turn r's
    keys are projections of turn r's hidden state), and a ring is read by
    its own application alone: a change to turn 1's ring of the first layer
    reaches the Q-values and every key made AFTER that application — the
    rest of turn 1, turns 2 and 3 — and nothing made before it (all of turn
    0: its keys, and what it hands on)."""
    cfg, env, net = _setup()
    per_turn = len(cfg.network.core.pattern)
    T, B = 5, 2
    obs = jax.random.normal(jax.random.PRNGKey(0),
                            (T + 1, B) + tuple(env.observation_shape))
    carry = net.initial_state(B)
    params = net.init(jax.random.PRNGKey(1), carry, obs[:1],
                      method=net.unroll)
    carry, _ = net.apply(params, carry, obs[:T], method=net.unroll)
    first_layer = [carry[r * per_turn] for r in range(4)]
    for a, b in zip(first_layer, first_layer[1:]):
        assert float(jnp.max(jnp.abs(a[0] - b[0]))) > 1e-3    # the keys
        np.testing.assert_array_equal(a[2], b[2])             # the counters
    changed = list(carry)
    at = 1 * per_turn
    changed[at] = (carry[at][0] + 1.0,) + carry[at][1:]
    after, q = net.apply(params, carry, obs[T])
    after_changed, q_changed = net.apply(params, tuple(changed), obs[T])
    assert float(jnp.max(jnp.abs(q - q_changed))) > 1e-4
    for i, (a, b) in enumerate(zip(after, after_changed)):
        if not a:
            continue
        moved = float(jnp.max(jnp.abs(a[1] - b[1])))    # the values written
        assert (moved == 0.0) == (i <= at), (i, moved)


@pytest.mark.parametrize("route", ["plain", "kernels"])
def test_acting_step_by_step_through_sixteen_rings_is_the_unroll(
        monkeypatch, route):
    """21 steps with an episode boundary a lane: the network stepped through
    its carry (the turns one after the other, one slot of each of a lane's
    eight rings written a step, a lane emptied by ``Agent.reset_state``
    through its counters alone) gives the Q-values of ``unroll`` over the
    same steps with the reset flags — a learner's window; so does an
    unroll split in two, the way the learner splits burn-in from loss."""
    from dist_dqn_tpu.agents import make_agent
    from dist_dqn_tpu.ops import pallas_attention as kernels

    # "kernels": the route a TPU takes (``loop_common.pallas_routing``),
    # interpreted — acting's one query a head through ``decode`` at ONE
    # query head a KV head, the unroll through the learner's kernels
    decoded, decode = [], kernels.decode
    monkeypatch.setattr(kernels, "decode", lambda *a, **k: (
        decoded.append(a[0].shape), decode(*a, **k))[1])
    if route == "kernels":
        monkeypatch.setenv("DIST_DQN_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("DIST_DQN_PALLAS_INTERPRET", raising=False)
    cfg, env, net = _setup()
    T, B = 21, 2
    obs = jax.random.normal(jax.random.PRNGKey(0),
                            (T, B) + tuple(env.observation_shape))
    done = np.zeros((T, B), bool)
    done[4, 0] = done[12, 1] = done[13, 1] = True
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
    half, first = unroll(params, carry, obs[:8], reset[:8])
    split_carry, second = unroll(params, half, obs[8:], reset[8:])
    np.testing.assert_allclose(jnp.concatenate([first, second]), want,
                               rtol=1e-4, atol=1e-5)
    q_next = [step(params, c, obs[0])[1] for c in (stepped, want_carry,
                                                   split_carry)]
    for other in q_next[1:]:
        np.testing.assert_allclose(other, q_next[0], rtol=1e-4, atol=1e-5)
    rings = [layer for layer in stepped if layer]
    assert len(rings) == 8
    for layer in rings:
        np.testing.assert_array_equal(layer[2], [16.0, 7.0])
    # 4 KV heads, ONE query head each: a trace of the step is 8 calls
    assert set(decoded) == ({(B, 4, 1, 8)} if route == "kernels" else set())
    assert agent.stored_state(carry) == ()


def test_required_flops_count_sixteen_passes():
    """``grad_step_flops`` at the published widths, by hand: four turns over
    four layers, each the four projections of 2,048 x 2,048, scores and
    weighted values over the 1,024.5 keys a query of a 2,048-step window
    sees on average, and a gated MLP of 5,632."""
    from dist_dqn_tpu.envs import make_jax_env

    cfg = CONFIGS["ouro_q"]
    env = make_jax_env(cfg.env_name)
    parts = ouro_float32.forward_flops_per_step(cfg, env)
    assert ouro_float32.mean_keys_seen(2048, None) == pytest.approx(1024.5)
    assert parts["attention_full"] == pytest.approx(16 * 2 * (
        4 * 2048 * 2048 + 2 * 16 * 128 * 1024.5))
    assert parts["mlp_dense"] == pytest.approx(16 * 2 * 3 * 2048 * 5632)
    assert set(parts) == {"torso", "heads", "attention_full", "mlp_dense"}
    whole = sum(v for k, v in parts.items() if k != "heads")
    # 1.78 GFLOP a token's forward: 0.67 attention, 1.11 MLP, 0.03 torso
    assert whole == pytest.approx(1.81e9, rel=1e-2)
    total = ouro_float32.grad_step_flops(cfg, env)
    assert total == pytest.approx(
        2 * (2 * 2048 * whole + 2 * 1536 * whole), rel=1e-3)
    assert total == pytest.approx(25.9e12, rel=1e-2)


def test_the_ring_check_holds_a_whole_window_of_the_preset():
    """The sequence ring's own check at the preset's geometry — windows of
    2,048 steps every 512 — over 4,096 time slices (``r2d2_float32``'s 2,048
    cannot hold a window and a stride); that module's count is left as it
    was. (Built only: the toy cell runs the five numbers.)"""
    from dist_dqn_tpu.envs import make_jax_env

    cfg = CONFIGS["ouro_q"]
    env = make_jax_env(cfg.env_name)
    with pytest.raises(NotImplementedError, match="2048 slots"):
        r2d2_float32.make_further_check(cfg, env)
    assert callable(ouro_float32.make_further_check(cfg, env))
    assert r2d2_float32.RING_SLOTS == 2048
    assert ouro_float32.RING_SLOTS == 4096 <= (
        cfg.replay.capacity // cfg.actor.num_envs)


def _tree_hash(tree) -> str:
    rows = sorted((jax.tree_util.keystr(path), tuple(leaf.shape),
                   str(leaf.dtype))
                  for path, leaf in jax.tree_util.tree_leaves_with_path(tree))
    return hashlib.sha256(repr(rows).encode()).hexdigest()[:16]


def test_an_unlooped_core_is_the_parents():
    """At ``loops`` 1 without ``sandwich_norm`` the ``laguna_q`` toy is what
    it was before either field existed: the parameter tree and the carry
    tree by path, shape and type, and the Q-values and the state of a
    seeded window — all recorded on the parent commit (5be22c0) by this
    very code. (That the chunk programs of the accepted cells are the
    parent's bit for bit is ``scripts/chunk_program_hash.py``'s to show:
    ``perf/records/pr53/``.)"""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from perf.tests.test_perf_laguna import TOY_LAGUNA_CONFIG

    cfg = apply_overrides(CONFIGS["laguna_q"], TOY_LAGUNA_CONFIG[
        "overrides"] + ["env_name=cartpole", "network.torso=mlp",
                        "network.mlp_features=(16,)"])
    assert (cfg.network.core.loops, cfg.network.core.sandwich_norm) == (
        1, False)
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    T, B = 9, 2
    obs = jax.random.normal(jax.random.PRNGKey(0),
                            (T, B) + tuple(env.observation_shape))
    reset = np.zeros((T, B), bool)
    reset[4, 1] = True
    carry = net.initial_state(B)
    params = net.init(jax.random.PRNGKey(1), net.initial_state(1),
                      obs[:1, :1], method=net.unroll)
    assert (_tree_hash(params), _tree_hash(carry)) == (
        "e17dfdf1bcd93c95", "a242b25ec5f63937")
    assert str(jax.tree.structure(carry)) == (
        "PyTreeDef(((*, *, *), (), (*, *, *), ()))")
    assert _count(params) == 18611
    assert not any("norm_out" in jax.tree_util.keystr(path) for path, _ in
                   jax.tree_util.tree_leaves_with_path(params))
    new_carry, q = jax.jit(lambda *a: net.apply(*a, method=net.unroll))(
        params, carry, obs, reset)
    np.testing.assert_allclose(
        np.asarray(q).ravel()[:6],
        [-0.872183084487915, -2.4180946350097656, 0.04295516014099121,
         -0.46511948108673096, -0.698327898979187, -0.9807384014129639],
        rtol=1e-5)
    assert float(jnp.sum(q)) == pytest.approx(-11.414862632751465, rel=1e-5)
    assert float(sum(jnp.sum(leaf) for leaf in jax.tree.leaves(
        new_carry))) == pytest.approx(69.68659210205078, rel=1e-5)
