"""The recurrent torso holds its convolutions' outputs behind a barrier
(models/recurrent.py ``_HeldCNNTorso``; PERF.md §7.8): the hold changes no
value, no gradient and no parameter name, sits in every pass of the
recurrent learner and inside what ``nn.remat`` wraps, and is in no DQN
program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.agents.dqn import make_learner
from dist_dqn_tpu.agents.r2d2 import make_r2d2_learner
from dist_dqn_tpu.config import CONFIGS, LearnerConfig, ReplayConfig
from dist_dqn_tpu.models import qnets, recurrent
from dist_dqn_tpu.models.recurrent import RecurrentQNetwork
from dist_dqn_tpu.types import SequenceSample, Transition

BURN, UNROLL, N_STEP, WINDOWS, ACTIONS = 2, 3, 1, 3, 3
# The smallest frame the Nature torso's three VALID convolutions take.
FRAME = (36, 36, 4)
TORSOS = ("nature", "small", "mlp")
CONV_TORSOS = ("nature", "small")
RCFG = ReplayConfig(burn_in=BURN, unroll_length=UNROLL)
LCFG = LearnerConfig(n_step=N_STEP, batch_size=WINDOWS, double_dqn=True,
                     value_rescale=True)


def _net(torso, **kw):
    return RecurrentQNetwork(num_actions=ACTIONS, torso=torso,
                             mlp_features=(16,), hidden=8, lstm_size=8, **kw)


def _obs(torso, steps, seed=0):
    r = np.random.default_rng(seed)
    if torso == "mlp":
        return jnp.asarray(r.normal(size=(steps, WINDOWS, 5)), jnp.float32)
    return jnp.asarray(r.integers(0, 256, (steps, WINDOWS) + FRAME), jnp.uint8)


def _sample(torso, net):
    L = BURN + UNROLL + N_STEP
    r = np.random.default_rng(1)
    state = tuple(jnp.asarray(r.normal(size=(WINDOWS, 8)), jnp.float32)
                  for _ in net.initial_state(WINDOWS))
    return SequenceSample(
        obs=_obs(torso, L),
        action=jnp.asarray(r.integers(0, ACTIONS, (L, WINDOWS)), jnp.int32),
        reward=jnp.asarray(r.normal(size=(L, WINDOWS)), jnp.float32),
        done=jnp.asarray(r.random((L, WINDOWS)) < 0.1),
        reset=jnp.asarray(r.random((L, WINDOWS)) < 0.1),
        start_state=state,
        weights=jnp.asarray(r.random(WINDOWS) + 0.5, jnp.float32),
        t_idx=jnp.zeros((WINDOWS,), jnp.int32),
        b_idx=jnp.arange(WINDOWS, dtype=jnp.int32))


def _plain_torso(monkeypatch):
    """The recurrent network over ``qnets.CNNTorso`` itself: what it was
    built from before the hold, and what the DQN networks are built from."""
    monkeypatch.setattr(recurrent, "_HeldCNNTorso", qnets.CNNTorso)


def _paths(tree):
    return {jax.tree_util.keystr(path, simple=True, separator="/"): leaf.shape
            for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _assert_trees_equal(a, b):
    assert _paths(a) == _paths(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _barriers(jaxpr, stack=""):
    """Name stacks of every ``optimization_barrier`` equation in ``jaxpr``
    and the jaxprs its equations hold (scan, cond, remat, custom_jvp)."""
    found = []
    for eqn in jaxpr.eqns:
        here = "/".join(s for s in (stack, str(eqn.source_info.name_stack))
                        if s)
        if eqn.primitive.name == "optimization_barrier":
            found.append(here)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _barriers(sub, here)
    return found


@pytest.mark.parametrize("torso", TORSOS)
def test_unroll_and_train_step_are_the_plain_torsos(torso, monkeypatch):
    """Same parameters, same batch: q, the carry and one whole train step —
    loss, priorities, the parameters after Adam and Adam's moments, which
    ARE the gradient — equal to the bit in float32 with and without the
    hold."""
    def run():
        net = _net(torso)
        init, train_step = make_r2d2_learner(net, LCFG, RCFG)
        state = init(jax.random.PRNGKey(0), _obs(torso, 1)[0, 0])
        sample = _sample(torso, net)
        carry, q = net.apply(state.params, sample.start_state, sample.obs,
                             sample.reset, method=net.unroll)
        new_state, metrics = jax.jit(train_step)(state, sample)
        return state.params, (carry, q), new_state, metrics

    held = run()
    _plain_torso(monkeypatch)
    plain = run()
    for a, b in zip(held, plain):
        _assert_trees_equal(a, b)
    assert float(jnp.abs(held[3]["loss"])) > 0


PARENT_TREE = {
    # models/recurrent.py at b4b1721 (PR 42), num_actions 3, hidden 8,
    # lstm_size 8, mlp_features (16,), frames 36x36x4 / vectors of 5.
    "nature": {
        "params/torso/CNNTorso_0/Conv_0/bias": (32,),
        "params/torso/CNNTorso_0/Conv_0/kernel": (8, 8, 4, 32),
        "params/torso/CNNTorso_0/Conv_1/bias": (64,),
        "params/torso/CNNTorso_0/Conv_1/kernel": (4, 4, 32, 64),
        "params/torso/CNNTorso_0/Conv_2/bias": (64,),
        "params/torso/CNNTorso_0/Conv_2/kernel": (3, 3, 64, 64),
        "params/torso/embed/kernel": (64, 8)},
    "small": {
        "params/torso/CNNTorso_0/Conv_0/bias": (16,),
        "params/torso/CNNTorso_0/Conv_0/kernel": (8, 8, 4, 16),
        "params/torso/CNNTorso_0/Conv_1/bias": (32,),
        "params/torso/CNNTorso_0/Conv_1/kernel": (4, 4, 16, 32),
        "params/torso/embed/kernel": (288, 8)},
    "mlp": {
        "params/torso/MLPTorso_0/Dense_0/bias": (16,),
        "params/torso/MLPTorso_0/Dense_0/kernel": (5, 16),
        "params/torso/embed/kernel": (16, 8)},
}
PARENT_REST = {
    "params/torso/embed/bias": (8,),
    "params/advantage/bias": (3,), "params/advantage/kernel": (8, 3),
    "params/value/bias": (1,), "params/value/kernel": (8, 1),
    **{f"params/core/lstm/h{g}/bias": (8,) for g in "fgio"},
    **{f"params/core/lstm/{x}{g}/kernel": (8, 8)
       for x in "hi" for g in "fgio"},
}


@pytest.mark.parametrize("torso", TORSOS)
def test_parameter_tree_is_the_parents(torso):
    """Names and shapes as the parent commit wrote them into a checkpoint."""
    net = _net(torso)
    params = net.init(jax.random.PRNGKey(0), net.initial_state(WINDOWS),
                      _obs(torso, 2), method=net.unroll)
    assert _paths(params) == {**PARENT_TREE[torso], **PARENT_REST}


@pytest.mark.parametrize("torso", CONV_TORSOS)
def test_recurrent_train_step_holds_in_every_pass_and_dqn_in_none(torso):
    net = _net(torso)
    init, train_step = make_r2d2_learner(net, LCFG, RCFG)
    state = init(jax.random.PRNGKey(0), _obs(torso, 1)[0, 0])
    stacks = _barriers(
        jax.make_jaxpr(train_step)(state, _sample(torso, net)).jaxpr)
    assert stacks and all("loss_grad" in s and "torso" in s for s in stacks)
    # Forward only (the tangent passes by): once a held layer — every
    # convolution but the last — and pass, the burn-in of both networks
    # under the one name.
    layers = len(qnets.CNN_TORSO_LAYERS[torso]) - 1
    for name, passes in (("burn_in", 2), ("online_unroll", 1),
                         ("target_unroll", 1)):
        assert sum(name in s for s in stacks) == passes * layers, (name,
                                                                   stacks)

    dqn = qnets.QNetwork(num_actions=ACTIONS, torso=torso, hidden=8)
    dinit, dstep = make_learner(dqn, LCFG)
    frames = _obs(torso, 1)[0]
    batch = Transition(obs=frames, action=jnp.zeros((WINDOWS,), jnp.int32),
                       reward=jnp.ones((WINDOWS,)),
                       discount=jnp.ones((WINDOWS,)), next_obs=frames)
    dstate = dinit(jax.random.PRNGKey(0), frames[0])
    assert _barriers(jax.make_jaxpr(dstep)(
        dstate, batch, jnp.ones((WINDOWS,))).jaxpr) == []


def test_mlp_torso_holds_nothing():
    net = _net("mlp")
    init, train_step = make_r2d2_learner(net, LCFG, RCFG)
    state = init(jax.random.PRNGKey(0), _obs("mlp", 1)[0, 0])
    assert _barriers(jax.make_jaxpr(train_step)(
        state, _sample("mlp", net)).jaxpr) == []


@pytest.mark.parametrize("torso", TORSOS)
def test_remat_embed_gives_the_same_gradients(torso):
    """``nn.remat(_Embed)`` wraps the hold: same parameter tree, same
    gradients, and the barrier inside the rematerialised region."""
    obs = _obs(torso, 4)
    nets = [_net(torso, remat_torso=r) for r in (False, True)]
    params = nets[0].init(jax.random.PRNGKey(0), nets[0].initial_state(WINDOWS),
                          obs, method=nets[0].unroll)

    def loss(net):
        return lambda p: jnp.sum(net.apply(
            p, net.initial_state(WINDOWS), obs, method=net.unroll)[1] ** 2)

    plain, remat = (jax.grad(loss(net))(params) for net in nets)
    assert _paths(plain) == _paths(params)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    if torso in CONV_TORSOS:
        jaxpr = jax.make_jaxpr(jax.grad(loss(nets[1])))(params).jaxpr
        inside = [s for eqn in jaxpr.eqns if eqn.primitive.name == "remat2"
                  for s in _barriers(eqn.params["jaxpr"])]
        assert inside and all("rematted_computation" in s for s in inside)


def test_r2d2_preset_builds_the_held_torso():
    """The recurrent network takes the path because it is the recurrent
    network: the preset as it stands, at the preset's frame, one step."""
    from dist_dqn_tpu.models import build_network

    net = build_network(CONFIGS["r2d2"].network, 6)
    obs = jnp.zeros((1, 1, 84, 84, 4), jnp.uint8)
    params = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), net.initial_state(1), obs, method=net.unroll))
    jaxpr = jax.make_jaxpr(lambda p: net.apply(
        p, net.initial_state(1), obs, method=net.unroll))(params).jaxpr
    assert len(_barriers(jaxpr)) == 2
