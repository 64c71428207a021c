"""The state-space mixer (``models/sequence_core.py _Mamba2``) against the
form it had before PR 45 — one in-projection split as an activation, the
convolution over one joined ``[B, K-1+T, channels]`` array, a ``cumsum``,
the grouped norm over a ``[.., groups, width]`` view — kept HERE as the
fixture, and the hybrid network's parameter tree pinned at the preset's
widths. Toy widths, CPU."""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS, CoreConfig
from dist_dqn_tpu.models import sequence_core

F32 = jnp.float32
HIDDEN = 32
Q = 4
CORE = dataclasses.replace(
    CONFIGS["twotower_q"].network.core, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=8, n_groups=2, chunk_size=Q)


def _rms_norm_before(x, scale, eps, groups):
    x = x.astype(F32)
    grouped = x.reshape(x.shape[:-1] + (groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(x.shape) * scale


def _ssd_chunked_before(x, dt, a, b, c, seg, state, chunk, dtype):
    B, T, H, P = x.shape
    G, N = b.shape[2:]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    nc = (T + pad) // Q

    def chunks(v):
        return v.reshape((B, nc, Q) + v.shape[2:])

    seg = chunks(seg)
    dt = chunks(dt.astype(F32))
    xdt = (chunks(x.astype(F32)) * dt[..., None]).astype(dtype)
    xdt = xdt.reshape(B, nc, Q, G, H // G, P)
    b, c = chunks(b.astype(dtype)), chunks(c.astype(dtype))
    cs = jnp.cumsum(dt * a, axis=2).reshape(B, nc, Q, G, H // G)
    seg_in = jnp.concatenate(
        [jnp.zeros((B, 1), seg.dtype), seg[:, :-1, -1]], axis=1)
    same = seg[:, :, :, None] == seg[:, :, None, :]
    causal = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    allowed = jnp.logical_and(same, causal)[..., None, None]
    decay = jnp.exp(jnp.where(
        allowed, cs[:, :, :, None] - cs[:, :, None, :], -jnp.inf))
    cb = jnp.einsum("bzign,bzjgn->bzijg", c, b, preferred_element_type=F32)
    weights = (decay * cb[..., None]).astype(dtype)
    y = jnp.einsum("bzijgh,bzjghp->bzighp", weights, xdt,
                   preferred_element_type=F32)
    to_end = jnp.where((seg == seg[:, :, -1:])[..., None, None],
                       jnp.exp(cs[:, :, -1:] - cs), 0.0)
    added = jnp.einsum("bzjghp,bzjgn->bzghpn",
                       (xdt * to_end[..., None]).astype(dtype), b,
                       preferred_element_type=F32)
    kept = jnp.where((seg[:, :, -1] == seg_in)[..., None, None],
                     jnp.exp(cs[:, :, -1]), 0.0)

    def hand_on(h, inputs):
        kept_z, added_z = inputs
        return h * kept_z[..., None, None] + added_z, h

    state = state.reshape(B, G, H // G, P, N)
    state, entering = jax.lax.scan(
        hand_on, state, (jnp.moveaxis(kept, 1, 0), jnp.moveaxis(added, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)
    from_in = jnp.where((seg == seg_in[:, :, None])[..., None, None],
                        jnp.exp(cs), 0.0)
    y = y + from_in[..., None] * jnp.einsum(
        "bzign,bzghpn->bzighp", c, entering.astype(dtype),
        preferred_element_type=F32)
    return y.reshape(B, nc * Q, H, P)[:, :T], state.reshape(B, H, P, N)


class _Mamba2Before(nn.Module):
    """``_Mamba2.__call__`` as PR 44 left it: same parameters by name,
    shape and order of creation."""

    cfg: CoreConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, u, seg, carry):
        cfg = self.cfg
        H, P, G, N, K = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                         cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel)
        inner, hidden = H * P, u.shape[-1]
        channels = inner + 2 * G * N
        B, T = u.shape[:2]
        zeros, ones = nn.initializers.zeros, nn.initializers.ones
        w_in = self.param("in_proj", zeros, (hidden, inner + channels + H))
        conv_w = self.param("conv_kernel", zeros, (K, channels))
        conv_b = self.param("conv_bias", zeros, (channels,))
        dt_bias = self.param("dt_bias", zeros, (H,))
        a_log = self.param("A_log", zeros, (H,))
        d_skip = self.param("D", ones, (H,))
        norm_w = self.param("norm", ones, (inner,))
        w_out = self.param("out_proj", zeros, (inner, hidden))
        tail, state = carry
        proj = jnp.dot(u.astype(self.dtype), w_in.astype(self.dtype),
                       preferred_element_type=F32)
        z, xbc, dt = jnp.split(proj, [inner, inner + channels], axis=-1)
        padded = jnp.concatenate([tail.astype(F32), xbc], axis=1)
        seg_padded = jnp.concatenate(
            [jnp.zeros((B, K - 1), seg.dtype), seg], axis=1)
        conv = conv_b.astype(F32)
        for d in range(K):
            lo = K - 1 - d
            tap = jnp.where((seg_padded[:, lo:lo + T] == seg)[..., None],
                            padded[:, lo:lo + T], 0.0)
            conv = conv + tap * conv_w[K - 1 - d]
        new_tail = jnp.where((seg_padded[:, T:] == seg[:, -1:])[..., None],
                             padded[:, T:], 0.0)
        xbc = jax.nn.silu(conv)
        x, b, c = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        x = x.reshape(B, T, H, P)
        y, state = _ssd_chunked_before(
            x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
            b.reshape(B, T, G, N), c.reshape(B, T, G, N), seg, state,
            cfg.chunk_size, self.dtype)
        y = (y + d_skip[:, None] * x).reshape(B, T, inner)
        y = _rms_norm_before(y * jax.nn.silu(z), norm_w, cfg.norm_eps, G)
        out = jnp.dot(y.astype(self.dtype), w_out.astype(self.dtype),
                      preferred_element_type=F32)
        return out, (new_tail, state)


def _resets(where: str, T: int) -> np.ndarray:
    """``[3, T]`` bool: lane 0 opens episodes where the case says (as far
    as T reaches), lane 1 never, lane 2 one step later than lane 0."""
    steps = {"none": [], "step0": [0],
             "inside": [Q + 1, Q + 2] if T > Q + 2 else [T // 2],
             "chunk_first": [Q, 2 * Q] if T > Q else [0]}[where]
    reset = np.zeros((3, T), bool)
    for t in steps:
        reset[0, min(t, T - 1)] = True
        reset[2, min(t + 1, T - 1)] = True
    return reset


# the two forms differ in the order of float32 sums (the running sum of
# dt a is a product with the lower triangle, the group's mean square a
# product with its indicator); with bfloat16 operands a sum that lands on
# the other side of a rounding moves an operand by 2^-8 of itself
TOLERANCE = {"float32": 1e-5, "bfloat16": 1e-2}
CASES = [("float32", T, where) for T in (1, Q - 1, Q, 2 * Q + 3)
         for where in ("step0", "inside", "chunk_first", "none")]
CASES += [("bfloat16", T, "inside") for T in (1, Q - 1, Q, 2 * Q + 3)]


@pytest.mark.parametrize("dtype,T,where", CASES)
def test_the_mixer_is_the_mixer_it_was(dtype, T, where):
    """Output, look-back and state handed on, and the gradient to every
    parameter and to ``u``: what the mixer of PR 44 gives from the same
    parameters and a non-empty carry."""
    new = sequence_core._Mamba2(CORE, jnp.dtype(dtype))
    old = _Mamba2Before(CORE, jnp.dtype(dtype))
    B = 3
    keys = jax.random.split(jax.random.PRNGKey(T), 6)
    u = jax.random.normal(keys[0], (B, T, HIDDEN))
    channels = 4 * 8 + 2 * 2 * 8
    carry = (jax.random.normal(keys[1], (B, CORE.conv_kernel - 1, channels)),
             jax.random.normal(keys[2], (B, 4, 8, 8)))
    seg = sequence_core.segments(jnp.asarray(_resets(where, T)))
    params = new.init(keys[3], u, seg, carry)
    assert (jax.tree.map(jnp.shape, params)
            == jax.tree.map(jnp.shape, old.init(keys[3], u, seg, carry)))
    pull = jax.random.normal(keys[4], (B, T, HIDDEN))

    def outputs_and_gradients(module):
        def loss(params, u):
            out, (tail, state) = module.apply(params, u, seg, carry)
            return (jnp.sum(out * pull) + jnp.sum(jnp.sin(state))
                    + jnp.sum(jnp.sin(tail))), (out, tail, state)
        (_, outs), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(params, u)
        return jax.tree.leaves((outs, grads))

    for got, want in zip(outputs_and_gradients(new),
                         outputs_and_gradients(old)):
        scale = max(1.0, float(jnp.max(jnp.abs(want))))
        np.testing.assert_allclose(got, want, rtol=TOLERANCE[dtype],
                                   atol=TOLERANCE[dtype] * scale)


M_LAYER = {"A_log": (64,), "D": (64,), "conv_bias": (6144,),
           "conv_kernel": (4, 6144), "dt_bias": (64,),
           "in_proj": (2688, 10304), "norm": (4096,),
           "out_proj": (4096, 2688)}
E_LAYER = {"e_score_correction_bias": (128,),
           "experts_down": (8, 1856, 2688), "experts_up": (2688, 8, 1856),
           "router": (2688, 128), "shared_down": (3712, 2688),
           "shared_up": (2688, 3712)}
A_LAYER = {"k_proj": (2688, 256), "o_proj": (4096, 2688),
           "q_proj": (2688, 4096), "v_proj": (2688, 256)}


def test_the_presets_parameter_tree_by_name_and_shape():
    """``twotower_q`` at its published widths, shapes only (no memory):
    every leaf of the nine layers by name and shape, as checkpoints and
    ``perf/reference/twotower_float32.py`` read them; 587.41 M in all."""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network

    cfg = CONFIGS["twotower_q"]
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    obs = jax.ShapeDtypeStruct((1, 1) + tuple(env.observation_shape),
                               env.observation_dtype)
    tree = jax.eval_shape(
        lambda key, carry, obs: net.init(key, carry, obs, method=net.unroll),
        jax.random.PRNGKey(0), jax.eval_shape(lambda: net.initial_state(1)),
        obs)["params"]
    assert set(tree) == {"torso", "core", "advantage", "value"}
    core = jax.tree.map(lambda leaf: leaf.shape, tree["core"])
    assert cfg.network.core.pattern == "MEMEM*EME"
    want = {f"layer_{i}": {
        "norm": (2688,),
        "mixer": {"M": M_LAYER, "E": E_LAYER, "*": A_LAYER}[kind]}
        for i, kind in enumerate(cfg.network.core.pattern)}
    assert core == dict(want, norm_f=(2688,))
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(tree))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(tree)) == 587_412_135
