"""Feed-forward Q-networks: MLP / Nature-CNN torsos, dueling, noisy, C51.

One configurable ``QNetwork`` covers the feed-forward half of the driver's
capability list (BASELINE.json:7-9,11): vanilla DQN heads, dueling streams,
NoisyNet exploration and C51 distributional output. The recurrent (R2D2)
network lives in ``models/recurrent.py``.

TPU notes: convs/matmuls run in ``compute_dtype`` (bfloat16 on TPU) with
float32 params and float32 head outputs, keeping the MXU fed without losing
loss precision. All shapes are static; no data-dependent control flow.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dist_dqn_tpu.config import NetworkConfig

Array = jnp.ndarray


def _symmetric_uniform(scale: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -scale, scale)
    return init


class NoisyDense(nn.Module):
    """Factorized-Gaussian NoisyNet layer (Fortunato et al., 2018).

    w = mu_w + sigma_w * (f(eps_in) f(eps_out)^T), f(x) = sign(x) sqrt(|x|).
    Noise is drawn from the ``noise`` rng collection when ``add_noise`` is
    True; otherwise the layer is the deterministic mu-only affine map.
    """

    features: int
    sigma0: float = 0.5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array, *, add_noise: bool = False) -> Array:
        in_features = x.shape[-1]
        bound = 1.0 / math.sqrt(in_features)
        mu_w = self.param("mu_w", _symmetric_uniform(bound),
                          (in_features, self.features))
        mu_b = self.param("mu_b", _symmetric_uniform(bound), (self.features,))
        sigma_w = self.param(
            "sigma_w", nn.initializers.constant(self.sigma0 * bound),
            (in_features, self.features))
        sigma_b = self.param(
            "sigma_b", nn.initializers.constant(self.sigma0 * bound),
            (self.features,))

        w = mu_w
        b = mu_b
        if add_noise:
            key = self.make_rng("noise")
            k_in, k_out = jax.random.split(key)
            f = lambda e: jnp.sign(e) * jnp.sqrt(jnp.abs(e))
            eps_in = f(jax.random.normal(k_in, (in_features,)))
            eps_out = f(jax.random.normal(k_out, (self.features,)))
            w = w + sigma_w * (eps_in[:, None] * eps_out[None, :])
            b = b + sigma_b * eps_out
        y = jnp.dot(x.astype(self.dtype), w.astype(self.dtype))
        return (y + b.astype(self.dtype)).astype(jnp.float32)


# (features, kernel, stride) stacks for the named CNN torsos:
#   nature — the 84x84 Atari torso (Mnih et al., 2015)
#   small  — ~7x cheaper variant for dev boxes and fast pixel tests
CNN_TORSO_LAYERS = {
    "nature": ((32, 8, 4), (64, 4, 2), (64, 3, 1)),
    "small": ((16, 8, 4), (32, 4, 2)),
}


class CNNTorso(nn.Module):
    """Stacked VALID convs + flatten; ``layers`` holds one (features,
    kernel, stride) tuple per conv (named presets: CNN_TORSO_LAYERS)."""

    layers: Tuple[Tuple[int, int, int], ...] = CNN_TORSO_LAYERS["nature"]
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        # x: [B, 84, 84, C] float in [0, 1]
        x = x.astype(self.dtype)
        for features, kernel, stride in self.layers:
            x = nn.Conv(features, (kernel, kernel), strides=(stride, stride),
                        padding="VALID", dtype=self.dtype)(x)
            x = nn.relu(x)
        return x.reshape((x.shape[0], -1))


def NatureCNN(dtype: jnp.dtype = jnp.float32) -> CNNTorso:
    """The classic Atari torso as a CNNTorso preset (kept as the public
    name other modules/tests import)."""
    return CNNTorso(CNN_TORSO_LAYERS["nature"], dtype=dtype)


class MLPTorso(nn.Module):
    features: Sequence[int]
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        x = x.reshape((x.shape[0], -1)).astype(self.dtype)
        for f in self.features:
            x = nn.relu(nn.Dense(f, dtype=self.dtype)(x))
        return x


class QNetwork(nn.Module):
    """Configurable feed-forward Q-network.

    Output: [B, A] Q-values when ``num_atoms == 1``; otherwise
    [B, A, num_atoms] — C51 categorical logits by default (use ``atoms()``
    for the support and softmax expected-Q reduction), or raw quantile
    VALUES when ``quantile`` is set (reduce with a plain mean; softmax/
    atoms are meaningless there). ``q_values()`` does the right reduction
    for every head type — prefer it over reducing by hand.
    """

    num_actions: int
    torso: str = "nature"
    mlp_features: Tuple[int, ...] = (256, 256)
    hidden: int = 512
    dueling: bool = False
    noisy: bool = False
    num_atoms: int = 1
    v_min: float = -10.0
    v_max: float = 10.0
    # num_atoms > 1 selects the distributional head family: C51 categorical
    # logits over a fixed v_min..v_max support by default, or — with
    # ``quantile`` — QR-DQN quantile values (no fixed support; atoms() and
    # v_min/v_max are unused).
    quantile: bool = False
    compute_dtype: jnp.dtype = jnp.float32

    def atoms(self) -> Array:
        return jnp.linspace(self.v_min, self.v_max, self.num_atoms)

    def _head(self, name: str, features: int):
        if self.noisy:
            return NoisyDense(features, dtype=self.compute_dtype, name=name)
        return nn.Dense(features, dtype=self.compute_dtype, name=name)

    def _apply_head(self, layer, x, add_noise):
        if self.noisy:
            return layer(x, add_noise=add_noise)
        return layer(x).astype(jnp.float32)

    @nn.compact
    def __call__(self, obs: Array, *, add_noise: bool = False) -> Array:
        x = obs
        if x.dtype == jnp.uint8:
            x = x.astype(self.compute_dtype) / 255.0
        if self.torso in CNN_TORSO_LAYERS:
            x = CNNTorso(CNN_TORSO_LAYERS[self.torso],
                         dtype=self.compute_dtype)(x)
        elif self.torso == "mlp":
            x = MLPTorso(self.mlp_features, dtype=self.compute_dtype)(x)
        else:
            raise ValueError(f"unknown torso {self.torso!r}")
        if self.hidden:
            x = nn.relu(nn.Dense(self.hidden, dtype=self.compute_dtype)(x))

        a_out = self.num_actions * self.num_atoms
        adv = self._apply_head(self._head("advantage", a_out), x, add_noise)
        adv = adv.reshape((-1, self.num_actions, self.num_atoms))
        if self.dueling:
            val = self._apply_head(self._head("value", self.num_atoms),
                                   x, add_noise)
            val = val.reshape((-1, 1, self.num_atoms))
            q = val + adv - jnp.mean(adv, axis=1, keepdims=True)
        else:
            q = adv
        if self.num_atoms == 1:
            return q[..., 0]
        return q

    def q_values(self, obs: Array, *, add_noise: bool = False) -> Array:
        """Scalar Q-values [B, A] regardless of head type (for acting)."""
        out = self(obs, add_noise=add_noise)
        if self.num_atoms == 1:
            return out
        if self.quantile:
            # QR head: expected return is the mean of the quantile values.
            return jnp.mean(out, axis=-1)
        return jnp.sum(jax.nn.softmax(out, axis=-1) * self.atoms(), axis=-1)


class ImplicitQuantileNetwork(nn.Module):
    """IQN head (Dabney et al., 2018b): Z_tau(s, a) for sampled tau.

    The third distributional family next to C51 and QR-DQN. Instead of a
    fixed set of output quantiles, the network is CONDITIONED on quantile
    fractions tau ~ U(0, 1): a cosine embedding of tau is mixed
    (Hadamard) into the state features, so one set of parameters
    represents the full return distribution. TPU notes: the embedding is
    a [B*K, E] x [E, H] matmul and the heads are [B*K, H] x [H, A]
    matmuls — all MXU work, batch-flattened over the tau-sample axis; no
    gather/scatter, static shapes throughout.

    Methods:
      __call__(obs, taus=None)      -> [B, A, K] quantile values; with
        taus=None uses the fixed, deterministic acting fractions from
        ``act_taus()`` (K = num_tau_act).
      sample_quantiles(obs, num)    -> ([B, A, num], [B, num]) at fresh
        tau ~ U(0, 1) draws from the "tau" rng collection (training).
      q_values(obs)                 -> [B, A] mean over the acting
        fractions — with ``risk_cvar_eta`` < 1 this is CVaR_eta, a
        risk-averse policy that only averages the lower eta tail of the
        return distribution (risk-sensitive control comes free with IQN).

    NoisyNet heads are not supported (build_network rejects the combo);
    exploration is epsilon-greedy. ``add_noise`` is accepted and ignored
    so the module is call-compatible with QNetwork in the shared
    learner/actor/eval paths.
    """

    num_actions: int
    torso: str = "nature"
    mlp_features: Tuple[int, ...] = (256, 256)
    hidden: int = 512
    dueling: bool = False
    embed_dim: int = 64
    num_tau: int = 64          # N: online tau draws per loss term
    num_tau_target: int = 64   # N': target tau draws per loss term
    num_tau_act: int = 32
    risk_cvar_eta: float = 1.0
    compute_dtype: jnp.dtype = jnp.float32
    iqn: bool = True  # marker for make_learner's loss dispatch

    def act_taus(self) -> Array:
        """Deterministic acting fractions: num_tau_act midpoints of
        (0, risk_cvar_eta] — uniform over the full distribution at
        eta=1.0, the lower-tail CVaR_eta fractions otherwise."""
        k = self.num_tau_act
        mids = (jnp.arange(k, dtype=jnp.float32) + 0.5) / k
        return mids * self.risk_cvar_eta

    @nn.compact
    def __call__(self, obs: Array, *, taus: Array = None,
                 add_noise: bool = False) -> Array:
        del add_noise  # accepted for QNetwork call-compat; no noisy heads
        x = obs
        if x.dtype == jnp.uint8:
            x = x.astype(self.compute_dtype) / 255.0
        if self.torso in CNN_TORSO_LAYERS:
            x = CNNTorso(CNN_TORSO_LAYERS[self.torso],
                         dtype=self.compute_dtype)(x)
        elif self.torso == "mlp":
            x = MLPTorso(self.mlp_features, dtype=self.compute_dtype)(x)
        else:
            raise ValueError(f"unknown torso {self.torso!r}")
        if self.hidden:
            x = nn.relu(nn.Dense(self.hidden, dtype=self.compute_dtype)(x))

        if taus is None:
            taus = jnp.broadcast_to(self.act_taus()[None, :],
                                    (x.shape[0], self.num_tau_act))
        k = taus.shape[-1]
        # Cosine embedding phi(tau)_e = relu(W cos(pi * e * tau) + b),
        # e = 0..E-1, projected to the feature width and Hadamard-mixed.
        freqs = jnp.arange(self.embed_dim, dtype=jnp.float32)
        emb = jnp.cos(jnp.pi * freqs[None, None, :]
                      * taus[..., None].astype(jnp.float32))   # [B, K, E]
        emb = nn.relu(nn.Dense(x.shape[-1], dtype=self.compute_dtype,
                               name="tau_embed")(emb.astype(
                                   self.compute_dtype)))       # [B, K, H]
        z = x[:, None, :] * emb                                # [B, K, H]

        a_out = self.num_actions
        adv = nn.Dense(a_out, dtype=self.compute_dtype,
                       name="advantage")(z).astype(jnp.float32)  # [B, K, A]
        if self.dueling:
            val = nn.Dense(1, dtype=self.compute_dtype,
                           name="value")(z).astype(jnp.float32)  # [B, K, 1]
            q = val + adv - jnp.mean(adv, axis=-1, keepdims=True)
        else:
            q = adv
        return jnp.transpose(q, (0, 2, 1))                     # [B, A, K]

    def sample_quantiles(self, obs: Array, num: int,
                         *, example_ids: Array = None,
                         add_noise: bool = False):
        """([B, A, num] values, [B, num] taus) at fresh U(0, 1) draws.

        Each example's taus come from its OWN key — the draw key with
        the example's batch position folded in — so the draw is
        shard-invariant: example i gets identical taus whether the
        batch is whole on one device or row-sharded over a mesh, as
        long as the caller passes GLOBAL positions via ``example_ids``
        (the sharded learner offsets by ``axis_index * local_B``;
        default: local arange, which IS the global position in the
        unsharded case). This is what lets the IQN learner join the
        sharded-vs-single-device equivalence tests (rtol 2e-5; VERDICT round-3
        ask #8)."""
        key = self.make_rng("tau")
        if example_ids is None:
            example_ids = jnp.arange(obs.shape[0], dtype=jnp.uint32)
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            example_ids.astype(jnp.uint32))
        taus = jax.vmap(lambda k: jax.random.uniform(k, (num,)))(keys)
        return self(obs, add_noise=add_noise, taus=taus), taus

    def q_values(self, obs: Array, *, add_noise: bool = False) -> Array:
        """[B, A] expected (eta=1) or CVaR_eta (eta<1) action values."""
        return jnp.mean(self(obs, add_noise=add_noise), axis=-1)


def build_network(cfg: NetworkConfig, num_actions: int) -> nn.Module:
    """Build the Q-network for a config; recurrent if ``cfg.recurrent``
    (an LSTM of ``lstm_size``, or the core ``cfg.core`` names)."""
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    if cfg.core.kind not in ("lstm", "hybrid"):
        raise ValueError(f"unknown network.core.kind {cfg.core.kind!r}")
    if cfg.iqn:
        if cfg.recurrent or cfg.noisy or cfg.num_atoms > 1:
            raise ValueError(
                "the IQN head is feed-forward, epsilon-greedy and already "
                "distributional; unset lstm_size/noisy/num_atoms or iqn")
        if not 0.0 < cfg.risk_cvar_eta <= 1.0:
            raise ValueError(
                f"risk_cvar_eta must be in (0, 1], got "
                f"{cfg.risk_cvar_eta} — 1.0 is risk-neutral, smaller "
                "values average only the lower CVaR tail")
        return ImplicitQuantileNetwork(
            num_actions=num_actions, torso=cfg.torso,
            mlp_features=cfg.mlp_features, hidden=cfg.hidden,
            dueling=cfg.dueling, embed_dim=cfg.iqn_embed_dim,
            num_tau=cfg.iqn_tau_samples,
            num_tau_target=cfg.iqn_tau_target_samples,
            num_tau_act=cfg.iqn_tau_act,
            risk_cvar_eta=cfg.risk_cvar_eta, compute_dtype=dtype)
    if cfg.recurrent:
        if cfg.noisy or cfg.num_atoms > 1:
            raise ValueError(
                "noisy/distributional heads are not supported on the "
                "recurrent (R2D2) network; unset noisy/num_atoms or "
                "lstm_size / core.kind")
        if cfg.core.kind == "hybrid":
            if cfg.lstm_size:
                raise ValueError(
                    "network.core.kind=hybrid scans its own layers; unset "
                    "lstm_size")
            from dist_dqn_tpu.models.sequence_core import HybridQNetwork
            return HybridQNetwork(
                num_actions=num_actions, core=cfg.core, torso=cfg.torso,
                mlp_features=cfg.mlp_features, hidden=cfg.hidden,
                dueling=cfg.dueling, remat_torso=cfg.remat_torso,
                compute_dtype=dtype)
        from dist_dqn_tpu.models.recurrent import RecurrentQNetwork
        return RecurrentQNetwork(
            num_actions=num_actions, torso=cfg.torso,
            mlp_features=cfg.mlp_features, hidden=cfg.hidden,
            lstm_size=cfg.lstm_size, dueling=cfg.dueling,
            remat_torso=cfg.remat_torso, compute_dtype=dtype,
            lstm_dtype=(jnp.bfloat16 if cfg.lstm_dtype == "bfloat16"
                        else jnp.float32),
            lstm_unroll=cfg.lstm_unroll)
    return QNetwork(
        num_actions=num_actions, torso=cfg.torso,
        mlp_features=cfg.mlp_features, hidden=cfg.hidden,
        dueling=cfg.dueling, noisy=cfg.noisy, num_atoms=cfg.num_atoms,
        v_min=cfg.v_min, v_max=cfg.v_max, quantile=cfg.quantile,
        compute_dtype=dtype)
