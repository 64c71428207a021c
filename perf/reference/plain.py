"""What the plain references share: layers and the optimizer written out in
float32 ``jax.numpy``, nothing of the program. A reference module imports
what its network is made of and adds its own loss; callers trace these under
``jax.default_matmul_precision("highest")``.

A convolution is written as what it is: the windows of the input laid side
by side (strided slices), times the kernel as one matrix — for the compiler,
not for the mathematics: XLA's float32 convolution backward at "highest"
precision takes two minutes to compile for a TPU, these matmuls seconds.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

# Strides of the VALID convs of each torso; the kernels' own shapes come
# from the parameter tree, only the strides are not stored there.
CONV_STRIDES = {"nature": (4, 2, 1), "small": (4, 2)}
# (features, kernel, stride) per conv, for the FLOP counts alone.
CONVS = {"nature": ((32, 8, 4), (64, 4, 2), (64, 3, 1)),
         "small": ((16, 8, 4), (32, 4, 2))}
ADAM_B1, ADAM_B2 = 0.9, 0.999


def dense(p: Dict, x):
    """``x @ kernel`` (+ ``bias`` where the layer has one)."""
    out = x @ p["kernel"].astype(jnp.float32)
    return out + p["bias"].astype(jnp.float32) if "bias" in p else out


def conv_valid(x, kernel, stride: int):
    """VALID convolution of NHWC ``x`` with an HWIO ``kernel``: the
    ``kh * kw`` strided window slices side by side in the kernel's own
    (row, column, channel) order, times the kernel as a matrix."""
    kh, kw, cin, cout = kernel.shape
    ho = (x.shape[1] - kh) // stride + 1
    wo = (x.shape[2] - kw) // stride + 1
    windows = jnp.concatenate(
        [x[:, i:i + stride * (ho - 1) + 1:stride,
           j:j + stride * (wo - 1) + 1:stride, :]
         for i in range(kh) for j in range(kw)], axis=-1)
    return windows @ kernel.reshape(kh * kw * cin, cout)


def cnn_torso(torso: Dict, x, strides):
    """The stacked VALID convs of ``CNNTorso_0`` with relu, flattened."""
    for i, stride in enumerate(strides):
        conv = torso[f"Conv_{i}"]
        x = conv_valid(x, conv["kernel"].astype(jnp.float32), stride)
        x = jax.nn.relu(x + conv["bias"].astype(jnp.float32))
    return x.reshape((x.shape[0], -1))


def mlp_torso(torso: Dict, x):
    """The dense layers of ``MLPTorso_0`` with relu, on flattened input."""
    x = x.reshape((x.shape[0], -1))
    for i in range(len(torso)):
        x = jax.nn.relu(dense(torso[f"Dense_{i}"], x))
    return x


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    """(clipped gradient, its norm before the clip, the factor applied):
    the optimizer's global-norm clip; ``max_norm`` 0 leaves it off."""
    norm = global_norm(grads)
    if not max_norm:
        return grads, norm, 1.0
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree.map(lambda g: g * scale, grads), norm, scale


def adam_delta(grads, adam_mu, adam_nu, adam_count, hp):
    """The parameter change Adam makes from moments ``(mu, nu)`` after
    ``count`` steps when handed ``grads`` (already clipped); ``hp`` carries
    ``learning_rate`` and ``adam_eps``."""
    count = adam_count.astype(jnp.float32) + 1.0
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                      adam_mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      adam_nu, grads)
    return jax.tree.map(
        lambda m, v: -hp.learning_rate * (m / (1 - ADAM_B1 ** count))
        / (jnp.sqrt(v / (1 - ADAM_B2 ** count)) + hp.adam_eps),
        mu, nu)
