"""FLOPs of one learner grad step, from shapes alone.

Counted as 2 x multiply-accumulates of what the forward and backward passes
REQUIRE: the online pass on ``obs`` forward and backward, the target pass on
``next_obs`` forward, and (double-Q) the online pass on ``next_obs``
forward. The backward pass costs two forwards (one for the weights'
gradient, one for the inputs') in every layer but the first, whose input is
data and needs no gradient. Elementwise work, the optimizer and the loss are
left out (under 1% at these widths).
"""
from __future__ import annotations

from typing import Sequence, Tuple

NATURE_CONVS = ((32, 8, 4), (64, 4, 2), (64, 3, 1))   # features, kernel, stride


def cnn_layer_macs(obs_shape: Tuple[int, int, int],
                   convs: Sequence[Tuple[int, int, int]], hidden: int,
                   num_actions: int, dueling: bool) -> list:
    """Multiply-accumulates per sample of each layer, first to last."""
    h, w, c = obs_shape
    macs = []
    for features, kernel, stride in convs:
        h = (h - kernel) // stride + 1
        w = (w - kernel) // stride + 1
        macs.append(h * w * kernel * kernel * c * features)
        c = features
    flat = h * w * c
    macs.append(flat * hidden)
    macs.append(hidden * (num_actions + (1 if dueling else 0)))
    return macs


def grad_step_flops(batch_size: int, obs_shape=(84, 84, 4),
                    convs=NATURE_CONVS, hidden: int = 512,
                    num_actions: int = 6, dueling: bool = False,
                    double_dqn: bool = True) -> float:
    macs = cnn_layer_macs(tuple(obs_shape), convs, hidden, num_actions,
                          dueling)
    forward = sum(macs)
    backward = 2 * forward - macs[0]
    forwards = 3 if double_dqn else 2
    return 2.0 * batch_size * (forwards * forward + backward)
