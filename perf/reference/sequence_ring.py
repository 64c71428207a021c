"""Plain reference of a prioritized sequence replay over a ring of steps.

What a sequence learner's replay guarantees, written from the steps
themselves and not from any ring arithmetic: every step is known by its
ABSOLUTE index (the count of steps written before it), and the ring of
``slots`` time slices x ``lanes`` is nothing but the rule that the newest
``slots`` steps are the stored ones and that step ``a`` lives in slot ``a
mod slots``. Numpy only; it imports nothing of the program.

The rules (R2D2, Kapturowski et al. 2019, over a ring of single steps):

- a window is ``length`` consecutive steps of one lane, known by its first
  step; windows start every ``stride`` steps;
- a start is alive once its whole window is written and until its first
  step is overwritten; where frames are stored once (dedup, ``stack`` > 1)
  the oldest ``stack - 1`` stored steps start no window, because the frames
  before them are gone;
- a fresh start carries the largest priority written so far (1 at first);
  a write-back sets ``|p| + eps`` on a start that is alive and leaves a dead
  one dead;
- a draw of ``n`` windows is stratified: draw ``k`` falls in the ``k``-th
  of ``n`` equal shares of the total mass ``sum(priority ** alpha)``, cells
  counted slot by slot and lane by lane; its importance weight is ``(alive
  * P) ** -beta`` over the largest weight of the draw;
- the observation at step ``q`` is the stack of the frames ``q - stack + 1
  .. q``, where a frame from before the episode's first step is replaced by
  that first frame (an episode opens on its first frame repeated);
  ``reset`` at a position says that the step before it ended an episode,
  and never at a window's first position; the recurrent state handed to the
  learner is the one stored with the window's first step.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def seeded_steps(seed: int, steps: int, lanes: int, num_actions: int,
                 end_share: float = 0.03) -> Dict[str, np.ndarray]:
    """The small fields of ``steps`` x ``lanes`` steps, from the seed: the
    actions, rewards, and where episodes end (about ``end_share`` of the
    steps, split into terminations and truncations, so that a window of a
    hundred steps holds a few, some of them within a stack's reach of each
    other). Frames and recurrent states are large and made by the caller."""
    rng = np.random.default_rng([seed, 0x52494E47])
    done = rng.random((steps, lanes)) < end_share
    terminated = done & (rng.random((steps, lanes)) < 0.5)
    return {"action": rng.integers(0, num_actions, (steps, lanes)).astype(
                np.int32),
            "reward": rng.standard_normal((steps, lanes)).astype(np.float32),
            "terminated": terminated, "truncated": done & ~terminated}


def episode_first_step(done: np.ndarray) -> np.ndarray:
    """``[steps, lanes]``: the absolute index of the first step of the
    episode each step belongs to (step 0 opens one; a ``done`` at step j
    makes j + 1 the next first step)."""
    steps = done.shape[0]
    opens = np.zeros_like(done)
    opens[0] = True
    opens[1:] = done[:-1]
    index = np.arange(steps)[:, None]
    return np.maximum.accumulate(np.where(opens, index, 0), axis=0)


def alive_starts(written: int, slots: int, lanes: int, length: int,
                 stride: int, stack: int) -> Tuple[np.ndarray, np.ndarray]:
    """(``alive``, ``drawable``), both ``[slots, lanes]`` bool by slot: the
    starts whose window is whole and not overwritten, and those of them
    that a draw may return (context stored)."""
    stored_from = max(written - slots, 0)
    a = np.arange(stored_from, written)
    alive = (a % stride == 0) & (a + length <= written)
    drawable = alive & (a >= stored_from + max(stack - 1, 0))
    planes = []
    for flags in (alive, drawable):
        plane = np.zeros((slots, lanes), bool)
        plane[a[flags] % slots] = True
        planes.append(plane)
    return planes[0], planes[1]


def absolute_step(slot: np.ndarray, written: int, slots: int) -> np.ndarray:
    """The absolute index of the step a slot holds after ``written`` steps:
    the newest step congruent to it."""
    newest = written - 1
    return newest - ((newest - slot) % slots)


def window_fields(steps: Dict[str, np.ndarray], start: np.ndarray,
                  lane: np.ndarray, length: int, stack: int
                  ) -> Dict[str, np.ndarray]:
    """What a draw of the windows at absolute ``start`` steps of ``lane``
    has to hold, time-major ``[length, n]``: the small fields, and
    ``frame_of`` ``[length, n, stack]``, the absolute step whose frame is
    each channel of each position's observation (oldest channel first)."""
    done = steps["terminated"] | steps["truncated"]
    q = start[None, :] + np.arange(length)[:, None]           # [L, n]
    lanes = np.broadcast_to(lane[None, :], q.shape)
    first = episode_first_step(done)[q, lanes]
    back = np.arange(max(stack, 1) - 1, -1, -1)                # oldest first
    frame_of = np.maximum(q[..., None] - back, first[..., None])
    ended = done[q, lanes]
    return {"action": steps["action"][q, lanes],
            "reward": steps["reward"][q, lanes],
            "done": ended,
            "reset": np.concatenate([np.zeros_like(ended[:1]), ended[:-1]]),
            "frame_of": frame_of}


def write_back(priorities: np.ndarray, largest: np.float32,
               slot: np.ndarray, lane: np.ndarray, new: np.ndarray,
               eps: float) -> Tuple[np.ndarray, np.float32]:
    """The priority plane and the largest priority after a write-back of
    ``new`` at distinct cells (float32, as stored)."""
    written = np.where(priorities[slot, lane] > 0,
                       np.abs(new.astype(np.float32)) + np.float32(eps),
                       np.float32(0.0)).astype(np.float32)
    out = priorities.copy()
    out[slot, lane] = written
    return out, np.maximum(np.float32(largest), written.max())


def strata_missed(mass: np.ndarray, slot: np.ndarray, lane: np.ndarray,
                  slack: float = 1e-4) -> int:
    """How many of the ``n`` draws fall outside their stratum: draw ``k``
    must sit on a cell whose span of the running total of ``mass`` (cells
    counted slot by slot, lane by lane) meets ``[k/n, (k+1)/n]`` of the
    whole, with ``slack`` of the whole for the program's float32 sums; a
    draw on a cell without mass misses."""
    flat = mass.astype(np.float64).reshape(-1)
    upper = np.cumsum(flat)
    total = upper[-1]
    cell = slot.astype(np.int64) * mass.shape[1] + lane
    lower = upper[cell] - flat[cell]
    n = len(cell)
    k = np.arange(n)
    inside = ((flat[cell] > 0)
              & (upper[cell] >= (k / n - slack) * total)
              & (lower <= ((k + 1) / n + slack) * total))
    return int(n - inside.sum())


def importance(mass: np.ndarray, drawable: np.ndarray, slot: np.ndarray,
               lane: np.ndarray, beta: float) -> np.ndarray:
    """``(alive * P) ** -beta`` of each drawn cell over the draw's
    largest (float64)."""
    flat = mass.astype(np.float64)
    share = flat[slot, lane] / flat.sum()
    with np.errstate(divide="ignore", invalid="ignore"):
        weights = (max(float(drawable.sum()), 1.0) * share) ** -beta
        return weights / weights.max()
