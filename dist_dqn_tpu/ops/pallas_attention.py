"""Pallas TPU kernels: the learner's rotary attention over a long window
(``models/sequence_core.py _RotaryAttention``, layers ``F`` and ``W``) with
its scores kept in VMEM, forward and backward, and its queries rotated on
their way into the kernels' layout; and acting's one query a head over the
float32 ring where it lies (``decode``, at the end).

The plain path (``_RotaryAttention.blockwise``) makes every block's scores
in HBM: written in float32, read back for the mask and the softmax, written
again in bfloat16 for the weighted values, and the same again in the
backward (``PERF.md`` §5, PR 46: 357 of ``laguna_q.preset``'s 491 ms grad
step). Here a tile of scores is made in VMEM, masked, soft-maxed online in
float32 (running maximum, running sum, a float32 accumulator), multiplied
into the values and never written out; key blocks a query block cannot see
by its place in the window are not visited at all. ``blockwise`` stays: it is
what runs off the chip, and the oracle ``tests/test_pallas_attention.py``
holds these kernels to.

**The mask rule** is ``blockwise``'s, and it is DATA, not array indices. The
keys are ``[ring || this call's]`` (``S = history + T``); query and key each
carry a position (steps since the episode opened) and a segment (episodes
opened so far in this call; the ring lies in segment 0; an invalid key —
an empty ring slot, padding — has a segment no query has, -1). Query t sees
key s where ``k_seg[s] == q_seg[t]`` and ``0 <= q_position[t] -
k_position[s]``, in a ``W`` layer also ``< window``. A reset inside a window
restarts positions; one inside the burn-in leaves invalid keys in front of
the ring's valid ones; an ``F`` layer's ring lies slot by slot, so one that
acting has wrapped is not in the order of its positions. The kernels build
the mask of a tile from those four int32 vectors.

**What is static** is which key blocks a query block visits (``key_ranges``):
a query block starting at new step ``lo`` reads keys ``[0, history + lo +
bq)`` in an ``F`` layer and ``[history + lo - window + 1, history + lo + bq)``
in a ``W`` layer — ``blockwise``'s own rule at its block of 512. The ``W``
range counts on what ``_RotaryAttention.window_keys`` hands over: a ``W``
layer's keys in the order of time, so that between a query and a key of its
segment the distance of the indices IS the distance of the positions,
whatever the ring's state. The ranges ride in as scalar-prefetch tables: the
grid's innermost axis is as long as the longest range, a step past a block's
range does nothing and re-reads nothing (its index map stays on the last
block it read).

**Both kernels work on the TRANSPOSED tile of scores, ``[bk, bq]``, one
query head at a time** (a Python loop over the ``G = heads / KV`` heads of a
KV head inside the step, so a K/V tile is loaded once for all of them and the
mask, which no head changes, is built once a tile). What is kept a QUERY —
the running maximum and sum, the log-sum-exp, ``di`` — is then a row ``[1,
bq]``, stored as it lies (``[B, KV, G, T]``, nothing replicated over lanes),
broadcast over a tile's sublanes at no cost, and reduced over the keys by
plain elementwise passes. The first forward here folded the heads into the rows of
a ``[G * bq, bk]`` tile instead: its per-query statistics were columns, a
lane reduction and a column of updates a row of queries in every step, and
it ran at 44% of the MXU's rate where this one reaches 53% and the backward
81% (``PERF.md`` §6, PR 47).

**Forward** (``rotary_attention_forward``): grid ``(B, KV head, query block,
key block)``, the key axis innermost and sequential. A head's step: ``scores
= k q^T`` ``[bk, bq]``, the mask, the running maximum and sum over the keys,
``p = exp(scores - max)``, and the accumulator ``[D, bq]`` rescaled by a row
and added ``v^T p`` (``v`` comes in transposed, so no tile is turned inside
the loop); the last step divides, turns the ``[D, bq]`` accumulator once and
writes ``out [B, KV, G, T, D]`` float32 and the log-sum-exp ``[B, KV, G,
T]``. A key block in a query block's range that holds no valid key at all
(the empty ring in front of a burn-in) is fetched and passed over — a test
of one scalar a step, on top of the static ranges.

**Backward** (``rotary_attention_backward``, under ``jax.custom_vjp``; the
residuals are ``q, k, v``, the output and the log-sum-exp): ONE pass, grid
``(B, KV head, key block, query block)``. The probabilities are made again
from the log-sum-exp; ``dk`` and ``dv`` accumulate in their output block over
the query blocks and the group's heads; ``dq`` accumulates in an output block
that holds the whole ``[G, T, D]`` of one (lane, KV head) in VMEM across the
two inner axes (6.3 MB float32 at 8 x 1,536 x 128; ``attend`` refuses a
window whose ``dq`` would not fit). One pass makes the scores and their
exponents once where a ``dq`` kernel beside a ``dk, dv`` kernel makes them
twice.

Precision: operands in the core's compute type (bfloat16 in the presets),
every product accumulated in float32; scores, maximum, exponent, sum and
the rescaling in float32; the probabilities are cast to the operands' type
only as the operand of the values product — ``blockwise``'s precision. The
``D ** -0.5`` is folded into ``q`` in float32 before its cast.

**Tiles**: one shape for both kinds and both passes; see ``TILES``.

**The queries' road in and out** (``rotary_embed_forward`` /
``rotary_embed_backward``; ``attend``'s ``rotary``). ``sequence_core.rotate``
writes its rotated halves as two arrays whose minor dimension is half the
rotary dims — 64 of a tile's 128 lanes in a ``W`` layer, 32 in an ``F``
layer, whose other 64 dims become a third array — and this compiler does so
for every form that slices the minor dimension there, whatever the algebra
around it: 41 ms of the ``laguna_q`` preset's grad step at a quarter of the
HBM's rate, with further whole-array passes to join, scale, cast and
transpose them and to bring ``dq`` back (``PERF.md`` §5, PR 47). The two
kernels here are that road in ONE pass each way over whole tiles: a block is
the ``G`` heads of one KV head, which lie side by side along the lanes of
``q_proj``'s step-major output, so a head is a tile-aligned slice and the
kernels' ``[B, KV, G, T, D]`` is written (or read) directly; the halves meet
in VMEM by a lane rotation (``pltpu.roll``) against full-width tables
(``wide_tables``). Float32 throughout, the one cast where ``kv_major`` casts
and the backward's rounding where ``_attend_bwd`` rounds; equal to ``rotate``
+ ``kv_major`` to the last bit (``tests/test_pallas_rotary.py``). ``rotate``
stays: the keys' path (an eighth of the bytes, cached rotated in float32),
acting's, every other backend's, and these kernels' oracle.

**Acting** (``rotary_attention_decode``; ``decode``). A lane's acting step
has ONE query a head and a ring of float32 keys and values ``[B, S, KV, D]``
(1.34 GB for the ``smallthinker_q`` preset's 16 lanes). The plain ``attend``
casts every ring to the compute type in front of its two products: the ring
read, a copy half its size written and read again, every step (``PERF.md``
§6, PR 51: 2.65 of 5.45 ms). Here the ring is read once: grid ``(lane, block
of slots)``, the blocks of a lane in sequence. A block is ``[slots * KV, D]``
of the ring's own bytes (``[B, S, KV, D]`` seen as ``[B, S * KV, D]``: a
bitcast, row ``s * KV + h``), so all KV heads ride in one instance and
nothing is transposed or copied in front. The queries of all heads, a KV
head's ``G`` padded to the 8 sublanes of a tile (``[KV * 8, D]``), meet all
the block's rows in one product ``[KV * 8, slots * KV]``; a score counts
where its row's KV head is its column's and its slot lies below the lane's
``count``; the others are masked, their probabilities are exactly 0 and add
nothing. The block is rounded to the operands' type in VMEM — the same
round-to-nearest ``astype`` — scores, running maximum, sum and the ``[KV *
8, D]`` accumulator are float32 (the forward kernel's scheme at one query a
head), and the last block divides. Slots at or past ``count`` are masked,
NOT skipped: every block is fetched whatever the lane's position.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray
F32 = jnp.float32

FORWARD_NAME = "rotary_attention_forward"    # findable in HLO text, traces
BACKWARD_NAME = "rotary_attention_backward"
EMBED_FORWARD_NAME = "rotary_embed_forward"
EMBED_BACKWARD_NAME = "rotary_embed_backward"
DECODE_NAME = "rotary_attention_decode"
NEG = -1e30         # a masked score; finite, so that NEG - NEG is 0, not NaN
INVALID_KEY = -1    # the segment of a key no query may see
PADDING_QUERY = -2  # the segment of a padding query: it sees no key at all
_LANES = 128
# VMEM a kernel may be granted (a v5e has 128 MiB; Mosaic's default scope is
# 16): the backward's resident dq, twice (output blocks are double-buffered),
# plus the tiles and the temporaries of one step.
_VMEM_STEP = 24 << 20
_VMEM_MOST = 100 << 20
_VMEM_LEAST = 16 << 20     # Mosaic's default scope


class Tiles(NamedTuple):
    """Queries and keys a tile, in either pass."""

    bq: int
    bk: int


#: 512 x 512 for both kinds of layer and both passes, from three sweeps on a
#: v5e (``scripts/attention_sweep.py``; ``docs/records/pr47/``, ``PERF.md``
#: §6 PR 47): a step has a cost the tile does not shrink (its queries'
#: statistics, the accumulator's rescaling, the grid step), so at the
#: preset's shapes the larger tile wins although it reads 2.0x a window
#: layer's band where 128 x 256 reads 1.25x — forward ``W`` 1.96 ms against
#: 2.42, ``F`` 2.28 against 3.48; backward 3.21 / 3.50 against 4.23 / 5.61;
#: 256 x 256 ties it in ``W`` (1.93) and loses in ``F`` (2.59) and in the
#: backwards (3.22 / 4.23); 1,024 keys a tile read too much of the band.
TILES = Tiles(512, 512)


class _Spec(NamedTuple):
    """What is static in one call (hashable: ``custom_vjp``'s
    ``nondiff_argnums``)."""

    steps: int                  # T, this call's steps before padding
    history: int                # ring slots in front of them
    window: Optional[int]       # ``W``: the sliding window; ``F``: None
    tiles: Tiles
    interpret: bool


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def key_ranges(steps: int, history: int, window: Optional[int], bq: int,
               bk: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(first, count) [query blocks]`` int32: the key blocks of ``bk`` keys
    a block of ``bq`` queries visits, ``first .. first + count - 1``, among
    the ``ceil((history + steps) / bk)`` there are. A ``W`` layer's keys lie
    in the order of time (``_RotaryAttention.window_keys``): the query at new
    step t sees no key in front of index ``history + t - window + 1``."""
    first, count = [], []
    for lo in range(0, steps, bq):
        start = 0 if window is None else max(0, history + lo - window + 1)
        stop = min(history + lo + bq, history + steps)
        first.append(start // bk)
        count.append(_cdiv(stop, bk) - start // bk)
    return np.asarray(first, np.int32), np.asarray(count, np.int32)


def query_ranges(steps: int, history: int, window: Optional[int], bq: int,
                 bk: int) -> Tuple[np.ndarray, np.ndarray]:
    """``key_ranges`` read the other way, for the backward's grid: the query
    blocks ``first .. first + count - 1`` that visit each key block."""
    k_first, k_count = key_ranges(steps, history, window, bq, bk)
    blocks = np.arange(_cdiv(history + steps, bk))[:, None]
    visits = (k_first <= blocks) & (blocks < k_first + k_count)
    first, count = visits.argmax(axis=1), visits.sum(axis=1)
    # a band and a triangle: the visitors of a key block lie side by side
    assert (count > 0).all() and all(
        visits[b, f:f + c].all() for b, (f, c) in enumerate(zip(first, count)))
    return first.astype(np.int32), count.astype(np.int32)


def fitted(tiles: Tiles, steps: int, history: int) -> Tiles:
    """``tiles`` no larger than the call (in whole lanes of 128)."""
    t, s = (_cdiv(n, _LANES) * _LANES for n in (steps, history + steps))
    return Tiles(min(tiles.bq, t), min(tiles.bk, s))


def key_block_census(steps: int, history: int, window: Optional[int],
                     tiles: Tiles = TILES) -> Tuple[int, int]:
    """``(visited, skipped)`` key blocks of the forward grid of one (lane, KV
    head): together the rectangle query blocks x key blocks."""
    tiles = fitted(tiles, steps, history)
    _, count = key_ranges(steps, history, window, tiles.bq, tiles.bk)
    visited = int(count.sum())
    return visited, len(count) * _cdiv(history + steps, tiles.bk) - visited


def _visible(q_position, q_seg, k_position, k_seg, window):
    """The mask of a tile from the marks of its queries and keys, either way
    round (queries a column and keys a row, or the reverse)."""
    below = q_position - k_position
    see = jnp.logical_and(k_seg == q_seg, below >= 0)
    return see if window is None else jnp.logical_and(see, below < window)


_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def _forward_kernel(first_ref, count_ref, live_ref, q_ref, k_ref, vt_ref,
                    q_mark_ref, k_mark_ref, out_ref, lse_ref, m_ref, l_ref,
                    acc_ref, *, window):
    i, j = pl.program_id(2), pl.program_id(3)
    G = q_ref.shape[0]
    block = first_ref[i] + jnp.minimum(j, count_ref[i] - 1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a block in the range whose keys are all invalid (the empty ring in
    # front of a burn-in) is fetched and passed over
    @pl.when(jnp.logical_and(j < count_ref[i],
                             live_ref[pl.program_id(0), block] > 0))
    def _():
        k, vt = k_ref[...], vt_ref[...]
        see = _visible(q_mark_ref[0:1, :], q_mark_ref[1:2, :],
                       k_mark_ref[:, 0:1], k_mark_ref[:, 1:2], window)
        for g in range(G):
            scores = jnp.where(see, jax.lax.dot_general(
                k, q_ref[g], _NT, preferred_element_type=F32), NEG)  # [bk, bq]
            m_old = m_ref[g:g + 1, :]
            m_new = jnp.maximum(m_old, jnp.max(scores, axis=0, keepdims=True))
            keep = jnp.exp(m_old - m_new)
            # a query that has seen nothing yet reads 1 everywhere: the first
            # key it does see (every query sees itself) sets ``keep`` to 0
            p = jnp.exp(scores - m_new)
            l_ref[g:g + 1, :] = keep * l_ref[g:g + 1, :] + jnp.sum(
                p, axis=0, keepdims=True)
            m_ref[g:g + 1, :] = m_new
            acc_ref[g] = keep * acc_ref[g] + jnp.dot(
                vt, p.astype(vt.dtype), preferred_element_type=F32)  # [D, bq]

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for g in range(G):
            total = l_ref[g:g + 1, :]
            out_ref[g] = (acc_ref[g] / total).T
            lse_ref[g:g + 1, :] = m_ref[g:g + 1, :] + jnp.log(total)


def _backward_kernel(first_ref, count_ref, q_ref, d_out_ref, lse_ref, di_ref,
                     k_ref, v_ref, q_mark_ref, k_mark_ref, dq_ref, dk_ref,
                     dv_ref, *, window):
    block, j = pl.program_id(2), pl.program_id(3)
    G, bq, _ = q_ref.shape

    @pl.when(jnp.logical_and(block == 0, j == 0))
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(j == 0)
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(j < count_ref[block])
    def _():
        k, v = k_ref[...], v_ref[...]
        see = _visible(q_mark_ref[0:1, :], q_mark_ref[1:2, :],
                       k_mark_ref[:, 0:1], k_mark_ref[:, 1:2], window)
        rows = pl.ds(pl.multiple_of((first_ref[block] + j) * bq, bq), bq)
        dk, dv = dk_ref[...], dv_ref[...]
        for g in range(G):
            q, d_out = q_ref[g], d_out_ref[g]
            scores = jnp.where(see, jax.lax.dot_general(
                k, q, _NT, preferred_element_type=F32), NEG)    # [bk, bq]
            p = jnp.exp(scores - lse_ref[g:g + 1, :])
            dv = dv + jnp.dot(p.astype(d_out.dtype), d_out,
                              preferred_element_type=F32)
            dp = jax.lax.dot_general(v, d_out, _NT,
                                     preferred_element_type=F32)
            ds = p * (dp - di_ref[g:g + 1, :])
            dk = dk + jnp.dot(ds.astype(q.dtype), q,
                              preferred_element_type=F32)
            dq_ref[g, rows, :] += jnp.dot(ds.T.astype(k.dtype), k,
                                          preferred_element_type=F32)
        dk_ref[...], dv_ref[...] = dk, dv


def _params(vmem_bytes: int) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary",
                             "arbitrary"),
        vmem_limit_bytes=vmem_bytes)


def _forward(spec: _Spec, q, k, v, marks):
    """``(out [B, KV, G, Tp, D] float32, lse [B, KV, G, Tp] float32)`` of
    ``q [B, KV, G, Tp, D]`` over ``k, v [B, KV, Sp, D]``, padded to whole
    tiles; ``marks`` as ``_marks`` lays them out."""
    B, KV, G, Tp, D = q.shape
    bq, bk = spec.tiles
    first, count = key_ranges(spec.steps, spec.history, spec.window, bq, bk)
    # key blocks that hold a key somebody may see
    live = jnp.any((marks[1][:, :, 1] != INVALID_KEY).reshape(B, -1, bk),
                   axis=-1).astype(jnp.int32)

    def of_key(b, h, i, j, first, count, live):
        return first[i] + jnp.minimum(j, count[i] - 1)

    def tile_of_queries(b, h, i, j, *_):
        return (b, h, 0, i, 0)

    return pl.pallas_call(
        functools.partial(_forward_kernel, window=spec.window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, KV, Tp // bq, int(count.max())),
            in_specs=[
                pl.BlockSpec((None, None, G, bq, D), tile_of_queries),
                pl.BlockSpec((None, None, bk, D),
                             lambda b, h, *at: (b, h, of_key(b, h, *at), 0)),
                pl.BlockSpec((None, None, D, bk),
                             lambda b, h, *at: (b, h, 0, of_key(b, h, *at))),
                pl.BlockSpec((None, 2, bq), lambda b, h, i, j, *_: (b, 0, i)),
                pl.BlockSpec((None, bk, 2),
                             lambda b, h, *at: (b, of_key(b, h, *at), 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, G, bq, D), tile_of_queries),
                pl.BlockSpec((None, None, G, bq),
                             lambda b, h, i, j, *_: (b, h, 0, i)),
            ],
            scratch_shapes=[pltpu.VMEM((G, bq), F32),
                            pltpu.VMEM((G, bq), F32),
                            pltpu.VMEM((G, D, bq), F32)]),
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, Tp, D), F32),
                   jax.ShapeDtypeStruct((B, KV, G, Tp), F32)],
        compiler_params=_params(_VMEM_STEP),
        name=FORWARD_NAME, interpret=spec.interpret,
    )(jnp.asarray(first), jnp.asarray(count), live, q, k,
      jnp.swapaxes(v, 2, 3), *marks)


def _backward(spec: _Spec, q, k, v, marks, lse, di, d_out):
    """``(dq [B, KV, G, Tp, D], dk, dv [B, KV, Sp, D])`` float32."""
    B, KV, G, Tp, D = q.shape
    Sp = k.shape[2]
    bq, bk = spec.tiles
    first, count = query_ranges(spec.steps, spec.history, spec.window, bq, bk)

    def of_query(b, h, block, j, first, count):
        return first[block] + jnp.minimum(j, count[block] - 1)

    def tile_of_queries(b, h, *at):
        return (b, h, 0, of_query(b, h, *at), 0)

    def row_of_queries(b, h, *at):
        return (b, h, 0, of_query(b, h, *at))

    def tile_of_keys(b, h, block, j, *_):
        return (b, h, block, 0)

    return pl.pallas_call(
        functools.partial(_backward_kernel, window=spec.window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, Sp // bk, int(count.max())),
            in_specs=[
                pl.BlockSpec((None, None, G, bq, D), tile_of_queries),
                pl.BlockSpec((None, None, G, bq, D), tile_of_queries),
                pl.BlockSpec((None, None, G, bq), row_of_queries),
                pl.BlockSpec((None, None, G, bq), row_of_queries),
                pl.BlockSpec((None, None, bk, D), tile_of_keys),
                pl.BlockSpec((None, None, bk, D), tile_of_keys),
                pl.BlockSpec((None, 2, bq),
                             lambda b, h, *at: (b, 0, of_query(b, h, *at))),
                pl.BlockSpec((None, bk, 2),
                             lambda b, h, block, j, *_: (b, block, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, G, Tp, D),
                             lambda b, h, *_: (b, h, 0, 0, 0)),
                pl.BlockSpec((None, None, bk, D), tile_of_keys),
                pl.BlockSpec((None, None, bk, D), tile_of_keys),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, Tp, D), F32),
                   jax.ShapeDtypeStruct((B, KV, Sp, D), F32),
                   jax.ShapeDtypeStruct((B, KV, Sp, D), F32)],
        compiler_params=_params(_VMEM_STEP + 2 * _dq_bytes(G, Tp, D)),
        name=BACKWARD_NAME, interpret=spec.interpret,
    )(jnp.asarray(first), jnp.asarray(count), q, d_out, lse, di, k, v,
      *marks)


def _dq_bytes(G: int, Tp: int, D: int) -> int:
    return 4 * G * Tp * D


# -- the queries' road to the kernels: the rotary embedding on the lanes ------
#: Steps a block, and rows a pass of the loop inside one (``scripts/
#: attention_sweep.py``; ``PERF.md`` §6 PR 48).
EMBED_BLOCK = 512
EMBED_ROWS = 64


def wide_tables(tables, D: int) -> Tuple[Array, Tuple[int, ...]]:
    """``sequence_core.rotary_tables``' ``(cos, sin) [B, T, 1, h]`` as the
    rotary kernels read them, ``([B, 1 + rolls, T, D] float32, shifts)``:
    plane 0 is what a head's ``[T, D]`` tile is multiplied by where it lies
    (``[cos, cos]``, and 1 on the dims past the rotary ones), plane ``1 + k``
    what the tile rolled by ``shifts[k]`` lanes is multiplied by. Rotary over
    all of ``D`` (``2 h == D``) is one roll by ``h`` against ``[-sin, sin]``;
    over fewer dims each half comes from a roll of its own (by ``D - h`` the
    second half comes under the first, by ``h`` the first under the second)
    and a plane is zero outside the half it serves, so the dims that pass
    are never an array of their own."""
    cos, sin = (t[:, :, 0].astype(F32) for t in tables)        # [B, T, h]
    h = cos.shape[-1]
    rest = D - 2 * h

    def plane(first, second, passing):
        return jnp.concatenate(
            [first, second, jnp.full(cos.shape[:2] + (rest,), passing, F32)],
            axis=-1)

    if not rest:
        planes, shifts = [plane(-sin, sin, 0.0)], (h,)
    else:
        zero = jnp.zeros_like(sin)
        planes = [plane(-sin, zero, 0.0), plane(zero, sin, 0.0)]
        shifts = (D - h, h)
    return jnp.stack([plane(cos, cos, 1.0)] + planes, axis=1), shifts


def negative_angle(wide: Array) -> Array:
    """``wide_tables`` of the negative angle: the rolled planes negated. A
    rotation's transpose is the rotation back."""
    return wide * jnp.asarray((1.0,) + (-1.0,) * (wide.shape[1] - 1),
                              F32)[:, None, None]


def _rotated(x, row, table_ref, shifts):
    """A head's rows ``x [rows, D]`` float32, rotated: ``x * c + sum_k
    roll(x, shifts[k]) * s_k`` — ``sequence_core.rotate`` with its halves
    met by a lane rotation (and summed in its order, to the last bit)."""
    turned = functools.reduce(jnp.add, (
        pltpu.roll(x, shift, 1) * table_ref[1 + k, row, :]
        for k, shift in enumerate(shifts)))
    return x * table_ref[0, row, :] + turned


def _embed_kernel(from_ref, table_ref, to_ref, *, forward, shifts, rows,
                  dtype):
    """There (``forward``): step-major float32 rows, rotated, scaled, cast,
    into the KV-head-major block. Back: that block's float32 cotangent,
    rounded to the queries' type where ``_attend_bwd`` rounds it, scaled,
    rotated by what the tables hold, into the step-major rows. ``rows`` steps
    a pass of the loop, all ``G`` heads of the block in each."""
    G, steps, D = (to_ref if forward else from_ref).shape

    def some_rows(i, carry):
        row = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for g in range(G):
            head = slice(g * D, (g + 1) * D)
            if forward:
                q = _rotated(from_ref[row, head], row, table_ref, shifts)
                to_ref[g, row, :] = (q * D ** -0.5).astype(dtype)
            else:
                dq = from_ref[g, row, :].astype(dtype).astype(F32) * D ** -0.5
                to_ref[row, head] = _rotated(dq, row, table_ref, shifts)
        return carry

    jax.lax.fori_loop(0, steps // rows, some_rows, None)


def _embed(x, tables, shifts: Tuple[int, ...], dtype, *,
           kv: Optional[int] = None, interpret: bool = False):
    """One pass between the projection's step-major ``[B, Tp, KV * G * D]``
    float32 and the attention kernels' ``[B, KV, G, Tp, D]``, by
    ``wide_tables``' ``tables`` and ``shifts``. There (``x`` step-major,
    ``kv`` its KV heads): ``kv_major(rotate(x) * D ** -0.5)`` in ``dtype``,
    rotation and scale in float32. Back (``x`` the float32 cotangent of
    that): rounded to ``dtype``, scaled, rotated by what ``tables`` hold —
    the caller hands in the negative angle's. A block is
    the ``G`` heads of one KV head over a block of steps: on the step-major
    side ``G`` whole tiles of ``D`` lanes side by side, so a head is a slice
    along the lanes and nothing is transposed. The KV head is the grid's
    innermost axis: the tables' block stays where it is while it turns."""
    B, planes, Tp, D = tables.shape
    forward = kv is not None
    KV, G = (kv, x.shape[2] // D // kv) if forward else x.shape[1:3]
    block = math.gcd(Tp, EMBED_BLOCK)
    step_major = pl.BlockSpec((None, block, G * D), lambda b, t, h: (b, t, h))
    kv_major = pl.BlockSpec((None, None, G, block, D),
                            lambda b, t, h: (b, h, 0, t, 0))
    return pl.pallas_call(
        functools.partial(_embed_kernel, forward=forward, shifts=shifts,
                          rows=math.gcd(block, EMBED_ROWS), dtype=dtype),
        grid=(B, Tp // block, KV),
        in_specs=[step_major if forward else kv_major,
                  pl.BlockSpec((None, planes, block, D),
                               lambda b, t, h: (b, 0, t, 0))],
        out_specs=kv_major if forward else step_major,
        out_shape=(jax.ShapeDtypeStruct((B, KV, G, Tp, D), dtype) if forward
                   else jax.ShapeDtypeStruct((B, Tp, KV * G * D), F32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=EMBED_FORWARD_NAME if forward else EMBED_BACKWARD_NAME,
        interpret=interpret,
    )(x, tables)


# -- acting: one query a lane over the float32 ring, where it lies -------------
#: Ring slots a block of the decode kernel (``scripts/attention_sweep.py
#: acting``; ``PERF.md`` §6 PR 51).
DECODE_BLOCK = 512
_SUBLANES = 8       # rows a float32 tile: a KV head's queries are padded to it


def _decode_kernel(count_ref, q_ref, k_ref, v_ref, out_ref, m_ref, l_ref,
                   acc_ref, *, kv, slots, history, dtype):
    """One lane's block of ring slots. The block is ``[slots * kv, D]``
    float32 as the ring lies: row ``s * kv + h`` is slot s of KV head h. The
    queries of ALL heads (``[kv * 8, D]``, a KV head's G padded to 8 rows)
    meet all rows in one product; a score counts where its row's KV head is
    its column's and the slot lies below the lane's count — the others are
    masked and their probabilities are exactly 0, so they add nothing to the
    sums or to the values product."""
    b, j = pl.program_id(0), pl.program_id(1)
    rows, D = q_ref.shape
    cols = k_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    slot = j * slots + jax.lax.div(col, kv)
    see = jnp.logical_and(jax.lax.rem(col, kv) == jax.lax.div(row, _SUBLANES),
                          slot < count_ref[b])
    # the ring's values rounded to the operands' type HERE, on their way to
    # the product: what the whole-ring ``astype`` in front of it did
    scores = jax.lax.dot_general(
        q_ref[...], k_ref[...].astype(dtype), _NT,
        preferred_element_type=F32) * D ** -0.5           # [rows, cols]
    scores = jnp.where(see, scores, NEG)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(scores, axis=1, keepdims=True))
    keep = jnp.exp(m_old - m_new)
    # slot 0 lies in every lane's count: after the first block every row's
    # maximum is a score, and a masked column reads exp(NEG - score) = 0
    p = jnp.exp(scores - m_new)
    l_ref[...] = keep * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[...]
    if history % slots:
        # the last block reaches past the ring: what lies there is not a
        # number to multiply by 0
        at = jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
        v = jnp.where(j * slots + jax.lax.div(at, kv) < history, v, 0.0)
    acc_ref[...] = keep * acc_ref[...] + jnp.dot(
        p.astype(dtype), v.astype(dtype), preferred_element_type=F32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[...] / l_ref[...]


def decode(q: Array, ring_k: Array, ring_v: Array, count: Array, dtype, *,
           interpret: bool = False, block: Optional[int] = None) -> Array:
    """The attended values ``[B, KV, G, D]`` float32 of ONE query a head,
    ``q [B, KV, G, D]``, over a lane's ring ``ring_k, ring_v [B, S, KV, D]``
    (float32; read once, a block of slots at a time, as they lie in HBM: no
    array the size of a ring is written) where slot s is seen while ``s <
    count [B]``. Operands of both products are the ``dtype`` roundings of
    ``q``, of what the ring holds and of the probabilities; accumulation
    and the online softmax are float32 — ``_RotaryAttention``'s ``attend``
    at one query. Slots at or past ``count`` are masked, not skipped: every
    block is fetched whatever the lane's position. ``block`` (slots) is for
    tests and sweeps."""
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "the attention kernels are never interpreted on a TPU backend")
    B, KV, G, D = q.shape
    S = ring_k.shape[1]
    slots = min(block or DECODE_BLOCK, S)
    rows = KV * _SUBLANES
    if G > _SUBLANES:
        raise ValueError(f"{G} query heads a KV head: the kernel holds 8")
    q = jnp.pad(q.astype(dtype), ((0, 0), (0, 0), (0, _SUBLANES - G), (0, 0)))
    # [B, S, KV, D] -> [B, S * KV, D]: the same bytes in the same order
    flat = (B, S * KV, D)
    tile = pl.BlockSpec((None, slots * KV, D), lambda b, j, count: (b, j, 0))
    whole = pl.BlockSpec((None, rows, D), lambda b, j, count: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, kv=KV, slots=slots, history=S,
                          dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, _cdiv(S, slots)),
            in_specs=[whole, tile, tile],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((rows, 1), F32),
                            pltpu.VMEM((rows, 1), F32),
                            pltpu.VMEM((rows, D), F32)]),
        out_shape=jax.ShapeDtypeStruct((B, rows, D), F32),
        # VMEM for the two rings' blocks, double-buffered, their roundings
        # and the scores, and no more: what a kernel reserves the compiler
        # cannot fill with the acting step's weights ahead of their
        # products while the kernel runs (``PERF.md`` §6 PR 51)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(
                _VMEM_LEAST, 8 * slots * KV * D * ring_k.dtype.itemsize)),
        name=DECODE_NAME, interpret=interpret,
    )(count.astype(jnp.int32), q.reshape(B, rows, D), ring_k.reshape(flat),
      ring_v.reshape(flat))
    return out.reshape(B, KV, _SUBLANES, D)[:, :, :G]


# -- the two routes into the kernels ------------------------------------------
def _gradients(spec, kept, d_out):
    """``(dq, dk, dv)`` float32 of what ``_forward`` kept."""
    q, k, v, marks, out, lse = kept
    di = jnp.sum(out * d_out, axis=-1)
    return _backward(spec, q, k, v, marks, lse, di, d_out.astype(q.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attend(spec: _Spec, q, k, v, marks):
    return _forward(spec, q, k, v, marks)[0]


def _attend_fwd(spec, q, k, v, marks):
    out, lse = _forward(spec, q, k, v, marks)
    return out, (q, k, v, marks, out, lse)


def _attend_bwd(spec, kept, d_out):
    q, k, v = kept[:3]
    dq, dk, dv = _gradients(spec, kept, d_out)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _attend_rotating(spec: _Spec, shifts, x, tables, k, v, marks):
    """``_attend`` of ``x``, the projection's output before its rotary
    embedding, by ``wide_tables``' ``tables`` and ``shifts``: ``_embed`` in
    front of the forward kernel, and behind the backward's ``dq``."""
    return _attend_rotating_fwd(spec, shifts, x, tables, k, v, marks)[0]


def _attend_rotating_fwd(spec, shifts, x, tables, k, v, marks):
    q = _embed(x, tables, shifts, k.dtype, kv=k.shape[1],
               interpret=spec.interpret)
    out, lse = _forward(spec, q, k, v, marks)
    return out, (tables, q, k, v, marks, out, lse)


def _attend_rotating_bwd(spec, shifts, kept, d_out):
    tables, q, k, v = kept[:4]
    dq, dk, dv = _gradients(spec, kept[1:], d_out)
    # the rotation's transpose needs no ``x``; the tables come from integer
    # positions, nothing flows into them
    dx = _embed(dq, negative_angle(tables), shifts, q.dtype,
                interpret=spec.interpret)
    return (dx, jnp.zeros_like(tables), dk.astype(k.dtype),
            dv.astype(v.dtype), None)


_attend_rotating.defvjp(_attend_rotating_fwd, _attend_rotating_bwd)


def _marks(q_position, q_seg, k_position, k_seg, Tp: int, Sp: int):
    """The four int32 vectors as the kernels read them, (position, segment)
    each: the queries' as rows ``[B, 2, Tp]``, the keys' as a column ``[B,
    Sp, 2]``; padded with marks that see or show nothing."""
    def padded(position, seg, length, nobody):
        more = length - position.shape[1]
        return jnp.stack([
            jnp.pad(position.astype(jnp.int32), ((0, 0), (0, more))),
            jnp.pad(seg.astype(jnp.int32), ((0, 0), (0, more)),
                    constant_values=nobody)], axis=1)       # [B, 2, length]

    return (padded(q_position, q_seg, Tp, PADDING_QUERY),
            jnp.swapaxes(padded(k_position, k_seg, Sp, INVALID_KEY), 1, 2))


def attend(q: Array, keys: Array, values: Array, q_position: Array,
           q_seg: Array, k_position: Array, k_seg: Array, *, history: int,
           window: Optional[int], dtype, interpret: bool = False,
           tiles: Optional[Tiles] = None, rotary=None) -> Array:
    """The attended values ``[B, T, KV, G, D]`` float32 of ``q [B, T, KV, G,
    D]`` over ``keys, values [B, S, KV, D]`` (the ring's ``history`` slots,
    then this call's T steps) under the module's mask rule: ``q_position,
    q_seg [B, T]``, ``k_position, k_seg [B, S]`` (``INVALID_KEY`` where a
    key may be seen by nobody); ``window`` None in an ``F`` layer. Products
    take ``dtype`` operands. Differentiable in ``q``, ``keys``, ``values``.
    With ``rotary`` — ``sequence_core.rotary_tables`` of the call's steps —
    ``q`` is the projection's output BEFORE its rotary embedding, float32,
    and is rotated on its way into the kernels' layout, as its gradient is
    on the way back (``_embed``). ``tiles`` is for tests; ``interpret`` runs
    the kernels in the Pallas interpreter (CPU tests at toy sizes; never on
    a TPU backend)."""
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "the attention kernels are never interpreted on a TPU backend")
    B, T, KV, G, D = q.shape
    S = keys.shape[1]
    if S != history + T:
        raise ValueError(f"{S} keys for a ring of {history} and {T} steps")
    tiles = fitted(tiles or TILES, T, history)
    Tp, Sp = _cdiv(T, tiles.bq) * tiles.bq, _cdiv(S, tiles.bk) * tiles.bk
    if _VMEM_STEP + 2 * _dq_bytes(G, Tp, D) > _VMEM_MOST:
        raise ValueError(
            f"a window of {T} steps: the backward keeps dq [{G}, {Tp}, {D}] "
            "float32 of one KV head in VMEM, twice")

    def kv_major(x, length):
        """``[B, n, KV, ...] -> [B, KV, ..., length, D]``, zeros behind."""
        x = jnp.moveaxis(x.astype(dtype), 1, -2)
        return jnp.pad(x, ((0, 0),) * (x.ndim - 2)
                       + ((0, length - x.shape[-2]), (0, 0)))

    keys, values = kv_major(keys, Sp), kv_major(values, Sp)
    marks = _marks(q_position, q_seg, k_position, k_seg, Tp, Sp)
    spec = _Spec(T, history, window, tiles, interpret)
    if rotary is None:
        out = _attend(spec, kv_major(q * D ** -0.5, Tp), keys, values, marks)
    else:
        wide, shifts = wide_tables(rotary, D)
        behind = ((0, 0), (0, Tp - T), (0, 0))      # zeros rotate to zeros
        out = _attend_rotating(
            spec, shifts, jnp.pad(q.reshape(B, T, KV * G * D), behind),
            jnp.pad(wide, ((0, 0),) + behind), keys, values, marks)
    return jnp.moveaxis(out[..., :T, :], -2, 1)
