"""Time ``ops/pallas_attention.py``'s two kernels on the chip at the
``laguna_q`` preset's learner shapes, a tile shape at a time, beside the plain
blocks they replace, and hold their results to the blocks' on the way:

    chiprun -- python3 scripts/attention_sweep.py

A ``W`` layer (64 heads over 8, window 512) and an ``F`` layer (48 over 8),
4 windows; the unroll call (1,536 steps behind a ring of 512, forward and
backward) and the burn-in call (512 steps from an empty ring, forward). One
JSON line a reading, all of them again in
``chiprun_out/attention_sweep.json``. ``TILES`` in the kernel's module was
chosen from this table (``PERF.md`` §6, PR 47). Times only on a TPU: off one
the script stops (``--smoke``: its own rehearsal, toy shapes interpreted).

Four parts, all of them unless some are named after the script: ``tiles`` (the
attention kernels alone, a tile shape at a time), ``path`` (the whole path
against the blocks), ``rotary`` (the queries' rotary pass alone — the kernel
by steps a block and rows a loop pass, beside ``rotate`` + ``kv_major``: ms a
call, bytes moved, share of the HBM's rate; ``EMBED_BLOCK`` / ``EMBED_ROWS``
were chosen from it, ``PERF.md`` §6 PR 48) and ``sublayer`` (one sublayer's
grad pass op by op). A fifth runs only when named, ``acting``: ONE acting
step's attention alone — the one query a head over the float32 ring of the
``smallthinker_q`` and ``laguna_q`` presets' own shapes and lanes, a kind of
layer at a time: the plain path (the whole-ring cast in front of two
products) and the decode kernel by slots a block, ms a call, the ops by name,
the share of the HBM's rate the ring's bytes moved at, results compared
(``DECODE_BLOCK`` was chosen from it, ``PERF.md`` §6 PR 51). A sixth likewise,
``band``: the ``smallthinker_q`` preset's ``W`` call alone (2 windows, 28
heads over 4, 4,096 steps behind a ring of 4,096, window 4,096 — eight tiles)
— the two kernels at ``TILES`` with the static grid they walk, ``window_keys``
alone (what handing the ring over costs), and the whole path forward and
backward op by op against the blocks (``PERF.md`` §6 PR 52;
``docs/records/pr52/``).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dist_dqn_tpu.config import CONFIGS  # noqa: E402
from dist_dqn_tpu.models import sequence_core  # noqa: E402
from dist_dqn_tpu.ops import pallas_attention as pa  # noqa: E402

SMOKE = "--smoke" in sys.argv     # the script's own rehearsal on a CPU
PARTS = [a for a in sys.argv[1:] if not a.startswith("--")] or [
    "tiles", "path", "rotary", "sublayer"]
DECODE_BLOCKS = (16, 512) if SMOKE else (256, 512, 1024, 2048)
B, KV, D, HISTORY, WINDOW = (1, 1, 16, 128, 128) if SMOKE else (
    4, 8, 128, 512, 512)
UNROLL, BURN_IN = (256, 128) if SMOKE else (1536, 512)
TILES = ((128, 128),) if SMOKE else (
    (128, 256), (128, 512), (256, 256), (256, 512), (512, 512), (256, 1024),
    (512, 1024))
EMBED_SHAPES = ((128, 128),) if SMOKE else (     # steps a block, rows a pass
    (512, 32), (512, 8), (512, 16), (512, 64), (512, 512), (256, 32),
    (128, 32))
REPEATS = 1 if SMOKE else 10


def seconds(fn, *args):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(REPEATS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / REPEATS


def top_ops(fn, *args, top=30):
    """``[[label, ms], ..]``: the device's time in one traced call of ``fn``
    by opcode and shape (the benchmark's own reduction), largest first."""
    import tempfile

    from perf.reduce import trace_reduce, xplane

    with tempfile.TemporaryDirectory() as where:
        with jax.profiler.trace(where):
            jax.block_until_ready(fn(*args))
        planes = xplane.load_newest(Path(where))
    totals = {}
    for plane in planes:
        if "TPU" in plane["name"]:
            totals = trace_reduce.DeviceTrace(plane).op_totals()
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[label, round(seconds * 1e3, 4)] for label, seconds in
            ranked[:top]] + [["all", round(sum(totals.values()) * 1e3, 4)]]


def block_attend(q, keys, values, see):
    """``blockwise``'s block function at the presets' precision: bfloat16
    operands, float32 scores and softmax."""
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", q.astype(jnp.bfloat16),
        keys.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    scores = jnp.where(see[:, None, None], scores, -1e30)
    return jnp.einsum(
        "bkgqs,bskd->bqkgd",
        jax.nn.softmax(scores, axis=-1).astype(jnp.bfloat16),
        values.astype(jnp.bfloat16), preferred_element_type=jnp.float32)


def kernel_ms(direction, spec, kv, G, d, position, seg, key_position,
              key_seg):
    """One attention kernel alone at ``spec`` (``forward`` or ``backward``),
    ms a call: constant operands padded to whole tiles, the call's marks."""
    lanes = position.shape[0]
    bq, bk = spec.tiles
    Tp = -(-spec.steps // bq) * bq
    Sp = -(-(spec.history + spec.steps) // bk) * bk
    q16 = jnp.zeros((lanes, kv, G, Tp, d), jnp.bfloat16) + 0.1
    k16 = jnp.zeros((lanes, kv, Sp, d), jnp.bfloat16) + 0.1
    marks = pa._marks(position, seg, key_position, key_seg, Tp, Sp)
    if direction == "forward":
        return seconds(jax.jit(functools.partial(pa._forward, spec)),
                       q16, k16, k16, marks) * 1e3
    row = jnp.zeros((lanes, kv, G, Tp), jnp.float32)
    return seconds(jax.jit(functools.partial(pa._backward, spec)),
                   q16, k16, k16, marks, row, row, q16) * 1e3


def window_of(rng, T, G, steps, lanes=B, kv=KV, d=D, history=HISTORY):
    """One call's inputs: a reset inside lane 0, lane 1's ring cut short by
    a reset in its burn-in (``steps`` < history)."""
    q = jnp.asarray(rng.normal(size=(lanes, T, kv, G, d)), jnp.float32)
    new_k, new_v = (jnp.asarray(rng.normal(size=(lanes, T, kv, d)),
                                jnp.float32) for _ in range(2))
    ring = tuple(jnp.asarray(rng.normal(size=(lanes, history, kv, d)),
                             jnp.float32) for _ in range(2))
    reset = np.zeros((lanes, T), bool)
    reset[0, T // 3] = True
    seg = sequence_core.segments(jnp.asarray(reset))
    index = jnp.arange(T)
    opened = jax.lax.cummax(jnp.where(
        jnp.diff(seg, axis=1, prepend=0) > 0, index, -1), axis=1)
    steps = jnp.asarray(steps, jnp.float32)
    position = jnp.where(seg == 0, steps.astype(jnp.int32)[:, None] + index,
                         index - opened)
    return q, new_k, new_v, position, seg, ring + (steps,)


def rotary_rows(say, rng, kind, G, rope, T, call):
    """The queries' rotary pass alone, there and back, a block shape at a
    time, beside the plain functions it replaces; results compared."""
    from perf.reduce import peaks

    # the rehearsal's CPU has no peak: its share is of a v5e's, and no time
    hbm_rate = peaks.peak("TPU v5e" if SMOKE else
                          jax.devices()[0].device_kind, "hbm_bytes_per_s")
    heads = KV * G
    x = jnp.asarray(rng.normal(size=(B, T, heads * D)), jnp.float32)
    pull = jnp.asarray(rng.normal(size=(B, KV, G, T, D)), jnp.float32)
    position = jnp.asarray(rng.integers(0, 4096, size=(B, T)), jnp.int32)
    tables = sequence_core.rotary_tables(position, rope, D)
    wide, shifts = pa.wide_tables(tables, D)
    back = pa.negative_angle(wide)

    def plain(x):
        q = sequence_core.rotate(x.reshape(B, T, heads, D), tables)
        q = q.reshape(B, T, KV, G, D) * D ** -0.5
        return jnp.moveaxis(q.astype(jnp.bfloat16), 1, -2)

    def plain_back(x, pull16):
        return jax.vjp(plain, x)[1](pull16)[0]

    # rounded out here: inside ``plain_back``'s program XLA would fold the
    # cast and the cast back away, and the kernel keeps its own
    pull16 = pull.astype(jnp.bfloat16)
    want = {"forward": jax.jit(plain)(x),
            "backward": jax.jit(plain_back)(x, pull16)}
    moved = {   # bytes the pass has to move: x or dq, the tables, the result
        "forward": (4 + 2) * x.size + 4 * wide.size,
        "backward": (4 + 4) * x.size + 4 * wide.size}
    shipped = pa.EMBED_BLOCK, pa.EMBED_ROWS
    for direction in ("forward", "backward"):
        if direction == "backward" and call == "burn_in":
            continue
        plain_ms = (seconds(jax.jit(plain), x) if direction == "forward"
                    else seconds(jax.jit(plain_back), x, pull16)) * 1e3
        for block, rows in EMBED_SHAPES:
            pa.EMBED_BLOCK, pa.EMBED_ROWS = block, rows
            fn = jax.jit(
                (lambda x: pa._embed(x, wide, shifts, jnp.bfloat16, kv=KV,
                                     interpret=SMOKE))
                if direction == "forward" else
                (lambda dq: pa._embed(dq, back, shifts, jnp.bfloat16,
                                      interpret=SMOKE)))
            arg = x if direction == "forward" else pull
            try:
                start = time.perf_counter()
                jax.block_until_ready(fn(arg))
                first = time.perf_counter() - start     # with its compile
                took = seconds(fn, arg)
            except Exception as e:  # noqa: BLE001 - a block Mosaic refuses
                say(kind=kind, call=call, kernel="rotary_" + direction,
                    block=block, rows=rows, refused=str(e)[:300])
                continue
            gap = jnp.max(jnp.abs(fn(arg).astype(jnp.float32)
                                  - want[direction].astype(jnp.float32)))
            say(kind=kind, call=call, kernel="rotary_" + direction,
                block=block, rows=rows, ms=took * 1e3, first_call_s=first,
                plain_ms=plain_ms,
                bytes=moved[direction],
                hbm_share=moved[direction] / took / hbm_rate,
                max_gap=float(gap),
                max_size=float(jnp.max(jnp.abs(
                    want[direction].astype(jnp.float32)))))
    pa.EMBED_BLOCK, pa.EMBED_ROWS = shipped


def acting_rows(say, rng, preset):
    """One acting step's attention alone at ``preset``'s ring shapes and
    lanes, a kind at a time: the plain path and the decode kernel."""
    from perf.reduce import peaks

    hbm_rate = peaks.peak("TPU v5e" if SMOKE else
                          jax.devices()[0].device_kind, "hbm_bytes_per_s")
    cfg = CONFIGS[preset]
    core = cfg.network.core
    lanes, KV, D = cfg.actor.num_envs, core.num_key_value_heads, core.head_dim
    rings = {kind: (core.sliding_window if kind == "W"
                    else core.attention_window, heads // KV)
             for kind, heads in zip(core.pattern,
                                    sequence_core.rotary_heads(core))
             if kind in sequence_core.ROTARY}
    for kind, (S, G) in rings.items():
        if SMOKE:
            lanes, S, D = 4, 40, 16
        q = jnp.asarray(rng.normal(size=(lanes, KV, G, D)), jnp.float32)
        ring_k, ring_v = (jnp.asarray(rng.normal(size=(lanes, S, KV, D)),
                                      jnp.float32) for _ in range(2))
        # lanes just reset, part full, exactly full and wrapped
        count = jnp.asarray(
            [(1, S // 3, S, S)[lane % 4] for lane in range(lanes)], jnp.int32)

        def plain(q, ring_k, ring_v, count):
            see = jnp.arange(S) < count[:, None]
            scores = jnp.einsum(
                "bkgd,bskd->bkgs", q.astype(jnp.bfloat16),
                ring_k.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32) * D ** -0.5
            scores = jnp.where(see[:, None, None], scores, -1e30)
            return jnp.einsum(
                "bkgs,bskd->bkgd",
                jax.nn.softmax(scores, axis=-1).astype(jnp.bfloat16),
                ring_v.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32)

        args = (q, ring_k, ring_v, count)
        read = 2 * ring_k.size * 4
        plain = jax.jit(plain)
        want = plain(*args)
        took = seconds(plain, *args)
        say(preset=preset, kind=kind, what="acting", path="plain",
            ring=list(ring_k.shape), G=G, ms=took * 1e3, ring_bytes=read,
            hbm_share=read / took / hbm_rate,
            ops=None if SMOKE else top_ops(plain, *args, top=8))
        # the ring rounded OUTSIDE the program: the kernel must round as that
        rounded = tuple(r.astype(jnp.bfloat16).astype(jnp.float32)
                        for r in (ring_k, ring_v))
        for block in sorted({min(block, S) for block in DECODE_BLOCKS}):
            fn = jax.jit(lambda *a: pa.decode(
                *a, jnp.bfloat16, interpret=SMOKE, block=block))
            shipped = block == min(pa.DECODE_BLOCK, S)
            try:
                got = fn(*args)
                took = seconds(fn, *args)
            except Exception as e:  # noqa: BLE001 - a block Mosaic refuses
                say(preset=preset, kind=kind, what="acting", path="kernel",
                    block=block, refused=str(e)[:300])
                continue
            say(preset=preset, kind=kind, what="acting", path="kernel",
                block=block, shipped=shipped, ring=list(ring_k.shape), G=G,
                ms=took * 1e3, ring_bytes=read,
                hbm_share=read / took / hbm_rate,
                max_gap=float(jnp.max(jnp.abs(got - want))),
                max_size=float(jnp.max(jnp.abs(want))),
                rounds_as_the_cast=bool(jnp.array_equal(
                    got, fn(q, *rounded, count))),
                ops=(top_ops(fn, *args, top=8)
                     if shipped and not SMOKE else None))


def band_rows(say, rng):
    """The ``smallthinker_q`` preset's ``W`` call alone: a window of eight
    tiles, every query block of the call inside the first window."""
    cfg = CONFIGS["smallthinker_q"]
    core = cfg.network.core
    lanes, kv, d = (cfg.learner.batch_size, core.num_key_value_heads,
                    core.head_dim)
    window, burn_in = core.sliding_window, cfg.replay.burn_in
    unroll = cfg.replay.unroll_length + cfg.learner.n_step
    G = max(sequence_core.rotary_heads(core)) // kv
    if SMOKE:
        lanes, kv, d, window, burn_in, unroll = 1, 1, 16, 256, 256, 256
        core = dataclasses.replace(core, sliding_window=window, head_dim=d,
                                   num_key_value_heads=kv)
    layer = sequence_core._RotaryAttention(core, jnp.bfloat16, heads=G * kv,
                                           windowed=True)
    tiles = pa.fitted(pa.TILES, unroll, window)
    # the unroll's rings: full, the last lane's cut short by a reset in its
    # burn-in; the burn-in's: empty
    cut = (window,) * (lanes - 1) + (window * 3 // 5,)
    for call, T, steps in (("unroll", unroll, cut),
                           ("burn_in", burn_in, (0,) * lanes)):
        q, new_k, new_v, position, seg, carry = window_of(
            rng, T, G, steps, lanes, kv, d, window)
        order = jax.jit(layer.window_keys)
        keys, values, key_position, key_seg = order(new_k, new_v, position,
                                                    seg, carry)
        pull = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
        visited, skipped = pa.key_block_census(T, window, window, tiles)
        shape = dict(preset="smallthinker_q", kind="W", call=call,
                     lanes=lanes, kv=kv, G=G, steps=T, history=window,
                     window=window, tiles=list(tiles))
        # -- the kernels alone --------------------------------------------
        spec = pa._Spec(T, window, window, tiles, SMOKE)
        for direction in ("forward", "backward")[:1 + (call == "unroll")]:
            say(**shape, kernel=direction, visited=visited, skipped=skipped,
                ms=kernel_ms(direction, spec, kv, G, d, position, seg,
                             key_position, key_seg))
        # -- handing the keys over: the ring's order, the concatenation ----
        say(**shape, what="window_keys",
            ms=seconds(order, new_k, new_v, position, seg, carry) * 1e3,
            ring_bytes=2 * carry[0].size * 4,
            ops=None if SMOKE else top_ops(order, new_k, new_v, position,
                                           seg, carry, top=8))

        # -- the whole path from the ring as it lies, against the blocks ---
        def fused(q, new_k, new_v):
            return pa.attend(
                q, *layer.window_keys(new_k, new_v, position, seg, carry)[:2],
                position, seg, key_position, key_seg, history=window,
                window=window, dtype=jnp.bfloat16, interpret=SMOKE)

        def plain(q, new_k, new_v):
            return layer.blockwise(
                jax.checkpoint(block_attend), q,
                *layer.window_keys(new_k, new_v, position, seg, carry)[:2],
                position, seg, key_position, key_seg)

        def with_grads(f):
            return jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(f(*a) * pull), argnums=(0, 1, 2)))

        # one jit a program: each is compiled once, on the chip's time
        args = (q, new_k, new_v)
        for what, both, top in (
                ("forward", (jax.jit(fused), jax.jit(plain)), 12),
                ("forward+backward", (with_grads(fused), with_grads(plain)),
                 16))[:1 + (call == "unroll")]:
            # the output; with gradients: the pulled sum, dq, dk, dv
            got, want = (jax.tree.leaves(f(*args)) for f in both)
            say(**shape, what=what,
                fused_ms=seconds(both[0], *args) * 1e3,
                blocks_ms=seconds(both[1], *args) * 1e3,
                max_gaps=[float(jnp.max(jnp.abs(a - b)))
                          for a, b in zip(got, want)],
                max_sizes=[float(jnp.max(jnp.abs(b))) for b in want],
                ops=None if SMOKE else top_ops(both[0], *args, top=top))


def main():
    if jax.default_backend() != "tpu" and not SMOKE:
        raise SystemExit("attention_sweep times kernels: it needs a TPU")
    device = jax.devices()[0]
    print(json.dumps({"device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": jax.device_count()}}), flush=True)
    core = dataclasses.replace(CONFIGS["laguna_q"].network.core,
                               sliding_window=WINDOW, head_dim=D,
                               num_key_value_heads=KV)
    rng = np.random.default_rng(47)
    rows = []

    def say(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    if "acting" in PARTS:
        for preset in ("smallthinker_q", "laguna_q", "ouro_q"):
            acting_rows(say, rng, preset)
    if "band" in PARTS:
        band_rows(say, rng)
    for kind, G in (("W", 8), ("F", 6)):
        windowed = kind == "W"
        rope = core.rope_window if windowed else core.rope_full
        window = WINDOW if windowed else None
        layer = sequence_core._RotaryAttention(
            core, jnp.bfloat16, heads=G * KV, windowed=windowed)
        # the unroll's rings: full, but every other lane's cut short by a
        # reset in its burn-in; the burn-in's: empty
        cut = tuple(HISTORY * 3 // 5 if lane % 2 else HISTORY
                    for lane in range(B))
        for call, T, steps in (("unroll", UNROLL, cut),
                               ("burn_in", BURN_IN, (0,) * B)):
            if "rotary" in PARTS:
                rotary_rows(say, rng, kind, G, rope, T, call)
            if not {"tiles", "path"} & set(PARTS):
                continue
            q, new_k, new_v, position, seg, carry = window_of(rng, T, G, steps)
            keys, values, key_position, key_seg = jax.jit(layer.window_keys)(
                new_k, new_v, position, seg, carry)
            pull = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

            def plain(q, keys, values):
                return layer.blockwise(jax.checkpoint(block_attend), q, keys,
                                       values, position, seg, key_position,
                                       key_seg)

            def fused(tiles):
                return lambda q, keys, values: pa.attend(
                    q, keys, values, position, seg, key_position, key_seg,
                    history=HISTORY, window=window, dtype=jnp.bfloat16,
                    tiles=tiles, interpret=SMOKE)

            def with_grads(f):
                return jax.jit(jax.value_and_grad(
                    lambda *a: jnp.sum(f(*a) * pull), argnums=(0, 1, 2)))

            # -- the kernels alone, a tile shape at a time -------------------
            for direction in ("forward", "backward"):
                if "tiles" not in PARTS or (
                        direction == "backward" and call == "burn_in"):
                    continue
                for bq, bk in TILES:
                    tiles = pa.fitted(pa.Tiles(bq, bk), T, HISTORY)
                    spec = pa._Spec(T, HISTORY, window, tiles, SMOKE)
                    try:
                        took = kernel_ms(direction, spec, KV, G, D, position,
                                         seg, key_position, key_seg)
                    except Exception as e:  # noqa: BLE001 - a tile Mosaic refuses
                        say(kind=kind, call=call, kernel=direction, bq=bq,
                            bk=bk, refused=str(e)[:300])
                        continue
                    visited, skipped = pa.key_block_census(
                        T, HISTORY, window, tiles)
                    say(kind=kind, call=call, kernel=direction, bq=bq, bk=bk,
                        ms=took, visited=visited, skipped=skipped)

            # -- the whole path: casts, layout, kernels; against the blocks ---
            if "path" not in PARTS:
                continue
            got = jax.jit(fused(None))(q, keys, values)
            want = jax.jit(plain)(q, keys, values)
            say(kind=kind, call=call, what="forward", tiles=list(pa.TILES),
                fused_ms=seconds(jax.jit(fused(None)), q, keys, values) * 1e3,
                blocks_ms=seconds(jax.jit(plain), q, keys, values) * 1e3,
                max_gap=float(jnp.max(jnp.abs(got - want))),
                max_size=float(jnp.max(jnp.abs(want))))
            if call == "unroll":
                (_, got), (_, want) = (with_grads(f)(q, keys, values)
                                       for f in (fused(None), plain))
                say(kind=kind, call=call, what="forward+backward",
                    fused_ms=seconds(with_grads(fused(None)), q, keys,
                                     values) * 1e3,
                    blocks_ms=seconds(with_grads(plain), q, keys,
                                      values) * 1e3,
                    grad_gaps=[float(jnp.max(jnp.abs(a - b)))
                               for a, b in zip(got, want)],
                    grad_sizes=[float(jnp.max(jnp.abs(b))) for b in want])
        # -- one whole sublayer, forward and backward, op by op ---------------
        if "sublayer" not in PARTS:
            continue
        hidden = 256 if SMOKE else CONFIGS["laguna_q"].network.hidden
        u = jnp.asarray(rng.normal(size=(B, UNROLL, hidden)), jnp.float32)
        seg = jnp.zeros((B, UNROLL), jnp.int32)
        ring = (jnp.zeros((B, HISTORY, KV, D)),) * 2 + (
            jnp.full((B,), float(HISTORY)),)
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), u, seg, ring)
        step = jax.jit(jax.grad(
            lambda params, u: jnp.sum(layer.apply(params, u, seg, ring)[0]
                                      ** 2), argnums=(0, 1)))
        say(kind=kind, what="sublayer forward+backward",
            ms=seconds(step, params, u) * 1e3, ops=top_ops(step, params, u))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attention_sweep.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
