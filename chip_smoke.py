"""The quickest proof that the system starts on the chip.

``python3 chip_smoke.py`` drives the main path once, in ONE process, on
the accelerator JAX finds, through the entry points a user calls:

1. ``fused``  — ``dist_dqn_tpu.train.main`` on the unmodified ``atari``
   preset (Nature CNN bf16, 64 lanes, 200k-transition ring, batch 256):
   three 500-iteration chunks, the last two past ``min_fill``, with the
   periodic evaluator and a checkpoint, then ``dist_dqn_tpu.evaluate.main``
   restores that checkpoint and plays greedy episodes.
2. ``kernel`` — the ``apex`` preset with ``replay.frame_dedup=true`` (the
   only way its 1M-transition ring fits one chip) through
   ``dist_dqn_tpu.train.train`` past its 50,000 ``min_fill``: the compiled
   chunk must contain the Mosaic custom call of the Pallas sampler, and
   the kernel's draws must agree with the XLA sampler and a float64
   reference on the run's own priority plane and on a full seeded one.
3. ``mesh``   — only where the machine shows four or more devices: the
   ``atari`` preset over a 4-device ``dp`` mesh; every device must hold a
   replay shard and env lanes, and the replicated learner must stay
   bit-identical across devices after real grad steps.

Weights are random from the preset's seed. Each leg prints one JSON line
(compile seconds apart from step seconds, persistent-cache hits, frames,
grad steps, loss); the last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``. No accelerator,
no grad step, a non-finite loss or any failed check is a nonzero exit with
no result line; nothing makes this script pass on a CPU — the tier-1 test
(tests/test_chip_smoke.py) calls the leg functions at toy size instead.
"""
from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import math
import sys
import tempfile
from typing import Dict, List, Sequence

import numpy as np

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"

# Both samplers sum in float32 (in different orders), so on a plane of a
# million real-valued cells they pick NEIGHBOURING cells for a small
# share of the draws (measured on the chip, PR 21: under 2%). On any
# plane the kernel's picks must be the XLA sampler's for at least
# MIN_EXACT_KERNEL_VS_XLA of the draws, and every pair of picks (kernel,
# XLA, float64 reference) must lie within MASS_GAP_TOL of the total mass
# of each other — 5% of one 512-draw stratum. On a plane of integer
# masses, where every float32 prefix sum is exact, the picks must be
# EXACTLY the reference's and each other's for nearly every draw — which
# is what catches a row or lane that is off by one.
MASS_GAP_TOL = 1e-4
MIN_EXACT_KERNEL_VS_XLA = 0.9
MIN_EXACT_ON_INTEGER_PLANE = 0.98


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileMeter:
    """Sums JAX's own compile-time and persistent-cache events.

    ``take()`` returns what accumulated since the previous ``take()``:
    trace + lowering + backend-compile seconds (a cache hit counts its
    retrieval time) and the number of persistent-cache hits and misses.
    """

    def __init__(self):
        import jax

        self._secs = 0.0
        self._hits = 0
        self._misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name: str, secs: float, **_) -> None:
        if name in COMPILE_EVENTS:
            self._secs += secs

    def _on_event(self, name: str, **_) -> None:
        if name == CACHE_HIT:
            self._hits += 1
        elif name == CACHE_MISS:
            self._misses += 1

    def take(self) -> Dict:
        out = {"compile_s": round(self._secs, 2), "cache_hits": self._hits,
               "cache_misses": self._misses}
        self._secs, self._hits, self._misses = 0.0, 0, 0
        return out


class _Tee(io.TextIOBase):
    """Passes writes through to ``sink`` and keeps them."""

    def __init__(self, sink):
        self._sink = sink
        self._kept: List[str] = []

    def write(self, text: str) -> int:
        self._kept.append(text)
        return self._sink.write(text)

    def flush(self) -> None:
        self._sink.flush()

    def json_rows(self) -> List[Dict]:
        return [json.loads(line)
                for line in "".join(self._kept).splitlines()
                if line.startswith("{")]


def run_cli(entry, argv: Sequence[str]) -> List[Dict]:
    """Call a CLI ``main(argv)`` in this process; its stdout still reaches
    ours, and its JSON lines come back parsed."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        entry(list(argv))
    return tee.json_rows()


def _set_flags(overrides: Sequence[str]) -> List[str]:
    return [arg for o in overrides for arg in ("--set", o)]


def _check_chunks(rows: List[Dict], lanes: int, chunk_iters: int,
                  chunks: int) -> Dict:
    """The chunk rows of one training run: all there, frames exact, the
    last chunk post-fill with grad steps and a finite loss."""
    check(len(rows) == chunks, f"expected {chunks} chunk rows, got {rows}")
    last = rows[-1]
    check(last["env_frames"] == chunks * chunk_iters * lanes,
          f"env_frames {last['env_frames']} != "
          f"{chunks} x {chunk_iters} x {lanes}")
    check(last["grad_steps_in_chunk"] > 0,
          "no grad step ran in the last chunk (min_fill not passed)")
    check(all(math.isfinite(r["loss"]) for r in rows),
          f"non-finite loss: {[r['loss'] for r in rows]}")
    return {
        "env_frames": last["env_frames"],
        "grad_steps": sum(r["grad_steps_in_chunk"] for r in rows),
        "grad_steps_last_chunk": last["grad_steps_in_chunk"],
        "loss": last["loss"],
        # Chunk walls from the trainer's own rate column. The compile is
        # in neither (the trainer compiles before the first dispatch and
        # the meter reports it): the first chunk is mostly pre-fill, the
        # last is steady state.
        "first_chunk_s": round(chunk_iters * lanes
                               / rows[0]["env_steps_per_sec"], 3),
        "last_chunk_s": round(chunk_iters * lanes
                              / last["env_steps_per_sec"], 3),
    }


def _check_stage_table(prioritized: bool, mesh: bool = False) -> Dict:
    """What the benchmark's per-stage metrics depend on: the executable the
    trainer kept (telemetry/stages.py) yields a stage table that names every
    stage the leg's program enters."""
    from dist_dqn_tpu.telemetry import stages

    table = stages.table()
    check(table, "the trainer kept no chunk program: the stage table is "
                 "empty")
    entered = set(stages.STAGES)
    if not prioritized:
        entered.discard("writeback")
    if not mesh:
        entered.discard("allreduce")
    missing = sorted(entered - set(table.values()))
    check(not missing, f"the stage table names no instruction of {missing}")
    return {"stage_instructions": len(table)}


def leg_fused(meter: CompileMeter, config: str = "atari",
              overrides: Sequence[str] = (), chunk_iters: int = 500,
              chunks: int = 3, episodes: int = 5) -> Dict:
    """train CLI -> periodic eval -> checkpoint -> evaluate CLI restores."""
    from dist_dqn_tpu import evaluate, train
    from dist_dqn_tpu.config import CONFIGS, apply_overrides
    cfg = apply_overrides(CONFIGS[config], list(overrides))
    lanes = cfg.actor.num_envs
    total = chunks * chunk_iters * lanes
    check(total > cfg.replay.min_fill + chunk_iters * lanes,
          f"{chunks} chunks of {chunk_iters} do not reach a whole "
          f"post-fill chunk (min_fill {cfg.replay.min_fill})")
    meter.take()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        rows = run_cli(train.main, [
            "--config", config, *_set_flags(overrides),
            "--total-env-steps", str(total),
            "--chunk-iters", str(chunk_iters),
            "--checkpoint-dir", ckpt_dir])
        out = {"leg": "fused", "config": config, **meter.take()}
        check("device" in rows[0], f"first log line is not the device: "
                                   f"{rows[0]}")
        chunk_rows = [r for r in rows if "env_frames" in r]
        out.update(_check_chunks(chunk_rows, lanes, chunk_iters, chunks))
        evals = [r["eval_return"] for r in chunk_rows if "eval_return" in r]
        check(evals and all(math.isfinite(e) for e in evals),
              f"the periodic evaluator returned {evals}")
        out["train_eval_return"] = evals[-1]
        out.update(_check_stage_table(cfg.replay.prioritized))

        rows = run_cli(evaluate.main, [
            "--config", config, *_set_flags(overrides),
            "--checkpoint-dir", ckpt_dir, "--episodes", str(episodes)])
        restored = [r for r in rows if "eval_return" in r]
        check(len(restored) == 1, f"evaluate printed {rows}")
        check(restored[0]["frames"] == total,
              f"evaluate restored step {restored[0]['frames']}, "
              f"trained to {total}")
        check(math.isfinite(restored[0]["eval_return"]),
              f"restored eval_return {restored[0]['eval_return']}")
        out["restored_eval_return"] = restored[0]["eval_return"]
        out["evaluate_compile_s"] = meter.take()["compile_s"]
    return out


def _train_direct(cfg, chunk_iters: int, chunks: int, num_devices: int = 1):
    """``dist_dqn_tpu.train.train`` with eval off; returns (final carry,
    chunk metric rows)."""
    from dist_dqn_tpu import train

    cfg = dataclasses.replace(cfg, eval_every_steps=0)
    return train.train(
        cfg, total_env_steps=chunks * chunk_iters * cfg.actor.num_envs,
        chunk_iters=chunk_iters, num_devices=num_devices)


def _compare_draws(w, u, B: int, interpret: bool,
                   integer_masses: bool) -> Dict:
    """Kernel vs XLA sampler vs a float64 host reference on the flat cells
    ``w`` [T * B] of one mass plane at uniforms ``u`` [S] (see
    MASS_GAP_TOL)."""
    import jax

    from dist_dqn_tpu.ops.pallas_sampler import stratified_sample_at

    tk, bk, pk, tot_k = jax.device_get(stratified_sample_at(
        w, u, B, use_pallas=True, interpret=interpret))
    tx, bx, _, tot_x = jax.device_get(stratified_sample_at(
        w, u, B, use_pallas=False))
    flat_k = tk.astype(np.int64) * B + bk
    flat_x = tx.astype(np.int64) * B + bx
    w64 = np.asarray(jax.device_get(w), np.float64)
    cdf = np.cumsum(w64)
    total = cdf[-1]
    u32 = np.asarray(jax.device_get(u), np.float32)

    def reference(targets) -> np.ndarray:
        # Both samplers pick the first cell whose inclusive CDF reaches
        # the target.
        return np.searchsorted(cdf, targets.astype(np.float64),
                               side="left").clip(0, w64.size - 1)

    # Each sampler's own float32 target arithmetic, on the exact CDF.
    ref_k = reference(u32 * np.float32(tot_k))
    ref_x = reference(u32 * np.float32(tot_x))

    def gap(a, b) -> float:
        return float(np.max(np.abs(cdf[a] - cdf[b])) / total)

    out = {"cells": int(w64.size), "nonzero_cells": int(np.sum(w64 > 0)),
           "draws": int(u32.size),
           "exact_kernel_vs_xla": float(np.mean(flat_k == flat_x)),
           "exact_kernel_vs_f64": float(np.mean(flat_k == ref_k)),
           "exact_xla_vs_f64": float(np.mean(flat_x == ref_x)),
           "mass_gap_kernel_vs_xla": gap(flat_k, flat_x),
           "mass_gap_kernel_vs_f64": gap(flat_k, ref_k),
           "mass_gap_xla_vs_f64": gap(flat_x, ref_x)}
    check(bool(np.all(w64[flat_k] > 0)), "the kernel drew a zero-mass cell")
    check(np.allclose(pk, w64[flat_k], rtol=1e-6),
          "the kernel's selected masses are not the plane's")
    check(abs(float(tot_k) - total) <= 1e-4 * total
          and abs(float(tot_x) - total) <= 1e-4 * total,
          f"total mass: kernel {tot_k}, xla {tot_x}, float64 {total}")
    check(max(out["mass_gap_kernel_vs_xla"], out["mass_gap_kernel_vs_f64"],
              out["mass_gap_xla_vs_f64"]) <= MASS_GAP_TOL,
          f"draws are further apart than {MASS_GAP_TOL} of the mass: {out}")
    check(out["exact_kernel_vs_xla"] >= MIN_EXACT_KERNEL_VS_XLA,
          f"the kernel's draws are not the XLA sampler's: {out}")
    if integer_masses:
        check(min(out["exact_kernel_vs_f64"], out["exact_xla_vs_f64"],
                  out["exact_kernel_vs_xla"]) >= MIN_EXACT_ON_INTEGER_PLANE,
              f"draws on the integer plane are not the reference's: {out}")
    return out


def leg_kernel(meter: CompileMeter, config: str = "apex",
               overrides: Sequence[str] = ("replay.frame_dedup=true",),
               chunk_iters: int = 1000, chunks: int = 4) -> Dict:
    """The Pallas sampler inside the program that uses it."""
    import jax
    import jax.numpy as jnp

    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.config import CONFIGS, apply_overrides
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.ops.pallas_sampler import KERNEL_NAME
    from dist_dqn_tpu.train_loop import make_fused_train

    cfg = apply_overrides(CONFIGS[config], list(overrides))
    check(cfg.replay.prioritized and cfg.replay.pallas_sampler,
          f"{config} does not select the Pallas sampler")
    use_pallas, interpret = loop_common.pallas_routing(True)
    check(use_pallas, "pallas_routing handed back the XLA sampler")
    on_tpu = jax.default_backend() == "tpu"

    meter.take()
    carry, history = _train_direct(cfg, chunk_iters, chunks)
    out = {"leg": "kernel", "config": config, **meter.take()}
    out.update(_check_chunks(history, cfg.actor.num_envs, chunk_iters,
                             chunks))
    out.update(_check_stage_table(prioritized=True))

    # The same program the trainer just ran, from the same factory at the
    # same shapes: its compiled text must hold the kernel as a Mosaic
    # custom call (a flag proves nothing — off the chip pallas_routing
    # hands back the XLA sampler or the interpreter).
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    _, run_chunk = make_fused_train(cfg, env, net)
    text = jax.jit(run_chunk, static_argnums=1, donate_argnums=0).lower(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     carry), chunk_iters).compile().as_text()
    out["mosaic_custom_call"] = ("tpu_custom_call" in text
                                 and KERNEL_NAME in text)
    check(out["mosaic_custom_call"] == on_tpu,
          f"Mosaic custom call {KERNEL_NAME!r} in the compiled chunk: "
          f"{out['mosaic_custom_call']} on backend "
          f"{jax.default_backend()!r}")

    # Draw agreement, outside any timing: the run's own priority plane,
    # and a seeded plane of integer masses over the same shape (one cell
    # in eight holds mass 64: every float32 prefix sum below 2**24 is
    # exact, so the picks must be the reference's own).
    plane = carry.replay.priorities
    out["plane_shape"] = list(plane.shape)
    S = loop_common.resolve_train_batch(cfg)
    k_u, k_w = jax.random.split(jax.random.PRNGKey(cfg.seed))
    u = (jnp.arange(S, dtype=jnp.float32)
         + jax.random.uniform(k_u, (S,))) / S
    out["run_plane"] = _compare_draws(
        plane ** cfg.replay.priority_exponent, u, cfg.actor.num_envs,
        interpret, integer_masses=False)
    sparse = jnp.where(jax.random.uniform(k_w, plane.shape) < 0.125,
                       64.0, 0.0)
    check(float(jnp.sum(sparse)) < 2 ** 24, "integer plane too heavy")
    out["integer_plane"] = _compare_draws(sparse, u, cfg.actor.num_envs,
                                          interpret, integer_masses=True)
    return out


def leg_mesh(meter: CompileMeter, config: str = "atari",
             overrides: Sequence[str] = (), chunk_iters: int = 500,
             chunks: int = 3, num_devices: int = 4) -> Dict:
    """The fused trainer over a ``dp`` mesh of ``num_devices`` devices."""
    import jax

    from dist_dqn_tpu.config import CONFIGS, apply_overrides
    cfg = apply_overrides(CONFIGS[config], list(overrides))
    meter.take()
    carry, history = _train_direct(cfg, chunk_iters, chunks,
                                   num_devices=num_devices)
    out = {"leg": "mesh", "config": config, "num_devices": num_devices,
           **meter.take()}
    out.update(_check_chunks(history, cfg.actor.num_envs, chunk_iters,
                             chunks))
    out.update(_check_stage_table(cfg.replay.prioritized, mesh=True))

    ring = carry.replay.ring if cfg.replay.prioritized else carry.replay
    # The acting observation: carried beside the env state, or — an env
    # that holds it (envs/base.py observe) — inside it.
    from dist_dqn_tpu.envs import make_jax_env
    held = make_jax_env(cfg.env_name).observe(carry.env_state)
    for name, leaf in (("replay.obs", jax.tree.leaves(ring.obs)[0]),
                       ("obs", jax.tree.leaves(carry.obs)[0]
                        if held is None else held)):
        shards = leaf.addressable_shards
        holders = len({s.device for s in shards})
        check(holders == num_devices, f"{name} lives on {holders} devices")
        check(len({s.data.shape for s in shards}) == 1
              and math.prod(shards[0].data.shape) * num_devices
              == math.prod(leaf.shape),
              f"{name} shards {[s.data.shape for s in shards]} do not "
              f"split {leaf.shape} evenly")
        out[f"{name}_shard_shape"] = list(shards[0].data.shape)

    leaves = jax.tree.leaves(carry.learner.params)
    for leaf in leaves:
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        check(len(copies) == num_devices
              and all(c.shape == leaf.shape for c in copies),
              "learner params are not replicated on every device")
        check(all(np.array_equal(copies[0], c) for c in copies[1:]),
              "learner params differ between devices after grad steps")
        check(bool(np.all(np.isfinite(copies[0].astype(np.float32)))),
              "non-finite learner params")
    out["replicated_param_leaves"] = len(leaves)

    stats = [d.memory_stats() for d in jax.devices()[:num_devices]]
    if all(s and "bytes_in_use" in s for s in stats):
        in_use = [s["bytes_in_use"] for s in stats]
        out["bytes_in_use_per_device"] = in_use
        check(max(in_use) <= 1.25 * min(in_use),
              f"per-device bytes_in_use are uneven: {in_use}")
    return out


def main() -> int:
    from dist_dqn_tpu.utils import backend

    cache_dir = backend.enable_compile_cache()
    device = backend.require_accelerator()
    print(json.dumps({"device": device, "compile_cache_dir": cache_dir}),
          flush=True)
    meter = CompileMeter()
    legs = [leg_fused, leg_kernel]
    if device["count"] >= 4:
        legs.append(leg_mesh)
    for leg in legs:
        print(json.dumps(leg(meter)), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
