"""Priority-sampling microbenchmark: Pallas vs XLA vs the C++ host tree.

For each shard size this script times, on the accelerator (or on the
platform ``--platform`` names; every row names its platform),

  * ``pallas``  — ops/pallas_sampler.pallas_stratified_sample (VMEM
    kernel; TPU only — skipped on CPU, where only interpret mode exists
    and timing it would measure the interpreter),
  * ``xla``     — the portable cumsum+searchsorted path of
    ops/pallas_sampler.stratified_sample,
  * ``host_cpp``— replay/_native/sumtree.cc on the learner-step workload
    (sample S + 2x set S — priority write-back and new-item insert),
  * ``sharded`` — ISSUE 18: per-shard DevicePrioritySampler planes
    (cells/shards each, one train event = batched write-back + fused
    draw per shard) vs ONE host tree serving the mesh's aggregate
    demand; reports per-shard, wall- and mesh-aggregate draws/sec
    (reading rule: docs/performance.md "sampling scales with the mesh"),

and prints one JSON line per (impl, size): median/min seconds per draw.

Device timings fence with a ``device_get`` on a kernel output.

Usage:
  python benchmarks/sampler_bench.py                 # the accelerator
  python benchmarks/sampler_bench.py --platform cpu  # force CPU (no pallas)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

LANES = 512          # env lanes (B) — the apex service's act-batch width
DEFAULT_CELLS = (16_384, 131_072, 1_048_576)  # 1e4..1e6 cells


def _timed(fn, iters: int) -> dict:
    """Median/min of ``iters`` timed calls; fn must fence internally."""
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return {"median_s": round(float(np.median(times)), 6),
            "min_s": round(float(np.min(times)), 6)}


def bench_device(jax, cells: int, batch: int, iters: int,
                 use_pallas: bool, amortize: int = 1) -> dict:
    import jax.numpy as jnp

    from dist_dqn_tpu.ops.pallas_sampler import stratified_sample

    T = cells // LANES
    r = np.random.default_rng(0)
    # Ape-X-shaped mass plane: TD-priority^alpha values, heavy-tailed.
    w = jnp.asarray(np.abs(r.standard_cauchy((T * LANES,)))
                    .astype(np.float32) ** 0.6)

    def make_draw(n_draws: int):
        if n_draws == 1:
            @jax.jit
            def draw(w, rng):
                return stratified_sample(w, rng, batch, LANES,
                                         use_pallas=use_pallas)[0]
            return draw

        # Chain ``n_draws`` sample+priority-write-back steps (the
        # learner-step pattern) inside ONE jit: the scan body compiles
        # once, data never leaves the device, and carrying ``w`` keeps the
        # mass plane loop-variant so XLA cannot hoist the cumsum out of
        # the scan (standalone it is loop-invariant, which would
        # unrealistically favor the XLA path).
        @jax.jit
        def draw(w, rng):
            def body(w, k):
                t_idx, b_idx, p_sel, _ = stratified_sample(
                    w, k, batch, LANES, use_pallas=use_pallas)
                return w.at[t_idx * LANES + b_idx].set(p_sel * 0.999), None
            w, _ = jax.lax.scan(body, w, jax.random.split(rng, n_draws))
            return w[0]
        return draw

    def timed_at(n_draws: int) -> dict:
        draw = make_draw(n_draws)
        keys = [jax.random.PRNGKey(1000 * n_draws + i)
                for i in range(iters + 2)]
        for k in keys[:2]:  # compile + cached-dispatch warmup
            jax.device_get(draw(w, k))
        it = iter(keys[2:])

        def one():
            jax.device_get(draw(w, next(it)))  # fence on an output

        return _timed(one, iters)

    if amortize <= 1:
        return timed_at(1)

    # Dividing one K-draw scan's time by K folds the dispatch+fence
    # constant into every draw. Two-point marginal cost subtracts it:
    # time the scan at K and 2K draws, report (t_2K - t_K) / K per draw.
    lo, hi = timed_at(amortize), timed_at(2 * amortize)
    return {
        "marginal_s": round((hi["median_s"] - lo["median_s"]) / amortize, 8),
        "dispatch_s": round(2 * lo["median_s"] - hi["median_s"], 6),
        "median_lo_s": lo["median_s"], "median_hi_s": hi["median_s"],
    }


def _shard_event(s, cells: int, batch: int, u, wi, wv):
    """One train event against a shard's plane: priority write-back
    (ONE batched scatter) + the stratified draw (ONE fused dispatch) +
    host materialization — the per-event device-sampling hot path."""
    s.set(wi, wv)
    return s.materialize_at(s.dispatch_at(u), cells)


def bench_sharded(jax, cells: int, shards: int, batch: int, iters: int,
                  one_shard_rate: float, host_rate: float) -> dict:
    """ISSUE 18 arm: ``shards`` per-shard device priority planes, each
    holding ``cells // shards`` cells and serving its own learner
    replica's ``batch`` draws + write-backs per event — against ONE
    host tree serving the same aggregate demand (``host_rate``).

    Reports BOTH aggregates (reading rule in docs/performance.md):

    * ``wall_agg_draws_per_s`` — shards*batch over the measured wall of
      one concurrent round. Honest for THIS host: on a 1-core CPU
      container the per-shard programs serialize, so this under-reports
      a real mesh (``cpus`` is in the row for exactly that judgement).
    * ``mesh_agg_draws_per_s`` — sum of per-shard rates, each shard
      timed solo: the aggregate a mesh with one chip per shard
      delivers, since each plane's work runs entirely on its own
      sticky device and the host only enqueues. This is the
      scales-with-the-mesh number the TPU procedure measures as true
      wall clock.
    """
    from dist_dqn_tpu.replay.host import DevicePrioritySampler

    devs = jax.devices()
    shard_cells = cells // shards
    r = np.random.default_rng(0)
    samplers = []
    for i in range(shards):
        s = DevicePrioritySampler(shard_cells, seed=i,
                                  device=devs[i % len(devs)], shard=i)
        prios = np.abs(r.standard_cauchy(shard_cells)
                       ).astype(np.float64) ** 0.6
        s.set(np.arange(shard_cells), prios)
        s._flush_writes()
        samplers.append(s)
    u = (np.arange(batch) + r.random(batch)) / batch
    rounds = 2 * (iters + 5)
    wi = r.integers(0, shard_cells, (rounds, shards, batch))
    wv = np.abs(r.standard_cauchy((rounds, shards, batch))) ** 0.6
    k = [0]  # round cursor shared by warmup and timed calls

    # Per-shard solo medians -> the mesh aggregate.
    per_shard = []
    for j, s in enumerate(samplers):
        def one(j=j, s=s):
            _shard_event(s, shard_cells, batch, u,
                         wi[k[0] % rounds, j], wv[k[0] % rounds, j])
            k[0] += 1

        for _ in range(5):
            one()
        per_shard.append(_timed(one, iters)["median_s"])

    # Concurrent round -> the single-host wall aggregate: every shard's
    # write-back + draw dispatched before the first materialization.
    def one_round():
        i = k[0] % rounds
        k[0] += 1
        handles = []
        for j, s in enumerate(samplers):
            s.set(wi[i, j], wv[i, j])
            handles.append(s.dispatch_at(u))
        for s, h in zip(samplers, handles):
            s.materialize_at(h, shard_cells)

    for _ in range(5):
        one_round()
    wall = _timed(one_round, iters)
    mesh_agg = sum(batch / t for t in per_shard)
    wall_agg = shards * batch / wall["median_s"]
    return {
        "shards": shards, "shard_cells": shard_cells,
        "per_shard_event_s": [round(t, 6) for t in per_shard],
        "wall_event_s": wall["median_s"],
        "mesh_agg_draws_per_s": round(mesh_agg),
        "wall_agg_draws_per_s": round(wall_agg),
        "one_shard_draws_per_s": round(one_shard_rate),
        "host_cpp_draws_per_s": round(host_rate),
        "mesh_speedup_vs_host_cpp": round(mesh_agg / host_rate, 3),
        "cpus": os.cpu_count(),
        "devices": len(devs),
    }


def bench_host_cpp(cells: int, batch: int, iters: int) -> dict:
    from dist_dqn_tpu.replay.host import make_sum_tree

    tree = make_sum_tree(cells, native=True)
    r = np.random.default_rng(0)
    prios = np.abs(r.standard_cauchy(cells)).astype(np.float64) ** 0.6
    tree.set(np.arange(cells, dtype=np.int64), prios)
    new_vals = np.abs(r.standard_cauchy((iters, batch))) ** 0.6
    u = r.random((iters, batch))
    it = iter(range(iters))

    def one():
        # The learner-step workload: one stratified
        # sample + priority write-back + new-item priority insert.
        i = next(it)
        mass = (np.arange(batch) + u[i]) / batch * tree.total
        idx = tree.sample(mass)
        tree.set(idx, new_vals[i])
        tree.set(idx, new_vals[i])

    return _timed(one, iters)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cells", type=int, nargs="*", default=DEFAULT_CELLS)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--platform", default=None)
    p.add_argument("--amortize", type=int, default=1,
                   help="two-point marginal mode: time K- and 2K-draw "
                        "scans per dispatch and report (t_2K-t_K)/K as "
                        "marginal_s — per-draw kernel time with the "
                        "dispatch constant subtracted")
    p.add_argument("--impls", nargs="*",
                   default=["pallas", "xla", "host_cpp", "sharded"])
    p.add_argument("--shards", type=int, nargs="*", default=[2, 4],
                   help="sharded-arm mesh widths (ISSUE 18): per-shard "
                        "device planes of cells/shards each")
    p.add_argument("--shard-batch", type=int, default=1024,
                   help="sharded-arm per-shard (per learner replica) "
                        "draw batch; the host tree serves "
                        "shards*shard_batch per event")
    args = p.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    else:
        # No platform named: an accelerator, never a silent CPU run.
        from dist_dqn_tpu.utils.backend import require_accelerator
        require_accelerator()
    platform = jax.devices()[0].platform

    for cells in args.cells:
        for impl in args.impls:
            if impl == "pallas" and platform == "cpu":
                continue  # interpret mode would time the interpreter
            if impl == "sharded":
                # One row per (cells, shards) point, each carrying its
                # own 1-shard and host_cpp references: the host tree
                # serves the mesh's AGGREGATE demand (shards * batch
                # draws + write-backs per event) from one thread — the
                # serialized resource the per-shard planes remove.
                one = bench_sharded(jax, cells, 1, args.shard_batch,
                                    args.iters, 1.0, 1.0)
                one_rate = one["mesh_agg_draws_per_s"]
                for shards in args.shards:
                    if shards < 2 or cells % shards:
                        continue
                    host = bench_host_cpp(cells,
                                          shards * args.shard_batch,
                                          args.iters)
                    host_rate = (shards * args.shard_batch
                                 / host["median_s"])
                    out = bench_sharded(jax, cells, shards,
                                        args.shard_batch, args.iters,
                                        one_rate, host_rate)
                    out.update(impl=impl, cells=cells, lanes=LANES,
                               batch=args.shard_batch, sampler="device",
                               platform=platform)
                    print(json.dumps(out), flush=True)
                continue
            if impl == "host_cpp":
                out = bench_host_cpp(cells, args.batch, args.iters)
            else:
                out = bench_device(jax, cells, args.batch, args.iters,
                                   use_pallas=(impl == "pallas"),
                                   amortize=args.amortize)
                if args.amortize > 1:
                    out["amortize"] = args.amortize
            out.update(impl=impl, cells=cells, lanes=LANES,
                       batch=args.batch, platform=platform)
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
