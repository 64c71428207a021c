"""Host-DRAM replay hybrid loop on chip: throughput + byte-stream costs.

Measures ``host_replay_loop.run_host_replay`` — device env chunks,
host-DRAM window, device learner — at bounded sizes and reports
env-steps/s beside the per-chunk D2H/H2D byte streams, so the cost of
moving the replay window off-chip is attributable. The module
docstring of host_replay_loop.py carries the TPU-VM link model
(~10 GB/s => ~1.4M deduped env-steps/s admissible), and the byte
columns this bench emits are what make that model checkable.

``--ab`` (ISSUE 3, re-armed for ISSUE 5's sample side) runs THREE legs
at the SAME sizes in one process (compiles cached between them):
uniform sampling with the serial sample-in-loop path
(``--no-prefetch``), uniform sampling with the background
SamplePrefetcher, and prioritized (PER) sampling with the prefetcher.
The ``trace_ab`` row carries the steady rates and speedups, the
prefetch overlap accounting (``sample_s`` measured off the critical
path: the prefetch leg's ``prefetch_wait_s`` against the serial leg's
``sample_s``), D2H byte conservation across all legs, the PER leg's
write-back volume + IS-weight sanity, and the uniform numerics pin
(serial and prefetched legs must produce an identical
``param_checksum``) — the same before/after discipline as
``apex_feeder_bench --trace``. tests/test_host_replay_pipeline.py runs
it as a tier-1 CPU smoke so the A/B harness cannot bit-rot.

Usage: python benchmarks/host_replay_bench.py [--allow-cpu] [--ab]
           [--lanes 64] [--chunks 10] [--chunk-iters 100]
           [--evac-slices 4] [--no-pipeline] [--no-prefetch] [--per]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from dist_dqn_tpu.utils.backend import select_platform  # noqa: E402


def _emit(row) -> None:
    print(json.dumps(row), flush=True)


def _steady_fields(out) -> dict:
    hist = out.get("history") or []
    steady = hist[-1] if hist else {}
    return {
        "steady_env_steps_per_sec": steady.get("env_steps_per_sec"),
        "steady_env_steps_per_sec_loop":
            steady.get("env_steps_per_sec_loop"),
        "steady_d2h_bytes_per_chunk": steady.get("d2h_bytes"),
        "steady_evac_s": steady.get("evac_s"),
        "steady_evac_fence_wait_s": steady.get("evac_fence_wait_s"),
        "steady_evac_overlap_frac": steady.get("evac_overlap_frac"),
        "steady_train_s": steady.get("chunk_train_s"),
        "steady_collect_fetch_s": steady.get("chunk_collect_fetch_s"),
        "steady_sample_s": steady.get("sample_s"),
        "steady_prefetch_wait_s": steady.get("prefetch_wait_s"),
        "steady_prefetch_depth": steady.get("prefetch_depth"),
    }


def _lineage_fields() -> dict:
    """Experience-lineage staleness quantiles (ISSUE 16). The loop ages
    each sampled batch's birth/version stamps into the shared lineage
    histograms at draw time; the quantiles here are cumulative over the
    process (in ``--ab`` mode, over all legs so far)."""
    import dist_dqn_tpu.telemetry.collectors as tmc
    age_h, stale_h = tmc.lineage_histograms("host_replay")
    if not age_h.count:
        return {}
    return {
        "sample_age_p50_s": round(tmc.histogram_quantile(age_h, 0.5), 6),
        "sample_age_p99_s": round(tmc.histogram_quantile(age_h, 0.99), 6),
        "staleness_versions_p99":
            round(tmc.histogram_quantile(stale_h, 0.99), 2),
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--lanes", type=int, default=64)
    p.add_argument("--chunks", type=int, default=10)
    p.add_argument("--chunk-iters", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--train-every", type=int, default=8)
    p.add_argument("--no-pipeline", action="store_true",
                   help="measure the serial monolithic-evacuation "
                        "reference instead of the pipelined runtime")
    p.add_argument("--evac-slices", type=int, default=4)
    p.add_argument("--no-prefetch", action="store_true",
                   help="measure the serial sample-in-loop reference "
                        "instead of the background SamplePrefetcher")
    p.add_argument("--prefetch-depth", type=int, default=2)
    p.add_argument("--per", action="store_true",
                   help="sample the host window by sum-tree priority "
                        "(IS weights + batched TD write-backs) instead "
                        "of uniformly")
    p.add_argument("--ab", action="store_true",
                   help="run uniform-serial, uniform-prefetch and "
                        "PER-prefetch at the same sizes and emit a "
                        "trace_ab comparison row (rates, prefetch "
                        "overlap, byte conservation, write-back volume, "
                        "uniform numerics pin)")
    p.add_argument("--window", type=int, default=1_048_576,
                   help="host-DRAM window in transitions (DRAM-priced: "
                        "1M deduped pixel transitions ~ 0.45 GB/lane-KB)")
    args = p.parse_args()

    platforms = select_platform(args.allow_cpu)
    if args.allow_cpu:
        args.lanes, args.chunks = min(args.lanes, 8), min(args.chunks, 3)
        args.chunk_iters = min(args.chunk_iters, 30)
        args.batch_size = min(args.batch_size, 16)
        args.window = min(args.window, 8_192)

    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    cfg = CONFIGS["atari"]
    cfg = dataclasses.replace(
        cfg,
        env_name="pixel_pong",
        network=dataclasses.replace(
            cfg.network,
            **({"torso": "small", "hidden": 32,
                "compute_dtype": "float32"} if args.allow_cpu else {})),
        actor=dataclasses.replace(cfg.actor, num_envs=args.lanes),
        replay=dataclasses.replace(cfg.replay, capacity=args.window,
                                   min_fill=args.batch_size * 4,
                                   frame_dedup=True),
        learner=dataclasses.replace(cfg.learner,
                                    batch_size=args.batch_size),
        train_every=args.train_every,
    )
    total = args.chunks * args.chunk_iters * args.lanes

    def _measure(pipeline: bool, prefetch: bool = True,
                 per: bool = False):
        t0 = time.perf_counter()
        out = run_host_replay(cfg, total_env_steps=total,
                              chunk_iters=args.chunk_iters,
                              log_fn=lambda s: print(s, flush=True),
                              pipeline=pipeline,
                              evac_slices=args.evac_slices,
                              prefetch=prefetch,
                              prefetch_depth=args.prefetch_depth,
                              prioritized=per)
        return out, time.perf_counter() - t0

    def _row(out, wall, **extra):
        steady = _steady_fields(out)
        out = dict(out)
        out.pop("history", None)
        return {
            **out,  # run summary first: bench-side fields below override
            "bench": "host_replay", "platforms": platforms,
            "lanes": args.lanes, "chunk_iters": args.chunk_iters,
            "batch_size": args.batch_size, "train_every": args.train_every,
            "frame_dedup": True,
            "window_transitions": out["window_transitions_max"],
            "wall_s_incl_setup": round(wall, 1),
            **steady, **_lineage_fields(), **extra,
        }

    if args.ab:
        # Each leg builds its own jit wrappers (run_host_replay creates
        # fresh closures), so every leg pays compiles — the headline
        # speedups therefore compare the STEADY last-chunk rates, which
        # exclude compile wall by construction; the whole-run rates are
        # emitted beside them for the compile-inclusive picture. The
        # D2H axis stays pipelined in all three legs (ISSUE 3's
        # serial-vs-pipelined pin lives in
        # tests/test_host_replay_pipeline.py); the A/B axis here is the
        # SAMPLE side: serial sample-in-loop vs prefetched vs
        # prefetched+prioritized.
        pipeline = not args.no_pipeline
        out_a, wall_a = _measure(pipeline, prefetch=False)
        _emit(_row(out_a, wall_a, phase="ab_uniform_serial"))
        out_b, wall_b = _measure(pipeline, prefetch=True)
        _emit(_row(out_b, wall_b, phase="ab_uniform_prefetch"))
        out_c, wall_c = _measure(pipeline, prefetch=True, per=True)
        _emit(_row(out_c, wall_c, phase="ab_per_prefetch"))

        def _steady(out):
            return out["history"][-1]["env_steps_per_sec"] \
                if out["history"] else out["env_steps_per_sec"]

        steady_a, steady_b, steady_c = (_steady(out_a), _steady(out_b),
                                        _steady(out_c))
        _emit({
            "bench": "host_replay", "phase": "trace_ab",
            "platforms": platforms, "total_env_steps": total,
            "serial_env_steps_per_sec": steady_a,
            "prefetch_env_steps_per_sec": steady_b,
            "per_env_steps_per_sec": steady_c,
            "serial_env_steps_per_sec_avg": out_a["env_steps_per_sec"],
            "prefetch_env_steps_per_sec_avg": out_b["env_steps_per_sec"],
            "per_env_steps_per_sec_avg": out_c["env_steps_per_sec"],
            "speedup_prefetch_x": round(steady_b / max(steady_a, 1e-9),
                                        3),
            "speedup_per_x": round(steady_c / max(steady_a, 1e-9), 3),
            # Prefetch overlap: the serial leg pays sample_s on the
            # critical path; the prefetch legs pay only the residual
            # main-thread wait for the background thread.
            "serial_sample_s_total": out_a["sample_s_total"],
            "prefetch_sample_s_total": out_b["sample_s_total"],
            "prefetch_wait_s_total": out_b["prefetch_wait_s_total"],
            "per_prefetch_wait_s_total": out_c["prefetch_wait_s_total"],
            "prefetch_overlap_frac": round(
                max(0.0, 1.0 - out_b["prefetch_wait_s_total"]
                    / max(out_b["sample_s_total"], 1e-9)), 4),
            "sample_off_critical_path":
                out_b["prefetch_wait_s_total"]
                < out_a["sample_s_total"],
            "stale_batches": out_b["stale_batches"]
            + out_c["stale_batches"],
            # PER leg health: write-backs actually flowed, IS weights
            # are sane (normalized into (0, 1]).
            "per_prio_writeback_flushes":
                out_c["prio_writeback_flushes"],
            "per_prio_writeback_rows": out_c["prio_writeback_rows"],
            "per_prio_writeback_dropped":
                out_c["prio_writeback_dropped"],
            "per_is_weight_mean": out_c["is_weight_mean"],
            "per_is_weight_min": out_c["is_weight_min"],
            "d2h_bytes_serial": out_a["d2h_bytes_total"],
            "d2h_bytes_prefetch": out_b["d2h_bytes_total"],
            "d2h_bytes_per": out_c["d2h_bytes_total"],
            "d2h_bytes_conserved":
                out_a["d2h_bytes_total"] == out_b["d2h_bytes_total"]
                == out_c["d2h_bytes_total"],
            "evac_overlap_frac_mean": out_b["evac_overlap_frac_mean"],
            "serial_param_checksum": out_a["param_checksum"],
            "prefetch_param_checksum": out_b["param_checksum"],
            # The uniform numerics pin: prefetching may only change
            # WHEN sampling happens, never what is trained on. (The
            # PER leg legitimately trains on different batches.)
            "numerics_match":
                out_a["param_checksum"] == out_b["param_checksum"]
                and out_a["grad_steps"] == out_b["grad_steps"],
        })
        return 0

    out, wall = _measure(pipeline=not args.no_pipeline,
                         prefetch=not args.no_prefetch, per=args.per)
    _emit(_row(out, wall))
    return 0


if __name__ == "__main__":
    sys.exit(main())
