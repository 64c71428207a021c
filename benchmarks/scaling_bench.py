"""n-chip scale-out measurement (ISSUE 10): the row that makes the
trajectory measure SCALE-OUT, not just single-chip rate.

Two legs, one emit-once JSON row (the contract.py ContractEmitter
discipline):

* **host-replay dp leg** — the same tiny run at ``dp=1`` and ``dp=N``
  (``run_host_replay --mesh-devices``): aggregate and PER-CHIP
  env-steps/sec and grad-steps/sec, so the row answers "what did the
  extra chips buy" instead of hiding the division. A virtual CPU mesh
  shares the host's cores, so dpN/dp1 near 1.0 is the honest
  expectation there — that row records that the mechanism works; only
  a run on real chips records scaling.
* **apex ingest-shard leg** — a real 4-actor fleet into a 2-shard
  store: ``records_by_shard`` / ``replay_added_by_shard`` prove the
  sticky crc32 spread end to end (skippable with --skip-apex; actor
  processes need ~30s even at tiny sizes).

Usage:
  python benchmarks/scaling_bench.py [--allow-cpu]
      [--force-host-devices 8] [--dp 0] [--chunks 12]
      [--chunk-iters 100] [--lanes 8] [--skip-apex]

``--force-host-devices N`` must be honored BEFORE jax initializes, so
pass it on the command line (not via an env var set after import).
tests/test_chip_benches.py smokes the CPU path so the harness cannot
bit-rot.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--force-host-devices", type=int, default=0,
                   help="CPU smoke: fake this many host devices "
                        "(XLA --xla_force_host_platform_device_count; "
                        "must be set before jax initializes, which is "
                        "why it is a flag here and not an env you "
                        "export after)")
    p.add_argument("--dp", type=int, default=0,
                   help="mesh width for the scaled leg (0 = all "
                        "devices)")
    p.add_argument("--lanes", type=int, default=8)
    p.add_argument("--chunks", type=int, default=12)
    p.add_argument("--chunk-iters", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--skip-apex", action="store_true",
                   help="skip the actor-fleet ingest-shard leg "
                        "(sub-second CI smokes)")
    return p.parse_args()


def _host_replay_leg(cfg, total, chunk_iters, dp):
    from dist_dqn_tpu.host_replay_loop import run_host_replay

    out = run_host_replay(cfg, total_env_steps=total,
                          chunk_iters=chunk_iters,
                          log_fn=lambda s: None, mesh_devices=dp)
    return {
        # The run's chunk walls by cause (telemetry/devtime.py
        # UtilizationLedger).
        "chip_time": out["chip_time"],
        "dp_size": out["dp_size"],
        "env_steps_per_sec": out["env_steps_per_sec"],
        "grad_steps_per_sec": out["grad_steps_per_sec"],
        "env_steps_per_sec_per_chip": round(
            out["env_steps_per_sec"] / out["dp_size"], 1),
        "grad_steps_per_sec_per_chip": round(
            out["grad_steps_per_sec"] / out["dp_size"], 1),
        "grad_steps": out["grad_steps"],
        "param_checksum": out["param_checksum"],
        # Collect-scaling arm inputs (ISSUE 15): acting-side provenance
        # + the per-shard conservation evidence.
        "sharded_collect": out["sharded_collect"],
        # ISSUE 18: which PER backend served the run's draws — "device"
        # (per-shard priority planes) or "tree" (host sum-trees);
        # "uniform" when PER is off.
        "sampler": out["sampler"],
        "collect_lane_block": out["collect_lane_block"],
        "collect_dispatch_s_total": out["collect_dispatch_s_total"],
        "d2h_bytes_total": out["d2h_bytes_total"],
        "d2h_bytes_by_shard": out["d2h_bytes_by_shard"],
        "ring_bytes_by_shard": out["ring_bytes_by_shard"],
        "wall_s": out["wall_s"],
        "env_steps": out["env_steps"],
    }


def _collect_arm(dp1_leg, dpn_leg, dp):
    """The collect-scaling arm (ISSUE 15): the dp1-vs-dpN row finally
    measures ACTING throughput, not just grad throughput — per-shard
    collect/evac rates plus the zero-cross-shard-scatter proof: each
    shard's own device evacuated exactly the bytes its own ring
    appended, all shards equal, summing to the run total."""
    per_shard = dpn_leg["d2h_bytes_by_shard"] or []
    ring_shard = dpn_leg["ring_bytes_by_shard"] or []
    conserved = (
        len(per_shard) == dp
        and per_shard == ring_shard
        and len(set(per_shard)) == 1
        and sum(per_shard) == dpn_leg["d2h_bytes_total"])
    wall = max(dpn_leg["wall_s"], 1e-9)
    return {
        "sharded": dpn_leg["sharded_collect"],
        "sampler": dpn_leg["sampler"],
        "lane_block": dpn_leg["collect_lane_block"],
        # Acting-side rates: aggregate env-steps/sec over the mesh and
        # each shard's share (equal lane blocks => equal shares; the
        # aggregate-vs-dp1 ratio is what the extra actor-devices buy).
        "env_steps_x_vs_dp1": round(
            dpn_leg["env_steps_per_sec"]
            / max(dp1_leg["env_steps_per_sec"], 1e-9), 3),
        "per_shard_env_steps_per_sec": round(
            dpn_leg["env_steps_per_sec"] / dp, 1),
        "per_shard_evac_bytes_per_sec": [
            round(b / wall, 1) for b in per_shard],
        "collect_dispatch_s_total": dpn_leg["collect_dispatch_s_total"],
        "d2h_bytes_by_shard": per_shard,
        "ring_bytes_by_shard": ring_shard,
        "d2h_bytes_conserved_per_shard": conserved,
    }


def main() -> int:
    args = _parse_args()
    if args.force_host_devices:
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{args.force_host_devices}").strip()

    from contract import ContractEmitter

    from dist_dqn_tpu.utils.backend import select_platform

    contract = ContractEmitter(
        "dp_scaling",
        "aggregate + per-chip env-steps/sec and grad-steps/sec over the "
        "dp mesh (host-replay runtime), with the apex sticky-shard "
        "ingest spread")

    try:
        import jax

        platforms = select_platform(args.allow_cpu)

        from dist_dqn_tpu.config import CONFIGS

        dp = args.dp or len(jax.devices())
        if dp < 2:
            contract.error("mesh", f"only {len(jax.devices())} device(s) "
                           "— a scaling row needs >= 2 (CPU smoke: "
                           "--force-host-devices 8)")
            return 1
        lanes = args.lanes - args.lanes % dp or dp
        # The train batch must divide over the mesh too (each shard
        # draws an equal row block): round UP to a multiple of dp so a
        # 32-device slice widens the batch instead of failing the
        # divisibility gate.
        batch = -(-args.batch_size // dp) * dp
        cfg = CONFIGS["cartpole"]
        cfg = dataclasses.replace(
            cfg,
            actor=dataclasses.replace(cfg.actor, num_envs=lanes),
            network=dataclasses.replace(cfg.network, torso="mlp",
                                        mlp_features=(64, 64), hidden=0,
                                        compute_dtype="float32"),
            replay=dataclasses.replace(cfg.replay, capacity=65536,
                                       min_fill=256, prioritized=False),
            learner=dataclasses.replace(cfg.learner, batch_size=batch),
        )
        total = args.chunks * args.chunk_iters * lanes
        legs = {
            "dp1": _host_replay_leg(cfg, total, args.chunk_iters, 1),
            f"dp{dp}": _host_replay_leg(cfg, total, args.chunk_iters,
                                        dp),
        }
        dpn = legs[f"dp{dp}"]
        scaling = {
            "env_steps_x": round(dpn["env_steps_per_sec"]
                                 / max(legs["dp1"]["env_steps_per_sec"],
                                       1e-9), 3),
            "grad_steps_x": round(dpn["grad_steps_per_sec"]
                                  / max(legs["dp1"]["grad_steps_per_sec"],
                                        1e-9), 3),
        }
        collect = _collect_arm(legs["dp1"], dpn, dp)
        if not collect["d2h_bytes_conserved_per_shard"]:
            contract.error(
                "collect",
                "per-shard D2H bytes not conserved: evacuated "
                f"{collect['d2h_bytes_by_shard']} vs ring-appended "
                f"{collect['ring_bytes_by_shard']} (total "
                f"{dpn['d2h_bytes_total']}) — a lane block crossed "
                "shards or was lost")
            return 1
        apex = None
        if not args.skip_apex:
            from dist_dqn_tpu.actors.service import (ApexRuntimeConfig,
                                                     run_apex)
            rt = ApexRuntimeConfig(
                host_env="CartPole-v1", num_actors=4, envs_per_actor=2,
                total_env_steps=2000, ingest_shards=2)
            acfg = dataclasses.replace(
                cfg, replay=dataclasses.replace(cfg.replay,
                                                capacity=4096,
                                                min_fill=128))
            aout = run_apex(acfg, rt, log_fn=lambda s: None)
            apex = {
                "ingest_shards": 2,
                "records_by_shard": aout["records_by_shard"],
                "replay_added_by_shard": aout["replay_added_by_shard"],
                "grad_steps": aout["grad_steps"],
            }
        contract.emit_payload({
            "metric": "dp_scaling", "unit": contract.unit,
            "value": scaling["grad_steps_x"],
            "platform": jax.default_backend(),
            "dp_size": dp,
            "host_replay": legs,
            "scaling": scaling,
            "collect": collect,
            "apex": apex,
        })
        return 0
    except Exception as e:  # noqa: BLE001 — the contract wants one line
        contract.error("run", f"{type(e).__name__}: {e}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
