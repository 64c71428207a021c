"""Chip-side Ape-X service ceiling: in-RAM feeders, no emulator.

The end-to-end split bench (apex_split_bench.py) measures the host's
CPU cores running emulator + preprocessing + actors + service — the
chip-side service idle-waits, so how many records/s the TPU-side service
can sustain when the host side is NOT starved stays unmeasured there.
This bench replaces the
rollout actors with ``actors/feeder.py`` processes that replay
pre-generated, pre-encoded trajectory records through the PRODUCTION
shm transport at maximum rate; everything downstream is the production
service — ``_drain_transports`` -> batched act -> C++ n-step assembly ->
|TD| priority bootstrap -> PER insert -> bounded train passes ->
priority write-back.

Reported per variant: sustained records/s, env-steps/s-equivalent
(records x lanes), grad-steps/s, and the cadence debt (whether the
learner kept the configured inserts-per-grad ratio at that ingest rate
— if not, trains-flat-out is the ceiling's meaning, standard Ape-X
semantics).

Honesty note: feeders and service share the host's cores, so the
feeder-side memcpy pump can steal service CPU — the measured ceiling is
a LOWER bound on what the service does with dedicated cores. The
emulator/preprocessing cost (the thing the split bench is bound by) is
gone, which is the point.

Sizing: the probe phase pays all compiles and measures the achievable
rate; the measure phase's frame budget is derived from it.

Usage:  python benchmarks/apex_feeder_bench.py [--allow-cpu]
            [--variants pixel vector] [--measure-seconds 120]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from dist_dqn_tpu.utils.backend import select_platform  # noqa: E402


def _configs(variant: str, smoke: bool):
    """(cfg, rt_kwargs, probe_total) per variant; probe sizes only —
    the measure phase is sized from the probe's measured rate."""
    from dist_dqn_tpu.config import CONFIGS

    if variant == "pixel":
        cfg = CONFIGS["apex"]
        cfg = dataclasses.replace(
            cfg,
            # Host-DRAM shard: 200k pixel slots ~ 5.6 GB on this box
            # (the 1M-slot pod shard would fit the 125 GB DRAM too, but
            # prefilling it would dominate the bench; C++ sum-tree cost
            # is measured separately and near-flat in shard size).
            replay=dataclasses.replace(
                cfg.replay, capacity=200_000 if not smoke else 8_192,
                min_fill=2_000 if not smoke else 200),
            learner=dataclasses.replace(
                cfg.learner, batch_size=512 if not smoke else 32),
        )
        rt_kwargs = dict(host_env="feeder:pixel", num_actors=2,
                         envs_per_actor=8)
        probe_total = 20_000 if not smoke else 1_000
    elif variant == "vector":
        cfg = CONFIGS["apex"]
        cfg = dataclasses.replace(
            cfg,
            network=dataclasses.replace(cfg.network, torso="mlp",
                                        mlp_features=(256, 256), hidden=0,
                                        compute_dtype="float32"),
            replay=dataclasses.replace(
                cfg.replay, capacity=500_000 if not smoke else 8_192,
                min_fill=2_000 if not smoke else 200),
            learner=dataclasses.replace(
                cfg.learner, batch_size=512 if not smoke else 32),
        )
        rt_kwargs = dict(host_env="feeder:vector", num_actors=2,
                         envs_per_actor=16)
        probe_total = 60_000 if not smoke else 2_000
    else:
        raise ValueError(f"unknown variant {variant!r}")
    return cfg, rt_kwargs, probe_total


def _run(cfg, rt_kwargs, total: int, trace_path=None, **rt_extra):
    """One service run; returns (summary, wall_s, steady_rates)."""
    from dist_dqn_tpu.actors.service import ApexRuntimeConfig, run_apex

    rows = []

    def capture(line):
        try:
            rows.append(json.loads(line))
        except (TypeError, ValueError):
            pass

    rt = ApexRuntimeConfig(total_env_steps=total, log_every_s=5.0,
                           trace_path=trace_path, **rt_extra, **rt_kwargs)
    t0 = time.perf_counter()
    summary = run_apex(cfg, rt, log_fn=capture)
    wall = time.perf_counter() - t0
    rate_rows = [r for r in rows
                 if r.get("env_steps_per_sec_per_chip", 0) > 0]
    steady = rate_rows[-1] if rate_rows else {}
    return summary, wall, steady


def _roundtrip_fields(summary) -> dict:
    """Device round-trip accounting (ISSUE 2): the service counts every
    dispatched program by kind, reported per ingest pass."""
    return {
        "device_calls": summary["device_calls"],
        "ingest_passes": summary["ingest_passes"],
        "ingest_device_calls_per_pass":
            summary["ingest_device_calls_per_pass"],
    }


def _emit(row: dict) -> None:
    """Single bench-contract emission point (scripts/check_metrics.py)."""
    print(json.dumps(row), flush=True)


def _lineage_fields() -> dict:
    """Experience-lineage staleness quantiles (ISSUE 16). The service
    ages every sampled batch's wire birth/version stamps into the
    shared ``apex`` lineage histograms; quantiles are cumulative over
    the process (probe + measure legs)."""
    import dist_dqn_tpu.telemetry.collectors as tmc
    age_h, stale_h = tmc.lineage_histograms("apex")
    if not age_h.count:
        return {}
    return {
        "sample_age_p50_s": round(tmc.histogram_quantile(age_h, 0.5), 6),
        "sample_age_p99_s": round(tmc.histogram_quantile(age_h, 0.99), 6),
        "staleness_versions_p99":
            round(tmc.histogram_quantile(stale_h, 0.99), 2),
    }


# ---------------------------------------------------------------------------
# Transport A/B (ISSUE 9 + 14): legacy JSON codec vs zero-copy wire vs
# shm ring vs frame-dedup plane vs batched slot publishes
# ---------------------------------------------------------------------------

#: (obs_shape, obs_dtype, default A/B record count) per variant. Pixel
#: records are ~450 KB raw (84x84x4 uint8, obs + next_obs), vector ~600 B.
_AB_SPECS = {
    "pixel": ((84, 84, 4), "uint8", 300),
    "vector": ((4,), "float32", 4000),
}

#: records coalesced per slot publish in the shm_batched arm.
_AB_SHM_BATCH = 8

#: Load-proofing for the VECTOR TCP arms (known flake, recorded in
#: PR 14): small records make both TCP arms GIL/scheduler-bound, so on
#: a loaded box either arm can draw the short straw and the wall-clock
#: ratio flips run to run. The fix is the one
#: tests/test_native_assembler.py uses — BEST of up to N interleaved
#: samples with backoff: the arms run back-to-back inside one round
#: (a load spike hits both sides of the ratio), any one quiet window
#: is enough, and the byte/decode-CPU columns are deterministic so
#: only the best wall is kept per arm.
_AB_TCP_SAMPLES = 3


def _ab_pool(variant: str, lanes: int):
    """Per-record (arrays, q_sel, q_max) source stream for the A/B.

    Pixel streams are FRAME-STACKED like the real actor path (ISSUE 14):
    a cyclic ring of random frames, each record's stacks shifted by one
    frame from the previous — the redundancy every stacked pixel env
    actually ships, which the dedup plane exists to strip and which
    zlib cannot see (frames are spatially random and interleaved at
    stride ``frame_stack``). ``obs`` and ``next_obs`` are the SAME
    stack per record (the HostVectorEnv steady-state contract). Vector
    streams keep the independent-random pool (no frame axis — the
    dedup negotiation declines them, honestly).

    Returns (pool list, frame_stack or 0). Record i = pool[i % len].
    Cycling is seamless for dedup: stack windows over a cyclic frame
    ring keep shifting by one at the wrap.
    """
    import numpy as np

    obs_shape, obs_dtype, _ = _AB_SPECS[variant]
    obs_dtype = np.dtype(obs_dtype)
    rng = np.random.default_rng(0)
    pool = []
    if len(obs_shape) == 3 and obs_shape[-1] > 1:
        fs = obs_shape[-1]
        F = 48
        frames = rng.integers(
            0, 256, (F, lanes) + obs_shape[:-1]).astype(obs_dtype)
        for t in range(F):
            stack = np.stack([frames[(t + k) % F] for k in range(fs)],
                             axis=-1)
            pool.append((
                {"obs": stack,
                 "reward": rng.normal(size=(lanes,)).astype(np.float32),
                 "terminated": np.zeros((lanes,), np.uint8),
                 "truncated": np.zeros((lanes,), np.uint8),
                 "next_obs": stack},
                rng.normal(size=(lanes,)).astype(np.float32),
                rng.normal(size=(lanes,)).astype(np.float32)))
        return pool, fs

    def obs_batch():
        if obs_dtype == np.uint8:
            return rng.integers(0, 256, (lanes,) + obs_shape
                                ).astype(np.uint8)
        return rng.normal(size=(lanes,) + obs_shape).astype(obs_dtype)

    for _ in range(16):
        pool.append((
            {"obs": obs_batch(),
             "reward": rng.normal(size=(lanes,)).astype(np.float32),
             "terminated": np.zeros((lanes,), np.uint8),
             "truncated": np.zeros((lanes,), np.uint8),
             "next_obs": obs_batch()},
            rng.normal(size=(lanes,)).astype(np.float32),
            rng.normal(size=(lanes,)).astype(np.float32)))
    return pool, 0


def _transport_ab(variant: str, records: int, lanes: int):
    """Measure the EXPERIENCE PATH in isolation — encode -> transport ->
    decode, no learner — one arm per codec/transport combination:

      * ``legacy``      — today's remote-actor path exactly: JSON-header
        codec (compress="auto": pixel records ride zlib-1) over the
        CRC-framed TCP loopback;
      * ``zerocopy``    — the same TCP framing, zero-copy payloads
        (schema-negotiated raw bytes + q planes);
      * ``shm``         — zero-copy records through the seqlock slot
        ring (the same-host path; no socket stack at all);
      * ``dedup``       — the ISSUE 14 frame-dedup plane over TCP
        (pixel variants only: one novel frame per record, stacks
        reconstructed at decode);
      * ``shm_dedup``   — dedup records through the slot ring;
      * ``shm_batched`` — zero-copy records, ``_AB_SHM_BATCH`` per slot
        publish (the seqlock-handshake amortization arm).

    Producer encodes live in a thread (what an actor does every step),
    the consumer decodes every record; both share this box's core, so
    rates reflect the full per-record CPU the codec costs each side.
    Per-arm row: trajectories/sec (1 record = one vector-env step
    batch), bytes on the wire, the consumer's decode CPU-seconds
    (``decode_cpu_s`` — for dedup arms this INCLUDES the stack
    reconstruction; the plain arms' equivalent byte movement happens in
    the transport copy instead, which is why ``trajectories_per_sec``
    is the end-to-end number), and the dedup savings counters.
    """
    import threading

    from dist_dqn_tpu import ingest
    from dist_dqn_tpu.actors.transport import (_FRAME_HDR,
                                               TcpRecordClient,
                                               TcpRecordServer,
                                               decode_arrays,
                                               encode_arrays)

    obs_shape, obs_dtype, _ = _AB_SPECS[variant]
    pool, fs = _ab_pool(variant, lanes)
    pool_n = len(pool)
    schema = ingest.step_schema(obs_shape, obs_dtype, lanes)
    enc = ingest.StepEncoder(schema)
    dec = ingest.StepDecoder(schema)
    dedup_enc = ingest.DedupStepEncoder(schema, fs) if fs else None
    dedup_dec = [None]      # fresh per arm (stateful ring)

    def encode_legacy(i):
        arrays, _, _ = pool[i % pool_n]
        return encode_arrays(arrays, {"kind": "step", "actor": 0,
                                      "t": i + 1}, compress="auto")

    def encode_zc(i):
        arrays, q_sel, q_max = pool[i % pool_n]
        return enc.encode_step(arrays, actor=0, t=i + 1,
                               q_sel=q_sel, q_max=q_max)

    def encode_dedup(i):
        arrays, q_sel, q_max = pool[i % pool_n]
        return dedup_enc.encode_step(arrays, actor=0, t=i + 1,
                                     q_sel=q_sel, q_max=q_max)

    decode_cpu = [0.0]

    def decode_legacy(payload):
        t0 = time.perf_counter()
        decode_arrays(payload)
        decode_cpu[0] += time.perf_counter() - t0

    def decode_zc(payload):
        t0 = time.perf_counter()
        dec.decode(payload)
        decode_cpu[0] += time.perf_counter() - t0

    def decode_dedup(payload):
        t0 = time.perf_counter()
        dedup_dec[0].decode(payload)
        decode_cpu[0] += time.perf_counter() - t0

    def fresh_dedup_arm():
        """Fresh encoder chain + decoder ring per arm (dedup state is a
        per-session chain; arms must not share it)."""
        dedup_enc.reset()
        dedup_dec[0] = ingest.DedupStepDecoder(schema, fs, t0=0)

    def tcp_arm(encode_one, decode_one):
        server = TcpRecordServer()
        client = TcpRecordClient(server.address)
        sent = [0]

        def produce():
            for i in range(records):
                payload = encode_one(i)
                sent[0] += len(payload) + _FRAME_HDR.size
                client.push(payload)

        th = threading.Thread(target=produce, daemon=True,
                              name="ab-producer")
        decode_cpu[0] = 0.0
        t0 = time.perf_counter()
        th.start()
        got = 0
        while got < records:
            rec = server.pop()
            if rec is None:
                # Real sleep, not sched_yield: every empty poll takes
                # the server's backlog lock, and a yield-spin contends
                # it against the serve thread (measured slower on both
                # codecs than the 200us poll).
                time.sleep(0.0002)
                continue
            decode_one(rec[1])
            got += 1
        wall = time.perf_counter() - t0
        th.join(timeout=10)
        client.close()
        server.close()
        return wall, sent[0], decode_cpu[0]

    def shm_arm(encode_one, decode_one, batch: int = 1,
                slot_size: int = 0):
        slot = slot_size or ingest.max_record_bytes(schema)
        if batch > 1:
            from dist_dqn_tpu.ingest.shm_ring import batch_bytes
            slot = batch_bytes([slot] * batch)
        ring = ingest.ShmSlotRing(
            f"ab_{os.getpid()}_{variant}", slot_size=slot, nslots=64,
            create=True)
        att = ingest.ShmSlotRing(f"ab_{os.getpid()}_{variant}")
        sent = [0]
        try:
            def produce():
                if batch > 1:
                    i = 0
                    while i < records:
                        group = []
                        for k in range(min(batch, records - i)):
                            p = bytes(encode_one(i + k))
                            sent[0] += len(p)
                            group.append(p)
                        att.push_batch_wait(group)
                        i += len(group)
                else:
                    for i in range(records):
                        payload = encode_one(i)
                        sent[0] += len(payload)
                        att.push_wait(payload)

            th = threading.Thread(target=produce, daemon=True,
                                  name="ab-producer")
            decode_cpu[0] = 0.0
            t0 = time.perf_counter()
            th.start()
            got = 0
            while got < records:
                payload = ring.pop()
                if payload is None:
                    # Yield, don't spin: a GIL-holding empty-poll loop
                    # starves the single producer thread (measured 7x
                    # on pixel records — 5 ms GIL switch interval).
                    time.sleep(0)
                    continue
                decode_one(payload)
                got += 1
            wall = time.perf_counter() - t0
            th.join(timeout=10)
            return wall, sent[0], decode_cpu[0]
        finally:
            att.close()
            ring.close()
            ring.unlink()

    arms = [
        ("legacy", lambda: tcp_arm(encode_legacy, decode_legacy)),
        ("zerocopy", lambda: tcp_arm(encode_zc, decode_zc)),
        ("shm", lambda: shm_arm(encode_zc, decode_zc)),
        ("shm_batched", lambda: shm_arm(encode_zc, decode_zc,
                                        batch=_AB_SHM_BATCH)),
    ]
    if fs:
        def dedup_tcp():
            fresh_dedup_arm()
            return tcp_arm(encode_dedup, decode_dedup)

        def dedup_shm():
            fresh_dedup_arm()
            return shm_arm(encode_dedup, decode_dedup,
                           slot_size=ingest.max_dedup_record_bytes(
                               schema, fs))

        arms += [("dedup", dedup_tcp), ("shm_dedup", dedup_shm)]

    # Vector TCP arms: best-of-N interleaved with backoff (see
    # _AB_TCP_SAMPLES). Pixel arms are memcpy/zlib-bound and stable;
    # shm arms never flaked — both stay single-sample.
    best_of = {}
    if not fs:
        tcp_arms = {"legacy", "zerocopy"}
        runs = dict(arms)
        best = {}
        for attempt in range(_AB_TCP_SAMPLES):
            improved = False
            for arm in ("legacy", "zerocopy"):
                sample = runs[arm]()
                # Lower wall = the quieter window; bytes/decode-CPU
                # are deterministic per arm, so the best run's row is
                # the arm's row.
                if arm not in best or sample[0] < best[arm][0]:
                    if arm in best and \
                            sample[0] < best[arm][0] * 0.95:
                        improved = True
                    elif arm not in best:
                        improved = True
                    best[arm] = sample
            if attempt and not improved:
                break
            if attempt + 1 < _AB_TCP_SAMPLES:
                time.sleep(0.2 * (attempt + 1))
        best_of = {arm: (res, attempt + 1)
                   for arm, res in best.items()}
        arms = [(a, r) for a, r in arms if a not in tcp_arms]

    rows = []
    results = [(arm, run(), 1) for arm, run in arms]
    results += [(arm, res, n) for arm, (res, n) in best_of.items()]
    order = ["legacy", "zerocopy", "shm", "shm_batched", "dedup",
             "shm_dedup"]
    results.sort(key=lambda r: order.index(r[0]))
    for arm, (wall, sent, cpu), samples in results:
        row = {
            "bench": "apex_feeder", "phase": "ab", "variant": variant,
            "arm": arm, "transport": arm, "records": records,
            "lanes_per_record": lanes,
            "trajectories_per_sec": round(records / max(wall, 1e-9), 1),
            "bytes_on_wire": int(sent),
            "bytes_per_record": round(sent / records, 1),
            "decode_cpu_s": round(cpu, 4),
            "ab_samples": samples,
            "dedup_bytes_saved": 0,
            "dedup_frames_reused": 0,
            "wall_s": round(wall, 3)}
        if arm in ("dedup", "shm_dedup"):
            row["dedup_bytes_saved"] = int(dedup_dec[0].bytes_saved)
            row["dedup_frames_reused"] = int(dedup_dec[0].frames_reused)
        rows.append(row)
    return rows


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--allow-cpu", action="store_true",
                   help="smoke the harness on CPU (tiny sizes; NOT for "
                        "BASELINE numbers)")
    p.add_argument("--variants", nargs="*", default=["vector", "pixel"])
    p.add_argument("--measure-seconds", type=float, default=120.0)
    p.add_argument("--transport", choices=("zerocopy", "legacy"),
                   default="zerocopy",
                   help="experience path for the service phases "
                        "(ISSUE 9); the --ab arms measure both "
                        "regardless")
    p.add_argument("--ab", action="store_true",
                   help="transport-isolated A/B (ISSUE 9): encode -> "
                        "wire -> decode for the legacy JSON codec, the "
                        "zero-copy TCP framing and the shm slot ring — "
                        "one BENCH row per arm with trajectories/sec, "
                        "bytes-on-wire and decode CPU-seconds. Runs "
                        "before the service phases; jax-free")
    p.add_argument("--ab-records", type=int, default=0,
                   help="records per A/B arm (0 = per-variant default; "
                        "the smoke test passes a small count)")
    p.add_argument("--trace", default=None,
                   help="path PREFIX for the measure phase's host-span "
                        "Chrome trace (utils/trace.py): writes "
                        "<prefix>.<variant>.json per variant — "
                        "attributes the per-pass cost: ingest vs act vs "
                        "train dispatch vs priority write-back. Also "
                        "runs a probe-sized SPLIT-DISPATCH (fused_ingest "
                        "=False) reference and emits a trace_ab row with "
                        "device round-trips per ingest pass, fused vs "
                        "split — the ISSUE 2 before/after")
    args = p.parse_args()

    platforms = select_platform(args.allow_cpu)

    ok = True
    for variant in args.variants:
        cfg, rt_kwargs, probe_total = _configs(variant, args.allow_cpu)
        lanes = rt_kwargs["envs_per_actor"]
        rt_kwargs["transport"] = args.transport

        if args.ab:
            # Transport-isolated A/B first: no learner, no jax in the
            # loop — the feeder-ceiling number for each codec/transport.
            default_records = _AB_SPECS[variant][2]
            n = args.ab_records or (default_records // 10
                                    if args.allow_cpu else default_records)
            ab_rows = _transport_ab(variant, n, lanes)
            for row in ab_rows:
                _emit(row)
            by_arm = {r["arm"]: r for r in ab_rows}
            summary = {
                "bench": "apex_feeder", "variant": variant,
                "phase": "ab_summary",
                "zerocopy_speedup_vs_legacy": round(
                    by_arm["zerocopy"]["trajectories_per_sec"]
                    / max(by_arm["legacy"]["trajectories_per_sec"],
                          1e-9), 3),
                "shm_speedup_vs_legacy": round(
                    by_arm["shm"]["trajectories_per_sec"]
                    / max(by_arm["legacy"]["trajectories_per_sec"],
                          1e-9), 3),
                "zerocopy_wire_bytes_vs_legacy": round(
                    by_arm["zerocopy"]["bytes_on_wire"]
                    / max(by_arm["legacy"]["bytes_on_wire"], 1), 3),
                # Batched slot publishes (ISSUE 14): the seqlock-
                # handshake amortization, read against the per-record
                # shm arm.
                "shm_batched_speedup_vs_shm": round(
                    by_arm["shm_batched"]["trajectories_per_sec"]
                    / max(by_arm["shm"]["trajectories_per_sec"],
                          1e-9), 3),
            }
            if "dedup" in by_arm:
                # Frame-dedup plane (ISSUE 14): wire bytes + decode CPU
                # against BOTH incumbent codecs, and the throughput
                # read on the same-host ring.
                summary.update({
                    "dedup_wire_bytes_vs_legacy": round(
                        by_arm["dedup"]["bytes_on_wire"]
                        / max(by_arm["legacy"]["bytes_on_wire"], 1), 3),
                    "dedup_wire_bytes_vs_zerocopy": round(
                        by_arm["dedup"]["bytes_on_wire"]
                        / max(by_arm["zerocopy"]["bytes_on_wire"], 1),
                        3),
                    "dedup_decode_cpu_vs_legacy": round(
                        by_arm["dedup"]["decode_cpu_s"]
                        / max(by_arm["legacy"]["decode_cpu_s"], 1e-9),
                        3),
                    "dedup_decode_cpu_vs_zerocopy": round(
                        by_arm["dedup"]["decode_cpu_s"]
                        / max(by_arm["zerocopy"]["decode_cpu_s"],
                              1e-9), 3),
                    "dedup_speedup_vs_legacy": round(
                        by_arm["dedup"]["trajectories_per_sec"]
                        / max(by_arm["legacy"]["trajectories_per_sec"],
                              1e-9), 3),
                    "shm_dedup_speedup_vs_shm": round(
                        by_arm["shm_dedup"]["trajectories_per_sec"]
                        / max(by_arm["shm"]["trajectories_per_sec"],
                              1e-9), 3),
                })
            _emit(summary)

        # Phase 1 — fixed small probe: pays every compile, measures the
        # saturated ingest rate on this host.
        summary, wall, steady = _run(cfg, rt_kwargs, probe_total)
        probe_rate = summary["env_steps"] / max(wall, 1e-9)
        probe_summary = summary
        _emit({"bench": "apex_feeder", "variant": variant,
               "phase": "probe", "wall_s": round(wall, 1),
               "avg_env_steps_per_sec": round(probe_rate, 1),
               # Transport identity + wire cost ride every BENCH row
               # (ISSUE 9 satellite): rows across PRs are comparable
               # only when they name the experience path they measured.
               "transport": summary["transport"],
               "bytes_on_wire": summary["bytes_on_wire"],
               **_roundtrip_fields(summary),
               **{k: summary[k] for k in
                  ("env_steps", "grad_steps", "ring_dropped",
                   "bad_records")}})

        # Phase 2 — measure run sized FROM the probe rate (compiles
        # cached in-process): ~measure-seconds of steady state.
        best_rate = max(probe_rate,
                        steady.get("env_steps_per_sec_per_chip") or 0.0)
        measure_total = max(int(best_rate * args.measure_seconds),
                            2 * probe_total)
        trace = (f"{args.trace}.{variant}.json" if args.trace else None)
        summary, wall, steady = _run(cfg, rt_kwargs, measure_total,
                                     trace_path=trace)
        avg_rate = summary["env_steps"] / max(wall, 1e-9)
        steady_rate = steady.get("env_steps_per_sec_per_chip") or avg_rate
        # Cadence debt: the ratio the config ASKS for vs what the
        # learner delivered at this ingest rate. Read the real runtime
        # default rather than duplicating the literal.
        from dist_dqn_tpu.actors.service import ApexRuntimeConfig
        inserts_per_grad = ApexRuntimeConfig(
            **rt_kwargs).inserts_per_grad_step
        target_grad = summary["env_steps"] // inserts_per_grad
        row = {
            "bench": "apex_feeder", "variant": variant, "phase": "measure",
            "platforms": platforms,
            # ISSUE 9 satellite (bugfix): the row must identify which
            # transport carried it and what it cost on the wire, or the
            # A/B trajectory across PRs is not comparable.
            "transport": summary["transport"],
            "bytes_on_wire": summary["bytes_on_wire"],
            "ingest_bytes": summary["ingest_bytes"],
            "host_env": rt_kwargs["host_env"],
            "feeders": rt_kwargs["num_actors"],
            "lanes_per_record": lanes,
            "batch_size": cfg.learner.batch_size,
            "replay_capacity": cfg.replay.capacity,
            "total_env_steps": measure_total,
            "wall_s": round(wall, 1),
            "avg_env_steps_per_sec": round(avg_rate, 1),
            "steady_env_steps_per_sec_per_chip": steady_rate,
            "steady_records_per_sec": round(steady_rate / lanes, 1),
            "steady_grad_steps_per_sec":
                steady.get("grad_steps_per_sec"),
            "grad_steps_target_at_cadence": int(target_grad),
            "learner_kept_cadence":
                bool(summary["grad_steps"] >= 0.95 * target_grad),
            "note": "feeders share the 1 host core with the service -> "
                    "lower bound on a dedicated-host service; no "
                    "emulator/preprocessing in the loop (see module "
                    "docstring)",
            **_roundtrip_fields(summary),
            **_lineage_fields(),
            **{k: summary[k] for k in
               ("env_steps", "grad_steps", "replay_size", "ring_dropped",
                "tcp_backpressure", "bad_records", "actor_restarts")},
        }
        _emit(row)
        if args.trace:
            # Split-dispatch reference (probe-sized; compiles are sunk):
            # the pre-ISSUE-2 ingest path exactly — split act/bootstrap
            # dispatches, per-256 bootstrap chunks, per-step priority
            # write-backs, serial H2D — vs the fast path's fused
            # power-of-two-batched dispatches above.
            ab_summary, ab_wall, _ = _run(
                cfg, rt_kwargs, probe_total,
                trace_path=(f"{args.trace}.{variant}.split.json"),
                fused_ingest=False, prio_writeback_batch=1,
                stage_depth=0,
                # The split reference must actually dispatch bootstraps:
                # with actor-shipped priorities (ISSUE 9) there is
                # nothing to split, so the reference disables them.
                actor_priorities=False)
            # Compare at the SAME run size: the fused PROBE (phase 1,
            # also probe_total) vs the split reference — identical work,
            # so the per-pass ratio isolates the dispatch fusion.
            fused_rt = probe_summary["ingest_device_calls_per_pass"]
            split_rt = ab_summary["ingest_device_calls_per_pass"]
            _emit({"bench": "apex_feeder", "variant": variant,
                   "phase": "trace_ab", "total_env_steps": probe_total,
                   "fused_ingest_device_calls_per_pass": fused_rt,
                   "split_ingest_device_calls_per_pass": split_rt,
                   "roundtrip_reduction":
                       round(split_rt / max(fused_rt, 1e-9), 3),
                   "split_device_calls": ab_summary["device_calls"],
                   "fused_device_calls": probe_summary["device_calls"],
                   "split_wall_s": round(ab_wall, 1),
                   "split_env_steps": ab_summary["env_steps"]})
        # ring_dropped counts ring-FULL push rejections: for feeders that
        # is the normal backpressure signal (the payload is retried, not
        # lost — actors/feeder.py pump loop), so unlike the split bench
        # it is reported, not failed on. bad_records is still corruption.
        ok = ok and summary["bad_records"] == 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
