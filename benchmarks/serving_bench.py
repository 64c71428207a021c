"""Closed-loop load generator for the serving tier (ISSUE 7).

Spins a :class:`~dist_dqn_tpu.serving.server.PolicyServer` in-process
over a checkpoint (an existing run dir via ``--checkpoint-dir``, or a
fresh randomly-initialized one saved into a temp dir), then drives it
with N closed-loop client threads — each holding one keep-alive HTTP
connection, sending the next act request the moment the previous answer
lands (the standard closed-loop saturation harness). Emits one BENCH
JSON row per arm with

  * ``acts_per_sec`` — served action rows / measured wall,
  * ``p50_ms`` / ``p99_ms`` — client-observed request latency,
  * ``mean_fanin_requests`` / ``mean_fanin_rows`` — dispatch coalescing
    (reconstructed exactly from the per-response fan-in headers:
    dispatches = sum over responses of 1/fanin_requests),
  * ``requests_shed`` — 429s the bounded queue returned,

plus the run manifest and a registry snapshot.
``--ab`` runs the dynamic micro-batcher against the ``--no-batching``
serialized-dispatch baseline at the same load and reports the speedup —
the acceptance smoke (tests/test_serving.py) asserts batched >= serial.

Usage: python benchmarks/serving_bench.py [--config cartpole]
           [--clients 8] [--duration-s 2] [--ab] [--no-batching]
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from contract import ContractEmitter  # noqa: E402

METRIC = "serving_acts_per_sec"
UNIT = ("action rows served/sec (closed-loop HTTP clients, greedy "
        "policy, dynamic micro-batching)")

contract = ContractEmitter(METRIC, UNIT)


def _make_checkpoint(cfg, directory: str) -> None:
    """Save one randomly-initialized learner checkpoint — serving cost
    does not depend on the params' training history."""
    import jax
    import jax.numpy as jnp

    from dist_dqn_tpu.agents.dqn import make_learner
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.utils.checkpoint import TrainCheckpointer

    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, _ = make_learner(net, cfg.learner)
    state = init(jax.random.PRNGKey(0),
                 jnp.zeros(env.observation_shape, env.observation_dtype))
    ckpt = TrainCheckpointer(directory, save_every_frames=1)
    try:
        ckpt.save(0, state)
    finally:
        ckpt.close()


def _obs_batch(cfg, rows: int) -> np.ndarray:
    from dist_dqn_tpu.envs import make_jax_env

    env = make_jax_env(cfg.env_name)
    rng = np.random.default_rng(0)
    return rng.standard_normal(
        (rows,) + tuple(env.observation_shape)).astype(
            env.observation_dtype)


def _proc_load(address: str, obs: np.ndarray, clients: int,
               warmup_s: float, duration_s: float, out_q) -> None:
    """One load-generation PROCESS (ISSUE 9 satellite): the in-process
    client threads are GIL-bound at 1-row requests — N real processes
    each run their own thread pool against the server and report
    (latencies_ms, fanin_inv, rows_served, shed, errors) through
    ``out_q``. Jax-free: only the ServingClient wire codec is needed.
    Module-level for the multiprocessing 'spawn' pickle contract."""
    import threading

    from dist_dqn_tpu.serving import QueueFullError, ServingClient

    lock = threading.Lock()
    latencies, fanin_inv, shed = [], [], [0]
    rows_served = [0]
    errors = []
    start = time.perf_counter()
    t_measure = start + warmup_s
    t_stop = t_measure + duration_s

    def worker():
        cl = None
        try:
            cl = ServingClient(address)
            while True:
                now = time.perf_counter()
                if now >= t_stop:
                    return
                t0 = now
                try:
                    r = cl.act(obs, greedy=True)
                except QueueFullError as e:
                    if time.perf_counter() >= t_measure:
                        with lock:
                            shed[0] += 1
                    time.sleep(min(e.retry_after_s, 0.1))
                    continue
                t1 = time.perf_counter()
                if t1 < t_measure:
                    continue
                with lock:
                    latencies.append((t1 - t0) * 1e3)
                    fanin_inv.append(1.0 / r.fanin_requests)
                    rows_served[0] += obs.shape[0]
        except Exception as e:  # noqa: BLE001 — reported to the parent
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            if cl is not None:
                cl.close()

    threads = [threading.Thread(target=worker, name=f"loadgen-{i}",
                                daemon=True) for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    out_q.put((latencies, fanin_inv, rows_served[0], shed[0], errors))


def run_arm(cfg, checkpoint_dir: str, *, batching: bool, clients: int,
            duration_s: float, warmup_s: float, rows_per_request: int,
            max_rows: int, max_wait_ms: float, queue_limit: int,
            transport: str = "http", procs: int = 1) -> dict:
    """One closed-loop measurement; returns its BENCH row dict.

    ``transport="http"`` drives the full stack — sockets, codec,
    handler threads — the end-to-end number; at 1-row requests on a
    small box the GIL-bound transport is the bottleneck there and the
    two arms converge. ``transport="inproc"`` calls
    ``batcher.submit`` directly (still the full batcher/router/store
    path), isolating the dispatch economics the micro-batcher exists
    to amortize — the arm the tier-1 A/B smoke pins, since it measures
    batching rather than socket throughput."""
    from dist_dqn_tpu.serving import QueueFullError, ServingClient
    from dist_dqn_tpu.serving.server import build_server

    server = build_server(
        cfg, {"default": checkpoint_dir}, max_rows=max_rows,
        max_wait_ms=max_wait_ms, queue_limit=queue_limit,
        batching=batching, poll_interval_s=3600.0,
        log_fn=lambda *_: None)
    obs = _obs_batch(cfg, rows_per_request)
    t_stop = [0.0]  # set after warmup; workers read it each pass
    t_measure = [0.0]
    lock = threading.Lock()
    latencies, fanin_inv, shed = [], [], [0]
    rows_served = [0]
    client_errors = []

    if procs > 1:
        # Process-separated load generation (ISSUE 9 satellite /
        # ROADMAP item 3 follow-up): at 1-row requests the in-process
        # client threads serialize on THIS interpreter's GIL and the
        # bench measures the load generator, not the server. Real
        # client processes each own a GIL; per-arm rows merge below.
        if transport != "http":
            server.close()
            raise ValueError("--procs drives the real HTTP surface; "
                             "combine it with --transport http")
        import multiprocessing as mp
        ctx = mp.get_context("spawn")
        out_q = ctx.Queue()
        # Distribute the EXACT requested client count (remainder across
        # the first processes) — rounding it would change the offered
        # load and make rows across --procs values incomparable.
        procs = min(procs, max(clients, 1))
        base, extra = divmod(max(clients, 1), procs)
        per_proc = [base + (1 if i < extra else 0) for i in range(procs)]
        workers = [
            ctx.Process(target=_proc_load,
                        args=(f"{server.host}:{server.port}", obs, n,
                              warmup_s, duration_s, out_q),
                        name=f"loadgen-proc-{i}", daemon=True)
            for i, n in enumerate(per_proc) if n > 0]
        for w in workers:
            w.start()
        try:
            for _ in workers:
                lat, fin, rows_n, shed_n, errs = out_q.get(
                    timeout=warmup_s + duration_s + 120)
                latencies.extend(lat)
                fanin_inv.extend(fin)
                rows_served[0] += rows_n
                shed[0] += shed_n
                client_errors.extend(errs)
        finally:
            for w in workers:
                w.join(timeout=30)
                if w.is_alive():
                    w.terminate()
            server.close()
        clients = sum(per_proc)
        return _arm_row(transport, batching, latencies, fanin_inv,
                        rows_served[0], shed[0], client_errors, clients,
                        rows_per_request, duration_s, max_rows,
                        max_wait_ms, procs)

    # NOTE: this in-thread worker and _proc_load's worker are twins by
    # design (the inproc transport can only run in-process; http with
    # --procs runs the process copy) — a change to the measure-window,
    # shed gating or retry rule must land in BOTH or the procs=1 and
    # procs=N rows silently measure different things.
    def worker():
        cl = None
        try:
            # Constructor inside the guard too: a client that dies
            # connecting (refused/timeout on a loaded box) must fail the
            # arm loudly, not silently thin the closed loop while the
            # BENCH row still claims the full client count.
            if transport == "http":
                cl = ServingClient(f"{server.host}:{server.port}")
                act = lambda: cl.act(obs, greedy=True)  # noqa: E731
            else:
                act = lambda: server.batcher.submit(  # noqa: E731
                    obs, greedy=True)
            while True:
                now = time.perf_counter()
                if t_stop[0] and now >= t_stop[0]:
                    return
                t0 = now
                try:
                    r = act()
                except QueueFullError as e:
                    # Same warmup gate as successes: cold-ladder pileup
                    # sheds must not inflate the measured-window count.
                    if time.perf_counter() >= t_measure[0]:
                        with lock:
                            shed[0] += 1
                    time.sleep(min(e.retry_after_s, 0.1))
                    continue
                t1 = time.perf_counter()
                if t1 < t_measure[0]:
                    continue  # warmup: compiles the bucket ladder
                with lock:
                    latencies.append((t1 - t0) * 1e3)
                    fanin_inv.append(1.0 / r.fanin_requests)
                    rows_served[0] += obs.shape[0]
        except Exception as e:  # noqa: BLE001 — a dead worker must not
            # silently thin the closed loop: record the error (the arm
            # fails loudly after the join) and exit this client.
            with lock:
                client_errors.append(f"{type(e).__name__}: {e}")
        finally:
            if cl is not None:
                cl.close()

    threads = [threading.Thread(target=worker, name=f"bench-client-{i}",
                                daemon=True) for i in range(clients)]
    start = time.perf_counter()
    t_measure[0] = start + warmup_s
    t_stop[0] = start + warmup_s + duration_s
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    server.close()
    return _arm_row(transport, batching, latencies, fanin_inv,
                    rows_served[0], shed[0], client_errors, clients,
                    rows_per_request, duration_s, max_rows, max_wait_ms,
                    procs)


def _arm_row(transport, batching, latencies, fanin_inv, rows_served,
             shed, client_errors, clients, rows_per_request, duration_s,
             max_rows, max_wait_ms, procs) -> dict:
    """Merge one arm's (possibly multi-process) samples into its BENCH
    row; dead clients fail the arm loudly (a zero-latency row from dead
    workers would read as a great measurement)."""
    if client_errors:
        raise RuntimeError(
            f"{len(client_errors)}/{clients} bench clients died: "
            + "; ".join(sorted(set(client_errors))[:3]))
    lat = np.asarray(latencies) if latencies else np.zeros((1,))
    dispatches = float(np.sum(fanin_inv)) or 1.0
    n = len(latencies)
    return {
        "bench": "serving",
        "transport": transport,
        "mode": "batched" if batching else "serial",
        "procs": procs,
        "acts_per_sec": round(rows_served / duration_s, 1),
        "requests_per_sec": round(n / duration_s, 1),
        "p50_ms": round(float(np.percentile(lat, 50)), 3),
        "p99_ms": round(float(np.percentile(lat, 99)), 3),
        "mean_fanin_requests": round(n / dispatches, 2),
        "mean_fanin_rows": round(rows_served / dispatches, 2),
        "requests_ok": n,
        "requests_shed": shed,
        "clients": clients,
        "rows_per_request": rows_per_request,
        "duration_s": duration_s,
        "max_batch_rows": max_rows,
        "max_wait_ms": max_wait_ms,
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", default="cartpole")
    parser.add_argument("--checkpoint-dir", default=None,
                        help="serve THIS run dir (default: save a fresh "
                             "random-params checkpoint to a temp dir)")
    parser.add_argument("--clients", type=int, default=16)
    parser.add_argument("--duration-s", type=float, default=2.0)
    parser.add_argument("--warmup-s", type=float, default=0.75,
                        help="untimed lead-in that compiles the pow2 "
                             "bucket ladder")
    parser.add_argument("--rows-per-request", type=int, default=1)
    parser.add_argument("--max-batch-rows", type=int, default=64)
    parser.add_argument("--max-wait-ms", type=float, default=2.0)
    parser.add_argument("--queue-limit", type=int, default=256)
    parser.add_argument("--no-batching", action="store_true",
                        help="measure ONLY the serialized per-request "
                             "dispatch baseline")
    parser.add_argument("--transport", choices=("http", "inproc"),
                        default="http",
                        help="http: full stack incl. sockets/codec; "
                             "inproc: direct batcher.submit — isolates "
                             "the dispatch economics (the A/B smoke's "
                             "arm)")
    parser.add_argument("--procs", type=int, default=1,
                        help="process-separated load generation "
                             "(ISSUE 9 satellite): spawn N REAL client "
                             "processes (clients split across them) "
                             "instead of GIL-bound in-process threads; "
                             "per-arm latency rows merge. http only")
    parser.add_argument("--ab", action="store_true",
                        help="run batched AND serial arms; the contract "
                             "line carries the speedup")
    parser.add_argument("--set", dest="overrides", action="append",
                        metavar="PATH=VALUE", default=[])
    args = parser.parse_args()

    from dist_dqn_tpu import telemetry
    from dist_dqn_tpu.config import CONFIGS, apply_overrides
    from dist_dqn_tpu.utils.backend import device_summary

    cfg = apply_overrides(CONFIGS[args.config], args.overrides)
    tmp = None
    ckpt_dir = args.checkpoint_dir
    if ckpt_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="serving_bench_")
        ckpt_dir = tmp.name
        _make_checkpoint(cfg, ckpt_dir)

    kw = dict(clients=args.clients, duration_s=args.duration_s,
              warmup_s=args.warmup_s,
              rows_per_request=args.rows_per_request,
              max_rows=args.max_batch_rows, max_wait_ms=args.max_wait_ms,
              queue_limit=args.queue_limit, transport=args.transport,
              procs=args.procs)
    try:
        rows = []
        if args.ab:
            arms = (True, False)
        else:
            arms = (not args.no_batching,)
        for batching in arms:
            row = run_arm(cfg, ckpt_dir, batching=batching, **kw)
            rows.append(row)
            print(json.dumps(row), flush=True)
        headline = rows[0]
        payload = {"metric": METRIC, "value": headline["acts_per_sec"],
                   "unit": UNIT, "vs_baseline": None,
                   "mode": headline["mode"],
                   "transport": headline["transport"],
                   "p50_ms": headline["p50_ms"],
                   "p99_ms": headline["p99_ms"],
                   "mean_fanin_rows": headline["mean_fanin_rows"],
                   "requests_shed": headline["requests_shed"],
                   "device": device_summary(),
                   "manifest": telemetry.build_manifest(cfg),
                   "telemetry": telemetry.snapshot(
                       telemetry.get_registry())}
        if args.ab:
            serial = rows[1]
            payload["serial_acts_per_sec"] = serial["acts_per_sec"]
            payload["speedup_vs_serial"] = round(
                headline["acts_per_sec"]
                / max(serial["acts_per_sec"], 1e-9), 3)
        contract.emit_payload(payload)
    except Exception as e:  # capture-proofing: one parseable line
        contract.error("measurement", repr(e))
        raise
    finally:
        if tmp is not None:
            tmp.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
