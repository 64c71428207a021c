"""Unified telemetry: registry, Prometheus exposition, flush lifecycle.

Quick start (what every layer does)::

    from dist_dqn_tpu import telemetry

    reg = telemetry.get_registry()
    steps = reg.counter("dqn_env_steps_total", "env steps processed")
    depth = reg.gauge("dqn_transport_tcp_backlog", "records queued")
    lat = reg.histogram("dqn_grad_step_latency_seconds",
                        "dispatch->materialize latency")

Serve ``/metrics`` with ``telemetry.start_server(port)``; dump a JSON
snapshot at exit with ``telemetry.install_snapshot_dump(path)``. The
package is stdlib-only (importable from jax-free actor processes) and
hands out Null-object twins via ``NullRegistry`` for zero-overhead
disabled paths. Naming scheme + the dashboards each gauge feeds:
docs/observability.md.

Crash forensics (ISSUE 4): ``telemetry.get_flight()`` is the process's
flight-recorder ring, ``telemetry.heartbeat(stage)`` registers a stall-
watchdog heartbeat (no-op twin until ``install_watchdog`` arms it), and
``telemetry.observe_divergence(loss=...)`` feeds the NaN/explosion
sentinel — see telemetry/flight.py and telemetry/watchdog.py.
"""
from dist_dqn_tpu.telemetry.devtime import (IDLE_CAUSES,  # noqa: F401
                                            UtilizationLedger,
                                            capture_profile,
                                            maybe_trace_first_chunk,
                                            sweep_device_memory)
from dist_dqn_tpu.telemetry.exposition import (CONTENT_TYPE,  # noqa: F401
                                               render_prometheus, snapshot,
                                               write_snapshot)
from dist_dqn_tpu.telemetry.flight import (FlightRecorder,  # noqa: F401
                                           NullFlightRecorder, get_flight)
from dist_dqn_tpu.telemetry.lifecycle import (  # noqa: F401
    install_snapshot_dump, maybe_install_snapshot_from_env, on_exit)
from dist_dqn_tpu.telemetry.manifest import (build_manifest,  # noqa: F401
                                             get_run_manifest,
                                             set_run_manifest)
from dist_dqn_tpu.telemetry.registry import (DEFAULT_BUCKETS,  # noqa: F401
                                             Counter, Gauge, Histogram,
                                             NullRegistry, Registry,
                                             get_registry)
from dist_dqn_tpu.telemetry.server import (TelemetryServer,  # noqa: F401
                                           start_server)
from dist_dqn_tpu.telemetry.watchdog import (DivergenceSentinel,  # noqa: F401
                                             Heartbeat, Watchdog,
                                             dump_forensics, get_watchdog,
                                             heartbeat, install_sentinel,
                                             install_watchdog,
                                             maybe_install_from_env,
                                             observe_divergence)
