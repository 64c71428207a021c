"""The emit-once BENCH contract shared by benchmarks/serving_bench.py and
benchmarks/scaling_bench.py."""
from __future__ import annotations

import json
import threading


class ContractEmitter:
    """Every exit path of a benchmark — success, backend hang, any
    exception — produces exactly ONE structured JSON line (first caller
    wins), so a driver capture is always parseable."""

    def __init__(self, metric: str, unit: str):
        self.metric, self.unit = metric, unit
        self._lock = threading.Lock()
        self._emitted = False

    def emit_payload(self, payload: dict) -> None:
        with self._lock:
            if self._emitted:
                return
            self._emitted = True
            print(json.dumps(payload), flush=True)

    def error(self, stage: str, err: str) -> None:
        self.emit_payload({"metric": self.metric, "value": None,
                           "unit": self.unit, "vs_baseline": None,
                           "error": f"{stage}: {err}"})
