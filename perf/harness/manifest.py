"""``BENCHMARK.json`` and the files it names.

A cell names a configuration and a traffic mix; a per-layer metric names a
reader. All three are found BY NAME under the manifest's ``paths``:
``configs[].file``, ``<path>/traffic/<traffic>.json`` and
``<path>/metrics/<metric>.py``. Adding one is adding a file and an entry —
no file that exists is edited.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or inconsistent."""


class Manifest:
    def __init__(self, root: Path):
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise ManifestError(f"no BENCHMARK.json in {self.root}")
        self.data = json.loads(path.read_text())
        self.paths = [self.root / p for p in self.data["paths"]]

    # -- entries ---------------------------------------------------------
    def _entry(self, group: str, name: str) -> Dict:
        for e in self.data[group]:
            if e["name"] == name:
                return e
        known = ", ".join(e["name"] for e in self.data[group])
        raise ManifestError(f"no {group} entry named {name!r} (has: {known})")

    def cell(self, name: str) -> Dict:
        return self._entry("workloads", name)

    @property
    def run_seconds(self) -> int:
        return int(self.data["run_seconds"])

    def metrics_of(self, group: str, cell_name: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports: a
        metric with a ``workloads`` list exists only in those cells."""
        return [m for m in self.data[group]
                if cell_name in m.get("workloads", [cell_name])]

    # -- files found by name ----------------------------------------------
    def _find(self, relative: str) -> Path:
        for base in self.paths:
            if (base / relative).is_file():
                return base / relative
        raise ManifestError(
            f"{relative} not found under paths {self.data['paths']}")

    def config(self, name: str) -> Dict:
        entry = self._entry("configs", name)
        path = self.root / entry["file"]
        if not path.is_file():
            raise ManifestError(f"config file {entry['file']} is missing")
        return json.loads(path.read_text())

    def traffic(self, name: str) -> Dict:
        return json.loads(self._find(f"traffic/{name}.json").read_text())

    def _module(self, relative: str):
        path = self._find(relative)
        spec = importlib.util.spec_from_file_location(
            "perf_found_" + re.sub(r"\W", "_", relative), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def metric_reader(self, name: str) -> Callable:
        """``read(run, trace)`` of ``metrics/<name>.py``: returns the value,
        or None where there is nothing to read."""
        return self._module(f"metrics/{name}.py").read

    def reference(self, name: str):
        """The plain-reference module ``reference/<name>.py`` a configuration
        file names under ``reference``."""
        return self._module(f"reference/{name}.py")


def resolve_cell(manifest: Manifest, cell_name: str) -> Dict:
    """One cell's plan: preset name, the ``--set`` overrides (configuration's
    first, traffic's after), chunk size, devices and warm-up rule. The
    traffic file's keys win over the configuration's defaults."""
    cell = manifest.cell(cell_name)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    plan = {
        "cell": cell_name,
        "config": cell["config"],
        "traffic": cell["traffic"],
        "chips": int(cell["chips"]),
        "preset": config["preset"],
        "sizes": config.get("sizes", {}),
        "reference": config["reference"],
        "config_overrides": list(config.get("overrides", [])),
        "traffic_overrides": list(traffic.get("overrides", [])),
        "num_devices": int(traffic.get("num_devices", 1)),
        "test_window_chunks": int(traffic.get("test_window_chunks", 0)),
    }
    for key in ("chunk_iters", "warmup", "trace_chunks"):
        value = traffic.get(key, config.get(key))
        if value is None:
            raise ManifestError(
                f"neither traffic {cell['traffic']!r} nor config "
                f"{cell['config']!r} states {key!r}")
        plan[key] = value
    if plan["num_devices"] != plan["chips"]:
        raise ManifestError(
            f"cell {cell_name}: chips={plan['chips']} but its traffic runs "
            f"on num_devices={plan['num_devices']}")
    return plan
