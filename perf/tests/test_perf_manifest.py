"""``BENCHMARK.json`` against the contract, and against its own files."""
import json
import re
from pathlib import Path

import pytest

from perf.harness.manifest import Manifest, resolve_cell

CHECKOUT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(CHECKOUT)


def test_top_level_keys_and_limits(manifest):
    d = manifest.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert d["command"] == ["python3", "perf/run.py"] and d["paths"] == ["perf"]
    assert 1 <= d["run_seconds"] <= 51
    assert len((CHECKOUT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 2 <= len(d["workloads"]) <= 24 and 1 <= len(d["per_layer"]) <= 128
    four = [w for w in d["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(d["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in d["workloads"])


def test_names_units_and_line_lengths(manifest):
    d = manifest.data
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in d[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in d["end_to_end"] + d["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in d["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in d["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert "setup_s" in [m["name"] for m in d["end_to_end"]]


def test_every_cell_finds_its_files_and_every_config_is_used(manifest):
    d = manifest.data
    for w in d["workloads"]:
        plan = resolve_cell(manifest, w["name"])
        assert plan["chunk_iters"] > 0 and plan["num_devices"] == w["chips"]
        manifest.reference(plan["reference"])
    assert ({c["name"] for c in d["configs"]}
            == {w["config"] for w in d["workloads"]})
    files = [c["file"] for c in d["configs"]]
    assert len(files) == len(set(files))
    for c in d["configs"]:
        stated = json.loads((CHECKOUT / c["file"]).read_text())
        assert set(c["reduced"]) == set(stated["reduced"])
        widths = ("hidden", "_dim", "_rank", "torso", "features")
        assert not any(any(w in k for w in widths) for k in c["reduced"])


def test_every_metric_has_a_reader_and_moves_a_metric_of_its_cells(manifest):
    d = manifest.data
    cells = [w["name"] for w in d["workloads"]]
    for m in d["per_layer"]:
        assert callable(manifest.metric_reader(m["name"]))
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert m["moves"] in [e["name"] for e in
                                  manifest.metrics_of("end_to_end", cell)]
    for cell in cells:
        assert "setup_s" in [e["name"] for e in
                             manifest.metrics_of("end_to_end", cell)]
        assert len(manifest.metrics_of("end_to_end", cell)) >= 2
        assert manifest.metrics_of("per_layer", cell)
    # metrics of one layer give the same layer, letter for letter
    layers = {m["layer"] for m in d["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)


def test_config_files_state_what_the_program_runs(manifest):
    from perf.harness.run_cell import build_config

    for w in manifest.data["workloads"]:
        cfg = build_config(resolve_cell(manifest, w["name"]))
        assert cfg.eval_every_steps == 0
