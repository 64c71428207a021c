"""Read the profiler's ``.xplane.pb`` into plain data.

A trace becomes a list of device planes, each ``{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}``. Only device planes are
kept (no metric reads host threads), of their lines the two the reduction
reads, and of an op's name what it parses. The same structure, dumped as
``.json.gz``, is what ``perf/testdata`` holds.
"""
from __future__ import annotations

import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional

DEVICE_PLANE_PREFIX = "/device:"
# Lines the reduction reads: one event per program run, one per HLO op.
KEPT_LINES = ("XLA Modules", "XLA Ops")
# An event of the ``XLA Ops`` line is named by the HLO text of its
# instruction: ``%copy.70 = u8[200000,28224]{1,0:T(8,128)(4,1)} copy(...)``,
# ``%fusion.584 = u8[64,84,84,1]{...} fusion(...), kind=kLoop, calls=...``.
_HLO = re.compile(r"^%?(?P<inst>[\w.\-]+) = (?P<shape>.*?) "
                  r"(?P<op>[a-z][a-z\-]*)\(")
_KIND = re.compile(r"kind=k(\w+)")
SHAPE_MAX = 200


class HloName(NamedTuple):
    inst: str               # the instruction's name: ``copy.70``
    shape: str              # with layouts: ``u8[200000,28224]{1,0:T(8,128)}``
    op: str                 # the opcode: ``copy``, ``fusion``, ``while``
    kind: Optional[str]     # a fusion's kind: ``Loop``, ``Output``, ``Custom``


def parse_name(name: str) -> Optional[HloName]:
    m = _HLO.match(name)
    if not m:
        return None
    kind = _KIND.search(name)
    return HloName(m.group("inst"), m.group("shape"), m.group("op"),
                   kind.group(1) if kind else None)


def compact_name(name: str) -> str:
    """Drop the operand list (most of the text; nothing reads it) and cut a
    shape that carries a whole loop state; ``parse_name`` reads the same
    instruction, opcode and kind from the result."""
    hlo = parse_name(name)
    if hlo is None:
        return name
    shape = hlo.shape if len(hlo.shape) <= SHAPE_MAX else (
        hlo.shape[:SHAPE_MAX] + "...")
    kind = f", kind=k{hlo.kind}" if hlo.kind else ""
    return f"%{hlo.inst} = {shape} {hlo.op}(){kind}"


def _planes_of(profile) -> List[Dict]:
    planes = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = [{"name": line.name,
                  "events": [[compact_name(e.name), float(e.start_ns),
                              float(e.duration_ns)] for e in line.events]}
                 for line in plane.lines if line.name in KEPT_LINES]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def load(path: Path) -> List[Dict]:
    import jax

    return _planes_of(jax.profiler.ProfileData.from_file(str(path)))


def load_newest(trace_dir: Path) -> List[Dict]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load(found[-1])


def dump(planes: List[Dict], path: str) -> None:
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(planes, f)


def read_dump(path: Path) -> List[Dict]:
    with gzip.open(path, "rt") as f:
        return json.load(f)
