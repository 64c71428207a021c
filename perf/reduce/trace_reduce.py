"""From a device trace to the numbers the per-layer metrics read.

Input: the device planes of ``xplane.load`` (or ``perf/testdata``). Per
device the reduction takes the ``XLA Ops`` line (one event per executed HLO
op; a ``while``, ``conditional`` or ``call`` is an event that CONTAINS its
body's events) and the ``XLA Modules`` line (one event per program run: one
chunk). From them:

* busy time: the union of the LEAF op intervals — containers are left out,
  a ``while`` would otherwise paint the whole loop busy;
* idle gaps between consecutive leaves, attributed to the op that ran
  before the gap (``inside_chunk_after_<op>``) or, where the gap spans the
  boundary between two program runs, to the host
  (``between_chunks_host:...``);
* op totals by name and shape (``copy_u8_200000_28224_``);
* the split of the chunk at the train ``conditional``: device time inside
  it is learning, the rest of the iteration loop is acting; what the
  program runs OUTSIDE the loop (the ring's layout copies at chunk entry and
  exit) is its own number;
* exposed collective time: all-reduce intervals during which no other leaf
  op runs on that device.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from . import xplane

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BETWEEN_CHUNKS = ("between_chunks_host:_fence__train.train_bookkeeping__"
                  "dispatch_")
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")
# Control flow: an event of one of these CONTAINS the events of its body. An
# asynchronous collective that merely overlaps a smaller op is not one.
CONTAINERS = ("while", "conditional", "call")
NS = 1e-9


LABEL_MAX = 80


class Op:
    """One device event: ``inst`` (the instruction's name, ``copy.70``),
    ``op`` (its opcode, ``copy``; ``fusion.loop`` for a loop fusion),
    ``shape`` (layout annotations dropped) and its interval in ns."""

    __slots__ = ("inst", "op", "shape", "start", "end", "leaf", "depth")

    def __init__(self, name: str, start: float, duration: float):
        hlo = xplane.parse_name(name)
        if hlo:
            self.inst = hlo.inst
            self.op = (f"fusion.{hlo.kind.lower()}"
                       if hlo.op == "fusion" and hlo.kind else hlo.op)
            self.shape = re.sub(r"/\*.*?\*/|\{[^}]*\}", "", hlo.shape)
        else:       # not HLO text (another backend's event)
            self.inst = name.lstrip("%")
            self.op, self.shape = re.sub(r"[.\-_]?\d+$", "", self.inst), ""
        self.start = start
        self.end = start + duration
        self.leaf = True
        self.depth = 0

    @property
    def duration(self) -> float:
        return self.end - self.start

    @property
    def label(self) -> str:
        """Name by opcode and shape: ``copy_u8_200000_28224_``,
        ``fusion.loop_u8_64_84_84_1_``."""
        if not self.shape:
            return self.op
        shape = re.sub(r"[^A-Za-z0-9]", "_", self.shape)
        return f"{self.op}_{shape}"[:LABEL_MAX]


def mark_containers(ops: List[Op]) -> None:
    """Sort by start (longer first on ties); control-flow events are
    containers and never leaves; set each op's nesting depth (the number of
    containers around it)."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        while stack and stack[-1].end <= op.start:
            stack.pop()
        op.depth = len(stack)
        if op.op in CONTAINERS:
            op.leaf = False
            stack.append(op)


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total * NS


class DeviceTrace:
    """One device plane, reduced."""

    def __init__(self, plane: Dict):
        self.name = plane["name"]
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        self.ops = [Op(*e) for e in lines.get(OPS_LINE, [])]
        self.modules = sorted((Op(*e) for e in lines.get(MODULES_LINE, [])),
                              key=lambda o: o.start)
        mark_containers(self.ops)
        self.leaves = [o for o in self.ops if o.leaf]
        # The chunk programs are the long modules; anything under a hundredth
        # of the longest (a bookkeeping op of the trainer) is not a chunk.
        longest = max((m.duration for m in self.modules), default=0.0)
        self.chunks = [m for m in self.modules
                       if m.duration >= 0.01 * longest]
        if self.leaves:
            self.window = (min(o.start for o in self.leaves),
                           max(o.end for o in self.leaves))
        else:
            self.window = (0.0, 0.0)
        self.window_s = (self.window[1] - self.window[0]) * NS
        self.busy_s = union_seconds([(o.start, o.end) for o in self.leaves])

    def idle_gaps(self) -> Dict[str, float]:
        """Seconds of idle time by cause."""
        gaps: Dict[str, float] = defaultdict(float)
        boundaries = [m.end for m in self.chunks[:-1]]
        reach, last = None, None
        for op in self.leaves:
            if reach is not None and op.start > reach:
                crosses = any(reach <= b <= op.start for b in boundaries)
                cause = (BETWEEN_CHUNKS if crosses else
                         f"inside_chunk_after_{last.op}")
                gaps[cause] += (op.start - reach) * NS
            if reach is None or op.end > reach:
                reach, last = op.end, op
        return dict(gaps)

    def op_totals(self) -> Dict[str, float]:
        totals: Dict[str, float] = defaultdict(float)
        for op in self.leaves:
            totals[op.label] += op.duration * NS
        return dict(totals)

    def train_conditional(self) -> Optional[Tuple[float, int]]:
        """(seconds inside, number of runs) of the train ``conditional``:
        the conditional instruction — events are grouped by their exact
        name, one HLO instruction each — with the most time inside it."""
        groups: Dict[str, List[Op]] = defaultdict(list)
        for op in self.ops:
            if op.op == "conditional":
                groups[op.inst].append(op)
        if not groups:
            return None
        best = max(groups.values(), key=lambda g: sum(o.duration for o in g))
        return sum(o.duration for o in best) * NS, len(best)

    def iteration_loop_seconds(self) -> Optional[float]:
        """Seconds inside the outermost ``while`` of the chunk programs: the
        scan over iterations."""
        loops = [o for o in self.ops if o.op == "while" and o.depth == 0]
        if not loops:
            return None
        return sum(o.duration for o in loops) * NS

    def outside_loop_seconds(self) -> float:
        """Busy seconds of leaves outside every container: what a chunk
        program does once, before and after its iteration loop."""
        return union_seconds([(o.start, o.end) for o in self.leaves
                              if o.depth == 0])

    def exposed_collective_seconds(self) -> Optional[float]:
        """Collective time during which no other leaf runs on this device;
        None where the program has no collective."""
        collectives = [(o.start, o.end) for o in self.leaves
                       if o.op.startswith(COLLECTIVE_PREFIXES)]
        others = [(o.start, o.end) for o in self.leaves
                  if not o.op.startswith(COLLECTIVE_PREFIXES)]
        if not collectives:
            return None
        return union_seconds(collectives + others) - union_seconds(others)


class Trace:
    """All devices of a traced window. Totals that feed ``device`` are
    averaged over the chips used; ``worst`` is the device with the largest
    idle share."""

    def __init__(self, devices: List[DeviceTrace]):
        self.devices = devices
        n = max(len(self.devices), 1)
        self.busy_s = sum(d.busy_s for d in self.devices) / n
        self.window_s = sum(d.window_s for d in self.devices) / n
        self.worst = max(self.devices, default=None,
                         key=lambda d: 1.0 - d.busy_s / d.window_s)

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        if self.worst is None:
            return {"device_ops": [], "idle_gaps": []}

        def ranked(table: Dict[str, float]):
            return [[k, v] for k, v in sorted(table.items(),
                                              key=lambda kv: -kv[1])[:top]]

        return {"device_ops": ranked(self.worst.op_totals()),
                "idle_gaps": ranked(self.worst.idle_gaps())}

    def summary(self) -> Dict:
        return {"devices": [
            {"name": d.name, "busy_s": d.busy_s, "window_s": d.window_s,
             "ops": len(d.ops), "leaves": len(d.leaves),
             "chunks": len(d.chunks), "modules": len(d.modules)}
            for d in self.devices]}


def reduce(planes: List[Dict], chips: int) -> Trace:
    """Reduce the device planes that ran ops; with more device planes than
    ``chips`` (a host with idle chips) the busiest ``chips`` are kept."""
    devices = [DeviceTrace(p) for p in planes]
    devices = sorted((d for d in devices if d.leaves),
                     key=lambda d: -d.busy_s)[:chips]
    return Trace(sorted(devices, key=lambda d: d.name))
