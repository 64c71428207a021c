"""How a rate is estimated inside one run: cycles, segments, median.

One reading is one whole loop cycle of ``train.train``, stamped fence to
fence by the harness: device program, fence, the trainer's bookkeeping, the
next dispatch. The window's whole chunks are cut into consecutive segments
of equal chunk count — one chunk each where a chunk lasts ``MIN_SEGMENT_
SECONDS``, as every cell's does, more where chunks are shorter; a segment's
rate is its work over its elapsed time. The metric is the MEDIAN of the
segment rates: a one-off stall lands in one segment and does not move it, a
program slower in every chunk moves every segment. What the median sets
aside — stalls, and any cost that falls in fewer than half of the chunks —
is reported beside it (``window_vs_median_pct``, ``chunk_wall_drift_pct``).
"""
from __future__ import annotations

import math
from statistics import median
from typing import Dict, List, Sequence

MIN_SEGMENTS = 9
MIN_SEGMENT_CHUNKS = 1
MIN_SEGMENT_SECONDS = 0.5


class TooFewChunks(ValueError):
    """The window does not hold enough whole chunks for the segment median.
    The run fails: there is no fallback to a whole-window rate."""


def plan_segments(cycles: Sequence[float],
                  min_seconds: float = MIN_SEGMENT_SECONDS) -> List[range]:
    """Index ranges of the segments: the smallest equal chunk count that
    makes every segment at least ``MIN_SEGMENT_CHUNKS`` chunks and
    ``min_seconds`` long, as many segments as fit, the remainder (fewer
    chunks than one segment) left off the end."""
    n = len(cycles)
    if n == 0 or min(cycles) <= 0.0:
        raise TooFewChunks(f"{n} chunks in the window, or a cycle of 0 s")
    k = max(MIN_SEGMENT_CHUNKS, math.ceil(min_seconds / median(cycles)))
    while k <= n and any(sum(cycles[i:i + k]) < min_seconds
                         for i in range(0, n - k + 1, k)):
        k += 1
    count = n // k
    if count < MIN_SEGMENTS:
        raise TooFewChunks(
            f"{n} chunks in the window make {count} segments of {k} chunks "
            f"(each must be >= {MIN_SEGMENT_CHUNKS} chunks and >= "
            f"{min_seconds} s); the median needs {MIN_SEGMENTS}. "
            "Lengthen the window or shorten the chunk")
    return [range(i * k, (i + 1) * k) for i in range(count)]


def segment_rates(cycles: Sequence[float], work: Sequence[float],
                  min_seconds: float = MIN_SEGMENT_SECONDS) -> List[float]:
    """Work per second of each segment (``work[i]`` was done in
    ``cycles[i]``)."""
    return [sum(work[i] for i in seg) / sum(cycles[i] for i in seg)
            for seg in plan_segments(cycles, min_seconds)]


def median_rate(cycles: Sequence[float], work: Sequence[float],
                min_seconds: float = MIN_SEGMENT_SECONDS) -> float:
    return median(segment_rates(cycles, work, min_seconds))


def window_rate(cycles: Sequence[float], work: Sequence[float]) -> float:
    """Total work over total elapsed time: what the median is compared with,
    never a metric by itself."""
    return sum(work) / sum(cycles)


def host_loop_summary(cycles: Sequence[float], walls: Sequence[float],
                      work: Sequence[float],
                      min_seconds: float = MIN_SEGMENT_SECONDS
                      ) -> Dict[str, float]:
    """The host-loop readings of one window (seconds in, ms and % out).

    ``walls[i]`` is the trainer's own dispatch-to-fence wall of chunk i, so
    ``cycles[i] - walls[i]`` is the host work between two chunks."""
    n = len(cycles)
    third = max(n // 3, 1)
    med_cycle = median(cycles)
    return {
        "chunk_host_gap_ms": 1e3 * median(c - w for c, w in zip(cycles,
                                                                walls)),
        "chunk_wall_ms": 1e3 * median(walls),
        "window_vs_median_pct":
            100.0 * (1.0 - window_rate(cycles, work)
                     / median_rate(cycles, work, min_seconds)),
        "chunk_wall_drift_pct":
            100.0 * (median(cycles[n - third:]) - median(cycles[:third]))
            / med_cycle,
    }
