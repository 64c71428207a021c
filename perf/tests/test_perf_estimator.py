"""The segment-median estimator on synthetic series."""
import pytest

from perf.harness import estimator


def _series(n=21, cycle=0.9, work=128000.0):
    return [cycle] * n, [work] * n


def test_steady_series_gives_the_plain_rate():
    cycles, work = _series()
    assert estimator.median_rate(cycles, work) == pytest.approx(128000 / 0.9)
    assert len(estimator.plan_segments(cycles)) == 21      # one chunk each


@pytest.mark.parametrize("stall_at", [0, 7, 12, 20])
def test_one_stalled_cycle_does_not_move_the_median(stall_at):
    cycles, work = _series()
    cycles[stall_at] += 1.5            # a collection pass, a busy neighbour
    assert estimator.median_rate(cycles, work) == pytest.approx(128000 / 0.9)
    # ... while the whole-window rate does move, and says by how much:
    summary = estimator.host_loop_summary(cycles, [0.898] * 21, work)
    assert summary["window_vs_median_pct"] == pytest.approx(
        100 * (1 - (21 * 0.9) / (21 * 0.9 + 1.5)))


def test_uniform_slowdown_moves_the_median_by_its_size():
    cycles, work = _series()
    slow = [c * 1.03 for c in cycles]
    assert (estimator.median_rate(slow, work)
            / estimator.median_rate(cycles, work)) == pytest.approx(1 / 1.03)


@pytest.mark.parametrize("n,cycle", [(0, 0.9), (8, 0.9), (26, 0.2),
                                     (40, 0.1)])
def test_too_few_chunks_is_an_error_not_a_fallback(n, cycle):
    cycles, work = _series(n, cycle)
    with pytest.raises(estimator.TooFewChunks):
        estimator.median_rate(cycles, work)


def test_segments_are_equal_whole_and_long_enough():
    cycles, _ = _series(100, 0.0625)
    segments = estimator.plan_segments(cycles)
    assert {len(s) for s in segments} == {8}            # 8 x 0.0625 = 0.5 s
    assert len(segments) == 12 and segments[-1].stop <= 100
    assert all(sum(cycles[i] for i in s) >= 0.5 for s in segments)
    # consecutive, no overlap
    assert [s.start for s in segments] == list(range(0, 96, 8))


def test_drift_and_gap_readings():
    cycles = [1.0] * 15 + [1.0] * 15 + [1.1] * 15
    walls = [c - 0.002 for c in cycles]
    s = estimator.host_loop_summary(cycles, walls, [1.0] * 45)
    assert s["chunk_wall_drift_pct"] == pytest.approx(10.0)
    assert s["chunk_host_gap_ms"] == pytest.approx(2.0)
    assert s["chunk_wall_ms"] == pytest.approx(998.0)
