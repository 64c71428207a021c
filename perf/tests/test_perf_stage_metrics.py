"""The per-stage readers (perf/metrics/_stages.py and the thirteen files
that call it) on the recorded TPU traces of PR 23, joined to stage tables:
the program's own (its chunk programs compiled for v5e in the sandbox, PR 26:
``testdata/*_v5e.hlo.txt.gz`` — stage names are metadata, so the instruction
names are the trace's), hand-written ones, an empty one and none."""
import gzip
from pathlib import Path

import pytest

from dist_dqn_tpu.telemetry import flight, stages
from perf.harness.manifest import Manifest
from perf.metrics import _stages
from perf.reduce import trace_reduce as tr
from perf.reduce.flops import grad_step_flops
from perf.reduce import xplane

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
READ = Manifest(TESTDATA.parents[1]).metric_reader
_BASE = {"chips": 1, "device": {"kind": "TPU v5 lite"}}
CELLS = {
    "atari": ("atari_preset_2x40iters", "atari_preset_v5e", dict(
        _BASE, traced_chunks=2, chunk_iters=40, grad_steps_per_chunk=10,
        grad_step_flops=grad_step_flops(256, dueling=False))),
    "apex": ("apex_preset_2x20iters", "apex_preset_v5e", dict(
        _BASE, traced_chunks=2, chunk_iters=20, grad_steps_per_chunk=20,
        grad_step_flops=grad_step_flops(512, dueling=True))),
}
PER_ITER = ["act_ms_per_iter", "env_ms_per_iter", "insert_ms_per_iter"]
PER_GRAD_STEP = ["sample_ms_per_grad_step", "gather_ms_per_grad_step",
                 "loss_grad_ms_per_grad_step", "optimizer_ms_per_grad_step",
                 "writeback_ms_per_grad_step"]
STAGE_METRICS = PER_ITER + PER_GRAD_STEP + ["loss_grad_mfu"]


@pytest.fixture(scope="module")
def recorded():
    """cell -> (reduced trace, the program's stage table, run record)."""
    out = {}
    for cell, (trace, hlo, run) in CELLS.items():
        with gzip.open(TESTDATA / f"{hlo}.hlo.txt.gz", "rt") as f:
            table = stages.table_from_text(f.read())
        out[cell] = (tr.reduce(xplane.read_dump(
            TESTDATA / f"{trace}.json.gz"), chips=1), table, run)
    return out


@pytest.fixture()
def use_table(monkeypatch):
    def use(table):
        monkeypatch.setattr(_stages, "table", lambda run=None: table)
    return use


def _read_all(names, run, trace):
    return {n: READ(n)(run, trace) for n in names}


# pinned from the traces: PR 25's ledger read 0.0577, 0.0610, 0.0457 and
# 0.0070, 0.192, 0.346, 0.0205 in atari.preset at 2000 iterations a chunk
PINNED = {
    "atari": {"act_ms_per_iter": 0.0603235, "env_ms_per_iter": 0.0683951,
              "insert_ms_per_iter": 0.0417331,
              "sample_ms_per_grad_step": 0.0111862,
              "gather_ms_per_grad_step": 0.1921794,
              "loss_grad_ms_per_grad_step": 0.345773,
              "optimizer_ms_per_grad_step": 0.0189494,
              "writeback_ms_per_grad_step": None},
    "apex": {"act_ms_per_iter": 0.0505545, "env_ms_per_iter": 0.0262414,
             "insert_ms_per_iter": 0.0320736,
             "sample_ms_per_grad_step": 0.1133921,
             "gather_ms_per_grad_step": 1.5962821,
             "loss_grad_ms_per_grad_step": 0.6655023,
             "optimizer_ms_per_grad_step": 0.0530142,
             "writeback_ms_per_grad_step": 0.0526797},
}


@pytest.mark.parametrize("metric", PER_ITER + PER_GRAD_STEP)
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_stage_reader_on_the_recorded_trace(recorded, use_table, cell,
                                            metric):
    trace, table, run = recorded[cell]
    use_table(table)
    value, pinned = READ(metric)(run, trace), PINNED[cell][metric]
    if pinned is None:      # no write-back in a uniform ring: left out
        assert value is None
    else:
        assert value == pytest.approx(pinned, rel=1e-5)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_stage_sums_stay_inside_the_outside_timed_halves(recorded, use_table,
                                                         cell):
    trace, table, run = recorded[cell]
    use_table(table)
    v = _read_all(STAGE_METRICS + ["collect_ms_per_iter",
                                   "learn_ms_per_grad_step",
                                   "stage_unattributed_share",
                                   "loop_gap_share"], run, trace)
    collect = sum(v[n] for n in PER_ITER)
    learn = sum(v[n] or 0.0 for n in PER_GRAD_STEP)
    # busy time under the stages never exceeds the half timed from outside
    # (which also holds the launch gaps), and leaves little of it out
    assert 0.85 * v["collect_ms_per_iter"] < collect < v[
        "collect_ms_per_iter"]
    assert 0.85 * v["learn_ms_per_grad_step"] < learn < v[
        "learn_ms_per_grad_step"]
    assert v["stage_unattributed_share"] < 5.0
    assert 0.0 < v["loop_gap_share"] < 10.0
    # the matmul part alone: far above train_mfu's 8%, under the peak
    assert 25.0 < v["loss_grad_mfu"] < 100.0


def test_loop_gap_share_needs_no_table(recorded, use_table):
    trace, _, run = recorded["atari"]
    use_table(None)
    d = trace.devices[0]
    loop = d.iteration_loop_seconds()
    busy = tr.union_seconds([(o.start, o.end) for o in d.leaves if o.depth])
    assert READ("loop_gap_share")(run, trace) == pytest.approx(
        100.0 * (1.0 - busy / loop))


@pytest.mark.parametrize("table,unattributed", [
    (None, None),       # a program from before stage names: no table at all
    ({}, 100.0),        # names lost (a stale compile cache): all of it
], ids=["no_table", "empty_table"])
def test_without_names_every_stage_metric_is_left_out(recorded, use_table,
                                                      table, unattributed):
    trace, _, run = recorded["apex"]
    use_table(table)
    assert set(_read_all(STAGE_METRICS, run, trace).values()) == {None}
    assert READ("stage_unattributed_share")(run, trace) == unattributed


def test_hand_written_table_is_joined_by_instruction_name(recorded,
                                                          use_table):
    """Two instructions named by hand: their time is their stage's, every
    other stage is absent (None, never a guess), the rest unattributed."""
    trace, _, run = recorded["atari"]
    d = trace.devices[0]
    in_loop = [o for o in d.leaves if o.depth]
    by_inst = {}
    for o in in_loop:
        by_inst[o.inst] = by_inst.get(o.inst, 0.0) + o.duration * tr.NS
    # the two heaviest instructions of the loop
    a, b = sorted(by_inst, key=by_inst.get)[-2:]
    use_table({a: "env", b: "gather", "not.in.the.trace": "act"})
    v = _read_all(STAGE_METRICS + ["stage_unattributed_share"], run, trace)
    assert v["env_ms_per_iter"] == pytest.approx(1e3 * by_inst[a] / 80)
    assert v["gather_ms_per_grad_step"] == pytest.approx(
        1e3 * by_inst[b] / 20)
    assert [n for n in STAGE_METRICS if v[n] is not None] == [
        "env_ms_per_iter", "gather_ms_per_grad_step"]
    assert v["stage_unattributed_share"] == pytest.approx(
        100.0 * (1.0 - (by_inst[a] + by_inst[b]) / sum(by_inst.values())))
    # ``mixed`` counts as unattributed
    use_table({a: "env", b: stages.MIXED})
    assert READ("stage_unattributed_share")(run, trace) == pytest.approx(
        100.0 * (1.0 - by_inst[a] / sum(by_inst.values())))


# -- the recurrent loop (PR 29): split without names --------------------------
R2D2 = ("r2d2_preset_2x2iters", "r2d2_preset_v5e", dict(
    _BASE, traced_chunks=2, chunk_iters=2, grad_steps_per_chunk=2,
    grad_step_flops=579525672960.0, rates={"grad_steps_per_s": 42.46}))


@pytest.fixture(scope="module")
def recorded_r2d2():
    """``r2d2.preset`` at 2 iterations a chunk (a traffic file in a copy of
    the root, ``perf/README.md``): the reduced trace, the program's stage
    table and the run record."""
    trace, hlo, run = R2D2
    with gzip.open(TESTDATA / f"{hlo}.hlo.txt.gz", "rt") as f:
        table = stages.table_from_text(f.read())
    return (tr.reduce(xplane.read_dump(TESTDATA / f"{trace}.json.gz"),
                      chips=1), table, run)


def test_recurrent_chunk_is_split_at_its_train_conditional(recorded_r2d2,
                                                           use_table):
    """The metrics that need no stage name read the sequence loop as they
    read the fused one: the outermost ``while`` is the scan over iterations,
    the ``conditional`` with most time in it the sequence train step (the
    LSTM's own ``while`` loops lie inside it), the ring's layout copies
    stand outside the loop."""
    trace, table, run = recorded_r2d2
    use_table(table)
    v = _read_all(["collect_ms_per_iter", "learn_ms_per_grad_step",
                   "chunk_outside_loop_ms", "loop_gap_share",
                   "device_idle_share", "train_mfu"], run, trace)
    assert v["collect_ms_per_iter"] == pytest.approx(0.139596, rel=1e-5)
    assert v["learn_ms_per_grad_step"] == pytest.approx(22.4440988, rel=1e-6)
    assert v["chunk_outside_loop_ms"] == pytest.approx(34.9300345, rel=1e-6)
    assert v["loop_gap_share"] == pytest.approx(0.311052, rel=1e-4)
    assert v["device_idle_share"] == pytest.approx(2.62302, rel=1e-4)
    # 0.5795 TFLOP a grad step x 42.46 steps/s over 197 TFLOP/s
    assert v["train_mfu"] == pytest.approx(12.49, abs=0.01)
    d = trace.devices[0]
    assert d.train_conditional()[1] == 4          # one run an iteration
    # loop + what stands outside it is the chunk program, to a hundredth
    chunk_ms = 1e3 * sum(m.duration for m in d.chunks) * tr.NS / 2
    inside = 2 * (v["collect_ms_per_iter"] + v["learn_ms_per_grad_step"])
    assert inside + v["chunk_outside_loop_ms"] == pytest.approx(chunk_ms,
                                                                rel=0.01)


def test_recurrent_program_enters_no_stage_names_yet(recorded_r2d2,
                                                     use_table):
    """``r2d2_loop.py`` and ``agents/r2d2.py`` enter no stage: the table of
    the compiled program holds one name, ``gather`` — the XLA primitive's
    own name on the op path of every indexed read, which the table's rule
    takes for the stage — most of the loop stays under no stage, and the
    instrument says so itself. That is why the metrics that need a stage
    name leave this cell out of their ``workloads`` lists until a PR that
    may touch the program enters the names; the ones that need none (the
    loop's gaps from the trace, the dispatch span from the flight ring) are
    reported here as in every cell."""
    trace, table, run = recorded_r2d2
    use_table(table)
    assert set(table.values()) <= {"gather"}
    assert READ("stage_unattributed_share")(run, trace) > 50.0
    v = _read_all(STAGE_METRICS + ["sampler_kernel_ms_per_draw"], run, trace)
    assert [n for n in v if v[n] is not None] == ["gather_ms_per_grad_step"]
    per_layer = Manifest(TESTDATA.parents[1]).metrics_of("per_layer",
                                                         "r2d2.preset")
    listed = {m["name"] for m in per_layer}
    assert not listed & set(STAGE_METRICS + ["stage_unattributed_share"])
    assert {"loop_gap_share", "chunk_dispatch_ms",
            "chunk_dispatch_worst_ms"} <= listed


# -- the host span ---------------------------------------------------------
@pytest.fixture()
def ring():
    flight._reset_for_tests()
    yield flight.configure(enabled=True, capacity=256)
    flight._reset_for_tests()


def _chunks(ring, dispatch_s):
    for s in dispatch_s:
        ring.record("span", "fused.dispatch", dur_s=s)
        ring.record("span", "fused.fence", dur_s=0.7)
        ring.record("span", "fused.bookkeeping", dur_s=0.001)


def test_chunk_dispatch_reads_the_windows_spans_from_the_flight_ring(ring):
    # 3 warm-up chunks (the first compiles), 5 in the window, 2 traced
    _chunks(ring, [9.0, 0.002, 0.002]
            + [0.0008, 0.0009, 0.0012, 0.0008, 0.0010] + [0.004, 0.005])
    run = {"series": {"cycle_s": [0.72] * 5}, "traced_chunks": 2}
    assert READ("chunk_dispatch_ms")(run, None) == pytest.approx(0.9)
    assert READ("chunk_dispatch_worst_ms")(run, None) == pytest.approx(1.2)
    # an untraced run: the window's chunks are the last ones
    run = {"series": {"cycle_s": [0.72] * 2}, "traced_chunks": 0}
    assert READ("chunk_dispatch_worst_ms")(run, None) == pytest.approx(5.0)


def test_chunk_dispatch_is_left_out_where_no_span_was_recorded(ring):
    """The parent's program: chunk events, no ``fused.dispatch`` span."""
    ring.record("chunk", "fused.chunk", frames=1, loss=0.0, wall_s=0.7)
    run = {"series": {"cycle_s": [0.72] * 5}, "traced_chunks": 2}
    assert READ("chunk_dispatch_ms")(run, None) is None
    assert READ("chunk_dispatch_worst_ms")(run, None) is None
