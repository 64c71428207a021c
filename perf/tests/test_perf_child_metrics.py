"""The readers of stage ``loss_grad``'s child names (perf/metrics/_children.py
and the six files that call it) and ``chunk_bookkeeping_worst_ms``, on a
recorded TPU trace of ``r2d2.preset`` with the names in (PR 42:
``testdata/r2d2_named_*``, 2 iterations a chunk, the chunk program's own
optimized text beside it), on PR 29's pair from before the names, and on
hand-written tables."""
import gzip
from pathlib import Path

import pytest

from dist_dqn_tpu.telemetry import flight, stages
from perf.harness.manifest import Manifest
from perf.metrics import _children, _stages
from perf.reduce import trace_reduce as tr
from perf.reduce import xplane

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
MANIFEST = Manifest(TESTDATA.parents[1])
READ = MANIFEST.metric_reader
RUN = {"chips": 1, "device": {"kind": "TPU v5 lite"}, "traced_chunks": 2,
       "chunk_iters": 2, "grad_steps_per_chunk": 2,
       "grad_step_flops": 579525672960.0}
PAIRS = {"named": ("r2d2_named_2x2iters", "r2d2_named_v5e"),
         "pr29": ("r2d2_preset_2x2iters", "r2d2_preset_v5e")}
PASSES = ["burn_in_ms_per_grad_step", "online_unroll_ms_per_grad_step",
          "target_unroll_ms_per_grad_step"]
PARTS = ["torso_ms_per_grad_step", "core_ms_per_grad_step"]
NEW = PASSES + PARTS + ["loss_grad_unsplit_share"]
PER_GRAD_STEP = ["sample_ms_per_grad_step", "gather_ms_per_grad_step",
                 "loss_grad_ms_per_grad_step", "optimizer_ms_per_grad_step",
                 "writeback_ms_per_grad_step"]
# the twelve generic readers a benchmark PR lists this cell under (D7)
GENERIC = (["act_ms_per_iter", "env_ms_per_iter", "insert_ms_per_iter"]
           + PER_GRAD_STEP + ["loss_grad_mfu", "stage_unattributed_share",
                              "collect_ms_per_iter",
                              "learn_ms_per_grad_step"])


@pytest.fixture(scope="module")
def recorded_pairs():
    """pair -> (reduced trace, stage table, {group: children})."""
    out = {}
    for pair, (trace, hlo) in PAIRS.items():
        with gzip.open(TESTDATA / f"{hlo}.hlo.txt.gz", "rt") as f:
            text = f.read()
        out[pair] = (
            tr.reduce(xplane.read_dump(TESTDATA / f"{trace}.json.gz"),
                      chips=1),
            stages.table_from_text(text),
            {group: stages.children_from_text(text, getattr(stages, group))
             for group in ("PASSES", "PARTS")})
    return out


@pytest.fixture()
def use_tables(monkeypatch):
    def use(table, children):
        monkeypatch.setattr(_stages, "table", lambda run=None: table)
        monkeypatch.setattr(
            _children, "children",
            lambda run, group: None if children is None
            else children[group])
    return use


def _read_all(names, trace):
    run = dict(RUN)
    return {n: READ(n)(run, trace) for n in names}


# pinned from the recording (my chip run, PR 42); the same tree's traced run
# at the cell's own 40 iterations a chunk read 1.8004, 6.2442, 1.9830,
# 7.9278, 1.5371 and 0.0367 (PERF.md section 5)
PINNED = {
    "burn_in_ms_per_grad_step": 1.80057575,
    "online_unroll_ms_per_grad_step": 6.24380975,
    "target_unroll_ms_per_grad_step": 1.98320825,
    "torso_ms_per_grad_step": 7.9281235,
    "core_ms_per_grad_step": 1.537274,
    "loss_grad_unsplit_share": 0.0366429194,
}


@pytest.mark.parametrize("metric", NEW)
def test_child_reader_on_the_recorded_trace(recorded_pairs, use_tables,
                                            metric):
    trace, table, children = recorded_pairs["named"]
    use_tables(table, children)
    assert READ(metric)(dict(RUN), trace) == pytest.approx(PINNED[metric],
                                                           rel=1e-5)


def test_children_split_loss_grad_and_take_nothing_from_it(recorded_pairs,
                                                           use_tables):
    trace, table, children = recorded_pairs["named"]
    use_tables(table, children)
    v = _read_all(NEW + ["loss_grad_ms_per_grad_step"], trace)
    loss_grad = v["loss_grad_ms_per_grad_step"]
    # the passes and what they leave are the stage, to the nanosecond
    assert sum(v[n] for n in PASSES) == pytest.approx(
        loss_grad * (1.0 - v["loss_grad_unsplit_share"] / 100.0), rel=1e-9)
    assert v["loss_grad_unsplit_share"] < 10.0
    # the parts split it another way and leave the heads and the loss out
    assert 0.8 * loss_grad < sum(v[n] for n in PARTS) <= loss_grad
    # the stage's own reading does not know the children exist
    use_tables(table, None)
    assert READ("loss_grad_ms_per_grad_step")(dict(RUN), trace) == loss_grad


def test_the_generic_stage_readers_read_the_named_program(recorded_pairs,
                                                          use_tables):
    """What the queued benchmark PR lists this cell under: every generic
    reader finds something, and the five per-grad-step stages cover the
    train ``conditional`` as they do in the DQN cells."""
    trace, table, children = recorded_pairs["named"]
    use_tables(table, children)
    v = _read_all(GENERIC, trace)
    assert [n for n in GENERIC if v[n] is None] == []
    learn = sum(v[n] for n in PER_GRAD_STEP)
    assert 0.85 * v["learn_ms_per_grad_step"] < learn < v[
        "learn_ms_per_grad_step"]
    assert v["stage_unattributed_share"] < 5.0
    assert 25.0 < v["loss_grad_mfu"] < 100.0


@pytest.mark.parametrize("has_groups", [True, False],
                         ids=["no_loss_grad_stage", "no_child_groups"])
def test_before_the_names_every_child_metric_is_left_out(recorded_pairs,
                                                         use_tables,
                                                         has_groups):
    """PR 29's pair: the table holds one name, ``gather``, so no
    instruction is of stage ``loss_grad``; and a program whose
    ``telemetry/stages.py`` has no child groups at all (the parent's)."""
    trace, table, children = recorded_pairs["pr29"]
    assert children == {"PASSES": {}, "PARTS": {}}
    use_tables(table, children if has_groups else None)
    assert set(_read_all(NEW, trace).values()) == {None}


def test_a_program_without_the_groups_reads_none_and_does_not_raise(
        monkeypatch):
    for name in ("PASSES", "PARTS"):
        monkeypatch.delattr(stages, name)
    assert _children.children({}, "PASSES") is None
    assert _children.children({}, "PARTS") is None


def test_hand_written_children_are_joined_by_instruction_name(
        recorded_pairs, use_tables):
    """Two instructions of stage ``loss_grad`` and one of ``act`` named by
    hand: a child counts under ``loss_grad`` only, an absent child is None
    (never a guess), ``mixed`` and None are the unsplit share."""
    trace, _, _ = recorded_pairs["named"]
    by_inst = {}
    for o in trace.devices[0].leaves:
        if o.depth:
            by_inst[o.inst] = by_inst.get(o.inst, 0.0) + o.duration * tr.NS
    a, b, c, d = sorted(by_inst, key=by_inst.get)[-4:]
    table = {a: "loss_grad", b: "loss_grad", c: "loss_grad", d: "act"}
    full = {a: "online_unroll", b: stages.MIXED, d: "burn_in"}
    use_tables(table, {"PASSES": {i: full.get(i) for i, s in table.items()
                                  if s == "loss_grad"}, "PARTS": {}})
    v = _read_all(NEW, trace)
    assert v["online_unroll_ms_per_grad_step"] == pytest.approx(
        1e3 * by_inst[a] / 4)
    assert v["loss_grad_unsplit_share"] == pytest.approx(
        100.0 * (by_inst[b] + by_inst[c])
        / (by_inst[a] + by_inst[b] + by_inst[c]))
    assert [n for n in NEW if v[n] is not None] == [
        "online_unroll_ms_per_grad_step", "loss_grad_unsplit_share"]


def test_the_new_metrics_are_listed_where_their_names_are():
    listed = {m["name"]: m for m in MANIFEST.data["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == ["r2d2.preset"]
        assert listed[name]["moves"] == "grad_steps_per_s"
    cells = [w["name"] for w in MANIFEST.data["workloads"]]
    assert listed["chunk_bookkeeping_worst_ms"]["workloads"] == cells


# -- the host span ---------------------------------------------------------
@pytest.fixture()
def spans():
    flight._reset_for_tests()
    yield flight.configure(enabled=True, capacity=256)
    flight._reset_for_tests()


def _record_chunks(ring, bookkeeping_s):
    for s in bookkeeping_s:
        ring.record("span", "fused.dispatch", dur_s=0.001)
        ring.record("span", "fused.fence", dur_s=0.7)
        ring.record("span", "fused.bookkeeping", dur_s=s)


def test_chunk_bookkeeping_reads_the_spans_wholly_inside_the_window(spans):
    # 3 warm-up chunks (the last one's span holds the collection before the
    # window), 5 in the window (the last one's holds the profiler's start),
    # 2 traced
    _record_chunks(spans, [0.002, 0.002, 0.090]
                   + [0.0011, 0.0012, 0.0150, 0.0010, 0.4000]
                   + [0.003, 0.003])
    run = {"series": {"cycle_s": [0.72] * 5}, "traced_chunks": 2}
    assert READ("chunk_bookkeeping_worst_ms")(run, None) == pytest.approx(
        15.0)
    # an untraced run: the window's chunks are the last ones
    run = {"series": {"cycle_s": [0.72] * 2}, "traced_chunks": 0}
    assert READ("chunk_bookkeeping_worst_ms")(run, None) == pytest.approx(
        3.0)
    # one chunk: no span lies wholly inside the window
    run = {"series": {"cycle_s": [0.72]}, "traced_chunks": 0}
    assert READ("chunk_bookkeeping_worst_ms")(run, None) is None


def test_chunk_bookkeeping_is_left_out_where_no_span_was_recorded(spans):
    spans.record("chunk", "fused.chunk", frames=1, loss=0.0, wall_s=0.7)
    run = {"series": {"cycle_s": [0.72] * 5}, "traced_chunks": 2}
    assert READ("chunk_bookkeeping_worst_ms")(run, None) is None
