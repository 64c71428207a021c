"""The trace reduction: on hand-made planes, and on the recorded trace."""
from pathlib import Path

import pytest

from perf.reduce import trace_reduce as tr
from perf.reduce import xplane

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def _plane(ops, modules, name="/device:TPU:0"):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}


def _hlo(inst, shape, op, kind=None):
    """An event name as the TPU profiler writes it: the instruction's HLO."""
    k = f", kind=k{kind}" if kind else ""
    return f"%{inst} = {shape} {op}(%p0){k}"


@pytest.fixture()
def two_chunks():
    """Two program runs of 1000 ns with a 100 ns host gap between them; in
    each, a ``while`` holding a copy, a loop fusion, a train conditional
    (with a convolution fusion inside) and an idle hole after the copy."""
    ops, modules = [], []
    for base in (0.0, 1100.0):
        modules.append(["jit_run_chunk(1)", base, 1000.0])
        ops += [
            [_hlo("while.3", "(s32[]{:T(128)}, /*index=1*/u8[4]{0})",
                  "while"), base, 1000.0],
            [_hlo("copy.7", "u8[200000,28224]{1,0:T(8,128)(4,1)}", "copy"),
             base, 200.0],
            # hole of 50 ns after the copy
            [_hlo("fusion.12", "u8[64,84,84,1]{3,2,1,0}", "fusion", "Loop"),
             base + 250.0, 300.0],
            [_hlo("conditional.5", "(f32[32]{0})", "conditional"),
             base + 550.0, 400.0],
            [_hlo("pad_fusion.40", "bf16[256,9,9,64]{3,2,1,0}", "fusion",
                  "Output"), base + 550.0, 400.0],
            [_hlo("conditional.9", "(s32[])", "conditional"),
             base + 950.0, 10.0],
            [_hlo("add.1", "s32[]", "add"), base + 950.0, 10.0],
            [_hlo("all-reduce.2", "f32[1024]{0}", "all-reduce"),
             base + 960.0, 40.0],
        ]
    return _plane(ops, modules)


def test_busy_is_the_union_of_leaves_not_of_containers(two_chunks):
    d = tr.DeviceTrace(two_chunks)
    assert [o.inst for o in d.ops if not o.leaf] == [
        "while.3", "conditional.5", "conditional.9"] * 2
    assert d.window_s == pytest.approx(2100e-9)
    assert d.busy_s == pytest.approx(2 * 950e-9)        # not 2 x 1000
    assert len(d.chunks) == 2


def test_idle_gaps_are_attributed(two_chunks):
    gaps = tr.DeviceTrace(two_chunks).idle_gaps()
    assert gaps == {
        "inside_chunk_after_copy": pytest.approx(100e-9),
        tr.BETWEEN_CHUNKS: pytest.approx(100e-9)}


def test_ops_are_named_by_op_and_shape(two_chunks):
    totals = tr.DeviceTrace(two_chunks).op_totals()
    assert totals["copy_u8_200000_28224_"] == pytest.approx(400e-9)
    assert totals["fusion.loop_u8_64_84_84_1_"] == pytest.approx(600e-9)
    assert totals["fusion.output_bf16_256_9_9_64_"] == pytest.approx(800e-9)
    assert totals["all-reduce_f32_1024_"] == pytest.approx(80e-9)


def test_train_conditional_and_iteration_loop(two_chunks):
    d = tr.DeviceTrace(two_chunks)
    seconds, runs = d.train_conditional()
    assert seconds == pytest.approx(800e-9) and runs == 2   # not conditional.9
    assert d.iteration_loop_seconds() == pytest.approx(2000e-9)
    assert d.outside_loop_seconds() == 0.0


def test_exposed_collective_time(two_chunks):
    d = tr.DeviceTrace(two_chunks)
    assert d.exposed_collective_seconds() == pytest.approx(80e-9)
    # hide half of one all-reduce under a fusion: 20 ns less exposed
    two_chunks["lines"][0]["events"].append(
        [_hlo("fusion.77", "f32[8]{0}", "fusion", "Loop"), 980.0, 20.0])
    assert tr.DeviceTrace(two_chunks).exposed_collective_seconds() == \
        pytest.approx(60e-9)


def test_trace_averages_devices_and_picks_the_idlest(two_chunks):
    idle = _plane([[_hlo("copy.1", "f32[2]{0}", "copy"), 0.0, 100.0],
                   [_hlo("copy.2", "f32[2]{0}", "copy"), 900.0, 100.0]],
                  [["m(1)", 0.0, 1000.0]], "/device:TPU:1")
    empty = _plane([], [], "/device:TPU:2")
    trace = tr.reduce([two_chunks, idle, empty], chips=2)
    assert [d.name for d in trace.devices] == ["/device:TPU:0",
                                               "/device:TPU:1"]
    assert trace.worst.name == "/device:TPU:1"
    assert trace.busy_s == pytest.approx((1900e-9 + 200e-9) / 2)
    b = trace.breakdown()
    assert b["device_ops"] == [["copy_f32_2_", pytest.approx(200e-9)]]
    assert b["idle_gaps"] == [["inside_chunk_after_copy",
                               pytest.approx(800e-9)]]
    assert tr.reduce([empty], chips=1).worst is None


def test_event_names_are_parsed_and_compacted():
    long = ("%fusion.584 = u8[64,84,84,1]{2,3,1,0:T(4,128)(4,1)S(1)} fusion("
            "pred[64,84]{1,0} %a.12, pred[84]{0} %b.14), kind=kLoop, "
            "calls=%fused_computation.521.clone.clone")
    short = xplane.compact_name(long)
    assert len(short) < len(long) / 2
    for name in (long, short):
        op = tr.Op(name, 0.0, 1.0)
        assert (op.inst, op.op) == ("fusion.584", "fusion.loop")
        assert op.label == "fusion.loop_u8_64_84_84_1_"
    kernel = tr.Op('%per_stratified_sample.3 = (s32[512,1]{1,0}, f32[1,1]{1,0})'
                   ' custom-call(f32[2048,512]{1,0} %r), custom_call_target='
                   '"tpu_custom_call"', 0.0, 1.0)
    assert kernel.inst == "per_stratified_sample.3"
    assert kernel.op == "custom-call"
    plain = tr.Op("some-event.4", 0.0, 1.0)       # not HLO text
    assert (plain.inst, plain.op, plain.label) == ("some-event.4",
                                                   "some-event", "some-event")


# -- the recorded traces (TPU v5e, PR 23: two traced chunks each, at a short
# chunk so the files stay small; made with ``run.py --dump-trace``) --------

@pytest.fixture(scope="module")
def atari_trace():
    return tr.reduce(xplane.read_dump(
        TESTDATA / "atari_preset_2x40iters.json.gz"), chips=1)


@pytest.fixture(scope="module")
def apex_trace():
    return tr.reduce(xplane.read_dump(
        TESTDATA / "apex_preset_2x20iters.json.gz"), chips=1)


def test_recorded_atari_trace_reduces_to_the_pinned_numbers(atari_trace):
    d = atari_trace.worst
    assert (len(d.ops), len(d.leaves), len(d.chunks)) == (15984, 15902, 2)
    assert d.window_s == pytest.approx(0.121163573, rel=1e-9)
    assert d.busy_s == pytest.approx(0.115826606, rel=1e-9)
    assert atari_trace.busy_s == d.busy_s
    # 2 chunks x 40 iterations: the train conditional ran 80 times (a grad
    # step on every 4th), inside the one outermost while of each chunk
    seconds, runs = d.train_conditional()
    assert runs == 80 and seconds == pytest.approx(0.012471527, rel=1e-9)
    assert d.iteration_loop_seconds() == pytest.approx(0.027046067, rel=1e-9)
    # the ring's layout copies at chunk entry and exit dwarf a short chunk
    assert d.outside_loop_seconds() == pytest.approx(0.090549359, rel=1e-9)


def test_recorded_atari_breakdown_keeps_pr22s_naming(atari_trace):
    b = atari_trace.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0] == ["copy_u8_200000_28224_",
                                  pytest.approx(0.09051086, rel=1e-9)]
    names = [n for n, _ in b["device_ops"]]
    assert {"fusion.loop_u8_64_84_84_1_", "fusion.custom_u8_256_28224_",
            "dynamic-update-slice_u8_200000_28224_"} <= set(names)
    assert b["idle_gaps"][0] == [tr.BETWEEN_CHUNKS,
                                 pytest.approx(0.003566839, rel=1e-9)]
    assert all(n.startswith("inside_chunk_after_")
               for n, _ in b["idle_gaps"][1:])
    total_gaps = sum(atari_trace.worst.idle_gaps().values())
    assert total_gaps == pytest.approx(
        atari_trace.worst.window_s - atari_trace.worst.busy_s, rel=1e-6)


def test_metric_readers_on_the_recorded_traces(atari_trace, apex_trace):
    from perf.harness.manifest import Manifest

    manifest = Manifest(TESTDATA.parents[1])
    run = {"traced_chunks": 2, "chunk_iters": 40, "grad_steps_per_chunk": 10}
    read = manifest.metric_reader
    assert read("collect_ms_per_iter")(run, atari_trace) == pytest.approx(
        1e3 * (0.027046067 - 0.012471527) / 80)
    assert read("learn_ms_per_grad_step")(run, atari_trace) == pytest.approx(
        1e3 * 0.012471527 / 20)
    assert read("chunk_outside_loop_ms")(run, atari_trace) == pytest.approx(
        1e3 * 0.090549359 / 2)
    assert read("device_idle_share")(run, atari_trace) == pytest.approx(
        4.40476198, rel=1e-6)
    # no Mosaic kernel and no collective in the atari program: nothing to
    # read, so the metric is left out
    assert read("sampler_kernel_ms_per_draw")(run, atari_trace) is None
    assert read("pmean_exposed_ms_per_grad_step")(run, atari_trace) is None
    # 2 chunks x 20 iterations of apex: 40 draws by the Mosaic kernel
    assert read("sampler_kernel_ms_per_draw")(run, apex_trace) == \
        pytest.approx(0.0538004, rel=1e-4)


def test_readers_of_the_record_take_what_the_harness_computed():
    """Host-loop, memory and utilisation readers read the run's record; a
    record without the counter gives nothing, not a zero."""
    from perf.harness import estimator
    from perf.harness.manifest import Manifest

    read = Manifest(TESTDATA.parents[1]).metric_reader
    cycles, walls = [0.9] * 12, [0.898] * 12
    run = {"host_loop": estimator.host_loop_summary(cycles, walls,
                                                    [1.0] * 12),
           "memory_stats": {"peak_bytes_in_use": 5_701_156_352,
                            "peak_bytes_reserved": 5_665_521_664},
           "rates": {"grad_steps_per_s": 690.0}, "chips": 1,
           "grad_step_flops": 256 * 86.9e6,
           "device": {"kind": "TPU v5 lite"}}
    assert read("chunk_host_gap_ms")(run, None) == pytest.approx(2.0)
    assert read("chunk_wall_ms")(run, None) == pytest.approx(898.0)
    assert read("window_vs_median_pct")(run, None) == pytest.approx(0.0)
    assert read("chunk_wall_drift_pct")(run, None) == pytest.approx(0.0)
    assert read("chunk_temp_reserved_gb")(run, None) == 5.665521664
    assert read("chunk_temp_reserved_gb")(dict(run, memory_stats={}),
                                          None) is None
    # 86.9 MFLOP a sample (PERF.md section 7) x 256 x 690 / 197 TFLOP/s
    assert read("train_mfu")(run, None) == pytest.approx(7.8, abs=0.1)


def test_only_device_planes_and_the_two_lines_are_kept():
    from types import SimpleNamespace as NS

    def event(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur)

    long = _hlo("fusion.9", "f32[8]{0}", "fusion", "Loop") + ", calls=%f.1"
    profile = NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            event("x", 0, 1)])]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=[event(long, 5, 2)]),
            NS(name="Steps", events=[event("step", 0, 9)]),
            NS(name="XLA Modules", events=[event("jit_run(1)", 0, 9)])])])
    planes = xplane._planes_of(profile)
    assert planes == [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops",
         "events": [["%fusion.9 = f32[8]{0} fusion(), kind=kLoop", 5.0, 2.0]]},
        {"name": "XLA Modules", "events": [["jit_run(1)", 0.0, 9.0]]}]}]
    assert tr.reduce(planes, chips=1).worst.op_totals() == {
        "fusion.loop_f32_8_": pytest.approx(2e-9)}
