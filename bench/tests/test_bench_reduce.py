"""The trace reduction (bench/trace.py) on small traces with hand-worked
answers: busy and idle time, time under a kernel scope, collective time
not hidden behind compute, the breakdown's top ops and idle gaps."""
import gzip
import json
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
HOST = "/host:CPU"


def op(pl, name, t, d):
    return {"pl": pl, "ln": tr.OPS_LINE, "name": name, "t": float(t),
            "d": float(d)}


def module(pl, name, t, d):
    return {"pl": pl, "ln": tr.MODULES_LINE, "name": name, "t": float(t),
            "d": float(d)}


def host(name, t, d):
    return {"pl": HOST, "ln": "python", "name": name, "t": float(t),
            "d": float(d)}


@pytest.fixture
def small():
    """Window [100, 1100) ns. Device 0 runs the program ``jit_step``
    over 0-800: fusion 100-250, the fd op's custom call 250-440,
    all-gather 500-700 of which 600-650 overlaps a fusion; then nothing
    700-1000, and another program's op named like the fd call at
    1000-1200 (clipped at 1100). Device 1: one 400 ns fusion. The host
    was in ``bench.data`` over 700-1000."""
    return tr.Trace([
        host(tr.WINDOW, 100, 1000),
        host("bench.trainer_step", 100, 600),
        host("bench.data", 700, 300),
        module(DEV0, "jit_step(4487466698269650707)", 0, 800),
        op(DEV0, "%fusion.1 = f32[8]{0} fusion(%p.1), kind=kLoop", 100, 150),
        op(DEV0, "%custom-call.4 = f32[8]{0} custom-call(%p.1)", 250, 190),
        op(DEV0, "%all-gather.3 = f32[8]{0} all-gather(%p.2)", 500, 200),
        op(DEV0, "%fusion.2 = f32[8]{0} fusion(%p.3), kind=kLoop", 600, 50),
        module(DEV0, "jit_other(17)", 1000, 200),
        op(DEV0, "%custom-call.4 = f32[8]{0} custom-call(%q)", 1000, 200),
        op(DEV1, "%fusion.1 = f32[8]{0} fusion(%p.1)", 200, 400),
        op(DEV1, "%all-reduce.1 = f32[8]{0} all-reduce(%p.1)", 600, 100),
    ])


def test_window_and_devices(small):
    assert small.window_s == pytest.approx(1000e-9)
    assert small.devices == [DEV0, DEV1]


def test_busy_is_the_union_of_op_intervals_averaged_over_devices(small):
    # dev0: [100,440) + [500,700) + [1000,1100) = 340 + 200 + 100 = 640
    # dev1: [200,600) + [600,700) = 500; mean 570 ns
    assert small.busy_s() == pytest.approx(570e-9)


def test_scoped_time_counts_the_named_ops_of_that_program(small):
    # custom-call.4 of jit_step: 190 ns on dev0 (the one of jit_other is
    # another op), 0 on dev1 (no jit_step there); mean 95
    assert small.scoped_s({"custom-call.4"}, "jit_step") == pytest.approx(
        95e-9)
    assert small.scoped_s({"custom-call.4"}, "jit_nothing") == 0.0
    assert small.scoped_s(set(), "jit_step") == 0.0


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={()}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/transpose(jvp(repro_kernel.fd_tno))/mul"}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(step)/add"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %fft.2 = c64[5]{0} fft(%x), fft_type=RFFT, fft_length={8}, metadata={op_name="jit(step)/jvp(repro_kernel.fd_tno)/jit(fft)/fft"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %y)
}

ENTRY %main.3 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/add"}
  %fusion.1 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/add"}
  %custom-call.4 = f32[8]{0} custom-call(%fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/repro_kernel.fd_tno/pallas_call"}
  %while.1 = (s32[], f32[8]{0}) while(%t0), condition=%cond, body=%body, metadata={op_name="jit(step)/while"}
  ROOT %mul.9 = f32[8]{0} multiply(%x.1, %x.1), metadata={op_name="jit(step)/repro_kernel.fd_tnox/mul"}
}
"""


def test_scope_ops_reads_the_hlo_metadata():
    # its own metadata (custom call, the fft in a loop body, under jvp),
    # a fusion whose fused op is in the scope (under transpose); not the
    # loop for its body, not a scope that only starts with the name
    assert tr.scope_ops(HLO, "repro_kernel.fd_tno") == {
        "custom-call.4", "fft.2", "fusion", "mul.1"}
    assert tr.module_name(HLO) == "jit_step"
    assert tr.op_names(HLO)["fusion.1"] == "add"
    assert tr.instruction("%fusion.12 = f32[8]{0} fusion(%a)") == "fusion.12"


def test_exposed_collective_leaves_out_overlapped_compute(small):
    # dev0: all-gather [500,700) minus fusion.2 [600,650) = 150
    # dev1: all-reduce [600,700), compute ends at 600 = 100; mean 125
    assert small.exposed_collective_s() == pytest.approx(125e-9)


def test_breakdown_top_ops_and_idle_gaps(small):
    top = small.top_ops(labels={"all-gather.3": "params/all_gather"})
    assert top[0] == ["all-gather.3 params/all_gather", pytest.approx(200e-9)]
    names = [n for n, _ in top]
    assert "%custom-call.4 = f32[8]{0} custom-call(%p.1)" in names
    gaps = small.idle_gaps()
    # dev0 idle: [440,500) and [700,1000); the long one is under bench.data
    assert gaps[0] == ["bench.data", pytest.approx(300e-9)]
    assert gaps[1][1] == pytest.approx(60e-9)
    assert gaps[1][0] == "bench.trainer_step"


def test_intervals_helpers():
    assert tr.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.clip([(0, 5), (8, 9)], 2, 8) == [(2, 5)]


def test_no_window_annotation_is_an_error():
    with pytest.raises(ValueError):
        tr.Trace([op(DEV0, "fusion", 0, 10)])


def test_recorded_chip_trace():
    """A slice of a real traced training window on one TPU v5e, with the
    ops that the step's HLO puts in the FD-TNO scope: the reductions stay
    inside their bounds and find that scope's ops."""
    path = os.path.join(DATA, "train_fd_8k_trace.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    t = tr.Trace(rec["events"])
    assert t.devices == ["/device:TPU:0"]
    busy = t.busy_s()
    assert 0 < busy <= t.window_s
    fd = t.scoped_s(set(rec["scope_ops"]), rec["module"])
    assert 0 < fd <= busy
    assert fd == pytest.approx(rec["fd_tno_s"], rel=1e-9)
    assert t.exposed_collective_s() == 0.0
    bd = t.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(0 < v <= t.window_s for _, v in bd["device_ops"])
    assert busy == pytest.approx(rec["busy_s"], rel=1e-9)
