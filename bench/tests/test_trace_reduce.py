"""The trace reduction, on hand-made traces and on traces recorded on a
TPU v5e by the harness (``bench/testdata/*.json.gz``, cropped)."""
import gzip
import json
import os

import pytest

from bench import harness, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")


def test_union_and_gaps():
    assert tr.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 11) == [
        (1, 4), (5, 11)]
    assert tr.gaps([(1, 4), (5, 11)], 0, 12) == [(0, 1), (4, 5), (11, 12)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_own_time_subtracts_nested_spans():
    spans = [(0, 10, "outer"), (2, 4, "inner"), (6, 9, "inner"),
             (7, 8, "deep"), (12, 15, "next")]
    own = {}
    for s, e, n in tr.own_time(spans):
        own[n] = own.get(n, 0) + e - s
    assert own == {"outer": 5, "inner": 4, "deep": 1, "next": 3}


def test_coverage_counts_overlap():
    cv = tr.Coverage([(2, 4), (6, 10)])
    assert cv.within(0, 20) == 6
    assert cv.within(3, 7) == 2
    assert cv.within(4, 6) == 0


def test_op_names_and_kernels():
    name = ('%genasm_tb_fused_op.14 = (s32[54,128]) custom-call(u32[5,2,128]'
            ' %p), custom_call_target="tpu_custom_call"')
    assert tr.op_name(name) == "genasm_tb_fused_op.14"
    assert tr.is_kernel(name)
    assert not tr.is_kernel("%fusion.131 = u8[8192] fusion(u8[128,64] %r)")


def test_summary_of_a_hand_made_trace():
    # window 0..100 ns; device busy 10..30 (kernel 15..25 inside a while)
    # and 60..70; the host decodes over 30..60 and admits over 70..100
    events = {
        "devices": {"/device:TPU:0": [
            ["while.1", 10, 20, 0], ["kern.1", 15, 10, 1],
            ["fusion.2", 60, 10, 0]]},
        "host": [["bench.trace", 0, 100, 9],
                 ["retire.decode", 25, 40, 1],
                 ["gateway.admit", 70, 30, 2],
                 ["session.dispatch", 75, 10, 2]]}
    s = tr.summarize(events, "bench.trace", harness.HOST_SPANS)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(30e-9)
    assert s.kernel_s == pytest.approx(10e-9)
    assert s.n_gaps == 3
    ops = dict(s.top_ops)
    assert ops["while.1"] == pytest.approx(10e-9)       # own time
    assert ops["kern.1"] == pytest.approx(10e-9)
    idle = dict(s.idle_by_host)
    assert idle["retire.decode"] == pytest.approx(30e-9)
    assert idle["gateway.admit"] == pytest.approx(30e-9)
    assert idle["no span"] == pytest.approx(10e-9)


def test_crop_keeps_the_window_mark():
    events = {"devices": {"d": [["a", 5, 10, 0], ["b", 50, 10, 0]]},
              "host": [["bench.trace", 0, 100, 1], ["load.wait", 0, 90, 2]]}
    c = tr.crop(events, "bench.trace", 20e-9)
    assert tr.window_bounds(c, "bench.trace") == (0, 20)
    assert [ev[0] for ev in c["devices"]["d"]] == ["a"]


def recorded(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)


def test_recorded_long_read_trace():
    """The first 30 ms of a long10k.backlog window: the client submits the
    first 128 pairs (device idle under gateway.admit), then the first
    dispatch's window scan runs to the end of the crop."""
    ev = recorded("long10k_backlog_30ms.json.gz")
    s = tr.summarize(ev, harness.Profiler.MARK, harness.HOST_SPANS)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.03)
    assert s.busy_s == pytest.approx(0.013588357, abs=1e-9)
    assert s.kernel_s == pytest.approx(0.002600418, abs=1e-9)
    assert s.n_gaps == 27
    assert s.top_ops[0] == ["genasm_tb_fused_op.14", pytest.approx(
        0.002600418, abs=1e-9)]
    idle = dict(s.idle_by_host)
    assert idle["gateway.admit"] == pytest.approx(0.016411604, abs=1e-9)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_recorded_short_read_trace():
    """80 ms of a short150.backlog window: short dispatches (square and
    tail kernels) between long host gaps under session.dispatch."""
    ev = recorded("short150_backlog_80ms.json.gz")
    s = tr.summarize(ev, harness.Profiler.MARK, harness.HOST_SPANS)
    assert s.window_s == pytest.approx(0.08)
    assert s.busy_s == pytest.approx(0.009054387, abs=1e-9)
    assert s.kernel_s == pytest.approx(0.003477999, abs=1e-9)
    assert s.n_gaps == 154
    kernels = {n.rsplit(".", 1)[0] for n, _s, _d, k in
               ev["devices"]["/device:TPU:0"] if k}
    assert kernels == {"genasm_tb_fused_op", "genasm_tail_fused_op"}
    idle = dict(s.idle_by_host)
    assert idle["session.dispatch"] == pytest.approx(0.062269217, abs=1e-9)
    assert max(idle, key=idle.get) == "session.dispatch"
