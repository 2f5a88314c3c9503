"""The readers of the k = 48 rung's lane-window counters and of the k = 48
kernels' share, on hand-built runs: counters that move, counters that do
not, traces with and without the k = 48 kernels, and a program that has
none of them (it reports nothing)."""
import numpy as np
import pytest

from bench import harness, spec, trace_reduce


def run_data(stats0=None, stats1=None, t0=10.0, t1=30.0):
    n = 4
    req = {"idx": np.arange(n), "t_due": np.full(n, t0),
           "t_done": np.full(n, t0 + 1.0), "queue_s": np.zeros(n),
           "state": np.full(n, harness.ANSWERED, np.int8)}
    return harness.RunData(t0=t0, t1=t1, t_giveup=t1 + 60, setup_s=1.0,
                           req=req, stats0=stats0 or {}, stats1=stats1 or {},
                           spans=[], trace=None)


def _summary(top_ops, busy_s=0.5, n_devices=1):
    return trace_reduce.Summary(
        n_devices=n_devices, window_s=1.0, busy_s=busy_s, kernel_s=0.3,
        busy_by_device=[busy_s] * n_devices, top_ops=top_ops,
        idle_by_host=[], n_gaps=0)


def test_k48_useful_share_reads_the_rungs_change_over_the_window():
    read = spec.metric_reader("rescue.k48_useful_share")
    s0 = {"lane_windows_k48": 500, "useful_lane_windows_k48": 60}
    s1 = {"lane_windows_k48": 500 + 251 * 128,
          "useful_lane_windows_k48": 60 + 251 * 16}
    assert read(run_data(stats0=s0, stats1=s1)) == pytest.approx(12.5)


def test_k48_useful_share_without_counters_or_k48_windows():
    read = spec.metric_reader("rescue.k48_useful_share")
    # a program without the per-rung counters (the aggregate ones only)
    agg = {"lane_windows": 5, "useful_lane_windows": 3}
    assert read(run_data(stats0=agg, stats1=dict(agg))) is None
    # a ladder that ran no k = 48 lane-window in the window
    same = {"lane_windows_k48": 7, "useful_lane_windows_k48": 1}
    assert read(run_data(stats0=same, stats1=dict(same))) is None


def test_k48_kernel_share_sums_the_rungs_kernels_over_busy_time():
    read = spec.metric_reader("kernel.k48_share")
    top = [["genasm_tb_k48.3", 0.2], ["genasm_tb_k24.6", 0.1],
           ["fusion.12", 0.08], ["genasm_tail_k48.1", 0.05],
           ["genasm_tail_k12.1", 0.01], ["genasm_tb_k48_glue.2", 0.04]]
    run = run_data()
    run.trace = _summary(top)
    assert read(run) == pytest.approx(100 * 0.25 / 0.5)
    # two devices: top_ops sum over them, busy_s is their mean
    run.trace = _summary([["genasm_tb_k48.3", 0.4]], busy_s=0.5, n_devices=2)
    assert read(run) == pytest.approx(40.0)


def test_k48_kernel_share_without_trace_or_k48_ops():
    read = spec.metric_reader("kernel.k48_share")
    assert read(run_data()) is None                 # an untraced run
    run = run_data()
    run.trace = _summary([["genasm_tb_k24.6", 0.3]])
    assert read(run) == 0.0
