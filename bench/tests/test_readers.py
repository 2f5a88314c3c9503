"""The readers of the retire spans and the lane-window counters, on
hand-built runs: spans that straddle the window's edges, counters that do
not move, and a program that has neither (it reports nothing)."""
import numpy as np
import pytest

from bench import harness, spec

SPAN_READERS = ["retire.fetch_us_per_read", "retire.records_us_per_read",
                "retire.fulfill_us_per_read"]


def run_data(n_answered=4, spans=(), stats0=None, stats1=None, t0=10.0,
             t1=30.0):
    t_done = np.full(n_answered + 1, t0 + 1.0)
    t_done[-1] = t1 + 5.0               # answered after the close
    state = np.full(len(t_done), harness.ANSWERED, np.int8)
    req = {"idx": np.arange(len(t_done)), "t_due": np.full(len(t_done), t0),
           "t_done": t_done, "queue_s": np.zeros(len(t_done)),
           "state": state}
    return harness.RunData(t0=t0, t1=t1, t_giveup=t1 + 60, setup_s=1.0,
                           req=req, stats0=stats0 or {}, stats1=stats1 or {},
                           spans=list(spans), trace=None)


def span(name, a, b):
    return {"name": name, "t0": a, "t1": b}


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_time_is_clipped_to_the_window(metric):
    name = metric.removesuffix("_us_per_read")
    read = spec.metric_reader(metric)
    spans = [span(name, 9.0, 10.5),          # straddles the start: 0.5 s
             span(name, 12.0, 12.25),        # inside: 0.25 s
             span(name, 29.5, 31.0),         # straddles the close: 0.5 s
             span(name, 31.0, 32.0),         # after the close: 0
             span("retire.decode", 10.0, 30.0)]   # another span: ignored
    got = read(run_data(n_answered=4, spans=spans))
    assert got == pytest.approx(1e6 * 1.25 / 4)


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_reader_without_its_span_reports_nothing(metric):
    read = spec.metric_reader(metric)
    assert read(run_data(spans=[span("retire.decode", 11.0, 12.0)])) is None
    name = metric.removesuffix("_us_per_read")
    assert read(run_data(n_answered=0, spans=[span(name, 11.0, 12.0)])) \
        is None


def test_useful_window_share_reads_the_change_over_the_window():
    read = spec.metric_reader("scan.useful_window_share")
    s0 = {"lane_windows": 1000, "useful_lane_windows": 700}
    s1 = {"lane_windows": 1000 + 818 * 128,
          "useful_lane_windows": 700 + 250 * 128}
    assert read(run_data(stats0=s0, stats1=s1)) == \
        pytest.approx(100 * 250 / 818)


def test_useful_window_share_with_nothing_run_or_counted():
    read = spec.metric_reader("scan.useful_window_share")
    same = {"lane_windows": 5, "useful_lane_windows": 3}
    assert read(run_data(stats0=same, stats1=dict(same))) is None
    # a program without the counters (its stats lack the keys)
    assert read(run_data(stats0={"dispatches": 1},
                         stats1={"dispatches": 2})) is None
