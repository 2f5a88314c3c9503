"""scan.useful_window_share: of the lane-windows the device ran in the
window (lanes x window steps, padding lanes and every rescue rung
included), the share whose ops are in an answer (a solved read's
n_main_windows(length) + 1), in percent: the session's
``useful_lane_windows`` counter over its ``lane_windows`` counter, each
taken as the change from the window's start to its close.  A program
without the counters, or a window in which no lane-window ran, reports
nothing."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    if "lane_windows" not in s1:
        return None
    ran = s1["lane_windows"] - s0["lane_windows"]
    if ran <= 0:
        return None
    return 100.0 * (s1["useful_lane_windows"]
                    - s0["useful_lane_windows"]) / ran
