"""rescue.k48_useful_share: of the lane-windows the k = 48 rung ran in
the window (lanes x that rung's window steps), the share whose ops are in
an answer (n_main_windows(length) + 1 for each read the rung solved), in
percent: the session's ``useful_lane_windows_k48`` counter over its
``lane_windows_k48`` counter, each taken as the change from the window's
start to its close.  A program without the per-rung counters, or a window
in which the k = 48 rung ran no lane-window, reports nothing."""

RAN, USEFUL = "lane_windows_k48", "useful_lane_windows_k48"


def read(run):
    s0, s1 = run.stats0, run.stats1
    if RAN not in s1 or USEFUL not in s1:
        return None
    ran = s1[RAN] - s0.get(RAN, 0)
    if ran <= 0:
        return None
    return 100.0 * (s1[USEFUL] - s0.get(USEFUL, 0)) / ran
