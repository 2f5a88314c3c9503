"""kernel.busy_share: device time inside the Pallas kernels over all
device busy time in the traced window, in percent (profiler trace).  The
rest is the XLA work around the kernels: per-window slices, reversals,
gathers and scatters."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or t.kernel_s <= 0:
        return None
    return 100.0 * t.kernel_s / t.busy_s
