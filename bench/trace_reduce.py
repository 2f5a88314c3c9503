"""Reduction of a profiler trace to the per-layer numbers.

``load_events`` reads the ``.xplane.pb`` the JAX profiler wrote into a
plain structure (kept small enough to commit a recorded one as test
data)::

    {"devices": {plane: [[op name, start ns, duration ns, is_kernel], ...]},
     "host": [[span name, start ns, duration ns, thread], ...]}

``summarize`` then computes, inside the host span that marks the window:

* busy time per device: the union of the intervals in which an operation
  ran on it (so nested or overlapping events count once);
* kernel time per device: the union of the Pallas kernels' intervals;
* idle gaps: the rest of the window, each gap attributed to the host span
  (of the names given) whose own time -- its time minus that of the spans
  nested in it on the same thread -- overlaps the gap most, "no span"
  when none does;
* the device operations that took most time, by name (own time: an op's
  time minus that of the ops nested in it).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

#: the line of a device plane that holds one event per operation run
DEVICE_OP_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(event_name: str) -> str:
    """An op's event carries its whole HLO instruction; its name is the
    part before ' = ' ('%genasm_tb_fused_op.14 = (...) custom-call(...)'
    is 'genasm_tb_fused_op.14')."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_kernel(event_name: str) -> bool:
    """A Pallas kernel: its op is a TPU custom call."""
    return "tpu_custom_call" in event_name


def load_events(path: str, host_names=None) -> dict:
    """The device ops and the host spans (those named in `host_names`, or
    all when None) of one trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    keep = None if host_names is None else set(host_names)
    on_device = any(p.name.startswith("/device:") for p in pd.planes)
    devices, host, cpu_ops = {}, [], []
    thread = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [[op_name(ev.name), int(ev.start_ns), int(ev.duration_ns),
                    int(is_kernel(ev.name))]
                   for line in plane.lines if line.name == DEVICE_OP_LINE
                   for ev in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread += 1
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    if keep is None or ev.name in keep:
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns), thread])
                    elif not on_device and "hlo_op" in dict(ev.stats):
                        cpu_ops.append([op_name(ev.name), int(ev.start_ns),
                                        int(ev.duration_ns),
                                        int(is_kernel(ev.name))])
    if not on_device and cpu_ops:
        # the CPU backend (rehearsals): its XLA ops run on host threads
        devices["/host:CPU"] = cpu_ops
    return {"devices": devices, "host": host}


def crop(events: dict, window: str, seconds: float) -> dict:
    """The first `seconds` of a trace's window, as a trace of its own (the
    window span cut to that length): small enough to keep as test data."""
    lo, hi = window_bounds(events, window)
    hi = min(hi, lo + int(seconds * 1e9))

    def inside(ev):
        return ev[1] < hi and ev[1] + ev[2] > lo

    host = [ev for ev in events["host"] if inside(ev) and ev[0] != window]
    host.append([window, lo, hi - lo, 0])
    return {"devices": {d: [ev for ev in ops if inside(ev)]
                        for d, ops in events["devices"].items()},
            "host": host}


def window_bounds(events: dict, window: str) -> tuple[int, int]:
    marks = [(s, s + d) for n, s, d, _t in events["host"] if n == window]
    if not marks:
        raise ValueError(f"no host span {window!r} in the trace")
    return max(marks, key=lambda m: m[1] - m[0])


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Summary:
    n_devices: int
    window_s: float
    busy_s: float            # mean over devices
    kernel_s: float          # mean over devices
    busy_by_device: list
    top_ops: list            # [[name, own seconds summed over devices]]
    idle_by_host: list       # [[host span, idle seconds, mean over devices]]
    n_gaps: int

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops[:10],
                "idle_gaps": self.idle_by_host[:10]}


def own_time(spans) -> list[tuple[int, int, str]]:
    """The own-time intervals of properly nested spans of one thread:
    each span's interval minus those of the spans nested in it."""
    out, stack = [], []          # stack entries: [start, end, name, cursor]
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            _, pe, pn, pc = stack.pop()
            out.append((pc, pe, pn))
            if stack:
                stack[-1][3] = pe
        if stack:
            out.append((stack[-1][3], s, stack[-1][2]))
            stack[-1][3] = s
        stack.append([s, e, n, s])
    while stack:
        _, pe, pn, pc = stack.pop()
        out.append((pc, pe, pn))
        if stack:
            stack[-1][3] = pe
    return [(s, e, n) for s, e, n in out if e > s]


class Coverage:
    """Covered length of a union of intervals up to any time (bisect)."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.before = []
        acc = 0
        for s, e in merged:
            self.before.append(acc)
            acc += e - s

    def upto(self, t: int) -> int:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0
        return self.before[i] + min(t, self.ends[i]) - self.starts[i]

    def within(self, a: int, b: int) -> int:
        return self.upto(b) - self.upto(a)


def summarize(events: dict, window: str, host_names) -> Summary:
    host = events["host"]
    lo, hi = window_bounds(events, window)
    names = set(host_names)
    by_thread: dict = {}
    for n, s, d, t in host:
        if n in names and s < hi and s + d > lo:
            by_thread.setdefault(t, []).append((s, s + d, n))
    own: dict = {}
    for spans in by_thread.values():
        for s, e, n in own_time(spans):
            own.setdefault(n, []).append((s, e))
    cover = {n: Coverage(union(iv, lo, hi)) for n, iv in own.items()}
    devs = events["devices"]
    if not devs:
        raise ValueError("no device operations in the trace")
    busy_all, kern_all, op_time, idle = [], [], {}, {}
    n_gaps = 0
    for ops in devs.values():
        busy = union(((s, s + d) for _, s, d, _k in ops), lo, hi)
        kern = union(((s, s + d) for _, s, d, k in ops if k), lo, hi)
        busy_all.append(sum(e - s for s, e in busy))
        kern_all.append(sum(e - s for s, e in kern))
        for s, e, name in own_time([(s, s + d, n) for n, s, d, _k in ops]):
            c = min(e, hi) - max(s, lo)
            if c > 0:
                op_time[name] = op_time.get(name, 0) + c
        for g0, g1 in gaps(busy, lo, hi):
            n_gaps += 1
            best, best_c = "no span", 0
            for n, cv in sorted(cover.items()):
                c = cv.within(g0, g1)
                if c > best_c:
                    best, best_c = n, c
            idle[best] = idle.get(best, 0) + (g1 - g0)
    nd = len(devs)
    top = sorted(op_time.items(), key=lambda kv: (-kv[1], kv[0]))
    idl = sorted(idle.items(), key=lambda kv: (-kv[1], kv[0]))
    return Summary(
        n_devices=nd, window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_all) / nd / 1e9, kernel_s=sum(kern_all) / nd / 1e9,
        busy_by_device=[b / 1e9 for b in busy_all],
        top_ops=[[n, t / 1e9] for n, t in top],
        idle_by_host=[[n, t / nd / 1e9] for n, t in idl], n_gaps=n_gaps)

