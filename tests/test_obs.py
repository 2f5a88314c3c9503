"""repro.obs — the unified observability subsystem (registry, tracer,
exporters) and its contract with the serving stack:

* registry semantics: memoised named/labelled metrics, kind conflicts,
  fixed histogram edges, labelled views, snapshots;
* tracer semantics: per-thread nesting stacks, injectable clock
  (byte-stable timestamps under a FakeClock — zero time.sleep), error
  attribution, bounded records;
* exporter formats: Prometheus exposition text, JSON-lines, perfetto
  (Chrome trace-event) JSON;
* the DISABLED contract: ``obs='off'`` resolves to the null bundle whose
  metrics/spans are process-wide singletons — identity is asserted, and
  tracemalloc holds the whole submit->align->retire path to ZERO
  obs-module allocations;
* legacy accessor == registry equality for all four migrated counter
  families (core.transfer, CompileCache/_SessionCacheView,
  gateway_stats(), the mapper funnel) — the migration's bit-equality
  acceptance criterion;
* the EXACT span tree of a 2-bucket ragged batch with one rescue rung,
  on a fake clock;
* the lane-window counters against closed forms (padding lanes, rescue
  rungs, obs='off');
* the done-callback regression: a raising callback (even a
  BaseException) must be swallowed-and-recorded, never poison the
  session (pre-PR code let it unwind into the retire path).
"""
import json
import os
import threading
import tracemalloc

import numpy as np
import pytest

import repro.obs
from repro.api import AlignSession, CompileCache, Gateway, GatewayPolicy, plan
from repro.core import transfer
from repro.core.config import AlignerConfig
from repro.core.windowing import n_main_windows
from repro.obs import (DEFAULT_EDGES, MetricsRegistry, NULL_METRIC,
                       NULL_REGISTRY, NULL_SPAN, NULL_TRACER, OBS_OFF, Obs,
                       Tracer, default_registry, perfetto_trace,
                       prometheus_text, qualified_name, resolve_obs,
                       trace_jsonl, write_artifacts)

CFG = AlignerConfig(W=16, O=6, k=2)
#: one spec shared by every session test below, so the process cache
#: lowers each bucket once for the whole module
PLAN_KW = dict(rescue_rounds=1, rescue_mode="bucket", batch_lanes=4)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _corpus():
    """3 exact pairs + 1 decoy at len 30 (bucket 32x32 — fills the
    4-lane class) then 2 exact pairs at len 70 (bucket 128x128 —
    partial, flush-dispatched).  The decoy fails the whole k-doubling
    ladder, forcing exactly one compacted rescue rung."""
    rng = np.random.default_rng(77)
    mk = lambda n: rng.integers(0, 4, n).astype(np.uint8)  # noqa: E731
    reads, refs = [], []
    for _ in range(3):
        r = mk(30)
        reads.append(r)
        refs.append(r.copy())
    reads.append(mk(30))
    refs.append(mk(30))            # decoy: unrelated ref
    for _ in range(2):
        r = mk(70)
        reads.append(r)
        refs.append(r.copy())
    return reads, refs


# --------------------------------------------------------------------------
# registry semantics
# --------------------------------------------------------------------------

def test_registry_memoises_by_name_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("x_total", tenant="a")
    assert reg.counter("x_total", tenant="a") is c
    assert reg.counter("x_total", tenant="b") is not c
    assert reg.counter("x_total") is not c
    c.inc()
    c.inc(5)
    assert c.value == 6
    g = reg.gauge("depth")
    g.set(3)
    g.add(-1)
    assert g.value == 2
    assert qualified_name(c.name, c.labels) == 'x_total{tenant="a"}'


def test_registry_kind_conflict_and_fixed_edges():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")
    h = reg.histogram("h_seconds", edges=(0.1, 1.0))
    assert reg.histogram("h_seconds", edges=(0.1, 1.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("h_seconds", edges=(0.1, 2.0))


def test_histogram_cumulative_snapshot():
    h = MetricsRegistry().histogram("lat", edges=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snap()
    assert snap["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 3}
    assert snap["count"] == 3 and snap["sum"] == 0.05 + 0.5 + 5.0


def test_labeled_view_stamps_and_filters():
    base = MetricsRegistry()
    view = base.labeled(session="jnp")
    c = view.counter("pairs_total")
    assert c is base.counter("pairs_total", session="jnp")
    assert c.labels == (("session", "jnp"),)
    base.counter("other_total").inc()
    assert set(view.snapshot()) == {'pairs_total{session="jnp"}'}
    assert set(base.snapshot()) == {'pairs_total{session="jnp"}',
                                    "other_total"}
    nested = view.labeled(shard="0")
    assert nested.counter("pairs_total").labels == \
        (("session", "jnp"), ("shard", "0"))


def test_default_registry_is_process_global():
    assert default_registry() is default_registry()
    assert default_registry().enabled


# --------------------------------------------------------------------------
# tracer semantics
# --------------------------------------------------------------------------

def test_tracer_nesting_timestamps_and_error_attr():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", x=1):
        clk.advance(1.0)
        with tr.span("inner"):
            clk.advance(0.5)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("no")
    inner, outer, boom = tr.records()
    assert (inner["name"], inner["t0"], inner["t1"]) == ("inner", 1.0, 1.5)
    assert inner["parent"] == outer["sid"]
    assert (outer["t0"], outer["t1"], outer["parent"]) == (0.0, 1.5, None)
    assert outer["attrs"] == {"x": 1}
    assert boom["attrs"]["error"] == "RuntimeError"


def test_tracer_stacks_are_per_thread():
    tr = Tracer(clock=FakeClock())
    with tr.span("main.open"):
        t = threading.Thread(
            target=lambda: tr.span("worker").__enter__().__exit__(
                None, None, None), name="obs-worker")
        t.start()
        t.join()
    worker, main = tr.records()
    assert worker["parent"] is None        # not a fake child of main.open
    assert worker["thread"] == "obs-worker"
    assert main["parent"] is None


def test_tracer_records_are_bounded():
    tr = Tracer(clock=FakeClock(), maxlen=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert [r["name"] for r in tr.records()] == ["s6", "s7", "s8", "s9"]


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------

def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("req_total", tenant="a").inc(3)
    reg.gauge("depth").set(2)
    h = reg.histogram("lat_seconds", edges=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = prometheus_text(reg)
    for line in (
        "# TYPE depth gauge",
        "depth 2",
        "# TYPE lat_seconds histogram",
        'lat_seconds_bucket{le="0.1"} 1',
        'lat_seconds_bucket{le="1.0"} 2',
        'lat_seconds_bucket{le="+Inf"} 3',
        f"lat_seconds_sum {h.sum}",
        "lat_seconds_count 3",
        "# TYPE req_total counter",
        'req_total{tenant="a"} 3',
    ):
        assert line in text.splitlines(), line
    assert prometheus_text(MetricsRegistry()) == ""


def test_jsonl_and_perfetto_export():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("work", lanes=4):
        clk.advance(0.002)
    lines = trace_jsonl(tr).splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["name"] == "work" and rec["attrs"] == {"lanes": 4}
    assert (rec["t0"], rec["t1"]) == (0.0, 0.002)

    doc = perfetto_trace(tr)
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert meta[0]["args"]["name"] == rec["thread"]
    (x,) = xs
    assert x["ts"] == 0.0 and x["dur"] == pytest.approx(2000.0)
    assert x["args"]["lanes"] == 4 and x["args"]["sid"] == rec["sid"]


def test_write_artifacts(tmp_path):
    obs = Obs.private(clock=FakeClock())
    obs.counter("c_total").inc()
    with obs.span("s"):
        pass
    paths = write_artifacts(obs, str(tmp_path), prefix="t")
    assert sorted(paths) == ["jsonl", "perfetto", "prometheus"]
    assert "c_total 1" in open(paths["prometheus"]).read()
    assert json.loads(open(paths["jsonl"]).read())["name"] == "s"
    assert json.load(open(paths["perfetto"]))["traceEvents"]


# --------------------------------------------------------------------------
# the disabled bundle: identity + zero allocations
# --------------------------------------------------------------------------

def test_resolve_obs_contract():
    assert resolve_obs("off") is OBS_OFF
    assert resolve_obs(False) is OBS_OFF
    bundle = Obs.private()
    assert resolve_obs(bundle) is bundle
    fresh = resolve_obs(None)
    assert fresh.enabled and fresh is not bundle
    with pytest.raises(TypeError):
        resolve_obs(42)


def test_null_bundle_identity():
    assert OBS_OFF.counter("anything", label="x") is NULL_METRIC
    assert OBS_OFF.gauge("g") is NULL_METRIC
    assert OBS_OFF.histogram("h") is NULL_METRIC
    assert OBS_OFF.span("s", a=1) is NULL_SPAN
    assert OBS_OFF.labeled(session="x") is not None
    assert OBS_OFF.labeled(session="x").counter("c") is NULL_METRIC
    assert NULL_REGISTRY.labeled(anything="y") is NULL_REGISTRY
    assert not OBS_OFF.enabled
    assert OBS_OFF.snapshot() == {} and OBS_OFF.prometheus() == ""
    assert NULL_TRACER.records() == []
    NULL_METRIC.inc()
    NULL_METRIC.observe(1.0)
    NULL_METRIC.set(5)
    assert NULL_METRIC.value == 0


def test_obs_off_session_is_a_true_noop():
    """plan(obs='off'): every session metric IS the null singleton, and a
    full submit->align->retire(+rescue) cycle performs ZERO allocations
    attributable to the repro.obs module (tracemalloc, filtered)."""
    reads, refs = _corpus()
    with plan(CFG, **PLAN_KW, obs="off") as s:
        assert s.obs is OBS_OFF
        assert all(m is NULL_METRIC for m in s._m.values())
        assert s.stats == {k: 0 for k in s.stat_metrics}
        assert AlignSession.STAT_METRICS.items() <= s.stat_metrics.items()
        s.align(reads, refs)           # warm: compiles outside the window

        obs_dir = os.path.dirname(repro.obs.__file__)
        filters = [tracemalloc.Filter(True, os.path.join(obs_dir, "*"))]
        tracemalloc.start()
        # one traced steady-state pass first: lets CPython's frame
        # freelist and the (still-enabled, process-global) transfer
        # counters reach steady state under tracing, so the measured
        # window is pure per-align cost
        s.align(reads, refs)
        before = tracemalloc.take_snapshot()
        res = s.align(reads, refs)
        after = tracemalloc.take_snapshot()
        tracemalloc.stop()
        diff = after.filter_traces(filters).compare_to(
            before.filter_traces(filters), "lineno")
        grew = [d for d in diff if d.size_diff > 0 or d.count_diff > 0]
        assert not grew, grew
        # the telemetry trade is explicit: stats read zeros, results don't
        assert s.stats["requests"] == 0
        assert not res.failed[:3].any() and res.failed[3]


# --------------------------------------------------------------------------
# legacy accessors == registry reads (the four migrated families)
# --------------------------------------------------------------------------

def test_transfer_family_matches_registry():
    transfer.reset()
    snap0 = default_registry().snapshot()
    assert snap0["transfer_h2d_calls_total"] == 0
    x = np.zeros((4, 8), np.uint8)
    dev = transfer.to_device((x, x))
    transfer.to_host(dev)
    s = transfer.stats()
    snap = default_registry().snapshot()
    assert s.h2d_calls == snap["transfer_h2d_calls_total"] == 1
    assert s.d2h_calls == snap["transfer_d2h_calls_total"] == 1
    assert s.h2d_bytes == snap["transfer_h2d_bytes_total"] == 2 * x.nbytes
    assert s.d2h_bytes == snap["transfer_d2h_bytes_total"]
    # reset() is per-family, never registry-wide
    marker = default_registry().counter("compile_cache_hits_total").value
    transfer.reset()
    assert transfer.stats() == transfer.TransferStats()
    assert default_registry().counter(
        "compile_cache_hits_total").value == marker


def test_compile_cache_family_matches_registry():
    reg = MetricsRegistry()
    cc = CompileCache(registry=reg)
    cc.get(("k1",), lambda: "exe1")
    cc.get(("k1",), lambda: "exe1")
    cc.get(("k2",), lambda: "exe2")
    snap = reg.snapshot()
    assert cc.hits == snap["compile_cache_hits_total"] == 1
    assert cc.misses == snap["compile_cache_misses_total"] == 2
    assert cc.lowerings == snap["compile_cache_lowerings_total"] == 2
    assert cc.stats()["lowerings"] == 2


def test_session_and_cache_view_families_match_registry():
    reads, refs = _corpus()
    with plan(CFG, **PLAN_KW) as s:
        s.align(reads, refs)
        snap = s.obs.snapshot()
        for key, name in s.stat_metrics.items():
            assert s.stats[key] == snap[name], (key, name)
        assert s.stats["requests"] == 6
        assert s.stats["dispatches"] == 2
        assert s.stats["rescue_dispatches"] == 1
        # the per-session cache view rides the same registry
        assert s.cache.hits == snap["session_cache_hits_total"]
        assert s.cache.misses == snap["session_cache_misses_total"]
        assert s.cache.lowerings == snap["session_cache_lowerings_total"]
        assert s.cache.shared_hits == snap["session_cache_shared_hits_total"]


def _lane_window_pairs():
    """Four pairs of one 32x32 bucket: exact matches of 20 and 30 bp, a
    30 bp read with three substitutions in its first window (k = 2 fails
    it, the k = 4 rung solves it) and an unrelated 25 bp decoy that fails
    every rung."""
    rng = np.random.default_rng(5)
    mk = lambda n: rng.integers(0, 4, n).astype(np.uint8)  # noqa: E731
    a, b, c = mk(20), mk(30), mk(30)
    c_read = c.copy()
    c_read[[2, 6, 10]] = (c_read[[2, 6, 10]] + 1) % 4
    return [a, b, c_read, mk(25)], [a.copy(), b.copy(), c, mk(25)]


def _windows(read_len):
    return n_main_windows(read_len, CFG) + 1


def _steps(*lane_steps):
    """Window steps the base rung of a dispatch runs (a plain step, or the
    on-device ladder, whose base rung runs every step): the loop ends at
    the last active lane, then the tail.  `lane_steps`: each lane's main
    steps — n_main_windows(read length) for a lane the step solves, the
    index of the window it fails in (at the step's top rung) + 1 for one
    it fails."""
    return max(lane_steps) + 1


def _escalated(steps, tail=False):
    """Window steps a higher rung of the on-device ladder runs: the steps
    in which some lane's window failed every rung below it, + 1 if some
    lane's tail did."""
    return steps + int(tail)


FIRST = 1                               # a lane failed in its first window


@pytest.mark.parametrize("rescue_mode", ["bucket", "device"])
def test_lane_window_counters_closed_forms(rescue_mode):
    """session_lane_windows_total counts lanes x window steps the device
    ran (padding lanes and every rung included), where a rung's loop runs
    until its last lane has finished or failed; the useful count is
    sum(n_main_windows(len) + 1) over the real lanes that were solved,
    whichever rung solved them.  Closed forms at tiny sizes on the CPU;
    under obs='off' both read 0."""
    reads, refs = _lane_window_pairs()
    main = lambda n: n_main_windows(n, CFG)  # noqa: E731
    kw = dict(PLAN_KW, rescue_mode=rescue_mode)
    with plan(CFG, **kw) as s:
        # exact pairs of several lengths, 3 real lanes in a 4-lane class:
        # the padding lane (a repeat of the last pair) adds to lane_windows
        # only
        s.align([reads[0], reads[1], reads[1]], [refs[0], refs[1], refs[1]])
        assert s.stats["lanes"] == 4 and s.stats["pad_lanes"] == 1
        assert s.stats["lane_windows"] == 4 * _steps(main(20), main(30))
        assert s.stats["useful_lane_windows"] == \
            _windows(20) + 2 * _windows(30)
        s0 = dict(s.stats)
        res = s.align(reads, refs)
        assert res.failed.tolist() == [False, False, False, True]
        assert res.k_used[:3].tolist() == [2, 2, 4]
        lw = s.stats["lane_windows"] - s0["lane_windows"]
        useful = s.stats["useful_lane_windows"] - s0["useful_lane_windows"]
        s1 = dict(s.stats)
        # a 20 bp read beside two decoys: the loop stops after the 20 bp
        # read's one main window, short of the 32 bucket's two
        short = s.align([reads[0], reads[3], reads[3]],
                        [refs[0], refs[3], refs[3]])
        assert short.failed.tolist() == [False, True, True]
        lw_short = s.stats["lane_windows"] - s1["lane_windows"]
    if rescue_mode == "bucket":
        # k = 2 solves the exact pairs and fails the substituted read and
        # the decoy in their first window; the k = 4 rung compacts the two
        # failed lanes into a 2-lane class and solves the substituted read;
        # the decoy fails again at once
        assert lw == 4 * _steps(main(20), main(30), FIRST, FIRST) \
            + 2 * _steps(main(30), FIRST)
        # the two decoys alone make the k = 4 rung: both fail at once
        assert lw_short == 4 * _steps(main(20), FIRST) + 2 * _steps(FIRST)
    else:
        # the on-device ladder runs k = 2 on every step until the
        # substituted read is done, and k = 4 in the first step only,
        # where the substituted read and the decoy failed at k = 2
        assert lw == 4 * (_steps(main(20), main(30), main(30), FIRST)
                          + _escalated(1))
        assert lw_short == 4 * (_steps(main(20), FIRST) + _escalated(1))
    assert _steps(main(20), FIRST) < _windows(32)
    # the rung adds only the lane it solved; the decoy adds nothing
    assert useful == _windows(20) + 2 * _windows(30)
    with plan(CFG, **kw, obs="off") as s:
        s.align(reads, refs)
        assert s.stats["lane_windows"] == 0
        assert s.stats["useful_lane_windows"] == 0



@pytest.mark.parametrize("rescue_mode", ["bucket", "device"])
def test_per_rung_lane_window_counters_sum_to_the_totals(rescue_mode):
    """For each rung k of the ladder the session counts the lane-windows
    that rung ran (lane_windows_k<k>) and those whose ops it committed to
    an answer (useful_lane_windows_k<k>); over the rungs they sum to
    lane_windows and useful_lane_windows, and no rung's useful count
    exceeds its lane-windows.  The on-device ladder reports the steps each
    rung ran (rung_window_steps) and each lane's commits per rung
    (rung_commits); bucket rescue reruns a failed read whole, so its
    compacted rung dispatches count at their own k."""
    reads, refs = _lane_window_pairs()
    main = lambda n: n_main_windows(n, CFG)  # noqa: E731
    with plan(CFG, **dict(PLAN_KW, rescue_mode=rescue_mode)) as s:
        assert [k for k in s.stat_metrics if k.startswith("lane_windows_")] \
            == ["lane_windows_k2", "lane_windows_k4"]
        s0 = dict(s.stats)
        s.align(reads, refs)
        d = {k: v - s0[k] for k, v in s.stats.items()}
    for total in ("lane_windows", "useful_lane_windows"):
        assert d[total] == d[f"{total}_k2"] + d[f"{total}_k4"], total
    for k in (2, 4):
        assert d[f"useful_lane_windows_k{k}"] <= d[f"lane_windows_k{k}"]
    if rescue_mode == "bucket":
        assert d["lane_windows_k2"] == 4 * _steps(main(20), main(30), FIRST,
                                                  FIRST)
        assert d["lane_windows_k4"] == 2 * _steps(main(30), FIRST)
        # k = 2 solves the two exact pairs, k = 4 the substituted read
        assert d["useful_lane_windows_k2"] == _windows(20) + _windows(30)
        assert d["useful_lane_windows_k4"] == _windows(30)
    else:
        assert d["lane_windows_k2"] == 4 * _steps(main(20), main(30),
                                                  main(30), FIRST)
        assert d["lane_windows_k4"] == 4 * _escalated(1)
        # k = 4 commits the substituted read's first window, k = 2 every
        # other window of the three solved reads
        assert d["useful_lane_windows_k2"] == \
            _windows(20) + 2 * _windows(30) - 1
        assert d["useful_lane_windows_k4"] == 1

def test_three_rung_ladder_counts_escalated_windows():
    """On the ladder 2, 4, 8 the k = 4 and k = 8 rungs run only the window
    step in which some lane needs them, and count as useful only the
    windows they committed: per rung, useful <= lane-windows, and the
    per-rung counts sum to the totals.  Window j covers read
    [10j, 10j + 16), so substitutions in [26, 30] lie in window 2 alone
    (and one in window 3): three give d = 3 (k = 4), five d = 5 (k = 8).
    Three real pairs in a 4-lane class; the padding lane repeats the last
    pair and counts in lane_windows only."""
    rng = np.random.default_rng(11)
    refs = [rng.integers(0, 4, 60).astype(np.uint8) for _ in range(3)]
    reads = [refs[0].copy(), refs[1].copy(), refs[2].copy()]
    reads[1][[26, 27, 29]] = (reads[1][[26, 27, 29]] + 1) % 4
    reads[2][26:31] = (reads[2][26:31] + 1) % 4
    with plan(CFG, rescue_rounds=2, rescue_mode="device",
              batch_lanes=4) as s:
        res = s.align(reads, refs)
        d = dict(s.stats)
    assert res.k_used.tolist() == [2, 4, 8]
    assert d["lanes"] == 4 and d["pad_lanes"] == 1
    w = _windows(60)
    assert d["lane_windows_k2"] == 4 * _steps(n_main_windows(60, CFG))
    assert d["lane_windows_k4"] == 4 * _escalated(1)
    assert d["lane_windows_k8"] == 4 * _escalated(1)
    assert [d[f"useful_lane_windows_k{k}"] for k in (2, 4, 8)] == \
        [3 * w - 2, 1, 1]
    for total in ("lane_windows", "useful_lane_windows"):
        assert d[total] == sum(d[f"{total}_k{k}"] for k in (2, 4, 8))
    for k in (2, 4, 8):
        assert d[f"useful_lane_windows_k{k}"] <= d[f"lane_windows_k{k}"]
    assert d["useful_lane_windows"] == 3 * w


def test_gateway_family_matches_registry():
    clk = FakeClock()
    s = plan(CFG, rescue_rounds=0, batch_lanes=4, clock=clk)
    g = Gateway(s, GatewayPolicy(capacity=64), clock=clk, auto_pump=False)
    try:
        rng = np.random.default_rng(3)
        ten = g.tenant("acme")
        pairs = []
        for _ in range(4):
            r = rng.integers(0, 4, 30).astype(np.uint8)
            pairs.append(ten.submit(r, r.copy()))
        g.pump(clk())
        for gf in pairs:
            assert gf.result()["ok"]
        snap = g.obs.snapshot()            # gateway shares the session obs
        for key, name in Gateway.STAT_METRICS.items():
            assert g.stats[key] == snap[name], (key, name)
        assert g.stats["submitted"] == 4 and g.stats["completed"] == 4
        out = g.gateway_stats()
        assert out["submitted"] == snap["gateway_submitted_total"]
        assert out["tenants"]["acme"]["completed"] == \
            snap['gateway_tenant_completed_total{tenant="acme"}'] == 4
        # live-load gauges mirror the functional ints
        assert out["queued"] == snap["gateway_queued"] == 0
        assert out["outstanding"] == snap["gateway_outstanding"] == 0
        # completion latency lands in the histogram
        assert snap["gateway_latency_seconds"]["count"] == 4
    finally:
        g.close()
        s.close()


def test_mapper_funnel_matches_registry_deltas():
    from repro.data.genome import ReadSimConfig, simulate_reads, synth_genome
    from repro.mapper import ReadMapper

    genome = synth_genome(30_000, seed=3)
    rs = simulate_reads(genome, 4, ReadSimConfig(read_len=200,
                                                 error_rate=0.05, seed=4))
    with ReadMapper(genome, backend="jnp", W=32, O=12, k=8,
                    rescue_rounds=1, batch_lanes=8) as m:
        b1 = m.map_batch(rs.reads[:2])
        b2 = m.map_batch(rs.reads[2:])
        snap = m.obs.snapshot()
        for key, name in ReadMapper.FUNNEL_METRICS.items():
            assert b1.stats[key] + b2.stats[key] == snap[name], (key, name)
        assert snap["mapper_batches_total"] == 2
        assert b1.stats["n_reads"] == 2 and b2.stats["n_reads"] == 2
        for b in (b1, b2):
            assert b.stats["kill_rate"] == \
                b.stats["n_killed"] / max(1, b.stats["n_candidates"])
        # funnel spans nested under the batch span
        recs = m.obs.tracer.records()
        batches = [r for r in recs if r["name"] == "mapper.map_batch"]
        assert len(batches) == 2
        for stage in ("index.lookup", "chain", "prefilter", "align"):
            stage_recs = [r for r in recs if r["name"] == stage]
            assert len(stage_recs) == 2, stage
            assert {r["parent"] for r in stage_recs} == \
                {b["sid"] for b in batches}


# --------------------------------------------------------------------------
# the exact span tree of a session dispatch (fake clock, zero sleeps)
# --------------------------------------------------------------------------

def test_session_trace_exact_span_tree():
    """2-bucket ragged batch, one rescue rung, sync executor, FakeClock:
    the complete trace is byte-stable — exact names, nesting, attrs and
    (never-advanced) timestamps.  Each retire.decode holds one child span
    per cost (fetch, records, fulfill); records opens on each side of the
    rescue rung; a dispatch-side span and its retire span share the
    ``dispatch`` attribute."""
    clk = FakeClock()
    reads, refs = _corpus()
    with plan(CFG, **PLAN_KW, clock=clk) as s:
        res = s.align(reads, refs)
    assert not res.failed[:3].any() and res.failed[3]
    recs = s.obs.tracer.records()
    assert [r["name"] for r in recs] == [
        "device.execute", "session.dispatch",   # bucket 32x32 (4 lanes)
        "device.execute", "session.dispatch",   # bucket 128x128 (flush)
        "retire.fetch", "retire.records",       # decoy forces one rung
        "rescue.rung", "retire.records", "retire.fulfill", "retire.decode",
        "retire.fetch", "retire.records", "retire.fulfill", "retire.decode",
    ]
    (exe_a, disp_a, exe_b, disp_b, fetch_a, rec_a0, rung, rec_a1, ful_a,
     ret_a, fetch_b, rec_b, ful_b, ret_b) = recs
    assert disp_a["attrs"] == {"bucket": "32x32", "lanes": 4, "n_real": 4,
                               "dispatch": 1}
    assert disp_b["attrs"] == {"bucket": "128x128", "lanes": 2, "n_real": 2,
                               "dispatch": 2}
    assert exe_a["attrs"] == {"lanes": 4, "dispatch": 1}
    assert exe_b["attrs"] == {"lanes": 2, "dispatch": 2}
    assert exe_a["parent"] == disp_a["sid"] and disp_a["parent"] is None
    assert exe_b["parent"] == disp_b["sid"] and disp_b["parent"] is None
    assert rung["attrs"] == {"k": 4, "lanes": 1, "n_todo": 1}
    assert ret_a["attrs"] == {"n": 4, "dispatch": 1}
    assert ret_b["attrs"] == {"n": 2, "dispatch": 2}
    assert ret_a["parent"] is None and ret_b["parent"] is None
    for child in (fetch_a, rec_a0, rung, rec_a1, ful_a):
        assert child["parent"] == ret_a["sid"], child["name"]
    for child in (fetch_b, rec_b, ful_b):
        assert child["parent"] == ret_b["sid"], child["name"]
    for child in (fetch_a, rec_a0, rec_a1, ful_a, fetch_b, rec_b, ful_b):
        assert child["attrs"] == {}, child["name"]
    # FakeClock never advanced: every timestamp is exactly 0.0, and the
    # whole trace ran on this thread (sync executor)
    assert {r["t0"] for r in recs} == {0.0} and {r["t1"] for r in recs} == {0.0}
    assert {r["thread"] for r in recs} == {threading.current_thread().name}
    # sids are allocated in OPEN order (parent before its children, the
    # children in the order they ran)
    assert disp_a["sid"] < exe_a["sid"] < disp_b["sid"] < exe_b["sid"]
    assert ret_a["sid"] < fetch_a["sid"] < rec_a0["sid"] < rung["sid"] \
        < rec_a1["sid"] < ful_a["sid"] < ret_b["sid"] < fetch_b["sid"] \
        < rec_b["sid"] < ful_b["sid"]


# --------------------------------------------------------------------------
# done-callback regression: raising callbacks never poison the session
# --------------------------------------------------------------------------

class _Boom(BaseException):
    """Deliberately NOT an Exception: the pre-PR code caught only
    Exception in _run_callbacks, so a BaseException (KeyboardInterrupt in
    a client hook) unwound into the retire path and poisoned the
    session."""


@pytest.mark.parametrize("executor", ["sync", "thread"])
def test_raising_done_callback_is_recorded_not_poisoning(executor):
    reads, refs = _corpus()
    with plan(CFG, **PLAN_KW, executor=executor) as s:
        futs = [s.submit(r, f) for r, f in zip(reads[:4], refs[:4])]

        def boom(_fut):
            raise _Boom("client hook blew up")

        seen = []
        futs[0].add_done_callback(boom)
        futs[1].add_done_callback(seen.append)
        s.flush()
        recs = [f.result() for f in futs]      # no SessionPoisonedError
        assert [r["ok"] for r in recs] == [True, True, True, False]
        assert seen == [futs[1]]               # other callbacks still ran
        assert s.stats["callback_errors"] == 1
        assert s.obs.counter("session_callback_errors_total").value == 1
        # the session stays fully usable afterwards
        res = s.align(reads[:3], refs[:3])
        assert not res.failed.any()
        assert s.stats["callback_errors"] == 1


def test_callback_on_already_done_future_also_guarded():
    reads, refs = _corpus()
    with plan(CFG, **PLAN_KW) as s:
        fut = s.submit(reads[0], refs[0])
        s.flush()
        assert fut.result()["ok"]

        def boom(_fut):
            raise _Boom("late hook")

        fut.add_done_callback(boom)            # runs immediately — guarded
        assert s.stats["callback_errors"] == 1
        assert not s.align(reads[:2], refs[:2]).failed.any()
