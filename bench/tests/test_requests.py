"""The compact record of a window's requests and the readers over it."""
import threading

import numpy as np

from bench import harness, spec


class Fut:
    """A stand-in for GatewayFuture: answered when `answer` is called."""

    def __init__(self, t_submit):
        self.t_submit, self.t_dispatch, self.t_done = t_submit, None, None
        self._ev, self._rec, self._err = threading.Event(), None, None

    def answer(self, t, rec=None, err=None):
        self.t_dispatch, self.t_done = self.t_submit + 0.001, t
        self._rec, self._err = rec, err
        self._ev.set()

    def done(self):
        return self._ev.is_set()

    def result(self, timeout=None):
        if not self._ev.wait(timeout):
            raise TimeoutError
        if self._err is not None:
            raise self._err
        return self._rec


class Tenant:
    def __init__(self, shed_every=0):
        self.futs, self.shed_every = [], shed_every

    def submit(self, read, ref):
        from repro.api.gateway import ShedError
        if self.shed_every and len(self.futs) % self.shed_every == 1:
            self.futs.append(None)
            raise ShedError("full")
        f = Fut(float(len(self.futs)))
        self.futs.append(f)
        return f


def test_states_and_kept_answers():
    tenant = Tenant(shed_every=4)
    reqs = harness.Requests(keep=[2], clock=lambda: 0.0)
    for i in range(6):
        reqs.submit(tenant, ("r", "f"), i % 3, float(i))
    futs = tenant.futs
    futs[0].answer(10.0, rec={"n": 0})
    futs[2].answer(11.0, rec={"n": 2})
    futs[3].answer(12.0, err=RuntimeError("boom"))
    reqs.harvest()                      # 0, 2 and 3 are done; 1, 5 shed
    assert [f for _, f in reqs.pending] == [futs[4]]
    reqs.settle_all(until=0.0)          # 4 never answered
    a = reqs.arrays()
    assert a["state"].tolist() == [harness.ANSWERED, harness.SHED,
                                   harness.ANSWERED, harness.ERROR,
                                   harness.UNANSWERED, harness.SHED]
    assert a["idx"].tolist() == [0, 1, 2, 0, 1, 2]
    assert a["t_done"][0] == 10.0 and np.isnan(a["t_done"][4])
    assert reqs.kept == [(2, {"n": 2})]


def run_data(t_done, t0=0.0, t1=20.0):
    n = len(t_done)
    req = {"idx": np.arange(n), "t_due": np.zeros(n),
           "t_done": np.asarray(t_done, float),
           "queue_s": np.full(n, 0.002),
           "state": np.where(np.isnan(t_done), harness.UNANSWERED,
                             harness.ANSWERED).astype(np.int8)}
    return harness.RunData(t0=t0, t1=t1, t_giveup=t1 + 60, setup_s=1.0,
                           req=req, stats0={}, stats1={}, spans=[],
                           trace=None)


def test_rate_ends_at_the_last_answer():
    """Lumps of 128 every 0.6 s: the rate is 128 / 0.6 whatever part of
    a lump the window's close cuts off."""
    read = spec.metric_reader("reads_per_s")
    for t1 in (19.3, 19.5, 19.79):
        t = np.repeat(np.arange(1, 40) * 0.6, 128)
        got = read(run_data(t, t1=t1))
        assert abs(got - 128 / 0.6) < 1e-9, (t1, got)


def test_p95_counts_a_missing_answer_as_the_give_up_time():
    read = spec.metric_reader("p95_latency_ms")
    t = np.full(100, 0.010)
    assert abs(read(run_data(t)) - 10.0) < 1e-9
    t[:5] = np.nan
    assert abs(read(run_data(t)) - 10.0) < 1e-9     # 5% missing: still p95
    t[:6] = np.nan
    assert read(run_data(t)) == 80_000.0            # t_giveup - t_due
