"""A run with the timed path broken underneath has to come out not
correct; a sound one correct.  Each drives the harness in-process at
rehearsal size (CPU, interpret-mode kernels) past its look for a chip."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from conftest import ROOT

ARGS = ["--workload", "short150.backlog", "--seed", "4242", "--seconds", "2",
        "--trace", "0", "--rehearse"]


def run(argv=ARGS):
    return harness.run(list(argv))


def test_sound_run_is_correct():
    out = run()
    assert out["correct"] is True
    assert out["checks"]["mismatched"]["value"] == 0
    assert out["checks"]["compared"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_answer_altered_where_produced(monkeypatch):
    from repro.api import session as sess
    real = sess.records_from_state

    def altered(*a, **kw):
        recs = real(*a, **kw)
        recs[0] = dict(recs[0], dist=recs[0]["dist"] + 1)
        return recs

    monkeypatch.setattr(sess, "records_from_state", altered)
    out = run()
    assert out["correct"] is False
    assert out["checks"]["mismatched"]["value"] > 0


def test_half_the_batch_left_out(monkeypatch):
    from repro.api.session import AlignSession
    real = AlignSession._pad_batch

    def half(self, reads, refs, lanes, Lr, Lf):
        n = len(reads)
        reads = list(reads[:max(1, n // 2)]) * 2
        refs = list(refs[:max(1, n // 2)]) * 2
        return real(self, reads[:n], refs[:n], lanes, Lr, Lf)

    monkeypatch.setattr(AlignSession, "_pad_batch", half)
    out = run()
    assert out["correct"] is False
    assert out["checks"]["mismatched"]["value"] > 0


def test_answers_that_never_come(monkeypatch):
    from repro.api.gateway import Gateway
    real = Gateway._on_inner_done

    def drop_some(self, gf, af):
        if gf.rid % 7 == 3:
            return                      # this answer never reaches the client
        return real(self, gf, af)

    monkeypatch.setattr(Gateway, "_on_inner_done", drop_some)
    monkeypatch.setattr(harness, "DRAIN_S", 2.0)
    out = run()
    assert out["correct"] is False
    assert out["checks"]["unanswered"]["value"] > 0
    assert out["failed"] > 0


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run_cell.py"] + ARGS[:-1],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run_cell.py"] + ARGS,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("seed", [2**31 + 7, 12, 99])
@pytest.mark.parametrize("name,read_len,n_pool", [
    ("short150.backlog", 150, 512), ("long10k.backlog", 2000, 16)])
def test_controls_fail_at_test_size(seed, name, read_len, n_pool):
    """The controls (bench/control.py) read not correct on every seed."""
    import json

    from bench import control, spec
    cfg = json.loads(json.dumps(spec.load_cell(name).config))
    cfg["genome_bp"] = 200_000
    cfg["reads"]["read_len"] = read_len
    got = control.readings(cfg, seed, n_pool=n_pool, n_check=n_pool)
    assert got["gaps_first"]["mismatched"] > 0, got
    assert not got["gaps_first"]["correct"]
    if "no_rescue" in got and name.startswith("long"):
        assert got["no_rescue"]["mismatched"] > 0, got
