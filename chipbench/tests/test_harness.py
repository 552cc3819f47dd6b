"""The harness end to end on the CPU: a cell made of new files only, the
faults that ``correct`` must catch, the slow step that it must not, and
the refusal to run without a TPU."""
import dataclasses
import json
import os
import re
import statistics
import subprocess
import sys

import numpy as np
import pytest

from chipbench import run
from chipbench.tests.conftest import ROOT
from repro.runtime.resilience import FaultInjector


def run_cell(root, workload, trace=0, fault=None, seconds=1.0):
    argv = ["--workload", workload, "--seed", str(2 ** 32 + 9),
            "--seconds", str(seconds), "--trace", str(trace)]
    if trace:
        argv += ["--keep-trace", os.path.join(root, "kept.xplane.pb")]
    return run.run(run.parse(argv), root=root, require_chip=False,
                   fault=fault)


@pytest.mark.parametrize("workload, trace, metrics", [
    ("lenet.closed", 0, {"images_per_s", "setup_s"}),
    ("lenet.closed", 1, {"steps.test"}),
    ("lenet.open", 0, {"latency_p50_ms", "setup_s"}),
    ("lenet.open", 1, {"pad_share.online"}),
])
def test_a_cell_of_new_files_runs(bench_root, workload, trace, metrics):
    r = run_cell(bench_root, workload, trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == metrics
    assert all(m["value"] >= 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    assert os.path.exists(os.path.join(bench_root, "kept.xplane.pb")) == (
        trace == 1)


def _wrap_step(srv, alter):
    step = srv.step

    def faulty():
        served = step()
        alter(served)
        return served
    srv.step = faulty


def answer_altered(srv):
    """One answer per batch altered where it is produced: its two most
    likely classes swap places."""
    def alter(served):
        if served:
            p = served[0].probs.copy()
            i, j = np.argsort(p)[-2:]
            p[i], p[j] = p[j], p[i]
            served[0].probs = p
    _wrap_step(srv, alter)


def half_batch_lost(srv):
    """The second half of every batch gets the first half's answers, as
    when one of two shards' results never arrives and another is reused."""
    def alter(served):
        half = len(served) // 2
        for a, b in zip(served[:half], served[half:2 * half]):
            b.probs = a.probs
    _wrap_step(srv, alter)


def stale_step(srv):
    """A step that hands back its previous batch's outputs unchanged."""
    box = {}

    def alter(served):
        fresh = [r.probs for r in served]
        if "last" in box:
            for r, p in zip(served, box["last"]):
                r.probs = p
        box["last"] = fresh
    _wrap_step(srv, alter)


def top_rung_kernel_fault(srv):
    """Every call of the top rung's kernels fails, as a kernel that faults
    on the chip: the server quarantines the rung and serves each batch a
    rung lower, with right answers."""
    srv.injector = FaultInjector(rates={f"kernel@{srv.ladder[0].name}": 1.0})


# the check that each fault has to fail
CAUGHT_BY = {answer_altered: "prob_gap", half_batch_lost: "prob_gap",
             stale_step: "prob_gap", top_rung_kernel_fault: "incidents"}


@pytest.mark.parametrize("fault", list(CAUGHT_BY))
def test_a_fault_in_the_timed_path_is_not_correct(bench_root, fault):
    r = run_cell(bench_root, "lenet.closed", fault=fault)
    assert r["correct"] is False
    check = r["checks"][CAUGHT_BY[fault]]
    assert check["value"] > check["limit"]


class OneSlowStep:
    """After thirty steps of the window, one step is stretched by a sleep
    of twenty median steps, through the injector's ``slow`` site inside the
    server's timer.  The bucket's watchdog starts afresh at the window:
    its statistics would hold warm-up's first call, which compiles for
    seconds in the interpreter, and no sleep a short test can afford would
    pass a threshold that high.  Thirty, because the watchdog takes the
    step into its own mean and variance before it compares: over n steps
    one step stands at most (n - 1) / sqrt(n) deviations above the mean,
    which passes its four only from n = 18."""

    def __init__(self):
        self.slept = None

    def __call__(self, srv):
        srv._watchdogs = {
            b: dataclasses.replace(wd, _n=0, _mean=0.0, _m2=0.0, flagged=[])
            for b, wd in srv._watchdogs.items()}
        run_guarded, seconds = srv._run_guarded, []

        def once(x, batch):
            if len(seconds) == 30:
                self.slept = 20 * statistics.median(seconds)
                srv.injector = FaultInjector(rates={"slow": 1.0},
                                             slow_s=self.slept)
            res = run_guarded(x, batch)
            srv.injector = None
            seconds.append(res.seconds)
            return res
        srv._run_guarded = once


def test_a_straggler_is_reported_and_not_compared(bench_root, capsys):
    fault = OneSlowStep()
    r = run_cell(bench_root, "lenet.closed", fault=fault, seconds=2.0)
    err = capsys.readouterr().err
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["incidents"]["value"] == 0
    assert re.search(r"incidents=\d+ \(straggler:\d+\)", err)
    flagged = [float(s) for s in re.findall(
        r"straggler at \d+\.\d+s from the window's start: serving "
        r"straggler: bucket=8 step=\d+ (\d+\.\d+)s", err)]
    assert fault.slept is not None and flagged
    assert max(flagged) >= fault.slept
    assert list(r)[-1] == "checks"


def test_no_tpu_exits_non_zero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "alexnet.offline", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_checkout_of_the_benchmark_alone_exits_non_zero(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "alexnet.offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_result_line_is_the_last_line_of_stdout(bench_root, capsys,
                                                monkeypatch):
    real = run.run
    monkeypatch.setattr(run, "run", lambda args: real(
        args, root=bench_root, require_chip=False))
    assert run.main(["--workload", "lenet.closed", "--seed", "3",
                     "--seconds", "0.5", "--trace", "0"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
