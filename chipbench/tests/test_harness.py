"""The harness end to end on the CPU: a cell made of new files only, the
faults that ``correct`` must catch, and the refusal to run without a
TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import run
from chipbench.tests.conftest import ROOT


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


@pytest.mark.parametrize("fault", [answer_altered, half_batch_lost,
                                   stale_step])
def test_a_fault_in_the_timed_path_is_not_correct(bench_root, fault):
    r = run_cell(bench_root, "lenet.closed", fault=fault)
    assert r["correct"] is False
    assert r["checks"]["prob_gap"]["value"] > r["checks"]["prob_gap"][
        "limit"]


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
