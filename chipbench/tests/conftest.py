"""Shared pieces of the benchmark's CPU tests.

``bench_root`` builds, in a temporary directory, a checkout that holds
``BENCHMARK.json`` and a copy of ``chipbench/`` to which a new LeNet cell
was added as new files and new entries only: a configuration, two traffic
mixes and one per-layer metric.  Run with ``python -m pytest
chipbench/tests``.
"""
import json
import os
import shutil
import sys
import tempfile

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
# the harness turns on the program's persistent compile cache; the tests
# keep theirs apart from the checkout's
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    tempfile.gettempdir(), "chipbench-tests-jax-cache"))

LENET = {
    "name": "lenet-fp32", "network": "lenet", "dtype": "float32",
    "precision": "highest", "policy": "uniform", "in_channels": 1,
    "image_hw": 28, "num_classes": 10,
    "layers": [
        {"name": "conv1", "kind": "conv", "out": 16, "kernel": 5,
         "stride": 1, "pad": 2},
        {"name": "relu1", "kind": "relu"},
        {"name": "pool1", "kind": "pool", "kernel": 2, "stride": 2,
         "op": "max"},
        {"name": "conv2", "kind": "conv", "out": 16, "kernel": 5,
         "stride": 1, "pad": 2},
        {"name": "relu2", "kind": "relu"},
        {"name": "pool2", "kind": "pool", "kernel": 2, "stride": 2,
         "op": "max"},
        {"name": "flatten", "kind": "flatten"},
        {"name": "fc1", "kind": "fc", "out": 128},
        {"name": "relu3", "kind": "relu"},
        {"name": "fc2", "kind": "fc", "out": 10},
        {"name": "softmax", "kind": "softmax"}],
    "reduced": [],
}
# the metric a later PR might add: steps run in the window
STEPS_METRIC = '''
def read(ctx):
    return float(len(ctx.steps)) if ctx.steps else None
'''


def make_root(tmp, limits):
    """A checkout with the LeNet cells ``lenet.closed`` and ``lenet.open``
    added to the benchmark by new files only."""
    bench_dir = os.path.join(tmp, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "lenet-fp32", "source": "LeCun et al. 1998",
        "file": "chipbench/configs/lenet-fp32.json", "reduced": [],
        "why": "a network small enough for the Pallas interpreter"})
    bench["workloads"] += [
        {"name": "lenet.closed", "config": "lenet-fp32",
         "traffic": "tiny-closed", "chips": 1, "why": "test"},
        {"name": "lenet.open", "config": "lenet-fp32",
         "traffic": "tiny-open", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += (["lenet.open"] if "online" in m["name"]
                               or m["name"] == "latency_p50_ms"
                               else ["lenet.closed"])
    bench["per_layer"].append({
        "name": "steps.test", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "admission",
        "moves": "images_per_s", "workloads": ["lenet.closed"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(bench_dir, "configs", "lenet-fp32.json"),
              "w") as f:
        json.dump(dict(LENET, limits=limits), f)
    for name, mix in (
            ("tiny-closed", {"kind": "closed", "depth": 8,
                             "max_bucket": 8, "pool": 16}),
            ("tiny-open", {"kind": "open", "rate": 40.0, "max_bucket": 4,
                           "pool": 16})):
        with open(os.path.join(bench_dir, "traffic", f"{name}.json"),
                  "w") as f:
            json.dump(mix, f)
    with open(os.path.join(bench_dir, "metrics", "steps.test.py"),
              "w") as f:
        f.write(STEPS_METRIC)
    return tmp


@pytest.fixture
def bench_root(tmp_path):
    return make_root(str(tmp_path), {"prob_gap": 1e-4, "logit_rms": 1e-5})
