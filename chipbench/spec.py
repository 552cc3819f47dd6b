"""What the benchmark is made of, found by name: ``BENCHMARK.json``, the
configuration files, the traffic mixes, the per-layer metric readers and the
peak table.  Nothing here imports the program under test or JAX, so a later
cell, mix or metric is a new file and a new entry, never an edit.

A configuration file holds the network's published layer table:

    {"name": "alexnet-fp32", "network": "alexnet", "dtype": "float32",
     "in_channels": 3, "image_hw": 227, "num_classes": 1000,
     "layers": [{"name": "conv1", "kind": "conv", "out": 96, "kernel": 11,
                 "stride": 4, "pad": 0}, {"name": "relu1", "kind": "relu"},
                ...]}

Layer kinds: conv (no bias), relu, pool (``op`` max or avg, unpadded),
flatten, fc (with bias), add (``inputs`` names two earlier layers) and
softmax.  A layer without ``inputs`` reads the layer before it.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"({[w['name'] for w in bench['workloads']]})")


def config_entry(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def config(bench: Dict, name: str, root: str = ROOT) -> Dict:
    cfg = load_json(os.path.join(root, config_entry(bench, name)["file"]))
    if cfg["name"] != name:
        raise ValueError(f"config file names {cfg['name']!r}, not {name!r}")
    return cfg


def traffic(name: str, bench_dir: str = HERE) -> Dict:
    return load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def load_module(path: str, name: str):
    """Import a file by path (metric readers and traffic kinds carry dots
    and dashes in their names, which ``import`` cannot spell)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_kind(kind: str, bench_dir: str = HERE):
    return load_module(os.path.join(bench_dir, "traffic", "kinds",
                                    f"{kind}.py"), f"chipbench_kind_{kind}")


def metric_reader(name: str, bench_dir: str = HERE):
    return load_module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                       "chipbench_metric_" + name.replace(".", "_"))


def peaks(device_kind: str, bench_dir: str = HERE) -> Dict:
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table['devices'])})")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# the layer table
# ---------------------------------------------------------------------------

def producers(cfg: Dict) -> List[Tuple[int, ...]]:
    """Per layer, the indices of the layers it reads (-1: the image)."""
    idx = {l["name"]: i for i, l in enumerate(cfg["layers"])}
    out = []
    for i, l in enumerate(cfg["layers"]):
        if "inputs" in l:
            ins = tuple(idx[n] for n in l["inputs"])
            if any(p >= i for p in ins):
                raise ValueError(f"{l['name']}: reads a later layer")
        else:
            ins = (i - 1,)
        out.append(ins)
    return out


def layer_shapes(cfg: Dict) -> List[Tuple[int, ...]]:
    """Per-image output shape of every layer: (C, H, W) or (features,)."""
    shapes: List[Tuple[int, ...]] = []
    img = (cfg["in_channels"], cfg["image_hw"], cfg["image_hw"])

    def shp(p):
        return img if p < 0 else shapes[p]

    for l, ins in zip(cfg["layers"], producers(cfg)):
        s = shp(ins[0])
        k = l["kind"]
        if k == "conv":
            hw = (s[1] + 2 * l["pad"] - l["kernel"]) // l["stride"] + 1
            shapes.append((l["out"], hw, hw))
        elif k == "pool":
            hw = (s[1] - l["kernel"]) // l["stride"] + 1
            shapes.append((s[0], hw, hw))
        elif k == "flatten":
            shapes.append((math.prod(s),))
        elif k == "fc":
            shapes.append((l["out"],))
        elif k == "add":
            if len({shp(p) for p in ins}) != 1:
                raise ValueError(f"{l['name']}: operands disagree")
            shapes.append(s)
        elif k in ("relu", "softmax"):
            shapes.append(s)
        else:
            raise ValueError(f"{l['name']}: unknown layer kind {k!r}")
    return shapes


def in_dims(cfg: Dict) -> List[int]:
    """Per layer, the channels (conv) or features (fc) it reads."""
    shapes = layer_shapes(cfg)
    return [cfg["in_channels"] if ins[0] < 0 else shapes[ins[0]][0]
            for ins in producers(cfg)]
