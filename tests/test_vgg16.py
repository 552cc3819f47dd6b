"""VGG-16 (configuration D of Simonyan & Zisserman, ICLR 2015) against the
benchmark's configuration file and its plain reference, on the CPU: the
program's layer table, the fused Pallas forward (interpreted) at a reduced
image size with the published widths, and the operation counts."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import flops, reference as R, spec  # noqa: E402
from chipbench.run import check_program_config  # noqa: E402
from repro.configs.cnn_networks import (  # noqa: E402
    CNN_CONFIGS, VGG16, reduced_cnn)

CONFIG = ROOT / "chipbench" / "configs" / "vgg16-fp32.json"
# Both sides compute in float32 and differ only in summation order (the
# kernels' per-tap matmuls against one lax convolution, contractions of at
# most 4608 terms): on three seeds the gaps read at most 1.7e-7 and 2.4e-7.
# One bf16 pass in the reference reads 3e-4 and more on both, so these
# bounds fail it by more than an order of magnitude, with 4x or more of
# room above the program's rounding.
PROB_GAP_TOL = 1e-5
LOGIT_RMS_TOL = 1e-6


def table():
    return spec.load_json(str(CONFIG))


def test_program_config_is_the_published_table():
    cfg = table()
    assert cfg["network"] == "vgg16" and cfg["reduced"] == []
    assert (cfg["precision"], cfg["policy"]) == ("highest", "uniform")
    assert len(cfg["layers"]) == 38
    check_program_config(cfg, CNN_CONFIGS["vgg16"])
    convs = [l for l in cfg["layers"] if l["kind"] == "conv"]
    assert [l["out"] for l in convs] == [64] * 2 + [128] * 2 + [256] * 3 \
        + [512] * 6
    assert all((l["kernel"], l["stride"], l["pad"]) == (3, 1, 1)
               for l in convs)


@pytest.mark.parametrize("field,value", [("out", 32), ("kernel", 5),
                                         ("pad", 0)])
def test_a_changed_layer_is_refused(field, value):
    cfg = table()
    layers = list(cfg["layers"])
    layers[2] = dict(layers[2], **{field: value})       # conv1_2
    with pytest.raises(ValueError):
        check_program_config(dict(cfg, layers=layers), CNN_CONFIGS["vgg16"])


def test_flops_and_parameters():
    cfg = table()
    assert round(flops.macs_per_image(cfg) / 1e9, 3) == 15.470
    assert round(flops.param_count(cfg) / 1e6, 1) == 138.4
    assert flops.param_count(cfg) * 4 == pytest.approx(553e6, rel=1e-3)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # compute-bound at bucket 32: 5.03 ms of FLOPs against 0.70 ms of bytes
    assert flops.least_seconds(cfg, 32, peak) == pytest.approx(5.03e-3,
                                                               rel=1e-3)
    assert flops.compulsory_bytes(cfg, 32) / 819e9 == pytest.approx(
        0.70e-3, rel=0.01)


@pytest.fixture(scope="module")
def small():
    """(table, program config, weights, images, reference probabilities)
    at 32 px, batch 2, with every published width."""
    pcfg = reduced_cnn(VGG16, batch=2)
    assert pcfg.image_hw == 32 and pcfg.layers == VGG16.layers
    cfg = dict(table(), image_hw=32)        # the layer table is unchanged
    params = R.init_params(cfg, 2 ** 40 + 11)
    x = np.random.default_rng(5).standard_normal(
        (2, 3, 32, 32), dtype=np.float32)
    ref = R.reference_probs(params, x, cfg, "highest", block=2)
    return cfg, pcfg, params, x, ref


def test_forward_fused_agrees_with_the_reference(small):
    import jax
    from repro.cnn.layers import init_cnn
    from repro.cnn.network import forward_fused, plan_network_fused
    cfg, pcfg, params, x, ref = small
    want = jax.eval_shape(lambda k: init_cnn(k, pcfg), jax.random.PRNGKey(0))
    assert (jax.tree.map(lambda a: a.shape, params)
            == jax.tree.map(lambda a: a.shape, want))
    plan = plan_network_fused(pcfg, dtype="float32")
    got, _ = forward_fused(params, x, pcfg, plan, impl="pallas",
                           interpret=True)
    nums = R.check_numbers(np.asarray(got, np.float32), ref)
    assert nums["prob_gap"] <= PROB_GAP_TOL, nums
    assert nums["logit_rms"] <= LOGIT_RMS_TOL, nums


def test_one_bf16_pass_fails_the_tolerances(small):
    cfg, _, params, x, ref = small
    got = R.reference_probs(params, x, cfg, "bf16", block=2)
    nums = R.check_numbers(got, ref)
    assert nums["prob_gap"] > PROB_GAP_TOL, nums
    assert nums["logit_rms"] > LOGIT_RMS_TOL, nums


@pytest.mark.parametrize("cell,depth,bucket,chips", [
    ("vgg16.offline", 32, 32, 1)])
def test_cell_is_found_by_name(cell, depth, bucket, chips):
    """The harness resolves each cell's configuration, mix and traffic
    kind from files alone; a closed mix's depth is the global batch."""
    bench = spec.benchmark(str(ROOT))
    w = spec.workload(bench, cell)
    assert w["chips"] == chips
    cfg = spec.config(bench, w["config"], str(ROOT))
    assert cfg["limits"].keys() == {"prob_gap", "logit_rms"}
    bench_dir = str(ROOT / "chipbench")
    mix = spec.traffic(w["traffic"], bench_dir)
    assert (mix["depth"], mix["max_bucket"]) == (depth, bucket)
    assert mix["depth"] == mix["max_bucket"] * chips
    kind = spec.traffic_kind(mix["kind"], bench_dir)
    assert kind.warm_sizes(mix, chips) == [depth]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"].endswith(".offline") or m["name"] == "images_per_s":
            assert cell in m["workloads"], m["name"]
