"""The plain reference, the comparison's control and the FLOP counts, on the
CPU at reduced sizes (Pallas in interpret mode)."""
import numpy as np
import pytest

from chipbench import flops, reference as R, spec
from chipbench.run import check_program_config
from chipbench.tests.conftest import ROOT


def load(name):
    return spec.load_json(f"{ROOT}/chipbench/configs/{name}.json")


def table_from(pcfg, base):
    """The layer table of a program config, in the configuration file's
    form (for the reduced sizes only the tests run)."""
    layers = []
    for s in pcfg.layers:
        l = {"name": s.name, "kind": s.kind}
        if s.kind == "conv":
            l.update(out=s.out_channels, kernel=s.kernel, stride=s.stride,
                     pad=s.pad)
        elif s.kind == "pool":
            l.update(kernel=s.kernel, stride=s.stride, op=s.pool_op)
        elif s.kind == "fc":
            l.update(out=s.fc_out)
        if s.inputs:
            l["inputs"] = list(s.inputs)
        layers.append(l)
    return dict(base, image_hw=pcfg.image_hw, layers=layers)


def reduced(name):
    """(table, program config) of a configuration at a CPU size."""
    from repro.configs.cnn_networks import CNN_CONFIGS, build_resnet18
    base = load(name)
    if base["network"] == "resnet18":
        pcfg = build_resnet18(batch=3, image_hw=64, width=16)
    else:
        pcfg = CNN_CONFIGS[base["network"]].replace(batch=3, image_hw=67)
    return table_from(pcfg, base), pcfg


@pytest.mark.parametrize("name, gmac, mparams", [
    ("alexnet-fp32", 1.135, 62.4), ("resnet18-fp32", 1.798, 11.7)])
def test_flops_and_parameters(name, gmac, mparams):
    cfg = load(name)
    assert round(flops.macs_per_image(cfg) / 1e9, 3) == gmac
    assert round(flops.param_count(cfg) / 1e6, 1) == mparams
    assert flops.flops_per_image(cfg) == 2 * flops.macs_per_image(cfg)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # AlexNet at 128: compute-bound (1.48 ms of FLOPs, 0.40 ms of bytes)
    if name == "alexnet-fp32":
        assert flops.least_seconds(cfg, 128, peak) == pytest.approx(
            128 * 2.270512192e9 / 197e12)
        assert flops.compulsory_bytes(cfg, 128) / 819e9 == pytest.approx(
            0.000402, rel=0.01)


@pytest.mark.parametrize("name", ["alexnet-fp32", "resnet18-fp32"])
def test_program_config_is_the_published_table(name):
    from repro.configs.cnn_networks import CNN_CONFIGS
    cfg = load(name)
    check_program_config(cfg, CNN_CONFIGS[cfg["network"]])
    bad = dict(cfg, layers=[dict(cfg["layers"][0], out=1)]
               + cfg["layers"][1:])
    with pytest.raises(ValueError):
        check_program_config(bad, CNN_CONFIGS[cfg["network"]])


@pytest.mark.parametrize("name", ["alexnet-fp32", "resnet18-fp32"])
def test_reference_agrees_with_forward_fused(name):
    import jax
    from repro.cnn.layers import init_cnn
    from repro.cnn.network import forward_fused, plan_network_fused
    cfg, pcfg = reduced(name)
    params = R.init_params(cfg, 2 ** 40 + 7)
    want = jax.eval_shape(lambda k: init_cnn(k, pcfg),
                          jax.random.PRNGKey(0))
    assert (jax.tree.map(lambda a: a.shape, params)
            == jax.tree.map(lambda a: a.shape, want))
    x = np.random.default_rng(3).standard_normal(
        (3, 3, cfg["image_hw"], cfg["image_hw"]), dtype=np.float32)
    plan = plan_network_fused(pcfg, dtype="float32")
    got, _ = forward_fused(params, x, pcfg, plan, impl="pallas",
                           interpret=True)
    ref = R.reference_probs(params, x, cfg, "highest", block=3)
    got = np.asarray(got, np.float32)
    nums = R.check_numbers(got, ref)
    assert all(nums[k] <= v for k, v in cfg["limits"].items()), nums


def test_one_pass_bf16_fc_fails_the_comparison(monkeypatch):
    cfg, _ = reduced("alexnet-fp32")
    params = R.init_params(cfg, 11)
    x = np.random.default_rng(4).standard_normal(
        (4, 3, cfg["image_hw"], cfg["image_hw"]), dtype=np.float32)
    ref = R.reference_probs(params, x, cfg, "highest", block=4)
    mm = R._mm

    def fc_in_bf16(op, a, b, precision):
        return mm(op, a, b, "bf16" if op.__name__ == "dot" else precision)
    monkeypatch.setattr(R, "_mm", fc_in_bf16)
    got = R.reference_probs(params, x, cfg, "highest", block=4)
    nums = R.check_numbers(got, ref)
    assert all(nums[k] > v for k, v in cfg["limits"].items()), nums


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_control_fails_the_comparison(seed):
    """The reference one precision below the configuration's (three bf16
    passes for float32 at highest), in the program's place, is not
    correct."""
    cfg, _ = reduced("alexnet-fp32")
    params = R.init_params(cfg, seed)
    x = np.random.default_rng(seed).standard_normal(
        (16, 3, cfg["image_hw"], cfg["image_hw"]), dtype=np.float32)
    ref = R.reference_probs(params, x, cfg, "highest", block=16)
    got = R.reference_probs(params, x, cfg, "high", block=16)
    nums = R.check_numbers(got, ref)
    assert any(nums[k] > v for k, v in cfg["limits"].items())


def test_split_is_exact_and_rounds_to_nearest():
    import jax.numpy as jnp
    a = np.random.default_rng(0).standard_normal(4096).astype(np.float32)
    hi, lo = R._split(jnp.asarray(a))
    assert np.array_equal(np.asarray(hi),
                          np.asarray(jnp.asarray(a).astype(jnp.bfloat16)))
    err = np.abs(np.asarray(hi, np.float32) + np.asarray(lo, np.float32) - a)
    assert (err <= np.abs(a) * 2.0 ** -16).all()


def test_seed_keys_take_large_seeds():
    import jax
    k1 = jax.random.key_data(R.seed_key(2 ** 31 + 5))
    k2 = jax.random.key_data(R.seed_key(2 ** 31 + 6))
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    assert np.array_equal(np.asarray(k1),
                          np.asarray(jax.random.key_data(
                              R.seed_key(2 ** 31 + 5))))
