"""Compile-only tests for a TPU v5e: the serving path's Pallas kernels at
AlexNet's published widths, compiled by Mosaic for a described (not
attached) chip.  Interpret-mode tests cannot see what these catch: blocks
that break the (8, 128) tiling rule, strided or gathered value slices,
VMEM overflow.

The topology is described inside a module-scoped fixture — never at import
time — so every xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cnn.layers import init_cnn
from repro.cnn.network import forward_fused, input_shape, plan_network_fused
from repro.configs.cnn_networks import ALEXNET
from repro.kernels.conv.ops import (conv_direct_chwn, conv_im2col_nchw_fused,
                                    conv_stack)
from repro.kernels.pool.ops import pool_chwn, pool_nchw

N = 8               # kernel compile cost does not grow with the batch grid

# AlexNet convs as the fused plans run them: (Ci, H, Co, F, S, pad, pool)
ALEX_CONVS = {
    "conv1": (3, 227, 96, 11, 4, 0, (3, 2, "max")),
    "conv2": (96, 27, 256, 5, 1, 2, (3, 2, "max")),
    "conv5": (384, 13, 256, 3, 1, 1, (3, 2, "max")),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _act(layout, C, H):
    return (C, H, H, N) if layout == "CHWN" else (N, C, H, H)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layer", sorted(ALEX_CONVS))
@pytest.mark.parametrize("engine", ["CHWN", "NCHW"])
def test_conv_engine_compiles_at_alexnet_width(one_chip, engine, layer,
                                               dtype):
    Ci, H, Co, F, S, pad, pool = ALEX_CONVS[layer]
    dt = jnp.dtype(dtype)
    if engine == "CHWN":
        def fn(x, w, b):
            return conv_direct_chwn(x, w, S, pad, interpret=False, bias=b,
                                    relu=True, pool=pool, dst_layout="NCHW")
        w_shape = (Ci, F, F, Co)
    else:
        def fn(x, w, b):
            return conv_im2col_nchw_fused(x, w, S, pad, interpret=False,
                                          bias=b, relu=True, pool=pool,
                                          dst_layout="CHWN")
        w_shape = (Co, Ci, F, F)
    _compile(fn, one_chip, (_act(engine, Ci, H), dt), (w_shape, dt),
             ((Co,), dt))


def test_conv3_conv4_stack_compiles(one_chip):
    """AlexNet's one stack (conv3 -> conv4, 3x3/1/1 both, 256->384->384) on
    the NCHW engine; the CHWN stack compiles inside the bucket-128 forward
    below (the same kernel: four samples per slab at either batch)."""
    def fn(x, w1, w2):
        return conv_stack(x, w1, w2, 1, 1, 1, 1, engine="NCHW",
                          interpret=False, relu1=True, relu2=True)
    f32 = jnp.float32
    _compile(fn, one_chip, (_act("NCHW", 256, 13), f32),
             ((384, 256, 3, 3), f32), ((384, 384, 3, 3), f32))


@pytest.mark.parametrize("engine", ["CHWN", "NCHW"])
def test_pool_compiles_at_alexnet_pool1(one_chip, engine):
    if engine == "CHWN":
        def fn(x):
            return pool_chwn(x, 3, 2, "max", interpret=False)
    else:
        def fn(x):
            return pool_nchw(x, 3, 2, "max", interpret=False)
    _compile(fn, one_chip, (_act(engine, 96, 55), jnp.float32))


@pytest.mark.parametrize("engine", ["CHWN", "NCHW"])
def test_calibration_proxy_shapes_compile(one_chip, engine):
    """The measured-threshold sweep's extreme proxy (HW 8, Co 32, Ci 512,
    N 512) compiles: ``measured_thresholds`` on a chip times these."""
    from repro.cnn.layers import conv_forward

    def fn(x, w):
        return conv_forward(x, w, engine, 1, 0, impl="pallas",
                            interpret=False)
    shp = (512, 8, 8, 512) if engine == "CHWN" else (512, 512, 8, 8)
    _compile(fn, one_chip, (shp, jnp.float32), ((32, 512, 3, 3),
                                                jnp.float32))


def test_alexnet_forward_fused_compiles_at_bucket_128(one_chip):
    """The whole fp32 program served at bucket 128: CCCCC, pools fused
    into conv1/conv2/conv5, the conv3->conv4 stack, the fused softmax."""
    cfg = ALEXNET.replace(batch=128)
    plan = plan_network_fused(cfg)
    assert plan.conv_signature == "CCCCC"
    assert any(op.stack_index is not None for op in plan.ops)
    params = jax.eval_shape(lambda k: init_cnn(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    x = jax.ShapeDtypeStruct(input_shape(cfg), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(lambda p, x: forward_fused(
        p, x, cfg, plan, impl="pallas", interpret=False)[0]).lower(
            params, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 5
