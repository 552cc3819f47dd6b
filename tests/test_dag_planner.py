"""Graph-level fusion (DESIGN.md §11): DAG planner properties, residual
epilogues on the real Pallas kernels, and the branching-network acceptance
criteria (ResNet-18 / U-Net mini)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cnn.layers import fused_conv_block, init_cnn, layer_shapes
from repro.cnn.network import (forward, forward_fused, input_shape,
                               make_train_step_fused, init_velocity,
                               network_descs, plan_network,
                               plan_network_fused)
from repro.configs.cnn_networks import (ALEXNET, CNN_CONFIGS, LENET,
                                        build_resnet18, build_unet_mini,
                                        reduced_cnn)
from repro.core.selector import assign_layouts, plan_fused
from repro.serve.plan_cache import network_id

KEY = jax.random.PRNGKey(0)

RESNET18 = CNN_CONFIGS["resnet18"]
UNET_MINI = CNN_CONFIGS["unet_mini"]


# ---------------------------------------------------------------------------
# planner properties
# ---------------------------------------------------------------------------

def _op_sig(op):
    return (op.kind, op.index, op.name, op.layout, op.src_layout,
            op.dst_layout, op.relu, op.pool_index, op.src_dtype,
            op.dst_dtype, op.add_index, op.res_index)


@pytest.mark.parametrize("base", [LENET, ALEXNET])
@pytest.mark.parametrize("policy", ["uniform", "mixed"])
@pytest.mark.parametrize("training", [False, True])
def test_linear_graph_degenerates_to_chain_plan(base, policy, training):
    """On a linear network the frontier DP must reproduce the chain DP
    byte-identically: same layouts, dtypes, costs, and op stream."""
    descs = network_descs(base)
    kw = dict(input_layout="NCHW", input_shape=input_shape(base),
              dtype_policy=policy, training=training)
    chain = plan_fused(descs, **kw)
    graph = plan_fused(descs, _force_graph=True, **kw)
    assert graph.layouts == chain.layouts
    assert graph.dtypes == chain.dtypes
    assert graph.transforms == chain.transforms
    assert graph.fused_bytes == chain.fused_bytes
    assert graph.unfused_bytes == chain.unfused_bytes
    assert graph.total_s == pytest.approx(chain.total_s, rel=1e-9)
    assert [_op_sig(o) for o in graph.ops] == [_op_sig(o) for o in chain.ops]


@pytest.mark.parametrize("cfg", [RESNET18, UNET_MINI],
                         ids=["resnet18", "unet_mini"])
def test_dag_plan_never_worse_than_unfused(cfg):
    """Fused DAG plans dominate their own unfused linearization in both DP
    objectives (modeled seconds, modeled HBM bytes)."""
    plan = plan_network_fused(cfg)
    asg = assign_layouts(network_descs(cfg), input_layout="NCHW",
                         input_shape=input_shape(cfg))
    assert plan.fused_bytes <= plan.unfused_bytes
    assert plan.total_s <= asg.total_s * (1 + 1e-9)


def test_resnet18_plan_acceptance():
    """ISSUE 6 acceptance: zero standalone residual adds and >= 25% fewer
    modeled HBM bytes than the decomposed execution at float32."""
    plan = plan_network_fused(RESNET18)
    assert plan.standalone_adds == 0
    assert plan.fused_bytes <= 0.75 * plan.unfused_bytes
    # every residual add is folded into a conv epilogue
    adds = [i for i, s in enumerate(RESNET18.layers) if s.kind == "add"]
    folded = {op.add_index for op in plan.ops if op.add_index is not None}
    assert folded == set(adds)


def test_unet_plan_folds_merges():
    plan = plan_network_fused(UNET_MINI)
    assert plan.standalone_adds == 0
    assert plan.fused_bytes < plan.unfused_bytes
    # concat/upsample stay as explicit graph ops with edges attached
    kinds = {op.kind for op in plan.ops}
    assert "concat" in kinds and "upsample" in kinds
    for op in plan.ops:
        if op.kind == "concat":
            assert len(op.inputs) == 2


def test_mixed_merge_join_keeps_skip_producers_at_base_dtype():
    """Under --dtype-policy mixed, int8 storage may only appear on conv->conv
    main edges; any tensor consumed by a folded residual add (or a concat)
    must stay at the base float dtype — the skip is added raw in VMEM with
    no dequant hook."""
    plan = plan_network_fused(RESNET18, policy="mixed")
    assert "int8" in plan.dtypes            # the policy actually engages
    skip_srcs = {op.res_index for op in plan.ops if op.res_index is not None}
    for s in skip_srcs:
        assert plan.dtypes[s] == plan.base_dtype, (s, plan.dtypes[s])
    # compare dtype policies on equal footing: mixed plans never stack
    # (DESIGN.md §12 pairing gates), so hold stacking off on both sides
    uplan = plan_network_fused(RESNET18, policy="uniform", stack_policy="off")
    assert plan.fused_bytes <= uplan.fused_bytes

    cplan = plan_network_fused(UNET_MINI, policy="mixed")
    for op in cplan.ops:
        if op.kind == "concat":
            for p in op.inputs:
                assert cplan.dtypes[p] == cplan.base_dtype


# ---------------------------------------------------------------------------
# residual epilogue on the real Pallas kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["CHWN", "NCHW"])
@pytest.mark.parametrize("res_layout", ["CHWN", "NCHW"])
@pytest.mark.parametrize("pool", [None, (2, 2, "max")],
                         ids=["nopool", "pool"])
def test_residual_epilogue_matches_xla(layout, res_layout, pool):
    """conv+bias+residual+relu[+pool] as ONE Pallas kernel: forward and all
    four gradients (x, w, bias, skip) agree with the decomposed XLA
    reference, for both engines and both skip storage layouts."""
    N, Ci, H, Co, F = 2, 4, 6, 8, 3
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    x_nchw = jax.random.normal(k1, (N, Ci, H, H))
    w = jax.random.normal(k2, (Co, Ci, F, F)) * 0.2
    b = jax.random.normal(k3, (Co,)) * 0.1
    res_nchw = jax.random.normal(k4, (N, Co, H, H))

    def tr(t, lay):
        return jnp.transpose(t, (1, 2, 3, 0)) if lay == "CHWN" else t

    x, res = tr(x_nchw, layout), tr(res_nchw, res_layout)

    def run(impl):
        def f(x, w, b, res):
            y = fused_conv_block(x, w, layout, stride=1, pad=1, bias=b,
                                 relu=True, pool=pool, res=res,
                                 res_layout=res_layout, impl=impl)
            return jnp.sum(y * jnp.cos(y)), y
        (_, y), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2, 3), has_aux=True)(x, w, b, res)
        return y, grads

    yp, gp = run("pallas")
    yx, gx = run("xla")
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yx), atol=1e-4)
    for a, b2 in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2), atol=1e-4)


# ---------------------------------------------------------------------------
# end-to-end branching execution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["resnet18", "unet_mini"])
def test_branching_network_pallas_fused_matches_xla_unfused(name):
    """ISSUE 6 acceptance: the fully fused Pallas execution of the branching
    networks reproduces the decomposed XLA reference to <= 1e-5."""
    cfg = reduced_cnn(CNN_CONFIGS[name], batch=4)
    params = init_cnn(KEY, cfg)
    x = jax.random.normal(KEY, input_shape(cfg))
    plan = plan_network_fused(cfg)
    got, stats = forward_fused(params, x, cfg, plan, impl="pallas")
    ref, sref = forward(params, x, cfg, plan_network(cfg, "cudnn"),
                        impl="xla")
    assert float(jnp.max(jnp.abs(got - ref))) <= 1e-5
    assert plan.standalone_adds == 0
    assert stats.hbm_bytes < sref.hbm_bytes


def test_resnet18_fused_training_decreases_loss():
    cfg = reduced_cnn(RESNET18, batch=4)
    params = init_cnn(KEY, cfg)
    x = jax.random.normal(KEY, input_shape(cfg))
    y = jax.random.randint(jax.random.PRNGKey(2), (4,), 0, cfg.num_classes)
    plan = plan_network_fused(cfg)
    step = make_train_step_fused(cfg, plan, lr=0.02)
    vel = init_velocity(params)
    losses = []
    for _ in range(3):
        params, vel, loss = step(params, vel, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# config / cache plumbing
# ---------------------------------------------------------------------------

def test_network_id_folds_topology():
    """Edge-stripped configs must not collide with the real graph, while
    pre-DAG linear fingerprints stay byte-stable."""
    cfg = reduced_cnn(RESNET18, batch=4)
    stripped = cfg.replace(layers=tuple(
        dataclasses.replace(s, inputs=()) for s in cfg.layers))
    assert network_id(cfg) != network_id(stripped)
    # regression pins: legacy linear fingerprints from the pre-DAG planner
    assert network_id(ALEXNET) == "alexnet@f24092e5d5"
    assert network_id(LENET) == "lenet@674789fa69"


def test_cnn_server_reduces_branching_net_through_builder(tmp_path):
    """The serving driver's quick mode must shrink resnet18 through its
    builder — a bare replace(image_hw=96) zeroes out the 7x7 global pool
    and init_cnn divides by zero on the fc fan-in."""
    from repro.launch.cnn_serve import CNNServer
    srv = CNNServer(network="resnet18", reduced=True, calibration="analytic",
                    cache_path=str(tmp_path / "cache.json"))
    assert srv.cfg.image_hw <= 96
    shapes = layer_shapes(srv.cfg)
    assert shapes[-1] == (srv.cfg.batch, srv.cfg.num_classes)
    assert all(0 not in s for s in shapes)


@pytest.mark.parametrize("hw", [16, 32])
@pytest.mark.parametrize("name", ["resnet18", "unet_mini"])
def test_builders_keep_merge_shapes_consistent(name, hw):
    """reduced_cnn re-derives every skip edge through the builder, so merge
    nodes validate at any supported size (layer_shapes raises on mismatch)."""
    cfg = reduced_cnn(CNN_CONFIGS[name].replace(image_hw=hw), batch=2)
    shapes = layer_shapes(cfg)
    assert shapes[-1] == (2, cfg.num_classes)
    # builders at a non-reduced size too
    big = (build_resnet18(batch=2, image_hw=64, width=8) if name == "resnet18"
           else build_unet_mini(batch=2, image_hw=64, width=8))
    assert layer_shapes(big)[-1] == (2, big.num_classes)
