"""Layout-aware CNN executor (the paper's §IV.D integration, end to end).

``plan_network`` turns a CNNConfig into selector LayerDescs, assigns a layout
per layer (heuristic or DP), and ``forward`` executes the stack natively in
those layouts, inserting the fast layout transform wherever consecutive
layers disagree (counting them, as the paper reports for AlexNet: 4).

``plan_network_fused`` / ``forward_fused`` are the fused execution engine
(DESIGN.md §5): conv->relu->pool chains run as ONE Pallas kernel with the
intermediate living in VMEM scratch, and every re-layout folds into a
producer's output write (or the first conv's input read), so no standalone
transform pass remains.  ``forward`` is kept as the unfused correctness
reference; both report HBM traffic through RunStats.

Modes reproduce the paper's §VI mechanisms:
  * "cuda-convnet": every layer CHWN (+ direct conv);
  * "cudnn":        every layer NCHW (+ im2col-MM conv);
  * "opt":          per-layer selection + fast transforms (ours/the paper's).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import CNNConfig
from repro.configs.paper_table1 import ConvLayer, PoolLayer
from repro.core import (FusedPlan, Thresholds, apply_transform,
                        assign_layouts, calibrate, paper_heuristic_layouts,
                        plan_fused)
from repro.core.selector import LayerDesc
from repro.perfmodel import CostModel, default_cost_model
from repro.cnn import layers as CL
from repro.dtypes import DEFAULT_DTYPE, INT8_DTYPE, canon_dtype, dtype_bytes
from repro.quant import (dequantize, fake_quant, fold_scale_into_weights,
                         quantize)
from repro.shapes import conv_out_hw, pool_out_hw


def network_descs(cfg: CNNConfig,
                  dtype: str = DEFAULT_DTYPE) -> List[LayerDesc]:
    """Selector LayerDescs for ``cfg`` at a storage ``dtype``: every desc
    carries the element size so the planner's byte models and sublane widths
    track the dtype the network will actually run in.  Graph configs
    (DESIGN.md §11) resolve their name-based ``inputs`` edges to layer
    indices here; linear configs emit descs with no explicit edges, so the
    planners take the original chain code path untouched."""
    db = dtype_bytes(dtype)
    descs = []
    rins = CL.resolved_cfg_inputs(cfg)
    shapes = CL.layer_shapes(cfg)
    in_shp = input_shape(cfg)
    for i, (spec, shp) in enumerate(zip(cfg.layers, shapes)):
        s0 = in_shp if rins[i][0] < 0 else shapes[rins[i][0]]
        # explicit edges only where they differ from the linear default —
        # keeps linear descs byte-identical to the pre-DAG planner's input
        lin = (i - 1,) if i else (-1,)
        ins = () if rins[i] == lin else rins[i]
        if spec.kind == "conv":
            conv = ConvLayer(spec.name, cfg.batch, spec.out_channels, s0[2],
                             spec.kernel, s0[1], spec.stride, cfg.name,
                             pad=spec.pad)
            descs.append(LayerDesc(spec.name, "conv", conv=conv,
                                   out_shape=shp, dtype_bytes=db,
                                   inputs=ins))
        elif spec.kind == "pool":
            pool = PoolLayer(spec.name, cfg.batch, s0[1], s0[2], spec.kernel,
                             spec.stride, cfg.name)
            descs.append(LayerDesc(spec.name, "pool", pool=pool,
                                   out_shape=shp, dtype_bytes=db,
                                   inputs=ins))
        else:
            # only ReLU may fold as a conv epilogue ("act"): reject unknown
            # kinds loudly rather than silently folding/skipping them
            if spec.kind not in ("relu", "fc", "softmax", "flatten",
                                 "add", "concat", "upsample"):
                raise ValueError(f"unsupported layer kind: {spec.kind!r}")
            kind = "act" if spec.kind == "relu" else spec.kind
            descs.append(LayerDesc(spec.name, kind, out_shape=shp,
                                   dtype_bytes=db, inputs=ins))
    return descs


def input_shape(cfg: CNNConfig) -> Tuple[int, int, int, int]:
    return (cfg.batch, cfg.in_channels, cfg.image_hw, cfg.image_hw)


def plan_network(cfg: CNNConfig, mode: str = "opt",
                 thresholds: Optional[Thresholds] = None,
                 use_dp: bool = True,
                 dtype: str = DEFAULT_DTYPE) -> List[str]:
    """Per-layer layout list, planned at the storage ``dtype``."""
    descs = network_descs(cfg, dtype)
    if mode == "cuda-convnet":
        return ["CHWN"] * len(descs)
    if mode == "cudnn":
        return ["NCHW"] * len(descs)
    if use_dp:
        return assign_layouts(descs, input_layout="NCHW",
                              input_shape=input_shape(cfg)).layouts
    th = thresholds or calibrate(dtype_bytes=dtype_bytes(dtype))
    return paper_heuristic_layouts(descs, th)


def plan_network_fused(cfg: CNNConfig, dtype: str = DEFAULT_DTYPE,
                       policy: str = "uniform",
                       stack_policy: str = "auto") -> FusedPlan:
    """Fused execution plan: layout DP with fold-aware edges + chain fusion.
    ``dtype`` is the storage dtype the network runs in — it scales every
    byte model and shifts the layout crossovers (sublane width doubles at
    2-byte elements), so bf16 plans can differ from fp32 plans.

    ``policy="mixed"`` (DESIGN.md §9) makes the DP search per-layer
    (layout, storage dtype) states: interior conv chains may store their
    output as int8 (quantize folded into the epilogue, dequantize into the
    consumer conv's VMEM read), while the host input, the first conv chain,
    and the classifier head stay at the base ``dtype``.

    ``stack_policy="auto"`` (DESIGN.md §12) additionally fuses profitable
    conv->conv stacks into single halo-recomputing kernels; ``"off"``
    reproduces the single-conv-node plans byte for byte."""
    return plan_fused(network_descs(cfg, dtype), input_layout="NCHW",
                      input_shape=input_shape(cfg), dtype_policy=policy,
                      base_dtype=dtype, stack_policy=stack_policy)


@dataclass
class RunStats:
    transforms: int = 0             # STANDALONE re-layout passes executed
    transform_bytes: int = 0        # HBM bytes those passes moved
    fused_ops: int = 0              # kernels that folded an epilogue/layout
    hbm_bytes: int = 0              # modeled forward HBM traffic of the run
    bwd_hbm_bytes: int = 0          # modeled backward traffic (training=True)

    @property
    def total_hbm_bytes(self) -> int:
        return self.hbm_bytes + self.bwd_hbm_bytes


def _nbytes(x) -> int:
    return x.size * x.dtype.itemsize


def _is_int8(dtype_name: str) -> bool:
    return bool(dtype_name) and canon_dtype(dtype_name) == INT8_DTYPE


def _stored_nbytes(x, dtype_name: str) -> int:
    """HBM bytes of ``x`` as STORED under the plan's declared dtype.  The
    training path carries int8 boundaries as straight-through floats, so the
    array's own itemsize over-prices what the serving engine stores; the
    declared int8 wins.  (Per-channel scale vectors — one f32 per channel —
    are negligible and not modeled; DESIGN.md §9.)"""
    if _is_int8(dtype_name):
        return x.size
    return _nbytes(x)


def _channel_axis(layout: str) -> int:
    return 0 if layout == "CHWN" else 1


def _spatial(x, layout: str) -> int:
    return x.shape[2] if layout == "NCHW" else x.shape[1]


def _channels(x, layout: str) -> int:
    return x.shape[1] if layout == "NCHW" else x.shape[0]


def _conv_desc(spec, x, layout: str, batch: int, net: str) -> ConvLayer:
    """Reconstruct the cost-model ConvLayer from runtime shapes so the
    executor's backward accounting and ``core.heuristic`` agree exactly."""
    return ConvLayer(spec.name, batch, spec.out_channels, _spatial(x, layout),
                     spec.kernel, _channels(x, layout), spec.stride, net,
                     pad=spec.pad)


# Shared per-kind traffic accounting: both executors MUST price these layers
# identically or the fused-vs-seed savings become an artifact of the model.
def _acct(stats: "RunStats", fwd_b: int, bwd_b: int, training: bool):
    stats.hbm_bytes += fwd_b
    if training:
        stats.bwd_hbm_bytes += bwd_b


def _acct_eltwise(stats, x, training):
    """relu / softmax: fwd read+write; bwd read g + read mask/out + write."""
    _acct(stats, 2 * _nbytes(x), 3 * _nbytes(x), training)


def _acct_flatten(stats, x, cur_layout, training):
    b = 2 * _nbytes(x) if cur_layout == "CHWN" else 0
    _acct(stats, b, b, training)


def _acct_fc(stats, io_b, training):
    """bwd dx = g W^T, dW = x^T g, db: same traffic again."""
    _acct(stats, io_b, io_b, training)


def _acct_pool(stats, in_b, out_b, training):
    """bwd: read g + read input (max mask) + write dx."""
    _acct(stats, in_b + out_b, 2 * in_b + out_b, training)


def forward(params: Dict, x_nchw, cfg: CNNConfig, layouts: List[str],
            impl: str = "xla", interpret: Optional[bool] = None,
            use_pallas_transform: bool = False, training: bool = False,
            cost_model: Optional[CostModel] = None
            ) -> Tuple[jnp.ndarray, RunStats]:
    """Run the network unfused; x enters as NCHW (the host data layout).
    Returns (class probabilities [N, classes], stats).  ``training`` also
    accounts the XLA-decomposed backward pass in ``stats.bwd_hbm_bytes``
    (shape-only arithmetic — works under ``jax.eval_shape``).  RunStats byte
    accounting delegates to ``cost_model`` (DESIGN.md §13) so the executor
    and the planner price traffic through the same oracle."""
    cm = cost_model or default_cost_model()
    stats = RunStats()
    rins = CL.resolved_cfg_inputs(cfg)
    last_use: Dict[int, int] = {}
    for i, ins in enumerate(rins):
        for p in ins:
            last_use[p] = i
    # produced tensors by layer index (-1 = the network input); a write is
    # counted once at its producer, every consumer counts its own read
    outs: Dict[int, Tuple[jnp.ndarray, str]] = {-1: (x_nchw, "NCHW")}
    flat = False
    x = x_nchw

    def _retuned(t, t_lay, lay):
        """Re-layout ``t`` into ``lay``, counting the standalone pass."""
        if t_lay == lay:
            return t
        stats.transforms += 1
        stats.transform_bytes += 2 * _nbytes(t)
        stats.hbm_bytes += 2 * _nbytes(t)
        if training:                 # the gradient re-layouts back
            stats.bwd_hbm_bytes += 2 * _nbytes(t)
        return apply_transform(t, t_lay, lay,
                               use_pallas=use_pallas_transform,
                               interpret=interpret)

    for i, (spec, lay) in enumerate(zip(cfg.layers, layouts)):
        x, cur_layout = outs[rins[i][0]]
        if spec.kind in ("conv", "pool") and lay != cur_layout and not flat:
            # distinct layouts always mean a real (non-identity) re-layout,
            # so every pass counted here moves bytes
            x = _retuned(x, cur_layout, lay)
            cur_layout = lay
        if spec.kind == "conv":
            w = params[spec.name]["w"]
            in_b = _nbytes(x)
            if training:
                desc = _conv_desc(spec, x, cur_layout, cfg.batch, cfg.name)
                stats.bwd_hbm_bytes += cm.conv_backward_bytes(
                    desc, cur_layout, x.dtype.itemsize, fused=False)
            x = CL.conv_forward(x, w, cur_layout,
                                spec.stride, spec.pad, impl=impl,
                                interpret=interpret)
            stats.hbm_bytes += in_b + _nbytes(w) + _nbytes(x)
        elif spec.kind == "pool":
            in_b = _nbytes(x)
            x = CL.pool_forward(x, cur_layout, spec.kernel, spec.stride,
                                spec.pool_op, impl=impl, interpret=interpret)
            _acct_pool(stats, in_b, _nbytes(x), training)
        elif spec.kind == "relu":
            x = CL.relu_forward(x)
            _acct_eltwise(stats, x, training)
        elif spec.kind == "flatten":
            _acct_flatten(stats, x, cur_layout, training)
            x = CL.flatten_forward(x, cur_layout)
            flat = True
        elif spec.kind == "fc":
            p = params[spec.name]
            in_b = _nbytes(x)
            x = CL.fc_forward(x, p["w"], p["b"])
            _acct_fc(stats, in_b + _nbytes(p["w"]) + _nbytes(p["b"])
                     + _nbytes(x), training)
        elif spec.kind == "softmax":
            x = CL.softmax_forward(x, impl=impl, interpret=interpret)
            _acct_eltwise(stats, x, training)
        elif spec.kind == "add":
            b2, b_lay = outs[rins[i][1]]
            x = _retuned(x, cur_layout, lay) + _retuned(b2, b_lay, lay)
            cur_layout = lay
            # fwd: read both operands + write; bwd: pure gradient fan-out
            _acct(stats, 3 * _nbytes(x), 0, training)
        elif spec.kind == "concat":
            parts = [_retuned(x, cur_layout, lay)]
            parts += [_retuned(*outs[p], lay) for p in rins[i][1:]]
            x = CL.concat_forward(parts, lay)
            cur_layout = lay
            # fwd read+write; bwd: slice the gradient back per branch
            _acct(stats, 2 * _nbytes(x), 2 * _nbytes(x), training)
        elif spec.kind == "upsample":
            x = CL.upsample_forward(_retuned(x, cur_layout, lay), lay,
                                    spec.kernel)
            cur_layout = lay
            # priced like a stream copy at the OUTPUT size both ways
            _acct(stats, 2 * _nbytes(x), 2 * _nbytes(x), training)
        outs[i] = (x, cur_layout)
        for p in set(rins[i]):
            if last_use[p] == i:
                outs.pop(p, None)
    return x, stats


def forward_fused(params: Dict, x_nchw, cfg: CNNConfig, plan: FusedPlan,
                  impl: str = "pallas", interpret: Optional[bool] = None,
                  training: bool = False,
                  cost_model: Optional[CostModel] = None
                  ) -> Tuple[jnp.ndarray, RunStats]:
    """Run the network through the fused plan; x enters as NCHW.

    ``impl="pallas"`` executes each FusedOp as one kernel; ``impl="xla"``
    decomposes them (correctness reference).  RunStats uses the same traffic
    model as ``forward``, so the two are directly comparable.  ``training``
    accounts the custom-VJP backward (activation stash, one-kernel pool+mask
    backward, native dgrad/wgrad, folded re-layouts) in
    ``stats.bwd_hbm_bytes``.

    Mixed-dtype plans (DESIGN.md §9) store int8 boundaries between conv
    chains.  Inference carries REAL int8 tensors: the producing chain's
    output is quantized per channel, and the consuming conv folds the scale
    into its weights and dequantizes in VMEM (an exact rewrite — the scale
    factors out of the channel contraction).  Training keeps the carrier in
    the base float dtype with a straight-through quantize->dequantize at
    each boundary (same forward numerics the server stores, identity
    gradient), so ``make_train_step_fused`` stays differentiable; the byte
    model still prices those boundaries at 1 byte/element.
    """
    cm = cost_model or default_cost_model()
    stats = RunStats()
    # Graph plans (DESIGN.md §11) address tensors by PRODUCER layer index
    # (op.inputs / op.out_index); legacy linear plans carry no edges and
    # chain through the previous op's output.  Tensors are refcounted so a
    # branch buffer lives exactly until its last consumer (and its write is
    # counted once, at the producer).
    nref: Dict[int, int] = {}
    for op in plan.ops:
        for p in op.inputs:
            nref[p] = nref.get(p, 0) + 1
        if op.res_index is not None:
            nref[op.res_index] = nref.get(op.res_index, 0) + 1
    # producer index -> (tensor, layout, per-channel int8 scale or None)
    outs: Dict[int, Tuple[jnp.ndarray, str, Optional[jnp.ndarray]]] = {
        -1: (x_nchw, "NCHW", None)}
    prev_key = -1
    x = x_nchw

    def take(p: int):
        t, t_lay, qs = outs[p]
        left = nref.get(p, 1) - 1    # legacy plans: single consumer
        nref[p] = left
        if left <= 0:
            outs.pop(p, None)
        return t, t_lay, qs

    def _retuned(t, t_lay, lay):
        """Standalone re-layout (no kernel absorbed it), with accounting."""
        if t_lay == lay:
            return t
        stats.transforms += 1
        stats.transform_bytes += 2 * _nbytes(t)
        stats.hbm_bytes += 2 * _nbytes(t)
        if training:
            stats.bwd_hbm_bytes += 2 * _nbytes(t)
        return apply_transform(t, t_lay, lay, interpret=interpret)

    for op in plan.ops:
        spec = cfg.layers[op.index]
        x, cur, qscale = take(op.inputs[0] if op.inputs else prev_key)
        out_q = None                 # per-channel scale of an int8 output
        if op.kind != "conv" and x.dtype == jnp.int8:
            # defensive: plans never route int8 into non-conv ops, but a
            # hand-built plan must not silently feed int8 to float kernels
            x = dequantize(x, qscale, _channel_axis(cur),
                           jnp.dtype(plan.base_dtype or "float32"))
            qscale = None
        if op.kind == "conv" and op.stack_index is not None:
            # Cross-layer stack (DESIGN.md §12): ``op.index`` is conv1 and
            # ``op.stack_index`` conv2; the mid activation between them is
            # staged in VMEM and NEVER touches HBM, so the byte model below
            # charges input + both weights + final output only.
            spec2 = cfg.layers[op.stack_index]
            p1, p2 = params[spec.name], params[spec2.name]
            pool = None
            if op.pool_index is not None:
                ps = cfg.layers[op.pool_index]
                pool = (ps.kernel, ps.stride, ps.pool_op)
            res = res_lay = None
            if op.res_index is not None:   # residual folds into conv2
                res, res_lay, _ = take(op.res_index)
                stats.hbm_bytes += _nbytes(res)
            in_b = _stored_nbytes(x, op.src_dtype)
            d1 = _conv_desc(spec, x, cur, cfg.batch, cfg.name)
            d2 = ConvLayer(spec2.name, cfg.batch, spec2.out_channels,
                           d1.out_hw, spec2.kernel, spec.out_channels,
                           spec2.stride, cfg.name, pad=spec2.pad)
            # the planner only emits stacks its VMEM bound admits; recompute
            # the same N tile here so executor and cost model agree
            nt = cm.stack_nt(d1, d2, op.layout, x.dtype.itemsize,
                             pool=pool[:2] if pool else None,
                             residual=res is not None) or 1
            if training:
                # stacks are inference-only plans; a training run over one
                # replays the unfused composition, so price both convs plus
                # the rematerialized mid round trip.
                mid_b = (cfg.batch * spec.out_channels * d1.out_hw ** 2
                         * x.dtype.itemsize)
                stats.bwd_hbm_bytes += (
                    cm.conv_backward_bytes(d1, op.layout, x.dtype.itemsize,
                                           relu=op.stack_relu, fused=True)
                    + cm.conv_backward_bytes(d2, op.layout,
                                             x.dtype.itemsize, relu=op.relu,
                                             pool=pool[:2] if pool else None,
                                             fused=True,
                                             residual=res is not None)
                    + 2 * mid_b)
            x = CL.fused_conv_stack(x, p1["w"], p2["w"], op.layout,
                                    spec.stride, spec.pad, spec2.stride,
                                    spec2.pad, relu1=op.stack_relu,
                                    relu2=op.relu, pool=pool, res=res,
                                    res_layout=res_lay, src_layout=cur,
                                    dst_layout=op.dst_layout, nt=nt,
                                    impl=impl, interpret=interpret)
            stats.hbm_bytes += (in_b + _nbytes(p1["w"]) + _nbytes(p2["w"])
                                + _stored_nbytes(x, op.dst_dtype))
            stats.fused_ops += 1
            cur = op.dst_layout
        elif op.kind == "conv":
            p = params[spec.name]
            pool = None
            if op.pool_index is not None:
                ps = cfg.layers[op.pool_index]
                pool = (ps.kernel, ps.stride, ps.pool_op)
            res = res_lay = None
            if op.res_index is not None:   # folded residual add: the skip
                res, res_lay, _ = take(op.res_index)
                stats.hbm_bytes += _nbytes(res)   # epilogue's second read
            in_b = _stored_nbytes(x, op.src_dtype)
            if training:
                desc = _conv_desc(spec, x, cur, cfg.batch, cfg.name)
                stats.bwd_hbm_bytes += cm.conv_backward_bytes(
                    desc, op.layout, x.dtype.itemsize, relu=op.relu,
                    pool=pool[:2] if pool else None, bias="b" in p,
                    fused=True, residual=res is not None)
            w = p["w"]
            if x.dtype == jnp.int8:  # dequant folds into the weights
                w = fold_scale_into_weights(w, qscale)
                qscale = None
            x = CL.fused_conv_block(x, w, op.layout, spec.stride,
                                    spec.pad, bias=p.get("b"), relu=op.relu,
                                    pool=pool, res=res, res_layout=res_lay,
                                    src_layout=cur, dst_layout=op.dst_layout,
                                    impl=impl, interpret=interpret)
            if _is_int8(op.dst_dtype):   # epilogue storage cast
                if training:             # straight-through float carrier
                    x = fake_quant(x, _channel_axis(op.dst_layout))
                else:                    # real int8 storage
                    x, out_q = quantize(x, _channel_axis(op.dst_layout))
            stats.hbm_bytes += (in_b + _nbytes(p["w"]) +
                                _stored_nbytes(x, op.dst_dtype))
            if "b" in p:
                stats.hbm_bytes += _nbytes(p["b"])
            if op.is_fused:          # folded an epilogue or a re-layout
                stats.fused_ops += 1
            cur = op.dst_layout
        elif op.kind == "pool":
            x = _retuned(x, cur, op.layout)   # no producer absorbed it
            cur = op.layout
            in_b = _nbytes(x)
            x = CL.pool_forward(x, cur, spec.kernel, spec.stride,
                                spec.pool_op, impl=impl, interpret=interpret,
                                dst_layout=op.dst_layout)
            _acct_pool(stats, in_b, _nbytes(x), training)
            if op.dst_layout != op.layout:
                stats.fused_ops += 1
            cur = op.dst_layout
        elif spec.kind == "relu":    # un-folded act (post-flatten)
            x = CL.relu_forward(x)
            _acct_eltwise(stats, x, training)
        elif op.kind == "flatten":
            _acct_flatten(stats, x, cur, training)
            x = CL.flatten_forward(x, cur)
        elif op.kind == "fc":
            p = params[spec.name]
            in_b = _nbytes(x)
            x = CL.fc_forward(x, p["w"], p["b"])
            _acct_fc(stats, in_b + _nbytes(p["w"]) + _nbytes(p["b"])
                     + _nbytes(x), training)
        elif op.kind == "softmax":
            x = CL.softmax_forward(x, impl=impl, interpret=interpret)
            _acct_eltwise(stats, x, training)
        elif op.kind == "add":       # standalone residual add (un-folded)
            b2, b_lay, _ = take(op.inputs[1])
            x = _retuned(x, cur, op.layout) + _retuned(b2, b_lay, op.layout)
            cur = op.layout
            # fwd: read both operands + write; bwd: pure gradient fan-out
            _acct(stats, 3 * _nbytes(x), 0, training)
        elif op.kind == "concat":
            parts = [_retuned(x, cur, op.layout)]
            parts += [_retuned(*take(p)[:2], op.layout)
                      for p in op.inputs[1:]]
            x = CL.concat_forward(parts, op.layout)
            cur = op.layout
            _acct(stats, 2 * _nbytes(x), 2 * _nbytes(x), training)
        elif op.kind == "upsample":
            x = CL.upsample_forward(_retuned(x, cur, op.layout), op.layout,
                                    spec.kernel)
            cur = op.layout
            _acct(stats, 2 * _nbytes(x), 2 * _nbytes(x), training)
        prev_key = op.out_index if op.out_index >= 0 else op.index
        outs[prev_key] = (x, cur, out_q)
    return x, stats


def batch_output_ok(y) -> jnp.ndarray:
    """Cheap finite-check hook on the batch output (DESIGN.md §14): one
    fused all-finite reduction over the class probabilities — a scalar bool
    the guarded serving path folds into the jitted forward, so detecting a
    poisoned batch (int8 saturation, a bad kernel, injected NaN/Inf) costs
    one [N, classes] pass, negligible next to the conv stack.  The cast
    keeps the reduction exact for bf16/f32 outputs alike."""
    return jnp.all(jnp.isfinite(y.astype(jnp.float32)))


def loss_fn(params, x_nchw, labels, cfg: CNNConfig, layouts: List[str]):
    """Differentiable NLL (training uses the xla engine)."""
    probs, _ = forward(params, x_nchw, cfg, layouts, impl="xla")
    logp = jnp.log(jnp.clip(probs.astype(jnp.float32), 1e-20))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    return nll


def make_train_step(cfg: CNNConfig, layouts: List[str], lr: float = 0.01,
                    momentum: float = 0.9):
    grad_fn = jax.value_and_grad(
        lambda p, x, y: loss_fn(p, x, y, cfg, layouts))

    @jax.jit
    def step(params, vel, x, y):
        loss, grads = grad_fn(params, x, y)
        new_vel = jax.tree.map(lambda v, g: momentum * v - lr * g, vel, grads)
        new_params = jax.tree.map(lambda p, v: p + v, params, new_vel)
        return new_params, new_vel, loss

    return step


def loss_fn_fused(params, x_nchw, labels, cfg: CNNConfig, plan: FusedPlan,
                  impl: str = "pallas", interpret: Optional[bool] = None):
    """Differentiable NLL over the FUSED engine: the forward runs the fused
    Pallas kernels and the backward flows through their custom VJPs
    (layout-aware dgrad/wgrad, one-kernel pool+mask backward)."""
    probs, _ = forward_fused(params, x_nchw, cfg, plan, impl=impl,
                             interpret=interpret)
    logp = jnp.log(jnp.clip(probs.astype(jnp.float32), 1e-20))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    return nll


def make_train_step_fused(cfg: CNNConfig, plan: FusedPlan, lr: float = 0.01,
                          momentum: float = 0.9, impl: str = "pallas",
                          interpret: Optional[bool] = None):
    """SGD+momentum step over the fused training engine — the layout-aware
    twin of ``make_train_step`` (which autodiffs the unfused XLA forward)."""
    grad_fn = jax.value_and_grad(
        lambda p, x, y: loss_fn_fused(p, x, y, cfg, plan, impl, interpret))

    @jax.jit
    def step(params, vel, x, y):
        loss, grads = grad_fn(params, x, y)
        new_vel = jax.tree.map(lambda v, g: momentum * v - lr * g, vel, grads)
        new_params = jax.tree.map(lambda p, v: p + v, params, new_vel)
        return new_params, new_vel, loss

    return step


def init_velocity(params):
    return jax.tree.map(jnp.zeros_like, params)
