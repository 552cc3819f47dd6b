"""Layout-polymorphic CNN layers (the paper's substrate).

Every op executes *natively in its assigned layout* — no hidden transposes.
``impl`` selects the engine:
  * "xla"    — lax convolution/reduce_window with layout-matching
               dimension_numbers (differentiable; used for training);
  * "pallas" — the Pallas kernels (direct-CHWN conv, im2col+MXU matmul for
               NCHW, window-reuse pooling, fused softmax) — the paper's
               optimized inference engines, validated in interpret mode;
  * "fft"    — frequency-domain conv (NCHW; the cuDNN-FFT analogue).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import CNNConfig, ConvSpec
from repro.shapes import conv_out_hw, pool_out_hw

# dimension_numbers per layout: (lhs, rhs, out)
_DIMNUMS = {
    "NCHW": ("NCHW", "OIHW", "NCHW"),
    "CHWN": ("CHWN", "IHWO", "CHWN"),
    "NHWC": ("NHWC", "HWIO", "NHWC"),
}


def conv_forward(x, w, layout: str, stride: int = 1, pad: int = 0,
                 impl: str = "xla", interpret: Optional[bool] = None):
    """x in ``layout``; w canonical [Co, Ci, F, F].

    int8 ``x`` (mixed-dtype storage, DESIGN.md §9) is consumed natively by
    the Pallas engines (cast to f32 in VMEM; the caller folded the
    per-channel dequant scale into ``w``, so weights keep their float dtype
    and the result comes out in it).  The XLA reference path dequantizes by
    casting up front — same arithmetic, without the 1-byte HBM read.
    """
    cdt = w.dtype if x.dtype == jnp.int8 else x.dtype  # compute/out dtype
    if impl == "xla":
        lhs, rhs, out = _DIMNUMS[layout]
        if rhs == "IHWO":
            wr = jnp.transpose(w, (1, 2, 3, 0))     # [Ci,F,F,Co]
        elif rhs == "HWIO":
            wr = jnp.transpose(w, (2, 3, 1, 0))
        else:
            wr = w
        return lax.conv_general_dilated(
            x.astype(cdt), wr.astype(cdt), (stride, stride),
            [(pad, pad), (pad, pad)], dimension_numbers=(lhs, rhs, out),
            precision=_precision(cdt),
            preferred_element_type=jnp.float32).astype(cdt)
    if impl == "pallas":
        from repro.kernels.conv.ops import conv_fused
        return conv_fused(x, w.astype(cdt), stride=stride, pad=pad,
                          engine=layout, interpret=interpret)
    if impl == "fft":
        assert layout == "NCHW", "FFT conv is bound to NCHW (paper §IV.A)"
        from repro.kernels.conv.ops import conv_fft_nchw
        return conv_fft_nchw(x.astype(cdt), w.astype(cdt), stride=stride,
                             pad=pad)
    raise ValueError(impl)


def pool_forward(x, layout: str, F: int, S: int, op: str = "max",
                 impl: str = "xla", interpret: Optional[bool] = None,
                 dst_layout: Optional[str] = None):
    dst = dst_layout or layout
    if impl == "pallas":
        from repro.kernels.pool.ops import pool_fused
        return pool_fused(x, F, S, op, layout=layout, dst_layout=dst,
                          interpret=interpret)
    from repro.kernels.pool.ref import pool_ref
    y = pool_ref(x, F, S, op, layout)
    if dst != layout:
        from repro.core.transform import apply_transform
        y = apply_transform(y, layout, dst)
    return y


def fused_conv_block(x, w, layout: str, stride: int = 1, pad: int = 0, *,
                     bias=None, relu: bool = False,
                     pool: Optional[Tuple[int, int, str]] = None,
                     res=None, res_layout: Optional[str] = None,
                     src_layout: Optional[str] = None,
                     dst_layout: Optional[str] = None,
                     impl: str = "pallas", interpret: Optional[bool] = None):
    """One fused-engine node: conv[+bias][+residual add][+relu][+pool]
    executed natively in ``layout``, consuming ``src_layout`` input and
    producing ``dst_layout`` output.  ``res`` is the skip tensor of a folded
    residual add (stored in ``res_layout``): it is added onto the conv
    accumulator BEFORE the ReLU, matching the ResNet epilogue order.
    ``impl="pallas"`` runs it as ONE kernel (the chain intermediate never
    leaves VMEM; the skip is read through a second, layout-folding
    BlockSpec); ``impl="xla"`` is the decomposed reference."""
    src = src_layout or layout
    dst = dst_layout or layout
    cdt = w.dtype if x.dtype == jnp.int8 else x.dtype  # compute/out dtype
    if impl == "pallas":
        from repro.kernels.conv.ops import conv_fused
        return conv_fused(x, w.astype(cdt), stride=stride, pad=pad,
                          engine=layout, interpret=interpret, bias=bias,
                          relu=relu, pool=pool, res=res,
                          res_layout=res_layout or layout,
                          src_layout=src, dst_layout=dst)
    from repro.core.transform import apply_transform
    y = apply_transform(x.astype(cdt), src, layout)
    y = conv_forward(y, w, layout, stride, pad, impl="xla")
    if bias is not None:
        b = bias.astype(y.dtype)
        y = y + (b[:, None, None, None] if layout == "CHWN"
                 else b[None, :, None, None])
    if res is not None:
        y = y + apply_transform(res.astype(y.dtype),
                                res_layout or layout, layout)
    if relu:
        y = jax.nn.relu(y)
    if pool is not None:
        y = pool_forward(y, layout, pool[0], pool[1], pool[2], impl="xla")
    return apply_transform(y, layout, dst)


def fused_conv_stack(x, w1, w2, layout: str, stride1: int = 1, pad1: int = 0,
                     stride2: int = 1, pad2: int = 0, *,
                     relu1: bool = False, relu2: bool = False,
                     pool: Optional[Tuple[int, int, str]] = None,
                     res=None, res_layout: Optional[str] = None,
                     src_layout: Optional[str] = None,
                     dst_layout: Optional[str] = None, nt: int = 8,
                     impl: str = "pallas", interpret: Optional[bool] = None):
    """Cross-layer stack node (DESIGN.md §12): conv1[+relu]->conv2[+residual
    add][+relu][+pool] executed natively in ``layout`` as ONE kernel — the
    intermediate activation between the convs is staged in VMEM and never
    written to HBM.  ``w1``/``w2`` are canonical [Co, Ci, F, F]; ``nt`` is
    the N tile the planner's VMEM bound admitted (``heuristic.stack_nt``).
    ``impl="xla"`` decomposes into two conv blocks (correctness reference);
    both paths are differentiable (the Pallas stack's custom VJP replays the
    unfused composition)."""
    src = src_layout or layout
    dst = dst_layout or layout
    if impl == "pallas":
        from repro.kernels.conv.ops import conv_stack
        return conv_stack(x, w1, w2, stride1, pad1, stride2, pad2,
                          engine=layout, nt=nt, interpret=interpret,
                          relu1=relu1, relu2=relu2, pool=pool, res=res,
                          res_layout=res_layout or layout,
                          src_layout=src, dst_layout=dst)
    y = fused_conv_block(x, w1, layout, stride1, pad1, relu=relu1,
                         src_layout=src, impl="xla")
    return fused_conv_block(y, w2, layout, stride2, pad2, relu=relu2,
                            pool=pool, res=res, res_layout=res_layout,
                            dst_layout=dst, impl="xla")


def flatten_forward(x, layout: str):
    """-> [N, features] regardless of layout."""
    if layout == "CHWN":
        C, H, W, N = x.shape
        return x.reshape(C * H * W, N).T
    N = x.shape[0]
    return x.reshape(N, -1)


def _precision(dtype):
    """fp32 operands multiply at full f32 precision (on a TPU the default
    is one bf16 MXU pass); narrower dtypes take the default."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def fc_forward(x2d, w, b):
    """y = xW + b with f32 MXU accumulation, emitted in the storage dtype
    (the cuDNN mixed-precision recipe: narrow storage, wide accumulate)."""
    y = jnp.dot(x2d, w, preferred_element_type=jnp.float32,
                precision=_precision(jnp.result_type(x2d, w)))
    return (y + b.astype(jnp.float32)).astype(x2d.dtype)


def softmax_forward(x2d, impl: str = "xla", interpret: Optional[bool] = None):
    if impl == "pallas":
        from repro.kernels.softmax.ops import softmax as softmax_fused
        return softmax_fused(x2d, interpret=interpret)
    return jax.nn.softmax(x2d.astype(jnp.float32), axis=-1).astype(x2d.dtype)


def relu_forward(x):
    return jax.nn.relu(x)


def concat_forward(xs: Sequence, layout: str):
    """Channel concat of the merge inputs (U-Net skip join)."""
    return jnp.concatenate(list(xs), axis=0 if layout == "CHWN" else 1)


def upsample_forward(x, layout: str, factor: int):
    """Nearest-neighbour spatial x``factor`` (the U-Net decoder expand)."""
    ha, wa = (1, 2) if layout == "CHWN" else (2, 3)
    return jnp.repeat(jnp.repeat(x, factor, axis=ha), factor, axis=wa)


# ---------------------------------------------------------------------------
# parameter init + shape propagation (graph-aware, DESIGN.md §11)
# ---------------------------------------------------------------------------

def resolved_cfg_inputs(cfg: CNNConfig) -> List[Tuple[int, ...]]:
    """Per-layer producer INDICES from the config's name-based ``inputs``
    edges (-1 is the network input; empty means "the previous layer").
    Every graph consumer resolves edges through this one function, so the
    planner and the executors can never disagree on the topology."""
    idx = {spec.name: i for i, spec in enumerate(cfg.layers)}
    rins: List[Tuple[int, ...]] = []
    for i, spec in enumerate(cfg.layers):
        if spec.inputs:
            try:
                ins = tuple(idx[nm] for nm in spec.inputs)
            except KeyError as e:
                raise ValueError(
                    f"layer {spec.name!r}: unknown input layer {e.args[0]!r}")
            for p in ins:
                if p >= i:
                    raise ValueError(
                        f"layer {spec.name!r}: input {cfg.layers[p].name!r} "
                        "is not an earlier layer (layers must be "
                        "topologically ordered)")
        else:
            ins = (i - 1,) if i else (-1,)
        rins.append(ins)
    return rins


def layer_shapes(cfg: CNNConfig):
    """Logical NCHW output shape after each layer (for the selector),
    propagated along the graph edges; merge nodes validate that their
    branches meet at consistent shapes."""
    rins = resolved_cfg_inputs(cfg)
    in_shape = (cfg.batch, cfg.in_channels, cfg.image_hw, cfg.image_hw)
    out: List[Tuple[int, ...]] = []

    def shp(p: int) -> Tuple[int, ...]:
        return in_shape if p < 0 else out[p]

    for i, spec in enumerate(cfg.layers):
        s0 = shp(rins[i][0])
        if spec.kind == "conv":
            hw = conv_out_hw(s0[2], spec.kernel, spec.stride, spec.pad)
            out.append((cfg.batch, spec.out_channels, hw, hw))
        elif spec.kind == "pool":
            hw = pool_out_hw(s0[2], spec.kernel, spec.stride)
            out.append((s0[0], s0[1], hw, hw))
        elif spec.kind == "flatten":
            out.append((s0[0], int(math.prod(s0[1:]))))
        elif spec.kind == "fc":
            out.append((cfg.batch, spec.fc_out))
        elif spec.kind == "add":
            shs = [shp(p) for p in rins[i]]
            if any(s != shs[0] for s in shs):
                raise ValueError(f"{spec.name}: add operands disagree "
                                 f"({shs})")
            out.append(shs[0])
        elif spec.kind == "concat":
            shs = [shp(p) for p in rins[i]]
            if any(s[0] != shs[0][0] or s[2:] != shs[0][2:] for s in shs):
                raise ValueError(f"{spec.name}: concat operands disagree "
                                 f"on batch/spatial dims ({shs})")
            out.append((shs[0][0], sum(s[1] for s in shs)) + shs[0][2:])
        elif spec.kind == "upsample":
            f = spec.kernel
            out.append((s0[0], s0[1], s0[2] * f, s0[3] * f))
        else:                            # act/softmax inherit their input
            out.append(s0)
    return out


def init_cnn(key, cfg: CNNConfig, dtype=jnp.float32) -> Dict:
    params = {}
    rins = resolved_cfg_inputs(cfg)
    shapes = layer_shapes(cfg)

    def in_dim(i: int) -> int:           # channels (4-D) or features (2-D)
        p = rins[i][0]
        return cfg.in_channels if p < 0 else shapes[p][1]

    for i, spec in enumerate(cfg.layers):
        key, sub = jax.random.split(key)
        if spec.kind == "conv":
            ci = in_dim(i)
            std = 1.0 / math.sqrt(ci * spec.kernel * spec.kernel)
            params[spec.name] = {
                "w": jax.random.normal(
                    sub, (spec.out_channels, ci, spec.kernel, spec.kernel),
                    dtype) * std,
            }
        elif spec.kind == "fc":
            feat = in_dim(i)
            std = 1.0 / math.sqrt(feat)
            params[spec.name] = {
                "w": jax.random.normal(sub, (feat, spec.fc_out), dtype) * std,
                "b": jnp.zeros((spec.fc_out,), dtype),
            }
    return params
