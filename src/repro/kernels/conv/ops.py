"""Conv wrappers: direct-CHWN Pallas kernel + im2col/matmul NCHW paths + FFT.

These are the paper's three convolution implementations, each bound to its
preferred layout (§II.B, §IV.A):
  * direct  (CHWN)  — cuda-convnet analogue;
  * im2col + MXU matmul (NCHW) — Caffe/cuDNN analogue.  Two forms: the
    fused Pallas engine and the seed's XLA-expansion + Pallas-matmul
    baseline (``conv_im2col_nchw``, kept for comparison);
  * FFT (NCHW) — cuDNN-FFT analogue (jnp.fft; XLA).

The CHWN and NCHW Pallas engines are one entry point, ``conv_fused(...,
engine=)`` (``conv_direct_chwn``/``conv_im2col_nchw_fused`` are its
engine-bound spellings), and run the one kernel of ``kernels/conv/conv.py``:
CHWN interleaves up to ``flat.CHWN_NT`` samples per slab, NCHW runs one.
It speaks the fused-epilogue protocol (DESIGN.md §5): ``bias``/``relu``/
``pool`` fold elementwise and pooling work into the conv's output write,
and ``src_layout``/``dst_layout`` fold the neighbouring layers' layouts
into the one XLA copy that feeds the kernel its flat slabs
(``kernels/flat.py``) and the one that stores its result, so no standalone
re-layout pass is needed.  ``conv_stack`` is the same for conv->conv
stacks.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flat, resolve_interpret
from repro.kernels.conv.conv import Epilogue, conv_pallas, pool_tiles_block
from repro.kernels.conv.ref import im2col_nchw
from repro.kernels.conv.stack import conv_stack_pallas, stack_lanes
from repro.kernels.matmul.ops import matmul
from repro.shapes import conv_out_hw


def pick_bho(Ho: int, F: int, S: int,
             pool: Optional[Tuple[int, int, str]] = None) -> int:
    """Smallest output-row block: the halo trick needs 2*bho*S to cover one
    window span, and a fused pool additionally needs its windows to tile the
    block (falling back to one whole-height block, which always tiles)."""
    min_bho = max(1, -(-(F - S) // S))
    cands = [d for d in range(1, Ho + 1) if Ho % d == 0 and d >= min_bho]
    if pool is not None:
        pF, pS, _ = pool
        cands = [d for d in cands if pool_tiles_block(d, Ho // d, pF, pS)]
        if not cands:
            return Ho
    return min(cands) if cands else Ho


def conv_blocking(Ho: int, F: int, S: int,
                  pool: Optional[Tuple[int, int, str]] = None):
    """Row blocking of the planner's conv cost model and of wgrad:
    (output row block, input row block, row-block count).  The halo trick
    needs the two stitched input blocks to cover one window span, so when
    the whole-height fallback gives bho below that bound the input block is
    widened: IBH = max(bho*S, ceil(((bho-1)*S + F)/2))."""
    bho = pick_bho(Ho, F, S, pool)
    IBH = max(bho * S, -(-((bho - 1) * S + F) // 2))
    return bho, IBH, Ho // bho


def stack_blocking(Ho2: int, F1: int, S1: int, F2: int, S2: int,
                   pool: Optional[Tuple[int, int, str]] = None):
    """Row blocking for a fused conv->conv stack (DESIGN.md §12): the stack
    is blocked as ONE virtual conv with the composite receptive field

        S_eff = S1*S2,  F_eff = (F2-1)*S1 + F1

    so ``conv_blocking`` gives (bho, IBH, n_ho) over the SECOND conv's
    output rows, and the halo-stitch invariant 2*IBH >= (bho-1)*S_eff +
    F_eff is exactly the input span that ``mho = (bho-1)*S2 + F2`` staged
    mid rows (conv1 outputs) need.  Returns (bho, IBH, n_ho, mho)."""
    S_eff, F_eff = S1 * S2, (F2 - 1) * S1 + F1
    bho, IBH, n_ho = conv_blocking(Ho2, F_eff, S_eff, pool)
    mho = (bho - 1) * S2 + F2
    assert 2 * IBH >= (mho - 1) * S1 + F1, (IBH, mho, S1, F1)
    return bho, IBH, n_ho, mho


def _conv_core(x, w, bias, res, stride, pad, nt, interpret, relu, pool,
               src_layout, dst_layout, res_layout, engine: str,
               save_act: bool = False):
    """Forward of ``conv_fused``: flat-slab prep (pad, stride removed by
    space-to-depth, re-layout from ``src_layout``), one fused kernel,
    re-layout to ``dst_layout``.  ``w`` is canonical [Co,Ci,F,F].  Returns
    (y, z) with z the pre-pool activation in the engine's layout when
    ``save_act``."""
    xn = flat.to_nchw(x, src_layout)
    N, _, H, W = xn.shape
    Co, _, F, _ = w.shape
    Ho = conv_out_hw(H + 2 * pad, F, stride)
    Wo = conv_out_hw(W + 2 * pad, F, stride)
    Fq = -(-F // stride)
    pitch = Wo + Fq - 1
    nt = flat.group_tile(N, engine, nt, (Ho + Fq) * pitch)
    cm = flat.sublane_multiple(x.dtype)
    cdt = w.dtype if x.dtype == jnp.int8 else jnp.result_type(x, w)
    _, rows = flat.conv_lanes(Ho, Fq, pitch, nt)
    xf = flat.prep(xn, "NCHW", pad=pad, stride=stride, rows=rows,
                   cols=pitch, nt=nt, cmult=cm)
    wt = flat.s2d_weights(w, stride, cm).astype(cdt)
    if res is not None:
        res = flat.prep(res, res_layout, pad=0, stride=1, rows=Ho, cols=Wo,
                        nt=nt, cmult=1)
    b2 = bias.reshape(-1, 1).astype(jnp.float32) if bias is not None else None
    ep = Epilogue(bias=bias is not None, relu=relu, pool=pool,
                  residual=res is not None)
    out = conv_pallas(xf, wt, F=Fq, pitch=pitch, nt=nt, Ho=Ho, Wo=Wo,
                      bias=b2, res=res, epilogue=ep, out_dtype=cdt,
                      save_act=save_act,
                      interpret=interpret)
    y, z = out if save_act else (out, None)
    PHo, PWo = flat.pool_geometry(Ho, Wo, pool)
    y = flat.unprep(y, N, Co, PHo, PWo, nt, dst_layout)
    if z is not None:
        z = flat.unprep(z, N, Co, Ho, Wo, nt, engine)
    return y, z


@partial(jax.custom_vjp, nondiff_argnums=tuple(range(4, 14)))
def _conv_vjp(x, w, bias, res, stride, pad, nt, interpret, relu, pool,
              src_layout, dst_layout, res_layout, engine):
    return _conv_core(x, w, bias, res, stride, pad, nt, interpret, relu, pool,
                      src_layout, dst_layout, res_layout, engine)[0]


def _conv_fwd(x, w, bias, res, stride, pad, nt, interpret, relu, pool,
              src_layout, dst_layout, res_layout, engine):
    y, z = _conv_core(x, w, bias, res, stride, pad, nt, interpret, relu, pool,
                      src_layout, dst_layout, res_layout, engine,
                      save_act=pool is not None)
    return y, (x, w, bias, res, y, z)


def _conv_bwd(stride, pad, nt, interpret, relu, pool, src_layout, dst_layout,
              res_layout, engine, prims, g):
    """VJP of ``conv_fused``.

    ``x``/``bias`` enter as the forward saw them, ``w`` canonical; ``g``
    arrives in ``dst_layout``.  The reversed re-layout chain folds into
    kernel I/O maps: pool backward consumes ``g`` in ``dst_layout`` directly
    and the dgrad engine writes dx straight in ``src_layout``.  Residual
    ``z`` (pre-pool post-relu activation, engine layout) was stashed by the
    forward kernel's ``save_act`` epilogue — no recompute pass.

    A folded skip add (``skip`` is not None) fans the gradient out: the
    post-relu-mask/pool-backward gradient IS d(skip) up to a re-layout,
    because the add sits right before the ReLU in the epilogue order.
    """
    from repro.kernels.conv.backward import bias_grad, conv_dgrad, conv_wgrad
    from repro.kernels.pool.backward import pool_backward
    interpret = resolve_interpret(interpret)
    x, w, bias, skip, y, z = prims
    F = w.shape[2]
    if src_layout == "NCHW":
        x_hw = (x.shape[2], x.shape[3])
    else:
        x_hw = (x.shape[1], x.shape[2])
    if pool is not None:
        # one kernel: route g through the max-mask/avg-scatter AND apply the
        # relu mask (z is in VMEM for the mask anyway)
        ga = pool_backward(z, g, pool[0], pool[1], pool[2], layout=engine,
                           g_layout=dst_layout, relu_mask=relu,
                           interpret=interpret)
        g_lay = engine
    else:
        ga = g * (y > 0).astype(g.dtype) if relu else g
        g_lay = dst_layout
    dx = conv_dgrad(ga, w, x_hw, stride, pad, layout=engine,
                    g_layout=g_lay, dst_layout=src_layout,
                    interpret=interpret)
    dw = conv_wgrad(x, ga, F, stride, pad, x_layout=src_layout,
                    g_layout=g_lay, interpret=interpret)
    db = None
    if bias is not None:
        db = bias_grad(ga, g_lay).astype(bias.dtype)
    dskip = None
    if skip is not None:
        from repro.core.transform import apply_transform
        dskip = apply_transform(ga, g_lay, res_layout).astype(skip.dtype)
    return dx.astype(x.dtype), dw.astype(w.dtype), db, dskip


_conv_vjp.defvjp(_conv_fwd, _conv_bwd)


@partial(jax.jit, static_argnames=("stride", "pad", "engine", "nt",
                                   "interpret", "relu", "pool", "src_layout",
                                   "dst_layout", "res_layout"))
def conv_fused(x, w, stride: int = 1, pad: int = 0, *, engine: str,
               nt: int = flat.CHWN_NT, interpret: Optional[bool] = None,
               bias=None, relu: bool = False,
               pool: Optional[Tuple[int, int, str]] = None,
               res=None, res_layout: Optional[str] = None,
               src_layout: Optional[str] = None,
               dst_layout: Optional[str] = None):
    """Fused conv node on the ``engine`` ("CHWN" or "NCHW") Pallas path: x
    in ``src_layout``, w canonical [Co,Ci,F,F] -> output in ``dst_layout``,
    with optional fused bias/residual-add/ReLU/pool epilogue (``res`` is the
    skip tensor, stored in ``res_layout``; the three layouts default to
    ``engine``).  The engines run one kernel and differ in samples per slab:
    CHWN interleaves up to ``nt`` (``flat.group_tile``), NCHW runs one.
    Differentiable: a custom VJP routes the backward pass through the
    layout-aware dgrad/wgrad Pallas engines and fans the gradient out to the
    skip branch when a residual is folded."""
    return _conv_vjp(x, w, bias, res, stride, pad, nt, interpret, relu, pool,
                     src_layout or engine, dst_layout or engine,
                     res_layout or engine, engine)


def conv_direct_chwn(x, w, stride: int = 1, pad: int = 0,
                     nt: int = flat.CHWN_NT,
                     interpret: Optional[bool] = None, **kw):
    """``conv_fused`` on the CHWN engine with its native weights: x
    [Ci,H,W,N], w [Ci,F,F,Co] -> [Co,Ho',Wo',N]."""
    return conv_fused(x, jnp.transpose(w, (3, 0, 1, 2)), stride, pad,
                      engine="CHWN", nt=nt, interpret=interpret, **kw)


def conv_im2col_nchw_fused(x, w, stride: int = 1, pad: int = 0,
                           interpret: Optional[bool] = None, **kw):
    """``conv_fused`` on the NCHW engine: x [N,Ci,H,W], w [Co,Ci,F,F] ->
    [N,Co,Ho',Wo']."""
    return conv_fused(x, w, stride, pad, engine="NCHW", interpret=interpret,
                      **kw)


# ---------------------------------------------------------------------------
# fused conv->conv stacks (DESIGN.md §12): the mid activation never leaves
# VMEM; conv1 runs on a halo-widened block, conv2's full epilogue applies
# ---------------------------------------------------------------------------

def _stack_core(x, w1, b1, w2, b2, res, stride1, pad1, stride2, pad2, nt,
                interpret, relu1, relu2, pool, src_layout, dst_layout,
                res_layout, engine):
    """Forward of ``conv_stack``: pads (conv1 padding + conv2 padding pulled
    to the input at stride1 scale), removes conv1's stride by
    space-to-depth, builds the mid validity mask, and dispatches the one
    stack kernel.  Weights are canonical [Co,Ci,F,F]."""
    Cm, _, F1, _ = w1.shape
    Co, _, F2, _ = w2.shape
    xn = flat.to_nchw(x, src_layout)
    N, _, H0, W0 = xn.shape
    Ho1 = conv_out_hw(H0 + 2 * pad1, F1, stride1)
    Wo1 = conv_out_hw(W0 + 2 * pad1, F1, stride1)
    Ho2 = conv_out_hw(Ho1 + 2 * pad2, F2, stride2)
    Wo2 = conv_out_hw(Wo1 + 2 * pad2, F2, stride2)
    F1q = -(-F1 // stride1)
    # mid columns conv2's stride-1 wide pass reads, plus conv1's taps
    pitch = (Wo2 - 1) * stride2 + F2 + F1q - 1
    nt = flat.group_tile(N, engine, nt,
                         ((Ho2 - 1) * stride2 + F2 + F1q) * pitch)
    cm = flat.sublane_multiple(x.dtype)
    cdt = w1.dtype if x.dtype == jnp.int8 else jnp.result_type(x, w1)
    Lm, _, rows = stack_lanes(Ho2, stride2, F1q, F2, pitch, nt)
    xf = flat.prep(xn, "NCHW", pad=pad1 + stride1 * pad2, stride=stride1,
                   rows=rows, cols=pitch, nt=nt, cmult=cm)
    Cmp = flat.ceil_to(Cm, 8)            # zero mid channels stay zero
    w1t = flat.s2d_weights(jnp.pad(w1, ((0, Cmp - Cm),) + ((0, 0),) * 3),
                           stride1, cm).astype(cdt)
    w2t = flat.s2d_weights(jnp.pad(w2, ((0, 0), (0, Cmp - Cm), (0, 0),
                                        (0, 0))), 1, 1).astype(cdt)
    b1v = jnp.zeros((Cm,), jnp.float32) if b1 is None else b1
    b1v = jnp.pad(b1v.astype(jnp.float32), (0, Cmp - Cm)).reshape(-1, 1)
    b2v = b2.reshape(-1, 1).astype(jnp.float32) if b2 is not None else None
    lane = np.arange(Lm)
    r, c = lane // (pitch * nt), (lane // nt) % pitch
    keep = (r >= pad2) & (r < pad2 + Ho1) & (c >= pad2) & (c < pad2 + Wo1)
    mask = jnp.asarray(keep.astype(np.float32)[None])
    if res is not None:
        res = flat.prep(res, res_layout, pad=0, stride=1, rows=Ho2, cols=Wo2,
                        nt=nt, cmult=1)
    ep = Epilogue(bias=b2 is not None, relu=relu2, pool=pool,
                  residual=res is not None)
    y = conv_stack_pallas(xf, w1t, b1v, mask, w2t, F1=F1q, F2=F2, S2=stride2,
                          pitch=pitch, nt=nt, Ho2=Ho2, Wo2=Wo2,
                          relu1=relu1, bias2=b2v, res=res, epilogue=ep,
                          out_dtype=cdt,
                          interpret=interpret)
    PHo, PWo = flat.pool_geometry(Ho2, Wo2, pool)
    return flat.unprep(y, N, Co, PHo, PWo, nt, dst_layout)


@partial(jax.custom_vjp, nondiff_argnums=tuple(range(6, 19)))
def _stack_vjp(x, w1, b1, w2, b2, res, stride1, pad1, stride2, pad2, nt,
               interpret, relu1, relu2, pool, src_layout, dst_layout,
               res_layout, engine):
    return _stack_core(x, w1, b1, w2, b2, res, stride1, pad1, stride2, pad2,
                       nt, interpret, relu1, relu2, pool, src_layout,
                       dst_layout, res_layout, engine)


def _stack_fwd(x, w1, b1, w2, b2, res, *static):
    return (_stack_core(x, w1, b1, w2, b2, res, *static),
            (x, w1, b1, w2, b2, res))


def _stack_bwd(stride1, pad1, stride2, pad2, nt, interpret, relu1, relu2,
               pool, src_layout, dst_layout, res_layout, engine, prims, g):
    """Stack backward = VJP of the UNFUSED two-conv composition: y1 is
    recomputed with one fused conv1 call (gradient-checkpoint style) and the
    gradient then flows through ``conv_fused``'s layout-aware custom VJP
    (Pallas dgrad/wgrad/pool-backward) — fused-forward memory wins,
    unfused-backward correctness (DESIGN.md §12)."""
    x, w1, b1, w2, b2, res = prims
    kw1 = dict(stride=stride1, pad=pad1, engine=engine, nt=nt,
               interpret=interpret, relu=relu1, src_layout=src_layout,
               dst_layout=engine)
    kw2 = dict(stride=stride2, pad=pad2, engine=engine, nt=nt,
               interpret=interpret, relu=relu2, pool=pool,
               res_layout=res_layout, src_layout=engine,
               dst_layout=dst_layout)

    diff = {"x": x, "w1": w1, "w2": w2}
    for k, v in (("b1", b1), ("b2", b2), ("res", res)):
        if v is not None:
            diff[k] = v

    def unfused(d):
        y1 = conv_fused(d["x"], d["w1"], bias=d.get("b1"), **kw1)
        return conv_fused(y1, d["w2"], bias=d.get("b2"), res=d.get("res"),
                          **kw2)

    _, vjp = jax.vjp(unfused, diff)
    (gd,) = vjp(g)
    return (gd["x"], gd["w1"], gd.get("b1"), gd["w2"], gd.get("b2"),
            gd.get("res"))


_stack_vjp.defvjp(_stack_fwd, _stack_bwd)


@partial(jax.jit, static_argnames=("stride1", "pad1", "stride2", "pad2",
                                   "engine", "nt", "interpret", "relu1",
                                   "relu2", "pool", "src_layout",
                                   "dst_layout", "res_layout"))
def conv_stack(x, w1, w2, stride1: int = 1, pad1: int = 0, stride2: int = 1,
               pad2: int = 0, *, engine: str, nt: int = flat.CHWN_NT,
               interpret: Optional[bool] = None, bias1=None, bias2=None,
               relu1: bool = True, relu2: bool = False,
               pool: Optional[Tuple[int, int, str]] = None,
               res=None, res_layout: Optional[str] = None,
               src_layout: Optional[str] = None,
               dst_layout: Optional[str] = None):
    """Fused conv->conv stack on the ``engine`` Pallas path: x in
    ``src_layout``, w1 [Cm,Ci,F1,F1], w2 [Co,Cm,F2,F2] (canonical) -> output
    in ``dst_layout`` (layouts default to ``engine``).  Conv1 carries a
    bias[+ReLU]-only epilogue; conv2 takes the full bias/residual-add/ReLU/
    pool protocol.  The intermediate activation stays in VMEM.
    Differentiable: the custom VJP replays the unfused two-conv composition
    (see ``_stack_bwd``)."""
    return _stack_vjp(x, w1, bias1, w2, bias2, res, stride1, pad1, stride2,
                      pad2, nt, interpret, relu1, relu2, pool,
                      src_layout or engine, dst_layout or engine,
                      res_layout or engine, engine)


@partial(jax.jit, static_argnames=("stride", "pad", "interpret", "use_pallas_mm"))
def conv_im2col_nchw(x, w, stride: int = 1, pad: int = 0,
                     interpret: Optional[bool] = None,
                     use_pallas_mm: bool = True):
    """im2col + matmul, NCHW: x [N,Ci,H,W], w [Co,Ci,F,F] -> [N,Co,Ho,Wo].
    The seed baseline: XLA materializes the patch matrix (the paper's
    'matrix expansion' traffic), only the matmul runs in Pallas."""
    N, Ci, H, W = x.shape
    Co, _, F, _ = w.shape
    patches, (n, Ho, Wo) = im2col_nchw(x, F, stride, pad)
    wmat = w.reshape(Co, Ci * F * F).T            # [CiFF, Co]
    if use_pallas_mm:
        out = matmul(patches, wmat, interpret=resolve_interpret(interpret))
    else:
        out = patches @ wmat
    return out.reshape(N, Ho, Wo, Co).transpose(0, 3, 1, 2)


@partial(jax.jit, static_argnames=("stride", "pad"))
def conv_fft_nchw(x, w, stride: int = 1, pad: int = 0):
    """FFT conv (NCHW): pads the filter to the image size, multiplies in the
    frequency domain (the paper's cuDNN-FFT mode; memory overhead included).
    Only exact for stride 1; strided layers subsample the full conv."""
    N, Ci, H, W = x.shape
    Co, _, F, _ = w.shape
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        H, W = x.shape[2], x.shape[3]
    Hf = H + F - 1
    Wf = W + F - 1
    xf = jnp.fft.rfft2(x.astype(jnp.float32), (Hf, Wf))          # [N,Ci,Hf,Wf']
    wf = jnp.fft.rfft2(w[:, :, ::-1, ::-1].astype(jnp.float32), (Hf, Wf))
    yf = jnp.einsum("nchw,ochw->nohw", xf, wf)
    y = jnp.fft.irfft2(yf, (Hf, Wf))
    y = y[:, :, F - 1:H, F - 1:W]                                # valid region
    if stride > 1:
        y = y[:, :, ::stride, ::stride]
    return y.astype(x.dtype)
