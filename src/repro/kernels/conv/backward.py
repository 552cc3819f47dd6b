"""Layout-aware conv backward: dgrad + wgrad Pallas engines (paper applied to
training — the layout study covers backward propagation, where the two
gradient convolutions are first-class layout-sensitive primitives, cuDNN
style).

dgrad (input gradient) uses the **transposed-conv formulation**: the output
gradient is spatially dilated by the forward stride and padded by F-1-pad,
then convolved (stride 1) with the 180°-rotated, channel-swapped filter.
The convolution itself runs on the existing layout-bound Pallas engines
(direct-CHWN / im2col-MM-NCHW), so dgrad inherits the whole layout-fusion
protocol: it consumes the incoming gradient in the *downstream* op's layout
(``g_layout`` -> the engine's ``src_layout``) and writes dx directly in the
*upstream* producer's layout (``dst_layout``) — the reversed re-layout chain
folds into kernel I/O maps exactly like the forward one.

wgrad (weight gradient) is a **native Pallas kernel** in the im2col-MM
formulation: dw = (virtual patch matrix)^T @ (output-gradient matrix).  Each
(dy, dx) filter tap contributes one [Co-block] x [Ci-block] MXU contraction
over (rows x N) — the im2col expansion stays virtual in VMEM, and the tiny
[Co, Ci, F, F] result accumulates in a VMEM scratch across the (N, row-block)
grid dims (innermost, so output-block revisits are consecutive).  The same
halo-stitch trick as the forward kernels covers row blocks whose windows
overlap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pad_axis(x, axis, m):
    p = (-x.shape[axis]) % m
    if p:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, p)
        x = jnp.pad(x, pad)
    return x


def _prep_rows(x, h_axis: int, need_rows: int):
    if x.shape[h_axis] < need_rows:
        pad = [(0, 0)] * x.ndim
        pad[h_axis] = (0, need_rows - x.shape[h_axis])
        x = jnp.pad(x, pad)
    return x


def _spatial_axes(layout: str):
    return (2, 3) if layout == "NCHW" else (1, 2)


def dilate_grad(g, S: int, F: int, layout: str):
    """Spatially dilate ``g`` by the forward stride and pad by F-1: the
    transposed-conv input.  Identity (plus padding) when S == 1."""
    ha, wa = _spatial_axes(layout)
    if S > 1:
        shape = list(g.shape)
        shape[ha] = (shape[ha] - 1) * S + 1
        shape[wa] = (shape[wa] - 1) * S + 1
        idx = [slice(None)] * g.ndim
        idx[ha] = slice(None, None, S)
        idx[wa] = slice(None, None, S)
        g = jnp.zeros(shape, g.dtype).at[tuple(idx)].set(g)
    if F > 1:
        pads = [(0, 0)] * g.ndim
        pads[ha] = (F - 1, F - 1)
        pads[wa] = (F - 1, F - 1)
        g = jnp.pad(g, pads)
    return g


def conv_dgrad(g, w, x_hw, stride: int = 1, pad: int = 0, *,
               layout: str = "CHWN", g_layout: str = None,
               dst_layout: str = None, interpret: bool = True):
    """Input gradient of conv(x, w, stride, pad).

    g: conv-output gradient in ``g_layout`` (NCHW [N,Co,Ho,Wo] or CHWN
    [Co,Ho,Wo,N]); w: canonical [Co,Ci,F,F]; x_hw: (H, W) of the forward
    input.  Computes in ``layout``'s Pallas engine, returns dx in
    ``dst_layout``.  Rows/cols of x beyond the last consumed window (when
    (H + 2*pad - F) % stride != 0) receive zero gradient.
    """
    g_layout = g_layout or layout
    dst_layout = dst_layout or layout
    F = w.shape[2]
    S = stride
    H, W = x_hw
    gd = dilate_grad(g, S, F, g_layout)
    # rotate 180° and swap channel roles: the transposed filter maps Co->Ci
    wt = jnp.transpose(w[:, :, ::-1, ::-1], (1, 0, 2, 3))     # [Ci, Co, F, F]
    from repro.kernels.conv.ops import conv_fused
    dx = conv_fused(gd, wt, stride=1, pad=0, engine=layout,
                    interpret=interpret, src_layout=g_layout,
                    dst_layout=dst_layout)
    # dx now covers the PADDED input rows 0..(Ho-1)*S+F-1; the unpadded
    # gradient is the [pad, pad+H) window, zero-filled past the last
    # consumed window when (H + 2*pad - F) % S != 0
    ha, wa = _spatial_axes(dst_layout)
    idx = [slice(None)] * dx.ndim
    idx[ha] = slice(pad, pad + H)
    idx[wa] = slice(pad, pad + W)
    dx = dx[tuple(idx)]
    tail_h = H - dx.shape[ha]
    tail_w = W - dx.shape[wa]
    if tail_h or tail_w:
        pads = [(0, 0)] * dx.ndim
        pads[ha] = (0, tail_h)
        pads[wa] = (0, tail_w)
        dx = jnp.pad(dx, pads)
    return dx


def bias_grad(g, layout: str = "CHWN"):
    """d(bias): reduce the conv-output gradient over all non-Co dims."""
    axes = (0, 2, 3) if layout == "NCHW" else (1, 2, 3)
    return g.astype(jnp.float32).sum(axes)


# ---------------------------------------------------------------------------
# native wgrad kernel
# ---------------------------------------------------------------------------

def _wgrad_kernel(xa_ref, xb_ref, g_ref, o_ref, acc_ref, *, F, S, bho, Wo,
                  n_n, n_ho, x_layout, g_layout):
    @pl.when((pl.program_id(2) == 0) & (pl.program_id(3) == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xa = xa_ref[...]
    xb = xb_ref[...]
    if x_layout == "NCHW":               # blocks arrive [nt, cit, IBH, W]
        xa = jnp.transpose(xa, (1, 2, 3, 0))
        xb = jnp.transpose(xb, (1, 2, 3, 0))
    x2 = jnp.concatenate([xa, xb], axis=1)       # [cit, 2*IBH, W, nt]
    g = g_ref[...]
    if g_layout == "NCHW":               # [nt, cot, bho, Wo]
        g = jnp.transpose(g, (1, 2, 3, 0))       # [cot, bho, Wo, nt]

    taps = []
    for dy in range(F):
        for dx in range(F):
            xs = x2[:, dy:dy + (bho - 1) * S + 1:S,
                    dx:dx + (Wo - 1) * S + 1:S, :]       # [cit, bho, Wo, nt]
            # one [Co-block] x [Ci-block] tap of the virtual-im2col matmul:
            # contraction over the (rows x N) output positions on the MXU
            taps.append(jnp.einsum("khwn,chwn->kc", g, xs,
                                   preferred_element_type=jnp.float32))
    upd = jnp.stack(taps).reshape(F, F, *taps[0].shape)
    acc_ref[...] = acc_ref[...] + jnp.transpose(upd, (2, 3, 0, 1))

    @pl.when((pl.program_id(2) == n_n - 1) & (pl.program_id(3) == n_ho - 1))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def wgrad_pallas(x, g, F: int, S: int, *, bho: int = 4, cot: int = 0,
                 cit: int = 0, nt: int = 128, ibh: int = 0,
                 x_layout: str = "CHWN", g_layout: str = None,
                 interpret: bool = True):
    """dw[Co,Ci,F,F] = wgrad(x, g): x the (pre-padded) forward input in
    ``x_layout``, g the conv-output gradient in ``g_layout``.

    Requirements (conv_wgrad pads): N % nt == 0, Co % cot == 0,
    Ci % cit == 0, Ho % bho == 0, rows >= (row blocks + 1)*IBH.
    """
    g_layout = g_layout or x_layout
    if x_layout == "NCHW":
        N, Ci, H, W = x.shape
    else:
        Ci, H, W, N = x.shape
    if g_layout == "NCHW":
        Co, Ho, Wo = g.shape[1], g.shape[2], g.shape[3]
    else:
        Co, Ho, Wo = g.shape[0], g.shape[1], g.shape[2]
    cot = cot or min(Co, 128)
    cit = cit or min(Ci, 32)
    IBH = ibh or bho * S
    n_ho = Ho // bho
    n_n = N // nt
    assert IBH == bho * S or n_ho == 1, (IBH, bho, S, n_ho)

    if x_layout == "NCHW":
        x_specs = [
            pl.BlockSpec((nt, cit, IBH, W), lambda c, k, n, h: (n, k, h, 0)),
            pl.BlockSpec((nt, cit, IBH, W),
                         lambda c, k, n, h: (n, k, h + 1, 0)),
        ]
    else:
        x_specs = [
            pl.BlockSpec((cit, IBH, W, nt), lambda c, k, n, h: (k, h, 0, n)),
            pl.BlockSpec((cit, IBH, W, nt),
                         lambda c, k, n, h: (k, h + 1, 0, n)),
        ]
    if g_layout == "NCHW":
        g_spec = pl.BlockSpec((nt, cot, bho, Wo),
                              lambda c, k, n, h: (n, c, h, 0))
    else:
        g_spec = pl.BlockSpec((cot, bho, Wo, nt),
                              lambda c, k, n, h: (c, h, 0, n))

    kern = functools.partial(_wgrad_kernel, F=F, S=S, bho=bho, Wo=Wo,
                             n_n=n_n, n_ho=n_ho, x_layout=x_layout,
                             g_layout=g_layout)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((Co, Ci, F, F), jnp.float32),
        # accumulation dims (N, row blocks) innermost: the (c, k) output
        # block is revisited consecutively, accumulating in VMEM scratch
        grid=(Co // cot, Ci // cit, n_n, n_ho),
        in_specs=x_specs + [g_spec],
        out_specs=pl.BlockSpec((cot, cit, F, F),
                               lambda c, k, n, h: (c, k, 0, 0)),
        scratch_shapes=[pltpu.VMEM((cot, cit, F, F), jnp.float32)],
        interpret=interpret,
    )(x, x, g)


def conv_wgrad(x, g, F: int, S: int = 1, pad: int = 0, *,
               x_layout: str = "CHWN", g_layout: str = None, nt: int = 128,
               interpret: bool = True):
    """Weight gradient of conv(x, w, S, pad) -> canonical [Co, Ci, F, F].

    x: the forward input (unpadded) in ``x_layout``; g: the conv-output
    gradient in ``g_layout``.  Pads channels/batch to tile multiples (zero
    contributions) and preps halo rows like the forward wrappers.
    """
    from repro.kernels.conv.ops import conv_blocking
    g_layout = g_layout or x_layout
    if x_layout == "NCHW":
        n_axis, ci_axis, h_axis = 0, 1, 2
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    else:
        n_axis, ci_axis, h_axis = 3, 0, 1
        if pad:
            x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    if g_layout == "NCHW":
        N, Co, Ho = g.shape[0], g.shape[1], g.shape[2]
        g_n, g_co = 0, 1
    else:
        Co, Ho, N = g.shape[0], g.shape[1], g.shape[3]
        g_n, g_co = 3, 0
    Ci = x.shape[ci_axis]
    cit = min(Ci, 32)
    cot = min(Co, 128)
    nt = min(nt, max(N, 1))
    x = _pad_axis(_pad_axis(x, ci_axis, cit), n_axis, nt)
    g = _pad_axis(_pad_axis(g, g_co, cot), g_n, nt)
    bho, IBH, n_ho = conv_blocking(Ho, F, S)
    x = _prep_rows(x, h_axis, (n_ho + 1) * IBH)
    dw = wgrad_pallas(x, g, F, S, bho=bho, cot=cot, cit=cit, nt=nt, ibh=IBH,
                      x_layout=x_layout, g_layout=g_layout,
                      interpret=interpret)
    return dw[:Co, :Ci]
