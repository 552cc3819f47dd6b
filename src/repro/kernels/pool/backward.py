"""Pooling backward Pallas kernels: max-mask routing and avg scatter.

Same slab decomposition as the forward kernels (§V.A): each program owns one
(c, n-tile) slab with the whole H x W input block in VMEM, so every
overlapping window routes its gradient from registers — the backward twin of
the thread-coarsening reuse.  Max pooling recomputes the window max from the
slab and routes each window's gradient to its FIRST maximal element in
row-major tap order (matching XLA's select-and-scatter tie-breaking, so the
differential tests agree exactly).  Avg pooling scatter-adds g/F^2 over each
window.

Layout fusion, reversed: ``g_layout`` lets the kernel consume the incoming
gradient in the *downstream* op's layout (the backward analogue of
``dst_layout`` on the forward kernels), and ``relu_mask`` folds the ReLU
backward mask into the same pass — the pool input is in VMEM for the max
mask anyway, so the fused conv block's whole relu+pool backward is one
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pad_axis(x, axis, m):
    p = (-x.shape[axis]) % m
    if p:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, p)
        x = jnp.pad(x, pad)
    return x


def _route(x, g, F, S, Ho, Wo, op, ha, wa, relu_mask):
    """Scatter the window gradients of one VMEM slab back onto x's grid.
    ``ha``/``wa`` are x's spatial axes; x and g share layout."""
    def hs(d):
        return slice(d, d + (Ho - 1) * S + 1, S)

    def ws(d):
        return slice(d, d + (Wo - 1) * S + 1, S)

    def at(a, dy, dx):
        idx = [slice(None)] * a.ndim
        idx[ha], idx[wa] = hs(dy), ws(dx)
        return tuple(idx)

    acc = jnp.zeros(x.shape, jnp.float32)
    if op == "avg":
        gavg = g / (F * F)
        for dy in range(F):
            for dx in range(F):
                acc = acc.at[at(acc, dy, dx)].add(gavg)
    else:
        mx = jnp.full(g.shape, -jnp.inf, jnp.float32)
        for dy in range(F):
            for dx in range(F):
                mx = jnp.maximum(mx, x[at(x, dy, dx)])
        claimed = jnp.zeros(g.shape, jnp.bool_)
        for dy in range(F):
            for dx in range(F):
                win = x[at(x, dy, dx)]
                take = (win == mx) & (~claimed)
                claimed = claimed | take
                acc = acc.at[at(acc, dy, dx)].add(jnp.where(take, g, 0.0))
    if relu_mask:
        acc = acc * (x > 0.0)
    return acc


def _pool_bwd_chwn_kernel(x_ref, g_ref, o_ref, *, F, S, op, Ho, Wo,
                          g_layout, relu_mask):
    x = x_ref[...].astype(jnp.float32)          # [1, H, W, nt]
    g = g_ref[...]
    if g_layout == "NCHW":                      # [nt, 1, Ho, Wo]
        g = jnp.transpose(g, (1, 2, 3, 0))
    g = g.astype(jnp.float32)                   # [1, Ho, Wo, nt]
    acc = _route(x, g, F, S, Ho, Wo, op, 1, 2, relu_mask)
    o_ref[...] = acc.astype(o_ref.dtype)


def _pool_bwd_nchw_kernel(x_ref, g_ref, o_ref, *, F, S, op, Ho, Wo,
                          g_layout, relu_mask):
    x = x_ref[...].astype(jnp.float32)          # [1, ct, H, W]
    g = g_ref[...]
    if g_layout == "CHWN":                      # [ct, Ho, Wo, 1]
        g = jnp.transpose(g, (3, 0, 1, 2))
    g = g.astype(jnp.float32)                   # [1, ct, Ho, Wo]
    acc = _route(x, g, F, S, Ho, Wo, op, 2, 3, relu_mask)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("F", "S", "op", "layout",
                                             "g_layout", "relu_mask", "nt",
                                             "ct", "interpret"))
def pool_backward(x, g, F: int, S: int, op: str = "max", *,
                  layout: str = "CHWN", g_layout: str = None,
                  relu_mask: bool = False, nt: int = 128, ct: int = 8,
                  interpret: bool = True):
    """dx of pool(x, F, S, op): x the pool input in ``layout``, g the pooled
    output's gradient in ``g_layout``.  Returns dx in ``layout``; rows/cols
    beyond the last window get zero gradient.  ``relu_mask`` multiplies dx by
    (x > 0) in the same pass."""
    g_layout = g_layout or layout
    if F == 1 and S == 1:
        # identity pool (e.g. a global-average window degenerated to 1x1 at
        # reduced image sizes): dx is g re-laid-out, with the optional mask
        from repro.core.transform import apply_transform
        ga = apply_transform(g, g_layout, layout).astype(jnp.float32)
        if relu_mask:
            ga = ga * (x > 0.0)
        return ga.astype(x.dtype)
    if layout == "CHWN":
        C, H, W, N = x.shape
        Ho = g.shape[2] if g_layout == "NCHW" else g.shape[1]
        Wo = g.shape[3] if g_layout == "NCHW" else g.shape[2]
        nt = min(nt, max(N, 1))
        xp = _pad_axis(x, 3, nt)
        gp = _pad_axis(g, 0 if g_layout == "NCHW" else 3, nt)
        if g_layout == "NCHW":
            g_spec = pl.BlockSpec((nt, 1, Ho, Wo), lambda c, n: (n, c, 0, 0))
        else:
            g_spec = pl.BlockSpec((1, Ho, Wo, nt), lambda c, n: (c, 0, 0, n))
        kern = functools.partial(_pool_bwd_chwn_kernel, F=F, S=S, op=op,
                                 Ho=Ho, Wo=Wo, g_layout=g_layout,
                                 relu_mask=relu_mask)
        dx = pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
            grid=(C, xp.shape[3] // nt),
            in_specs=[pl.BlockSpec((1, H, W, nt), lambda c, n: (c, 0, 0, n)),
                      g_spec],
            out_specs=pl.BlockSpec((1, H, W, nt), lambda c, n: (c, 0, 0, n)),
            interpret=interpret,
        )(xp, gp)
        return dx[..., :N]
    N, C, H, W = x.shape
    Ho = g.shape[1] if g_layout == "CHWN" else g.shape[2]
    Wo = g.shape[2] if g_layout == "CHWN" else g.shape[3]
    ct = min(ct, C)
    xp = _pad_axis(x, 1, ct)
    gp = _pad_axis(g, 0 if g_layout == "CHWN" else 1, ct)
    if g_layout == "CHWN":
        g_spec = pl.BlockSpec((ct, Ho, Wo, 1), lambda n, c: (c, 0, 0, n))
    else:
        g_spec = pl.BlockSpec((1, ct, Ho, Wo), lambda n, c: (n, c, 0, 0))
    kern = functools.partial(_pool_bwd_nchw_kernel, F=F, S=S, op=op,
                             Ho=Ho, Wo=Wo, g_layout=g_layout,
                             relu_mask=relu_mask)
    dx = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        grid=(N, xp.shape[1] // ct),
        in_specs=[pl.BlockSpec((1, ct, H, W), lambda n, c: (n, c, 0, 0)),
                  g_spec],
        out_specs=pl.BlockSpec((1, ct, H, W), lambda n, c: (n, c, 0, 0)),
        interpret=interpret,
    )(xp, gp)
    return dx[:, :C]
