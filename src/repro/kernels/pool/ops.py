"""jit'd pooling wrapper: flat slabs in (``kernels/flat.py``), one pool
kernel, re-layout out; differentiable through the pool-backward kernel."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels import flat, resolve_interpret
from repro.kernels.pool.pool import pool_pallas


def _pool_flat(x, F, S, op, nt, layout, dst_layout, interpret):
    """Forward: flat slabs in, one pool kernel, re-layout out."""
    xn = flat.to_nchw(x, layout)
    N, C, H, W = xn.shape
    xf = flat.prep(xn, "NCHW", pad=0, stride=1, rows=H, cols=W, nt=nt,
                   cmult=1)
    y = pool_pallas(xf, F, S, op, H=H, W=W, nt=nt, interpret=interpret)
    Ho, Wo = flat.pool_geometry(H, W, (F, S, op))
    return flat.unprep(y, N, C, Ho, Wo, nt, dst_layout)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def _pool_vjp(x, F, S, op, nt, layout, dst_layout, interpret):
    return _pool_flat(x, F, S, op, nt, layout, dst_layout, interpret)


def _pool_fwd(x, *static):
    return _pool_vjp(x, *static), x


def _pool_bwd(F, S, op, nt, layout, dst_layout, interpret, x, g):
    from repro.kernels.pool.backward import pool_backward
    dx = pool_backward(x, g, F, S, op, layout=layout, g_layout=dst_layout,
                       interpret=resolve_interpret(interpret))
    return (dx.astype(x.dtype),)


_pool_vjp.defvjp(_pool_fwd, _pool_bwd)


@partial(jax.jit, static_argnames=("F", "S", "op", "layout", "nt",
                                   "dst_layout", "interpret"))
def pool_fused(x, F: int, S: int, op: str = "max", *, layout: str,
               nt: int = 0, dst_layout: Optional[str] = None,
               interpret: Optional[bool] = None):
    """Pooling of x in ``layout`` with VMEM window reuse.  CHWN (the
    preferred layout) shares each slab's lanes among up to ``nt`` samples
    (``flat.group_tile``); NCHW runs one sample per slab (the paper's
    inefficient-layout baseline).  ``dst_layout`` writes the result directly
    in the consumer's layout, replacing a standalone transform pass.
    Differentiable: the VJP runs the max-mask/avg-scatter Pallas kernel,
    consuming the cotangent in ``dst_layout`` (the reversed re-layout folds
    into its input read)."""
    if layout == "CHWN":
        _, H, W, N = x.shape
    else:
        N, _, H, W = x.shape
    nt =flat.group_tile(N, layout, nt or flat.CHWN_NT, H * W)
    return _pool_vjp(x, F, S, op, nt, layout, dst_layout or layout,
                     interpret)


def pool_chwn(x, F: int, S: int, op: str = "max", nt: int = 0,
              dst_layout: str = "CHWN", interpret: Optional[bool] = None):
    """``pool_fused`` on [C,H,W,N] input."""
    return pool_fused(x, F, S, op, layout="CHWN", nt=nt,
                      dst_layout=dst_layout, interpret=interpret)


def pool_nchw(x, F: int, S: int, op: str = "max",
              dst_layout: str = "NCHW", interpret: Optional[bool] = None):
    """``pool_fused`` on [N,C,H,W] input."""
    return pool_fused(x, F, S, op, layout="NCHW", dst_layout=dst_layout,
                      interpret=interpret)
