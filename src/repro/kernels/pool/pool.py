"""Pooling Pallas kernel — the paper's §V.A off-chip-access optimization.

GPU original: CHWN layout + thread coarsening: each thread produces E output
elements so overlapping input windows are loaded into registers once
(hill-climbed E).  TPU adaptation: each program owns one slab of ``nt``
samples (``kernels/flat.py``); the whole H x W x nt input slab is loaded into
VMEM ONCE and every overlapping window is computed from it (VMEM plays the
register file).  The CHWN engine interleaves ``nt`` samples on the lanes
(the paper's coalescing dim); the NCHW engine pools one sample per slab,
with the window sliding along the lanes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.experimental import pallas as pl

from repro.kernels import flat, resolve_interpret


def _pool_kernel(x_ref, *refs, W, nt, F, S, op, Ho, Wo, has_sel):
    rest = list(refs)
    sel_ref = rest.pop(0) if has_sel else None
    (o_ref,) = rest
    rl = Wo * nt

    def store(r, v):
        o_ref[0, :, r * rl:(r + 1) * rl] = v.astype(o_ref.dtype)

    flat.pool_rows(lambda off, size: x_ref[0, :, off:off + size], store,
                   W, nt, F, S, op, Ho, Wo,
                   sel=None if sel_ref is None else sel_ref[...])


def pool_pallas(xf, F: int, S: int, op: str, *, H: int, W: int, nt: int,
                interpret: Optional[bool] = None):
    """xf: flat [G, C, H*W*nt] -> [G, C, Ho*Wo*nt] (F x F / S windows)."""
    G, C, L = xf.shape
    Ho, Wo = flat.pool_geometry(H, W, (F, S, op))
    in_specs = [pl.BlockSpec((1, C, L), lambda g: (g, 0, 0))]
    operands = [xf]
    has_sel = S > 1
    if has_sel:
        sel = flat.selection(S, Wo, nt)
        in_specs.append(pl.BlockSpec(sel.shape, lambda g: (0, 0)))
        operands.append(sel)
    nbytes = C * L * (xf.dtype.itemsize + 4) + 2 * C * Ho * Wo * nt * 4
    kern = functools.partial(_pool_kernel, W=W, nt=nt, F=F, S=S, op=op,
                             Ho=Ho, Wo=Wo, has_sel=has_sel)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((G, C, Ho * Wo * nt), xf.dtype),
        grid=(G,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C, Ho * Wo * nt), lambda g: (g, 0, 0)),
        compiler_params=flat.compiler_params(1, nbytes),
        interpret=resolve_interpret(interpret),
    )(*operands)
