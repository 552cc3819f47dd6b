"""Convolution Pallas kernel with a fused epilogue, on flat spatial tiles.

Both of the paper's conv engines run this one kernel (``kernels/flat.py``
describes the tile form): the direct-CHWN engine with ``nt`` samples
interleaved on the lanes of a slab, the im2col-MM NCHW engine with one
sample per slab.  Each filter tap is one MXU matmul of the [Co, Ci] weight
tap against the whole input slab shifted by the tap — the im2col matrix
multiply with the patch matrix kept virtual.

Grid: (sample groups, Co blocks).  The whole Ci slab and all taps reduce in
one step into a VMEM f32 scratch, so there is no cross-step accumulator.

Fusion (DESIGN.md §5): the epilogue runs on the f32 result while it lives in
VMEM — bias add, residual add, ReLU and max/avg pooling — and only the final
(pooled) tensor is written.  ``save_act`` (training) also writes the
pre-pool activation from the same VMEM slab.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import flat, resolve_interpret


@dataclass(frozen=True)
class Epilogue:
    """What the conv kernel folds into its final VMEM->HBM write.

    ``pool`` is ``(F, S, op)`` with op in {"max", "avg"}.  ``residual``
    folds a skip-tensor add onto the accumulator (after bias, before ReLU —
    the ResNet epilogue order), so the standalone add and its operand
    re-layout both vanish from HBM traffic (DESIGN.md §11).
    """
    bias: bool = False
    relu: bool = False
    pool: Optional[Tuple[int, int, str]] = None
    residual: bool = False


def pool_tiles_block(bho: int, n_ho: int, pF: int, pS: int) -> bool:
    """True when every pool window lies inside one conv-output row block:
    either one block covers the whole height, or the block height is a
    multiple of the pool stride and windows don't overlap block seams.
    (Row blocking of the planner's cost model; see ``ops.pick_bho``.)"""
    if pF > bho:
        return False
    return n_ho == 1 or (bho % pS == 0 and pF <= pS)


def finish(y_ref, o_ref, *, b_ref, r_ref, z_ref, sel_ref, epi: Epilogue,
           Ho: int, Wo: int, nt: int, PWo: int):
    """Shared epilogue tail: compact slab in ``y_ref`` -> bias/residual/
    ReLU -> optional saved activation -> optional pool -> ``o_ref``."""
    y = flat.epilogue(y_ref[...],
                      bias=None if b_ref is None else b_ref[...],
                      res=None if r_ref is None else r_ref[0],
                      relu=epi.relu)
    if z_ref is not None:
        z_ref[0] = y.astype(z_ref.dtype)
    if epi.pool is None:
        o_ref[0] = y.astype(o_ref.dtype)
        return
    pF, pS, pop = epi.pool
    PHo, _ = flat.pool_geometry(Ho, Wo, epi.pool)
    rl = PWo * nt

    def store(r, v):
        o_ref[0, :, r * rl:(r + 1) * rl] = v.astype(o_ref.dtype)

    flat.pool_rows(lambda off, size: y[:, off:off + size], store, Wo, nt,
                   pF, pS, pop, PHo, PWo,
                   sel=None if sel_ref is None else sel_ref[...])


def _split_refs(refs, epi: Epilogue, has_sel: bool, save_act: bool):
    rest = list(refs)
    b_ref = rest.pop(0) if epi.bias else None
    r_ref = rest.pop(0) if epi.residual else None
    sel_ref = rest.pop(0) if has_sel else None
    o_ref = rest.pop(0)
    z_ref = rest.pop(0) if save_act else None
    return b_ref, r_ref, sel_ref, o_ref, z_ref, rest


def _conv_kernel(x_ref, w_ref, *refs, F, pitch, nt, Ho, Wo, PWo,
                 epi: Epilogue, has_sel: bool, save_act: bool):
    b_ref, r_ref, sel_ref, o_ref, z_ref, (acc_ref, y_ref) = _split_refs(
        refs, epi, has_sel, save_act)
    flat.conv_taps(lambda start, size: x_ref[0, :, pl.ds(start, size)],
                   w_ref, acc_ref, F, pitch, nt)
    flat.compact_rows(acc_ref, y_ref, Ho, 1, pitch, Wo, nt)
    finish(y_ref, o_ref, b_ref=b_ref, r_ref=r_ref, z_ref=z_ref,
           sel_ref=sel_ref, epi=epi, Ho=Ho, Wo=Wo, nt=nt, PWo=PWo)


def conv_pallas(xf, wt, *, F: int, pitch: int, nt: int, Ho: int, Wo: int,
                bias=None, res=None, epilogue: Epilogue = Epilogue(),
                out_dtype=None, save_act: bool = False,
                interpret: Optional[bool] = None):
    """Stride-1 conv on flat slabs with a fused epilogue.

    xf: [G, Ci, rows*pitch*nt] (``flat.prep``; pitch = Wo + F - 1; rows
    from ``flat.conv_lanes``);
    wt: tap-major [F*F, Co, Ci]; bias: [Co, 1] f32; res: [G, Co, Ho*Wo*nt]
    (compact).  Returns [G, Co, PHo*PWo*nt] (post-pool when a pool is
    fused), plus the compact pre-pool activation [G, Co, Ho*Wo*nt] when
    ``save_act``."""
    G, Ci, _ = xf.shape
    T, Co, _ = wt.shape
    total, rows = flat.conv_lanes(Ho, F, pitch, nt)
    assert xf.shape[2] >= rows * pitch * nt, (xf.shape, rows, pitch, nt)
    cot = Co if (Co <= 128 or Co % 128) else 128
    odt = out_dtype or jnp.result_type(xf.dtype, wt.dtype)
    PHo, PWo = flat.pool_geometry(Ho, Wo, epilogue.pool)
    Lo, Lp = Ho * Wo * nt, PHo * PWo * nt
    in_specs = [pl.BlockSpec((1, Ci, xf.shape[2]), lambda g, c: (g, 0, 0)),
                pl.BlockSpec((T, cot, Ci), lambda g, c: (0, c, 0))]
    operands = [xf, wt]
    if epilogue.bias:
        in_specs.append(pl.BlockSpec((cot, 1), lambda g, c: (c, 0)))
        operands.append(bias)
    if epilogue.residual:
        in_specs.append(pl.BlockSpec((1, cot, Lo), lambda g, c: (g, c, 0)))
        operands.append(res)
    has_sel = epilogue.pool is not None and epilogue.pool[1] > 1
    if has_sel:
        sel = flat.selection(epilogue.pool[1], PWo, nt)
        in_specs.append(pl.BlockSpec(sel.shape, lambda g, c: (0, 0)))
        operands.append(sel)
    out_shape = [jax.ShapeDtypeStruct((G, Co, Lp), odt)]
    out_specs = [pl.BlockSpec((1, cot, Lp), lambda g, c: (g, c, 0))]
    if save_act:
        out_shape.append(jax.ShapeDtypeStruct((G, Co, Lo), odt))
        out_specs.append(pl.BlockSpec((1, cot, Lo), lambda g, c: (g, c, 0)))
    isz = jnp.dtype(xf.dtype).itemsize
    nbytes = (Ci * xf.shape[2] * isz + T * cot * Ci * wt.dtype.itemsize
              + cot * total * 4 + 3 * cot * Lo * 4 + cot * Lp * 4)
    kern = functools.partial(_conv_kernel, F=F, pitch=pitch, nt=nt, Ho=Ho,
                             Wo=Wo, PWo=PWo, epi=epilogue, has_sel=has_sel,
                             save_act=save_act)
    out = pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid=(G, Co // cot),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((cot, total), jnp.float32),
                        pltpu.VMEM((cot, Lo), jnp.float32)],
        compiler_params=flat.compiler_params(2, nbytes),
        interpret=resolve_interpret(interpret),
    )(*operands)
    return out if save_act else out[0]
