"""Cross-layer halo fusion: two stacked convolutions in ONE Pallas kernel.

The biggest HBM round-trip left after epilogue fusion (DESIGN.md §5/§11) is
the intermediate activation between adjacent convs.  This kernel removes it:
the first conv runs over the input slab far enough to cover the SECOND
conv's receptive field, stages its post-bias/ReLU output in a VMEM scratch,
and the second conv — with the full bias/residual-add/ReLU/pool epilogue
protocol — contracts straight off the staged tile.  The mid activation never
touches HBM (DESIGN.md §12).

Both convs use the flat wide form of ``kernels/flat.py`` with one shared row
pitch: conv1's wide output IS conv2's input slab.  The wrapper pre-pads the
input by ``pad1 + S1*pad2`` rows/cols per side, which makes the staged mid
tile exactly ``pad2``-padded y1 — EXCEPT that conv1's epilogue (bias/ReLU)
is nonzero on the padding ring, so a precomputed 0/1 mask zeroes mid
positions outside the valid range [pad2, pad2 + Ho1) x [pad2, pad2 + Wo1)
before conv2 reads them.  Conv1's stride is removed by space-to-depth;
conv2's stride keeps every ``S2``-th row and column of its stride-1 result.

Both engines run this kernel (``nt`` samples per slab for CHWN, one for
NCHW).  Channels are NOT grid-blocked — the full (Ci, Cm, Co) slabs live in
VMEM, which is why the planner gates stack fusion on a VMEM-footprint bound
(``stack_vmem_bytes``) instead.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import flat, resolve_interpret
from repro.kernels.conv.conv import Epilogue, _split_refs, finish


def stack_lanes(Ho2: int, S2: int, F1: int, F2: int, pitch: int, nt: int):
    """(mid lanes, conv2 accumulator lanes, input rows) of a stack: conv2's
    stride-1 wide pass over ``(Ho2-1)*S2 + 1`` rows reads its windows off
    the staged mid tile, which conv1 fills from the input slab."""
    total2, _ = flat.conv_lanes((Ho2 - 1) * S2 + 1, F2, pitch, nt)
    halo2 = flat.ceil_to(flat.tap_offsets(F2, pitch, nt)[-1], 128)
    total1 = flat.ceil_to(total2 + halo2, flat.CHUNK)
    halo1 = flat.ceil_to(flat.tap_offsets(F1, pitch, nt)[-1], 128)
    return total1, total2, -(-(total1 + halo1) // (pitch * nt))


def _stack_kernel(x_ref, w1_ref, b1_ref, m_ref, w2_ref, *refs, F1, F2, S2,
                  pitch, nt, Ho2, Wo2, PWo, relu1: bool, epi: Epilogue,
                  has_sel: bool, has_sel2: bool):
    rest = list(refs)
    sel2_ref = rest.pop(0) if has_sel2 else None
    b_ref, r_ref, sel_ref, o_ref, _, (mid_ref, acc_ref, y_ref) = \
        _split_refs(rest, epi, has_sel, False)

    def mid_epilogue(acc, start):
        y = flat.epilogue(acc, bias=b1_ref[...], relu=relu1)
        return y * m_ref[:, pl.ds(start, flat.CHUNK)]

    # ---- conv1 over the mid lanes conv2 reads, staged in VMEM ----
    flat.conv_taps(lambda start, size: x_ref[0, :, pl.ds(start, size)],
                   w1_ref, mid_ref, F1, pitch, nt, post=mid_epilogue)

    # ---- conv2 straight off the staged tile ----
    flat.conv_taps(lambda start, size: mid_ref[:, pl.ds(start, size)],
                   w2_ref, acc_ref, F2, pitch, nt)
    flat.compact_rows(acc_ref, y_ref, Ho2, S2, pitch, Wo2, nt,
                      sel=None if sel2_ref is None else sel2_ref[...])
    finish(y_ref, o_ref, b_ref=b_ref, r_ref=r_ref, z_ref=None,
           sel_ref=sel_ref, epi=epi, Ho=Ho2, Wo=Wo2, nt=nt, PWo=PWo)


def conv_stack_pallas(xf, w1t, b1, mask, w2t, *, F1: int, F2: int, S2: int,
                      pitch: int, nt: int, Ho2: int, Wo2: int,
                      relu1: bool = True, bias2=None, res=None,
                      epilogue: Epilogue = Epilogue(), out_dtype=None,
                      interpret: Optional[bool] = None):
    """Fused conv->conv stack on flat slabs.

    xf: [G, Ci, rows*pitch*nt] (``stack_lanes``); w1t: tap-major [F1*F1,
    Cm, Ci]; b1: [Cm, 1] f32 (conv1's epilogue is bias[+ReLU] only); mask:
    [1, mid lanes] f32 validity of the staged mid positions; w2t: [F2*F2,
    Co, Cm]; ``bias2``/``res``/``epilogue`` follow the single-conv protocol,
    applied to conv2.  Returns [G, Co, PHo2*PWo2*nt] (post-pool when a pool
    is fused)."""
    G, Ci, Lx = xf.shape
    T1, Cm, _ = w1t.shape
    T2, Co, _ = w2t.shape
    odt = out_dtype or jnp.result_type(xf.dtype, w1t.dtype)
    PHo, PWo = flat.pool_geometry(Ho2, Wo2, epilogue.pool)
    Lm, total2, rows = stack_lanes(Ho2, S2, F1, F2, pitch, nt)
    assert mask.shape == (1, Lm), (mask.shape, Lm)
    assert Lx >= rows * pitch * nt, (Lx, rows, pitch, nt)
    Lo, Lp = Ho2 * Wo2 * nt, PHo * PWo * nt

    def whole(a):
        return pl.BlockSpec(a.shape, lambda g: (0,) * a.ndim)

    in_specs = [pl.BlockSpec((1, Ci, Lx), lambda g: (g, 0, 0)),
                whole(w1t), whole(b1), whole(mask), whole(w2t)]
    operands = [xf, w1t, b1, mask, w2t]
    has_sel2 = S2 > 1
    if has_sel2:
        sel2 = flat.selection(S2, Wo2, nt)
        in_specs.append(whole(sel2))
        operands.append(sel2)
    if epilogue.bias:
        in_specs.append(whole(bias2))
        operands.append(bias2)
    if epilogue.residual:
        in_specs.append(pl.BlockSpec((1, Co, Lo), lambda g: (g, 0, 0)))
        operands.append(res)
    has_sel = epilogue.pool is not None and epilogue.pool[1] > 1
    if has_sel:
        sel = flat.selection(epilogue.pool[1], PWo, nt)
        in_specs.append(whole(sel))
        operands.append(sel)
    isz = jnp.dtype(xf.dtype).itemsize
    wsz = jnp.dtype(w1t.dtype).itemsize
    nbytes = (Ci * Lx * isz + (T1 * Cm * Ci + T2 * Co * Cm) * wsz
              + 2 * Cm * Lm * 4 + Co * total2 * 4 + 3 * Co * Lo * 4
              + Co * Lp * 4)
    kern = functools.partial(_stack_kernel, F1=F1, F2=F2, S2=S2, pitch=pitch,
                             nt=nt, Ho2=Ho2, Wo2=Wo2, PWo=PWo,
                             relu1=relu1, epi=epilogue, has_sel=has_sel,
                             has_sel2=has_sel2)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((G, Co, Lp), odt),
        grid=(G,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Co, Lp), lambda g: (g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Cm, Lm), jnp.float32),
                        pltpu.VMEM((Co, total2), jnp.float32),
                        pltpu.VMEM((Co, Lo), jnp.float32)],
        compiler_params=flat.compiler_params(1, nbytes),
        interpret=resolve_interpret(interpret),
    )(*operands)
