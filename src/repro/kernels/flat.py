"""Flat spatial tiles: the in-VMEM form every conv/pool kernel computes on.

Mosaic compiles 2-D work well — matmuls on ``[channels, lanes]`` tiles and
static lane slices at any offset — and refuses what the 4-D formulation
needed: strided value slices, 4-D transposes and blocks whose last two dims
are neither tile-aligned nor whole.  So the kernels see activations as

    flat[g, c, (r * pitch + col) * nt + j]        sample n = g * nt + j

one ``[C, rows * pitch * nt]`` slab per group of ``nt`` samples (``nt = 1``
for the per-sample NCHW engine, a small lane-interleaved group for CHWN).
Blocks are whole (group, channel-block) slabs, so every BlockSpec is legal.

A stride-1 conv is then one matmul per filter tap: output position
``(r, col)`` reads input lane ``((r + dy) * pitch + col + dx) * nt``, i.e.
the whole output slab for tap ``(dy, dx)`` is the input slab shifted by
``(dy * pitch + dx) * nt`` lanes.  The result is "wide" (``pitch`` columns
per row, the last ``pitch - Wo`` garbage); ``compact_rows`` keeps the valid
columns.  Strides > 1 are removed before the kernel by space-to-depth
(``prep``/``s2d_weights``: an exact rewrite into a stride-1 conv with
``ceil(F / S)`` taps over ``C * S * S`` channels).  Lane subsampling (pool
strides, a stacked conv's stride) is a matmul against a 0/1 selection
matrix at HIGHEST precision, which reproduces every value exactly.

``prep``/``unprep`` are the XLA passes on either side of a kernel: pad,
space-to-depth, re-layout from the producer's and to the consumer's layout,
in one fused copy each.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.shapes import pool_out_hw

HIGHEST = jax.lax.Precision.HIGHEST

# CHWN engines interleave up to this many samples on the lanes of one slab
# (NCHW engines run one sample per slab) ...
CHWN_NT = 8
# ... while the slab stays within this many lanes: Mosaic unrolls every
# value op per vreg, so the slab width bounds compile time and VMEM
SLAB_LANES = 2048

# output lanes a conv computes per loop step (a multiple of 128)
CHUNK = 512

# scoped-VMEM request bounds for the flat kernels (v5e has 128 MiB of VMEM)
_VMEM_FLOOR = 32 * (1 << 20)
_VMEM_CEIL = 100 * (1 << 20)


def sublane_multiple(dtype) -> int:
    """Channel padding that keeps a slab's second-minor dim tile-aligned."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def ceil_to(v: int, m: int) -> int:
    return -(-v // m) * m


def group_tile(N: int, engine: str, nt: int, lanes: int) -> int:
    """Samples per slab: 1 for NCHW; for CHWN the largest power of two up
    to ``min(nt, CHWN_NT, N)`` whose slab of ``lanes`` per sample fits
    ``SLAB_LANES``."""
    if engine == "NCHW":
        return 1
    g = 1
    while 2 * g <= min(nt, CHWN_NT, N) and 2 * g * lanes <= SLAB_LANES:
        g *= 2
    return g


def to_nchw(x, layout: str):
    return jnp.transpose(x, (3, 0, 1, 2)) if layout == "CHWN" else x


def prep(x, layout: str, *, pad: int, stride: int, rows: int, cols: int,
         nt: int, cmult: int):
    """[N,C,H,W] / [C,H,W,N] -> flat [G, C', rows*cols*nt].

    Pads ``pad`` on each spatial side, crops or zero-fills to
    ``stride*rows`` x ``stride*cols``, applies space-to-depth by ``stride``
    (channel ``c*S*S + a*S + b`` holds phase (a, b)), pads channels to a
    ``cmult`` multiple and N to an ``nt`` multiple, then interleaves ``nt``
    samples on the lanes.  One XLA copy."""
    x = to_nchw(x, layout)
    N, C, H, W = x.shape
    S = stride
    Ht, Wt = S * rows, S * cols
    x = x[:, :, :max(0, Ht - pad), :max(0, Wt - pad)]
    h, w = x.shape[2], x.shape[3]
    G = -(-N // nt)
    x = jnp.pad(x, ((0, G * nt - N), (0, 0), (pad, Ht - pad - h),
                    (pad, Wt - pad - w)))
    if S > 1:
        x = x.reshape(G * nt, C, rows, S, cols, S)
        x = jnp.transpose(x, (0, 1, 3, 5, 2, 4))
        x = x.reshape(G * nt, C * S * S, rows, cols)
    Cs = x.shape[1]
    x = jnp.pad(x, ((0, 0), (0, ceil_to(Cs, cmult) - Cs), (0, 0), (0, 0)))
    x = x.reshape(G, nt, x.shape[1], rows, cols)
    x = jnp.transpose(x, (0, 2, 3, 4, 1))
    return x.reshape(G, x.shape[1], rows * cols * nt)


def unprep(y, N: int, C: int, rows: int, cols: int, nt: int, layout: str):
    """flat [G, C', rows*cols*nt] -> ``layout`` ([N,C,rows,cols] or
    [C,rows,cols,N]), dropping channel and sample padding."""
    G = y.shape[0]
    y = y.reshape(G, y.shape[1], rows, cols, nt)[:, :C]
    if layout == "CHWN":
        y = jnp.transpose(y, (1, 2, 3, 0, 4)).reshape(C, rows, cols, G * nt)
        return y[..., :N]
    y = jnp.transpose(y, (0, 4, 1, 2, 3)).reshape(G * nt, C, rows, cols)
    return y[:N]


def s2d_weights(w, stride: int, cmult: int):
    """Canonical [Co, Ci, F, F] -> tap-major [F'*F', Co, Ci'] for the
    space-to-depth input ``prep`` makes (F' = ceil(F / stride))."""
    Co, Ci, F, _ = w.shape
    S = stride
    Fq = -(-F // S)
    if S > 1:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, Fq * S - F), (0, Fq * S - F)))
        w = w.reshape(Co, Ci, Fq, S, Fq, S)
        w = jnp.transpose(w, (0, 1, 3, 5, 2, 4)).reshape(Co, Ci * S * S,
                                                         Fq, Fq)
    Cs = w.shape[1]
    w = jnp.pad(w, ((0, 0), (0, ceil_to(Cs, cmult) - Cs), (0, 0), (0, 0)))
    return jnp.transpose(w, (2, 3, 0, 1)).reshape(Fq * Fq, Co, w.shape[1])


def selection(stride: int, cols_out: int, nt: int):
    """0/1 matrix picking lane ``(c*stride)*nt + j`` into ``c*nt + j``:
    [((cols_out-1)*stride + 1)*nt, cols_out*nt]."""
    rows = ((cols_out - 1) * stride + 1) * nt
    sel = np.zeros((rows, cols_out * nt), np.float32)
    for c in range(cols_out):
        for j in range(nt):
            sel[c * stride * nt + j, c * nt + j] = 1.0
    return jnp.asarray(sel)


def vmem_limit(nbytes: int) -> int:
    """Scoped-VMEM request: double-buffered blocks plus working values."""
    return int(min(max(2 * nbytes + (8 << 20), _VMEM_FLOOR), _VMEM_CEIL))


def compiler_params(n_axes: int, nbytes: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_axes,
        vmem_limit_bytes=vmem_limit(nbytes))


# ---------------------------------------------------------------------------
# in-kernel helpers (traced inside pallas kernels)
# ---------------------------------------------------------------------------

def tap_offsets(F: int, pitch: int, nt: int):
    """Lane offset of each filter tap (tap-major order) in a flat slab."""
    return [(dy * pitch + dx) * nt for dy in range(F) for dx in range(F)]


def conv_lanes(out_rows: int, F: int, pitch: int, nt: int):
    """(accumulator lanes, input rows) of a wide stride-1 conv producing
    ``out_rows`` rows: the accumulator is padded to whole ``CHUNK``s, and
    the input slab covers the last chunk's window plus the taps' halo."""
    total = ceil_to(out_rows * pitch * nt, CHUNK)
    halo = ceil_to(tap_offsets(F, pitch, nt)[-1], 128)
    return total, -(-(total + halo) // (pitch * nt))


def conv_taps(load, w_ref, acc_ref, F: int, pitch: int, nt: int,
              post=None):
    """Wide stride-1 conv into ``acc_ref`` (f32 [Co, lanes], lanes a
    ``CHUNK`` multiple): the sum over taps of ``w[t] @ slab shifted by the
    tap``, one ``CHUNK`` of output lanes per loop step.  ``load(start,
    size)`` returns the [C, size] input window at a 128-aligned ``start``;
    each tap is a static slice of it.  ``w_ref`` is tap-major [F*F, Co, C].
    ``post(acc, start)`` transforms a finished chunk before it is stored.
    The loop keeps Mosaic's unrolled code (and compile time) to one
    chunk."""
    offs = tap_offsets(F, pitch, nt)
    halo = ceil_to(offs[-1], 128)

    def body(i, carry):
        start = pl.multiple_of(i * CHUNK, CHUNK)
        win = load(start, CHUNK + halo)
        acc = None
        for t, off in enumerate(offs):
            w = w_ref[t]
            xs = win[:, off:off + CHUNK]
            if jnp.issubdtype(xs.dtype, jnp.integer):
                # int8 storage: the cast IS the dequant (scale folded in w)
                xs = xs.astype(w.dtype)
            cdt = jnp.result_type(xs.dtype, w.dtype)
            xs, w = xs.astype(cdt), w.astype(cdt)
            prec = HIGHEST if cdt == jnp.float32 else None
            d = jnp.dot(w, xs, preferred_element_type=jnp.float32,
                        precision=prec)
            acc = d if acc is None else acc + d
        if post is not None:
            acc = post(acc, start)
        acc_ref[:, pl.ds(start, CHUNK)] = acc
        return carry

    jax.lax.fori_loop(0, acc_ref.shape[1] // CHUNK, body, 0)


def pick(v, sel):
    """Exact lane subsample of ``v`` (f32) by a 0/1 selection matrix."""
    return jnp.dot(v, sel, preferred_element_type=jnp.float32,
                   precision=HIGHEST)


def compact_rows(acc_ref, y_ref, rows: int, stride: int, pitch: int,
                 cols: int, nt: int, sel=None):
    """Write the valid ``rows x cols`` window of a wide stride-1 result in
    ``acc_ref`` into
    ``y_ref`` [C, rows*cols*nt], taking every ``stride``-th row and column
    (``sel`` = ``selection(stride, cols, nt)`` when stride > 1)."""
    span = ((cols - 1) * stride + 1) * nt
    for r in range(rows):
        off = r * stride * pitch * nt
        v = acc_ref[:, off:off + span]
        if stride > 1:
            v = pick(v, sel)
        y_ref[:, r * cols * nt:(r + 1) * cols * nt] = v


def pool_rows(load, store, W: int, nt: int, F: int, S: int, op: str,
              Ho: int, Wo: int, sel=None):
    """F x F / S pooling of a compact [C, rows*W*nt] slab read through
    ``load(off, size)``; each f32 output row goes to ``store(row, value)``."""
    rl = W * nt
    span = ((Wo - 1) * S + 1) * nt
    comb = jnp.maximum if op == "max" else jnp.add
    for r in range(Ho):
        row = None
        for dy in range(F):
            v = load((r * S + dy) * rl, rl).astype(jnp.float32)
            row = v if row is None else comb(row, v)
        win = None
        for dx in range(F):
            v = row[:, dx * nt:dx * nt + span]
            win = v if win is None else comb(win, v)
        if S > 1:
            win = pick(win, sel)
        if op == "avg":
            win = win / (F * F)
        store(r, win)


def epilogue(y, *, bias=None, res=None, relu: bool = False):
    """bias -> residual add -> ReLU on an f32 compact slab."""
    if bias is not None:
        y = y + bias
    if res is not None:
        y = y + res.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y


def pool_geometry(H: int, W: int, pool: Optional[Tuple[int, int, str]]):
    """Output (rows, cols) after an optional fused pool."""
    if pool is None:
        return H, W
    return pool_out_hw(H, pool[0], pool[1]), pool_out_hw(W, pool[0], pool[1])
