"""Tiled 2-D transpose Pallas kernel — the paper's §IV.C fast layout
transform, TPU-native.

GPU original: flatten 4-D -> 2-D, shared-memory 32x32 tile transpose with
+1 padding (bank conflicts), float2 vectorized stores.
TPU adaptation: VMEM-resident (bm x bn) tiles aligned to the native
(sublane x lane) tiling — (8,128) f32 / (16,128) bf16; the in-register
transpose is a VPU shuffle emitted by Mosaic for ``.T`` on the block; the
float2 analogue is the doubled sublane count of 2-byte dtypes (handled by
dtype-aware block sizing in ops.py).  There is no bank-conflict padding on
TPU — the corresponding constraint is tile alignment, which the BlockSpecs
encode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _transpose_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].T


def transpose2d_pallas(x, bm: int, bn: int,
                       interpret: Optional[bool] = None):
    """x: [M, N] -> [N, M].  M % bm == 0 and N % bn == 0 (ops.py pads)."""
    M, N = x.shape
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        _transpose_kernel,
        out_shape=jax.ShapeDtypeStruct((N, M), x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (j, i)),
        interpret=resolve_interpret(interpret),
    )(x)


def _batched_kernel(x_ref, o_ref):
    o_ref[...] = jnp.swapaxes(x_ref[...], 1, 2)


def transpose2d_batched_pallas(x, bm: int, bn: int,
                               interpret: Optional[bool] = None):
    """x: [B, M, N] -> [B, N, M] (batched tile transpose)."""
    B, M, N = x.shape
    grid = (B, M // bm, N // bn)
    return pl.pallas_call(
        _batched_kernel,
        out_shape=jax.ShapeDtypeStruct((B, N, M), x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j))],
        out_specs=pl.BlockSpec((1, bn, bm), lambda b, i, j: (b, j, i)),
        interpret=resolve_interpret(interpret),
    )(x)
