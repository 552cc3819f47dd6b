"""jit'd wrappers: dtype-aware tile sizing + padding for arbitrary shapes."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.transpose.transpose import (transpose2d_batched_pallas,
                                               transpose2d_pallas)

LANES = 128
VMEM_BUDGET = 2 * 1024 * 1024      # per-block in+out working set


def _sublanes(dtype) -> int:
    return {2: 16, 4: 8, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def pick_blocks(M: int, N: int, dtype) -> tuple:
    """Largest aligned square-ish tile fitting the VMEM budget.  The doubled
    sublane count of 2-byte dtypes is the paper's float2 trick."""
    sl = _sublanes(dtype)
    item = jnp.dtype(dtype).itemsize
    bm, bn = sl, LANES
    # grow alternately while under budget and under the dims
    while True:
        grew = False
        if 2 * (2 * bm) * bn * item <= VMEM_BUDGET and bm * 2 <= max(M, sl):
            bm *= 2
            grew = True
        if 2 * bm * (2 * bn) * item <= VMEM_BUDGET and bn * 2 <= max(N, LANES):
            bn *= 2
            grew = True
        if not grew:
            return bm, bn


def _pad_to(x, m0: int, m1: int):
    p0 = (-x.shape[-2]) % m0
    p1 = (-x.shape[-1]) % m1
    if p0 or p1:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, p0), (0, p1)]
        x = jnp.pad(x, pad)
    return x


@partial(jax.jit, static_argnames=("interpret",))
def transpose2d(x, interpret: Optional[bool] = None):
    """[M, N] -> [N, M] via the tiled Pallas kernel."""
    M, N = x.shape
    bm, bn = pick_blocks(M, N, x.dtype)
    xp = _pad_to(x, bm, bn)
    y = transpose2d_pallas(xp, bm, bn, interpret=interpret)
    return y[:N, :M]


@partial(jax.jit, static_argnames=("interpret",))
def transpose2d_batched(x, interpret: Optional[bool] = None):
    """[B, M, N] -> [B, N, M]."""
    B, M, N = x.shape
    bm, bn = pick_blocks(M, N, x.dtype)
    xp = _pad_to(x, bm, bn)
    y = transpose2d_batched_pallas(xp, bm, bn, interpret=interpret)
    return y[:, :N, :M]
