"""jit'd wrappers with row-block sizing + padding."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.softmax.softmax import softmax_pallas, softmax_xent_pallas

VMEM_BUDGET = 4 * 1024 * 1024


def pick_bn(N: int, C: int, itemsize: int) -> int:
    bn = 8
    while 2 * (2 * bn) * C * max(itemsize, 4) <= VMEM_BUDGET and 2 * bn <= N:
        bn *= 2
    return bn


def _pad_rows(x, bn):
    p = (-x.shape[0]) % bn
    if p:
        pad = [(0, p)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, pad)
    return x


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _softmax_vjp(x, interpret):
    N, C = x.shape
    bn = pick_bn(N, C, x.dtype.itemsize)
    xp = _pad_rows(x, bn)
    return softmax_pallas(xp, bn, interpret=interpret)[:N]


def _softmax_fwd(x, interpret):
    y = _softmax_vjp(x, interpret)
    return y, y


def _softmax_bwd(interpret, y, g):
    yf = y.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    dx = (gf - (gf * yf).sum(-1, keepdims=True)) * yf
    return (dx.astype(y.dtype),)


_softmax_vjp.defvjp(_softmax_fwd, _softmax_bwd)


@partial(jax.jit, static_argnames=("interpret",))
def softmax(x, interpret: Optional[bool] = None):
    """Fused row softmax for [N, C] (paper §V.B single-kernel);
    differentiable via the closed-form softmax VJP on the saved output."""
    return _softmax_vjp(x, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def softmax_xent(x, labels, interpret: Optional[bool] = None):
    """Fused softmax+NLL rows: x [N, C], labels [N] -> [N] f32."""
    N, C = x.shape
    bn = pick_bn(N, C, 4)
    xp = _pad_rows(x, bn)
    lp = _pad_rows(labels, bn)
    return softmax_xent_pallas(xp, lp, bn, interpret=interpret)[:N]
