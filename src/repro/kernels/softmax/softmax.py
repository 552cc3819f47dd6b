"""Fused softmax Pallas kernel — the paper's §V.B five-step fusion.

GPU original: five kernels (max / shift / exp / sum / normalize) each
round-tripping [N, C] through DRAM, with the inner reduction parallelized
via shared memory.  TPU adaptation: ONE kernel; a row-block (Bn x C) lives in
VMEM, the five steps run back-to-back on the VPU with f32 accumulation, and
the only HBM traffic is one read + one write of the matrix — the 5x-kernel
inter-step traffic is gone by construction.  Reductions across lanes/sublanes
(the warp-shuffle analogue) are emitted by Mosaic for jnp.max/sum on the
block.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)       # step 1
    e = jnp.exp(x - m)                           # steps 2+3
    s = jnp.sum(e, axis=-1, keepdims=True)       # step 4
    o_ref[...] = (e / s).astype(o_ref.dtype)     # step 5


def softmax_pallas(x, bn: int, interpret: Optional[bool] = None):
    """Row softmax of x: [N, C];  N % bn == 0 (ops pads)."""
    N, C = x.shape
    return pl.pallas_call(
        _softmax_kernel,
        out_shape=jax.ShapeDtypeStruct((N, C), x.dtype),
        grid=(N // bn,),
        in_specs=[pl.BlockSpec((bn, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, C), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
    )(x)


def _softmax_xent_kernel(x_ref, lab_ref, loss_ref):
    """Fused softmax + NLL for one row block (used by the CNN classifier)."""
    x = x_ref[...].astype(jnp.float32)
    lab = lab_ref[...]
    m = jnp.max(x, axis=-1)
    e = jnp.exp(x - m[:, None])
    lse = jnp.log(jnp.sum(e, axis=-1)) + m
    C = x.shape[-1]
    onehot = (lab[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, C), 1))
    gold = jnp.sum(jnp.where(onehot, x, 0.0), axis=-1)
    loss_ref[...] = lse - gold


def softmax_xent_pallas(x, labels, bn: int,
                        interpret: Optional[bool] = None):
    """Row-wise cross entropy: x [N, C], labels [N] -> loss [N]."""
    N, C = x.shape
    return pl.pallas_call(
        _softmax_xent_kernel,
        out_shape=jax.ShapeDtypeStruct((N,), jnp.float32),
        grid=(N // bn,),
        in_specs=[pl.BlockSpec((bn, C), lambda i: (i, 0)),
                  pl.BlockSpec((bn,), lambda i: (i,))],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        interpret=resolve_interpret(interpret),
    )(x, labels)
