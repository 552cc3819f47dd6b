"""Layout-transform execution (paper §IV.C).

``apply_transform`` collapses common dim groups (layout.plan_transform) and
executes the minimal transpose; for the 2-D case it dispatches to the tiled
Pallas transpose kernel (repro.kernels.transpose) — the TPU analogue of the
paper's shared-memory tiled + vectorized transpose — or to XLA transpose when
running without kernels (e.g. inside jit-of-everything graphs).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.layout import TransformPlan, perm_between, plan_transform


def apply_transform(x, src: str, dst: str, *, use_pallas: bool = False,
                    interpret: Optional[bool] = None):
    """Re-layout ``x`` from layout ``src`` to ``dst``."""
    if src == dst:
        return x
    plan = plan_transform(src, dst)
    if plan.is_identity:
        return x
    cshape = plan.collapsed_shape(x.shape)
    xc = x.reshape(cshape)
    if use_pallas and plan.is_2d_transpose:
        from repro.kernels.transpose.ops import transpose2d
        yc = transpose2d(xc, interpret=interpret)
    elif use_pallas and len(plan.perm) == 3 and plan.perm[0] == 0:
        # batched 2-D transpose (e.g. NCHW -> NHWC)
        from repro.kernels.transpose.ops import transpose2d_batched
        yc = transpose2d_batched(xc, interpret=interpret)
    else:
        yc = jnp.transpose(xc, plan.perm)
    dims = dict(zip(src, x.shape))
    return yc.reshape(tuple(dims[d] for d in dst))


def naive_transform(x, src: str, dst: str):
    """The paper's Fig. 7a baseline: direct 4-D transpose, no collapsing."""
    return jnp.transpose(x, perm_between(src, dst))
