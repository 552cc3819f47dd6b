"""Multi-chip CNN serving mesh: batch-dim data parallelism over shard_map
(DESIGN.md §15).

One interpreter serves one chip; a mesh absorbs production traffic by
sharding the admitted batch data-parallel across ``devices`` chips and
running the SAME fused plan inside every shard.  The load-bearing planning
invariant is that the plan is produced for the *shard* batch, never the
global one: the paper's Nt threshold makes the CHWN/NCHW choice
batch-dependent (§IV.A), so a global batch of 128 on 8 chips is sixteen
images per chip — below the crossover where the 128-image plan lives.
``PlanCache`` therefore keys plans on (per-shard bucket, devices) and plans
at ``cfg.replace(batch=shard_bucket)``; this module provides the mesh, the
sharded executor, and the check that the invariant holds.

Kernels are untouched: ``forward_fused`` executes the per-shard plan
unchanged inside each shard — ``shard_map`` hands every device a
``[shard_bucket, C, H, W]`` block and replicated params, and conv/pool/fc/
softmax are all batch-row-independent, so the sharded output is the
unsharded output (no cross-shard reductions exist in inference).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import CNNConfig

# the single mesh axis batch rows shard over (matches the LM-side "data"
# axis naming so a future pod/model extension composes)
BATCH_AXIS = "data"


def shard_batch_for(global_batch: int, devices: int) -> int:
    """Per-shard batch: ceil so every request fits (the last shard's
    shortfall is padding, sliced off after the forward)."""
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    if global_batch < 1:
        raise ValueError(f"batch must be >= 1, got {global_batch}")
    return math.ceil(global_batch / devices)


def cnn_data_mesh(devices: Optional[int] = None) -> Mesh:
    """1-D data-parallel mesh over the first ``devices`` jax devices
    (default: all of them).  Serving needs no model axis — params are small
    enough to replicate and every request is independent."""
    avail = jax.devices()
    d = len(avail) if devices is None else devices
    if d < 1 or d > len(avail):
        plat = avail[0].platform
        hint = (" (on the CPU, XLA_FLAGS=--xla_force_host_platform_"
                "device_count=N forces host devices)" if plat == "cpu" else "")
        raise ValueError(f"devices={d} but jax sees {len(avail)} {plat} "
                         f"device(s){hint}")
    return Mesh(np.array(avail[:d]), (BATCH_AXIS,))


def replicate_params(params, mesh: Mesh):
    """Replicate the param tree onto every mesh device (pure data
    parallelism: weights are read-only at serving time)."""
    return jax.device_put(params, NamedSharding(mesh, P()))


def forward_fused_sharded(params, x, shard_cfg: CNNConfig, plan,
                          mesh: Mesh, *, impl: str = "pallas",
                          interpret: Optional[bool] = None):
    """Data-parallel ``forward_fused``: ``x`` is the GLOBAL padded batch
    ``[shard_cfg.batch * devices, C, H, W]``; each shard executes the fused
    plan on its own ``shard_cfg.batch`` rows with replicated params.
    Returns the global ``[N, classes]`` probabilities.

    The plan MUST be the per-shard plan (``shard_cfg.batch`` is the shard
    batch) — ``verify_shard_plan`` is the planner-side check.  Stats are not
    returned: modeled per-chip traffic is shape-only arithmetic, accounted
    once outside the mesh (``jax.eval_shape`` at the shard config)."""
    from repro.cnn.network import forward_fused
    devices = mesh.shape[BATCH_AXIS]
    if x.shape[0] != shard_cfg.batch * devices:
        raise ValueError(
            f"global batch {x.shape[0]} != shard batch {shard_cfg.batch} x "
            f"{devices} devices; pad to the shard bucket before sharding")

    def _shard(p, xs):
        y, _ = forward_fused(p, xs, shard_cfg, plan, impl=impl,
                             interpret=interpret)
        return y

    f = jax.shard_map(_shard, mesh=mesh, in_specs=(P(), P(BATCH_AXIS)),
                      out_specs=P(BATCH_AXIS), check_vma=False)
    return f(params, x)


class ShardPlanError(AssertionError):
    """A sharded bucket is executing a plan that was not produced for its
    shard batch (the global-batch plan leaked through)."""


def verify_shard_plan(plan, cfg: CNNConfig, shard_bucket: int, *,
                      dtype: str = "float32", policy: str = "uniform",
                      stack: str = "auto") -> None:
    """Roofline check (DESIGN.md §15): assert ``plan`` is byte-identical to
    a fresh plan at the SHARD batch — layouts, conv signature, and modeled
    fused bytes all match, so any per-shard Nt flip was taken rather than
    inherited from the global batch.  Deterministic planner arithmetic;
    called from tests and the scaling bench, not the serving hot path."""
    from repro.cnn.network import plan_network_fused
    fresh = plan_network_fused(cfg.replace(batch=shard_bucket), dtype=dtype,
                               policy=policy, stack_policy=stack)
    if (plan.layouts != fresh.layouts
            or plan.conv_signature != fresh.conv_signature
            or plan.fused_bytes != fresh.fused_bytes):
        raise ShardPlanError(
            f"plan for shard bucket {shard_bucket} is not the shard-batch "
            f"plan: {plan.conv_signature} ({plan.fused_bytes}B) vs fresh "
            f"{fresh.conv_signature} ({fresh.fused_bytes}B) — the planner "
            f"must plan for the shard batch, not the global one")


def shard_flip(cfg: CNNConfig, global_batch: int, devices: int, *,
               dtype: str = "float32") -> Tuple[str, str]:
    """(global-batch signature, shard-batch signature) for a fixed global
    batch — shows where sharding itself flips the layout choice (per-shard
    N drops below Nt while the global N sits above it)."""
    from repro.cnn.network import plan_network_fused
    gsig = plan_network_fused(cfg.replace(batch=global_batch),
                              dtype=dtype).conv_signature
    ssig = plan_network_fused(
        cfg.replace(batch=shard_batch_for(global_batch, devices)),
        dtype=dtype).conv_signature
    return gsig, ssig
