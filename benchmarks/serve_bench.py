"""Batch-adaptive serving sweep (ISSUE 3 + ISSUE 4 + ISSUE 5 acceptance).

Five claims, per network:

  * **flip** — sweeping batch 1 -> 256, the cached planner selects different
    conv layouts for at least two buckets of the same network (the paper's
    Nt threshold in action);
  * **dtype** — the same sweep at the reduced-precision storage dtype
    (bf16): modeled fused HBM bytes drop ~2x vs fp32 (the element-size
    lever), and at least one (network, bucket) point is assigned DIFFERENT
    conv layouts under bf16 than fp32 — the sublane width doubling moves the
    crossover, it doesn't just scale the bytes;
  * **mixed** — the per-layer (layout, dtype) DP (``--dtype-policy mixed``):
    modeled fused HBM bytes strictly below the uniform reduced-precision
    plan wherever the network has int8-eligible interior chains (AlexNet:
    conv2-4 store int8, ``b888b``), with >= 2 distinct storage dtypes
    across conv layers, and the int8 fused forward matching the fp32
    reference within the documented tolerance (``INT8_FORWARD_ATOL``);
  * **cache** — replaying a bursty request stream whose batch sizes repeat,
    the ``PlanCache`` replans 0 times after each bucket's first sight
    (``replans_repeat=0``), with hits accumulating;
  * **numerics** — executing a small batch under its *bucket's* padded plan
    matches the exact-batch plan's outputs on the real rows to <= 1e-5
    (quick-size networks, real fused Pallas kernels for lenet);
  * **scale** — weak-scaling the serving mesh (ISSUE 10): global batch
    B0*D over D in {1,2,4,8} chips holds the per-shard bucket at B0, so
    modeled per-chip HBM bytes stay exactly flat while modeled img/s grows
    linearly, every point passing ``verify_shard_plan`` (the plan cached
    under the (bucket, devices) key IS the shard-batch plan) — plus the
    shard-flip row showing where per-shard N crossing under Nt changes the
    layout the global batch would have picked.

Derived columns: ``conv_layouts`` per bucket/dtype, ``modeled_MB``
(fused-engine HBM bytes at the bucket size), ``bytes_ratio`` (fp32/bf16),
``dtype_flip``, ``distinct``/``flip``, ``replans_repeat``, ``hit_rate``,
``maxdiff``.  Structured trajectory records go to ``BENCH_serve.json`` via
``benchmarks/run.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, record
from repro.configs.cnn_networks import CNN_BUILDERS, CNN_CONFIGS, reduced_cnn
from repro.cnn.layers import init_cnn
from repro.cnn.network import forward_fused, input_shape, plan_network_fused
from repro.perfmodel import calibrate
from repro.dtypes import canon_dtype, dtype_bytes
from repro.quant import INT8_FORWARD_ATOL
from repro.serve import PlanCache, pad_to_bucket

BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)
# bursty stream with repeating sizes: every bucket recurs at least once
STREAM = (1, 3, 7, 1, 4, 64, 9, 130, 2, 128, 64, 5, 255, 16, 3, 100, 12)


def run(quick: bool = True, dtype: str = "bfloat16"):
    """``dtype`` is the reduced-precision fast path compared against the
    fp32 baseline; pass "float32" to skip the dtype-comparison section."""
    dtype = canon_dtype(dtype)
    names = ["lenet", "alexnet", "resnet18"] if quick else list(CNN_CONFIGS)
    dtypes = ["float32"] + ([dtype] if dtype != "float32" else [])
    th = {d: calibrate(dtype_bytes=dtype_bytes(d)) for d in dtypes}
    for name in names:
        cfg0 = CNN_CONFIGS[name]
        cache = PlanCache(thresholds=th)

        # (a) full-size bucket sweep per dtype: where does the layout flip
        # with batch, and where does it flip with element size?
        sigs = {d: {} for d in dtypes}
        mb = {d: {} for d in dtypes}
        for d in dtypes:
            for b in BUCKETS:
                plan, bkt, _ = cache.fused_plan(cfg0, b, dtype=d)
                sigs[d][bkt] = plan.conv_signature
                mb[d][bkt] = plan.fused_bytes
                emit(f"serve/{name}/{d}/bucket{bkt}", 0.0,
                     f"conv_layouts={sigs[d][bkt]};"
                     f"modeled_MB={plan.fused_bytes / 1e6:.1f}")
                record(f"serve/{name}/bucket{bkt}", network=name, dtype=d,
                       bucket=bkt, conv_layouts=sigs[d][bkt],
                       modeled_bytes=plan.fused_bytes,
                       standalone_adds=plan.standalone_adds)
        distinct = len(set(sigs["float32"].values()))
        emit(f"serve/{name}/flip", 0.0,
             f"distinct={distinct};flip={distinct >= 2}")

        if dtype != "float32":
            # element-size lever: fused bytes at the network's native batch.
            # Stacking (DESIGN.md §12) is held off on BOTH sides — fp32 and
            # bf16 plans can fuse different stacks, which would contaminate
            # a ratio that exists to isolate the dtype lever alone.
            bkt0 = cache.bucket(cfg0.batch)
            bcfg = cfg0.replace(batch=bkt0)
            ratio = (plan_network_fused(bcfg, dtype="float32",
                                        stack_policy="off").fused_bytes
                     / plan_network_fused(bcfg, dtype=dtype,
                                          stack_policy="off").fused_bytes)
            flips = [b for b in sigs["float32"]
                     if sigs["float32"][b] != sigs[dtype][b]]
            emit(f"serve/{name}/dtype", 0.0,
                 f"dtype={dtype};bytes_ratio={ratio:.2f};"
                 f"ok={ratio >= 1.8};dtype_flip_buckets={flips};"
                 f"dtype_flip={bool(flips)}")
            record(f"serve/{name}/dtype", network=name, dtype=dtype,
                   bucket=bkt0, bytes_ratio=ratio,
                   fp32_bytes=mb["float32"][bkt0],
                   reduced_bytes=mb[dtype][bkt0],
                   dtype_flip_buckets=flips)

        # (a'') per-layer mixed-dtype DP (ISSUE 5): interior conv chains
        # store int8 where both casts fold; bytes must land strictly below
        # the uniform reduced-precision plan on int8-eligible networks
        base = dtype                   # the mixed plan's float base dtype
        bkt0 = cache.bucket(cfg0.batch)
        pm, _, _ = cache.fused_plan(cfg0, cfg0.batch, dtype=base,
                                    policy="mixed")
        uni_b = mb[base][bkt0]
        mratio = uni_b / max(pm.fused_bytes, 1)
        emit(f"serve/{name}/mixed", 0.0,
             f"base={base};conv_dtypes={pm.dtype_signature};"
             f"uniform_MB={uni_b / 1e6:.1f};"
             f"mixed_MB={pm.fused_bytes / 1e6:.1f};"
             f"bytes_ratio={mratio:.2f};"
             f"distinct={pm.distinct_conv_dtypes};"
             f"below_uniform={pm.fused_bytes < uni_b}")
        record(f"serve/{name}/mixed", network=name, dtype=base,
               bucket=bkt0, policy="mixed",
               dtype_signature=pm.dtype_signature,
               uniform_bytes=uni_b, mixed_bytes=pm.fused_bytes,
               distinct_dtypes=pm.distinct_conv_dtypes)

        # (b) replay the bursty stream: repeats must not replan
        first_sight = cache.planner_calls
        seen = set(cache.per_key)
        replans_repeat = 0
        for b in STREAM:
            bkt = cache.bucket(b)
            known = any(k.bucket == bkt and k.dtype == "float32"
                        for k in seen)
            before = cache.planner_calls
            _, _, hit = cache.fused_plan(cfg0, b)
            if known and cache.planner_calls != before:
                replans_repeat += 1
            seen = set(cache.per_key)
        emit(f"serve/{name}/cache", 0.0,
             f"planner_calls={cache.planner_calls};"
             f"first_sight={first_sight};replans_repeat={replans_repeat};"
             f"hit_rate={cache.stats.hit_rate:.2f}")

        # (c) quick-size numerics: padded bucket plan == exact plan on the
        # real rows (fused Pallas for lenet; decomposed-xla for big nets).
        # Branching nets downscale through their builder so merge shapes
        # stay consistent at the quick size.
        impl = "pallas" if cfg0.image_hw <= 32 else "xla"
        if cfg0.image_hw <= 32:
            cfgq = cfg0
        elif cfg0.name in CNN_BUILDERS:
            cfgq = reduced_cnn(cfg0, batch=cfg0.batch)
        else:
            cfgq = cfg0.replace(image_hw=96)
        params = init_cnn(jax.random.PRNGKey(0), cfgq.replace(batch=1))
        worst = 0.0
        for B in (1, 3, 6):
            bkt = cache.bucket(B)
            bplan, _, _ = cache.fused_plan(cfgq, B)
            eplan = plan_network_fused(cfgq.replace(batch=B))
            x = jax.random.normal(jax.random.PRNGKey(B),
                                  input_shape(cfgq.replace(batch=B)),
                                  jnp.float32)
            yb, _ = forward_fused(params, pad_to_bucket(x, bkt),
                                  cfgq.replace(batch=bkt), bplan, impl=impl)
            ye, _ = forward_fused(params, x, cfgq.replace(batch=B), eplan,
                                  impl=impl)
            worst = max(worst, float(jnp.abs(yb[:B] - ye).max()))
        emit(f"serve/{name}/numerics", 0.0,
             f"impl={impl};maxdiff={worst:.2e};ok={worst <= 1e-5}")

        # (c') int8 numerics: the mixed plan at base fp32 isolates the
        # quantization error — softmax outputs must track the uniform fp32
        # reference within the documented tolerance
        B = 3
        bq = cfgq.replace(batch=B)
        mplan = plan_network_fused(bq, policy="mixed")
        xq = jax.random.normal(jax.random.PRNGKey(B), input_shape(bq),
                               jnp.float32)
        ym, _ = forward_fused(params, xq, bq, mplan, impl=impl)
        ye, _ = forward_fused(params, xq, bq, plan_network_fused(bq),
                              impl=impl)
        mdiff = float(jnp.abs(ym - ye).max())
        emit(f"serve/{name}/mixed_numerics", 0.0,
             f"impl={impl};conv_dtypes={mplan.dtype_signature};"
             f"maxdiff={mdiff:.2e};tol={INT8_FORWARD_ATOL};"
             f"ok={mdiff <= INT8_FORWARD_ATOL}")
        record(f"serve/{name}/mixed_numerics", network=name,
               dtype="float32", policy="mixed", impl=impl,
               dtype_signature=mplan.dtype_signature)

        # (d) resilience (ISSUE 9 / DESIGN.md §14): the same serving stack
        # under seeded fault injection — a kernel-fault rate on every rung —
        # must serve 100% of the stream by degrading down the ladder and
        # re-queueing fully-failed batches.  ``dropped_requests`` is an
        # exact-zero trajectory counter (check_trajectory COUNT_FIELDS).
        from repro.launch.cnn_serve import CNNServer, ImageRequest
        from repro.runtime.resilience import FaultInjector
        srv = CNNServer(name, reduced=True, max_bucket=8, impl="xla",
                        calibration="analytic",
                        injector=FaultInjector(seed=0,
                                               rates={"kernel": 0.5}))
        rng = np.random.default_rng(0)
        c, h = srv.cfg.in_channels, srv.cfg.image_hw
        reqs = [ImageRequest(i, rng.standard_normal(
            (c, h, h)).astype(np.float32)) for i in range(20)]
        done = srv.run(reqs)
        dropped = len(reqs) - len(done)
        counts = srv.incidents.counts
        emit(f"serve/{name}/resilience", 0.0,
             f"incidents={srv.incidents.total};"
             f"kernel_faults={counts.get('kernel_fault', 0)};"
             f"requeues={counts.get('requeue', 0)};"
             f"dropped_requests={dropped};ok={dropped == 0}")
        record(f"serve/{name}/resilience", network=name, dtype="float32",
               impl="xla", incidents=srv.incidents.total,
               dropped_requests=dropped)

        # (e) multi-chip weak scaling (ISSUE 10 / DESIGN.md §15): a global
        # batch of B0*D sharded over D chips keeps the per-shard bucket at
        # B0, so every scale point executes the SAME per-shard plan —
        # modeled per-chip HBM bytes are exactly flat while modeled img/s
        # scales linearly with D.  Rows are planner arithmetic only (no
        # device execution), so a 1-device CI host regenerates them
        # byte-identically; the sharded-vs-unsharded numerics live in
        # tests/test_cnn_mesh.py under forced host devices.
        from repro.distributed.cnn_mesh import (shard_batch_for, shard_flip,
                                                verify_shard_plan)
        B0 = 16
        scache = PlanCache(thresholds=th)
        ips0 = pcb0 = None
        for D in (1, 2, 4, 8):
            g = B0 * D
            plan, bkt, _ = scache.fused_plan(cfg0, g, devices=D)
            assert bkt == shard_batch_for(g, D) == B0
            # roofline check: the cached plan IS the shard-batch plan
            verify_shard_plan(plan, cfg0, bkt)
            ips = bkt * D / plan.total_s
            ips0 = ips if ips0 is None else ips0
            pcb0 = plan.fused_bytes if pcb0 is None else pcb0
            flat = abs(plan.fused_bytes - pcb0) <= 0.05 * pcb0
            emit(f"serve/{name}/scale/d{D}", 0.0,
                 f"devices={D};global_batch={g};shard_bucket={bkt};"
                 f"conv_layouts={plan.conv_signature};"
                 f"per_chip_MB={plan.fused_bytes / 1e6:.1f};"
                 f"img_s_modeled={ips:.1f};speedup={ips / ips0:.2f};"
                 f"planner_calls={scache.planner_calls};"
                 f"per_chip_flat={flat};ok={flat and ips >= ips0}")
            record(f"serve/{name}/scale/d{D}", network=name,
                   dtype="float32", bucket=bkt, devices=D,
                   conv_layouts=plan.conv_signature,
                   per_chip_bytes=plan.fused_bytes,
                   modeled_bytes=plan.fused_bytes * D,
                   img_s_modeled=ips, planner_calls=scache.planner_calls)
        # one plan per (shard bucket, devices) key: a re-admitted global
        # batch at the same D must hit, never replan
        before = scache.planner_calls
        _, _, hit = scache.fused_plan(cfg0, B0 * 8, devices=8)
        emit(f"serve/{name}/scale/replan", 0.0,
             f"planner_calls={scache.planner_calls};hit={hit};"
             f"replans_repeat={scache.planner_calls - before}")

        # where sharding itself flips the layout: per-shard N under a fixed
        # global batch drops below the calibrated Nt threshold
        gsig, ssig = shard_flip(cfg0, 128, 8)
        emit(f"serve/{name}/scale/flip", 0.0,
             f"global_batch=128;devices=8;global_sig={gsig};"
             f"shard_sig={ssig};shard_flip={gsig != ssig}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--dtype", default="bf16",
                    choices=["float32", "fp32", "bfloat16", "bf16"],
                    help="reduced-precision path compared against the fp32 "
                         "baseline (float32: baseline only)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(quick=not args.full, dtype=args.dtype)
