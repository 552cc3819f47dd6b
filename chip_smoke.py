"""Chip smoke test: serve full-size AlexNet on a TPU through compiled Pallas
kernels and check every served output against an XLA reference.

    python3 chip_smoke.py              # one chip: the serving path
    python3 chip_smoke.py --chips 4    # four chips: sharded vs unsharded

One process, no subprocesses; JAX is touched only here.  Exits non-zero,
with no result line, when JAX finds no TPU or any phase fails.  The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

One chip: ``CNNServer("alexnet")`` at its published size (227 px, 1000
classes), Pallas engine, fp32, uniform policy, measured calibration, a
fresh plan cache.  Seeded requests arrive in batches of 1, 8 and 128 (one
bucket each); across them the served plans must use both conv engines
(``C`` and ``N`` in ``conv_signature``) and AlexNet's conv3->conv4 stack.
Every batch must be served by rung 0 with no incident.  Per bucket it
prints the first call's seconds, the compile seconds (first call minus the
steady median) and the steady milliseconds per batch (median of 3, host
loop included) — for information only, not a benchmark.

Tolerance: every served output is checked twice against the unfused
float32 reference (``forward(..., plan_network(cfg, "cudnn"),
impl="xla")``) run on the chip at HIGHEST matmul/conv precision:

* probabilities, within ``RTOL`` relative to the row's largest
  probability.  With random weights the logits are small and the softmax
  is close to uniform, so this reads about the absolute logit error;
* logits, within ``RTOL`` of the reference row's logit spread (largest
  distance from the row mean).  The served output is the softmax, which
  fixes the logits up to a per-row constant, so both sides' logits are
  ``log p`` centered per row.

Both sides accumulate in f32 at full precision and differ only in
summation order (about 1e-6 of a logit).  The smallest fault seen so far
is a matmul left at one bf16 pass (2^-9 relative): the f32 fc layers at
XLA's default precision read 3.7e-3 on the probability metric on a v5e
(bucket 8); emulated on a CPU they read 4.1e-3 (probabilities) and
5.7e-3 (logits).  Flipping or shifting one conv's taps reads 0.13-0.22
and 0.22-0.37.  With seed-0 weights the logits' spread is about 1.0 and
their standard deviation 0.32.  1e-3 sits between noise and faults.

Four chips (``--chips 4``): ``CNNServer(devices=4)`` against
``CNNServer(devices=1)`` on the same 32 requests.  The sharded outputs must
match the unsharded ones within ``RTOL``, the mesh must hold four distinct
devices, and each shard of the sharded output must sit on its own chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

RTOL = 1e-3
PROB_FLOOR = 1e-30
SIZES = (1, 8, 128)
MESH_REQUESTS = 32
HERE = os.path.dirname(os.path.abspath(__file__))


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def device_info():
    import jax
    devs = jax.devices()
    print(f"jax {jax.__version__}", flush=True)
    print(f"devices {devs}", flush=True)
    d = devs[0]
    print(f"platform={d.platform} device_kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        fail(f"no TPU found (JAX platform is {d.platform!r})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def make_requests(cfg, n: int, seed: int):
    import numpy as np
    from repro.launch.cnn_serve import ImageRequest
    rng = np.random.default_rng(seed)
    c, h = cfg.in_channels, cfg.image_hw
    return [ImageRequest(i, rng.standard_normal((c, h, h))
                         .astype(np.float32)) for i in range(n)]


def reference_probs(params, images, cfg):
    """Unfused float32 XLA reference at full precision, on the chip."""
    import jax
    import numpy as np
    from repro.cnn.network import forward, plan_network
    bcfg = cfg.replace(batch=len(images))
    layouts = plan_network(bcfg, "cudnn")
    with jax.default_matmul_precision("highest"):
        f = jax.jit(lambda p, x: forward(p, x, bcfg, layouts,
                                         impl="xla")[0])
        y = f(params, np.stack(images))
    return np.asarray(y, np.float32)


def rel_diff(got, ref) -> float:
    """Largest probability difference, relative to each row's maximum."""
    import numpy as np
    scale = np.abs(ref).max(axis=1, keepdims=True)
    return float((np.abs(got - ref) / scale).max())


def logit_diff(got, ref) -> float:
    """Largest logit difference, relative to each reference row's spread.
    Logits are recovered as ``log p`` centered per row (probabilities below
    ``PROB_FLOOR`` are clipped on both sides alike)."""
    import numpy as np

    def logits(p):
        z = np.log(np.maximum(p.astype(np.float64), PROB_FLOOR))
        return z - z.mean(axis=1, keepdims=True)
    zg, zr = logits(got), logits(ref)
    spread = np.abs(zr).max(axis=1, keepdims=True)
    return float((np.abs(zg - zr) / spread).max())


def check_outputs(probs, ref, what: str):
    """(probability diff, logit diff), failing past ``RTOL`` on either."""
    import numpy as np
    if probs.shape != ref.shape:
        fail(f"{what}: shape {probs.shape} != reference {ref.shape}")
    if not np.isfinite(probs).all():
        fail(f"{what}: non-finite outputs")
    d, dz = rel_diff(probs, ref), logit_diff(probs, ref)
    if not (d <= RTOL and dz <= RTOL):
        fail(f"{what}: max relative difference {d:.3e} (probabilities), "
             f"{dz:.3e} (logits) > {RTOL:g}")
    return d, dz


def stack_probs(rids, done):
    import numpy as np
    return np.stack([done[r] for r in rids])


def one_chip() -> None:
    import numpy as np
    from repro.launch.cnn_serve import CNNServer
    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        srv = CNNServer("alexnet", impl="pallas", dtype="float32",
                        dtype_policy="uniform", max_bucket=max(SIZES),
                        cache_path=os.path.join(tmp, "alexnet.plans.json"),
                        calib_path=os.path.join(tmp, "thresholds.json"))
        if srv.interpret:
            fail("server would run the Pallas interpreter on a TPU")
        cfg = srv.cfg
        print(f"server up in {time.perf_counter() - t0:.1f}s: "
              f"{cfg.name} {cfg.image_hw}px classes={cfg.num_classes} "
              f"hw={srv._hw}", flush=True)
        sigs, stacks = {}, {}
        for size in SIZES:
            reqs = make_requests(cfg, size, seed=size)
            t1 = time.perf_counter()
            done = srv.run(reqs)
            first_s = time.perf_counter() - t1
            steady = []
            for _ in range(3):
                again = make_requests(cfg, size, seed=size)
                t2 = time.perf_counter()
                srv.run(again)
                steady.append(time.perf_counter() - t2)
            bucket = srv.cache.bucket(size)
            plan = srv.cache.peek_fused(cfg, bucket, dtype=srv.dtype,
                                        policy=srv.dtype_policy,
                                        pre_sharded=True)
            if plan is None:
                fail(f"no cached plan for bucket {bucket}")
            sigs[bucket] = plan.conv_signature
            stacks[bucket] = sum(op.stack_index is not None
                                 for op in plan.ops)
            probs = stack_probs([r.rid for r in reqs], done)
            ref = reference_probs(srv.params, [r.image for r in reqs], cfg)
            d, dz = check_outputs(probs, ref, f"bucket {bucket}")
            med = float(np.median(steady))
            print(f"bucket={bucket} layouts={plan.conv_signature} "
                  f"stacks={stacks[bucket]} first_call_s={first_s:.1f} "
                  f"compile_s={first_s - med:.1f} "
                  f"steady_ms={1e3 * med:.2f} "
                  f"max_rel_diff={d:.3e} logit_rel_diff={dz:.3e} "
                  f"(rtol {RTOL:g})", flush=True)
        for line in srv.report_lines():
            print(line, flush=True)
        joined = "".join(sigs.values())
        if "C" not in joined or "N" not in joined:
            fail(f"served plans {sigs} do not use both conv engines")
        if not any(stacks.values()):
            fail(f"served plans {sigs} hold no conv->conv stack")
        top = srv.ladder[0].name
        for b, rep in srv.reports.items():
            if rep.rung != top or rep.degraded or rep.failures:
                fail(f"bucket {b}: rung={rep.rung} degraded={rep.degraded} "
                     f"failures={rep.failures}")
        if srv.incidents.total or srv._quarantine:
            fail(f"{srv.incidents.summary()} "
                 f"quarantined={len(srv._quarantine)}")


def four_chips() -> None:
    import jax
    import numpy as np
    from repro.launch.cnn_serve import CNNServer
    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    with tempfile.TemporaryDirectory() as tmp:
        shard_bucket = MESH_REQUESTS // 4
        sharded = CNNServer("alexnet", impl="pallas", devices=4,
                            max_bucket=shard_bucket, calibration="analytic",
                            cache_path=os.path.join(tmp, "d4.plans.json"))
        single = CNNServer("alexnet", impl="pallas", devices=1,
                           max_bucket=MESH_REQUESTS, calibration="analytic",
                           cache_path=os.path.join(tmp, "d1.plans.json"))
        cfg = single.cfg
        reqs = make_requests(cfg, MESH_REQUESTS, seed=4)
        rids = [r.rid for r in reqs]
        t0 = time.perf_counter()
        got4 = stack_probs(rids, sharded.run(
            make_requests(cfg, MESH_REQUESTS, seed=4)))
        t4 = time.perf_counter() - t0
        t0 = time.perf_counter()
        got1 = stack_probs(rids, single.run(reqs))
        t1 = time.perf_counter() - t0
        d, dz = check_outputs(got4, got1, "devices=4 vs devices=1")
        mesh_devs = list(sharded._mesh.devices.flat)
        if len({dv.id for dv in mesh_devs}) != 4:
            fail(f"mesh devices are not 4 distinct chips: {mesh_devs}")
        x = np.stack([r.image for r in reqs])
        y, _ = sharded._forward_for(shard_bucket)(sharded.params, x)
        shard_devs = [s.device for s in y.addressable_shards]
        if (len(y.sharding.device_set) != 4
                or len({dv.id for dv in shard_devs}) != 4):
            fail(f"sharded output not on 4 distinct chips: "
                 f"{y.sharding.device_set}")
        for line in sharded.report_lines() + single.report_lines():
            print(line, flush=True)
        print(f"mesh devices={[dv.id for dv in mesh_devs]} "
              f"shard devices={sorted(dv.id for dv in shard_devs)} "
              f"first-run s: devices=4 {t4:.1f} devices=1 {t1:.1f} "
              f"max_rel_diff={d:.3e} logit_rel_diff={dz:.3e} "
              f"(rtol {RTOL:g})", flush=True)
        for srv in (sharded, single):
            if srv.incidents.total:
                fail(f"devices={srv.devices}: {srv.incidents.summary()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: compare the four-chip serving mesh with one "
                         "chip, and run nothing else")
    args = ap.parse_args()
    info = device_info()
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repository's code is not next to this script: {e}")
    print(f"compile cache {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
