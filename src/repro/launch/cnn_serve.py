"""CNN request-serving driver: batch-adaptive fused inference (DESIGN.md §7).

The CNN twin of ``launch.serve``'s queue shape: requests (single images)
arrive in a queue, the admission loop drains up to ``max_bucket`` of them
per step, rounds the batch up to its pow-2 bucket, pads, and executes ONE
fused ``forward_fused`` batch under the bucket's cached plan.  Planning and
threshold calibration are both one-time costs paid per bucket / per
process, never per request:

  * layouts come from the ``PlanCache`` (replans only on first sight of a
    bucket — the paper's Nt threshold makes the plan batch-dependent);
  * thresholds come from ``measured_thresholds`` (real Pallas kernel
    timings, persisted), not the analytic sweep.

``--dtype bf16`` serves the mixed-precision fast path (DESIGN.md §8):
params and admission are cast to the storage dtype, kernels accumulate in
f32, and plans/thresholds come from the dtype's own cache rows — halving
every tensor's HBM footprint and shifting the layout crossovers.

``--dtype-policy mixed`` (DESIGN.md §9) goes further: the planner searches
per-layer (layout, storage dtype) states, so interior conv chains store
their activations as int8 (quantize folded into the producing kernel's
epilogue, per-channel dequant folded into the consumer conv's weights)
while the host input, the first conv chain, and the classifier head stay at
the base ``--dtype``.  Plans are cached under their own ``policy`` key, and
the int8 calibration row is measured alongside the base row.

Execution is GUARDED (DESIGN.md §14): every batch runs under a degradation
ladder — pallas+stacks → pallas stacks-off → mixed→uniform dtype →
decomposed XLA — with a cheap finite-check folded into the jitted forward.
A kernel exception or non-finite batch quarantines that (bucket, policy,
stack) plan variant and retries the next rung after exponential backoff;
subsequent batches of the bucket skip straight to the known-good rung
(their fallback plan is a PlanCache key, never an ad-hoc replan).  If every
rung fails, the in-flight requests return to the FRONT of the queue in
their original order — a failed step loses zero requests.  ``--inject``
drives the deterministic fault harness (``runtime.resilience``) for smoke
tests; every incident is counted and surfaced in the report.

The report shows per-bucket plan-cache hit rates, the plan's conv layouts
and storage dtypes, modeled HBM bytes, images/s, the serving rung, and the
incident/quarantine/straggler totals.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import tempfile
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CNNConfig
from repro.configs.cnn_networks import (CNN_BUILDERS, CNN_CONFIGS,
                                        reduced_cnn)
from repro.cnn.layers import init_cnn
from repro.cnn.network import batch_output_ok, forward_fused, input_shape
from repro.distributed.cnn_mesh import (cnn_data_mesh, forward_fused_sharded,
                                        replicate_params)
from repro.dtypes import canon_dtype, dtype_bytes, jnp_dtype
from repro.kernels import resolve_interpret
from repro.perfmodel import Thresholds, calibrate, hardware_id
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import StragglerWatchdog
from repro.runtime.resilience import (FaultInjector, IncidentLog,
                                      InjectedKernelFault, Rung,
                                      ServingFault, degradation_ladder,
                                      parse_inject_spec)
from repro.serve import PlanCache, measured_thresholds, pad_to_bucket

log = logging.getLogger("repro.cnn_serve")


class NonFiniteOutput(RuntimeError):
    """The batch output failed the cheap finite check (``batch_output_ok``)."""


@dataclasses.dataclass
class ImageRequest:
    rid: int
    image: np.ndarray                  # [C, H, W] float32
    probs: Optional[np.ndarray] = None # filled by the server


@dataclasses.dataclass
class BucketReport:
    bucket: int                        # PER-SHARD bucket (§15)
    batches: int = 0
    images: int = 0
    padded: int = 0                    # pad rows executed (bucket waste)
    hits: int = 0
    misses: int = 0
    hbm_bytes: int = 0                 # modeled GLOBAL bytes, summed/batch
    per_chip_bytes: int = 0            # modeled per-chip bytes, summed (§15)
    seconds: float = 0.0
    staged_bytes: int = 0              # host bytes sent to the chip, summed
    degraded: int = 0                  # batches served below the top rung
    failures: int = 0                  # rung attempts that failed (§14)
    rung: str = ""                     # rung that served the LAST batch

    @property
    def hit_rate(self) -> float:
        t = self.hits + self.misses
        return self.hits / t if t else 0.0


@dataclasses.dataclass
class _GuardResult:
    """One guarded batch execution: where it landed and what it cost."""
    bucket: int
    rung: Rung
    rung_index: int
    probs: np.ndarray
    seconds: float
    hit: bool                          # plan-cache hit for the serving rung


class CNNServer:
    """Queue-draining batch-adaptive server over the fused CNN engine.

    ``thresholds``, when supplied, is filed as THIS server's dtype row —
    the caller must have swept it at the matching element size
    (``calibrate(dtype_bytes=4)`` for an fp32 server; bare ``calibrate()``
    sweeps at the 2-byte paper-fidelity default).

    ``injector`` enables the deterministic fault harness (§14);
    ``backoff_s`` seeds the exponential backoff between rung retries (0 in
    tests); ``max_step_failures`` bounds how many times ``run`` retries a
    fully-failed step before giving up (requests survive regardless —
    they are re-queued before the failure propagates).

    ``devices`` > 1 (DESIGN.md §15) serves over a data-parallel mesh: the
    admitted batch is split batch-dim across the first ``devices`` jax
    devices via ``shard_map``, params are replicated, and every shard
    executes ONE cached plan — planned, bucketed, and quarantined at the
    PER-SHARD batch (``max_bucket`` bounds the shard bucket; admission
    drains up to ``max_bucket * devices`` requests per step).  The §14
    ladder, incident counters, and re-queue semantics operate on the whole
    shard-group batch, unchanged.

    The network is served at its published size unless ``reduced`` (96 px
    images for the big nets — a CPU-sized variant).  ``interpret=None``
    follows the backend (``resolve_interpret``): compiled Mosaic kernels on
    a TPU, the Pallas interpreter elsewhere."""

    def __init__(self, network: str = "lenet", *, reduced: bool = False,
                 max_bucket: int = 64, impl: str = "pallas",
                 interpret: Optional[bool] = None,
                 cache_path: Optional[str] = None,
                 calibration: str = "measured",
                 thresholds: Optional[Thresholds] = None,
                 calib_path: Optional[str] = None,
                 dtype: str = "float32",
                 dtype_policy: str = "uniform",
                 max_plans: Optional[int] = None,
                 injector: Optional[FaultInjector] = None,
                 backoff_s: float = 0.0,
                 max_step_failures: int = 8,
                 devices: int = 1):
        cfg = CNN_CONFIGS[network]
        if reduced and cfg.image_hw > 96:
            # branching nets re-derive skip edges (and the gap-pool window)
            # through their builder; a bare replace() would zero out the
            # global pool at the reduced size
            if cfg.name in CNN_BUILDERS:
                cfg = reduced_cnn(cfg, batch=cfg.batch)
            else:
                cfg = cfg.replace(image_hw=96)
        self.cfg = cfg
        self.impl = impl
        self.interpret = resolve_interpret(interpret)
        self.dtype = canon_dtype(dtype)
        if dtype_policy not in ("uniform", "mixed"):
            raise ValueError(f"unknown dtype policy {dtype_policy!r}")
        self.dtype_policy = dtype_policy
        self._jdtype = jnp_dtype(self.dtype)
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        self.devices = devices
        # the §15 serving mesh: 1-D data-parallel over the first `devices`
        # jax devices; devices == 1 keeps the single-chip path bit-identical
        self._mesh = cnn_data_mesh(devices) if devices > 1 else None
        self.injector = injector
        self.backoff_s = backoff_s
        self.max_step_failures = max_step_failures
        self.incidents = IncidentLog()
        # the §14 degradation ladder, built from this server's operating
        # point; rung 0 is normal service
        self.ladder = degradation_ladder(impl, dtype_policy)
        # quarantined (bucket, policy, stack, impl) plan variants: a rung
        # that failed for a bucket is skipped by later batches, which start
        # straight at the known-good rung.  The PLAN stays cached — only
        # its use is suspended, so lifting a quarantine costs no replan.
        self._quarantine: set = set()
        # threshold rows are versioned by hardware id (DESIGN.md §13): a
        # cache file carried to a different accelerator keeps its old rows
        # under their id and measures fresh rows for this one
        self._hw = hardware_id(self.interpret)
        # build the cache first: a persisted cache already carries the
        # per-dtype threshold rows it was planned under, so calibration (the
        # ~4 s measured sweep) only runs when neither the caller nor the
        # cache has this dtype's row.  A corrupt cache file was renamed
        # aside inside load (§14) — count it, don't crash.
        self.cache = PlanCache(
            path=cache_path,
            thresholds=(None if thresholds is None
                        else {self.dtype: thresholds}),
            max_bucket=max_bucket, max_entries=max_plans)
        for dst in self.cache.corrupt_recoveries:
            self.incidents.record("corrupt_state",
                                  f"plan cache quarantined to {dst}")
        # mixed policy also measures the 1-byte row (ISSUE 5): the per-dtype
        # threshold contract covers every storage dtype the server's plans
        # use, and the sweep is one-time per cache dir (persisted) — ~4 s of
        # interpret-mode timing, never paid again on restart
        need_rows = [self.dtype]
        if self.dtype_policy == "mixed":
            need_rows.append("int8")
        if calib_path is None and cache_path:
            calib_path = os.path.join(os.path.dirname(cache_path),
                                      "thresholds.json")
        for row in need_rows:
            if self.cache.thresholds_for(row, self._hw) is not None:
                continue
            if calibration == "measured":
                self.cache.set_thresholds(
                    measured_thresholds(
                        calib_path, dtype=row, interpret=self.interpret,
                        hardware=self._hw,
                        on_corrupt=lambda dst, e: self.incidents.record(
                            "corrupt_state",
                            f"threshold table quarantined to {dst}")),
                    row, hardware=self._hw)
            else:
                self.cache.set_thresholds(
                    calibrate(dtype_bytes=dtype_bytes(row)), row,
                    hardware=self._hw)
        self.params = init_cnn(jax.random.PRNGKey(0), cfg,
                               dtype=self._jdtype)
        if self._mesh is not None:     # replicate once, serve forever
            self.params = replicate_params(self.params, self._mesh)
        self.queue: Deque[ImageRequest] = deque()
        self.reports: Dict[int, BucketReport] = {}
        self._fwd = {}                 # (bucket, rung.name) -> jitted fwd
        self._plan_stats = {}          # (bucket, rung.name) -> modeled bytes
        self._watchdogs: Dict[int, StragglerWatchdog] = {}
        # the admitted batch's host rows: one float32 buffer of
        # max_bucket * devices images, allocated on the first step and
        # refilled by every later one, so that no step pays the page
        # faults of a fresh batch-sized allocation
        self._staging: Optional[np.ndarray] = None
        self.staging_allocs = 0

    # -- admission -----------------------------------------------------------

    def submit(self, req: ImageRequest) -> None:
        c, h = self.cfg.in_channels, self.cfg.image_hw
        if req.image.shape != (c, h, h):
            raise ValueError(
                f"request {req.rid}: image shape {req.image.shape} != "
                f"{(c, h, h)}")
        self.queue.append(req)

    def _modeled_bytes(self, bcfg: CNNConfig, plan) -> int:
        """Shape-only HBM accounting for one bucket batch (eval_shape —
        never executes)."""
        box = {}

        def f(p, x):
            y, st = forward_fused(p, x, bcfg, plan, impl="xla")
            box["st"] = st
            return y

        aparams = jax.eval_shape(lambda k: init_cnn(k, bcfg,
                                                    dtype=self._jdtype),
                                 jax.random.PRNGKey(0))
        jax.eval_shape(f, aparams,
                       jax.ShapeDtypeStruct(input_shape(bcfg), self._jdtype))
        return box["st"].hbm_bytes

    def _forward_for(self, bucket: int, rung: Optional[Rung] = None):
        """Jitted forward for (shard bucket, rung) — rung defaults to the
        top of the ladder.  The rung's plan is the PlanCache's own plan for
        that (policy, stack, devices) variant.  The jitted function takes
        the bucket's rows flat, padded, and also
        returns the §14 finite-check scalar so the guard costs no extra
        device round trip.  Under a mesh (§15) the forward is the sharded
        executor: every shard runs the ONE per-shard-bucket plan, so this
        compiles once per (bucket, rung) across all shards."""
        rung = rung or self.ladder[0]
        key = (bucket, rung.name)
        if key not in self._fwd:
            bcfg = self.cfg.replace(batch=bucket)   # the SHARD config
            # step() already planned this bucket; peek keeps stats honest.
            # `bucket` is the PER-SHARD bucket, so pre_sharded=True — the
            # default path would divide by devices a second time and
            # resolve (then plan) a bogus bucket/devices key
            plan = self.cache.peek_fused(self.cfg, bucket, dtype=self.dtype,
                                         policy=rung.policy,
                                         stack=rung.stack,
                                         devices=self.devices,
                                         pre_sharded=True)
            if plan is None:
                plan, _, _ = self.cache.fused_plan(self.cfg, bucket,
                                                   dtype=self.dtype,
                                                   policy=rung.policy,
                                                   stack=rung.stack,
                                                   devices=self.devices,
                                                   pre_sharded=True)
            # _modeled_bytes at the shard config IS the per-chip traffic
            self._plan_stats[key] = self._modeled_bytes(bcfg, plan)
            impl, interp, mesh = rung.impl, self.interpret, self._mesh
            # every shard's rows, one global batch
            shape = (bucket * self.devices,) + input_shape(bcfg)[1:]
            jdtype = self._jdtype

            @jax.jit
            def fwd(params, x):
                # the batch arrives as staged, flat: a 1-D array needs no
                # host relayout to the device's tiles; the reshape and the
                # cast to the storage dtype run here, on the device
                x = x.reshape(shape).astype(jdtype)
                if mesh is None:
                    y, _ = forward_fused(params, x, bcfg, plan, impl=impl,
                                         interpret=interp)
                else:
                    y = forward_fused_sharded(params, x, bcfg, plan, mesh,
                                              impl=impl, interpret=interp)
                return y, batch_output_ok(y)

            self._fwd[key] = fwd
        return self._fwd[key]

    # -- guarded execution (§14) ---------------------------------------------

    def _qkey(self, bucket: int, rung: Rung) -> Tuple[int, str, str, str]:
        """Quarantine key: the (bucket, policy, stack) plan variant plus the
        engine executing it (rungs 2 and 3 share a plan but not an impl)."""
        return (bucket, rung.policy, rung.stack, rung.impl)

    def _shard_bucket(self, B: int) -> int:
        """The per-shard bucket an admitted global batch of ``B`` lands in
        (== the plain bucket when devices == 1)."""
        return self.cache.bucket(-(-B // self.devices))

    def _stage(self, batch: List[ImageRequest]) -> np.ndarray:
        """Copy the admitted images into the server's staging buffer and
        return their rows as one flat view of it."""
        c, h = self.cfg.in_channels, self.cfg.image_hw
        if self._staging is None:
            self._staging = np.empty(
                (self.cache.max_bucket * self.devices, c, h, h), np.float32)
            self.staging_allocs += 1
        B = len(batch)
        np.stack([r.image for r in batch], out=self._staging[:B])
        return self._staging[:B].reshape(-1)

    def _run_guarded(self, x_np: np.ndarray, B: int) -> _GuardResult:
        """Run one admitted batch, staged flat by ``_stage``, down the
        degradation ladder.  Raises ``ServingFault`` only when EVERY rung
        failed; the caller re-queues the batch before propagating.  The
        staging buffer is not written again before this returns, after
        ``block_until_ready``: a transfer may alias host memory."""
        bucket = self._shard_bucket(B)
        # skip straight to the first non-quarantined rung; the terminal
        # rung is always eligible (a fully-quarantined bucket still serves)
        start = next((i for i, r in enumerate(self.ladder)
                      if self._qkey(bucket, r) not in self._quarantine),
                     len(self.ladder) - 1)
        delay = self.backoff_s
        errors: List[str] = []
        t0 = time.perf_counter()
        # one transfer serves every rung.  It carries the batch's own rows;
        # the pad rows are zeros added on the device, as `pad_to_bucket`
        # gives them, so they never cross the host link
        x = pad_to_bucket(jnp.asarray(x_np),
                          bucket * self.devices * (x_np.size // B))
        for i in range(start, len(self.ladder)):
            rung = self.ladder[i]
            quals = (rung.name, rung.policy, rung.impl)
            try:
                if self.injector is not None:
                    self.injector.maybe_slow(quals)
                    self.injector.maybe_kernel_fault(quals)
                _, _, hit = self.cache.fused_plan(self.cfg, B,
                                                  dtype=self.dtype,
                                                  policy=rung.policy,
                                                  stack=rung.stack,
                                                  devices=self.devices)
                fwd = self._forward_for(bucket, rung)
                y, ok = fwd(self.params, x)
                y = jax.block_until_ready(y)
                probs = np.asarray(y.astype(jnp.float32))
                if self.injector is not None:
                    probs = self.injector.maybe_poison(probs, quals)
                if not (bool(ok) and np.isfinite(probs[:B]).all()):
                    raise NonFiniteOutput(
                        f"non-finite batch output (bucket={bucket}, "
                        f"rung={rung.name})")
                return _GuardResult(bucket, rung, i, probs,
                                    time.perf_counter() - t0, hit)
            except Exception as e:     # noqa: BLE001 — the guard IS the
                # handler: any execution failure steps down the ladder
                kind = ("nonfinite" if isinstance(e, NonFiniteOutput)
                        else "kernel_fault")
                # logged at WARNING by the incident log: type + first line
                first = (str(e).splitlines() or [""])[0]
                self.incidents.record(
                    kind, f"bucket={bucket} rung={rung.name}: "
                    f"{type(e).__name__}: {first}")
                rep = self.reports.setdefault(bucket, BucketReport(bucket))
                rep.failures += 1
                qk = self._qkey(bucket, rung)
                if qk not in self._quarantine:
                    self._quarantine.add(qk)
                    self.incidents.record(
                        "quarantine",
                        f"bucket={bucket} variant=({rung.policy},"
                        f"{rung.stack},{rung.impl})")
                errors.append(f"{rung.name}: {type(e).__name__}: {e}")
                if i + 1 < len(self.ladder) and delay > 0.0:
                    time.sleep(min(delay, 2.0))
                    delay *= 2.0       # exponential backoff down the chain
                t0 = time.perf_counter()
        raise ServingFault(
            f"all rungs failed for bucket {bucket}: {'; '.join(errors)}")

    # -- serving loop --------------------------------------------------------

    def step(self) -> List[ImageRequest]:
        """Drain up to ``max_bucket`` queued requests as one fused batch.

        Failure semantics (§14): the admitted batch either completes on
        some rung of the ladder, or returns to the FRONT of the queue in
        its original order before ``ServingFault`` propagates — a failed
        step loses zero requests."""
        if not self.queue:
            return []
        cap = self.cache.max_bucket * self.devices
        batch = [self.queue.popleft()
                 for _ in range(min(len(self.queue), cap))]
        B = len(batch)
        try:
            x_np = self._stage(batch)
            res = self._run_guarded(x_np, B)
        except Exception:
            self.queue.extendleft(reversed(batch))
            self.incidents.record(
                "requeue", f"{B} in-flight requests re-queued (front, "
                f"original order)")
            raise
        rep = self.reports.setdefault(res.bucket, BucketReport(res.bucket))
        rep.hits += int(res.hit)
        rep.misses += int(not res.hit)
        for i, r in enumerate(batch):
            r.probs = res.probs[i]
        rep.batches += 1
        rep.images += B
        rep.padded += res.bucket * self.devices - B
        rep.staged_bytes += x_np.nbytes
        per_chip = self._plan_stats[(res.bucket, res.rung.name)]
        rep.per_chip_bytes += per_chip
        rep.hbm_bytes += per_chip * self.devices
        rep.seconds += res.seconds
        rep.rung = res.rung.name
        if res.rung_index > 0:
            rep.degraded += 1
            self.incidents.record(
                "degraded", f"bucket={res.bucket} served by rung "
                f"{res.rung_index} ({res.rung.name})")
        # §14 satellite: serving and training share one anomaly detector —
        # per-batch wall time feeds the bucket's StragglerWatchdog; a
        # flagged bucket is an incident and a report line, the response
        # (swap/recalibration) stays a logged callback hook
        wd = self._watchdogs.setdefault(
            res.bucket, StragglerWatchdog(
                on_straggler=lambda step, dt, mean: log.warning(
                    "serving straggler: bucket=%d step=%d %.3fs (mean "
                    "%.3fs)", res.bucket, step, dt, mean)))
        if wd.observe(rep.batches, res.seconds):
            self.incidents.record("straggler",
                                  f"bucket={res.bucket} {res.seconds:.3f}s")
        return batch

    def run(self, requests: List[ImageRequest]) -> Dict[int, np.ndarray]:
        """Serve ``requests`` to completion.  A fully-failed step re-queues
        its batch and is retried (the quarantine makes the retry start at
        the next rung), bounded by ``max_step_failures`` consecutive
        failures — within the bound, every submitted request is served."""
        for r in requests:
            self.submit(r)
        done: Dict[int, np.ndarray] = {}
        failures = 0
        while self.queue:
            try:
                served = self.step()
            except ServingFault:
                failures += 1
                if failures > self.max_step_failures:
                    raise
                continue
            failures = 0
            for r in served:
                done[r.rid] = r.probs
        if self.cache.path:
            self.cache.save()
        return done

    # -- reporting -----------------------------------------------------------

    def prediction_errors(self) -> Dict[int, float]:
        """Per-bucket relative error of the plan's analytic seconds against
        the measured wall clock (DESIGN.md §13).  Analytic roofline seconds
        are not wall-clock on any one machine, so ONE global scale — the
        geomean of measured/analytic across buckets — is fitted first; the
        per-bucket error then reports how well the model ranks/shapes the
        buckets, which is what the planner actually relies on."""
        pairs: Dict[int, Tuple[float, float]] = {}
        for b, rep in self.reports.items():
            # report buckets ARE per-shard buckets — peek pre-sharded so
            # pred_err compares against the plan the step actually ran
            plan = self.cache.peek_fused(self.cfg, b, dtype=self.dtype,
                                         policy=self.dtype_policy,
                                         devices=self.devices,
                                         pre_sharded=True)
            if plan is None or not rep.batches or rep.seconds <= 0.0:
                continue
            if plan.total_s <= 0.0:
                continue
            pairs[b] = (plan.total_s, rep.seconds / rep.batches)
        if not pairs:
            return {}
        scale = float(np.exp(np.mean(
            [np.log(m / a) for a, m in pairs.values()])))
        return {b: abs(scale * a - m) / m for b, (a, m) in pairs.items()}

    def report_lines(self) -> List[str]:
        th = self.cache.thresholds_for(self.dtype, self._hw)
        lines = [f"net={self.cfg.name} dtype={self.dtype} "
                 f"policy={self.dtype_policy} hw={self._hw} "
                 f"devices={self.devices} "
                 f"thresholds=Ct:{th.Ct},Nt:{th.Nt} "
                 f"planner_calls={self.cache.planner_calls}"]
        errs = self.prediction_errors()
        for b in sorted(self.reports):
            rep = self.reports[b]
            plan = self.cache.peek_fused(self.cfg, b, dtype=self.dtype,
                                         policy=self.dtype_policy,
                                         devices=self.devices,
                                         pre_sharded=True)
            # a bounded cache may have LRU-evicted this bucket's plan since
            # it last executed; the report must not resurrect (replan) it
            sig = plan.conv_signature if plan is not None else "(evicted)"
            dsig = plan.dtype_signature if plan is not None else "(evicted)"
            ips = rep.images / rep.seconds if rep.seconds else 0.0
            perr = (f"{errs[b]:.2f}" if b in errs else "n/a")
            pcmb, smb = ((rep.per_chip_bytes / rep.batches / 1e6,
                          rep.staged_bytes / rep.batches / 1e6)
                         if rep.batches else (0.0, 0.0))
            wd = self._watchdogs.get(b)
            lines.append(
                f"  bucket={b:<4d} batches={rep.batches:<4d} "
                f"images={rep.images:<5d} pad_waste={rep.padded:<4d} "
                f"hit_rate={rep.hit_rate:.2f} conv_layouts={sig} "
                f"conv_dtypes={dsig} "
                f"modeled_MB={rep.hbm_bytes / 1e6:.1f} "
                f"per_chip_MB={pcmb:.1f} staged_MB={smb:.3f} "
                f"img/s={ips:.1f} "
                f"pred_err={perr} rung={rep.rung or 'n/a'} "
                f"degraded={rep.degraded} failures={rep.failures} "
                f"stragglers={len(wd.flagged) if wd else 0}")
        # §14: the resilience summary — incident taxonomy totals and the
        # quarantined plan variants currently being skipped
        lines.append(f"  {self.incidents.summary()} "
                     f"quarantined_variants={len(self._quarantine)} "
                     f"staging_allocs={self.staging_allocs}")
        return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", default="lenet", choices=list(CNN_CONFIGS))
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-bucket", type=int, default=32)
    ap.add_argument("--impl", default="pallas", choices=["xla", "pallas"])
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "fp32", "bfloat16", "bf16"],
                    help="storage dtype: bf16 halves HBM bytes and plans "
                         "under its own calibrated threshold row")
    ap.add_argument("--dtype-policy", default="uniform",
                    choices=["uniform", "mixed"],
                    help="mixed: per-layer (layout, dtype) DP — interior "
                         "conv chains store int8, boundaries stay --dtype")
    ap.add_argument("--calibration", default="measured",
                    choices=["measured", "analytic"])
    ap.add_argument("--devices", type=int, default=1,
                    help="shard admitted batches data-parallel over this "
                         "many chips (§15); plans are made for the "
                         "per-shard bucket, so Nt flips taken at the shard "
                         "batch are honored")
    ap.add_argument("--cache-dir",
                    default=os.path.join(tempfile.gettempdir(),
                                         "repro_serve"))
    ap.add_argument("--max-plans", type=int, default=None,
                    help="LRU bound on cached plans per engine (default: "
                         "unbounded)")
    ap.add_argument("--inject", default="",
                    help="fault-injection spec 'site=rate,...' (§14), e.g. "
                         "'kernel=0.1,nan@mixed=1.0,slow=0.05'; sites are "
                         "kernel/nan/slow, optionally qualified @rung-name, "
                         "@policy or @impl; empty = injection off")
    ap.add_argument("--inject-seed", type=int, default=0,
                    help="seed for the deterministic fault injector")
    ap.add_argument("--backoff", type=float, default=0.0,
                    help="initial exponential-backoff delay (s) between "
                         "degradation-ladder retries")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    os.makedirs(args.cache_dir, exist_ok=True)
    srv = CNNServer(
        args.network, max_bucket=args.max_bucket, impl=args.impl,
        calibration=args.calibration, dtype=args.dtype,
        dtype_policy=args.dtype_policy, max_plans=args.max_plans,
        devices=args.devices,
        cache_path=os.path.join(args.cache_dir, f"{args.network}.plans.json"),
        calib_path=os.path.join(args.cache_dir, "thresholds.json"),
        injector=parse_inject_spec(args.inject, seed=args.inject_seed),
        backoff_s=args.backoff)
    rng = np.random.default_rng(args.seed)
    c, h = srv.cfg.in_channels, srv.cfg.image_hw
    reqs = [ImageRequest(i, rng.standard_normal((c, h, h)).astype(np.float32))
            for i in range(args.requests)]
    # bursty arrivals: drain in variable-size chunks to exercise buckets
    t0 = time.time()
    done: Dict[int, np.ndarray] = {}
    i = 0
    while i < len(reqs):
        n = int(rng.integers(1, args.max_bucket + 1))
        for r in reqs[i:i + n]:
            srv.submit(r)
        i += n
        try:
            for r in srv.step():
                done[r.rid] = r.probs
        except ServingFault as e:
            log.warning("step failed on every rung (%s); requests "
                        "re-queued", e)
    while srv.queue:
        try:
            for r in srv.step():
                done[r.rid] = r.probs
        except ServingFault as e:
            log.warning("step failed on every rung (%s); requests "
                        "re-queued", e)
    if srv.cache.path:
        srv.cache.save()
    dt = time.time() - t0
    dropped = len(reqs) - len(done)
    # replans of an already-planned key: the mesh CI job greps this to
    # prove the per-shard bucket compiles exactly once across all shards
    rr = sum(max(0, st.misses - 1) for st in srv.cache.per_key.values())
    print(f"served {len(done)}/{len(reqs)} requests in {dt:.2f}s "
          f"({len(done) / dt:.1f} img/s overall, dropped={dropped}, "
          f"devices={args.devices}, replans_repeat={rr})")
    for line in srv.report_lines():
        print(line)


if __name__ == "__main__":
    main()
