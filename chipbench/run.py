"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<mix>.json``, whose ``kind`` names the generator in
``chipbench/traffic/kinds/``).  A run:

1. turns on the program's persistent compile cache (inside the checkout);
2. builds ``CNNServer`` as its command line does (Pallas, the
   configuration's dtype, uniform policy, measured calibration) with a
   fresh state directory, and hands it weights made here from the seed;
3. makes a pool of request images from the seed;
4. warms the buckets that the mix uses, and only those;
5. drives the mix for ``--seconds`` through ``CNNServer.submit`` and
   ``CNNServer.step`` (with ``--trace 1`` under the JAX profiler), and
   drains the requests that fell due inside the window;
6. reads peak device memory, frees the server, and compares every served
   request with the plain reference (``chipbench/reference.py``);
7. prints the server's incidents (a straggler, a step its watchdog timed as
   slow, is reported there and not compared) and the numbers compared
   beside their limits on standard error, and one JSON line on standard
   output.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics; each metric is read by
``chipbench/metrics/<metric>.py``.  Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spec  # noqa: E402

# every compile goes to the persistent cache, so that a second run of a
# cell in the same checkout compiles nothing
CACHE_MIN_COMPILE_S = 0.0
MAX_STEP_FAILURES = 3
# the incident kind that times a step and finds no fault: the server's
# watchdog flags a step far above its bucket's running mean.  The window's
# own timing is what the end-to-end metrics judge, so ``correct`` leaves it
# out and the run reports it on standard error
TIMING_INCIDENT = "straggler"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class StragglerWarnings(logging.Handler):
    """Keeps the server's straggler warnings, each with the host-clock time
    it came at; every other record it prints as Python's default would."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.seen = []           # (perf_counter, message)

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("serving straggler"):
            self.seen.append((time.perf_counter(), msg))
        else:
            log(msg)


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def check_program_config(cfg, pcfg) -> None:
    """The program's ``CNN_CONFIGS`` entry must be the published table."""
    got = {"in_channels": pcfg.in_channels, "image_hw": pcfg.image_hw,
           "num_classes": pcfg.num_classes}
    want = {k: cfg[k] for k in got}
    if got != want:
        raise ValueError(f"program config {pcfg.name}: {got} != {want}")
    if len(pcfg.layers) != len(cfg["layers"]):
        raise ValueError(f"program config {pcfg.name} has "
                         f"{len(pcfg.layers)} layers, the table "
                         f"{len(cfg['layers'])}")
    for s, l in zip(pcfg.layers, cfg["layers"]):
        p = {"name": s.name, "kind": s.kind}
        if s.kind == "conv":
            p.update(out=s.out_channels, kernel=s.kernel, stride=s.stride,
                     pad=s.pad)
        elif s.kind == "pool":
            p.update(kernel=s.kernel, stride=s.stride, op=s.pool_op)
        elif s.kind == "fc":
            p.update(out=s.fc_out)
        if s.inputs:
            p["inputs"] = list(s.inputs)
        if p != l:
            raise ValueError(f"program layer {p} != table layer {l}")


class Loop:
    """What a traffic kind drives: a clock from the window's start, the
    server's queue, and the benchmark's host spans."""

    def __init__(self, srv, pool, seed: int, annotate):
        import numpy as np
        self.srv, self.pool, self.seed = srv, pool, seed
        self._annotate = annotate
        # requests take the pool's images in an order drawn from the seed
        self.order = np.random.default_rng(seed).permutation(len(pool))
        self.requests = []       # [due, submitted, done, pool index, probs]
        self.steps = []          # (start, end, admitted, executed rows)
        self.gc_pauses = []      # (start, seconds, generation)
        self.failed_steps = 0
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def queued(self) -> int:
        return len(self.srv.queue)

    def span(self, name: str):
        return self._annotate(name)

    def submit(self, due: float) -> None:
        from repro.launch.cnn_serve import ImageRequest
        k = len(self.requests)
        j = int(self.order[k % len(self.order)])
        rec = [due, self.now(), None, j, None]
        self.requests.append(rec)
        self.srv.submit(ImageRequest(k, self.pool[j]))

    def step(self) -> None:
        from repro.runtime.resilience import ServingFault
        t = self.now()
        try:
            with self.span("bench.step"):
                served = self.srv.step()
        except ServingFault as e:
            self.failed_steps += 1
            log(f"step failed on every rung: {e}")
            if self.failed_steps > MAX_STEP_FAILURES:
                raise
            return
        end = self.now()
        rows = self.srv._shard_bucket(len(served)) * self.srv.devices
        self.steps.append((t, end, len(served), rows))
        for r in served:
            rec = self.requests[r.rid]
            rec[2], rec[4] = end, r.probs

    def sleep_until(self, t: float) -> None:
        dt = t - self.now()
        if dt > 0:
            time.sleep(dt)


def warm(srv, sizes, pool) -> dict:
    """Serve each batch size twice; return the first call's seconds."""
    from repro.launch.cnn_serve import ImageRequest
    first = {}
    for b in sizes:
        for rep in range(2):
            t = time.perf_counter()
            for i in range(b):
                srv.submit(ImageRequest(-1, pool[i % len(pool)]))
            srv.step()
            if rep == 0:
                first[b] = time.perf_counter() - t
    return first


def report_counts(srv) -> dict:
    reps = srv.reports.values()
    return {"images": sum(r.images for r in reps),
            "padded": sum(r.padded for r in reps),
            "degraded": sum(r.degraded for r in reps)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def compare(loop, ref, limits) -> dict:
    """Every served request against the reference row of its image."""
    import numpy as np
    from chipbench.reference import check_numbers
    served = [r for r in loop.requests if r[4] is not None]
    if served:
        got = np.stack([r[4] for r in served]).astype(np.float32)
        checks = check_numbers(got, ref[[r[3] for r in served]])
    else:
        checks = {k: float("inf") for k in limits}
    checks["unserved"] = len(loop.requests) - len(served)
    return {k: {"value": v, "limit": limits.get(k, 0)}
            for k, v in checks.items()}


class Cell:
    """One cell's server, built once; ``window`` can then run it on as many
    seeds as the caller asks (the harness asks one)."""

    def __init__(self, workload: str, *, root: str = ROOT,
                 require_chip: bool = True):
        bench = spec.benchmark(root)
        self.bench = bench
        self.bench_dir = os.path.join(root, bench["paths"][0])
        self.cell = spec.workload(bench, workload)
        self.cfg = spec.config(bench, self.cell["config"], root)
        self.mix = spec.traffic(self.cell["traffic"], self.bench_dir)
        self.kind = spec.traffic_kind(self.mix["kind"], self.bench_dir)
        self.chips = chips = self.cell["chips"]
        self.phases = {}

        src = os.path.join(root, "src")
        if src not in sys.path:
            sys.path.insert(0, src)
        import jax
        t = time.perf_counter()
        if require_chip:
            self.devices = check_devices(chips)
            self.peak = spec.peaks(self.devices[0].device_kind,
                                   self.bench_dir)
        else:
            self.devices, self.peak = jax.devices()[:chips], None
        self.phases["jax_init"] = time.perf_counter() - t

        # XLA compiles that miss the persistent cache, counted so that a
        # compile inside the window shows
        self.backend_compiles = 0

        def on_event(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.backend_compiles += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
        from repro.runtime.compile_cache import enable_compile_cache
        self.cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          CACHE_MIN_COMPILE_S)
        from repro.configs.cnn_networks import CNN_CONFIGS
        from repro.launch.cnn_serve import CNNServer
        check_program_config(self.cfg, CNN_CONFIGS[self.cfg["network"]])
        self.state = tempfile.mkdtemp(prefix="chipbench-state-")
        t = time.perf_counter()
        self.srv = CNNServer(
            self.cfg["network"], impl="pallas", dtype=self.cfg["dtype"],
            dtype_policy=self.cfg["policy"], calibration="measured",
            max_bucket=self.mix["max_bucket"], devices=chips,
            cache_path=os.path.join(self.state, "plans.json"),
            calib_path=os.path.join(self.state, "thresholds.json"))
        if self.srv.interpret and require_chip:
            raise RuntimeError("the server would interpret its kernels")
        self.phases["server"] = time.perf_counter() - t
        if chips > 1:               # replicated over the server's mesh
            from jax.sharding import NamedSharding, PartitionSpec
            self.sharding = NamedSharding(self.srv._mesh, PartitionSpec())
        else:
            self.sharding = jax.sharding.SingleDeviceSharding(
                self.devices[0])
        from repro.launch.cnn_serve import log as server_log
        self._server_log = server_log
        self.stragglers = StragglerWarnings()
        server_log.addHandler(self.stragglers)

    def prepare(self, seed: int):
        """Weights and the image pool from ``seed``; warm the buckets."""
        import jax
        import numpy as np
        from chipbench.reference import init_params
        t = time.perf_counter()
        params = init_params(self.cfg, seed, self.sharding)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), self.srv.params)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if want != got:
            raise ValueError("the benchmark's weights do not fit the "
                             "program's parameter tree")
        self.params = self.srv.params = jax.block_until_ready(params)
        self.phases["weights"] = time.perf_counter() - t
        t = time.perf_counter()
        c, hw = self.cfg["in_channels"], self.cfg["image_hw"]
        self.pool = np.random.default_rng(seed).standard_normal(
            (self.mix.get("pool", 256), c, hw, hw), dtype=np.float32)
        self.phases["images"] = time.perf_counter() - t
        t = time.perf_counter()
        self.first = warm(self.srv, self.kind.warm_sizes(self.mix,
                                                         self.chips),
                          self.pool)
        self.phases["warm"] = time.perf_counter() - t

    def window(self, seed: int, seconds: float, trace_dir=None) -> Loop:
        """Drive the mix for ``seconds``; with ``trace_dir``, under the
        profiler.  Sets ``counts`` (the server's counters over the window),
        ``compiles`` and ``setup_s`` (process start to the window)."""
        import jax
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            annotate = jax.profiler.TraceAnnotation
        else:
            def annotate(name):
                return contextlib.nullcontext()
        compiles = self.backend_compiles
        before = report_counts(self.srv)
        # set-up leaves some hundred thousand objects behind; frozen, the
        # collector's full passes inside the window no longer walk them
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - PROCESS_START
        loop = Loop(self.srv, self.pool, seed, annotate)
        self.t0 = loop.t0
        started = []

        def on_gc(phase, info):         # each collection's pause, timed
            if phase == "start":
                started.append(time.perf_counter())
            elif started:
                t = started.pop()
                loop.gc_pauses.append((t - loop.t0, time.perf_counter() - t,
                                       info["generation"]))
        gc.callbacks.append(on_gc)
        try:
            with annotate("bench.window"):
                self.kind.drive(loop, self.mix, seconds)
        finally:
            gc.callbacks.remove(on_gc)
        loop.window_s = loop.now()
        if trace_dir:
            jax.profiler.stop_trace()
        self.compiles = self.backend_compiles - compiles
        after = report_counts(self.srv)
        self.counts = {k: after[k] - before[k] for k in after}
        loop.srv = None
        return loop

    def free_server(self) -> None:
        """Read the server's incident counts over its life, then free it.
        ``incidents`` counts every kind of the program's taxonomy but the
        timing one."""
        inc = self.srv.incidents
        self.incidents = sum(n for k, n in inc.counts.items()
                             if k != TIMING_INCIDENT)
        self.timing_incidents = inc.counts.get(TIMING_INCIDENT, 0)
        self.incident_summary = inc.summary()
        self.srv = None
        gc.unfreeze()
        gc.collect()

    def close(self) -> None:
        self._server_log.removeHandler(self.stragglers)
        shutil.rmtree(self.state, ignore_errors=True)


def run(args, *, root: str = ROOT, require_chip: bool = True,
        fault=None) -> dict:
    """One run of one cell; returns the result object.  ``require_chip``
    and ``fault`` are for the harness's own tests: the first lets it run on
    the CPU, the second is handed the server after warm-up, to plant a
    fault in its output."""
    import numpy as np
    from chipbench import reference as R
    cell = Cell(args.workload, root=root, require_chip=require_chip)
    keep_trace = getattr(args, "keep_trace", None)
    try:
        cell.prepare(args.seed)
        if fault is not None:
            fault(cell.srv)
        trace_dir = os.path.join(cell.state, "trace") if args.trace else None
        loop = cell.window(args.seed, args.seconds, trace_dir)
        mem = memory_peak(cell.devices)
        cell.free_server()
        summary = None
        if trace_dir:
            from chipbench import trace as T
            path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True)
            summary = T.summarize(path[0], devices=len(cell.devices))
            if keep_trace:
                shutil.copy(path[0], keep_trace)
        t = time.perf_counter()
        ref = R.reference_probs(cell.params, cell.pool, cell.cfg,
                                cell.cfg["precision"],
                                device=cell.devices[0])
        ref_s = time.perf_counter() - t
    finally:
        cell.close()
    checks = compare(loop, ref, cell.cfg["limits"])
    checks["degraded_batches"] = {"value": cell.counts["degraded"],
                                  "limit": 0}
    checks["incidents"] = {"value": cell.incidents, "limit": 0}
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    ctx = types.SimpleNamespace(
        cfg=cell.cfg, mix=cell.mix, cell=cell.cell, chips=cell.chips,
        peak=cell.peak, seconds=args.seconds, window_s=loop.window_s,
        setup_s=cell.setup_s, requests=loop.requests, steps=loop.steps,
        counts=cell.counts, trace=summary)
    which = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.bench[which]:
        if "workloads" in m and cell.cell["name"] not in m["workloads"]:
            continue
        v = spec.metric_reader(m["name"], cell.bench_dir).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    c = cell.counts
    log(f"cell {cell.cell['name']} seed {args.seed}: window "
        f"{loop.window_s:.3f}s, {len(loop.steps)} steps, "
        f"{len(loop.requests)} requests, {c['images']} images, "
        f"{c['padded']} padded rows, compiles in window {cell.compiles}, "
        f"compile cache {cell.cache_dir}")
    if loop.steps:
        dt = np.array([e - s for s, e, _, _ in loop.steps])
        log(f"step ms: p50 {1e3 * np.median(dt):.3f}, p90 "
            f"{1e3 * np.percentile(dt, 90):.3f}, max {1e3 * dt.max():.3f}, "
            f"{int((dt > 2 * np.median(dt)).sum())} steps over twice the "
            f"median")
        log("longest steps: " + ", ".join(
            f"{1e3 * (e - s):.1f} ms at {s:.3f}s"
            for s, e, _, _ in sorted(loop.steps, key=lambda x: x[0] - x[1])
            [:5]))
    p = loop.gc_pauses
    if p:
        g = [sum(1 for x in p if x[2] == k) for k in range(3)]
        t, d, k = max(p, key=lambda x: x[1])
        log(f"gc in window: {len(p)} collections (generation 0/1/2: "
            f"{g[0]}/{g[1]}/{g[2]}), {1e3 * sum(x[1] for x in p):.1f} ms in "
            f"all, longest {1e3 * d:.1f} ms (generation {k} at {t:.3f}s)")
    lat = [r[2] - r[0] for r in loop.requests if r[2] is not None]
    if lat:
        log("latency ms: " + ", ".join(
            f"p{q} {1e3 * float(np.percentile(lat, q)):.3f}"
            for q in (50, 90, 95, 99)) + f", max {1e3 * max(lat):.3f}")
    late = [r[1] - r[0] for r in loop.requests]
    if late:
        log(f"generator lateness (submit - due): p50 "
            f"{1e3 * float(np.percentile(late, 50)):.3f} ms, p95 "
            f"{1e3 * float(np.percentile(late, 95)):.3f} ms, max "
            f"{1e3 * max(late):.3f} ms")
    log("setup: " + ", ".join(f"{k} {v:.3f}s" for k, v in
                              cell.phases.items())
        + f", total {cell.setup_s:.3f}s; first call per batch size "
        + ", ".join(f"{b}: {s:.3f}s" for b, s in cell.first.items())
        + f"; reference {ref_s:.3f}s")
    d0 = cell.devices[0]
    result = {
        "correct": correct,
        "attempted": len(loop.requests),
        "failed": checks["unserved"]["value"],
        "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(cell.devices), "memory_peak_bytes": mem},
    }
    if summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
    result["checks"] = checks
    log(f"incidents over the server's life: {cell.incident_summary}; "
        f"{cell.timing_incidents} {TIMING_INCIDENT}, reported and not "
        f"compared")
    for t, msg in cell.stragglers.seen:
        log(f"{TIMING_INCIDENT} at {t - cell.t0:.3f}s from the window's "
            f"start: {msg}")
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="PATH",
                    help="with --trace 1, also copy the profiler's "
                         ".xplane.pb to PATH")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        log(f"FAIL: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
