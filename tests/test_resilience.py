"""Serving-grade resilience (ISSUE 9 / DESIGN.md §14): deterministic fault
injection, the guarded degradation ladder (zero request loss, per-rung
bit-equality, quarantine without replanning), and crash-safe persisted
plan/calibration state (corruption matrix, quarantine-aside, restart)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.cnn_networks import LENET
from repro.cnn.network import forward_fused, plan_network_fused
from repro.core import heuristic as H
from repro.launch.cnn_serve import CNNServer, ImageRequest
from repro.perfmodel import calibrate
from repro.perfmodel.calibration import save_thresholds
from repro.runtime.fault_tolerance import FaultTolerantRunner, StepFailure
from repro.checkpoint.checkpointer import Checkpointer
from repro.runtime.resilience import (CHECKSUM_FIELD, CorruptStateError,
                                      FaultInjector, IncidentLog,
                                      InjectedKernelFault, ServingFault,
                                      atomic_json_dump, degradation_ladder,
                                      load_json_guarded, parse_inject_spec,
                                      verify_checksum, with_checksum)
from repro.serve import PlanCache, measured_thresholds, pad_to_bucket

TH4 = calibrate(dtype_bytes=4)


def make_requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    c, h = cfg.in_channels, cfg.image_hw
    return [ImageRequest(i, rng.standard_normal((c, h, h)).astype(np.float32))
            for i in range(n)]


def make_server(tmp_path=None, **kw):
    kw.setdefault("max_bucket", 8)
    kw.setdefault("impl", "xla")
    kw.setdefault("thresholds", TH4)
    kw.setdefault("calibration", "analytic")
    if tmp_path is not None:
        kw.setdefault("cache_path", str(tmp_path / "plans.json"))
    return CNNServer("lenet", **kw)


# ---------------------------------------------------------------------------
# fault injector: determinism, site qualifiers, spec parsing
# ---------------------------------------------------------------------------

def test_injector_deterministic_per_seed():
    a = FaultInjector(seed=7, rates={"kernel": 0.5})
    b = FaultInjector(seed=7, rates={"kernel": 0.5})
    draws_a = [a.fire("kernel", ("rung", "pol", "impl")) for _ in range(32)]
    draws_b = [b.fire("kernel", ("rung", "pol", "impl")) for _ in range(32)]
    assert draws_a == draws_b
    assert any(draws_a) and not all(draws_a)     # rate 0.5 actually draws
    c = FaultInjector(seed=8, rates={"kernel": 0.5})
    draws_c = [c.fire("kernel", ("rung", "pol", "impl")) for _ in range(32)]
    assert draws_a != draws_c                    # seed moves the sequence
    # independent sites draw from independent streams
    d = FaultInjector(seed=7, rates={"kernel": 0.5, "nan": 0.5})
    assert d.draws == {}
    d.fire("kernel", ()), d.fire("nan", ())
    assert set(d.draws) == {"kernel", "nan"}


def test_injector_site_qualifiers():
    inj = FaultInjector(seed=0, rates={"nan@mixed": 1.0})
    y = np.ones(4, np.float32)
    out = inj.maybe_poison(y, ("pallas-mixed", "mixed", "pallas"))
    assert np.isnan(out[0]) and np.isfinite(y).all()   # copy, not in place
    # a uniform-policy site never matches the @mixed qualifier
    out2 = inj.maybe_poison(y, ("pallas", "uniform", "pallas"))
    assert np.isfinite(out2).all()
    # rate-1.0 kernel site raises every time it matches
    inj2 = FaultInjector(seed=0, rates={"kernel@xla": 1.0})
    with pytest.raises(InjectedKernelFault):
        inj2.maybe_kernel_fault(("xla", "uniform", "xla"))
    inj2.maybe_kernel_fault(("pallas", "uniform", "pallas"))  # no match


def test_parse_inject_spec():
    assert parse_inject_spec("") is None
    inj = parse_inject_spec("kernel=0.1,nan@mixed=1.0", seed=3)
    assert inj.rates == {"kernel": 0.1, "nan@mixed": 1.0}
    assert inj.seed == 3
    with pytest.raises(ValueError):
        parse_inject_spec("kernel")
    with pytest.raises(ValueError):
        FaultInjector(rates={"kernel": 1.5})


def test_incident_log_rejects_unknown_kind():
    log = IncidentLog()
    log.record("kernel_fault")
    log.record("requeue", n=2)
    assert log.total == 3
    assert "kernel_fault:1" in log.summary()
    with pytest.raises(ValueError):
        log.record("typo_kind")


# ---------------------------------------------------------------------------
# the degradation ladder
# ---------------------------------------------------------------------------

def test_ladder_shapes():
    l = degradation_ladder("pallas", "mixed")
    assert [r.name for r in l] == ["pallas+stacks-mixed", "pallas-mixed",
                                   "pallas", "xla"]
    # terminal rung is always the decomposed-XLA ground truth
    t = l[-1]
    assert (t.impl, t.stack, t.policy) == ("xla", "off", "uniform")
    assert [r.name for r in degradation_ladder("xla", "uniform")] == \
        ["xla+stacks", "xla"]
    with pytest.raises(ValueError):
        degradation_ladder("cuda", "uniform")


# ---------------------------------------------------------------------------
# zero request loss (ISSUE 9 satellite: the step() re-queue fix)
# ---------------------------------------------------------------------------

def test_injected_fault_loses_zero_requests(tmp_path):
    """One injected kernel fault on the top rung: the batch completes on
    the fallback rung — every request served, none dropped."""
    srv = make_server(tmp_path, injector=FaultInjector(
        seed=0, rates={"kernel@xla+stacks": 1.0}))
    reqs = make_requests(srv.cfg, 16)
    done = srv.run(reqs)
    assert set(done) == {r.rid for r in reqs}
    for probs in done.values():
        assert np.isfinite(probs).all()
    assert srv.incidents.counts["kernel_fault"] >= 1
    assert srv.incidents.counts["degraded"] >= 1


def test_total_failure_requeues_in_original_order(tmp_path):
    """When EVERY rung fails, the admitted batch returns to the FRONT of
    the queue in its original order before ServingFault propagates."""
    srv = make_server(tmp_path, injector=FaultInjector(
        seed=0, rates={"kernel": 1.0}))
    reqs = make_requests(srv.cfg, 6)
    tail = make_requests(srv.cfg, 2, seed=9)
    for r in reqs:
        srv.submit(r)
    for i, r in enumerate(tail):                 # waiting behind the batch
        r.rid = 100 + i
        srv.submit(r)
    with pytest.raises(ServingFault):
        srv.step()
    # all 8 still queued: the failed batch back at the front, original
    # order, the untouched tail behind it
    assert [r.rid for r in srv.queue] == [0, 1, 2, 3, 4, 5, 100, 101]
    assert srv.incidents.counts["requeue"] == 1
    # lifting the injection serves the exact same queue to completion
    srv.injector = None
    srv._quarantine.clear()
    done = {}
    while srv.queue:
        for r in srv.step():
            done[r.rid] = r.probs
    assert set(done) == {0, 1, 2, 3, 4, 5, 100, 101}


def test_run_retries_through_step_failures(tmp_path):
    """run() absorbs fully-failed steps (bounded) — with the terminal rung
    clean, every request is eventually served."""
    srv = make_server(tmp_path, injector=FaultInjector(
        seed=0, rates={"kernel@xla+stacks": 1.0, "nan@xla": 0.3}))
    reqs = make_requests(srv.cfg, 24)
    done = srv.run(reqs)
    assert set(done) == {r.rid for r in reqs}


# ---------------------------------------------------------------------------
# per-rung differential: degraded output == fallback rung's direct execution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rung_idx", [0, 1, 2])
def test_degraded_output_bit_equal_to_rung(tmp_path, rung_idx):
    """Force failure of every rung above ``rung_idx``: the served batch
    must be BIT-EQUAL to executing the landing rung's own plan directly
    (mixed policy gives a 3-rung xla ladder)."""
    ladder = degradation_ladder("xla", "mixed")
    rates = {f"kernel@{ladder[i].name}": 1.0 for i in range(rung_idx)}
    srv = make_server(tmp_path, dtype_policy="mixed",
                      injector=FaultInjector(seed=0, rates=rates) if rates
                      else None)
    reqs = make_requests(srv.cfg, 5)
    done = srv.run(reqs)
    assert set(done) == {r.rid for r in reqs}
    rung = ladder[rung_idx]
    assert srv.reports[8].rung == rung.name
    # direct execution of the landing rung's plan — same planner inputs,
    # bypassing the server entirely
    bcfg = srv.cfg.replace(batch=8)
    plan = plan_network_fused(bcfg, dtype=srv.dtype, policy=rung.policy,
                              stack_policy=rung.stack)

    @jax.jit
    def direct(params, x):
        y, _ = forward_fused(params, x, bcfg, plan, impl=rung.impl,
                             interpret=srv.interpret)
        return y

    x = jnp.asarray(np.stack([r.image for r in reqs])).astype(srv._jdtype)
    y = np.asarray(direct(srv.params, pad_to_bucket(x, 8))
                   .astype(jnp.float32))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(done[r.rid], y[i])


# ---------------------------------------------------------------------------
# staging: one reused host buffer, sent flat, pad rows zeroed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_staged_steps_bit_equal_to_direct_forward(tmp_path, dtype):
    """A full batch, then a short one, through one server and its one
    staging buffer: each step's forward receives exactly ``pad_to_bucket``
    of its own batch (zero pad rows, not the first batch's stale images),
    each request's probabilities are bit-equal to a direct jitted forward
    over that, and the first step's probabilities survive the second."""
    srv = make_server(tmp_path, dtype=dtype, thresholds=calibrate(
        dtype_bytes=4 if dtype == "float32" else 2))
    sent = []
    forward_for = srv._forward_for

    def spy(bucket, rung=None):
        fwd = forward_for(bucket, rung)

        def run(params, x):
            sent.append(np.array(x))             # what the forward gets
            return fwd(params, x)
        return run
    srv._forward_for = spy

    def direct(batch):
        bkt = srv._shard_bucket(len(batch))
        bcfg = srv.cfg.replace(batch=bkt)
        plan = srv.cache.peek_fused(srv.cfg, bkt, dtype=srv.dtype,
                                    pre_sharded=True)
        x = pad_to_bucket(jnp.asarray(np.stack([r.image for r in batch])),
                          bkt)
        y = jax.jit(lambda p, x: forward_fused(
            p, x.astype(srv._jdtype), bcfg, plan, impl="xla")[0])(
                srv.params, x)
        return np.asarray(x).ravel(), np.asarray(y.astype(jnp.float32))

    full, short = make_requests(srv.cfg, 8), make_requests(srv.cfg, 3, seed=1)
    for r in full + short:
        srv.submit(r)
    assert len(srv.step()) == 8
    kept = [r.probs.copy() for r in full]
    assert len(srv.step()) == 3
    assert srv.staging_allocs == 1
    for batch, x_sent in zip((full, short), sent):
        x, y = direct(batch)
        np.testing.assert_array_equal(x_sent, x)
        for i, r in enumerate(batch):
            np.testing.assert_array_equal(r.probs, y[i])
    assert sent[1].size == 4 * sent[0].size // 8   # bucket 4: one pad row
    for r, p in zip(full, kept):
        np.testing.assert_array_equal(r.probs, p)


@pytest.mark.parametrize("B", [8, 5, 3])
def test_staging_buffer_allocated_once(tmp_path, B):
    """Ten steps allocate the buffer once, sized by the largest bucket;
    each step sends its batch's own rows, ``B × C×H×W × 4`` bytes."""
    srv = make_server(tmp_path)
    for k in range(10):
        srv.run(make_requests(srv.cfg, B, seed=k))
    bkt = srv._shard_bucket(B)
    c, h = srv.cfg.in_channels, srv.cfg.image_hw
    rep = srv.reports[bkt]
    assert srv.staging_allocs == 1
    assert srv._staging.shape == (8, c, h, h)
    assert rep.batches == 10
    assert rep.staged_bytes == 10 * B * c * h * h * 4
    lines = srv.report_lines()
    assert f"staged_MB={B * c * h * h * 4 / 1e6:.3f}" in lines[1]
    assert "staging_allocs=1" in lines[-1]


# ---------------------------------------------------------------------------
# quarantine: skip straight to the known-good rung, planner_calls bounded
# ---------------------------------------------------------------------------

def test_quarantine_skips_without_replanning(tmp_path):
    """After the first batch quarantines the mixed rungs, later batches of
    the bucket start at the known-good rung: no new failures, no new
    planner calls — the fallback plan is a cache key, not a replan."""
    srv = make_server(tmp_path, dtype_policy="mixed",
                      injector=FaultInjector(seed=0,
                                             rates={"nan@mixed": 1.0}))
    srv.run(make_requests(srv.cfg, 8))           # one bucket-8 batch
    calls = srv.cache.planner_calls
    fails = srv.reports[8].failures
    assert calls == 3                            # the 3 distinct variants
    assert fails == 2                            # both mixed rungs, once
    assert len(srv._quarantine) == 2
    srv.run(make_requests(srv.cfg, 24, seed=1))  # three more batches
    assert srv.cache.planner_calls == calls      # zero replans
    assert srv.reports[8].failures == fails      # zero retries
    assert srv.reports[8].degraded == 4          # every batch, fallback rung


def test_clean_server_stays_on_top_rung(tmp_path):
    """No injector, no faults: rung 0 serves everything — the resilience
    layer is inert (plans/planner_calls identical to the unguarded path)."""
    srv = make_server(tmp_path)
    done = srv.run(make_requests(srv.cfg, 24))
    assert len(done) == 24
    assert srv.incidents.total == 0
    assert not srv._quarantine
    for rep in srv.reports.values():
        assert rep.rung == "xla+stacks" and rep.degraded == 0
    # one planner call per bucket seen, exactly as before §14
    assert srv.cache.planner_calls == len(srv.reports)
    assert "incidents=0" in srv.report_lines()[-1]


def test_watchdog_hook_wired_into_step(tmp_path):
    """Serving shares the training StragglerWatchdog: a flagged batch is a
    'straggler' incident and a report column."""
    class AlwaysFlag:
        flagged = [(1, 9.9)]

        def observe(self, step, dt):
            return True

    srv = make_server(tmp_path)
    srv._watchdogs[8] = AlwaysFlag()
    srv.run(make_requests(srv.cfg, 8))
    assert srv.incidents.counts["straggler"] == 1
    assert any("stragglers=1" in l for l in srv.report_lines())


# ---------------------------------------------------------------------------
# crash-safe persisted state: checksum + corruption matrix + restart
# ---------------------------------------------------------------------------

def test_checksum_roundtrip_and_tamper(tmp_path):
    obj = with_checksum({"version": 1, "rows": [1, 2, 3]})
    assert CHECKSUM_FIELD in obj
    verify_checksum(dict(obj))                   # intact: passes
    tampered = dict(obj)
    tampered["rows"] = [1, 2, 4]
    with pytest.raises(CorruptStateError):
        verify_checksum(tampered)
    legacy = {"version": 1, "rows": []}          # checksum-free: accepted
    verify_checksum(legacy)


def test_atomic_json_dump_and_guarded_load(tmp_path):
    path = str(tmp_path / "state.json")
    atomic_json_dump({"version": 1, "x": 5}, path)
    assert load_json_guarded(path, lambda o: None) == \
        with_checksum({"version": 1, "x": 5})
    assert not any(p.name.startswith("state.json.tmp")
                   for p in tmp_path.iterdir())
    # a validator rejection quarantines the file aside
    hits = []
    assert load_json_guarded(
        path, lambda o: (_ for _ in ()).throw(ValueError("bad")),
        on_corrupt=lambda dst, e: hits.append(dst)) is None
    assert hits and os.path.exists(hits[0])
    assert not os.path.exists(path)


CORRUPTIONS = ("truncate", "garbage", "version", "checksum")


@pytest.mark.parametrize("mode", CORRUPTIONS)
def test_plan_cache_corruption_matrix(tmp_path, mode):
    path = str(tmp_path / "plans.json")
    cache = PlanCache(path=path, thresholds=TH4)
    cache.fused_plan(LENET, 8)
    cache.save()
    FaultInjector.corrupt_json(path, mode)
    cache2 = PlanCache(path=path, thresholds=TH4)  # constructs, no raise
    assert cache2.corrupt_recoveries               # recovery recorded
    assert os.path.exists(path + ".corrupt")       # renamed aside
    _, _, hit = cache2.fused_plan(LENET, 8)
    assert not hit and cache2.planner_calls == 1   # rebuilt from scratch
    cache2.save()
    # restart after recovery: plans load, zero replanning
    cache3 = PlanCache(path=path, thresholds=TH4)
    _, _, hit = cache3.fused_plan(LENET, 8)
    assert hit and cache3.planner_calls == 0


@pytest.mark.parametrize("mode", CORRUPTIONS)
def test_thresholds_corruption_matrix(tmp_path, mode):
    path = str(tmp_path / "thresholds.json")
    calls = []

    def measure(l, lay):
        calls.append(1)
        return H.conv_cost(l, lay, 4).total_s

    th = measured_thresholds(path, dtype="float32", measure=measure)
    assert calls                                   # first sight: measured
    FaultInjector.corrupt_json(path, mode)
    calls.clear()
    hits = []
    th2 = measured_thresholds(path, dtype="float32", measure=measure,
                              on_corrupt=lambda dst, e: hits.append(dst))
    assert th2 == th                               # re-measured, same sweep
    assert calls                                   # corrupt row re-measured
    if mode != "version":
        # version-bump keeps valid JSON+checksum: handled as unknown
        # version (row missing), not quarantined
        assert hits and os.path.exists(hits[0])
    calls.clear()
    assert measured_thresholds(path, dtype="float32",
                               measure=measure) == th
    assert not calls                               # fresh file: loads clean


def test_server_recovers_from_corrupt_cache_and_restarts_clean(tmp_path):
    srv = make_server(tmp_path)
    srv.run(make_requests(srv.cfg, 16))
    buckets = sorted(srv.reports)
    FaultInjector.corrupt_json(str(tmp_path / "plans.json"), "garbage")
    srv2 = make_server(tmp_path)                   # constructs, no raise
    assert srv2.incidents.counts["corrupt_state"] == 1
    done = srv2.run(make_requests(srv2.cfg, 16))
    assert len(done) == 16
    assert srv2.cache.planner_calls == len(buckets)  # replanned once each
    # restart AFTER recovery: the rebuilt cache serves with zero planning
    srv3 = make_server(tmp_path)
    assert srv3.incidents.total == 0
    srv3.run(make_requests(srv3.cfg, 16))
    assert srv3.cache.planner_calls == 0


# ---------------------------------------------------------------------------
# FaultTolerantRunner restart fixes (ISSUE 9 satellite)
# ---------------------------------------------------------------------------

def _counting_step(fail_at):
    """Functional step: state['x'] += 1; fails ONCE at each step in
    ``fail_at`` (by attempt count)."""
    seen = {}

    def step_fn(state, step):
        if step in fail_at and not seen.get(step):
            seen[step] = True
            raise StepFailure(f"injected at {step}")
        return {"x": state["x"] + 1}, {}

    return step_fn


def test_runner_restart_without_checkpoint_resets_to_initial(tmp_path):
    """Nothing checkpointed when the step fails: replay must restart from
    the INITIAL state, not the partially-advanced binding (the pre-§14 bug
    produced x == total + progress-before-failure)."""
    runner = FaultTolerantRunner(Checkpointer(str(tmp_path),
                                              async_write=False),
                                 save_every=100)
    step, state = runner.run({"x": 0}, _counting_step({2}), total_steps=4)
    assert step == 4 and state["x"] == 4


def test_runner_restart_protects_against_inplace_mutation(tmp_path):
    """A step_fn that mutates state in place before failing must not
    poison the replay baseline (the snapshot is a deep copy)."""
    attempts = {"n": 0}

    def step_fn(state, step):
        if step == 0 and attempts["n"] == 0:
            attempts["n"] = 1
            state["x"] += 999                     # in-place, then fail —
            raise StepFailure("boom")             # hits the caller's dict
        return {"x": state["x"] + 10}, {}

    runner = FaultTolerantRunner(Checkpointer(str(tmp_path),
                                              async_write=False),
                                 save_every=100)
    _, state = runner.run({"x": 0}, step_fn, total_steps=3)
    assert state["x"] == 30                       # replayed from x=0


def test_runner_falls_back_to_next_oldest_checkpoint(tmp_path):
    """The latest checkpoint fails validation: restore walks back to the
    next-oldest instead of dying (latest-only was the pre-§14 behavior)."""
    ck = Checkpointer(str(tmp_path), async_write=False)
    runner = FaultTolerantRunner(ck, save_every=2, keep=5)
    step_fn = _counting_step({5})
    # seed two good checkpoints, then corrupt the newer one's manifest
    state = {"x": 0}
    for s in range(4):
        state, _ = step_fn(state, s)
        if (s + 1) % 2 == 0:
            ck.save(s + 1, state)
    (tmp_path / "step_0000000004" / "manifest.json").write_text("not json")
    step, state = runner.run(state, step_fn, total_steps=6, start_step=4)
    assert step == 6 and state["x"] == 6
    assert ck.steps()                              # store still usable


def test_checkpointer_steps_listing(tmp_path):
    ck = Checkpointer(str(tmp_path), async_write=False)
    assert ck.steps() == []
    for s in (4, 2, 8):
        ck.save(s, {"x": np.float32(s)})
    assert ck.steps() == [2, 4, 8]
    assert ck.latest_step() == 8
