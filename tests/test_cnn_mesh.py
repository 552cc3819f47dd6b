"""Multi-chip CNN serving mesh (ISSUE 10 / DESIGN.md §15): shard-batch
planning invariant, PlanCache ``devices`` keying + legacy-file roundtrip,
sharded-vs-single-device ``forward_fused`` differentials, and the sharded
server smoke.

Planner/cache tests are pure arithmetic and run on any host (tier-1).
The subprocess differential forces fake host devices via
``tests.util.run_with_devices`` so it ALSO runs on 1-device tier-1; the
in-process ``multidevice``-marked differentials and server smoke need the
mesh CI job (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.cnn_networks import LENET
from repro.cnn.layers import init_cnn
from repro.cnn.network import forward_fused, input_shape, plan_network_fused
from repro.distributed.cnn_mesh import (ShardPlanError, cnn_data_mesh,
                                        forward_fused_sharded,
                                        replicate_params, shard_batch_for,
                                        shard_flip, verify_shard_plan)
from repro.perfmodel import calibrate
from repro.serve import PlanCache, pad_to_bucket
from tests.util import run_with_devices


# ---------------------------------------------------------------------------
# shard-batch planning invariant (pure planner arithmetic, tier-1)
# ---------------------------------------------------------------------------

def test_shard_batch_for_ceil_and_validation():
    assert shard_batch_for(128, 8) == 16
    assert shard_batch_for(9, 4) == 3          # ceil: last shard padded
    assert shard_batch_for(1, 1) == 1
    assert shard_batch_for(7, 8) == 1
    with pytest.raises(ValueError):
        shard_batch_for(0, 1)
    with pytest.raises(ValueError):
        shard_batch_for(8, 0)


def test_per_shard_nt_flip_is_taken_not_inherited():
    """The property at the heart of §15: a global batch above Nt whose
    per-shard batch falls below it MUST replan — lenet at fp32 has Nt=64,
    so 128 globally is CHWN-side while 128/8=16 per shard is NCHW-side."""
    gsig, ssig = shard_flip(LENET, 128, 8)
    assert gsig != ssig, "expected sharding to flip the layout choice"
    splan = plan_network_fused(LENET.replace(batch=16))
    verify_shard_plan(splan, LENET, 16)        # the shard-batch plan passes
    gplan = plan_network_fused(LENET.replace(batch=128))
    with pytest.raises(ShardPlanError):
        verify_shard_plan(gplan, LENET, 16)    # the leaked global plan fails


def test_plan_cache_devices_key_hit_miss():
    cache = PlanCache(thresholds=calibrate(dtype_bytes=4))
    # sharded admission plans the PER-SHARD bucket
    p1, b1, hit1 = cache.fused_plan(LENET, 128, devices=8)
    assert b1 == 16 and not hit1 and cache.planner_calls == 1
    # re-admission at the same (bucket, devices) hits — compile once
    p2, b2, hit2 = cache.fused_plan(LENET, 128, devices=8)
    assert hit2 and b2 == 16 and cache.planner_calls == 1
    assert p2.conv_signature == p1.conv_signature
    # the pre-sharded entry point (callers already holding the per-shard
    # batch) must resolve to the SAME key the global-batch call planned —
    # dividing by devices twice would miss into a bogus bucket-2 key
    p1s, b1s, hit1s = cache.fused_plan(LENET, 16, devices=8,
                                       pre_sharded=True)
    assert hit1s and b1s == 16 and cache.planner_calls == 1
    assert p1s is p1
    assert cache.peek_fused(LENET, 16, devices=8, pre_sharded=True) is p1
    # same shard bucket at a DIFFERENT mesh width is its own key: an
    # 8-chip row must not silently serve from the 4-chip entry
    _, b3, hit3 = cache.fused_plan(LENET, 64, devices=4)
    assert b3 == 16 and not hit3 and cache.planner_calls == 2
    # unsharded admission of the same global batch plans the global
    # bucket — and takes the other side of the Nt flip
    p4, b4, hit4 = cache.fused_plan(LENET, 128)
    assert b4 == 128 and not hit4
    assert p4.conv_signature != p1.conv_signature
    with pytest.raises(ValueError):
        cache.fused_plan(LENET, 16, devices=0)


def test_plan_cache_devices_legacy_file_roundtrip(tmp_path):
    path = str(tmp_path / "plans.json")
    cache = PlanCache(path=path, thresholds=calibrate(dtype_bytes=4))
    cache.fused_plan(LENET, 8)                  # single-chip (legacy) key
    cache.fused_plan(LENET, 64, devices=4)      # mesh key
    cache.save()
    # single-chip keys serialize WITHOUT the devices field, so a cache
    # holding only devices=1 plans is byte-compatible with pre-§15 files;
    # the mesh key carries devices=4 explicitly
    payload = json.load(open(path))
    keys = [e["key"] for e in payload["fused"]]
    assert sum("devices" in k for k in keys) == 1
    assert {k.get("devices", 1) for k in keys} == {1, 4}
    loaded = PlanCache(path=path)
    _, _, h1 = loaded.fused_plan(LENET, 8)
    _, _, h2 = loaded.fused_plan(LENET, 64, devices=4)
    assert h1 and h2 and loaded.planner_calls == 0


# ---------------------------------------------------------------------------
# sharded-vs-single differential, subprocess (runs on 1-device tier-1 too)
# ---------------------------------------------------------------------------

def test_sharded_forward_matches_unsharded_subprocess():
    out = run_with_devices("""
import jax, jax.numpy as jnp
from repro.configs.cnn_networks import LENET
from repro.cnn.layers import init_cnn
from repro.cnn.network import forward_fused, input_shape, plan_network_fused
from repro.distributed.cnn_mesh import (cnn_data_mesh, forward_fused_sharded,
                                        replicate_params, verify_shard_plan)

D, shard = 4, 4
cfg = LENET.replace(batch=shard * D)
scfg = LENET.replace(batch=shard)
plan = plan_network_fused(scfg)
verify_shard_plan(plan, LENET, shard)
params = init_cnn(jax.random.PRNGKey(0), scfg)
x = jax.random.normal(jax.random.PRNGKey(1), input_shape(cfg), jnp.float32)

mesh = cnn_data_mesh(D)
ys = forward_fused_sharded(replicate_params(params, mesh), x, scfg, plan,
                           mesh, impl="pallas", interpret=True)
# unsharded reference: the same per-shard plan applied shard by shard
# (bit-identical blocking), and the global-batch plan (numerical check)
yr = jnp.concatenate([forward_fused(params, x[i*shard:(i+1)*shard], scfg,
                                    plan, impl="pallas", interpret=True)[0]
                      for i in range(D)])
yg, _ = forward_fused(params, x, cfg, plan_network_fused(cfg), impl="pallas",
                      interpret=True)
print("maxdiff_shardplan=%.3e" % float(jnp.abs(ys - yr).max()))
print("maxdiff_globalplan=%.3e" % float(jnp.abs(ys - yg).max()))
""", n_devices=4)
    diffs = dict(line.split("=") for line in out.split() if "=" in line)
    assert float(diffs["maxdiff_shardplan"]) <= 1e-5
    assert float(diffs["maxdiff_globalplan"]) <= 1e-5


def assert_staged_matches_direct(srv, reqs, done):
    """Every request a mesh server served, bit-equal to a direct jitted
    sharded forward over ``pad_to_bucket`` of its admitted batch: the
    staged flat path changes no output.  ``srv.run`` admits ``reqs`` in
    order, ``max_bucket * devices`` at a time."""
    d = srv.devices
    cap = srv.cache.max_bucket * d
    for k in range(0, len(reqs), cap):
        batch = reqs[k:k + cap]
        bkt = srv._shard_bucket(len(batch))
        plan = srv.cache.peek_fused(srv.cfg, bkt, dtype=srv.dtype,
                                    policy=srv.dtype_policy, devices=d,
                                    pre_sharded=True)
        x = pad_to_bucket(jnp.asarray(np.stack([r.image for r in batch])),
                          bkt * d)
        y = np.asarray(jax.jit(lambda p, x: forward_fused_sharded(
            p, x, srv.cfg.replace(batch=bkt), plan, srv._mesh,
            impl="xla"))(srv.params, x))
        for i, r in enumerate(batch):
            np.testing.assert_array_equal(done[r.rid], y[i])
    assert srv.staging_allocs == 1


def test_sharded_server_staged_matches_direct_subprocess():
    """The mesh server at devices=2 through the staged path: a full global
    batch, then a short one whose last shard is padded."""
    out = run_with_devices("""
import numpy as np
from repro.launch.cnn_serve import CNNServer, ImageRequest
from tests.test_cnn_mesh import assert_staged_matches_direct

srv = CNNServer("lenet", max_bucket=4, impl="xla", calibration="analytic",
                devices=2)
rng = np.random.default_rng(0)
reqs = [ImageRequest(i, rng.standard_normal((1, 28, 28)).astype(np.float32))
        for i in range(11)]
done = srv.run(reqs)
assert len(done) == len(reqs)
assert sorted(srv.reports) == [2, 4]
assert_staged_matches_direct(srv, reqs, done)
print("staged_ok")
""", n_devices=2)
    assert "staged_ok" in out


# ---------------------------------------------------------------------------
# in-process multi-device tier (mesh CI job)
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
@pytest.mark.parametrize("devices", [2, 4, 8])
@pytest.mark.parametrize("policy", ["uniform", "mixed"])
def test_sharded_matches_unsharded(multi_devices, devices, policy):
    if devices > multi_devices:
        pytest.skip(f"host exposes {multi_devices} devices, need {devices}")
    shard = 2
    scfg = LENET.replace(batch=shard)
    cfg = LENET.replace(batch=shard * devices)
    plan = plan_network_fused(scfg, policy=policy)
    params = init_cnn(jax.random.PRNGKey(0), scfg)
    x = jax.random.normal(jax.random.PRNGKey(devices), input_shape(cfg),
                          jnp.float32)
    mesh = cnn_data_mesh(devices)
    ys = forward_fused_sharded(replicate_params(params, mesh), x, scfg,
                               plan, mesh, impl="xla")
    yr = jnp.concatenate([
        forward_fused(params, x[i * shard:(i + 1) * shard], scfg, plan,
                      impl="xla")[0] for i in range(devices)])
    assert float(jnp.abs(ys - yr).max()) <= 1e-5
    assert ys.shape == (shard * devices, LENET.num_classes)


@pytest.mark.multidevice
def test_sharded_server_smoke(multi_devices, tmp_path):
    """CNNServer --devices path end to end: per-shard bucket admission,
    zero drops, zero repeat replans, per-chip accounting populated."""
    from repro.launch.cnn_serve import CNNServer, ImageRequest
    d = min(multi_devices, 4)
    srv = CNNServer("lenet", max_bucket=8, impl="xla",
                    calibration="analytic", devices=d,
                    cache_path=str(tmp_path / "plans.json"))
    rng = np.random.default_rng(0)
    c, h = srv.cfg.in_channels, srv.cfg.image_hw
    reqs = [ImageRequest(i, rng.standard_normal((c, h, h)).astype(np.float32))
            for i in range(3 * d + 1)]
    done = srv.run(reqs)
    assert len(done) == len(reqs)
    rr = sum(max(0, st.misses - 1) for st in srv.cache.per_key.values())
    assert rr == 0
    assert all(k.devices == d for k in srv.cache.per_key)
    # every cached key's bucket is an ADMITTED shard bucket — a planner or
    # executor dividing by devices twice would mint a bogus smaller key
    # (devices=d, one miss each), invisible to the rr/devices checks above
    assert {k.bucket for k in srv.cache.per_key} == set(srv.reports)
    assert any(rep.per_chip_bytes > 0 for rep in srv.reports.values())
    # every executed global batch is shard_bucket * devices wide, and the
    # plan the executor ran IS the shard-batch plan: the global-batch and
    # pre-sharded cache entry points resolve to one entry, and that plan
    # passes the §15 shard invariant at the executed shard bucket
    for b, rep in srv.reports.items():
        assert rep.hbm_bytes == rep.per_chip_bytes * d
        plan = srv.cache.peek_fused(srv.cfg, b, dtype=srv.dtype,
                                    policy=srv.dtype_policy, devices=d,
                                    pre_sharded=True)
        assert plan is not None
        assert plan is srv.cache.peek_fused(srv.cfg, b * d,
                                            dtype=srv.dtype,
                                            policy=srv.dtype_policy,
                                            devices=d)
        verify_shard_plan(plan, srv.cfg, b, dtype=srv.dtype,
                          policy=srv.dtype_policy)
    assert_staged_matches_direct(srv, reqs, done)
