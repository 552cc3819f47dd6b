"""The reduction from a profiler trace to device numbers."""
import gzip
import os

import pytest

from chipbench import trace as T
from chipbench.tests.conftest import HERE

PALLAS = ('%conv_fused.4 = f32[64,256,338]{2,1,0:T(8,128)} custom-call('
          'f32[64,96,2356]{2,1,0} %copy.36), '
          'custom_call_target="tpu_custom_call"')
COPY = '%copy.31 = f32[128,48,3762]{2,1,0:T(8,128)} copy(f32[128] %x)'


def test_reduce_busy_idle_and_gaps():
    ms = 1_000_000
    dev = {"/device:TPU:0": [
        (10 * ms, 30 * ms, PALLAS),
        (25 * ms, 40 * ms, COPY),        # overlaps the kernel
        (70 * ms, 80 * ms, PALLAS),
        (95 * ms, 120 * ms, COPY),       # runs past the window
    ]}
    host = {"bench.window": [(0, 100 * ms)],
            "bench.step": [(0, 45 * ms), (62 * ms, 85 * ms)],
            "bench.wait": [(45 * ms, 62 * ms)],
            "bench.admit": [(85 * ms, 100 * ms)],
            "np.asarray(jax.Array)": [(78 * ms, 90 * ms)]}
    s = T.reduce(dev, host, devices=1)
    assert s["window_s"] == pytest.approx(0.1)
    # busy: [10, 40] + [70, 80] + [95, 100] = 45 ms
    assert s["busy_s"] == pytest.approx(0.045)
    assert s["pallas_s"] == pytest.approx(0.030)
    assert s["other_s"] == pytest.approx(0.020)
    gaps = s["breakdown"]["idle_gaps"]
    # longest first: [40,70] mostly waiting (17 ms against 13 in steps),
    # [80,95] mostly admitting while a copy to the host runs, [0,10] in a
    # step with no traced call
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.015, 0.010])
    assert [g[0] for g in gaps] == [
        "bench.wait > python", "bench.admit > np.asarray(jax.Array)",
        "bench.step > python"]
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["conv_fused.4 f32[64,256,338]"] == pytest.approx(0.030)
    assert ops["copy.31 f32[128,48,3762]"] == pytest.approx(0.020)


def test_reduce_averages_over_devices_and_needs_the_window():
    ms = 1_000_000
    dev = {f"/device:TPU:{i}": [(0, (i + 1) * 10 * ms, PALLAS)]
           for i in range(4)}
    host = {"bench.window": [(0, 100 * ms)]}
    s = T.reduce(dev, host, devices=4)
    assert s["busy_s"] == pytest.approx(0.025)
    with pytest.raises(ValueError):
        T.reduce(dev, {}, devices=4)
    with pytest.raises(ValueError):
        T.reduce(dev, host, devices=8)
    assert T.reduce({}, host, devices=1) is None


def test_is_pallas():
    assert T.is_pallas(PALLAS)
    assert not T.is_pallas(COPY)


def test_recorded_trace(tmp_path):
    """A trace recorded on one v5e by a 0.4 s ``--trace 1`` run of
    ``alexnet.offline`` (four steps at bucket 128), kept gzipped."""
    path = tmp_path / "alexnet_offline.xplane.pb"
    with gzip.open(os.path.join(HERE, "data",
                                "alexnet_offline.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    s = T.summarize(str(path), devices=1)
    assert s["window_s"] == pytest.approx(0.44594604)
    assert s["busy_s"] == pytest.approx(0.075876901)
    assert s["pallas_s"] == pytest.approx(0.064682756)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["pallas_s"] > s["other_s"] > 0
    names = [n for n, _ in s["breakdown"]["device_ops"]]
    assert any(n.startswith("conv_fused") for n in names)
    assert any(n.startswith("conv_stack") for n in names)
    assert all(g[0].startswith("bench.") for g in s["breakdown"][
        "idle_gaps"])
