"""The chip path's host-side contract, checked on the CPU: interpret mode
follows the backend, the compile cache lands where it should, and
``chip_smoke.py`` refuses to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.kernels import resolve_interpret
from repro.runtime.compile_cache import DEFAULT_DIR

ROOT = Path(__file__).resolve().parents[1]


def _run(code_or_args, env_extra=None, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    args = (code_or_args if isinstance(code_or_args, list)
            else ["-c", code_or_args])
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("given,want", [(None, True), (True, True),
                                        (False, False)])
def test_resolve_interpret_follows_backend_on_cpu(given, want):
    assert resolve_interpret(given) is want


def test_server_interprets_on_cpu_and_serves_published_size():
    from repro.launch.cnn_serve import CNNServer
    from repro.perfmodel import calibrate
    srv = CNNServer("alexnet", calibration="analytic",
                    thresholds=calibrate(dtype_bytes=4))
    assert srv.interpret is True and srv.impl == "pallas"
    assert (srv.cfg.image_hw, srv.cfg.num_classes) == (227, 1000)
    assert srv._hw.endswith("/interpret")


def test_compile_cache_uses_env_dir_when_set(tmp_path):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.runtime.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()"
            "\n")
    r = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no compiled program in the cache dir"


def test_compile_cache_defaults_to_fixed_dir_in_checkout():
    code = ("import jax\n"
            "from repro.runtime.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    r = _run(code)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == DEFAULT_DIR == str(ROOT / ".jax_cache")


def test_chip_smoke_refuses_cpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def _smoke_module():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


@pytest.mark.parametrize("delta", [0.0, 5e-4, 1e-2])
def test_smoke_logit_metric_reads_a_logit_change(delta):
    """The logit metric ignores the softmax's per-row shift and reads a
    change of one logit as that change over the row's spread; the check
    fails past RTOL."""
    import numpy as np
    cs = _smoke_module()
    rng = np.random.default_rng(0)
    z = rng.standard_normal((4, 1000)) * 0.3

    def softmax(v):
        e = np.exp(v - v.max(axis=1, keepdims=True))
        return (e / e.sum(axis=1, keepdims=True)).astype(np.float32)
    ref = softmax(z)
    moved = z + 3.0                      # same probabilities
    moved[2, 17] += delta
    got = softmax(moved)
    zc = z - z.mean(axis=1, keepdims=True)
    want = delta * (1 - 1 / z.shape[1]) / np.abs(zc[2]).max()
    assert abs(cs.logit_diff(got, ref) - want) <= 1e-6 + 1e-3 * want
    if want <= cs.RTOL:
        assert cs.check_outputs(got, ref, "t")[1] <= cs.RTOL
    else:
        with pytest.raises(SystemExit):
            cs.check_outputs(got, ref, "t")


def test_smoke_path_never_imports_xla_flag_modules():
    """``launch/dryrun.py`` and ``launch/perf.py`` set XLA_FLAGS when
    imported; nothing the smoke path imports may pull them in."""
    code = ("import sys; sys.path.insert(0, '.')\n"
            "import chip_smoke, repro.launch.cnn_serve, repro.cnn.network\n"
            "import repro.runtime.compile_cache\n"
            "bad = {'repro.launch.dryrun', 'repro.launch.perf'} & "
            "set(sys.modules)\n"
            "assert not bad, bad\n")
    r = _run(code)
    assert r.returncode == 0, r.stderr
