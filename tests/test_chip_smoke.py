"""CPU rehearsal of ``chip_smoke.py``: its training and codec phases at
gpt2-xl smoke widths (codec through the platform's choice: the fused-XLA
oracle in the step, the Pallas interpreter in the codec phase), and its
refusal to run without a TPU."""
import math
from pathlib import Path

import jax
import pytest

from helpers import load_chip_smoke

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    return load_chip_smoke()


def _cfg():
    from repro.configs import resolve
    return resolve("gpt2-xl").smoke


def test_training_phase_matches_reference_and_stays_finite(smoke):
    out = smoke.training_phase(_cfg(), batch=2, seq=32, steps=3)
    assert out["batch"] == 2 and out["stages"] > 1
    assert len(out["losses"]) == 3
    assert out["edges"] and all(len(shape) == 3 and ratio > 1.0
                                for shape, ratio in out["edges"].values())
    assert all(math.isfinite(v) for v in out["losses"])
    assert out["rel_diff"] <= smoke.LOSS_RTOL
    assert out["grad_rel_diff"] <= smoke.GRAD_RTOL
    assert out["tpu_custom_call"] == (jax.default_backend() == "tpu")


@pytest.mark.parametrize("shape,ratio", [
    ((2, 32, 128), 100.0), ((1, 40, 128), 100.0),
    ((2, 32, 512), 8.5)])    # a logits edge's ratio: 482 kept per block
def test_codec_phase_parity(smoke, shape, ratio):
    out = smoke.codec_phase(shape, ratio=ratio)
    assert set(out) == {"float32", "bfloat16"}
    assert all(all(v.values()) for v in out.values())


FOUR_DEVICE_SCRIPT = """
import numpy as np, jax
from jax.sharding import Mesh
from helpers import load_chip_smoke
smoke = load_chip_smoke()
mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pod", "model"))
print(smoke.pod_edge_codec_phase(mesh, (1, 64, 128)))
"""


def test_pod_edge_codec_phase_on_four_cpu_devices(tmp_path):
    """The sharded-codec witness of ``--four-chips`` on a (2, 2) mesh of
    virtual CPU devices (codec through ``"auto"``: the fused-XLA oracle)."""
    import subprocess
    import sys
    env = {"PYTHONPATH": f"{ROOT / 'src'}:{ROOT / 'tests'}",
           "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    res = subprocess.run([sys.executable, "-c", FOUR_DEVICE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=tmp_path)
    assert res.returncode == 0, res.stderr[-2000:]
    lines = res.stdout.strip().splitlines()
    assert "sharded_codec_equal=[true, true, true, true]" in lines[-2]
    assert lines[-1] == "True"


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chips"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and out.strip() == ""


CACHE_SCRIPT = """
import jax, jax.numpy as jnp
from repro.launch.cache import CHECKOUT_CACHE, enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(3.0)).block_until_ready()
print(path, jax.config.jax_compilation_cache_dir, CHECKOUT_CACHE)
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and receives the entries; unset, the
    cache is the fixed .jax_cache/ at the checkout root."""
    import os
    import subprocess
    import sys
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)}
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    res = subprocess.run([sys.executable, "-c", CACHE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=tmp_path)
    path, configured, checkout = res.stdout.split()
    assert checkout == str(ROOT / ".jax_cache")
    want = str(tmp_path / "cache") if from_env else checkout
    assert path == configured == want
    if from_env:
        assert os.listdir(want)
