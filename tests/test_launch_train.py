"""``python -m repro.launch.train`` runs end to end at reduced size on one
and on four (virtual) devices: the data-parallel mesh, the sharding rules
and the fault-tolerant loop together."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("devices", [1, 4])
def test_launch_train_reduced_two_steps(tmp_path, devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.abspath(ROOT), "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, "-m", "repro.launch.train", "--reduced",
           "--steps", "2", "--batch", "4", "--seq", "32",
           "--ckpt-dir", str(tmp_path / "ckpt")]
    proc = subprocess.run(cmd, env=env, timeout=600, capture_output=True,
                          text=True, cwd=tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert f"devices={devices}" in proc.stdout
    assert "done: losses" in proc.stdout
