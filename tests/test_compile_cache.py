"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache:
in ``JAX_COMPILATION_CACHE_DIR`` when it is set, and nowhere else;
otherwise in ``.jax_cache`` at the root of the checkout.  Each case runs
in its own process against a copy of the module placed in a scratch
checkout, so the real checkout's cache is never touched."""
import os
import shutil
import subprocess
import sys

import pytest

MODULE = os.path.join(os.path.dirname(__file__), "..", "src", "repro",
                      "launch", "compile_cache.py")

COMPILE = """
import importlib.util
spec = importlib.util.spec_from_file_location("compile_cache", {path!r})
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
import jax, jax.numpy as jnp
print("CACHE_DIR", mod.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _entries(path):
    return os.listdir(path) if os.path.isdir(path) else []


@pytest.mark.parametrize("placed", [True, False],
                         ids=["env_dir", "checkout_default"])
def test_compile_cache_location(tmp_path, placed):
    module = tmp_path / "src" / "repro" / "launch" / "compile_cache.py"
    module.parent.mkdir(parents=True)
    shutil.copy(MODULE, module)
    default = str(tmp_path / ".jax_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    proc = subprocess.run(
        [sys.executable, "-c", COMPILE.format(path=str(module))], env=env,
        cwd=tmp_path, timeout=300, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    want = str(tmp_path / "placed") if placed else default
    assert f"CACHE_DIR {want}" in proc.stdout
    assert _entries(want), f"no cache entry in {want}"
    if placed:
        assert not _entries(default)
