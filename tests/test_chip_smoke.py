"""chip_smoke.py's phases at reduced size on the CPU, and its refusal to
run without a TPU.  The phases are the script's own functions, so a change
that would break the run on the chip breaks these first."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def search_data(smoke):
    return smoke.make_search_data(0, n_samples=48, length=60000,
                                  decimation=16)


SMALL_NAS = dict(train_steps=4, init_population=4, children_per_gen=4,
                 n_accept=2, n_workers=2, train_batch=16)


def test_search_phase_cpu(smoke, search_data):
    res = smoke.search_phase(search_data, 0, generations=1, platform="cpu",
                             **SMALL_NAS)
    assert res["outcomes"]["failed"] == res["outcomes"]["diverged"] == 0
    assert res["outcomes"]["trained"] >= 4
    assert set(res["times"]) == {"search.init_population",
                                 "search.generation_1",
                                 "search.compile_winner"}


def test_search_phase_rejects_wrong_platform(smoke, search_data):
    with pytest.raises(smoke.SmokeFailure, match="training data lived on"):
        smoke.search_phase(search_data, 0, generations=0, platform="tpu",
                           **SMALL_NAS)


def test_search_phase_fails_on_failed_candidates(smoke, search_data,
                                                 monkeypatch):
    from repro.core import trainer_batch

    def boom(*a, **k):
        raise RuntimeError("injected training failure")

    monkeypatch.setattr(trainer_batch, "train_candidate", boom)
    with pytest.raises(smoke.SmokeFailure, match="failed"):
        smoke.search_phase(search_data, 0, generations=0, platform="cpu",
                           **SMALL_NAS)


@pytest.fixture(scope="module")
def served(smoke):
    from repro.configs import reduced_config
    return smoke.serving_phases(
        reduced_config("qwen2-0.5b"), 0, n_requests=3, prompt_range=(5, 20),
        max_new=4, slots=2, cache_len=32, block_size=8, pad_to=8,
        n_reference=2, interpret=True)


def test_serving_phases_cpu(served):
    dense = served["engines"]["dense"]["outputs"]
    paged = served["engines"]["paged"]["outputs"]
    assert dense == paged and len(dense) == 3
    # float32 weights on the CPU: both engines equal the reference exactly
    assert served["reference_matches"] == {"dense": 8, "paged": 8}
    assert max(served["kernel_err"].values()) < 1e-5


def test_router_phase_cpu(smoke, served):
    dev = jax.devices()[0]
    out = served["engines"]["dense"]["outputs"]
    res = smoke.router_phase(served["bundle"], served["params"],
                             served["prompts"], [dev, dev], out,
                             max_new=4, slots=2, cache_len=32, pad_to=8)
    assert res["stats"]["completed"] == 3


def test_row_error_catches_a_dropped_block_on_a_long_row(smoke):
    from repro.kernels.decode_attention.ref import decode_attention_ref
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (2, 2, 64))
    k = jax.random.normal(kk, (2, 1024, 1, 64))
    v = jax.random.normal(kv, (2, 1024, 1, 64))
    kv_len = jax.numpy.asarray([1024, 1024])
    ref = decode_attention_ref(q, k, v, kv_len)
    # the last 16-token block left out of every row
    faulty = decode_attention_ref(q, k, v, kv_len - 16)
    assert smoke.row_error(ref, ref).max() == 0.0
    assert smoke.row_error(faulty, ref).min() > 2 * smoke.KERNEL_TOL


def test_check_served_flags_shed_requests(smoke):
    from repro.serve.engine import ServeRequest
    r = ServeRequest(rid=0, prompt=np.zeros(3, np.int32), max_new=2,
                     out=[1, 2], done=True, oom=True)
    with pytest.raises(smoke.SmokeFailure, match="shed"):
        smoke.check_served([r], 1, 2, 10, "engine")
    r.oom, r.out = False, [1, 10]
    with pytest.raises(smoke.SmokeFailure, match="vocabulary"):
        smoke.check_served([r], 1, 2, 10, "engine")


AFFINE_CHECK = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {root!r})
import chip_smoke as s
data = s.make_search_data(0, n_samples=48, length=60000, decimation=16)
res = s.affine_search_phase(data, 0, platform="cpu", generations=1,
                            **{nas!r})
print("DEVICES_USED", len(res["devices_used"]))
"""


def test_affine_search_phase_four_virtual_devices():
    code = AFFINE_CHECK.format(root=os.path.abspath(ROOT), nas=SMALL_NAS)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env, timeout=600,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DEVICES_USED" in proc.stdout


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_repo", "script_alone"])
def test_main_refuses_without_tpu(tmp_path, alone):
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], env=env, timeout=300,
                          capture_output=True, text=True, cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    # alone, the script finds no program to run and falls back to nothing
    assert ("No module named 'repro'" if alone else "no TPU found") \
        in proc.stderr
