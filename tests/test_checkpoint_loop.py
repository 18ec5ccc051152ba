"""Checkpointing (atomicity, gc, restore) + fault-tolerant training loop."""
import os

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from repro.checkpoint import Checkpointer
from repro.configs import reduced_config
from repro.data.lm import LMDataConfig, data_iterator, make_batch
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model
from repro.training.loop import LoopConfig, train_loop
from repro.training.step import TrainState, make_train_step


def _state():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16),
                       "step": jnp.asarray(3, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    state = _state()
    ck.save(10, state)
    step, restored = ck.restore(jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state))
    assert step == 10
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_gc_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _state())
    assert ck.all_steps() == [3, 4]
    assert ck.latest_step() == 4


def test_checkpoint_async_then_wait(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    ck.save_async(7, _state())
    ck.wait()
    assert ck.latest_step() == 7


def test_checkpoint_ignores_partial_tmp(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    os.makedirs(tmp_path / "step_0000000099.tmp")  # crashed mid-save
    ck.save(5, _state())
    assert ck.latest_step() == 5  # tmp dir never counts


def test_lm_data_deterministic_restart():
    cfg = LMDataConfig(vocab_size=97, seq_len=16, global_batch=4)
    a = make_batch(cfg, 12)
    b = make_batch(cfg, 12)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    it = data_iterator(cfg, start_step=12)
    c = next(it)
    np.testing.assert_array_equal(a["labels"], c["labels"])


@pytest.mark.slow
def test_train_loop_survives_injected_failures(tmp_path):
    """Kill the 'node' twice mid-run; the loop must restore and finish with
    exactly the same loss trajectory as an uninterrupted run."""
    cfg = reduced_config("qwen2-0.5b")
    bundle = build_model(cfg)
    data_cfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                            global_batch=4)
    lc = lambda d: LoopConfig(total_steps=12, ckpt_every=4, ckpt_dir=str(d),
                              log_every=100, max_restarts=3)

    out_clean = train_loop(bundle, lambda s: data_iterator(data_cfg, s),
                           lc(tmp_path / "clean"),
                           log=lambda *_: None, jit=True)
    assert out_clean["restarts"] == 0

    failures = {5: True, 9: True}

    def injector(step):
        if failures.pop(step, False):
            raise RuntimeError(f"injected node failure @ step {step}")

    out_faulty = train_loop(bundle, lambda s: data_iterator(data_cfg, s),
                            lc(tmp_path / "faulty"),
                            fail_injector=injector,
                            log=lambda *_: None, jit=True)
    assert out_faulty["restarts"] == 2
    # identical final params (bitwise): deterministic data + restored state
    pa = jax.tree_util.tree_leaves(out_clean["state"].params)
    pb = jax.tree_util.tree_leaves(out_faulty["state"].params)
    for a, b in zip(pa, pb):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_elastic_restore_with_resharding(tmp_path):
    """Checkpoints are mesh-agnostic: restore with explicit shardings on the
    (single-device) 'new mesh' still works leaf-for-leaf."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1,), ("data",))
    ck = Checkpointer(str(tmp_path), keep=1)
    state = _state()
    ck.save(1, state)
    shardings = jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, P()), state)
    _, restored = ck.restore(
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state),
        shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(state["a"]))
