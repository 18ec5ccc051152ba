"""Subprocess check: shard_map EP MoE == pjit sort MoE (run on 8 devices).

Executed by tests/test_ep_moe.py with XLA_FLAGS forcing 8 host devices.
Exits non-zero on mismatch.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import ModelConfig
from repro.distributed.sharding import axis_rules, default_rules
from repro.launch.mesh import make_mesh
from repro.models.moe import init_moe, moe_block


def main():
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = default_rules(multi_pod=False)

    cfg = ModelConfig(
        name="tiny-moe", family="moe", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab_size=128, n_experts=8,
        experts_per_token=2, moe_d_ff=48, n_shared_experts=1,
        capacity_factor=8.0,  # no drops -> paths must agree exactly
        dtype="float32")

    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)

    with axis_rules(rules, mesh):
        y_sort, aux_sort = jax.jit(
            lambda p_, x_: moe_block(p_, x_, cfg))(p, x)
        cfg_ep = dataclasses.replace(cfg, moe_impl="ep_a2a")
        y_ep, aux_ep = jax.jit(
            lambda p_, x_: moe_block(p_, x_, cfg_ep))(p, x)

        # gradients must agree too (the dispatch is differentiable)
        def loss(p_, impl_cfg):
            y, aux = moe_block(p_, x, impl_cfg)
            return jnp.sum(y ** 2) + aux

        g_sort = jax.jit(jax.grad(loss), static_argnums=1)(p, cfg)
        g_ep = jax.jit(jax.grad(loss), static_argnums=1)(p, cfg_ep)

    err_y = float(jnp.abs(y_sort - y_ep).max())
    err_aux = abs(float(aux_sort) - float(aux_ep))
    print(f"y err={err_y:.3e} aux err={err_aux:.3e}")
    assert err_y < 1e-4, err_y
    assert err_aux < 1e-5, err_aux
    for k in ("router", "gate", "up", "down", "shared_gate"):
        ga, gb = g_sort[k], g_ep[k]
        err = float(jnp.abs(ga - gb).max())
        denom = float(jnp.abs(ga).max()) + 1e-9
        print(f"grad[{k}] rel err={err/denom:.3e}")
        assert err / denom < 1e-3, (k, err, denom)
    print("EP equivalence OK")


if __name__ == "__main__":
    main()
