"""Compile the decode-attention Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (tile alignment, VMEM limits) before any chip time is spent.  The
shapes are the serving engine's at qwen2-0.5b widths in bf16: 8 slots, 14
query heads over 2 KV heads, head_dim 64; dense over a 1024-token cache,
paged over 16-token and 8-token blocks.  The kernels are compiled
directly, because a whole decode step traced here would take the CPU
branch of ``models/attention.py``.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas)

CFG = get_config("qwen2-0.5b")
SLOTS, CACHE_LEN = 8, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or it cannot describe v5e
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One v5e chip, with the persistent compilation cache off: programs
    compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _qkv_heads():
    return CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim


def test_dense_decode_kernel_compiles_for_v5e(one_chip):
    h, kvh, hd = _qkv_heads()
    bf16 = jnp.bfloat16
    args = (_spec((SLOTS, h, hd), bf16, one_chip),
            _spec((SLOTS, CACHE_LEN, kvh, hd), bf16, one_chip),
            _spec((SLOTS, CACHE_LEN, kvh, hd), bf16, one_chip),
            _spec((SLOTS,), jnp.int32, one_chip))
    compiled = jax.jit(decode_attention_pallas).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block_size", [16, 8])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, block_size):
    h, kvh, hd = _qkv_heads()
    bf16 = jnp.bfloat16
    nb = CACHE_LEN // block_size
    pages = SLOTS * nb
    args = (_spec((SLOTS, h, hd), bf16, one_chip),
            _spec((pages, block_size, kvh, hd), bf16, one_chip),
            _spec((pages, block_size, kvh, hd), bf16, one_chip),
            _spec((SLOTS, nb), jnp.int32, one_chip),
            _spec((SLOTS,), jnp.int32, one_chip))
    compiled = jax.jit(paged_decode_attention_pallas).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
