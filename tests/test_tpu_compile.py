"""Compile the decode-attention Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (tile alignment, VMEM limits) before any chip time is spent.  The
shapes are the serving engine's at qwen2-0.5b widths in bf16: 8 slots, 14
query heads over 2 KV heads, head_dim 64; dense over a 1024-token cache,
paged over 16-token and 8-token blocks; the stacked kernel over the
benchmark's 24 layers x 32 slots x 4096, and x 6144.  The kernels are compiled
directly.  The serving engine's whole decode step and splice are compiled
too, at the benchmark's 32 slots x 4096, with ``jax.default_backend``
steered to the TPU so that ``models/attention.py`` takes its TPU branch.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, decode_attention_stacked_pallas,
    paged_decode_attention_pallas)
from repro.models.attention import tpu_cache_layout
from repro.models.registry import build_model
from repro.serve import EngineConfig, ServeEngine

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


@pytest.mark.parametrize("cache_len", [4096, 6144])
def test_stacked_decode_kernel_compiles_for_v5e(one_chip, cache_len):
    """The stacked kernel at the benchmark's shapes, and over a cache that
    is not a multiple of its default block: its blocks come straight out
    of the (L, B, KVH, hd, S) view of the whole cache."""
    h, kvh, hd = _qkv_heads()
    bf16 = jnp.bfloat16
    stack = (CFG.n_layers, 32, kvh, hd, cache_len)
    args = (_spec((32, h, hd), bf16, one_chip),
            _spec(stack, bf16, one_chip), _spec(stack, bf16, one_chip),
            _spec((), jnp.int32, one_chip),
            _spec((32,), jnp.int32, one_chip))
    compiled = jax.jit(decode_attention_stacked_pallas).lower(
        *args).compile()
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


@pytest.mark.parametrize("head_dim", [64, 128])
def test_pinned_cache_layout_is_the_chips_default(one_chip, head_dim):
    """The decode step holds its carried cache in the layout the chip
    gives the cache's arrays, so entering the layer loop copies nothing."""
    spec = _spec((24, 32, 4096, 2, head_dim), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda a: a + 1).lower(spec).compile()
    default = compiled.input_formats[0][0].layout
    assert default.major_to_minor == \
        tpu_cache_layout(head_dim).major_to_minor


def _ops_of_size(hlo, n):
    """Instructions whose result has ``n`` elements, by kind."""
    kinds = {}
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(", hlo):
        e = 1
        for d in m.group(1).split(","):
            e *= int(d) if d else 1
        if e == n:
            kinds[m.group(2)] = kinds.get(m.group(2), 0) + 1
    return kinds


def test_engine_decode_and_splice_update_the_cache_in_place_on_v5e(
        one_chip, monkeypatch):
    """qwen2-0.5b's decode step and an 8-row splice into 32 slots x 4096:
    the cache is aliased to the output, no whole-cache copy is left, and
    the step's temporaries stay far below one cache (1.6 GB).  The step's
    kernel reads the stack in place: no layer's plane is sliced out or
    relaid, and the stack only enters, is written row by row and is seen
    through a bitcast."""
    bundle = build_model(CFG)
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    eng = ServeEngine(bundle, shapes, EngineConfig(slots=2, cache_len=16))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = on_chip(shapes)
    cache = on_chip(jax.eval_shape(lambda: bundle.make_slot_cache(32, 4096)))
    rows = on_chip(jax.eval_shape(lambda: bundle.make_slot_cache(8, 4096)))
    decode = eng._decode.lower(
        params, cache, _spec((32, 1), jnp.int32, one_chip),
        _spec((32,), jnp.bool_, one_chip)).compile()
    splice = eng._splice.lower(
        cache, rows, _spec((8,), jnp.int32, one_chip)).compile()
    cache_bytes = 2 * cache["k"].size * 2
    for compiled in (decode, splice):
        hlo = compiled.as_text()
        assert "input_output_alias" in hlo.split("\n", 1)[0]
        kinds = _ops_of_size(hlo, cache["k"].size)
        assert "copy" not in kinds and "copy-start" not in kinds, kinds
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes
        assert mem.temp_size_in_bytes < cache_bytes / 8
    hlo = decode.as_text()
    assert not _ops_of_size(hlo, cache["k"].size // CFG.n_layers)
    assert set(_ops_of_size(hlo, cache["k"].size)) <= {
        "dynamic-update-slice", "bitcast", "parameter", "get-tuple-element"}
    kernels = re.findall(r"%([\w.-]+) = .* custom-call\(.*"
                         r'custom_call_target="tpu_custom_call"', hlo)
    assert kernels and all(k.startswith("decode_attention")
                           for k in kernels), kernels


@pytest.mark.parametrize("head_dim,kernel", [
    (64, "decode_attention_stacked"), (128, "decode_attention")])
def test_stacked_decode_takes_the_kernel_its_cache_layout_reads(
        one_chip, monkeypatch, head_dim, kernel):
    """A sequence-minor stack (hd < 128) goes to the stacked kernel; a
    row-major one (hd >= 128) keeps the per-plane kernel on its plane."""
    from repro.kernels.decode_attention import ops
    from repro.models.attention import (attention_decode_stacked,
                                        init_attention)
    cfg = dataclasses.replace(CFG, head_dim=head_dim)
    called = []

    def spy(name):
        real = getattr(ops, name)

        def call(*a, **k):
            called.append(name)
            return real(*a, **k)
        monkeypatch.setattr(ops, name, call)

    spy("decode_attention")
    spy("decode_attention_stacked")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = jax.eval_shape(lambda: init_attention(jax.random.PRNGKey(0), cfg))
    b, stack = SLOTS, (2, SLOTS, CACHE_LEN, cfg.n_kv_heads, head_dim)
    args = (jax.tree_util.tree_map(
                lambda a: _spec(a.shape, a.dtype, one_chip), p),
            _spec((b, 1, cfg.d_model), jnp.bfloat16, one_chip),
            _spec(stack, jnp.bfloat16, one_chip),
            _spec(stack, jnp.bfloat16, one_chip),
            _spec((), jnp.int32, one_chip),
            _spec((b,), jnp.int32, one_chip))
    step = jax.jit(lambda *a: attention_decode_stacked(*a, cfg))
    hlo = step.lower(*args).compile().as_text()
    assert called == [kernel]
    assert "tpu_custom_call" in hlo
