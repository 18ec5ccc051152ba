"""The dense slot cache is updated in place (serve/engine.py).

The engine donates its cache to the decode step and to the prefill splice,
and the decode step carries the layer-stacked cache through its layer loop,
writing one row per slot and layer.  So the compiled programs alias the
cache to their output and hold no copy of the whole stack, the buffers
handed in are consumed, and the engine counts each hand-over.  The
arithmetic does not move: the bit-parity tests of tests/test_serve.py hold
unchanged.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import transformer as T
from repro.models import attention as A
from repro.models.attention import attention_decode_slotted
from repro.models.common import apply_norm
from repro.models.mlp import mlp_block
from repro.models.moe import moe_block
from repro.models.registry import build_model
from repro.serve import EngineConfig, ServeEngine, ServeRequest

SLOTS, CACHE_LEN = 4, 48
ARCHS = ["qwen2-0.5b", "dbrx-132b"]     # a dense and an MoE decoder


def _engine(arch):
    cfg = reduced_config(arch)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, params, ServeEngine(bundle, params, EngineConfig(
        slots=SLOTS, cache_len=CACHE_LEN, pad_to=8, max_prefill_batch=4))


def _aliased_param_shapes(hlo: str):
    """Shapes of the entry parameters that the module aliases to an
    output, read from its header."""
    head = hlo.split("\n", 1)[0]
    aliased = {int(i) for i in re.findall(
        r"\(\s*(\d+),\s*\{[^}]*\},\s*(?:may|must)-alias\)", head)}
    params = re.search(r"entry_computation_layout=\{\((.*?)\)->", head)
    shapes = re.findall(r"\w+\[([\d,]*)\]", params.group(1))
    return sorted(tuple(int(d) for d in shapes[i].split(",") if d)
                  for i in aliased)


def _whole_cache_copies(hlo: str, shape) -> list:
    """Copies (and asynchronous copy starts) whose result holds as many
    elements as the whole stacked cache in some order of its dimensions:
    a restack, or a transpose of the stack."""
    out = []
    for m in re.finditer(r"%([\w.-]+) = \w+\[([\d,]*)\]\S* "
                         r"(copy|copy-start)\(", hlo):
        dims = sorted(int(d) for d in m.group(2).split(",") if d)
        if dims == sorted(shape):
            out.append(m.group(1))
    return out


def _cache_shapes(cache):
    return sorted(tuple(a.shape) for a in jax.tree_util.tree_leaves(cache))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_program_updates_the_cache_in_place(arch):
    _, params, eng = _engine(arch)
    hlo = eng._decode.lower(
        params, eng.cache, jnp.zeros((SLOTS, 1), jnp.int32),
        jnp.ones((SLOTS,), bool)).compile().as_text()
    assert _aliased_param_shapes(hlo) == _cache_shapes(eng.cache)
    assert _whole_cache_copies(hlo, eng.cache["k"].shape) == []


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("rows", [1, 4])
def test_splice_program_updates_the_cache_in_place(arch, rows):
    _, params, eng = _engine(arch)
    _, cache1 = eng._prefill(params, jnp.zeros((rows, 8), jnp.int32),
                             jnp.full((rows,), 5, jnp.int32))
    slot_idx = jnp.asarray([0] + [SLOTS] * (rows - 1), jnp.int32)
    hlo = eng._splice.lower(eng.cache, cache1,
                            slot_idx).compile().as_text()
    assert _aliased_param_shapes(hlo) == _cache_shapes(eng.cache)
    assert _whole_cache_copies(hlo, eng.cache["k"].shape) == []


def test_splice_writes_real_rows_and_skips_pad_rows():
    """Each real row lands in its slot; the pad rows, which come last with
    an out-of-range slot index, touch no slot."""
    _, params, eng = _engine("qwen2-0.5b")
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 64, (4, 8)), jnp.int32)
    _, cache1 = eng._prefill(params, toks,
                             jnp.asarray([5, 8, 1, 1], jnp.int32))
    before = {k: np.asarray(v) for k, v in eng.cache.items()}
    eng.cache = eng._splice(eng.cache, cache1,
                            jnp.asarray([2, 0, SLOTS, SLOTS], jnp.int32))
    for key in ("k", "v"):
        got, new = np.asarray(eng.cache[key]), np.asarray(cache1[key])
        np.testing.assert_array_equal(got[:, 2], new[:, 0])
        np.testing.assert_array_equal(got[:, 0], new[:, 1])
        np.testing.assert_array_equal(got[:, [1, 3]], before[key][:, [1, 3]])
    np.testing.assert_array_equal(np.asarray(eng.cache["lens"]),
                                  [8, 0, 5, 0])


def _request(rid, n, max_new, seed=0):
    rng = np.random.default_rng(seed + rid)
    return ServeRequest(rid=rid, prompt=rng.integers(0, 64, n).astype(
        np.int32), max_new=max_new)


@pytest.mark.parametrize("arch", ARCHS)
def test_step_and_admitting_tick_consume_the_cache_handed_in(arch):
    _, _, eng = _engine(arch)
    eng.submit(_request(0, 5, 6))
    old = jax.tree_util.tree_leaves(eng.cache)
    eng.tick(0.0)                     # admits (prefill, splice) and decodes
    assert all(a.is_deleted() for a in old)
    st = eng.stats()
    assert st["cache_updates"] == 2 and st["cache_inplace"] == 2
    old = jax.tree_util.tree_leaves(eng.cache)
    eng.step(1.0)                     # decode only
    assert all(a.is_deleted() for a in old)
    eng.submit(_request(1, 9, 3))
    eng.submit(_request(2, 17, 2))
    while eng.has_work:
        eng.tick(2.0)
    st = eng.stats()
    assert st["cache_inplace"] == st["cache_updates"] \
        == st["decode_steps"] + st["prefill_calls"] > 3
    eng.reset()
    st = eng.stats()
    assert st["cache_updates"] == st["cache_inplace"] == 0
    assert not any(a.is_deleted() for a in jax.tree_util.tree_leaves(
        eng.cache))


def test_a_cache_update_that_is_not_donated_is_counted_as_not_in_place():
    """The counter reads what JAX did with the buffers: an undonated
    program leaves them alive and is not counted as in place."""
    _, _, eng = _engine("qwen2-0.5b")
    donating = eng._decode
    eng._decode = jax.jit(donating.__wrapped__)
    eng.submit(_request(0, 5, 3))
    while eng.has_work:
        eng.tick(0.0)
    st = eng.stats()
    assert st["cache_updates"] == st["decode_steps"] + st["prefill_calls"]
    assert st["cache_inplace"] == st["prefill_calls"] > 0


def _restacking_step(params, cache, tokens, active, cfg):
    """The slotted decode step as it was before the cache rode the carry:
    each layer's plane is scanned in as ``xs`` and restacked as ``ys``."""
    x = T.embed_tokens(params, tokens, cfg)
    lens = cache["lens"]

    def body(x_, layer):
        lp, kc, vc = layer
        h = apply_norm(cfg.norm, x_, lp["attn_norm"], cfg.norm_eps)
        a, kc, vc = attention_decode_slotted(lp["attn"], h, kc, vc, lens, cfg)
        h = x_ + a
        hn = apply_norm(cfg.norm, h, lp["mlp_norm"], cfg.norm_eps)
        y = (moe_block(lp["moe"], hn, cfg)[0] if cfg.family == "moe"
             else mlp_block(lp["mlp"], hn, cfg))
        return h + y, (kc, vc)

    x, (k_all, v_all) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    x = apply_norm(cfg.norm, x, params["final_norm"], cfg.norm_eps)
    return T.unembed(params, x, cfg)[:, 0], {
        "k": k_all, "v": v_all, "lens": lens + active.astype(jnp.int32)}


@pytest.mark.parametrize("arch", ARCHS)
def test_carried_cache_step_matches_the_restacking_step(arch):
    """The same logits and the same cache, bit for bit, from slots at
    mixed lengths (one at the cache's last row, one inactive)."""
    cfg, params, eng = _engine(arch)
    rng = np.random.default_rng(1)
    cache = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype)
             for k, v in eng.cache.items() if k != "lens"}
    cache["lens"] = jnp.asarray([5, CACHE_LEN - 1, 0, 17], jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (SLOTS, 1)),
                         jnp.int32)
    active = jnp.asarray([True, True, False, True])
    want = jax.jit(_restacking_step, static_argnums=4)(
        params, cache, tokens, active, cfg)
    got = jax.jit(T.lm_decode_step_slotted, static_argnums=4)(
        params, cache, tokens, active, cfg)
    for w, g in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("layer", [0, 2])
def test_in_place_kernel_branch_matches_the_chunked_branch(monkeypatch,
                                                          layer):
    """``attention_decode_stacked`` through the stacked kernel (forced on,
    in interpret mode) against the chunked attention it takes on the CPU:
    the same output and the same stacks, from slots at mixed lengths (one
    empty, one at a chunk's edge, one at the cache's last row)."""
    cfg = reduced_config("qwen2-0.5b")
    L, S = cfg.n_layers, 1024
    hd, kvh = cfg.resolved_head_dim, cfg.n_kv_heads
    rng = np.random.default_rng(layer)
    p = A.init_attention(jax.random.PRNGKey(layer), cfg)
    x = jnp.asarray(rng.standard_normal((SLOTS, 1, cfg.d_model)),
                    jnp.float32)
    k_all, v_all = (jnp.asarray(rng.standard_normal((L, SLOTS, S, kvh, hd)),
                                jnp.float32) for _ in range(2))
    lens = jnp.asarray([0, 511, S - 1, 700], jnp.int32)

    def step():   # a fresh function, so the second call traces again
        return jax.jit(lambda *a: A.attention_decode_stacked(*a, cfg))(
            p, x, k_all, v_all, jnp.int32(layer), lens)

    want = step()
    monkeypatch.setattr(A, "_reads_stack_in_place", lambda head_dim: True)
    got = step()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-5)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
