"""Per-kernel allclose sweeps vs pure-jnp oracles (interpret=True on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.conv1d.ops import dwsep_conv1d
from repro.kernels.conv1d.ref import dwsep_conv1d_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.moe_gmm.ops import gmm
from repro.kernels.moe_gmm.ref import gmm_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref
from repro.models.mamba2 import ssd_chunked

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------- conv1d ---
@pytest.mark.parametrize("B,L,Cin,K,Cout,S", [
    (2, 64, 2, 5, 8, 1),
    (1, 200, 8, 3, 16, 2),
    (3, 97, 4, 7, 32, 4),
    (2, 50, 16, 1, 2, 1),
    (1, 33, 2, 3, 130, 1),     # C_out > one lane block -> multi-block grid
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv1d_matches_ref(B, L, Cin, K, Cout, S, dtype):
    x = jnp.asarray(RNG.normal(size=(B, L, Cin)), dtype)
    dw = jnp.asarray(RNG.normal(size=(K, Cin)), dtype)
    pw = jnp.asarray(RNG.normal(size=(Cin, Cout)), dtype)
    b = jnp.asarray(RNG.normal(size=(Cout,)), dtype)
    got = dwsep_conv1d(x, dw, pw, b, stride=S, interpret=True)
    want = dwsep_conv1d_ref(x, dw, pw, b, stride=S)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@given(k=st.sampled_from([1, 3, 5, 7]), s=st.sampled_from([1, 2, 4]),
       cin=st.sampled_from([2, 4, 8]), cout=st.sampled_from([2, 8, 32]))
@settings(max_examples=12, deadline=None)
def test_conv1d_hypothesis_sweep(k, s, cin, cout):
    rng = np.random.default_rng(k * 100 + s * 10 + cin + cout)
    L = 64
    x = jnp.asarray(rng.normal(size=(1, L, cin)), jnp.float32)
    dw = jnp.asarray(rng.normal(size=(k, cin)), jnp.float32)
    pw = jnp.asarray(rng.normal(size=(cin, cout)), jnp.float32)
    b = jnp.zeros((cout,), jnp.float32)
    got = dwsep_conv1d(x, dw, pw, b, stride=s, interpret=True)
    want = dwsep_conv1d_ref(x, dw, pw, b, stride=s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- flash attention ---
@pytest.mark.parametrize("B,S,H,KVH,hd,causal", [
    (2, 64, 4, 2, 32, True),
    (1, 128, 8, 1, 64, True),     # MQA
    (2, 96, 6, 6, 16, False),     # MHA bidirectional
    (1, 256, 4, 4, 128, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(B, S, H, KVH, hd, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, KVH, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, KVH, hd)), dtype)
    got = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=32, block_k=32)
    want = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_chunked_jnp_attention_matches_ref():
    from repro.models.attention import chunked_attention
    q = jnp.asarray(RNG.normal(size=(2, 96, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 96, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 96, 2, 32)), jnp.float32)
    got = chunked_attention(q, k, v, causal=True, chunk=16)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------------- ssd ---
@pytest.mark.parametrize("B,L,H,P,G,N,Q", [
    (2, 64, 4, 16, 1, 16, 16),
    (1, 128, 8, 32, 2, 32, 32),
    (2, 96, 6, 8, 3, 8, 24),
])
def test_ssd_kernel_and_chunked_match_naive(B, L, H, P, G, N, Q):
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, size=(B, L, H)), jnp.float32)
    a_neg = -jnp.asarray(RNG.uniform(1, 8, size=(H,)), jnp.float32)
    bm = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    want, state_ref = ssd_ref(x, dt, a_neg, bm, cm)
    got_pallas = ssd(x, dt, a_neg, bm, cm, chunk=Q, interpret=True)
    got_jnp, state_jnp = ssd_chunked(x, dt, a_neg, bm, cm, Q)
    np.testing.assert_allclose(np.asarray(got_pallas), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state_jnp), np.asarray(state_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssd_decode_matches_sequence():
    """Step-by-step decode must reproduce the chunked full-sequence output."""
    from repro.models.mamba2 import ssd_decode_step
    B, L, H, P, G, N = 1, 16, 2, 8, 1, 8
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, size=(B, L, H)), jnp.float32)
    a_neg = -jnp.asarray(RNG.uniform(1, 8, size=(H,)), jnp.float32)
    bm = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    full, _ = ssd_ref(x, dt, a_neg, bm, cm)
    state = jnp.zeros((B, H, N, P), jnp.float32)
    outs = []
    for t in range(L):
        y, state = ssd_decode_step(x[:, t:t+1], dt[:, t:t+1], a_neg,
                                   bm[:, t:t+1], cm[:, t:t+1], state)
        outs.append(y)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- moe gmm ---
@pytest.mark.parametrize("E,C,D,F", [(4, 32, 64, 48), (8, 16, 128, 64),
                                     (2, 64, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_matches_ref(E, C, D, F, dtype):
    x = jnp.asarray(RNG.normal(size=(E, C, D)), dtype)
    w = jnp.asarray(RNG.normal(size=(E, D, F)), dtype)
    got = gmm(x, w, interpret=True, block_c=16, block_f=16, block_d=32)
    want = gmm_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ------------------------------------------------------- decode attention ---
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref


@pytest.mark.parametrize("B,S,H,KVH,hd,bk", [
    (2, 128, 4, 2, 32, 32),
    (1, 256, 8, 1, 64, 64),     # MQA, long cache
    (3, 64, 6, 6, 16, 16),      # MHA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(B, S, H, KVH, hd, bk, dtype):
    rng = np.random.default_rng(B * 100 + S)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, hd)), dtype)
    lens = jnp.asarray(rng.integers(1, S, B), jnp.int32)
    got = decode_attention(q, k, v, lens, interpret=True, block_k=bk)
    want = decode_attention_ref(q, k, v, lens)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_empty_blocks_skipped():
    """kv_len=1 in a long cache: only block 0 contributes (block-skip path)."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 32)), jnp.float32)
    lens = jnp.asarray([1], jnp.int32)
    got = decode_attention(q, k, v, lens, interpret=True, block_k=32)
    want = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("H,S,block_k,lens", [
    (4, 1024, 256, [1, 256, 256, 257, 1024]),       # rep 2
    (14, 1024, None, [1, 511, 512, 513, 1024]),     # rep 7, own blocks
    (14, 1024, 1024, [1, 512, 1023, 1024, 1024]),   # one block
    (14, 1536, 1024, [1, 511, 512, 513, 1536]),     # S not a block multiple
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_stacked_matches_ref_on_every_layer(
        H, S, block_k, lens, dtype):
    """The stacked kernel reads layer ``layer`` of an (L, B, S, KVH, hd)
    stack through its (L, B, KVH, hd, S) view, the layer a traced scalar;
    lengths at 1, at and around a block boundary, and the whole cache.
    Where ``block_k`` does not divide S the block is the largest divisor
    of S below it (1536 over 1024: blocks of 512)."""
    from repro.kernels.decode_attention.kernel import (
        decode_attention_stacked_pallas)
    from repro.kernels.decode_attention.ref import decode_attention_ref
    L, B, KVH, hd = 3, 5, 2, 64
    sizes = {"block_k": block_k} if block_k else {}
    rng = np.random.default_rng(H + S)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), dtype)
    k_all = jnp.asarray(rng.normal(size=(L, B, S, KVH, hd)), dtype)
    v_all = jnp.asarray(rng.normal(size=(L, B, S, KVH, hd)), dtype)
    lens = jnp.asarray(lens, jnp.int32)
    seq_minor = (0, 1, 3, 4, 2)
    k_t, v_t = jnp.transpose(k_all, seq_minor), jnp.transpose(v_all, seq_minor)
    attend = jax.jit(functools.partial(decode_attention_stacked_pallas,
                                       interpret=True, **sizes))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for layer in range(L):
        got = attend(q, k_t, v_t, jnp.int32(layer), lens)
        want = decode_attention_ref(q, k_all[layer], v_all[layer], lens)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
