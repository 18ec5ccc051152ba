"""Pallas TPU kernel: single-token GQA decode attention over a KV cache.

The serving hot spot: one query per sequence, KV cache of up to 512k
tokens.  Grid ``(B, KVH, n_kv)`` — each step processes one KV head's block
for all its ``rep`` grouped query heads at once (an MXU-friendly
(rep, hd) x (hd, BK) contraction), with the online-softmax state in VMEM
scratch across the sequential KV-block axis.

The valid cache length arrives as a scalar-prefetch operand
(``PrefetchScalarGridSpec``), so fully-invalid blocks are skipped via
``pl.when`` — a request at position 1k in a 512k cache does ~0.2 % of the
worst-case work (the production analogue of paged attention block tables).

VMEM per step (f32): q rep*hd + k/v 2*BK*hd + acc rep*hd + scores rep*BK.
rep = 8, BK = 512, hd = 128 → ~0.8 MB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _softmax_update(q, k, v, start, kv_len, m, l, acc, *, seq_minor):
    """Fold one tile of keys at positions ``start...`` into the online
    softmax state ``(m, l, acc)`` of a ``(rep, hd)`` query (scaled, f32),
    masking positions at or past ``kv_len``.  ``k``/``v`` are ``(n, hd)``
    tiles, or ``(hd, n)`` where ``seq_minor``."""
    n_dim = 1 if seq_minor else 0
    s = jax.lax.dot_general(q, k.astype(jnp.float32),
                            (((1,), (1 - n_dim,)), ((), ())),
                            preferred_element_type=jnp.float32)
    k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=1)
    acc = acc * corr[:, None] + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (n_dim,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def _normalise(l, acc):
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bk: int, n_kv: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[b]

    @pl.when(j * bk < kv_len)   # skip fully-invalid cache blocks
    def _compute():
        hd = q_ref.shape[-1]
        q = q_ref[0, 0].astype(jnp.float32) / (hd ** 0.5)   # (rep, hd)
        m_scr[...], l_scr[...], acc_scr[...] = _softmax_update(
            q, k_ref[0, 0], v_ref[0, 0], j * bk, kv_len, m_scr[...],
            l_scr[...], acc_scr[...], seq_minor=False)

    @pl.when(j == n_kv - 1)
    def _finish():
        o_ref[0, 0] = _normalise(l_scr[...], acc_scr[...]).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,        # (B, H, hd)
    k: jnp.ndarray,        # (B, S, KVH, hd)
    v: jnp.ndarray,
    kv_len: jnp.ndarray,   # (B,) int32
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    bk = min(block_k, s)
    assert s % bk == 0, "pad the cache to a block multiple"
    n_kv = s // bk

    qg = q.reshape(b, kvh, rep, hd)
    kt = k.swapaxes(1, 2)          # (B, KVH, S, hd)
    vt = v.swapaxes(1, 2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd),
                         lambda b_, g_, j, lens: (b_, g_, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, g_, j, lens: (b_, g_, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, g_, j, lens: (b_, g_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd),
                               lambda b_, g_, j, lens: (b_, g_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, n_kv=n_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, hd), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, kt, vt)
    return out.reshape(b, h, hd)


def _stacked_kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                    m_scr, l_scr, acc_scr, *, bk: int, n_kv: int):
    # ``_kernel``'s online softmax over sequence-minor blocks: K and V
    # arrive as (hd, BK), every KV head of the block in one grid step.  The
    # layer index is consumed by the index maps.
    del layer_ref
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[b]

    @pl.when(j * bk < kv_len)   # skip fully-invalid cache blocks
    def _compute():
        hd = q_ref.shape[-1]
        for g in range(q_ref.shape[1]):
            q = q_ref[0, g].astype(jnp.float32) / (hd ** 0.5)   # (rep, hd)
            m_scr[g], l_scr[g], acc_scr[g] = _softmax_update(
                q, k_ref[0, 0, g], v_ref[0, 0, g], j * bk, kv_len, m_scr[g],
                l_scr[g], acc_scr[g], seq_minor=True)

    @pl.when(j == n_kv - 1)
    def _finish():
        o_ref[0] = _normalise(l_scr[...], acc_scr[...]).astype(o_ref.dtype)


def decode_attention_stacked_pallas(
    q: jnp.ndarray,        # (B, H, hd)
    k_t: jnp.ndarray,      # (L, B, KVH, hd, S) every layer, sequence-minor
    v_t: jnp.ndarray,
    layer: jnp.ndarray,    # int32 scalar: the layer to attend over
    kv_len: jnp.ndarray,   # (B,) int32
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """:func:`decode_attention_pallas` over one layer of the layer-stacked
    cache, read where it lies.

    ``k_t``/``v_t`` are the ``(L, B, S, KVH, hd)`` stack seen as
    ``(L, B, KVH, hd, S)``: on a TPU that keeps the stack sequence-minor
    (``hd`` below a 128-lane tile) the transpose is a bitcast.  The layer
    and the lengths are scalar-prefetch operands, so each K/V block is
    DMA'd straight out of the stack: no plane is sliced out or relaid."""
    b, h, hd = q.shape
    kvh, s = k_t.shape[2], k_t.shape[4]
    rep = h // kvh
    # the largest block that divides S and is at most ``block_k``: every
    # S that the per-plane kernel takes (a multiple of 512) is taken here
    bk = s if s <= block_k else math.gcd(s, block_k)
    n_kv = s // bk

    qg = q.reshape(b, kvh, rep, hd)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    kv_spec = pl.BlockSpec((1, 1, kvh, hd, bk),
                           lambda b_, j, li, lens: (li[0], b_, 0, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_kv),
        in_specs=[
            pl.BlockSpec((1, kvh, rep, hd),
                         lambda b_, j, li, lens: (b_, 0, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, kvh, rep, hd),
                               lambda b_, j, li, lens: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, rep), jnp.float32),
            pltpu.VMEM((kvh, rep), jnp.float32),
            pltpu.VMEM((kvh, rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_stacked_kernel, bk=bk, n_kv=n_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, hd), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(layer, kv_len.astype(jnp.int32), qg, k_t, v_t)
    return out.reshape(b, h, hd)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, bk: int, n_kv: int):
    # The block table is consumed entirely inside the BlockSpec index maps
    # (scalar prefetch steers which pool page lands in VMEM); the online-
    # softmax body is identical to the dense kernel's.
    del tbl_ref
    _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            bk=bk, n_kv=n_kv)


def paged_decode_attention_pallas(
    q: jnp.ndarray,        # (B, H, hd)
    k_pages: jnp.ndarray,  # (P, BS, KVH, hd) global block pool
    v_pages: jnp.ndarray,
    tables: jnp.ndarray,   # (B, NB) int32 per-row block tables
    kv_len: jnp.ndarray,   # (B,) int32 valid logical prefix
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged decode attention: gather K/V pages via block tables.

    Both the table and the lengths ride as scalar-prefetch operands, so
    the K/V BlockSpec index map reads ``tables[b, j]`` to pull the j-th
    logical block of row ``b`` straight from the pool — no host gather,
    no per-row dense cache.  Unallocated table entries (sentinel >= P)
    are clamped to a valid page and masked by ``kv_len`` (positions past
    the valid prefix score ``NEG_INF`` exactly as in the dense kernel);
    fully-invalid logical blocks are skipped via ``pl.when``.
    """
    b, h, hd = q.shape
    n_pages, bs, kvh = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    nb = tables.shape[1]
    rep = h // kvh

    qg = q.reshape(b, kvh, rep, hd)
    kt = k_pages.swapaxes(1, 2)    # (P, KVH, BS, hd)
    vt = v_pages.swapaxes(1, 2)
    tbl = jnp.minimum(tables, n_pages - 1).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, nb),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd),
                         lambda b_, g_, j, tbl, lens: (b_, g_, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda b_, g_, j, tbl, lens: (tbl[b_, j], g_, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda b_, g_, j, tbl, lens: (tbl[b_, j], g_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd),
                               lambda b_, g_, j, tbl, lens: (b_, g_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, bk=bs, n_kv=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, hd), q.dtype),
        interpret=interpret,
    )(tbl, kv_len.astype(jnp.int32), qg, kt, vt)
    return out.reshape(b, h, hd)
