"""Public op: decode attention (Pallas on TPU, oracle elsewhere)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, decode_attention_stacked_pallas,
    paged_decode_attention_pallas)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref, gather_paged_kv, paged_decode_attention_ref)

NEG_INF = -1e30


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> auto: compiled on TPU, interpreter everywhere else.

    ``jax.default_backend()`` is static at trace time, so this is safe to
    call under ``jit`` (the choice is baked into the compiled program).
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


@functools.partial(jax.jit, static_argnames=("impl", "interpret", "block_k"))
def decode_attention(q, k, v, kv_len, *, impl: str = "pallas",
                     interpret: Optional[bool] = None, block_k: int = 512
                     ) -> jnp.ndarray:
    """Single-token GQA attention. q: (B,H,hd); k/v: (B,S,KVH,hd);
    kv_len: (B,) valid prefix lengths.

    ``interpret=None`` auto-selects: the compiled Pallas kernel on TPU,
    interpret mode elsewhere (so CPU/GPU callers never hit the Mosaic
    lowering path by accident, and TPU callers never silently run the
    interpreter)."""
    if impl == "ref":
        return decode_attention_ref(q, k, v, kv_len)
    return decode_attention_pallas(q, k, v, kv_len, block_k=block_k,
                                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_stacked(q, k_t, v_t, layer, kv_len, *,
                             interpret: Optional[bool] = None
                             ) -> jnp.ndarray:
    """Single-token GQA attention over layer ``layer`` of the stacked
    cache, read in place.  q: (B,H,hd); k_t/v_t: (L,B,KVH,hd,S), the
    ``(L,B,S,KVH,hd)`` stack transposed (a bitcast where the TPU keeps it
    sequence-minor); layer: int32 scalar; kv_len: (B,)."""
    return decode_attention_stacked_pallas(
        q, k_t, v_t, layer, kv_len, interpret=resolve_interpret(interpret))


def paged_decode_attention_chunked(q, k_pages, v_pages, tables, kv_len,
                                   *, pages_per_chunk: int = 8
                                   ) -> jnp.ndarray:
    """Non-TPU fast path: online softmax over page-table chunks.

    Never materializes the full (B, NB*BS, ...) gathered cache — each
    ``lax.scan`` step gathers ``pages_per_chunk`` pages per row and folds
    them into running (m, l, acc) online-softmax state, so peak memory is
    bounded by the chunk, not the logical context.  Matches the paged
    reference to float tolerance (the accumulation order differs, so it is
    deliberately *not* the engine's bit-parity path).
    """
    b, h, hd = q.shape
    n_pages, bs, kvh = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    nb = tables.shape[1]
    rep = h // kvh
    ppc = min(pages_per_chunk, nb)
    pad = (-nb) % ppc
    tbl = jnp.minimum(tables, n_pages - 1).astype(jnp.int32)
    if pad:
        # Sentinel-pad to a chunk multiple; padded pages sit past every
        # row's kv_len and are masked below.
        tbl = jnp.concatenate(
            [tbl, jnp.zeros((b, pad), jnp.int32)], axis=1)
    n_chunks = tbl.shape[1] // ppc
    chunks = tbl.reshape(b, n_chunks, ppc).swapaxes(0, 1)   # (NC, B, PPC)

    qg = q.reshape(b, kvh, rep, hd).astype(jnp.float32) / (hd ** 0.5)

    def body(carry, inp):
        m, l, acc = carry
        c, tbl_c = inp                                       # (B, PPC)
        kc = k_pages[tbl_c].astype(jnp.float32)              # (B,PPC,BS,KVH,hd)
        vc = v_pages[tbl_c].astype(jnp.float32)
        kc = kc.reshape(b, ppc * bs, kvh, hd)
        vc = vc.reshape(b, ppc * bs, kvh, hd)
        s = jnp.einsum("bgrd,bcgd->bgrc", qg, kc)            # (B,KVH,rep,C)
        pos = c * (ppc * bs) + jnp.arange(ppc * bs)          # logical positions
        mask = pos[None, :] < kv_len[:, None]                # (B, C)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bgrc,bcgd->bgrd", p, vc)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, kvh, rep), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, rep), jnp.float32),
            jnp.zeros((b, kvh, rep, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init,
                                  (jnp.arange(n_chunks), chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, hd).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("impl", "interpret", "pages_per_chunk"))
def paged_decode_attention(q, k_pages, v_pages, tables, kv_len, *,
                           impl: str = "auto",
                           interpret: Optional[bool] = None,
                           pages_per_chunk: int = 8) -> jnp.ndarray:
    """Paged single-token GQA attention over a global block pool.

    q: (B,H,hd); k/v_pages: (P,BS,KVH,hd); tables: (B,NB) int32 block
    tables (sentinel >= P marks unallocated slots); kv_len: (B,) valid
    logical prefix lengths.

    ``impl``: "auto" runs the Pallas kernel when it would compile (TPU, or
    an explicit ``interpret=True``... the auto default keeps TPU on the
    compiled kernel) and the chunked online-softmax path elsewhere;
    "pallas" forces the kernel (interpret auto-resolved); "chunked" forces
    the scan path; "ref" is the dense-gather oracle.
    """
    if impl == "ref":
        return paged_decode_attention_ref(q, k_pages, v_pages, tables,
                                          kv_len)
    if impl == "chunked":
        return paged_decode_attention_chunked(
            q, k_pages, v_pages, tables, kv_len,
            pages_per_chunk=pages_per_chunk)
    itp = resolve_interpret(interpret)
    if impl == "pallas" or not itp:
        return paged_decode_attention_pallas(q, k_pages, v_pages, tables,
                                             kv_len, interpret=itp)
    return paged_decode_attention_chunked(
        q, k_pages, v_pages, tables, kv_len,
        pages_per_chunk=pages_per_chunk)


__all__ = [
    "decode_attention",
    "decode_attention_stacked",
    "paged_decode_attention",
    "paged_decode_attention_chunked",
    "paged_decode_attention_ref",
    "gather_paged_kv",
    "resolve_interpret",
]
