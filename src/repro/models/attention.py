"""GQA attention: chunked (flash-like) jnp reference + KV-cache decode.

The chunked path is the default lowering everywhere (train / prefill): an
online-softmax ``lax.scan`` over KV blocks, so no O(S²) score tensor is ever
materialized — the per-step transient is (B, Sq, H, chunk).  The Pallas
flash-attention kernel (repro/kernels/flash_attention) is the TPU-target
implementation of the same contraction and is validated against this
reference; the dry-run lowers the jnp path (Pallas does not lower on the CPU
backend — DESIGN.md §5).

Supports: grouped KV heads (GQA/MQA), qk-norm (qwen3), QKV bias (qwen2),
RoPE / M-RoPE, bidirectional (whisper encoder) and cross attention.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ModelConfig
from repro.models.common import (
    KeyGen,
    apply_mrope,
    apply_rope,
    dense_init,
    rmsnorm,
)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg: ModelConfig,
                   cross: bool = False) -> Dict[str, jnp.ndarray]:
    kg = KeyGen(key)
    d, h, kvh, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                     cfg.resolved_head_dim)
    p = {
        "q": dense_init(kg(), (d, h * hd), d),
        "k": dense_init(kg(), (d, kvh * hd), d),
        "v": dense_init(kg(), (d, kvh * hd), d),
        "o": dense_init(kg(), (h * hd, d), h * hd),
    }
    if cfg.qkv_bias:
        p["q_b"] = jnp.zeros((h * hd,), jnp.float32)
        p["k_b"] = jnp.zeros((kvh * hd,), jnp.float32)
        p["v_b"] = jnp.zeros((kvh * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def attention_specs(cfg: ModelConfig, prefix: Tuple = ()) -> Dict[str, Tuple]:
    """Logical axes per param dim (layer-stack prefix prepended by caller)."""
    p = {
        "q": prefix + ("embed", "heads"),
        "k": prefix + ("embed", "kv_heads"),
        "v": prefix + ("embed", "kv_heads"),
        "o": prefix + ("heads", "embed"),
    }
    if cfg.qkv_bias:
        p["q_b"] = prefix + ("heads",)
        p["k_b"] = prefix + ("kv_heads",)
        p["v_b"] = prefix + ("kv_heads",)
    if cfg.qk_norm:
        p["q_norm"] = prefix + (None,)
        p["k_norm"] = prefix + (None,)
    return p


# ---------------------------------------------------------------------------
# Core contraction: chunked online-softmax attention
# ---------------------------------------------------------------------------


N_CAUSAL_Q_BLOCKS = 8


def chunked_attention(
    q: jnp.ndarray,           # (B, Sq, H, hd)
    k: jnp.ndarray,           # (B, Sk, KVH, hd)
    v: jnp.ndarray,           # (B, Sk, KVH, hd)
    *,
    causal: bool,
    chunk: int = 512,
    q_offset=0,               # int or scalar array: absolute pos of q[0]
    kv_len=None,              # scalar array: valid KV prefix (decode masking)
    block_causal: bool = True,
) -> jnp.ndarray:
    """Online-softmax attention over KV chunks. Returns (B, Sq, H, hd).

    Causal full-sequence calls are q-blocked (§Perf iteration C1): the query
    range is split into ``N_CAUSAL_Q_BLOCKS`` python-unrolled blocks, each
    attending only to its causal KV prefix — skipping the fully-masked
    chunks that a single whole-q scan would compute and discard (~45 % of
    the score FLOPs at 8 blocks).
    """
    b, sq, h, hd = q.shape
    if (block_causal and causal and kv_len is None and sq == k.shape[1]
            and isinstance(q_offset, int) and q_offset == 0
            and sq >= 2 * chunk and sq % N_CAUSAL_Q_BLOCKS == 0):
        qb = sq // N_CAUSAL_Q_BLOCKS
        outs = []
        for i in range(N_CAUSAL_Q_BLOCKS):
            hi = (i + 1) * qb
            outs.append(chunked_attention(
                q[:, i * qb: hi], k[:, :hi], v[:, :hi],
                causal=True, chunk=chunk, q_offset=i * qb,
                block_causal=False))
        return jnp.concatenate(outs, axis=1)
    sk, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0
    rep = h // kvh
    if sq == 1:
        # decode fast path: no scan — scores are only (B, H, Sk), and the
        # softmax/contraction reductions over a sharded Sk lower to clean
        # psum patterns under SPMD (no dynamic slicing of sharded dims).
        # ``kv_len`` may be a scalar (all rows share a length) or a (B,)
        # vector (per-slot lengths — the serving engine's slotted decode).
        scale = 1.0 / (hd ** 0.5)
        qg = q.reshape(b, kvh, rep, hd).astype(jnp.float32) * scale
        s = jnp.einsum("bgrd,bcgd->bgrc", qg, k.astype(jnp.float32))
        k_pos = jnp.arange(sk)
        if kv_len is not None and jnp.ndim(kv_len) == 1:
            mask = k_pos[None, :] < kv_len[:, None]            # (B, Sk)
            s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        else:
            limit = sk if kv_len is None else kv_len
            mask = k_pos < limit
            if causal and q_offset is not None and kv_len is None:
                mask = mask & (k_pos <= q_offset)
            s = jnp.where(mask[None, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bgrc,bcgd->bgrd", p, v.astype(jnp.float32))
        return out.reshape(b, 1, h, hd).astype(q.dtype)
    chunk = min(chunk, sk)
    n_chunks = (sk + chunk - 1) // chunk
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    scale = 1.0 / (hd ** 0.5)
    qg = (q.reshape(b, sq, kvh, rep, hd).astype(jnp.float32) * scale)
    kc = k.reshape(b, n_chunks, chunk, kvh, hd)
    vc = v.reshape(b, n_chunks, chunk, kvh, hd)
    q_pos = q_offset + jnp.arange(sq)                      # (Sq,)
    limit = sk if kv_len is None else kv_len

    # The chunk body is checkpointed: without it, the scan's backward stores
    # every chunk's (B, Sq, H, chunk) score tensor — an O(S²) f32 residual
    # that defeats the entire point of the online softmax (measured: 7.2 GiB
    # per layer for qwen2-0.5b train_4k; see EXPERIMENTS.md §Perf iter 1).
    @jax.checkpoint
    def body(carry, inputs):
        m, l, acc = carry
        kj, vj, j = inputs
        k_pos = j * chunk + jnp.arange(chunk)              # (chunk,)
        s = jnp.einsum("bqgrd,bcgd->bqgrc", qg, kj.astype(jnp.float32))
        mask = k_pos[None, :] < limit                      # (1, chunk)
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = (acc * corr[..., None]
                   + jnp.einsum("bqgrc,bcgd->bqgrd", p,
                                vj.astype(jnp.float32)))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, kvh, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kvh, rep), jnp.float32)
    a0 = jnp.zeros((b, sq, kvh, rep, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
         jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, sq, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention block forward
# ---------------------------------------------------------------------------


def _project_qkv(p, x, cfg: ModelConfig, kv_src: Optional[jnp.ndarray] = None):
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    src = x if kv_src is None else kv_src
    sk = src.shape[1]
    q = (x @ p["q"].astype(x.dtype)).reshape(b, s, h, hd)
    k = (src @ p["k"].astype(x.dtype)).reshape(b, sk, kvh, hd)
    v = (src @ p["v"].astype(x.dtype)).reshape(b, sk, kvh, hd)
    if cfg.qkv_bias:
        q = q + p["q_b"].astype(x.dtype).reshape(h, hd)
        k = k + p["k_b"].astype(x.dtype).reshape(kvh, hd)
        v = v + p["v_b"].astype(x.dtype).reshape(kvh, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _rotate(q, k, positions, cfg: ModelConfig):
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


def attention_block(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                 # (B, S, D)
    cfg: ModelConfig,
    *,
    positions: Optional[jnp.ndarray] = None,   # (B,S) or (3,B,S) for mrope
    causal: bool = True,
    use_rope: bool = True,
) -> jnp.ndarray:
    """Self-attention over a full sequence (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    if use_rope:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        q, k = _rotate(q, k, positions, cfg)
    out = chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk)
    return out.reshape(b, s, -1) @ p["o"].astype(x.dtype)


def attention_prefill(p, x, cfg: ModelConfig, cache_len: int,
                      positions=None, use_rope: bool = True):
    """Prefill: returns (out, (k_cache, v_cache)) with caches padded to
    ``cache_len`` so decode can append in place."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    if use_rope:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        q, k = _rotate(q, k, positions, cfg)
    out = chunked_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    pad = cache_len - s
    kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y = out.reshape(b, s, -1) @ p["o"].astype(x.dtype)
    return y, (kc, vc)


def attention_decode(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                 # (B, 1, D)
    k_cache: jnp.ndarray,           # (B, S_max, KVH, hd)
    v_cache: jnp.ndarray,
    pos,                            # scalar int32: current length
    cfg: ModelConfig,
    use_rope: bool = True,
):
    """One decode step. Returns (out, k_cache, v_cache)."""
    b = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg)
    if use_rope:
        if cfg.mrope:
            positions = jnp.broadcast_to(pos, (3, b, 1))
        else:
            positions = jnp.broadcast_to(pos, (b, 1))
        q, k = _rotate(q, k, positions, cfg)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, pos, axis=1)
    out = chunked_attention(q, k_cache, v_cache, causal=False,
                            chunk=cfg.attn_chunk, kv_len=pos + 1)
    y = out.reshape(b, 1, -1) @ p["o"].astype(x.dtype)
    return y, k_cache, v_cache


def _slotted_qkv(p, x, lens, cfg: ModelConfig, use_rope: bool):
    """q/k/v of one decode token per slot, rotated at each slot's own
    position ``lens[b]``."""
    b = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg)
    if use_rope:
        if cfg.mrope:
            positions = jnp.broadcast_to(lens[None, :, None], (3, b, 1))
        else:
            positions = lens[:, None]
        q, k = _rotate(q, k, positions, cfg)
    return q, k, v


def _slotted_attend(p, x, q, k_cache, v_cache, lens, cfg: ModelConfig,
                    interpret: Optional[bool]):
    """Each slot's query against its own valid prefix ``lens[b] + 1`` of
    ``(B, S_max, KVH, hd)`` caches, then the output projection in the
    dtype of the layer input ``x``."""
    kv_len = lens + 1
    if jax.default_backend() == "tpu":
        from repro.kernels.decode_attention.ops import decode_attention
        out = decode_attention(q[:, 0], k_cache, v_cache, kv_len,
                               interpret=interpret)[:, None]
    else:
        out = chunked_attention(q, k_cache, v_cache, causal=False,
                                chunk=cfg.attn_chunk, kv_len=kv_len)
    return _out_proj(p, x, out)


def _out_proj(p, x, out):
    """The attention output projection, in the dtype of the layer input."""
    return out.reshape(x.shape[0], 1, -1) @ p["o"].astype(x.dtype)


def attention_decode_slotted(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                 # (B, 1, D)
    k_cache: jnp.ndarray,           # (B, S_max, KVH, hd)
    v_cache: jnp.ndarray,
    lens: jnp.ndarray,              # (B,) int32: per-slot current lengths
    cfg: ModelConfig,
    use_rope: bool = True,
    interpret: Optional[bool] = None,
):
    """One decode step with independent per-slot sequence lengths.

    Each batch row is a serving slot at its own position: RoPE is applied at
    ``lens[b]``, the new KV row is scattered at ``lens[b]`` (clamped so a
    finished slot at the cache boundary overwrites its own dead tail rather
    than a neighbour), and attention masks each row to its own valid prefix.
    On TPU the masked contraction is the Pallas decode-attention kernel
    (kernels/decode_attention — per-row ``kv_len`` is a scalar-prefetch
    operand there; ``interpret=None`` auto-selects the compiled kernel);
    elsewhere it is the same jnp fast path the scalar decode uses, so batch
    rows are bit-identical to a one-request decode.

    Returns (out, k_cache, v_cache).
    """
    q, k, v = _slotted_qkv(p, x, lens, cfg, use_rope)
    pos_w = jnp.minimum(lens, k_cache.shape[1] - 1)
    upd = jax.vmap(lambda c, one, pw: jax.lax.dynamic_update_slice_in_dim(
        c, one, pw, axis=0))
    k_cache = upd(k_cache, k, pos_w)
    v_cache = upd(v_cache, v, pos_w)
    y = _slotted_attend(p, x, q, k_cache, v_cache, lens, cfg, interpret)
    return y, k_cache, v_cache


def tpu_cache_layout(head_dim: int) -> Layout:
    """The layout a TPU gives a ``(L, B, S, KVH, hd)`` cache by default:
    sequence-minor where ``hd`` is narrower than a 128-lane tile (so no
    lane is padded), row-major otherwise."""
    return Layout(major_to_minor=(0, 1, 3, 4, 2) if head_dim < 128
                  else (0, 1, 2, 3, 4))


def _reads_stack_in_place(head_dim: int) -> bool:
    """Whether the decode kernel reads the layer-stacked cache where it
    lies: on a TPU that keeps the stack sequence-minor."""
    return (jax.default_backend() == "tpu"
            and tpu_cache_layout(head_dim).major_to_minor[-1] == 2)


def attention_decode_stacked(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                 # (B, 1, D)
    k_all: jnp.ndarray,             # (L, B, S_max, KVH, hd): every layer
    v_all: jnp.ndarray,
    layer,                          # scalar int32: this layer's index
    lens: jnp.ndarray,              # (B,) int32: per-slot current lengths
    cfg: ModelConfig,
    use_rope: bool = True,
    interpret: Optional[bool] = None,
):
    """:func:`attention_decode_slotted` against the layer-stacked cache.

    Writes each slot's new KV row at ``[layer, b, min(lens[b], S_max-1)]``
    with one row-sized dynamic-update-slice per slot, so a stack carried
    through the layer loop is updated in place, then attends over layer
    ``layer``'s plane, which holds what the slotted step's plane would:
    the arithmetic is the same.  On TPU the stack is held in its default
    layout (:func:`tpu_cache_layout`): left free, layout assignment moves
    the whole carry into the kernel's layout, copying it in and out of the
    layer loop, and a scatter copies it into a row-major layout and back.

    Where that layout is sequence-minor (``hd`` < 128), the stacked decode
    kernel reads layer ``layer``'s blocks straight out of the stack, seen
    as ``(L, B, KVH, hd, S)`` through a transpose that is a bitcast there.
    Where it is row-major (``hd`` >= 128) the plane is sliced out and goes
    to the per-plane kernel, whose block would be another body here.
    Returns (out, k_all, v_all).
    """
    q, k, v = _slotted_qkv(p, x, lens, cfg, use_rope)
    pos_w = jnp.minimum(lens, k_all.shape[2] - 1)
    on_tpu = jax.default_backend() == "tpu"
    layout = tpu_cache_layout(k_all.shape[-1])

    def pin(c):
        return with_layout_constraint(c, layout) if on_tpu else c

    k_all, v_all = pin(k_all), pin(v_all)
    for b in range(x.shape[0]):
        at = (layer, b, pos_w[b], 0, 0)
        k_all = jax.lax.dynamic_update_slice(k_all, k[b][None, None], at)
        v_all = jax.lax.dynamic_update_slice(v_all, v[b][None, None], at)
    k_all, v_all = pin(k_all), pin(v_all)
    if _reads_stack_in_place(k_all.shape[-1]):
        from repro.kernels.decode_attention.ops import (
            decode_attention_stacked)
        seq_minor = (0, 1, 3, 4, 2)
        out = decode_attention_stacked(
            q[:, 0], jnp.transpose(k_all, seq_minor),
            jnp.transpose(v_all, seq_minor), layer, lens + 1,
            interpret=interpret)[:, None]
        return _out_proj(p, x, out), k_all, v_all
    y = _slotted_attend(p, x, q, k_all[layer], v_all[layer], lens, cfg,
                        interpret)
    return y, k_all, v_all


def attention_decode_paged(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                 # (B, 1, D)
    k_pool: jnp.ndarray,            # (P, BS, KVH, hd) global block pool
    v_pool: jnp.ndarray,
    lens: jnp.ndarray,              # (B,) int32: per-slot current lengths
    tables: jnp.ndarray,            # (B, NB) int32 block tables
    active: jnp.ndarray,            # (B,) bool: rows holding live requests
    cfg: ModelConfig,
    use_rope: bool = True,
    interpret: Optional[bool] = None,
):
    """One decode step against a paged (block-pool) KV cache.

    Identical per-row arithmetic to :func:`attention_decode_slotted`, but
    K/V live in a global pool of fixed-size blocks addressed through each
    slot's block table.  The new KV row is scattered at the block/offset
    of logical position ``lens[b]``; the write is *dropped* for inactive
    rows (``mode="drop"`` via the sentinel block index) — a freed block
    may already belong to another slot, so unlike the dense path an
    inactive row must not touch the pool at all.

    Off-TPU the contraction gathers each row's blocks into a contiguous
    ``(B, NB*BS, KVH, hd)`` view and reuses the exact sq==1 jnp fast path
    — when ``NB*BS`` equals the dense engine's ``cache_len``, the result
    is bit-identical to the dense slotted decode (same shapes, same
    reduction order; invalid positions mask to exact zeros).  On TPU the
    paged Pallas kernel consumes the table directly via scalar prefetch.

    Returns (out, k_pool, v_pool).
    """
    b = x.shape[0]
    n_blocks, bs = k_pool.shape[0], k_pool.shape[1]
    span = tables.shape[1] * bs
    q, k, v = _project_qkv(p, x, cfg)
    if use_rope:
        if cfg.mrope:
            positions = jnp.broadcast_to(lens[None, :, None], (3, b, 1))
        else:
            positions = lens[:, None]
        q, k = _rotate(q, k, positions, cfg)
    pos_w = jnp.minimum(lens, span - 1)
    blk = jnp.take_along_axis(tables, (pos_w // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(active, blk, n_blocks)      # inactive rows: dropped
    off = pos_w % bs
    k_pool = k_pool.at[blk, off].set(k[:, 0], mode="drop")
    v_pool = v_pool.at[blk, off].set(v[:, 0], mode="drop")
    kv_len = lens + 1
    if jax.default_backend() == "tpu":
        from repro.kernels.decode_attention.ops import paged_decode_attention
        out = paged_decode_attention(q[:, 0], k_pool, v_pool, tables,
                                     kv_len, interpret=interpret)[:, None]
    else:
        from repro.kernels.decode_attention.ref import gather_paged_kv
        kd, vd = gather_paged_kv(k_pool, v_pool, tables)
        out = chunked_attention(q, kd, vd, causal=False,
                                chunk=cfg.attn_chunk, kv_len=kv_len)
    y = out.reshape(b, 1, -1) @ p["o"].astype(x.dtype)
    return y, k_pool, v_pool


def cross_attention_block(p, x, enc_out, cfg: ModelConfig) -> jnp.ndarray:
    """Cross attention (whisper decoder): queries from x, KV from encoder."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, kv_src=enc_out)
    out = chunked_attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
    return out.reshape(b, s, -1) @ p["o"].astype(x.dtype)
