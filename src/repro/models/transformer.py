"""Decoder-only LM: scan-over-layers transformer for dense / MoE / VLM.

Design notes (DESIGN.md §5):

* layer parameters are stacked on a leading ``layers`` axis and consumed by
  ``lax.scan`` — one compiled layer body regardless of depth (88-layer
  configs compile as fast as 4-layer ones, and remat applies per layer);
* three entry points share the layer body: ``forward`` (training),
  ``prefill`` (returns a padded KV cache), ``decode_step`` (one token);
* MoE layers thread an auxiliary load-balance loss through the scan carry;
* activations may enter as token ids (LM) or precomputed embeddings
  (VLM / audio stub frontends).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import logical_constraint
from repro.models.attention import (
    attention_block,
    attention_decode,
    attention_decode_paged,
    attention_decode_stacked,
    attention_prefill,
    attention_specs,
    init_attention,
)
from repro.models.common import (
    KeyGen,
    apply_norm,
    cast_tree,
    embed_init,
    init_norm,
    norm_specs,
)
from repro.models.mlp import init_mlp, mlp_block, mlp_specs
from repro.models.moe import init_moe, moe_block, moe_specs


# ---------------------------------------------------------------------------
# Init / specs
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    kg = KeyGen(key)
    p: Dict[str, Any] = {
        "attn_norm": init_norm(cfg.norm, cfg.d_model),
        "attn": init_attention(kg(), cfg),
        "mlp_norm": init_norm(cfg.norm, cfg.d_model),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(kg(), cfg)
    else:
        p["mlp"] = init_mlp(kg(), cfg)
    return p


def init_lm(key: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    kg = KeyGen(key)
    layer_keys = jax.random.split(kg(), cfg.n_layers)
    layers = jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys)
    params: Dict[str, Any] = {
        "embed": embed_init(kg(), (cfg.vocab_size, cfg.d_model)),
        "layers": layers,
        "final_norm": init_norm(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(kg(), (cfg.d_model, cfg.vocab_size))
    return cast_tree(params, jnp.dtype(cfg.dtype))


def lm_specs(cfg: ModelConfig) -> Dict[str, Any]:
    lp: Dict[str, Any] = {
        "attn_norm": norm_specs(cfg.norm),
        "attn": attention_specs(cfg),
        "mlp_norm": norm_specs(cfg.norm),
    }
    if cfg.family == "moe":
        lp["moe"] = moe_specs(cfg)
    else:
        lp["mlp"] = mlp_specs(cfg)
    # prepend the stacked "layers" axis to every layer param
    lp = jax.tree_util.tree_map(lambda s: ("layers",) + s, lp,
                                is_leaf=lambda s: isinstance(s, tuple))
    specs: Dict[str, Any] = {
        "embed": ("vocab", "embed_unsharded"),
        "layers": lp,
        "final_norm": norm_specs(cfg.norm),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ("embed_unsharded", "vocab")
    return specs


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------


def _layer_fwd(lp: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig,
               positions) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = x + attention_block(
        lp["attn"], apply_norm(cfg.norm, x, lp["attn_norm"], cfg.norm_eps),
        cfg, positions=positions, causal=True)
    h = logical_constraint(h, "batch", "seq", None)
    hn = apply_norm(cfg.norm, h, lp["mlp_norm"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_block(lp["moe"], hn, cfg)
    else:
        y, aux = mlp_block(lp["mlp"], hn, cfg), jnp.zeros((), jnp.float32)
    out = h + y
    out = logical_constraint(out, "batch", "seq", None)
    return out, aux


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def embed_tokens(params, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    x = jnp.take(params["embed"], tokens, axis=0)
    return x.astype(jnp.dtype(cfg.dtype))


def unembed(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T.astype(x.dtype)
    else:
        logits = x @ params["unembed"].astype(x.dtype)
    return logical_constraint(logits, "batch", "seq", "vocab")


def lm_hidden(
    params: Dict[str, Any],
    cfg: ModelConfig,
    *,
    tokens: Optional[jnp.ndarray] = None,     # (B, S) int32
    embeds: Optional[jnp.ndarray] = None,     # (B, S, D) — VLM/audio stubs
    positions: Optional[jnp.ndarray] = None,  # (B,S) or (3,B,S) for M-RoPE
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Backbone forward. Returns (final-norm hidden (B,S,D), aux_loss) —
    the loss path unembeds per sequence chunk so full (B,S,V) logits never
    materialize (§Perf iteration C2')."""
    x = embed_tokens(params, tokens, cfg) if embeds is None \
        else embeds.astype(jnp.dtype(cfg.dtype))
    x = logical_constraint(x, "batch", "seq", None)

    body = _remat(
        lambda lp, x_: _layer_fwd(lp, x_, cfg, positions), cfg)

    def scan_body(carry, lp):
        x_, aux = carry
        x_new, aux_l = body(lp, x_)
        return (x_new, aux + aux_l), None

    (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    x = apply_norm(cfg.norm, x, params["final_norm"], cfg.norm_eps)
    return x, aux


def lm_forward(
    params: Dict[str, Any],
    cfg: ModelConfig,
    *,
    tokens: Optional[jnp.ndarray] = None,
    embeds: Optional[jnp.ndarray] = None,
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full forward. Returns (logits (B,S,V), aux_loss)."""
    x, aux = lm_hidden(params, cfg, tokens=tokens, embeds=embeds,
                       positions=positions)
    return unembed(params, x, cfg), aux


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=None) -> Dict[str, Any]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, cache_len, kvh, hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def cache_specs(cfg: ModelConfig) -> Dict[str, Any]:
    kv = ("layers", "batch", None, "kv_heads", "head_dim")
    return {"k": kv, "v": kv, "len": ()}


def lm_prefill(
    params: Dict[str, Any],
    cfg: ModelConfig,
    *,
    tokens: Optional[jnp.ndarray] = None,
    embeds: Optional[jnp.ndarray] = None,
    positions: Optional[jnp.ndarray] = None,
    cache_len: int,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Prefill pass: returns (last-token logits (B,V), populated cache)."""
    x = embed_tokens(params, tokens, cfg) if embeds is None \
        else embeds.astype(jnp.dtype(cfg.dtype))
    x = logical_constraint(x, "batch", "seq", None)
    s = x.shape[1]

    def scan_body(x_, lp):
        h = apply_norm(cfg.norm, x_, lp["attn_norm"], cfg.norm_eps)
        a, (kc, vc) = attention_prefill(lp["attn"], h, cfg, cache_len,
                                        positions=positions)
        h = x_ + a
        hn = apply_norm(cfg.norm, h, lp["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = moe_block(lp["moe"], hn, cfg)
        else:
            y = mlp_block(lp["mlp"], hn, cfg)
        out = logical_constraint(h + y, "batch", "seq", None)
        return out, (kc, vc)

    x, (k_all, v_all) = jax.lax.scan(scan_body, x, params["layers"])
    x = apply_norm(cfg.norm, x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg)[:, 0]
    cache = {"k": k_all, "v": v_all,
             "len": jnp.asarray(s, jnp.int32)}
    return logits, cache


def init_slot_cache(cfg: ModelConfig, batch: int, cache_len: int,
                    dtype=None) -> Dict[str, Any]:
    """Slot-cache layout (serving engine): like :func:`init_cache` but with
    independent per-slot lengths ``lens: (batch,)`` instead of one shared
    scalar ``len`` — each batch row is a serving slot at its own position."""
    cache = init_cache(cfg, batch, cache_len, dtype)
    del cache["len"]
    cache["lens"] = jnp.zeros((batch,), jnp.int32)
    return cache


def lm_prefill_slotted(
    params: Dict[str, Any],
    cfg: ModelConfig,
    *,
    tokens: jnp.ndarray,          # (B, L) right-padded prompts
    lens: jnp.ndarray,            # (B,) true prompt lengths (<= L)
    cache_len: int,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Bucket prefill: prompts right-padded to a shared length ``L``.

    Causality keeps each row's first ``lens[b]`` positions independent of
    the pad tail, so the gathered last-real-token logits and the cache rows
    ``< lens[b]`` are exact; pad-tail KV rows hold garbage but stay masked
    forever because the slot's length is ``lens[b]``.  Returns per-row
    last-real-token logits ``(B, V)`` and a slot cache (``lens`` per row).
    """
    x = embed_tokens(params, tokens, cfg)
    x = logical_constraint(x, "batch", "seq", None)

    def scan_body(x_, lp):
        h = apply_norm(cfg.norm, x_, lp["attn_norm"], cfg.norm_eps)
        a, (kc, vc) = attention_prefill(lp["attn"], h, cfg, cache_len)
        h = x_ + a
        hn = apply_norm(cfg.norm, h, lp["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = moe_block(lp["moe"], hn, cfg)
        else:
            y = mlp_block(lp["mlp"], hn, cfg)
        out = logical_constraint(h + y, "batch", "seq", None)
        return out, (kc, vc)

    x, (k_all, v_all) = jax.lax.scan(scan_body, x, params["layers"])
    last = jnp.take_along_axis(
        x, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)  # (B, 1, D)
    last = apply_norm(cfg.norm, last, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, last, cfg)[:, 0]
    cache = {"k": k_all, "v": v_all, "lens": lens.astype(jnp.int32)}
    return logits, cache


def lm_decode_step_slotted(
    params: Dict[str, Any],
    cache: Dict[str, Any],        # slot cache: k/v + "lens" (B,)
    tokens: jnp.ndarray,          # (B, 1) int32
    active: jnp.ndarray,          # (B,) bool: rows that hold a live request
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step over every slot with independent lengths.

    The stacked ``k``/``v`` caches ride the layer loop's carry, and each
    layer writes only its new row per slot into them: the cache passed in
    is consumed and updated in place when the caller donates it (the
    serving engine does), instead of restacked into a fresh copy.

    Inactive slots still flow through the batch (their output logits are
    garbage and ignored by the engine) but their length does not advance,
    so the next admission's prefill overwrites a clean slot."""
    x = embed_tokens(params, tokens, cfg)
    lens = cache["lens"]

    def scan_body(carry, layer):
        x_, k_all, v_all = carry
        lp, li = layer
        h = apply_norm(cfg.norm, x_, lp["attn_norm"], cfg.norm_eps)
        a, k_all, v_all = attention_decode_stacked(lp["attn"], h, k_all,
                                                   v_all, li, lens, cfg)
        h = x_ + a
        hn = apply_norm(cfg.norm, h, lp["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = moe_block(lp["moe"], hn, cfg)
        else:
            y = mlp_block(lp["mlp"], hn, cfg)
        return (h + y, k_all, v_all), None

    layer_idx = jnp.arange(cache["k"].shape[0], dtype=jnp.int32)
    (x, k_all, v_all), _ = jax.lax.scan(
        scan_body, (x, cache["k"], cache["v"]),
        (params["layers"], layer_idx))
    x = apply_norm(cfg.norm, x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg)[:, 0]
    new_cache = {"k": k_all, "v": v_all,
                 "lens": lens + active.astype(jnp.int32)}
    return logits, new_cache


def init_paged_cache(cfg: ModelConfig, slots: int, cache_len: int,
                     n_blocks: int, block_size: int,
                     dtype=None) -> Dict[str, Any]:
    """Paged cache layout: a global pool of fixed-size KV blocks shared by
    every slot, plus per-slot block tables.

    ``k``/``v``: (layers, n_blocks, block_size, KVH, hd) pools;
    ``tables``: (slots, cache_len // block_size) int32, sentinel
    ``n_blocks`` marks unallocated entries; ``lens``: per-slot lengths.
    Pools are zero-initialized so unwritten positions gather finite values
    (masked to exact zeros by the softmax)."""
    assert cache_len % block_size == 0, \
        "cache_len must be a block_size multiple"
    dtype = dtype or jnp.dtype(cfg.dtype)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (cfg.n_layers, n_blocks, block_size, kvh, hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "lens": jnp.zeros((slots,), jnp.int32),
        "tables": jnp.full((slots, cache_len // block_size), n_blocks,
                           jnp.int32),
    }


def paged_cache_specs(cfg: ModelConfig) -> Dict[str, Any]:
    """Axis-name specs for the paged cache: leaves with a "blocks" axis are
    pool-resident (spliced block/offset-wise); "batch" leaves are per-slot."""
    kv = ("layers", "blocks", "block", "kv_heads", "head_dim")
    return {"k": kv, "v": kv,
            "lens": ("batch",), "tables": ("batch", None)}


def lm_prefill_paged(
    params: Dict[str, Any],
    cfg: ModelConfig,
    *,
    tokens: jnp.ndarray,          # (B, L) right-padded prompts
    lens: jnp.ndarray,            # (B,) true prompt lengths (<= L)
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Bucket prefill for the paged engine: identical forward to the
    slotted prefill, but the K/V rows come back *unpadded* (cache_len = L)
    as a row cache the engine scatters into pool blocks — prefill never
    reserves worst-case dense rows."""
    return lm_prefill_slotted(params, cfg, tokens=tokens, lens=lens,
                              cache_len=tokens.shape[1])


def lm_decode_step_paged(
    params: Dict[str, Any],
    cache: Dict[str, Any],        # paged cache: k/v pools + lens + tables
    tokens: jnp.ndarray,          # (B, 1) int32
    active: jnp.ndarray,          # (B,) bool
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step over every slot against the shared block pool.

    Like :func:`lm_decode_step_slotted` but K/V scatter/gather goes
    through each slot's block table; inactive rows never write the pool
    (their blocks may have been reassigned)."""
    x = embed_tokens(params, tokens, cfg)
    lens, tables = cache["lens"], cache["tables"]

    def scan_body(x_, layer):
        lp, kc, vc = layer
        h = apply_norm(cfg.norm, x_, lp["attn_norm"], cfg.norm_eps)
        a, kc_new, vc_new = attention_decode_paged(
            lp["attn"], h, kc, vc, lens, tables, active, cfg)
        h = x_ + a
        hn = apply_norm(cfg.norm, h, lp["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = moe_block(lp["moe"], hn, cfg)
        else:
            y = mlp_block(lp["mlp"], hn, cfg)
        return h + y, (kc_new, vc_new)

    x, (k_all, v_all) = jax.lax.scan(
        scan_body, x, (params["layers"], cache["k"], cache["v"]))
    x = apply_norm(cfg.norm, x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg)[:, 0]
    new_cache = {"k": k_all, "v": v_all, "tables": tables,
                 "lens": lens + active.astype(jnp.int32)}
    return logits, new_cache


def lm_decode_step(
    params: Dict[str, Any],
    cache: Dict[str, Any],
    tokens: jnp.ndarray,          # (B, 1) int32
    cfg: ModelConfig,
    embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step: returns (logits (B,V), updated cache)."""
    x = embed_tokens(params, tokens, cfg) if embeds is None \
        else embeds.astype(jnp.dtype(cfg.dtype))
    pos = cache["len"]

    def scan_body(x_, layer):
        lp, kc, vc = layer
        h = apply_norm(cfg.norm, x_, lp["attn_norm"], cfg.norm_eps)
        a, kc_new, vc_new = attention_decode(lp["attn"], h, kc, vc, pos, cfg)
        h = x_ + a
        hn = apply_norm(cfg.norm, h, lp["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = moe_block(lp["moe"], hn, cfg)
        else:
            y = mlp_block(lp["mlp"], hn, cfg)
        return h + y, (kc_new, vc_new)

    x, (k_all, v_all) = jax.lax.scan(
        scan_body, x, (params["layers"], cache["k"], cache["v"]))
    x = apply_norm(cfg.norm, x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg)[:, 0]
    new_cache = {"k": k_all, "v": v_all, "len": pos + 1}
    return logits, new_cache
