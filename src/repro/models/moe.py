"""Routed mixture-of-experts with sort-based, capacity-bounded dispatch.

Static-shape, jit/SPMD-safe dispatch (the standard TPU formulation):

1. top-k routing per token;
2. stable-sort the (token, expert) pairs by expert id;
3. position-in-segment (cumsum of per-expert counts) gives each pair a slot
   in a fixed ``(E, capacity, D)`` buffer — overflow tokens are dropped
   (their contribution falls back to the residual stream);
4. batched expert FFN: ``einsum('ecd,edf->ecf')`` — the contraction the
   Pallas ``moe_gmm`` kernel implements on TPU;
5. scatter-add results back, weighted by the (renormalized) router gates.

Expert weights carry the ``experts`` logical axis so expert parallelism maps
them over the ``model`` mesh axis.  An auxiliary load-balance loss (Switch
style) is returned for training.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import logical_constraint
from repro.models.common import KeyGen, dense_init


def init_moe(key: jax.Array, cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    kg = KeyGen(key)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    p = {
        "router": dense_init(kg(), (d, e), d),
        "gate": dense_init(kg(), (e, d, f), d),
        "up": dense_init(kg(), (e, d, f), d),
        "down": dense_init(kg(), (e, f, d), f),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared_gate"] = dense_init(kg(), (d, fs), d)
        p["shared_up"] = dense_init(kg(), (d, fs), d)
        p["shared_down"] = dense_init(kg(), (fs, d), fs)
    return p


def moe_specs(cfg: ModelConfig, prefix: Tuple = ()) -> Dict[str, Tuple]:
    p = {
        "router": prefix + ("embed", None),
        "gate": prefix + ("experts", "embed", "expert_mlp"),
        "up": prefix + ("experts", "embed", "expert_mlp"),
        "down": prefix + ("experts", "expert_mlp", "embed"),
    }
    if cfg.n_shared_experts:
        p["shared_gate"] = prefix + ("embed", "mlp")
        p["shared_up"] = prefix + ("embed", "mlp")
        p["shared_down"] = prefix + ("mlp", "embed")
    return p


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    cap = math.ceil(n_tokens * cfg.experts_per_token / cfg.n_experts
                    * cfg.capacity_factor)
    return max(8, -(-cap // 8) * 8)  # round up to a multiple of 8


def moe_block(p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ModelConfig,
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out (B,S,D), aux_loss scalar)."""
    from repro.distributed.sharding import current_mesh, current_rules
    mesh, rules = current_mesh(), current_rules()
    if (cfg.moe_impl == "ep_a2a" and mesh is not None and rules is not None
            and x.shape[1] % mesh.shape.get(
                rules.get("experts") or "", 1) == 0):
        return moe_block_ep(p, x, cfg, mesh, rules)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    t = b * s
    xf = x.reshape(t, d)

    logits = (xf @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)                # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss.
    me = probs.mean(axis=0)                                 # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[experts.reshape(-1)].add(
        1.0 / (t * k))
    aux = e * jnp.sum(me * ce)

    # ---- sort-based dispatch -------------------------------------------
    cap = expert_capacity(t, cfg)
    flat_e = experts.reshape(-1)                            # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    token_idx = order // k
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    seg_start = jnp.cumsum(counts) - counts                 # (E,)
    pos = jnp.arange(t * k, dtype=jnp.int32) - seg_start[sorted_e]
    keep = pos < cap
    pos_c = jnp.where(keep, pos, 0)

    xt = jnp.where(keep[:, None], xf[token_idx], 0)         # (T*k, D)
    buf = jnp.zeros((e, cap, d), x.dtype).at[sorted_e, pos_c].add(xt)

    # ---- expert FFN (the moe_gmm contraction) ---------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                               p["gate"].astype(x.dtype))) \
        * jnp.einsum("ecd,edf->ecf", buf, p["up"].astype(x.dtype))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["down"].astype(x.dtype))

    # ---- combine ---------------------------------------------------------
    vals = out_buf[sorted_e, pos_c]                         # (T*k, D)
    gates_sorted = gates.reshape(-1)[order].astype(x.dtype)
    contrib = jnp.where(keep[:, None], vals * gates_sorted[:, None], 0)
    y = jnp.zeros((t, d), x.dtype).at[token_idx].add(contrib)

    if cfg.n_shared_experts:
        hs = jax.nn.silu(xf @ p["shared_gate"].astype(x.dtype)) \
            * (xf @ p["shared_up"].astype(x.dtype))
        y = y + hs @ p["shared_down"].astype(x.dtype)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism (§Perf iteration B2 — beyond-paper)
# ---------------------------------------------------------------------------
#
# The pjit sort-based dispatch above is correct but the SPMD partitioner
# lowers its data-dependent scatter/gather as replicate + all-reduce of the
# full (T, D) token buffer PER LAYER (measured: 7.5 TB/device/step on
# kimi-k2 train_4k; constraining the buffers made it worse — see
# EXPERIMENTS.md §Perf B1).  This path does the textbook thing instead:
# tokens stay on their home shard, and two explicit all_to_all exchanges
# over the expert-parallel ("model") axis move only the routed activations:
#
#   route locally -> bucket by destination shard -> all_to_all ->
#   local per-expert capacity buffers -> expert FFN (gmm) ->
#   all_to_all back -> weighted combine.
#
# FSDP composes: expert weights arrive (E_loc, D/fsdp, F) and are
# all-gathered over the fsdp axis inside the block; the transpose of that
# gather is the reduce-scatter that FSDP backward requires.


def _shard_map(f, mesh, in_specs, out_specs):
    """shard_map without replication checking."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _dispatch_local(ids, n_buckets, capacity):
    """Stable-sort (row -> bucket) assignment with per-bucket capacity.

    Returns (order, bucket_of_sorted, slot_of_sorted, keep)."""
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    counts = jnp.zeros((n_buckets,), jnp.int32).at[ids].add(1)
    seg_start = jnp.cumsum(counts) - counts
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32) - seg_start[sorted_ids]
    keep = pos < capacity
    return order, sorted_ids, jnp.where(keep, pos, 0), keep


def moe_block_ep(p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ModelConfig,
                 mesh, rules) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map + all_to_all. x: (B, S, D)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import spec_for

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    f = cfg.moe_d_ff

    def ax(name):
        v = rules.get(name)
        return v if v is None or isinstance(v, tuple) else (v,)

    batch_axes = tuple(a for a in (ax("batch") or ()) if a in mesh.axis_names)
    model_ax = (ax("experts") or (None,))[0]
    fsdp_axes = tuple(a for a in (ax("embed") or ())
                      if a in mesh.axis_names)
    n_model = mesh.shape[model_ax]
    n_fsdp = 1
    for a in fsdp_axes:
        n_fsdp *= mesh.shape[a]
    e_loc = e // n_model
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    # per-device token count: batch over data axes, seq over the model axis
    t_loc = (b // n_batch) * (s // n_model)
    c_send = -(-int(t_loc * k / n_model * cfg.capacity_factor) // 8) * 8
    c_loc = -(-int(n_model * c_send / e_loc * cfg.capacity_factor) // 8) * 8

    def body(xb, router_w, gate_w, up_w, down_w):
        # xb: (B_loc, S_loc, D); weights: (E_loc, D/fsdp, F)
        for a2 in fsdp_axes:     # FSDP: gather the expert weights
            gate_w = jax.lax.all_gather(gate_w, a2, axis=1, tiled=True)
            up_w = jax.lax.all_gather(up_w, a2, axis=1, tiled=True)
            down_w = jax.lax.all_gather(down_w, a2, axis=2, tiled=True)
        xf = xb.reshape(-1, d)                              # (T_loc, D)
        logits = (xf @ router_w.astype(xf.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, k)            # (T_loc, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[experts.reshape(-1)].add(
            1.0 / (xf.shape[0] * k))
        aux_names = tuple(a for a in (batch_axes + (model_ax,)) if a)
        aux = e * jnp.sum(jax.lax.pmean(me, aux_names)
                          * jax.lax.pmean(ce, aux_names))

        flat_e = experts.reshape(-1)                        # (T_loc*k,)
        token_idx_all = jnp.arange(flat_e.shape[0]) // k
        dest = flat_e // e_loc                              # target shard
        order, dest_s, slot_s, keep_s = _dispatch_local(
            dest, n_model, c_send)
        tok_s = token_idx_all[order]
        send = jnp.zeros((n_model, c_send, d), xb.dtype).at[
            dest_s, slot_s].add(
            jnp.where(keep_s[:, None], xf[tok_s], 0))
        # metadata: local expert id (or -1 for empty slots)
        send_exp = jnp.full((n_model, c_send), -1, jnp.int32).at[
            dest_s, slot_s].max(jnp.where(keep_s, flat_e[order] % e_loc, -1))

        recv = jax.lax.all_to_all(send, model_ax, split_axis=0,
                                  concat_axis=0, tiled=False)
        recv_exp = jax.lax.all_to_all(send_exp[..., None], model_ax,
                                      split_axis=0, concat_axis=0,
                                      tiled=False)[..., 0]

        rx = recv.reshape(n_model * c_send, d)
        rexp = recv_exp.reshape(-1)
        valid = rexp >= 0
        rexp_c = jnp.where(valid, rexp, 0)
        order2, exp_s, slot2, keep2 = _dispatch_local(rexp_c, e_loc, c_loc)
        keep2 = keep2 & valid[order2]
        ebuf = jnp.zeros((e_loc, c_loc, d), xb.dtype).at[exp_s, slot2].add(
            jnp.where(keep2[:, None], rx[order2], 0))

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ebuf,
                                   gate_w.astype(xb.dtype))) \
            * jnp.einsum("ecd,edf->ecf", ebuf, up_w.astype(xb.dtype))
        obuf = jnp.einsum("ecf,efd->ecd", h, down_w.astype(xb.dtype))

        vals2 = obuf[exp_s, slot2]                          # (R, D)
        back_rows = jnp.zeros((n_model * c_send, d), xb.dtype).at[
            order2].add(jnp.where(keep2[:, None], vals2, 0))
        ret = jax.lax.all_to_all(back_rows.reshape(n_model, c_send, d),
                                 model_ax, split_axis=0, concat_axis=0,
                                 tiled=False)

        got = ret[dest_s, slot_s]                           # (T_loc*k, D)
        gates_s = gates.reshape(-1)[order].astype(xb.dtype)
        contrib = jnp.where(keep_s[:, None], got * gates_s[:, None], 0)
        y = jnp.zeros((t_loc, d), xb.dtype).at[tok_s].add(contrib)
        return y.reshape(xb.shape), aux

    x_spec = P(batch_axes or None, model_ax, None)
    w_spec = P(model_ax, fsdp_axes or None, None)
    w_spec_down = P(model_ax, None, fsdp_axes or None)
    y, aux = _shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec_down),
        out_specs=(x_spec, P()),
    )(x, p["router"], p["gate"], p["up"], p["down"])

    if cfg.n_shared_experts:
        xf = x.reshape(-1, d)
        hs = jax.nn.silu(xf @ p["shared_gate"].astype(x.dtype)) \
            * (xf @ p["shared_up"].astype(x.dtype))
        y = y + (hs @ p["shared_down"].astype(x.dtype)).reshape(y.shape)
    return y, aux
