"""Plain reference of a Qwen2 decoder (arXiv:2407.10671; the published
``Qwen2ForCausalLM``), and the seeded weights the benchmark serves.

The forward pass is straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision: token embedding, then per layer RMSNorm,
GQA self-attention with q/k/v biases and rotary embeddings (half-split
``rotate_half`` convention, theta from the config), a causal softmax, the
output projection, RMSNorm and a SwiGLU MLP, each with its residual; a
final RMSNorm and the tied embedding as the output head.  No kernel,
cache, padding bucket or batching: one sequence at a time over all its
positions.

``control=True`` computes the same forward with every projection's
inputs, activations and weights alike, rounded to float8 e4m3 with one
scale per tensor: the precision below the bfloat16 that the
configuration serves in.  It is the check's control and never runs in a
benchmark run.

The weights are made here, from the seed, in the parameter layout the
serving engine takes (layers stacked on a leading axis), in the
configuration's dtype (bfloat16).
The reference reads the same arrays in float32.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"layers": cfg["num_hidden_layers"], "d": d, "heads": h,
            "kv_heads": cfg["num_key_value_heads"], "head_dim": d // h,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"]}


def make_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Seeded weights in the configuration's ``torch_dtype``, made on the
    device in one jitted call."""
    s = sizes(cfg)
    n, d, h, kvh, hd, ff, v = (s["layers"], s["d"], s["heads"],
                               s["kv_heads"], s["head_dim"], s["ff"],
                               s["vocab"])

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def mat(shape, fan_in):
            return jax.random.normal(next(ks), shape, jnp.float32) \
                / np.sqrt(fan_in)

        def small(shape, std):
            return jax.random.normal(next(ks), shape, jnp.float32) * std

        layers = {
            "attn_norm": {"scale": 1.0 + small((n, d), 0.1)},
            "attn": {"q": mat((n, d, h * hd), d),
                     "k": mat((n, d, kvh * hd), d),
                     "v": mat((n, d, kvh * hd), d),
                     "o": mat((n, h * hd, d), h * hd),
                     "q_b": small((n, h * hd), 0.1),
                     "k_b": small((n, kvh * hd), 0.1),
                     "v_b": small((n, kvh * hd), 0.1)},
            "mlp_norm": {"scale": 1.0 + small((n, d), 0.1)},
            "mlp": {"gate": mat((n, d, ff), d), "up": mat((n, d, ff), d),
                    "down": mat((n, ff, d), ff)},
        }
        params = {"embed": small((v, d), 0.02), "layers": layers,
                  "final_norm": {"scale": 1.0 + small((d,), 0.1)}}
        dtype = jnp.dtype(cfg["torch_dtype"])
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)

    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _f8(x: jnp.ndarray) -> jnp.ndarray:
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x, w, control: bool):
    if control:
        x, w = _f8(x), _f8(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    # x: (S, H, hd); rotate_half: the first half pairs with the second
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def logits_at(cfg: Dict[str, Any], params: Dict[str, Any],
              tokens: jnp.ndarray, *, control: bool = False) -> jnp.ndarray:
    """Float32 logits ``(S, vocab)`` of one sequence at every position."""
    s = sizes(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    h, kvh, hd = s["heads"], s["kv_heads"], s["head_dim"]
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    seq = tokens.shape[0]
    pos = jnp.arange(seq)
    x = f32(params["embed"])[tokens]
    causal = pos[:, None] >= pos[None, :]

    def layer(x, lp):
        lp = f32(lp)
        a = lp["attn"]
        hn = _rms(x, lp["attn_norm"]["scale"], eps)
        q = (_mm(hn, a["q"], control) + a["q_b"]).reshape(seq, h, hd)
        k = (_mm(hn, a["k"], control) + a["k_b"]).reshape(seq, kvh, hd)
        v = (_mm(hn, a["v"], control) + a["v_b"]).reshape(seq, kvh, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _mm(o.reshape(seq, h * hd), a["o"], control)
        m = lp["mlp"]
        hn = _rms(x, lp["mlp_norm"]["scale"], eps)
        g = jax.nn.silu(_mm(hn, m["gate"], control))
        x = x + _mm(g * _mm(hn, m["up"], control), m["down"], control)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"].astype(jnp.float32), eps)
    return _mm(x, params["embed"].astype(jnp.float32).T, control)


def make_scorer(cfg: Dict[str, Any]):
    """Two jitted functions of ``(params, tokens)`` for one sequence
    ``tokens (S,)``: ``score(params, tokens, picks)`` gives the
    reference's best logit at each position and its logit of each pick
    there (``picks (k, S)`` token ids; results ``(S,)`` and ``(k, S)``);
    ``control_first(params, tokens)`` gives the token the control puts
    first at each position."""
    def score(params, tokens, picks):
        ref = logits_at(cfg, params, tokens)
        best = ref.max(-1)
        at = jnp.take_along_axis(ref, picks.T, axis=-1).T
        return best, at

    def control_first(params, tokens):
        return jnp.argmax(logits_at(cfg, params, tokens, control=True), -1)

    return jax.jit(score), jax.jit(control_first)
