"""Operations and bytes that an algorithm needs, from shapes alone.

These count what the mathematics asks for, never what a compiled program
happens to do: a padded slot, an inactive row, a copy or a transpose adds
nothing here.  So a change that removes such waste is judged by the same
yardstick as the program before it.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Sequence


# ---------------------------------------------------------------------------
# decoder LMs (the serving cells)
# ---------------------------------------------------------------------------

def lm_sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Sizes of a decoder LM configuration in the published key names."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"layers": int(cfg["num_hidden_layers"]), "d": d, "heads": h,
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg.get("head_dim") or d // h),
            "ff": int(cfg["intermediate_size"]),
            "vocab": int(cfg["vocab_size"])}


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that take part in a matrix product for each token: the
    q/k/v/o projections and the SwiGLU MLP of every layer, and the
    output head (the embedding lookup is a gather, not a product)."""
    s = lm_sizes(cfg)
    d, hd = s["d"], s["head_dim"]
    attn = d * s["heads"] * hd * 2 + d * s["kv_heads"] * hd * 2
    mlp = 3 * d * s["ff"]
    return s["layers"] * (attn + mlp) + s["vocab"] * d


def decode_flops(cfg: Dict[str, Any], kv_lens: Sequence[int]) -> float:
    """One decode step over the active sequences, ``kv_lens`` being each
    one's attended length (its cache plus the new token): 2 FLOPs per
    matmul weight per sequence, and per layer 4 * kv_len * heads *
    head_dim for the scores and the weighted sum."""
    s = lm_sizes(cfg)
    attn = 4.0 * sum(kv_lens) * s["heads"] * s["head_dim"] * s["layers"]
    return 2.0 * matmul_params(cfg) * len(kv_lens) + attn


def decode_attention_bytes(cfg: Dict[str, Any], kv_lens: Sequence[int],
                           itemsize: int = 2) -> float:
    """HBM bytes the decode-attention kernel needs for one step, over
    every layer: each active sequence's keys and values up to its length,
    its queries and its output."""
    s = lm_sizes(cfg)
    kv = 2.0 * sum(kv_lens) * s["kv_heads"] * s["head_dim"]
    q_out = 2.0 * len(kv_lens) * s["heads"] * s["head_dim"]
    return (kv + q_out) * itemsize * s["layers"]


def total(fn, cfg: Dict[str, Any], steps: Iterable[Any]) -> float:
    """``fn`` summed over the steps (each a list of kv_lens)."""
    return float(sum(fn(cfg, k) for k in steps))
