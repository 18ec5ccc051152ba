"""The one traffic generator: reads a traffic file's parameters and makes
a run's requests from ``--seed``.

Every seed gets the same multiset of inter-arrival gaps, prompt lengths
and output lengths, in its own order: the sizes are the quantiles of the
file's distributions at ``(i + 0.5) / n``, shuffled by the seed, and the
token ids are drawn from the seed.  So the work offered in a window is
the same for every seed, and seeds differ in arrangement only.

Traffic file keys read here::

    "arrivals":   {"process": "poisson", "rate_per_s": r}
    "prompt_len": {"dist": "lognormal", "median": m, "sigma": s,
                   "min": lo, "max": hi}
    "output_len": the same form

The window holds ``round(rate * seconds)`` arrivals, the last of them
before the window closes.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def seed_words(seed: int, tag: str, n: int = 1) -> List[int]:
    """``n`` 32-bit words from a seed of any size and a purpose tag, so
    different draws of one run never share a stream."""
    tag_words = [ord(c) for c in tag]
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1),
                                 int(seed) >> 64, *tag_words])
    return [int(w) for w in ss.generate_state(n)]


def rng_for(seed: int, tag: str) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, tag, 4))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles, ascending."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(p)) for p in _quantiles(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def gap_quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) at the process's quantiles."""
    rate = float(spec["rate_per_s"])
    p = _quantiles(n)
    if spec["process"] == "poisson":
        return -np.log1p(-p) / rate
    raise ValueError(f"unknown arrival process {spec['process']!r}")


@dataclasses.dataclass
class Planned:
    """One request of the schedule."""
    rid: int
    arrival_s: float
    prompt: np.ndarray         # int32 token ids
    max_new: int


def schedule(traffic: Dict[str, Any], seed: int, seconds: float, *,
             vocab_size: int) -> List[Planned]:
    """The run's requests in arrival order."""
    arr = traffic["arrivals"]
    n = max(1, int(round(float(arr["rate_per_s"]) * seconds)))
    rng = rng_for(seed, "traffic")
    gaps = rng.permutation(gap_quantiles(arr, n))
    arrivals = np.cumsum(gaps)
    # stretch so that the n arrivals span the window at the stated rate
    # and the last one lands before the window closes
    arrivals *= seconds * n / (n + 0.5) / arrivals[-1]
    plens = rng.permutation(length_quantiles(traffic["prompt_len"], n))
    olens = rng.permutation(length_quantiles(traffic["output_len"], n))
    toks = rng_for(seed, "tokens")
    return [Planned(rid=i, arrival_s=float(arrivals[i]),
                    prompt=toks.integers(0, vocab_size, int(plens[i]))
                    .astype(np.int32),
                    max_new=int(olens[i]))
            for i in range(n)]


def offered_tokens_per_s(traffic: Dict[str, Any], n: int = 4096) -> float:
    """Output tokens per second the traffic offers."""
    mean_out = float(length_quantiles(traffic["output_len"], n).mean())
    return float(traffic["arrivals"]["rate_per_s"]) * mean_out
