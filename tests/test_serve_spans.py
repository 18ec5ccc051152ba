"""Engine phase spans and counters (serve/engine.py ``PHASES``).

Each host phase of a scheduling round is a ``jax.profiler.TraceAnnotation``
named ``engine.<phase>`` and a pair of counters in ``stats()``; the prefill
buckets also count their real and padded tokens.  The counters must agree
with the engine's own step and dispatch counts, and the spans must land in
the profiler's host trace, nested as the code nests them.
"""
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models.registry import build_model
from repro.serve import EngineConfig, ServeEngine, ServeRequest
from repro.serve.buckets import build_buckets
from repro.serve.engine import PHASES

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks", "chip"))
import engine_spans  # noqa: E402

SLOTS, CACHE_LEN, PAD_TO, MAX_BATCH = 4, 48, 8, 4
# (arrival round, prompt length, max_new): a burst that fills every slot
# with a queue behind it, a three-row bucket padded to four, and
# arrivals while earlier requests decode
WORK = [(0, 5, 3), (0, 6, 6), (0, 7, 2), (0, 11, 5), (0, 9, 4),
        (2, 17, 3), (3, 4, 7), (3, 20, 2), (7, 8, 3)]


@pytest.fixture(scope="module")
def engine():
    cfg = reduced_config("qwen2-0.5b")
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return ServeEngine(bundle, params, EngineConfig(
        slots=SLOTS, cache_len=CACHE_LEN, pad_to=PAD_TO,
        max_prefill_batch=MAX_BATCH))


def _serve(engine):
    """Drive ``WORK`` through ``submit``/``tick`` on a round clock; returns
    the requests, the number of ticks, and the prompt lengths admitted in
    each round that admitted any."""
    rng = np.random.default_rng(0)
    engine.reset()
    reqs = [ServeRequest(rid=i, prompt=rng.integers(0, 64, n).astype(
        np.int32), max_new=m) for i, (_, n, m) in enumerate(WORK)]
    pending = list(range(len(WORK)))
    rounds, ticks, t = [], 0, 0
    while pending or engine.has_work:
        while pending and WORK[pending[0]][0] <= t:
            engine.submit(reqs[pending.pop(0)])
        queue = list(engine.waiting)
        n = engine.tick(float(t))["admitted"]
        if n:
            rounds.append([len(r.prompt) for r in queue[:n]])
        ticks += 1
        t += 1
    return reqs, ticks, rounds


def test_phase_counts_agree_with_the_engines_own_counters(engine):
    reqs, ticks, rounds = _serve(engine)
    assert all(r.done and len(r.out) == r.max_new for r in reqs)
    st = engine.stats()
    n, s = st["phase_n"], st["phase_s"]
    assert set(n) == set(s) == set(PHASES)
    assert n["tick"] == ticks
    assert n["decode"] == n["decode_wait"] == n["emit"] == \
        st["decode_steps"] > 0
    assert n["admit"] == len(rounds) >= 3
    assert n["prefill"] == n["prefill_wait"] == st["prefill_calls"]
    assert s["decode_wait"] <= s["decode"]
    assert s["decode_wait"] + s["emit"] <= s["decode"]
    assert s["prefill"] + s["prefill_wait"] <= s["admit"]
    assert s["admit"] + s["decode"] <= s["tick"]
    assert all(v > 0 for v in s.values())


def test_prefill_counts_real_and_padded_tokens(engine):
    _, _, rounds = _serve(engine)
    st = engine.stats()
    assert st["prefill_tokens"] == sum(n for _, n, _ in WORK)
    padded = 0
    for lens in rounds:
        prompts = [np.zeros(n, np.int32) for n in lens]
        padded += sum(b.tokens.size for b in build_buckets(
            prompts, list(range(len(lens))), SLOTS, pad_to=PAD_TO,
            max_batch=MAX_BATCH))
    assert st["prefill_padded_tokens"] == padded
    assert st["prefill_padded_tokens"] > st["prefill_tokens"]


def test_reset_zeroes_the_phase_counters(engine):
    _serve(engine)
    assert engine.stats()["phase_n"]["tick"] > 0
    engine.reset()
    st = engine.stats()
    assert st["phase_s"] == dict.fromkeys(PHASES, 0.0)
    assert st["phase_n"] == dict.fromkeys(PHASES, 0)
    assert st["prefill_tokens"] == st["prefill_padded_tokens"] == 0


def _inside(inner, outer):
    """Every ``inner`` span lies within some ``outer`` span."""
    return all(any(s0 <= s and s + d <= s0 + d0 for _, s0, d0 in outer)
               for _, s, d in inner)


def test_spans_land_in_the_profilers_host_trace(engine, tmp_path):
    _serve(engine)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(engine)
    finally:
        jax.profiler.stop_trace()
    ev = engine_spans.load_events(engine_spans.newest_trace(str(tmp_path)))
    spans = {}
    for name, s, d in ev["host"]:
        spans.setdefault(name, []).append((name, s, d))
    assert set(spans) == {f"engine.{p}" for p in PHASES}
    n = engine.stats()["phase_n"]
    assert {p: len(spans[f"engine.{p}"]) for p in PHASES} == n
    assert _inside(spans["engine.decode_wait"], spans["engine.decode"])
    assert _inside(spans["engine.emit"], spans["engine.decode"])
    assert _inside(spans["engine.decode"], spans["engine.tick"])
    assert _inside(spans["engine.prefill"], spans["engine.admit"])
    assert _inside(spans["engine.admit"], spans["engine.tick"])
