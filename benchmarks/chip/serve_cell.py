"""Serving cells: a decoder LM behind ``ServeEngine``, driven open loop.

Set-up makes the weights from the seed on the device (the configuration's
``reference.py``), builds the engine from the traffic file's ``engine``
entry, and runs one prefill of every (batch, padded length) bucket the
run's own requests can make and one decode step, through ``submit``/``tick``.
Then the window opens.

The window is the harness's own open loop over ``submit``/``tick``: each
request is submitted when its scheduled arrival has come, whether or not
the engine is keeping up, and each token is stamped when the ``tick``
that produced it returns.  Time to first token runs from the scheduled
arrival, so the generator's own lateness counts against the engine.
After the window the requests that arrived in it are drained.

``correct`` compares the served tokens with the configuration's float32
reference (``check``).
"""
from __future__ import annotations

import dataclasses
import gc
import os
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

import harness
import loadgen
from harness import BenchError, CellResult, Check, log


# ---------------------------------------------------------------------------
# model and engine
# ---------------------------------------------------------------------------

def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a published decoder config."""
    from repro.configs.base import ModelConfig
    if cfg["model_type"] != "qwen2":
        raise BenchError(f"no mapping for model_type {cfg['model_type']!r}")
    if cfg["hidden_act"] != "silu":
        raise BenchError(f"hidden_act {cfg['hidden_act']!r} is not SwiGLU")
    return ModelConfig(
        name=cfg.get("name", "qwen2"), family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=True, rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), norm="rmsnorm",
        act="swiglu", dtype=cfg["torch_dtype"],
        norm_eps=float(cfg["rms_norm_eps"]))


def make_weights(ref, cfg: Dict[str, Any], seed: int, bundle):
    """The configuration's seeded weights, checked against the layout the
    engine takes (tree, shapes and dtypes, from ``eval_shape`` alone)."""
    import jax
    key = jax.random.PRNGKey(loadgen.seed_words(seed, "weights")[0])
    params = jax.block_until_ready(ref.make_params(cfg, key))
    want = jax.eval_shape(bundle.init, key)
    got_s = jax.tree_util.tree_structure(params)
    if got_s != jax.tree_util.tree_structure(want):
        raise BenchError(f"weights tree {got_s} is not the engine's "
                         f"{jax.tree_util.tree_structure(want)}")
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise BenchError(f"weight {a.shape} {a.dtype} where the engine "
                             f"takes {b.shape} {b.dtype}")
    return params


def build_engine(bundle, params, spec: Dict[str, Any]):
    from repro.serve.engine import EngineConfig, ServeEngine
    ecfg = EngineConfig(slots=spec["slots"], cache_len=spec["cache_len"],
                        pad_to=spec["pad_to"],
                        max_prefill_batch=spec["max_prefill_batch"])
    return ServeEngine(bundle, params, ecfg)


def prefill_shapes(engine_spec: Dict[str, Any],
                   prompt_lens: Sequence[int]) -> List[tuple]:
    """Every (batch, padded length) prefill bucket that prompts of these
    lengths can make: each length padded to ``pad_to``, in a batch of
    any power of two up to ``max_prefill_batch``."""
    pad, top = engine_spec["pad_to"], engine_spec["max_prefill_batch"]
    lens = sorted({-(-int(n) // pad) * pad for n in prompt_lens})
    batches, b = [], 1
    while b < top:
        batches.append(b)
        b *= 2
    batches.append(top)
    return [(b, n) for n in lens for b in batches]


def warm_up(engine, engine_spec: Dict[str, Any],
            plan: Sequence[loadgen.Planned], vocab: int, seed: int) -> int:
    """One prefill of every bucket the plan's prompts can make and one
    decode step, through the public ``submit``/``tick``; returns the
    number of prefill shapes run."""
    from repro.serve.engine import ServeRequest
    rng = loadgen.rng_for(seed, "warmup")
    shapes = prefill_shapes(engine_spec, [len(p.prompt) for p in plan])
    rid = -1
    for b, n in shapes:
        for _ in range(b):
            engine.submit(ServeRequest(
                rid=rid, prompt=rng.integers(0, vocab, n).astype(np.int32),
                max_new=1))
            rid -= 1
        engine.tick(0.0)
    engine.submit(ServeRequest(
        rid=rid, prompt=rng.integers(0, vocab, 8).astype(np.int32),
        max_new=2))
    while engine.has_work:
        engine.tick(0.0)
    engine.reset()
    return len(shapes)


# ---------------------------------------------------------------------------
# the open loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """One request's measured life, on the window's clock (seconds)."""
    planned: loadgen.Planned
    req: Any
    submit_s: float = float("nan")
    stamps: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class WindowLog:
    served: List[Served]
    seconds: float
    ticks: List[Dict[str, Any]]          # per tick: start, dur, admitted...
    traced_decode: List[List[int]]       # kv_lens of each traced decode
    drained_s: float = 0.0


def open_loop(engine, plan: Sequence[loadgen.Planned], seconds: float,
              *, drain_s: float,
              trace: Optional[harness.TraceSlice] = None) -> WindowLog:
    """Serve ``plan`` in real time; returns what was measured.  ``trace``
    runs the profiler over its slice of the window."""
    from repro.serve.engine import ServeRequest

    served = [Served(p, ServeRequest(rid=p.rid, prompt=p.prompt,
                                     max_new=p.max_new,
                                     arrival_s=p.arrival_s))
              for p in plan]
    live: Dict[int, Served] = {}
    seen: Dict[int, int] = {}
    ticks: List[Dict[str, Any]] = []
    traced: List[List[int]] = []
    nxt = 0
    t0 = time.monotonic()
    deadline = seconds + drain_s
    while True:
        now = time.monotonic() - t0
        if trace is not None:
            trace.poll(now)
        while nxt < len(served) and served[nxt].planned.arrival_s <= now:
            s = served[nxt]
            with TraceAnnotation("submit"):
                s.submit_s = time.monotonic() - t0
                engine.submit(s.req)
            live[s.req.rid] = s
            seen[s.req.rid] = 0
            nxt += 1
        if engine.has_work:
            with TraceAnnotation("tick"):
                t_start = time.monotonic() - t0
                out = engine.tick(t_start)
                t_end = time.monotonic() - t0
            decode_lens = []
            for rid, s in list(live.items()):
                n = len(s.req.out)
                new = n - seen[rid]
                if new:
                    s.stamps.extend([t_end] * new)
                    # a request in the decode step gains a token there;
                    # one admitted in this tick also gains its first
                    if new - (1 if seen[rid] == 0 else 0) > 0:
                        decode_lens.append(len(s.req.prompt) + n - 1)
                    seen[rid] = n
                if s.req.done:
                    del live[rid]
            engine.take_finished()
            ticks.append({"start": t_start, "dur": t_end - t_start,
                          "admitted": out["admitted"],
                          "produced": out["produced"]})
            if trace is not None and trace.running and decode_lens:
                traced.append(decode_lens)
        elif nxt < len(served):
            gap = served[nxt].planned.arrival_s - (time.monotonic() - t0)
            if gap > 0.002:
                time.sleep(gap - 0.001)
        elif trace is None or trace.done:
            break
        if now > deadline:
            break
    if trace is not None:
        trace.close()
    return WindowLog(served=served, seconds=seconds, ticks=ticks,
                     traced_decode=traced,
                     drained_s=time.monotonic() - t0 - seconds)


def window_metrics(log_: WindowLog) -> Dict[str, Any]:
    """End-to-end numbers and the samples behind them."""
    sec = log_.seconds
    ttft, itl, lag, out_tokens = [], [], [], 0
    done = unanswered = 0
    for s in log_.served:
        unanswered += not s.req.done
        lag.append(s.submit_s - s.planned.arrival_s)
        out_tokens += sum(1 for t in s.stamps if t <= sec)
        if s.stamps:
            ttft.append(s.stamps[0] - s.planned.arrival_s)
            itl.extend(np.diff(s.stamps).tolist())
        if s.req.done and len(s.req.out) == s.req.max_new \
                and not (s.req.oom or s.req.expired or s.req.rejected):
            done += 1
    m: Dict[str, Any] = {"attempted": len(log_.served),
                         "failed": len(log_.served) - done,
                         "unanswered": unanswered,
                         "ttft_s": ttft, "itl_s": itl, "lag_s": lag,
                         "out_tokens_in_window": out_tokens}
    if ttft:
        m["ttft_p95_ms"] = harness.percentile(ttft, 95) * 1e3
        m["ttft_p50_ms"] = harness.percentile(ttft, 50) * 1e3
    if itl:
        m["itl_p95_ms"] = harness.percentile(itl, 95) * 1e3
        m["itl_p50_ms"] = harness.percentile(itl, 50) * 1e3
    m["out_tok_per_s"] = out_tokens / sec
    return m


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def pick_checked(served: Sequence[Served], n: int, seed: int
                 ) -> List[Served]:
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    done = [s for s in served if s.req.done and s.req.out]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.req.prompt) + len(s.req.out),
                                       s.req.rid))
    rest = [s for s in done if s is not longest]
    rng = loadgen.rng_for(seed, "check")
    take = rng.choice(len(rest), min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(take)]


def token_gaps(ref, cfg: Dict[str, Any], params, checked: Sequence[Served],
               length: int, *, control: bool = False) -> Dict[str, Any]:
    """For every served token of the checked requests, how far the
    reference's logit of that token lies below the reference's best at
    its position.  With ``control`` the same, of the token the control
    puts first instead of the served one.  Sequences are padded to
    ``length``, so one program serves them all."""
    import jax.numpy as jnp
    score, control_first = ref.make_scorer(cfg)
    gaps: List[float] = []
    for s in checked:
        prompt, out = list(s.req.prompt), list(s.req.out)
        seq = np.zeros(length, np.int32)
        toks = prompt + out[:-1]
        seq[:len(toks)] = toks
        lo, hi = len(prompt) - 1, len(prompt) - 1 + len(out)
        picks = np.zeros(length, np.int32)
        if control:
            first = np.asarray(control_first(params, jnp.asarray(seq)))
            picks[lo:hi] = first[lo:hi]
        else:
            picks[lo:hi] = out
        best, at = score(params, jnp.asarray(seq), jnp.asarray(picks[None]))
        best, at = np.asarray(best), np.asarray(at)[0]
        gaps.extend((best[lo:hi] - at[lo:hi]).tolist())
    return {"max": float(max(gaps)) if gaps else float("nan"),
            "median": float(np.median(gaps)) if gaps else float("nan"),
            "tokens": len(gaps)}


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def load_reference(cell: harness.Cell):
    """The plain reference beside the configuration's file."""
    return harness.load_module(os.path.join(cell.config_dir,
                                            "reference.py"))


@dataclasses.dataclass
class Prepared:
    """A built and warmed engine with its weights and schedule."""
    bundle: Any
    params: Any
    engine: Any
    plan: List[loadgen.Planned]
    ref: Any
    warm_shapes: int


def prepare(cell: harness.Cell, seed: int, seconds: float) -> Prepared:
    from repro.models.registry import build_model
    cfg = cell.config
    ref = load_reference(cell)
    bundle = build_model(model_config(cfg))
    params = make_weights(ref, cfg, seed, bundle)
    engine = build_engine(bundle, params, cell.traffic["engine"])
    plan = loadgen.schedule(cell.traffic, seed, seconds,
                            vocab_size=cfg["vocab_size"])
    n_shapes = warm_up(engine, cell.traffic["engine"], plan,
                       cfg["vocab_size"], seed)
    return Prepared(bundle, params, engine, plan, ref, n_shapes)


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        devices: Sequence[Any], t_start: float,
        clock: harness.CompileClock) -> CellResult:
    traffic = cell.traffic
    prep = prepare(cell, seed, seconds)
    tslice = None
    if trace:
        # the slice ends as the window closes: writing the trace out
        # stalls the host for seconds, and only the drain follows
        tslice = harness.TraceSlice(
            seconds - float(traffic["trace"]["slice_s"]), seconds,
            os.path.join(harness.TRACE_DIR, cell.name))
    c0 = clock.snapshot()
    setup_s = time.monotonic() - t_start
    log(f"[setup] {setup_s:.2f}s; {prep.warm_shapes} prefill shapes warmed; "
        f"compile {c0['compile_s']:.2f}s over {c0['programs']} programs, "
        f"{c0['cache_hits']} persistent-cache hits")
    wlog = open_loop(prep.engine, prep.plan, seconds,
                     drain_s=float(traffic["drain_s"]), trace=tslice)
    in_window = clock.delta(c0, clock.snapshot())
    m = window_metrics(wlog)
    stats = prep.engine.stats()
    mem = harness.device_info(devices)["memory_peak_bytes"]
    log(f"[window] {seconds}s, {m['attempted']} requests, {m['failed']} "
        f"failed, drained in {wlog.drained_s:.2f}s; ttft p50/p95 "
        f"{m.get('ttft_p50_ms', float('nan')):.2f}/"
        f"{m.get('ttft_p95_ms', float('nan')):.2f} ms over "
        f"{len(m['ttft_s'])}; itl p50/p95 "
        f"{m.get('itl_p50_ms', float('nan')):.2f}/"
        f"{m.get('itl_p95_ms', float('nan')):.2f} ms over "
        f"{len(m['itl_s'])}; out {m['out_tok_per_s']:.1f} tok/s (offered "
        f"{loadgen.offered_tokens_per_s(traffic):.1f}); generator lag p95 "
        f"{harness.percentile(m['lag_s'], 95) * 1e3:.3f} ms, max "
        f"{max(m['lag_s']) * 1e3:.3f} ms; engine {stats}; compile in window "
        f"{in_window['compile_s']:.3f}s over {in_window['programs']} "
        f"programs")
    # the reference runs once the window has closed and the engine's
    # state is freed, so the peak read above is the engine's
    checked = pick_checked(wlog.served, int(traffic["check"]["requests"]),
                           seed)
    prep.engine.reset()
    del prep.engine
    gc.collect()
    gaps = token_gaps(prep.ref, cell.config, prep.params, checked,
                      int(traffic["engine"]["cache_len"]))
    log(f"[check] {gaps['tokens']} served tokens of {len(checked)} "
        f"requests against the float32 reference: widest gap below the "
        f"reference's best logit {gaps['max']:.6f}, median "
        f"{gaps['median']:.6f}")
    checks = [Check("max_logit_gap", gaps["max"],
                    float(cell.limits["max_logit_gap"]["limit"])),
              Check("nonfinite_rows", float(stats["nonfinite_rows"]), 0.0),
              Check("unanswered", float(m["unanswered"]), 0.0)]
    e2e = {k: m[k] for k in ("ttft_p95_ms", "itl_p95_ms", "out_tok_per_s")
           if k in m}
    e2e["setup_s"] = setup_s
    # host-clock readings leave out the traced slice, which the profiler
    # slows
    until = tslice.start if tslice else seconds
    ctx: Dict[str, Any] = {
        "config": cell.config,
        "peaks": harness.peaks_for(devices[0].device_kind),
        "window_s": seconds,
        "ticks": [t for t in wlog.ticks if t["start"] < until],
        "submit_lag_s": [s.submit_s - s.planned.arrival_s
                         for s in wlog.served if s.submit_s < until],
        "traced_decode": wlog.traced_decode,
        "engine_stats": stats,
        "trace": None,
    }
    if trace:
        import trace_reduce
        ctx["trace"] = trace_reduce.reduce_dir(tslice.trace_dir)
    return CellResult(attempted=m["attempted"], failed=m["failed"],
                      checks=checks, end_to_end=e2e, ctx=ctx,
                      devices=list(devices), memory_peak_bytes=mem)
