"""Run the system's two hot paths once on a TPU, at full width.

1. **search** — the paper's flow (examples/quickstart.py) at the paper's
   input widths: a seeded synthetic ECG set of 2-channel windows of 3750
   steps (genomes may ask for 1875),
   ``EvolutionarySearch`` with ``NASConfig``'s default population, 300
   training steps per candidate and two generations, then
   ``select_for_goal`` and ``compile_candidate`` for the winner;
2. **serve** — qwen2-0.5b at its published widths with random bf16 weights
   through ``ServeEngine``, dense and paged, each engine's greedy tokens
   counted against ``greedy_reference``, and the compiled decode-attention
   kernels checked against their float32 reference at the engine's shapes.

``--chips 4`` runs only the paths that span devices: a ``ReplicaRouter``
of four one-chip replicas against one engine, then a one-generation
device-affine search over four chips against the same search on one.

Every phase raises on a failure, so any failure exits non-zero.  The last
line of standard output is ``{"ok": true, "device": {...}}``; everything
else goes on earlier lines.  Without a TPU the script exits non-zero
before any phase.  It runs in one process, and keeps JAX's compilation
cache where ``repro.launch.compile_cache`` says.

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

# the search at the paper's input widths: 60000-sample records, held at
# decimation 16 (3750 steps), the finest the search space reaches; genomes
# that ask for decimation 32 (1875 steps) subsample them
SEARCH_SIZES = dict(n_samples=1600, length=60000, decimation=16,
                    train_steps=300, generations=2)
# qwen2-0.5b serving: ~16 requests of 128-512 prompt tokens, 32 new each
SERVE_SIZES = dict(n_requests=16, prompt_range=(128, 512), max_new=32,
                   slots=8, cache_len=1024, block_size=16, pad_to=128)
N_REFERENCE = 4          # requests decoded again by greedy_reference
# compiled bf16 kernel vs the float32 reference on the same inputs, per
# slot: max |out - ref| over max |ref|, so long rows, whose softmax
# averages are small, are held as tightly as short ones.  bf16 rounding
# read 3.8e-3 dense and 3.3e-3 paged (max |err| 2.8e-3 and 3.2e-3) on a
# TPU v5e at the engine's shapes, and at most 4.9e-3 over seeds 0-2;
# dropping one 16-token block or one block's scores moved every row tried
# by 0.08 or more, at kv_len 1024 too
KERNEL_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase saw a wrong or missing result."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds of backend compilation (a persistent-cache read counts as
    its retrieval time), programs compiled, and persistent-cache hits,
    summed from ``jax.monitoring`` events."""

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _count(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Tuple[float, int, int]:
        return self.seconds, self.programs, self.cache_hits


_CLOCK: Optional[CompileClock] = None


def compile_clock() -> CompileClock:
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK


@contextlib.contextmanager
def timed(name: str, out: Dict[str, Any]) -> Iterator[None]:
    """Wall seconds and compile seconds of a block, logged and stored in
    ``out[name]``."""
    clock = compile_clock()
    c0, p0, h0 = clock.snapshot()
    t0 = time.monotonic()
    yield
    wall = time.monotonic() - t0
    c1, p1, h1 = clock.snapshot()
    out[name] = {"wall_s": wall, "compile_s": c1 - c0,
                 "programs": p1 - p0, "cache_hits": h1 - h0}
    log(f"[time] {name}: wall {wall:.2f}s, compile {c1 - c0:.2f}s "
        f"summed over threads ({p1 - p0} programs, {h1 - h0} "
        f"persistent-cache hits)")


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def make_search_data(seed: int, *, n_samples: int, length: int,
                     decimation: int):
    """The seeded synthetic ECG set, split into (train, val)."""
    from repro.data.ecg import make_ecg_dataset, train_val_split
    x, y = make_ecg_dataset(seed, n_samples=n_samples, length=length,
                            decimation=decimation)
    return train_val_split(x, y, seed=seed)


def search_phase(data, seed: int, *, train_steps: int, generations: int,
                 platform: str, label: str = "search",
                 **nas: Any) -> Dict[str, Any]:
    """The paper's search through ``EvolutionarySearch`` (batched training,
    NASConfig's default population unless ``nas`` overrides it), then the
    winner selected for the design goal and compiled.  Raises when a
    candidate failed or diverged, a device was quarantined, or training
    ran on arrays outside ``platform``."""
    import jax

    from repro.core.compile_model import compile_candidate
    from repro.core.evolution import EvolutionarySearch, NASConfig
    from repro.core.trainer import forward, init_candidate
    from repro.core.trainer_batch import (compile_cache_stats,
                                          reset_compile_cache)

    data_train, data_val = data
    cfg = NASConfig(train_steps=train_steps, batch_training=True,
                    generations=generations, seed=seed, **nas)
    search = EvolutionarySearch(cfg, data_train, data_val,
                                log=lambda m: log(f"[{label}] {m}"))
    reset_compile_cache()
    times: Dict[str, Any] = {}
    # the synchronous loop of EvolutionarySearch.run, one generation per
    # timed block
    with timed(f"{label}.init_population", times):
        state = search.init_state()
    for g in range(generations):
        with timed(f"{label}.generation_{g + 1}", times):
            state = search.step(state)

    outcomes = dict(search.train_outcomes)
    stats = compile_cache_stats()
    log(f"[{label}] candidates trained {outcomes['trained']}, failed "
        f"{outcomes['failed']}, diverged {outcomes['diverged']}; "
        f"quarantined devices {search.quarantined_devices}")
    log(f"[{label}] trainer_batch: {stats['misses']} compiles over "
        f"{stats['hits'] + stats['misses']} vmap bucket runs "
        f"(singleton buckets train on the scalar path)")
    require(outcomes["failed"] == 0,
            f"{outcomes['failed']} candidate training job(s) failed")
    require(outcomes["diverged"] == 0,
            f"{outcomes['diverged']} candidate(s) quarantined for "
            f"non-finite loss")
    require(not search.quarantined_devices,
            f"quarantined devices: {search.quarantined_devices}")
    require(outcomes["trained"] > 0, "no candidate was trained")
    staged = [a for arrays in search.stage_cache.values() for a in arrays
              if isinstance(a, jax.Array)]
    require(bool(staged), "the trainer staged no dataset on a device")
    places = sorted({d.platform for a in staged for d in a.devices()})
    require(places == [platform],
            f"training data lived on {places}, not {platform}")
    busy = {}
    for rec in state.history:
        for dev, s in rec["device_busy_s"].items():
            busy[dev] = busy.get(dev, 0.0) + s

    sol = search.select_for_goal(state)
    if sol is None:
        log(f"[{label}] no candidate meets the goal's limits yet; "
            f"compiling the best-detection member")
        sol = max(state.population, key=lambda c: -c.expensive[0])
    specs = sol.genome.phenotype()
    length = sol.genome.input_length()
    x_val = data_val[0]
    calib = x_val[:32, ::x_val.shape[1] // length][:, :length]
    with timed(f"{label}.compile_winner", times):
        params = init_candidate(jax.random.PRNGKey(seed), specs)
        compiled = compile_candidate(sol.genome, params,
                                     jax.numpy.asarray(calib))
        logits = np.asarray(jax.jit(lambda xb: forward(
            compiled.params, specs, xb, quant=None, train=False))(calib))
    require(logits.shape == (len(calib), 2),
            f"winner logits have shape {logits.shape}")
    require(bool(np.isfinite(logits).all()), "winner logits not finite")
    est = compiled.estimate_max
    require(bool(np.isfinite(est.throughput_sps)),
            "winner estimate not finite")
    det, fa = 1.0 - sol.expensive[0], sol.expensive[1]
    log(f"[{label}] winner: detection {det:.3f}, false alarm {fa:.3f}, "
        f"{len(specs)} layers, input {length}x2; est. max-alpha "
        f"{est.throughput_sps:.0f} samples/s @ {est.p_total_w:.2f} W")
    gens = [times[f"{label}.generation_{g + 1}"]["wall_s"]
            for g in range(generations)]
    log(f"[{label}] seconds per generation: "
        f"{', '.join(f'{s:.2f}' for s in gens)}")
    return {"state": state, "outcomes": outcomes, "times": times,
            "device_busy_s": busy, "trainer_batch": stats}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_prompts(seed: int, vocab: int, n: int,
                 prompt_range: Tuple[int, int]) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    lo, hi = prompt_range
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


def _requests(prompts: Sequence[np.ndarray], max_new: int):
    from repro.serve.engine import ServeRequest
    return [ServeRequest(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]


def check_served(done, n: int, max_new: int, vocab: int, what: str) -> None:
    """Every request completed in full, nothing shed, expired or rejected,
    and every token inside the vocabulary."""
    require(len(done) == n, f"{what}: {len(done)} of {n} requests came back")
    for r in done:
        require(not (r.rejected or r.expired or r.oom),
                f"{what}: request {r.rid} rejected={r.rejected} "
                f"expired={r.expired} shed={r.oom}")
        require(r.done and len(r.out) == max_new,
                f"{what}: request {r.rid} produced {len(r.out)} of "
                f"{max_new} tokens")
        require(all(0 <= t < vocab for t in r.out),
                f"{what}: request {r.rid} has a token outside the "
                f"vocabulary")


def serve_phase(bundle, params, prompts: Sequence[np.ndarray], *,
                max_new: int, slots: int, cache_len: int, pad_to: int,
                paged: bool, block_size: int) -> Dict[str, Any]:
    """One ``ServeEngine`` over the requests, run cold (compiling) and
    again warm.  Raises on any shed, expired or rejected request, a token
    outside the vocabulary, or non-finite logits."""
    from repro.serve.engine import EngineConfig, ServeEngine
    name = "paged" if paged else "dense"
    ecfg = EngineConfig(slots=slots, cache_len=cache_len, pad_to=pad_to,
                        max_prefill_batch=slots, paged=paged,
                        block_size=block_size)
    engine = ServeEngine(bundle, params, ecfg)
    times: Dict[str, Any] = {}
    outs = {}
    for run in ("cold", "warm"):
        with timed(f"serve.{name}.{run}", times):
            done = engine.run(_requests(prompts, max_new))
        stats = engine.stats()
        check_served(done, len(prompts), max_new, bundle.cfg.vocab_size,
                     f"{name} engine")
        require(stats["nonfinite_rows"] == 0,
                f"{name} engine: {stats['nonfinite_rows']} rows of "
                f"non-finite logits")
        require(stats["shed_blocks"] == 0,
                f"{name} engine shed {stats['shed_blocks']} requests")
        outs[run] = {r.rid: list(r.out) for r in done}
    require(outs["cold"] == outs["warm"],
            f"{name} engine: the warm run's tokens differ from the cold "
            f"run's")
    n_tok = sum(len(o) for o in outs["warm"].values())
    warm = times[f"serve.{name}.warm"]["wall_s"]
    log(f"[serve] {name}: {len(prompts)} requests served, 0 shed/expired/"
        f"rejected, {n_tok} tokens; warm run {warm:.2f}s "
        f"({n_tok / warm:.1f} tok/s host clock), stats {stats}")
    return {"outputs": outs["warm"], "times": times, "stats": stats}


def reference_phase(bundle, params, prompts: Sequence[np.ndarray], *,
                    max_new: int, cache_len: int) -> Dict[int, List[int]]:
    """Greedy tokens of the scalar-cache path for each prompt."""
    import jax

    from repro.serve.engine import greedy_reference
    dec = jax.jit(bundle.decode_step)
    return {i: greedy_reference(bundle, params, p, max_new, cache_len,
                                decode_jit=dec)
            for i, p in enumerate(prompts)}


def prefix_match(a: Sequence[int], b: Sequence[int]) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def row_error(out, ref) -> np.ndarray:
    """Per slot, the largest |out - ref| over every head and lane, divided
    by the largest |ref| there."""
    d = np.abs(np.asarray(out, np.float32) - np.asarray(ref))
    scale = np.abs(np.asarray(ref)).reshape(len(ref), -1).max(1)
    return d.reshape(len(ref), -1).max(1) / scale


def kernel_phase(cfg, *, slots: int, cache_len: int, block_size: int,
                 seed: int, interpret: bool) -> Dict[str, float]:
    """The dense and paged decode-attention Pallas kernels at the engine's
    shapes and dtype against ``kernels/decode_attention/ref.py`` in
    float32 on the same inputs.  Raises when a slot's error, relative to
    the slot's scale (:func:`row_error`), is above ``KERNEL_TOL``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.ops import (decode_attention,
                                                    paged_decode_attention)
    from repro.kernels.decode_attention.ref import (
        decode_attention_ref, paged_decode_attention_ref)

    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    kq, kk, kv, kpk, kpv = jax.random.split(jax.random.PRNGKey(seed), 5)
    rng = np.random.default_rng(seed)
    kv_len = rng.integers(1, cache_len + 1, slots)
    kv_len[0], kv_len[-1] = 1, cache_len          # both extremes
    kv_len = jnp.asarray(kv_len, jnp.int32)
    q = jax.random.normal(kq, (slots, h, hd), jnp.float32).astype(dt)
    k = jax.random.normal(kk, (slots, cache_len, kvh, hd)).astype(dt)
    v = jax.random.normal(kv, (slots, cache_len, kvh, hd)).astype(dt)

    nb = cache_len // block_size
    n_blocks = slots * nb
    kp = jax.random.normal(kpk, (n_blocks, block_size, kvh, hd)).astype(dt)
    vp = jax.random.normal(kpv, (n_blocks, block_size, kvh, hd)).astype(dt)
    # each slot owns a shuffled set of blocks; entries past its length
    # hold the unallocated sentinel, as the engine's block tables do
    tables = rng.permutation(n_blocks).reshape(slots, nb).astype(np.int32)
    used = -(-np.asarray(kv_len) // block_size)
    tables[np.arange(nb)[None, :] >= used[:, None]] = n_blocks
    tables = jnp.asarray(tables)

    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    out = decode_attention(q, k, v, kv_len, impl="pallas",
                           interpret=interpret)
    out_p = paged_decode_attention(q, kp, vp, tables, kv_len,
                                   impl="pallas", interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = decode_attention_ref(f32(q), f32(k), f32(v), kv_len)
        ref_p = paged_decode_attention_ref(f32(q), f32(kp), f32(vp),
                                           tables, kv_len)
    errs = {}
    for name, o, r in (("dense", out, ref), ("paged", out_p, ref_p)):
        o = f32(o)
        rows = row_error(o, r)
        errs[name] = float(rows.max())
        log(f"[kernel] {name} {dt.name} B={slots} H={h}/{kvh} hd={hd} "
            f"cache={cache_len} block={block_size}: max |err| / max |ref| "
            f"per slot {errs[name]:.3e} (tolerance {KERNEL_TOL:g}), max "
            f"|err| {float(jnp.max(jnp.abs(o - r))):.3e}, "
            f"interpret={interpret}")
        require(bool(np.isfinite(rows).all()) and errs[name] <= KERNEL_TOL,
                f"{name} decode kernel off the float32 reference by "
                f"{errs[name]:.3e} of a slot's scale > {KERNEL_TOL:g}")
    return errs


def build_server_model(cfg, seed: int):
    """The model bundle and its seeded random parameters."""
    import jax

    from repro.models.registry import build_model
    bundle = build_model(cfg)
    params = jax.block_until_ready(bundle.init(jax.random.PRNGKey(seed)))
    return bundle, params


def serving_phases(cfg, seed: int, *, n_requests: int,
                   prompt_range: Tuple[int, int], max_new: int, slots: int,
                   cache_len: int, block_size: int, pad_to: int,
                   n_reference: int, interpret: bool) -> Dict[str, Any]:
    """Dense and paged engines over the same requests, each checked, their
    greedy tokens counted against ``greedy_reference``, then the decode
    kernels at the engines' shapes."""
    import jax
    times: Dict[str, Any] = {}
    with timed("serve.init_params", times):
        bundle, params = build_server_model(cfg, seed)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {n_params / 1e6:.1f}M "
        f"params in {cfg.dtype}")
    prompts = make_prompts(seed, cfg.vocab_size, n_requests, prompt_range)
    sizes = dict(max_new=max_new, slots=slots, cache_len=cache_len,
                 pad_to=pad_to, block_size=block_size)
    res = {name: serve_phase(bundle, params, prompts, paged=paged, **sizes)
           for name, paged in (("dense", False), ("paged", True))}
    with timed("serve.greedy_reference", times):
        ref = reference_phase(bundle, params, prompts[:n_reference],
                              max_new=max_new, cache_len=cache_len)
    matches = {}
    for name, r in res.items():
        got = sum(prefix_match(r["outputs"][i], ref[i]) for i in ref)
        matches[name] = got
        log(f"[serve] {name} engine vs greedy_reference: {got} of "
            f"{sum(len(t) for t in ref.values())} greedy tokens match "
            f"(leading tokens of {len(ref)} requests; a report, not a "
            f"gate)")
    with timed("serve.kernels", times):
        errs = kernel_phase(cfg, slots=slots, cache_len=cache_len,
                            block_size=block_size, seed=seed,
                            interpret=interpret)
    return {"engines": res, "reference_matches": matches,
            "kernel_err": errs, "times": times, "bundle": bundle,
            "params": params, "prompts": prompts}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def affine_search_phase(data, seed: int, *, platform: str,
                        **sizes: Any) -> Dict[str, Any]:
    """Device-affine search over every visible device against the same
    seeds on one device; trained objectives must be equal, and at least
    two devices must have trained."""
    multi = search_phase(data, seed, platform=platform, label="affine",
                         device_affinity=True, pipeline="off", **sizes)
    single = search_phase(data, seed, platform=platform, label="single",
                          device_affinity=False, pipeline="off", **sizes)
    a, b = multi["state"], single["state"]
    require(list(a.pop.phash) == list(b.pop.phash),
            "device-affine and one-device searches kept different "
            "populations")
    require(np.array_equal(a.pop.expensive, b.pop.expensive,
                           equal_nan=True),
            "device-affine and one-device trained objectives differ")
    used = sorted(d for d, s in multi["device_busy_s"].items() if s > 0)
    log(f"[affine] objectives equal to the one-device search; busy "
        f"seconds per device {multi['device_busy_s']}")
    require(len(used) >= 2, f"only {used} trained in the device-affine "
                            f"search")
    return {"devices_used": used, "affine": multi, "single": single}


def router_phase(bundle, params, prompts: Sequence[np.ndarray], devices,
                 reference: Dict[int, List[int]], *, max_new: int,
                 slots: int, cache_len: int, pad_to: int) -> Dict[str, Any]:
    """``ReplicaRouter`` with one replica per device over the requests;
    tokens must equal the one-engine run's, with no failover."""
    from repro.serve.engine import EngineConfig
    from repro.serve.router import ReplicaRouter, RouterConfig
    ecfg = EngineConfig(slots=slots, cache_len=cache_len, pad_to=pad_to,
                        max_prefill_batch=slots)
    router = ReplicaRouter(bundle, params,
                           RouterConfig(replicas=len(devices), engine=ecfg),
                           devices=devices)
    times: Dict[str, Any] = {}
    with timed("router.run", times):
        done = router.run(_requests(prompts, max_new))
    st = router.stats
    check_served(done, len(prompts), max_new, bundle.cfg.vocab_size,
                 "router")
    require(st["failovers"] == 0 and st["restarts"] == 0
            and not st["quarantined"],
            f"router failed over: {st}")
    nonfinite = sum(r.engine.nonfinite_rows for r in router.replicas)
    require(nonfinite == 0, f"router replicas saw {nonfinite} rows of "
                            f"non-finite logits")
    per_replica = [r.engine.decode_steps for r in router.replicas]
    equal = sum(list(r.out) == reference[r.rid] for r in done)
    log(f"[router] {len(devices)} replicas on "
        f"{[str(d) for d in devices]}: decode steps per replica "
        f"{per_replica}; {equal} of {len(done)} requests token-for-token "
        f"equal to one engine")
    require(equal == len(done),
            f"router tokens differ from one engine on "
            f"{len(done) - equal} requests")
    require(sum(s > 0 for s in per_replica) >= 2,
            f"fewer than two replicas decoded: {per_replica}")
    return {"stats": st, "times": times}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for data, weights and requests")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the device-affine search and the "
                         "four-replica router, each against one chip")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX reports platform "
              f"{dev.platform!r} with {len(devices)} device(s); this script "
              f"runs only on a TPU", file=sys.stderr)
        return 1
    log(f"[device] platform {dev.platform}, kind {dev.device_kind}, count "
        f"{len(devices)}; compile cache {cache_dir}")
    compile_clock()
    t0 = time.monotonic()

    from repro.configs import get_config
    cfg = get_config("qwen2-0.5b")
    data = make_search_data(args.seed, n_samples=SEARCH_SIZES["n_samples"],
                            length=SEARCH_SIZES["length"],
                            decimation=SEARCH_SIZES["decimation"])
    train = dict(train_steps=SEARCH_SIZES["train_steps"],
                 generations=SEARCH_SIZES["generations"])
    if args.chips == 1:
        search_phase(data, args.seed, platform="tpu", **train)
        log("[search] phase done")
        serving_phases(cfg, args.seed, n_reference=N_REFERENCE,
                       interpret=False, **SERVE_SIZES)
        log("[serve] phase done")
    else:
        require(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX sees "
                                   f"{len(devices)}")
        bundle, params = build_server_model(cfg, args.seed)
        s = SERVE_SIZES
        prompts = make_prompts(args.seed, cfg.vocab_size, s["n_requests"],
                               s["prompt_range"])
        sizes = dict(max_new=s["max_new"], slots=s["slots"],
                     cache_len=s["cache_len"], pad_to=s["pad_to"])
        one = serve_phase(bundle, params, prompts, paged=False,
                          block_size=s["block_size"], **sizes)
        router_phase(bundle, params, prompts, devices[:4], one["outputs"],
                     **sizes)
        log("[router] phase done")
        # one generation: the device-affine dispatch is the same in every
        # generation, and the one-chip search it is checked against runs
        # after it, serially
        affine_search_phase(data, args.seed, platform="tpu",
                            **dict(train, generations=1))
        log("[affine] phase done")
    clock = compile_clock()
    log(f"[time] total {time.monotonic() - t0:.2f}s, of which backend "
        f"compile {clock.seconds:.2f}s over {clock.programs} programs, "
        f"{clock.cache_hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
